"""Convolutional autoencoder (reference: example/autoencoder — encoder/
decoder trained to reconstruct, the representation-learning classic).

Encoder: strided Conv2D stack to a small code; decoder: Conv2DTranspose
back to the input. Trains on the synthetic blob images used by the other
offline examples and asserts reconstruction error drops well below the
variance baseline.

  python examples/autoencoder.py --ctx tpu
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def build_ae(code_channels=8):
    enc = nn.HybridSequential(prefix="enc_")
    with enc.name_scope():
        enc.add(nn.Conv2D(16, 3, strides=2, padding=1, activation="relu"))
        enc.add(nn.Conv2D(code_channels, 3, strides=2, padding=1,
                          activation="relu"))
    dec = nn.HybridSequential(prefix="dec_")
    with dec.name_scope():
        dec.add(nn.Conv2DTranspose(16, 4, strides=2, padding=1,
                                   activation="relu"))
        dec.add(nn.Conv2DTranspose(1, 4, strides=2, padding=1))
    net = nn.HybridSequential()
    net.add(enc, dec)
    return net


def blobs(n, size=16, seed=0):
    rng = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    cx = rng.uniform(0.2, 0.8, (n, 1, 1, 1)).astype(np.float32)
    cy = rng.uniform(0.2, 0.8, (n, 1, 1, 1)).astype(np.float32)
    s = rng.uniform(0.05, 0.2, (n, 1, 1, 1)).astype(np.float32)
    img = np.exp(-((xx[None, None] - cx) ** 2 + (yy[None, None] - cy) ** 2)
                 / (2 * s ** 2))
    return img.astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()
    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()

    X = blobs(2048)
    var = float(((X - X.mean()) ** 2).mean())
    net = build_ae()
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True)
    loss_fn = gluon.loss.L2Loss()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 2e-3})
    b = 64
    t0 = time.time()
    first = last = None
    for step in range(args.steps):
        lo = (step * b) % (len(X) - b)
        x = nd.array(X[lo:lo + b], ctx=ctx)
        with autograd.record():
            loss = loss_fn(net(x), x)
        loss.backward()
        tr.step(b)
        cur = float(loss.mean().asnumpy()) * 2
        first = first if first is not None else cur
        last = cur
    print("reconstruction MSE %.5f -> %.5f (pixel variance %.5f, %.0f "
          "steps, %.1fs)" % (first, last, var, args.steps,
                             time.time() - t0))
    assert last < 0.25 * var, (last, var)
    print("autoencoder OK: reconstruction beats the variance baseline 4x")


if __name__ == "__main__":
    main()

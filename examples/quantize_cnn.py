"""Post-training INT8 quantization (reference: example/quantization/
imagenet_gen_qsym_mkldnn.py + python/mxnet/contrib/quantization.py —
train fp32, calibrate layer ranges on sample batches, convert to int8,
compare accuracy and output agreement).

Offline flow on a synthetic 10-class blob dataset: a small CNN is trained
fp32 to high accuracy, then quantized with each calibration mode
('naive' abs-max and 'entropy' KL thresholds). The script reports fp32 vs
int8 agreement and asserts the quantized net keeps accuracy — the same
acceptance shape the reference example documents (~<1% drop on ImageNet).

  python examples/quantize_cnn.py --ctx tpu
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.contrib import quantization as qz
from mxnet_tpu.gluon import nn


def build_cnn(classes=10):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(16, 3, padding=1, activation="relu"))
        net.add(nn.MaxPool2D(2))
        net.add(nn.Conv2D(32, 3, padding=1, activation="relu"))
        net.add(nn.MaxPool2D(2))
        net.add(nn.Flatten())
        net.add(nn.Dense(64, activation="relu"))
        net.add(nn.Dense(classes))
    return net


def blob_dataset(n, classes=10, size=16, seed=0):
    """Class-conditional blob images: class k = a gaussian bump at a fixed
    grid position with class-specific width."""
    rng = np.random.RandomState(seed)
    y = rng.randint(0, classes, n)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    cx = (y % 5) * 0.2 + 0.1
    cy = (y // 5) * 0.5 + 0.25
    s = 0.08 + 0.04 * (y % 3)
    img = np.exp(-((xx[None] - cx[:, None, None]) ** 2 +
                   (yy[None] - cy[:, None, None]) ** 2) /
                 (2 * s[:, None, None] ** 2))
    img = img[:, None] + rng.normal(0, 0.15, (n, 1, size, size))
    return img.astype(np.float32), y.astype(np.int64)


def accuracy(net, X, Y, ctx, batch=128):
    correct = 0
    for lo in range(0, len(Y), batch):
        out = net(nd.array(X[lo:lo + batch], ctx=ctx))
        correct += int((out.asnumpy().argmax(-1) ==
                        Y[lo:lo + batch]).sum())
    return correct / len(Y)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--train-steps", type=int, default=120)
    args = ap.parse_args()
    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()

    Xtr, Ytr = blob_dataset(4096, seed=0)
    Xte, Yte = blob_dataset(1024, seed=1)

    net = build_cnn()
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 2e-3})
    b = 128
    t0 = time.time()
    for step in range(args.train_steps):
        lo = (step * b) % (len(Ytr) - b)
        x = nd.array(Xtr[lo:lo + b], ctx=ctx)
        y = nd.array(Ytr[lo:lo + b], ctx=ctx)
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(b)
    fp32_acc = accuracy(net, Xte, Yte, ctx)
    print("fp32: test acc %.3f (%.0f steps, %.1fs)"
          % (fp32_acc, args.train_steps, time.time() - t0))
    assert fp32_acc > 0.9, "fp32 baseline failed to train"

    calib = nd.array(Xtr[:256], ctx=ctx)
    fp32_out = net(nd.array(Xte[:256], ctx=ctx)).asnumpy()
    for mode in ("naive", "entropy"):
        qnet = qz.quantize_net(net, calib_data=calib, calib_mode=mode,
                               ctx=ctx)
        q_acc = accuracy(qnet, Xte, Yte, ctx)
        q_out = qnet(nd.array(Xte[:256], ctx=ctx)).asnumpy()
        agree = (q_out.argmax(-1) == fp32_out.argmax(-1)).mean()
        print("int8 (%s calibration): test acc %.3f, top-1 agreement "
              "with fp32 %.3f" % (mode, q_acc, agree))
        assert q_acc > fp32_acc - 0.02, (
            "int8 accuracy dropped too far: %.3f vs %.3f" % (q_acc, fp32_acc))
    print("quantization OK: int8 holds fp32 accuracy within 2%")


if __name__ == "__main__":
    main()

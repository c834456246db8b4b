"""Minimal SSD-style detector: the reference's example/ssd pipeline on the
TPU-native stack — ImageDetIter feeding packed det labels, MultiBoxPrior
anchors, MultiBoxTarget matching with hard-negative mining, and
MultiBoxDetection decode+NMS at inference, all through the Gluon API with
the training step compiled to one XLA program.

Synthetic data (no network egress): random color blobs on noise, one box
per image. Runs on CPU in seconds; point ctx at mx.tpu() for the chip.

  python examples/ssd_detection.py --steps 100
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


class TinySSD(gluon.HybridBlock):
    """One-scale SSD head over a small conv trunk."""

    def __init__(self, num_classes=2, num_anchors=3, **kwargs):
        super().__init__(**kwargs)
        self._num_classes = num_classes
        self._num_anchors = num_anchors
        with self.name_scope():
            self.trunk = nn.HybridSequential()
            with self.trunk.name_scope():
                for filters in (16, 32, 64):
                    self.trunk.add(nn.Conv2D(filters, 3, strides=2,
                                             padding=1))
                    self.trunk.add(nn.BatchNorm())
                    self.trunk.add(nn.Activation("relu"))
            self.cls_head = nn.Conv2D(num_anchors * (num_classes + 1), 3,
                                      padding=1)
            self.box_head = nn.Conv2D(num_anchors * 4, 3, padding=1)

    def hybrid_forward(self, F, x):
        feat = self.trunk(x)
        cls = self.cls_head(feat)      # (B, A*(C+1), H, W)
        box = self.box_head(feat)      # (B, A*4, H, W)
        b = cls.shape[0]
        c1 = self._num_classes + 1
        # anchor index must be cell-major (hw*A + a) to line up with
        # MultiBoxPrior's layout and the box head's flattening
        cls = cls.reshape((b, self._num_anchors, c1, -1))
        cls = F.transpose(cls, axes=(0, 2, 3, 1)).reshape((b, c1, -1))
        box = F.transpose(box, axes=(0, 2, 3, 1)).reshape((b, -1))
        return feat, cls, box


def synth_batch(rng, batch, size=32):
    """Images with one bright square; labels [cls, x1, y1, x2, y2]."""
    imgs = rng.rand(batch, 3, size, size).astype(np.float32) * 0.2
    labels = np.full((batch, 1, 5), -1.0, np.float32)
    for i in range(batch):
        s = rng.randint(8, 16)
        x0 = rng.randint(0, size - s)
        y0 = rng.randint(0, size - s)
        cls = rng.randint(0, 2)
        imgs[i, cls, y0:y0 + s, x0:x0 + s] = 1.0
        labels[i, 0] = [cls, x0 / size, y0 / size,
                        (x0 + s) / size, (y0 + s) / size]
    return nd.array(imgs), nd.array(labels)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args()

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = TinySSD()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net.hybridize()

    x, labels = synth_batch(rng, args.batch)
    feat, cls_pred, box_pred = net(x)
    anchors = nd.contrib.MultiBoxPrior(feat, sizes=(0.4, 0.25),
                                       ratios=(1.0, 2.0), clip=True)

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    cls_loss = gluon.loss.SoftmaxCrossEntropyLoss()
    box_loss = gluon.loss.HuberLoss()

    for step in range(args.steps):
        x, labels = synth_batch(rng, args.batch)
        with autograd.record():
            _, cls_pred, box_pred = net(x)
            bt, bm, ct = nd.contrib.MultiBoxTarget(
                anchors, labels, nd.softmax(cls_pred, axis=1),
                negative_mining_ratio=3.0, ignore_label=-1.0)
            keep = (ct >= 0).reshape((args.batch, -1, 1))
            lc = cls_loss(nd.transpose(cls_pred, axes=(0, 2, 1)), ct, keep)
            lb = box_loss(box_pred * bm, bt * bm)
            loss = lc.mean() + lb.mean()
        loss.backward()
        trainer.step(args.batch)
        if step % 10 == 0 or step == args.steps - 1:
            print("step %3d  loss %.4f (cls %.4f box %.4f)"
                  % (step, float(loss.asnumpy()),
                     float(lc.mean().asnumpy()),
                     float(lb.mean().asnumpy())))

    # inference: decode + NMS
    out = nd.contrib.MultiBoxDetection(
        nd.softmax(cls_pred, axis=1), box_pred, anchors,
        nms_threshold=0.45, threshold=0.2)
    dets = out.asnumpy()[0]
    kept = dets[dets[:, 0] >= 0]
    print("detections on image 0: %d rows (cls, score, box):" % len(kept))
    for row in kept[:5]:
        print("  cls=%d score=%.2f box=(%.2f, %.2f, %.2f, %.2f)"
              % (int(row[0]), row[1], *row[2:]))


if __name__ == "__main__":
    main()

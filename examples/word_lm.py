"""Word-level language model (reference: example/rnn/word_lm/train.py —
embedding -> stacked LSTM -> tied softmax, truncated BPTT, perplexity).

Uses a real tokenized corpus if --data points at a text file, else a
synthetic Zipf-distributed corpus (offline environment). Runs on mx.cpu()
or mx.tpu(); hybridized so the whole unrolled step compiles to one XLA
program.

  python examples/word_lm.py --ctx tpu --epochs 3
"""
import argparse
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn, rnn


class WordLM(gluon.HybridBlock):
    """Embedding -> LSTM stack -> (tied) vocab projection."""

    def __init__(self, vocab, emb=128, hidden=128, layers=2, dropout=0.2,
                 **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, emb)
            self.lstm = rnn.LSTM(hidden, num_layers=layers,
                                 dropout=dropout, layout="NTC")
            self.drop = nn.Dropout(dropout)
            self.proj = nn.Dense(vocab, flatten=False)

    def hybrid_forward(self, F, x, *args, **params):
        h = self.embed(x)                    # (N, T, E)
        h = self.lstm(h)                     # (N, T, H)
        h = self.drop(h)
        return self.proj(h)                  # (N, T, V)


def corpus(path, n_tokens=200_000, vocab=2000, seed=0):
    if path and os.path.exists(path):
        with open(path) as f:
            words = f.read().split()
        idx = {}
        data = np.array([idx.setdefault(w, len(idx)) for w in words],
                        np.int32)
        return data, len(idx)
    rng = np.random.RandomState(seed)
    # Zipf: realistic token frequency profile for the softmax
    data = (rng.zipf(1.3, n_tokens) % vocab).astype(np.int32)
    return data, vocab


def batchify(data, batch, seq):
    n = (len(data) - 1) // (batch * seq) * (batch * seq)
    x = data[:n].reshape(batch, -1)
    y = data[1:n + 1].reshape(batch, -1)
    for t in range(0, x.shape[1] - seq + 1, seq):
        yield x[:, t:t + seq], y[:, t:t + seq]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    p.add_argument("--data", default=None, help="tokenized text file")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seq", type=int, default=35)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--lr", type=float, default=1.0)
    p.add_argument("--clip", type=float, default=0.25)
    args = p.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    data, vocab = corpus(args.data)
    print("corpus: %d tokens, vocab %d" % (len(data), vocab))

    with mx.Context(ctx):
        mx.random.seed(0)
        net = WordLM(vocab, emb=args.hidden, hidden=args.hidden,
                     layers=args.layers)
        net.initialize(mx.init.Xavier())
        net.hybridize(static_alloc=True)
        sce = gluon.loss.SoftmaxCrossEntropyLoss()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": args.lr,
                                 "clip_gradient": args.clip})

        for epoch in range(args.epochs):
            total, count, t0 = 0.0, 0, time.time()
            for x_np, y_np in batchify(data, args.batch, args.seq):
                x = nd.array(x_np, ctx=ctx)
                y = nd.array(y_np, ctx=ctx)
                with autograd.record():
                    logits = net(x)
                    loss = sce(logits.reshape((-1, vocab)),
                               y.reshape((-1,))).mean()
                loss.backward()
                trainer.step(1)
                total += float(loss.asnumpy())
                count += 1
            ppl = math.exp(total / max(count, 1))
            tok_s = count * args.batch * args.seq / (time.time() - t0)
            print("epoch %d: ppl %.2f  (%.0f tok/s)" % (epoch, ppl, tok_s))
        # generation smoke: greedy continuation from a seed token
        seed_tok = nd.array(np.full((1, 1), 1, np.int32), ctx=ctx)
        out = []
        cur = seed_tok
        for _ in range(10):
            logits = net(cur)
            nxt = int(np.argmax(logits.asnumpy()[0, -1]))
            out.append(nxt)
            cur = nd.array(np.array([[nxt]], np.int32), ctx=ctx)
        print("greedy continuation token ids:", out)


if __name__ == "__main__":
    main()

"""Text sentiment classification: contrib.text Vocabulary + embeddings +
gluon.rnn BiLSTM, trained end-to-end — the reference ecosystem's
GluonNLP-style workflow (vocab -> embed -> encode -> classify) on the
TPU-native stack.

Synthetic corpus (no network egress): sequences of "positive" and
"negative" marker words among filler tokens; the label is which marker
family dominates. The model must learn word identity -> sentiment.

  python examples/text_sentiment.py --steps 60
"""
import argparse
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.contrib import text
from mxnet_tpu.gluon import nn, rnn

POS = ["good", "great", "superb", "love", "happy"]
NEG = ["bad", "awful", "poor", "hate", "sad"]
FILLER = ["the", "a", "it", "was", "very", "movie", "film", "plot"]


def make_corpus(rng, n, seq_len=12):
    sents, labels = [], []
    for _ in range(n):
        label = rng.randint(0, 2)
        markers = POS if label else NEG
        k = rng.randint(2, 5)
        words = [markers[rng.randint(len(markers))] for _ in range(k)]
        words += [FILLER[rng.randint(len(FILLER))]
                  for _ in range(seq_len - k)]
        rng.shuffle(words)
        sents.append(words)
        labels.append(label)
    return sents, labels


def encode(vocab, sents, seq_len=12):
    pad = vocab.to_indices("<pad>")
    out = np.full((len(sents), seq_len), float(pad), np.float32)
    for i, words in enumerate(sents):
        idx = vocab.to_indices(words)[:seq_len]
        out[i, :len(idx)] = idx
    return out


class BiLSTMClassifier(gluon.HybridBlock):
    def __init__(self, vocab_size, embed_dim=32, hidden=32, classes=2,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, embed_dim)
            self.encoder = rnn.LSTM(hidden, bidirectional=True,
                                    layout="NTC")
            self.out = nn.Dense(classes)

    def hybrid_forward(self, F, x):
        e = self.embed(x)                 # (N, T, E)
        h = self.encoder(e)               # (N, T, 2H)
        pooled = F.max(h, axis=1)         # max-over-time
        return self.out(pooled)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    mx.random.seed(0)
    rng = np.random.RandomState(0)

    # vocabulary from the corpus (reference: contrib.text workflow)
    sents, labels = make_corpus(rng, 512)
    counter = collections.Counter(w for s in sents for w in s)
    vocab = text.Vocabulary(counter, reserved_tokens=["<pad>"])
    print("vocab size:", len(vocab))

    net = BiLSTMClassifier(len(vocab))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    x_all = encode(vocab, sents)
    y_all = np.asarray(labels, np.float32)
    for step in range(args.steps):
        sel = rng.randint(0, len(sents), args.batch)
        x = nd.array(x_all[sel])
        y = nd.array(y_all[sel])
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(args.batch)
        if step % 20 == 0 or step == args.steps - 1:
            print("step %3d  loss %.4f" % (step, float(loss.asnumpy())))

    # eval on fresh data
    test_s, test_y = make_corpus(rng, 256)
    logits = net(nd.array(encode(vocab, test_s))).asnumpy()
    acc = (logits.argmax(1) == np.asarray(test_y)).mean()
    print("test accuracy: %.3f" % acc)
    assert acc > 0.9, "sentiment classifier failed to learn"


if __name__ == "__main__":
    main()

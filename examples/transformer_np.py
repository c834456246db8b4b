"""Transformer encoder written entirely on mx.np / mx.npx.

reference: GluonNLP's BERT cells are written against mx.np arrays and
npx ops (npx.layer_norm, npx.interleaved_matmul_selfatt_*, npx.softmax,
npx.embedding); this example exercises the same surface end-to-end — a
small transformer encoder trained on a synthetic "sort the tokens" task
with autograd flowing through the np namespace.

  python examples/transformer_np.py --epochs 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd

np = mx.np
npx = mx.npx

VOCAB, DIM, HEADS, SEQ = 16, 32, 4, 12


def init_params(rng):
    def W(*shape, s=0.08):
        return np.array((rng.randn(*shape) * s).astype("float32"))

    p = {
        "embed": W(VOCAB, DIM),
        "pos": W(SEQ, DIM),
        "qkv_w": W(3 * DIM, DIM), "qkv_b": np.zeros((3 * DIM,)),
        "proj_w": W(DIM, DIM), "proj_b": np.zeros((DIM,)),
        "ln1_g": np.ones((DIM,)), "ln1_b": np.zeros((DIM,)),
        "ffn1_w": W(4 * DIM, DIM), "ffn1_b": np.zeros((4 * DIM,)),
        "ffn2_w": W(DIM, 4 * DIM), "ffn2_b": np.zeros((DIM,)),
        "ln2_g": np.ones((DIM,)), "ln2_b": np.zeros((DIM,)),
        "out_w": W(VOCAB, DIM), "out_b": np.zeros((VOCAB,)),
    }
    for v in p.values():
        v.attach_grad()
    return p


def encoder(p, tokens):
    """tokens (B, S) int32 -> logits (B, S, VOCAB), all mx.np/npx calls."""
    B = tokens.shape[0]
    h = npx.embedding(tokens, p["embed"], input_dim=VOCAB,
                      output_dim=DIM) + p["pos"]
    # attention block rides the fused interleaved op surface: (S, B, 3C)
    x = np.transpose(h, (1, 0, 2))
    qkv = npx.fully_connected(x.reshape(-1, DIM), p["qkv_w"], p["qkv_b"],
                              num_hidden=3 * DIM, flatten=False)
    qkv = qkv.reshape(SEQ, B, 3 * DIM)
    att = npx.interleaved_matmul_selfatt_qk(qkv, heads=HEADS)
    att = npx.softmax(att, axis=-1)
    ctx = npx.interleaved_matmul_selfatt_valatt(qkv, att, heads=HEADS)
    ctx = npx.fully_connected(ctx.reshape(-1, DIM), p["proj_w"],
                              p["proj_b"], num_hidden=DIM, flatten=False)
    h = npx.layer_norm(x.reshape(-1, DIM) + ctx, p["ln1_g"], p["ln1_b"])
    # ffn
    f = npx.fully_connected(h, p["ffn1_w"], p["ffn1_b"],
                            num_hidden=4 * DIM, flatten=False)
    f = npx.activation(f, act_type="gelu")
    f = npx.fully_connected(f, p["ffn2_w"], p["ffn2_b"], num_hidden=DIM,
                            flatten=False)
    h = npx.layer_norm(h + f, p["ln2_g"], p["ln2_b"])
    logits = npx.fully_connected(h, p["out_w"], p["out_b"],
                                 num_hidden=VOCAB, flatten=False)
    return np.transpose(logits.reshape(SEQ, B, VOCAB), (1, 0, 2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    rng = onp.random.RandomState(0)
    p = init_params(rng)
    # hand-rolled adam on the np surface
    m = {k: np.zeros(v.shape) for k, v in p.items()}
    s2 = {k: np.zeros(v.shape) for k, v in p.items()}
    t = 0

    for epoch in range(args.epochs):
        tot, hits, count = 0.0, 0, 0
        for _ in range(args.steps):
            toks = rng.randint(0, VOCAB, (args.batch_size, SEQ))
            target = onp.sort(toks, axis=1)     # task: sort the tokens
            x = np.array(toks.astype("int32"), dtype="int32")
            y = np.array(target.astype("int32"), dtype="int32")
            with autograd.record():
                logits = encoder(p, x)
                logp = npx.log_softmax(logits, axis=-1)
                nll = -npx.pick(logp.reshape(-1, VOCAB),
                                y.reshape(-1).astype("float32"))
                loss = np.mean(nll)
            loss.backward()
            t += 1
            corr = float(onp.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t))
            for k, v in p.items():
                g = v.grad
                m[k] = 0.9 * m[k] + 0.1 * g
                s2[k] = 0.999 * s2[k] + 0.001 * np.square(g)
                v -= args.lr * corr * m[k] / (np.sqrt(s2[k]) + 1e-8)
                v.grad[:] = 0
            tot += float(loss.asnumpy())
            pred = np.argmax(logits, axis=-1).asnumpy()
            hits += int((pred == target).sum())
            count += target.size
        print("epoch %2d  loss %.4f  token-acc %.3f"
              % (epoch, tot / args.steps, hits / count))


if __name__ == "__main__":
    main()

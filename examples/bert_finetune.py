"""Config #3: BERT-base masked-LM training / fine-tune step.

reference: the fork served BERT through GluonNLP on the fused attention ops
(src/operator/contrib/transformer.cc); here the encoder is first-class
(models/bert.py) and the op surface is exposed as
mx.nd.contrib.interleaved_matmul_selfatt_qk/_valatt + npx.* for GluonNLP-
style code.

Runs a masked-LM training loop on synthetic data (no network egress; real
corpora drop in via mx.io.CSVIter / RecordIO) with the whole step — forward,
loss, backward, AdamW — compiled into one XLA program, then reports tok/s.

  python examples/bert_finetune.py --config bert_tiny --steps 20
  python examples/bert_finetune.py --config bert_base   # needs the TPU chip

Multi-chip (TP+FSDP over a mesh) via --mesh, same recipe as llama_sharded:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/bert_finetune.py --config bert_tiny --mesh data=2,fsdp=2,model=2
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def synth_batch(key, batch, seq, vocab):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "tokens": jax.random.randint(k1, (batch, seq), 0, vocab),
        "targets": jax.random.randint(k2, (batch, seq), 0, vocab),
        "mask": (jax.random.uniform(k3, (batch, seq)) < 0.15)
        .astype(jnp.int32),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="bert_tiny")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--mesh", default="",
                    help="e.g. data=2,fsdp=2,model=2 (default single device)")
    args = ap.parse_args()

    from mxnet_tpu.models.bert import CONFIGS, bert_init, bert_mlm_loss
    from mxnet_tpu.parallel.train_step import ShardedTrainStep
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel.sharding import BERT_RULES

    cfg = CONFIGS[args.config]
    batch = args.batch or (64 if args.config != "bert_tiny" else 8)
    seq = args.seq or min(cfg.max_seq_len, 128)

    params = bert_init(jax.random.PRNGKey(0), cfg)
    if args.mesh:
        axes = dict(kv.split("=") for kv in args.mesh.split(","))
        mesh = create_mesh(**{k: int(v) for k, v in axes.items()})
    else:
        mesh = create_mesh(data=1, devices=jax.devices()[:1])
    step = ShardedTrainStep(lambda p, b: bert_mlm_loss(p, b, cfg), params,
                            mesh, rules=BERT_RULES, optimizer="adamw",
                            lr=args.lr)
    p, s = step.init()

    key = jax.random.PRNGKey(1)
    data = synth_batch(key, batch, seq, cfg.vocab_size)
    p, s, loss = step(p, s, data)          # compile
    jax.block_until_ready(loss)
    print("compiled; initial loss %.4f" % float(loss))

    t0 = time.perf_counter()
    losses = []
    for i in range(args.steps):
        key, sub = jax.random.split(key)
        data = synth_batch(sub, batch, seq, cfg.vocab_size)
        p, s, loss = step(p, s, data)
        losses.append(loss)
    jax.block_until_ready(losses[-1])
    dt = time.perf_counter() - t0
    tok_s = batch * seq * args.steps / dt
    print("config=%s batch=%d seq=%d: %.0f tok/s, loss %.4f -> %.4f"
          % (args.config, batch, seq, tok_s,
             float(losses[0]), float(losses[-1])))


if __name__ == "__main__":
    main()

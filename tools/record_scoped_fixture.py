#!/usr/bin/env python3
"""Record the pair that tests/benchmark checks the scope join on: a device
trace and the scope map the program gave in the same run.

    chiprun -- python tools/record_scoped_fixture.py

The toy BERT of `benchmark/tools/record_fixture.py` (2 layers, 128 wide,
heads of 64, 8 x 128 tokens) through `ShardedTrainStep`, three steps to warm
it up and four under the profiler with the harness's own annotations. The
step sees the session and reads its own compiled module
(`telemetry.note_step_program`). Writes under `chiprun_out/fixture_scoped/`:
`toy_bert_scoped.xplane.pb.gz`, and `toy_bert_scoped.scopes.json`, the map
`{instruction: [opcode, op_name]}` of `telemetry.module_scopes()` for the
step's module. Copy both to `tests/benchmark/fixtures/`. No run of the
benchmark calls this.
"""
import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "fixture_scoped")


def main():
    from mxnet_tpu.runtime import place_compile_cache
    place_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.bert import BertConfig, bert_init, bert_mlm_loss
    from mxnet_tpu.parallel import ShardedTrainStep, create_mesh
    from mxnet_tpu.parallel.train_step import STEP_MODULE

    dev = jax.devices()[0]
    print("device: %s %s x%d" % (dev.platform, dev.device_kind,
                                 len(jax.devices())), flush=True)
    cfg = BertConfig(vocab_size=1024, dim=128, n_layers=2, n_heads=2,
                     hidden_dim=256, max_seq_len=128)
    params = bert_init(jax.random.PRNGKey(0), cfg)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = {"tokens": jax.random.randint(k1, (8, 128), 0, cfg.vocab_size),
             "targets": jax.random.randint(k2, (8, 128), 0, cfg.vocab_size),
             "mask": (jax.random.uniform(k3, (8, 128)) < 0.15
                      ).astype(jnp.int32)}
    step = ShardedTrainStep(lambda p, b: bert_mlm_loss(p, b, cfg), params,
                            create_mesh(data=1), optimizer="adamw", lr=1e-4,
                            wd=0.01)
    params, state = step.init()
    for _ in range(3):
        params, state, loss = step(params, state, batch)
    loss.block_until_ready()

    log = os.path.join(OUT, "log")
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(log)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log, profiler_options=opts):
        for i in range(4):
            with jax.profiler.StepTraceAnnotation("step", step_num=i):
                with jax.profiler.TraceAnnotation("ShardedTrainStep.__call__"):
                    params, state, loss = step(params, state, batch)
        loss.block_until_ready()
    found = glob.glob(os.path.join(log, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    print("traces: %s" % [(p, os.path.getsize(p)) for p in found])
    with open(found[0], "rb") as f, gzip.open(os.path.join(
            OUT, "toy_bert_scoped.xplane.pb.gz"), "wb", 9) as g:
        g.write(f.read())
    shutil.rmtree(log)
    scopes = telemetry.module_scopes()[STEP_MODULE]
    with open(os.path.join(OUT, "toy_bert_scoped.scopes.json"), "w") as f:
        json.dump({name: [i.opcode, i.op_name]
                   for name, i in sorted(scopes.items())}, f, indent=0)
    print("scopes: %d instructions of %s; loss %.4f" % (
        len(scopes), STEP_MODULE, float(loss)))


if __name__ == "__main__":
    main()

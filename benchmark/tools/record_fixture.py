"""Record the small device trace that tests/benchmark checks the reduction on.

    chiprun -- python benchmark/tools/record_fixture.py

A few steps of a toy BERT (2 layers, 128 wide, heads of 64, 8 x 128 tokens)
through `ShardedTrainStep`, the flash kernels included, under the profiler
with the harness's own annotations. Writes the `.xplane.pb` and a listing of
its planes, lines and statistics under `chiprun_out/fixture/`. A tool for a
`benchmark` PR; no run of the benchmark calls it.
"""
import collections
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "fixture")


def listing(path, out):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE %r" % plane.name, file=out)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE %r events=%d" % (line.name, len(events)), file=out)
            keys = collections.Counter()
            for ev in events:
                keys.update(k for k, _ in ev.stats)
            print("    stat keys: %s" % dict(keys), file=out)
            for ev in events[:6]:
                print("    %r start=%.0f dur=%.0f %s" % (
                    ev.name, ev.start_ns, ev.duration_ns,
                    {k: (v if not isinstance(v, (str, bytes)) or len(v) < 80
                         else v[:80]) for k, v in ev.stats}), file=out)


def main():
    from mxnet_tpu.runtime import place_compile_cache
    place_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.bert import BertConfig, bert_init, bert_mlm_loss
    from mxnet_tpu.parallel import ShardedTrainStep, create_mesh

    dev = jax.devices()[0]
    print("device: %s %s x%d" % (dev.platform, dev.device_kind,
                                 len(jax.devices())), flush=True)
    print("memory_stats keys: %s" % sorted(dev.memory_stats() or {}))
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
    opts.host_tracer_level = int(os.environ.get("HOST_TRACER_LEVEL", "2"))
    with jax.profiler.trace(log, profiler_options=opts):
        for i in range(4):
            with jax.profiler.StepTraceAnnotation("step", step_num=i):
                with jax.profiler.TraceAnnotation("ShardedTrainStep.__call__"):
                    params, state, loss = step(params, state, batch)
        loss.block_until_ready()
    found = glob.glob(os.path.join(log, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    print("traces: %s" % [(p, os.path.getsize(p)) for p in found])
    dst = os.path.join(OUT, "toy_bert.xplane.pb")
    shutil.copy(found[0], dst)
    shutil.rmtree(log)
    import gzip
    with open(dst, "rb") as f, gzip.open(dst + ".gz", "wb", 9) as g:
        g.write(f.read())
    with open(os.path.join(OUT, "structure.txt"), "w") as out:
        listing(dst, out)
    print("loss %.4f" % float(loss))


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""What each part of the flash kernels' tile is worth, read apart in one
process on the chip.

    chiprun -- python tools/flash_parts.py --workload bert_base_s128 \
        [--batch 32 --seq 512] \
        [--parent build/parent/mxnet_tpu/parallel/flash_attention.py]

Builds a benchmark cell's BERT step (the cell's own runner, weights and
batch; `--batch` and `--seq` give the mix another shape, as seq 512 has no
cell) once per variant, each with one part of `parallel/flash_attention.py`
put back to what it was, and reads a traced window of each with the
benchmark's own reduction: the step, the three kernels and the copies.

  change         the tree as it is: one packed q|k|v projection, read and
                 written in place by the kernels, head groups on the lanes,
                 whole-sequence tiles
  w_fused        ... with the packed weight and bias left for XLA to fuse
                 into the product's operand, not written once before it
  split          three projections a layer and `flash_attention_bshd` on
                 their (B, S, H, D) results, as the layer was before the
                 packed entry
  old_tile       ... with 128 x 128 tiles of one batch row a grid step
  tile:K=V;K=V   ... with those constants of the tile chooser set, e.g.
                 `tile:_MAX_ROWS=4` (what the chooser's constants are worth)
  a+b            several of these at once: `split+tile:_VMEM_LIMIT=50331648`
                 is the layer and the kernels' VMEM request of before the
                 packed entry
  transposed     three projections, and the layer transposes to (B, H, S, D)
                 and back as it used to, around the same kernels on that
                 view (D on the lanes)
  parent         the transposing layer around the parent commit's kernels,
                 loaded from `--parent` (a `git archive` of it)

One JSON line a variant, on standard output and in
`chiprun_out/flash_parts/<config>_b<batch>_s<seq>.jsonl`. A tool for PERF.md's findings; no
run of the benchmark calls it, and nothing in the package reads it.
"""
import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
VARIANTS = ("change", "w_fused", "split", "old_tile", "transposed",
            "parent")


def three_projection_layer(attention, transposed):
    """`models.bert._encoder_layer` as it was before the packed entry:
    three projections, and `attention` on their (B, S, H, D) results; or,
    `transposed`, as it was before that entry: four transposes a layer
    forward, around `attention` on (B, H, S, D)."""
    import jax
    from mxnet_tpu.models.bert import layer_norm

    def layer(lp, x, cfg):
        B, S, _ = x.shape
        a = lp["attn"]
        q, k, v = ((x @ a["w" + n] + a["b" + n])
                   .reshape(B, S, cfg.n_heads, cfg.head_dim) for n in "qkv")
        if transposed:
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        o = attention(q, k, v, causal=False)
        if transposed:
            o = o.transpose(0, 2, 1, 3)
        o = o.reshape(B, S, -1)
        x = layer_norm(x + (o @ a["wo"] + a["bo"]), lp["attn_norm"],
                       cfg.norm_eps)
        f = lp["ffn"]
        h = jax.nn.gelu(x @ f["w1"] + f["b1"], approximate=True)
        return layer_norm(x + (h @ f["w2"] + f["b2"]), lp["ffn_norm"],
                          cfg.norm_eps)
    return layer


def put_back(variant, parent_path):
    """Patch the package for one variant; returns the undo."""
    import jax
    from mxnet_tpu.models import bert
    fa = sys.modules["mxnet_tpu.parallel.flash_attention"]
    saved = {(fa, n): getattr(fa, n) for n in
             ("_MAX_BLOCK", "_MAX_ROWS", "_STEP_SCORES", "_VMEM_LIMIT")}
    saved[(bert, "_encoder_layer")] = bert._encoder_layer
    saved[(bert, "_packed_projection")] = bert._packed_projection
    for part in variant.split("+"):     # `split+tile:_MAX_ROWS=4`: both
        if part == "old_tile":
            fa._MAX_BLOCK, fa._MAX_ROWS = 128, 1
        elif part.startswith("tile:"):
            for pair in part[len("tile:"):].split(";"):
                name, value = pair.split("=")
                if (fa, name) not in saved:
                    raise SystemExit("flash_parts: no constant %r" % name)
                setattr(fa, name, int(value))
        elif part == "w_fused":
            bert._packed_projection = lambda a, n_heads: tuple(
                fa.pack_qkv(*(a[kind + n] for n in "qkv"), n_heads)
                for kind in "wb")
        elif part == "split":
            bert._encoder_layer = three_projection_layer(
                fa.flash_attention_bshd, False)
        elif part == "transposed":
            bert._encoder_layer = three_projection_layer(
                fa.flash_attention, True)
        elif part == "parent":
            spec = importlib.util.spec_from_file_location(
                "mxnet_tpu.parallel._flash_attention_parent", parent_path)
            parent = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(parent)
            bert._encoder_layer = three_projection_layer(
                parent.flash_attention, True)
        elif part != "change":
            raise SystemExit("flash_parts: no variant %r" % part)

    def undo():
        for (module, name), value in saved.items():
            setattr(module, name, value)
    # `_forward` and `_backward` are jitted: a tile chosen under other
    # constants must not come back from their cache
    jax.clear_caches()
    return undo


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2700000027)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--batch", type=int, help="rows, instead of the mix's")
    ap.add_argument("--seq", type=int, help="tokens a row, likewise")
    ap.add_argument("--variants", default=",".join(VARIANTS[:-1]),
                    help="comma-separated, of the module's list")
    ap.add_argument("--parent", default="",
                    help="the parent commit's flash_attention.py")
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()
    variants = opts.variants.split(",") + (["parent"] if opts.parent else [])

    sys.path[:0] = [ROOT, BENCH_DIR]
    import run as bench
    cell, devices, _ = bench.start(opts.workload, opts.rehearse)
    import jax
    from harness import runners, trace_reduce, traffic
    from harness.window import run_window
    from mxnet_tpu import telemetry
    cfg, mix, reference = cell.cfg, cell.traffic, cell.reference()
    mix = dict(mix, batch=opts.batch or mix["batch"],
               seq=opts.seq or mix["seq"])
    tokens = traffic.samples_per_step(mix)
    name = "%s_b%d_s%d" % (cell.entry["config"], mix["batch"], mix["seq"])
    out_dir = os.path.join(ROOT, "chiprun_out", "flash_parts")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, name + ".jsonl"), "a")

    def wait(loss):
        loss.block_until_ready()

    for variant in variants:
        undo, ops = put_back(variant, opts.parent), {}
        try:
            before = dict(telemetry.snapshot()["counters"])
            start, batch = traffic.make(opts.seed, reference, cfg, mix)
            runner = runners.ShardedStep(cfg, mix, reference, start, batch,
                                         cell.rehearse)
            del start, batch
            t = time.perf_counter()
            losses = [float(runner.call()) for _ in range(3)]
            first_s = time.perf_counter() - t
            window = run_window(runner.call, wait, opts.seconds)
            line = {"shape": name, "variant": variant,
                    "device": devices[0].device_kind,
                    "first_three_s": round(first_s, 2), "losses": losses,
                    "steps": window["completed"],
                    "tok_per_s": window["completed"] * tokens
                    / window["elapsed_s"],
                    "step_ms": 1e3 * window["elapsed_s"]
                    / max(window["completed"], 1)}
            after = telemetry.snapshot()["counters"]
            line["counters"] = {
                k: v - before.get(k, 0) for k, v in after.items()
                if k.startswith("ops.pallas.") and v != before.get(k, 0)}
            quiet = jax.profiler.ProfileOptions()
            quiet.python_tracer_level = 0
            with tempfile.TemporaryDirectory(prefix="flash_parts_") as tmp:
                with jax.profiler.trace(tmp, profiler_options=quiet):
                    run_window(runner.call, wait, min(opts.seconds, 2.0))
                found = glob.glob(os.path.join(
                    tmp, "plugins", "profile", "*", "*.xplane.pb"))
                reduced = (trace_reduce.reduce_trace(
                    trace_reduce.load(found[0])) if found else None)
            if reduced and reduced["devices"]:
                dev = reduced["devices"][0]
                per = 1e3 / max(dev["steps"], 1)
                line["traced_step_ms"] = dev["window_s"] * per
                line["category_ms"] = {c: s * per for c, s in
                                       dev["category_s"].items() if s > 0}
                kernels = {}
                for key, s in dev["op_s"].items():
                    if key.startswith("mosaic/"):
                        kernel = key[len("mosaic/"):].rsplit(".", 1)[0]
                        kernels[kernel] = kernels.get(kernel, 0.0) + s * per
                line["kernel_ms"] = kernels
                ops = {key: s * per for key, s in dev["op_s"].items()}
            runner.free()
            del runner
        except Exception as e:     # one variant refused: read the others
            line = {"shape": name, "variant": variant,
                    "error": repr(e)[-2000:]}
        finally:
            undo()
        # every operation's time a step goes to the file alone: with the
        # step's dumped module it says which product takes what
        out.write(json.dumps(dict(line, op_ms=ops)) + "\n")
        out.flush()
        print(json.dumps(line), flush=True)
    out.close()
    if opts.rehearse:
        print(bench.REHEARSAL)


if __name__ == "__main__":
    main()

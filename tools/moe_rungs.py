#!/usr/bin/env python3
"""How many chunks of its dispatch buffer the expert layer walked, loop by
loop, read on the chip from one traced window joined with the module the
chip compiled.

    chiprun -- python tools/moe_rungs.py --workload qwen3_next_ep16_s4096

`ops/moe.py` walks its dispatch buffer in chunks, for as many chunks as the
routing filled, in one `lax.fori_loop` a direction whose bound is read on
the device; no value leaves the step to say how many. But every instruction
of a chunk's body carries `rows_<R>` in its `op_name` (the body's
`jax.named_scope`), each loop is one `while` of the compiled module, and the
device trace names every instruction that ran, once a trip. So: build the
cell's own runner (its weights and batch from the seed), take its first
three steps, trace a short window, take the step's compiled module as the
step itself read it under the profiler's session
(`mxnet_tpu.telemetry.module_scopes()`), and file each traced operation
under the `while` whose body holds it. A container inside a body (a `while`,
a `call`) is filed nowhere: its own event covers operations that are filed
already. A loop's trips in a step are the events there of its body's
kernels (`moe_gmm`, `moe_tgmm`) over the kernels the body holds; of a body
without kernels (the XLA loop over experts, off the TPU), of all that is
filed.

Printed, and written to `chiprun_out/moe_rungs/<workload>_<seed>.json`: the
trace-time counters `ops.moe.chunk.<R>` (a body that compiled); and for
every loop in the order it runs in a step (the forward passes of the
layers, then the backward passes from the last layer down) the trips a
step, the device time a trip and a step under its `rows_<R>` scope, the
trips in the order the window's steps took them, and the body's longest
instructions with their time a trip. A tool for PERF.md's
findings; no run of the benchmark calls it.
"""
import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)
from mxnet_tpu.parallel.train_step import STEP_MODULE  # noqa: E402
from mxnet_tpu.telemetry import hlo_scopes  # noqa: E402
_SCOPE = re.compile(r"/rows_(\d+)/")
_KERNEL = re.compile(r"moe_t?gmm(\.\d+)?$")
LONGEST = 16     # of a body's instructions in the report


def loop_of_instruction(instrs):
    """({instruction: loop}, {loop: (pass, rows)}, {loop: the instructions
    that mark a trip}) of a compiled module as `mxnet_tpu.telemetry.
    hlo_scopes.parse` gives it: every instruction of the body of a `while`
    whose body carries a `rows_<R>` scope, and of what that body calls,
    filed under the loop; `pass` as `hlo_scopes.path` reads the loop's own
    `op_name`; a trip's marks are the body's kernels, or all that is filed
    where it has none."""
    held = collections.defaultdict(list)    # computation -> its instructions
    for name, instr in instrs.items():
        held[instr.computation].append(name)

    def reach(computation, seen):
        if computation in seen or computation not in held:
            return
        seen.add(computation)
        for name in held[computation]:
            for callee in instrs[name].calls:
                reach(callee, seen)

    inside = {}     # a loop with `rows_<R>` in its body -> what it reaches
    for loop, instr in instrs.items():
        if instr.opcode == "while":
            reached = set()
            for callee in instr.calls:
                reach(callee, reached)
            if any(_SCOPE.search(instrs[name].op_name)
                   for computation in reached for name in held[computation]):
                inside[loop] = reached
    filed, loops, marks = {}, {}, {}
    for loop, reached in inside.items():
        # a loop inside a chunk's body (the pieces of its scatter-add) is
        # the body's, not a loop of the layer
        if any(instrs[loop].computation in other for other in inside.values()):
            continue
        body = [name for computation in reached for name in held[computation]
                if instrs[name].opcode not in hlo_scopes.CONTAINERS]
        rows = collections.Counter(
            int(r) for name in body
            for r in _SCOPE.findall(instrs[name].op_name))
        loops[loop] = (hlo_scopes.path(instrs[loop].op_name)[0],
                       rows.most_common(1)[0][0])
        filed.update((name, loop) for name in body)
        marks[loop] = (frozenset(filter(_KERNEL.match, body))
                       or frozenset(body))
    return filed, loops, marks


def _runs(trips):
    """[2, 2, 3] -> "2x2 3x1"."""
    out = []
    for n in trips:
        if out and out[-1][0] == n:
            out[-1][1] += 1
        else:
            out.append([n, 1])
    return " ".join("%gx%d" % (n, times) for n, times in out)


def by_loop(events, step_starts, filed, loops, marks):
    """events [(start_ns, duration_ns, instruction)] of one chip's `XLA
    Ops` line and the starts of the window's steps -> the report's `loops`,
    in the order a step runs them."""
    per = collections.defaultdict(lambda: {
        "ns": 0, "starts": [], "by_name": collections.Counter()})
    for start, duration, name in events:
        loop = filed.get(name)
        if loop is None:
            continue
        per[loop]["ns"] += duration
        per[loop]["by_name"][name] += duration
        if name in marks[loop]:
            per[loop]["starts"].append(start)
    steps = max(len(step_starts), 1)
    report = []
    for loop, slot in per.items():
        in_steps = collections.Counter(
            bisect.bisect_right(step_starts, start) for start in
            slot["starts"])
        trips = [in_steps[step] / len(marks[loop])
                 for step in range(1, len(step_starts) + 1)]
        ms = slot["ns"] * 1e-6
        report.append({
            "loop": loop, "pass": loops[loop][0], "rows": loops[loop][1],
            "first_ns": min(slot["starts"], default=0),
            "trips_a_step": sum(trips) / steps,
            "ms_a_trip": ms / sum(trips) if sum(trips) else None,
            "ms_a_step": ms / steps,
            "in_order": _runs(trips),
            # the body's longest instructions, ms a trip each
            "body": {name: ns * 1e-6 / sum(trips) for name, ns in
                     slot["by_name"].most_common(LONGEST) if sum(trips)}})
    report.sort(key=lambda r: r.pop("first_ns"))
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3400000034)
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="of the traced window")
    ap.add_argument("--warm", type=float, default=0.0,
                    help="seconds of untraced steps before it (the "
                         "benchmark's window follows the first three steps)")
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    sys.path.insert(0, BENCH_DIR)
    import run as bench
    cell, devices, _ = bench.start(opts.workload, opts.rehearse)
    import jax
    from harness import runners, trace_reduce, traffic
    from harness.window import run_window
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import moe
    cfg, mix, reference = cell.cfg, cell.traffic, cell.reference()
    start, batch = traffic.make(opts.seed, reference, cfg, mix)
    runner = runners.ShardedStep(cfg, mix, reference, start, batch,
                                 cell.rehearse)
    del start, batch
    losses = [float(runner.call()) for _ in range(3)]

    def wait(loss):
        loss.block_until_ready()

    if opts.warm:
        run_window(runner.call, wait, opts.warm)
    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="moe_rungs_") as tmp:
        with jax.profiler.trace(tmp, profiler_options=quiet):
            window = run_window(runner.call, wait, opts.seconds)
        found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        data = trace_reduce.load(found[0]) if found else None
    # the step read its own module when it saw the profiler's session
    instrs = telemetry.module_scopes().get(STEP_MODULE)
    if instrs is None:
        raise SystemExit("moe_rungs: the traced window left no scope map of "
                         "%r" % STEP_MODULE)
    filed, loops, marks = loop_of_instruction(instrs)
    events, step_starts = [], []
    for plane in (data.planes if data is not None else ()):
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                events = [(ev.start_ns, ev.duration_ns,
                           trace_reduce.op_name_and_category(ev.name)[0])
                          for ev in line.events]
            elif line.name == "XLA Modules":
                step_starts = sorted(ev.start_ns for ev in line.events
                                     if ev.name.startswith(STEP_MODULE))
        break       # the first chip
    report = by_loop(events, step_starts, filed, loops, marks)
    for loop in report:     # say what each of the longest is
        loop["body"] = {"%s %s" % (name, "/".join(
            instrs[name].op_name.split("/")[-2:])): ms
            for name, ms in loop["body"].items()}
    counters = {k: v for k, v in telemetry.snapshot()["counters"].items()
                if k.startswith("ops.moe.chunk.")}
    report = {"workload": opts.workload, "seed": opts.seed,
              "device": devices[0].device_kind, "losses": losses,
              "window_steps": window["completed"],
              "traced_steps": len(step_starts),
              "instructions_filed": len(filed), "counters": counters,
              "loops": report,
              "rows_ms_a_step": sum(r["ms_a_step"] for r in report),
              "trips_a_step": sum(r["trips_a_step"] for r in report)}
    out_dir = os.path.join(ROOT, "chiprun_out", "moe_rungs")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "%s_%d.json" % (
            opts.workload, opts.seed)), "w") as out:
        json.dump(report, out, indent=1)
    print(json.dumps(report, indent=1), flush=True)
    if opts.rehearse:
        print(bench.REHEARSAL)


if __name__ == "__main__":
    main()

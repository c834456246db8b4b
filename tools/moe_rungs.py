#!/usr/bin/env python3
"""Which rung of the expert layer's ladder ran, layer by layer, read on the
chip from one traced window joined with the module the chip compiled.

    chiprun -- python tools/moe_rungs.py --workload qwen3_next_ep16_s4096

`ops/moe.py` picks on the device, from what the routing filled, the prefix
of its dispatch buffer that it works over; no value leaves the step to say
which. But every instruction of a rung's body carries `rows_<R>` in its
`op_name` (the body's `jax.named_scope`), each `lax.switch` is one
`conditional` of the compiled module with a branch a rung, and the device
trace names every instruction that ran. So: build the cell's own runner
(its weights and batch from the seed), take its first three steps, trace a
short window, take the step's compiled module as the step itself read it
under the profiler's session (`mxnet_tpu.telemetry.module_scopes()`), and
file each traced operation under the (conditional, rung) whose branch
computation holds it. A container inside a branch (a `while`, a `call`) is
filed nowhere: its own event covers operations that are filed already.

Printed, and written to `chiprun_out/moe_rungs/<workload>_<seed>.json`: the
trace-time counters `ops.moe.ladder.<R>` (a rung that compiled); for every
conditional in the order it runs in a step (the forward passes of the
layers, then the backward passes from the last layer down) how many times a
step each rung ran, the device time a step under each `rows_<R>` scope and
the rungs in the order the window took them; and the sums by rung. A tool
for PERF.md's findings; no run of the benchmark calls it.
"""
import argparse
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


def rung_of_instruction(instrs):
    """({instruction: (conditional, rows)}, {conditional: "forward" |
    "backward"}) of a compiled module as `mxnet_tpu.telemetry.hlo_scopes.
    parse` gives it: every instruction of a branch computation of a
    conditional whose branches carry `rows_<R>` scopes, and of what that
    branch calls, filed under the branch's rung."""
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

    filed, direction = {}, {}
    for conditional, instr in instrs.items():
        if instr.opcode != "conditional":
            continue
        by_rows = {}
        for branch in instr.calls:
            inside = set()
            reach(branch, inside)
            body = [name for computation in inside
                    for name in held[computation]]
            rows = collections.Counter(
                int(r) for name in body
                for r in _SCOPE.findall(instrs[name].op_name))
            if rows:
                by_rows[rows.most_common(1)[0][0]] = body
        if len(by_rows) < 2:    # an interpreted kernel's `pl.when`
            continue
        direction[conditional] = (
            "backward" if "transpose(" in instr.op_name else "forward")
        for rung, body in by_rows.items():
            for name in body:
                if instrs[name].opcode not in hlo_scopes.CONTAINERS:
                    filed[name] = (conditional, rung)
    return filed, direction


def _runs(rungs):
    """[84, 84, 168] -> "84x2 168x1"."""
    out = []
    for rung in rungs:
        if out and out[-1][0] == rung:
            out[-1][1] += 1
        else:
            out.append([rung, 1])
    return " ".join("%dx%d" % (rung, n) for rung, n in out)


def by_rung(events, filed, direction, steps, row_tile):
    """events [(start_ns, duration_ns, instruction)] of one chip's `XLA
    Ops` line -> the report's `conditionals` and `rungs`."""
    per = collections.defaultdict(lambda: collections.defaultdict(
        lambda: {"ns": 0, "starts": {}}))
    for start, duration, name in events:
        if name not in filed:
            continue
        conditional, rows = filed[name]
        slot = per[conditional][rows]
        slot["ns"] += duration
        slot["starts"].setdefault(name, []).append(start)
    report, totals = [], collections.defaultdict(lambda: [0.0, 0.0])
    for conditional, rungs in per.items():
        taken = []
        for rows, slot in rungs.items():
            # an instruction of a branch runs once a run of the branch (one
            # inside a loop of its own more often): the commonest count
            runs = collections.Counter(
                len(starts) for starts in slot["starts"].values())
            starts = next(starts for starts in slot["starts"].values()
                          if len(starts) == runs.most_common(1)[0][0])
            taken += [(s, rows) for s in starts]
            totals[rows][0] += len(starts) / max(steps, 1)
            totals[rows][1] += slot["ns"] * 1e-6 / max(steps, 1)
        taken.sort()
        report.append({
            "conditional": conditional,
            "pass": direction.get(conditional, ""),
            "first_ns": taken[0][0],
            "runs_a_step": {str(rows // row_tile): sum(
                1 for _, r in taken if r == rows) / max(steps, 1)
                for rows in sorted(rungs)},
            "ms_a_step": {str(rows // row_tile): rungs[rows]["ns"] * 1e-6
                          / max(steps, 1) for rows in sorted(rungs)},
            "in_order": _runs([rows // row_tile for _, rows in taken])})
    report.sort(key=lambda r: r.pop("first_ns"))
    return report, {str(rows // row_tile): {"runs_a_step": runs,
                                           "ms_a_step": ms}
                    for rows, (runs, ms) in sorted(totals.items())}


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
    filed, direction = rung_of_instruction(instrs)
    events, steps = [], 0
    for plane in (data.planes if data is not None else ()):
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                events = [(ev.start_ns, ev.duration_ns,
                           trace_reduce.op_name_and_category(ev.name)[0])
                          for ev in line.events]
            elif line.name == "XLA Modules":
                steps = sum(1 for ev in line.events
                            if ev.name.startswith(STEP_MODULE))
        break       # the first chip
    conditionals, rungs = by_rung(events, filed, direction, steps,
                                  moe.ROW_TILE)
    counters = {k: v for k, v in telemetry.snapshot()["counters"].items()
                if k.startswith("ops.moe.ladder.")}
    report = {"workload": opts.workload, "seed": opts.seed,
              "device": devices[0].device_kind, "losses": losses,
              "window_steps": window["completed"], "traced_steps": steps,
              "instructions_filed": len(filed), "counters": counters,
              "conditionals": conditionals, "rungs": rungs}
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

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
(its weights and batch from the seed), take its first three steps, read the
step's compiled module from the loaded executable, trace a short window,
and file each traced operation under the (conditional, rung) whose branch
computation holds it.

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
_HEADER = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\) -> .* \{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SCOPE = re.compile(r"/rows_(\d+)/")


def computations(text):
    """{computation: [instruction lines]} of a compiled module's text."""
    out, lines = {}, None
    for line in text.splitlines():
        m = _HEADER.match(line)
        if m:
            lines = out.setdefault(m.group(1).lstrip("%"), [])
        elif line == "}":
            lines = None
        elif lines is not None:
            lines.append(line)
    return out


def rung_of_instruction(text):
    """({instruction: (conditional, rows)}, {conditional: "forward" |
    "backward"}): every instruction of a branch computation of a
    conditional whose branches carry `rows_<R>` scopes, and of what that
    branch calls, filed under the branch's rung."""
    comps = computations(text)

    def reach(name, seen):
        if name in seen or name not in comps:
            return
        seen.add(name)
        for line in comps[name]:
            for callee in _CALLED.findall(line):
                reach(callee.lstrip("%"), seen)

    filed, direction = {}, {}
    for lines in comps.values():
        for line in lines:
            branches = _BRANCHES.search(line)
            if not branches or " conditional(" not in line:
                continue
            by_rows = {}
            for branch in branches.group(1).split(","):
                inside = set()
                reach(branch.strip().lstrip("%"), inside)
                body = [ln for name in inside for ln in comps[name]]
                rows = collections.Counter(
                    int(r) for ln in body for r in _SCOPE.findall(ln))
                if rows:
                    by_rows[rows.most_common(1)[0][0]] = body
            if len(by_rows) < 2:    # an interpreted kernel's `pl.when`
                continue
            conditional = _INSTR.match(line).group(1)
            direction[conditional] = (
                "backward" if "transpose(" in line else "forward")
            for rung, body in by_rows.items():
                for ln in body:
                    m = _INSTR.match(ln)
                    if m:
                        filed[m.group(1)] = (conditional, rung)
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


def step_module_text(name="jit_step_fn"):
    """The optimized HLO of the loaded step, from the runtime."""
    import jax
    for executable in jax.devices()[0].client.live_executables():
        module = executable.hlo_modules()[0]
        if module.name == name:
            return module.to_string()
    raise SystemExit("moe_rungs: no loaded program %r" % name)


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

    sys.path[:0] = [ROOT, BENCH_DIR]
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
    filed, direction = rung_of_instruction(step_module_text())

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
                            if ev.name.startswith("jit_step_fn"))
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

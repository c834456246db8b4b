"""Read what a cell's limits are set from, many seeds in one process.

    chiprun -- python benchmark/tools/readings.py --workload <cell> \
        --seeds 11,12,13 [--program 1] [--controls fp8,bf16@bfloat16] \
        [--faults half_batch] [--resolved bf16_stack] [--leaves 1]

For each seed the plain reference follows the first three steps; then, each
against it: the program (the cell's own runner), the controls (the reference
in a lower precision, put in the program's place; `mode@type` also keeps
weights and optimizer state in that type) and the planted faults (the
reference with a fault, put in the program's place). `--resolved` adds how far
the reference's own first gradient in that mode lies from its float32 one,
leaf by leaf (`correct.unresolved`). One JSON line a reading
on standard output and in `chiprun_out/readings/<cell>.jsonl`. A tool for
the PR that sets or changes a limit; no run of the benchmark calls it.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, default=1)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--resolved", default="")
    ap.add_argument("--leaves", type=int, default=0,
                    help="1: keep every leaf's norms in the file")
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    sys.path[:0] = [ROOT, BENCH_DIR]
    import run as bench
    cell, devices, _ = bench.start(opts.workload, opts.rehearse)
    devices = devices[:cell.chips]
    from harness import correct, runners, traffic
    cfg, mix, reference = cell.cfg, cell.traffic, cell.reference()
    out_dir = os.path.join(ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, cell.name + ".jsonl"), "a")

    def read(seed, what, factory, expected):
        t = time.perf_counter()
        start, batch = traffic.make(seed, reference, cfg, mix)
        runner = factory(cfg, mix, reference, start, batch, cell.rehearse)
        resolve = opts.resolved and expected is None
        followed = correct.follow(runner, start, keep_first=bool(resolve))
        got = correct.to_host(followed)
        runner.free()
        line = {"cell": cell.name, "seed": seed, "what": what,
                "loss": got["loss"]}
        if resolve:
            line["far"], _ = correct.unresolved(
                followed["first"], runners.first_gradient_in(
                    opts.resolved, cfg, mix, reference, start, batch,
                    cell.rehearse, devices=devices), 0)
        del runner, start, batch, followed
        line["seconds"] = round(time.perf_counter() - t, 1)
        if opts.leaves:
            line["grad"], line["change"] = got["grad"], got["change"]
        if expected is not None:
            line["numbers"] = {k: {"value": v, "leaf": leaf} for k, (v, leaf)
                               in correct.compare(
                                   got, expected,
                                   reference.leaves(cfg)).items()}
        out.write(json.dumps(line) + "\n")
        for key in ("grad", "change", "far"):
            line.pop(key, None)
        print(json.dumps(line), flush=True)
        out.flush()
        return got

    def standing_in(**kw):
        return lambda *a: runners.ReferenceRunner(*a, devices=devices, **kw)

    for seed in (int(s) for s in opts.seeds.split(",")):
        expected = read(seed, "reference", standing_in(), None)
        if opts.program:
            read(seed, "program", runners.RUNNERS[cfg["entry"]], expected)
            stats = devices[0].memory_stats() or {}
            print("after the program: bytes_in_use %s" %
                  stats.get("bytes_in_use"), flush=True)
        for control in filter(None, opts.controls.split(",")):
            mode, _, stored = control.partition("@")
            read(seed, "control:" + control,
                 standing_in(mode=mode, stored=stored or None), expected)
        for fault in filter(None, opts.faults.split(",")):
            read(seed, "fault:" + fault, standing_in(fault=fault), expected)
    out.close()


if __name__ == "__main__":
    main()

"""What of a mesh cell's step time is the window's own doing.

    chiprun --chips 4 -- python benchmark/tools/staging.py \
        --workload resnet50_dp4_b1024 --seed 5 --seconds 4

One process builds the cell's step once and times four short windows over
it: 8 and 2 steps in flight, each with the batch staged onto the mesh by
every call (as the cell feeds it, and as a user's loop does) and with the
batch laid out on the mesh beforehand, where the step's own `device_put` has
nothing left to move. One line a window. A tool for the PR that asks what the
window costs; no run of the benchmark calls it, and it reads the step's
private shardings, which the benchmark never does.
"""
import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    sys.path[:0] = [ROOT, BENCH_DIR]
    import run as bench
    cell, devices, _ = bench.start(opts.workload, opts.rehearse)
    import jax
    from harness import runners, traffic
    from harness.window import rate, run_window
    from mxnet_tpu import nd, telemetry
    cfg, mix, reference = cell.cfg, cell.traffic, cell.reference()
    start, batch = traffic.make(opts.seed, reference, cfg, mix)
    runner = runners.RUNNERS[cfg["entry"]](cfg, mix, reference, start, batch,
                                           cell.rehearse)
    for _ in range(3):
        runner.call().block_until_ready()
    staged = runner._x, runner._y
    step = runner._step
    laid = tuple(
        nd.from_jax(jax.device_put(a._read(), s), ctx=runner._ctx)
        for a, s in zip(staged, (step._data_sharding, step._label_sharding)))
    jax.block_until_ready([a._read() for a in laid])
    samples = traffic.samples_per_step(mix)
    for feed, arrays in (("staged by every call", staged),
                         ("laid out beforehand", laid)):
        runner._x, runner._y = arrays
        for in_flight in (8, 2):
            window = run_window(runner.call,
                                lambda loss: loss.block_until_ready(),
                                opts.seconds, in_flight=in_flight)
            print(json.dumps({
                "cell": cell.name, "feed": feed, "in_flight": in_flight,
                "steps": window["completed"],
                "rate": rate(window, samples),
                "ms_per_step": 1e3 * window["elapsed_s"]
                / window["completed"],
                "dispatch_ms": 1e3 * sum(window["dispatch_s"])
                / len(window["dispatch_s"]),
                "step_programs_built": telemetry.snapshot()["counters"].get(
                    "fused_step.compile")}), flush=True)
    if opts.rehearse:
        print(bench.REHEARSAL)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. It builds the cell's step from
the seed, drives it through its first three steps (which the comparison that
decides `correct` reads, and which warm it up), measures the timed window,
reads the chips' memory, frees the program and follows the same three steps
with the plain reference. The last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics`, `device`, the window's
`trimmed_steps` and `last_gap_ms`, with `--trace 1` `breakdown`, and last
`compared`, every number compared beside its limit. `--trace 0` reports the
cell's end-to-end metrics, `--trace 1` its per-layer metrics from a short
traced window.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. `--rehearse` runs the same code at toy size on the CPU
(`JAX_PLATFORMS=cpu`) and ends on `REHEARSAL (cpu): not a chip result`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_SECONDS = 3.0     # the traced window: a few seconds, in a run of its own
REHEARSAL = "REHEARSAL (cpu): not a chip result"


def say(text):
    print(text, flush=True)


def find_devices(cell, rehearse):
    """The cell's chips, or an exit that names what was found."""
    import jax
    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want:
        raise SystemExit(
            "benchmark: needs a %s backend; jax found platform %r (%d x %s)"
            % (want, devices[0].platform, len(devices),
               devices[0].device_kind))
    if len(devices) < cell.chips:
        raise SystemExit(
            "benchmark: workload %s asks for %d chip(s); jax found %d %s "
            "device(s)" % (cell.name, cell.chips, len(devices), want))
    return devices


def start(workload, rehearse):
    """(cell, devices, cache directory): the cell's files found, the compile
    cache placed before anything compiles, and the chips looked for."""
    sys.path[:0] = [ROOT, BENCH_DIR]
    from harness.spec import Cell
    cell = Cell(workload, rehearse=rehearse)
    if rehearse:
        os.environ["MXNET_FLASH_INTERPRET"] = "1"
        flag = "--xla_force_host_platform_device_count"
        if cell.chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = "%s %s=%d" % (
                os.environ.get("XLA_FLAGS", ""), flag, cell.chips)
    from mxnet_tpu.runtime import place_compile_cache
    # a rehearsal keeps no cache: a CPU's programs are no use to the chip
    cache_dir = None if rehearse else place_compile_cache()
    import jax
    # the sub-second programs of a start are most of a warm set-up: keep them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell, find_devices(cell, rehearse), cache_dir


def memory_held(devices):
    """What each chip holds at this moment, in bytes (None where the backend
    reports none): its live buffers and what the runtime has set aside for
    the loaded programs' temporaries (`bytes_in_use` + `bytes_reserved`).
    Both are read at one moment, so they add up; two peaks would not."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(stats["bytes_in_use"] + stats.get("bytes_reserved", 0)
                   if stats else None)
    return out


def memory_peaks(devices, held):
    """The most each chip is known to have held: the allocator's peak of
    live buffers (`peak_bytes_in_use`, which leaves out a running program's
    temporaries), or what it held with the window's queue full (`held`,
    from `memory_held`), whichever is more. Printed beside them: the
    allocator's own account, and the compiler's of the loaded program with
    the largest temporaries, which is the step's."""
    out = []
    for d, h in zip(devices, held):
        stats = d.memory_stats()
        say("memory_stats %s: %s" % (d, stats))
        out.append(max(stats["peak_bytes_in_use"], h or 0) if stats else None)
    try:
        programs = [(e.get_compiled_memory_stats(), e.hlo_modules()[0].name)
                    for e in devices[0].client.live_executables()]
        m, name = max(programs, key=lambda p: p[0].temp_size_in_bytes)
        say("largest loaded program %s: temporaries %d, arguments %d, "
            "outputs %d, aliased %d, code %d bytes" % (
                name, m.temp_size_in_bytes, m.argument_size_in_bytes,
                m.output_size_in_bytes, m.alias_size_in_bytes,
                m.generated_code_size_in_bytes))
    except Exception as e:  # a record, not a reading: never fails a run
        say("loaded programs' memory not read: %r" % (e,))
    return out


def measure(cell, devices, seed, seconds, trace, runner_factory=None):
    """Set-up, the window, the memory reading, the reference and the
    comparison. Returns the result object; what goes before it is printed
    as it happens. `runner_factory`, for the tests of planted faults, stands in for the
    configuration's runner."""
    import jax

    from harness import correct, readers, runners, trace_reduce, traffic
    from harness.window import rate, run_window
    from mxnet_tpu import telemetry

    clock = time.perf_counter
    marks = [("imports and devices", clock())]
    cfg, mix, reference = cell.cfg, cell.traffic, cell.reference()
    mine = devices[:cell.chips]
    misses = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: misses.append(name)
        if name == "/jax/compilation_cache/cache_misses" else None)

    def counters():
        return dict(telemetry.snapshot()["counters"])

    # ---- set-up: the step and its state from the seed, its first steps
    start, batch = traffic.make(seed, reference, cfg, mix)
    jax.block_until_ready((start, batch))
    marks.append(("weights and batch", clock()))
    factory = runner_factory or runners.RUNNERS[cfg["entry"]]
    runner = factory(cfg, mix, reference, start, batch, cell.rehearse)
    marks.append(("the step built", clock()))
    first_call = {}

    def call():
        if first_call:
            return runner.call()
        t = clock()
        loss = runner.call()
        loss.block_until_ready()
        first_call["s"] = clock() - t
        marks.append(("first call", clock()))
        return loss

    def wait(loss):
        loss.block_until_ready()

    readings = correct.to_host(correct.follow(runner, start, call))
    del start, batch
    gc.collect()
    before = counters()
    cache_misses = len(misses)
    setup_s = clock() - T_START
    marks.append(("first steps read", clock()))
    say("set-up %.2f s: %s" % (setup_s, ", ".join(
        "%s %.2f" % (what, t - t0) for (what, t), t0 in
        zip(marks, [T_START] + [t for _, t in marks]))))

    # ---- the window
    reduced, held = None, []

    def dispatched():
        held[:] = memory_held(mine)
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory(prefix="benchmark_trace_") as tmp:
            def annotate(i):
                stack = contextlib.ExitStack()
                stack.enter_context(jax.profiler.StepTraceAnnotation(
                    trace_reduce.STEP, step_num=i))
                stack.enter_context(
                    jax.profiler.TraceAnnotation(runner.entry))
                return stack

            def traced_wait(loss):     # names the gaps the host waits in
                with jax.profiler.TraceAnnotation("window.wait"):
                    wait(loss)
            with jax.profiler.trace(tmp, profiler_options=opts):
                window = run_window(call, traced_wait,
                                    min(seconds, TRACE_SECONDS),
                                    annotate=annotate, dispatched=dispatched)
            found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                           "*.xplane.pb"))
            t = clock()
            reduced = trace_reduce.reduce_trace(
                trace_reduce.load(found[0])) if found else None
            say("trace: %s bytes, read in %.1f s" % (
                [os.path.getsize(p) for p in found], clock() - t))
    else:
        window = run_window(call, wait, seconds, dispatched=dispatched)
    after = counters()
    # every step that was awaited, those after the window's end too
    losses = [float(x) for x in window["losses"][:len(window["done_s"])]]
    failed = sum(not math.isfinite(x) for x in losses)
    if window["error"] is not None:
        failed += 1
        say("window ended by: %r" % (window["error"],))
    peaks = memory_peaks(mine, held or [None] * len(mine))
    last_gap_ms = 1e3 * (window["last_gap_s"] or 0.0)
    say("steps_done_ms: %s" % json.dumps(
        [round(1e3 * t, 2) for t in window["done_s"]]))
    say("window: %d steps counted of %d in %.4f s, %d after its end (the "
        "last step's gap %.2f ms); losses %.4f .. %.4f"
        % (window["completed"], window["attempted"], window["elapsed_s"],
           window["trimmed_steps"], last_gap_ms,
           losses[0] if losses else float("nan"),
           losses[-1] if losses else float("nan")))
    say("bytes per chip with the queue full (in use + reserved): %s; the "
        "most known: %s" % (held, peaks))

    # ---- the reference, once the program's state is freed
    runner.free()
    del runner
    gc.collect()
    t = clock()
    start, batch = traffic.make(seed, reference, cfg, mix)
    plain = runners.ReferenceRunner(cfg, mix, reference, start, batch,
                                    cell.rehearse, devices=mine)
    followed = correct.follow(plain, start, keep_first=bool(cell.resolved))
    expected = correct.to_host(followed)
    plain.free()
    say("reference: %d steps followed in %.1f s" % (correct.STEPS,
                                                    clock() - t))
    left_out = set()
    if cell.resolved:
        # which leaves the stated precision resolves, read on the reference
        t = clock()
        far, left_out = correct.unresolved(
            followed["first"], runners.first_gradient_in(
                cell.resolved["mode"], cfg, mix, reference, start, batch,
                cell.rehearse, devices=mine), cell.resolved["within"])
        say("resolved: the reference's first gradient in %s lies within %s "
            "of its own on %d of %d leaves (%.1f s); left out: %s" % (
                cell.resolved["mode"], cell.resolved["within"],
                len(far) - len(left_out), len(far), clock() - t,
                json.dumps({k: round(far[k], 3) for k in sorted(left_out)})))
    del start, batch, plain, followed
    ok, compared, read = correct.judge(
        correct.compare(readings, expected, reference.leaves(cfg), left_out),
        cell.limits, cell.not_compared)
    say("read and not compared: %s" % json.dumps(read))
    ok = ok and failed == 0 and window["completed"] > 0

    # ---- the metrics
    samples = traffic.samples_per_step(mix)
    kind = mine[0].device_kind
    device = {"platform": mine[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max((p for p in peaks if p), default=0)}
    metrics = {}
    result = {"correct": bool(ok), "attempted": window["attempted"],
              "failed": failed, "metrics": metrics, "device": device,
              "trimmed_steps": window["trimmed_steps"],
              "last_gap_ms": last_gap_ms}
    if trace:
        run = {"cfg": cfg, "traffic": mix, "reference": reference,
               "chips": cell.chips,
               "samples_per_step": samples, "window": window,
               "counters_before": before, "counters_after": after,
               "first_call_s": first_call["s"], "cache_misses": cache_misses,
               "memory_peak_bytes": device["memory_peak_bytes"],
               "trace": reduced, "notes": [],
               "peak": None if cell.rehearse else cell.peak(kind)}
        for metric in cell.per_layer:
            value = readers.read(metric, run, cell.bench_dir)
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
        for note in run["notes"]:
            say("note: " + note)
        if reduced and reduced["devices"]:
            devs = reduced["devices"]
            device["busy_s"] = sum(d["busy_s"] for d in devs) / len(devs)
            device["window_s"] = sum(d["window_s"] for d in devs) / len(devs)
            result["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        metric = cell.rate_metric()
        metrics[metric["name"]] = {"value": rate(window, samples),
                                   "unit": metric["unit"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU; not a chip result")
    opts = ap.parse_args(argv)

    cell, devices, cache_dir = start(opts.workload, opts.rehearse)
    say("cell %s: config %s, traffic %s, %d chip(s); seed %d, %.1f s, "
        "trace %d" % (cell.name, cell.entry["config"], cell.entry["traffic"],
                      cell.chips, opts.seed, opts.seconds, opts.trace))
    say("device: platform=%s kind=%s count=%d; compile cache: %s"
        % (devices[0].platform, devices[0].device_kind, len(devices),
           cache_dir))

    result = measure(cell, devices, opts.seed, opts.seconds, opts.trace)

    for name, check in result["compared"].items():
        print("compared %s: %.6g (limit %s)%s" % (
            name, check["value"], check["limit"],
            " worst leaf %s" % check["leaf"] if "leaf" in check else ""),
            file=sys.stderr, flush=True)
    say(json.dumps(result))
    if opts.rehearse:
        say(REHEARSAL)


if __name__ == "__main__":
    main()

"""Per-layer metrics: one small reader each, found by the `reader` key of
the metric's file under `benchmark/metrics/`.

A reader takes what the traced run gathered (`run`, see `run.py`; its
`reference` is the configuration's reference module, where a FLOPs count or a
kernel's work that `flops.py` lacks is looked for) and its own parameters,
and returns a number, or None where it finds nothing to read: the
harness then leaves that metric out of the line. It never returns 0 for a
share of a roofline or of a peak. A metric's file may name a `.py` beside it
(`"reader_file"`) that defines `read(run, params)` instead.
"""
import importlib.util
import os

from . import flops


def _first_device(run):
    devices = run["trace"]["devices"] if run.get("trace") else []
    return devices[0] if devices else None


def dispatch_ms_per_step(run, params):
    """Host time inside each call of the entry, mean over the window."""
    spans = run["window"]["dispatch_s"]
    return 1e3 * sum(spans) / len(spans) if spans else None


def counter_delta(run, params):
    """How far the named telemetry counters moved across the window."""
    before, after = run["counters_before"], run["counters_after"]
    return float(sum(after.get(c, 0) - before.get(c, 0)
                     for c in params["counters"]))


def first_call_s(run, params):
    """Host clock around the first call of the step program, awaited."""
    return run["first_call_s"]


def cache_misses(run, params):
    """Programs the persistent cache did not hold, during set-up."""
    return float(run["cache_misses"])


def mfu(run, params):
    """Required operations a sample x samples a second of the traced window
    over the chips' peak."""
    dev = _first_device(run)
    if dev is None or not dev["steps"] or run["peak"] is None:
        return None
    per_sample = flops.train_flops_per_sample(run["cfg"], run["traffic"],
                                              run.get("reference"))
    samples_per_s = dev["steps"] * run["samples_per_step"] / dev["window_s"]
    return 100.0 * per_sample * samples_per_s / (
        run["chips"] * run["peak"]["bf16_flops_per_s"])


def category_ms_per_step(run, params):
    """Device time of one category of operations on the first chip, a
    step."""
    dev = _first_device(run)
    if dev is None or not dev["steps"]:
        return None
    seconds = dev["category_s"].get(params["category"], 0.0)
    return 1e3 * seconds / dev["steps"] if seconds > 0 else None


def category_roofline(run, params):
    """The least time the chip could take for the category's required work
    (the larger of operations over peak and bytes over peak bandwidth), over
    the time its operations took. Per chip: the work is the chip's share."""
    ms = category_ms_per_step(run, params)
    if ms is None or run["peak"] is None:
        return None
    work, nbytes = flops.kernel_work(params["work"], run["cfg"],
                                     run["traffic"], run.get("reference"))
    least, bound = flops.roofline_seconds(work / run["chips"],
                                          nbytes / run["chips"], run["peak"])
    run["notes"].append("%s: bound by %s (%.3f ms least, %.3f ms taken)"
                        % (params["work"], bound, least * 1e3, ms))
    return 100.0 * least * 1e3 / ms


def collective_exposed_ms_per_step(run, params):
    """Time in which a collective runs on the first chip and no other
    operation does, a step."""
    dev = _first_device(run)
    if dev is None or not dev["steps"] or dev["collective_s"] <= 0:
        return None
    return 1e3 * dev["collective_exposed_s"] / dev["steps"]


def device_idle_share(run, params):
    """1 - busy over the traced window, on the idlest chip of the cell."""
    devices = run["trace"]["devices"] if run.get("trace") else []
    if not devices:
        return None
    return 100.0 * max(1.0 - d["busy_s"] / d["window_s"] for d in devices)


def peak_hbm_gb(run, params):
    """What the fullest chip held at its peak (`run.py`, `memory_peaks`)."""
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None


READERS = {f.__name__: f for f in (
    dispatch_ms_per_step, counter_delta, first_call_s, cache_misses, mfu,
    category_ms_per_step, category_roofline, collective_exposed_ms_per_step,
    device_idle_share, peak_hbm_gb)}


def read(metric, run, bench_dir):
    """The value of one per-layer metric (a `metrics/<name>.json`), or
    None."""
    if "reader_file" in metric:
        spec = importlib.util.spec_from_file_location(
            "metric_reader_" + metric["name"].replace(".", "_"),
            os.path.join(bench_dir, "metrics", metric["reader_file"]))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read(run, metric.get("params", {}))
    return READERS[metric["reader"]](run, metric.get("params", {}))

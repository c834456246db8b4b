"""Host time of one of the program's own spans, from the program's ring.

`mxnet_tpu.telemetry.span_events()` holds `(name, cat, ts, dur, tid)` on the
ring's own clock; `span_epoch()` is that clock's zero on `perf_counter`, the
clock of `run["window"]["t0"]` and `["t1"]`. A span's parent is the innermost
span that covers it on the same thread. A name matches `params["span"]` as it
stands or followed by `:<function>` (jit's spans carry the function's name).

    {"span": "fused_step.stage", "parent": "fused_step"}
        the spans that start in the window, their summed time over the
        number of parent spans there: ms a step
    {"span": "fused_step", "self": true}
        the span's own time less what the spans inside it cover, a step
    {"span": "jit.trace", "when": "setup"}
        the spans that ended before the window: seconds covered by any of
        them (a union: jax times a nested trace apart from the one around
        it)

A program without `span_epoch`, or a ring without such a span: None, and the
harness leaves the metric out of the line.
"""
from harness.trace_reduce import length, union


def program_spans():
    """[(name, start, end, tid)] on `perf_counter`'s clock, or None."""
    try:
        from mxnet_tpu import telemetry
        zero = telemetry.span_epoch()
    except (ImportError, AttributeError):
        return None
    return [(name, zero + ts, zero + ts + dur, tid)
            for name, _cat, ts, dur, tid in telemetry.span_events()]


def named(spans, name):
    return [s for s in spans
            if s[0] == name or s[0].startswith(name + ":")]


def self_seconds(span, spans):
    """The span's time that no other span of its thread inside it covers."""
    _, start, end, tid = span
    inside = [(o[1], o[2]) for o in spans if o is not span
              and o[3] == tid and start <= o[1] and o[2] <= end]
    return (end - start) - length(union(inside))


def read(run, params):
    spans = program_spans()
    if not spans:
        return None
    t0, t1 = run["window"]["t0"], run["window"]["t1"]
    if params.get("when", "window") == "setup":
        before = [(s, e) for _, s, e, _ in named(spans, params["span"])
                  if e <= t0]
        return length(union(before)) if before else None
    window = [s for s in spans if t0 <= s[1] <= t1]
    mine = named(window, params["span"])
    parents = named(window, params.get("parent", params["span"]))
    if not mine or not parents:
        return None
    if params.get("self"):
        seconds = sum(self_seconds(s, window) for s in mine)
    else:
        seconds = sum(e - s for _, s, e, _ in mine)
    return 1e3 * seconds / len(parents)

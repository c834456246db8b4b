"""Comm-overlap attribution: per-step compute/collective/host/idle
decomposition from recorded spans.

The bucketed comm engine's overlap has so far been *asserted* — "bucket
N's collective overlaps bucket N+1's pack under async dispatch" — never
measured. This module turns the spans the runtime already records (one
``comm.bucket[...]`` / ``comm.key[...]`` / ``comm.rs[...]`` /
``comm.ag[...]`` span per launch, one cat-``step`` span per
trainer/fused/serve step) into the measured evidence ROADMAP item #4's
schedule autotuner consumes, with NO new instrumentation burden at comm
call sites.

The model — host-side attribution, stated honestly: every span here is a
**host** interval (the time the dispatching thread spent inside the
call); device execution is asynchronous underneath. For one step window
``[t0, t1]`` the decomposition is a *partition* (it sums to the step time
exactly, which is why the acceptance's 5 % bound holds by construction):

* ``collective_ms`` — time covered by comm-cat spans: the host was inside
  a collective dispatch/launch (the *exposed* comm cost — per-launch
  latency × launches; the thing bucketing shrinks);
* ``host_ms``       — time covered by host-overhead spans (resilience
  checkpoints/restores/backoff, injected faults, user profiler scopes)
  not already counted as comm;
* ``idle_ms``       — time covered by explicit cat-``idle`` spans (queue
  parks); zero where none are recorded;
* ``compute_ms``    — the remainder: the host was off the comm/overhead
  path — packing the next bucket, dispatching compute, or running python
  while previously-launched device work (including in-flight collectives)
  proceeds underneath.

``overlap_frac`` is the bucketed engine's overlap claim made measurable:
within the step's *comm phase* (first collective launch → step end — the
region where collectives are in flight), the fraction the host spent OFF
the collective path, i.e. free to overlap pack/compute against in-flight
comm. Per-parameter sync (``MXNET_TPU_COMM_BUCKET_MB=0``) serializes the
host through N launches and drives the fraction down; bucketing frees the
phase and drives it up.

Surfaces: `telemetry.overlap_report()` (full per-step report),
``parse_log --overlap`` (same table from a chrome trace dump, stdlib
re-derivation), per-step ``attrib`` records in the flight recorder, and
``attrib.<site>.*`` gauges for scrapers — all inert under
``MXNET_TPU_TELEMETRY=0`` because `step_event` (the only live caller)
already is.
"""
from __future__ import annotations

__all__ = ["COMM_CATS", "HOST_CATS", "IDLE_CATS", "STEP_CAT",
           "attribute_window", "overlap_report", "step_attribution",
           "interval_union"]

COMM_CATS = frozenset(("comm",))
HOST_CATS = frozenset(("host", "resilience", "fault", "user"))
IDLE_CATS = frozenset(("idle",))
STEP_CAT = "step"

# spans fed to the per-step live pass (step_event): bounded tail so the
# attribution of one step never pays O(ring) on the 100k-span buffer; a
# window that outruns it (per-param sync over >512 params) widens once to
# _TAIL_SPANS_MAX and past THAT is counted, never silently clipped
_TAIL_SPANS = 512
_TAIL_SPANS_MAX = 8192


def interval_union(intervals):
    """Merge [(start, end)] into disjoint intervals; returns (total
    covered duration, merged list)."""
    if not intervals:
        return 0.0, []
    intervals = sorted(intervals)
    merged = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def _clip(events, cats, t0, t1):
    """[(start, end)] of spans in `cats` clipped to [t0, t1]."""
    out = []
    for name, cat, ts, dur, _tid in events:
        if cat not in cats:
            continue
        s, e = max(ts, t0), min(ts + dur, t1)
        if e > s:
            out.append((s, e))
    return out


def _subtract(intervals, cover):
    """`intervals` minus the (merged, disjoint) `cover` list."""
    out = []
    for s, e in intervals:
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def attribute_window(events, t0, t1):
    """Decompose one step window into the compute/collective/host/idle
    partition (ms) + comm-launch stats + overlap_frac. `events` are
    (name, cat, ts_s, dur_s, tid) tuples on the same clock as t0/t1."""
    width = max(0.0, t1 - t0)
    comm_iv = _clip(events, COMM_CATS, t0, t1)
    comm_busy = sum(e - s for s, e in comm_iv)
    collective, comm_cover = interval_union(comm_iv)
    host_raw = _clip(events, HOST_CATS, t0, t1)
    host, host_cover = interval_union(_subtract(host_raw, comm_cover))
    idle_raw = _subtract(_subtract(_clip(events, IDLE_CATS, t0, t1),
                                   comm_cover), host_cover)
    idle, _ = interval_union(idle_raw)
    compute = max(0.0, width - collective - host - idle)
    out = {
        "step_ms": round(width * 1e3, 3),
        "compute_ms": round(compute * 1e3, 3),
        "collective_ms": round(collective * 1e3, 3),
        "host_ms": round(host * 1e3, 3),
        "idle_ms": round(idle * 1e3, 3),
        "comm_launches": len(comm_iv),
        # dispatch concurrency across threads (busy > union means two
        # threads were inside collective launches at once)
        "comm_busy_ms": round(comm_busy * 1e3, 3),
    }
    if comm_iv:
        phase_start = min(s for s, _e in comm_iv)
        phase = t1 - phase_start
        in_phase, _ = interval_union(_clip(events, COMM_CATS,
                                           phase_start, t1))
        out["comm_phase_ms"] = round(phase * 1e3, 3)
        out["overlap_frac"] = round(
            max(0.0, phase - in_phase) / phase, 4) if phase > 0 else 0.0
    else:
        out["comm_phase_ms"] = 0.0
        out["overlap_frac"] = None
    return out


def _step_spans(events, site=None):
    return [(name, ts, dur) for name, cat, ts, dur, _tid in events
            if cat == STEP_CAT and (site is None or name == site)]


def overlap_report(events=None, site=None, limit=None):
    """Per-step attribution over every recorded cat-``step`` span (or
    just `site`'s). `events` defaults to the live span buffer; pass a
    trace dump's event list for post-hoc analysis. Returns::

        {"steps": [{"site", "ts_s", <attribute_window fields>}...],
         "summary": {"steps", "step_ms", "compute_ms", "collective_ms",
                     "host_ms", "idle_ms", "comm_launches",
                     "overlap_frac"}}   # sums; overlap_frac comm-phase-
                                        # weighted mean over comm steps

    The per-step partition sums to the step time exactly; the summary
    sums therefore do too.
    """
    if events is None:
        from .. import telemetry as _telem
        events = _telem.span_events()
    steps = _step_spans(events, site)
    if limit is not None and len(steps) > limit:
        steps = steps[-limit:]
    rows = []
    for name, ts, dur in steps:
        row = {"site": name, "ts_s": round(ts, 6)}
        row.update(attribute_window(events, ts, ts + dur))
        rows.append(row)
    summary = {"steps": len(rows), "overlap_frac": None}
    for key in ("step_ms", "compute_ms", "collective_ms", "host_ms",
                "idle_ms", "comm_launches", "comm_busy_ms"):
        summary[key] = round(sum(r[key] for r in rows), 3)
    phase_total = sum(r["comm_phase_ms"] for r in rows)
    if phase_total > 0:
        summary["overlap_frac"] = round(
            sum(r["overlap_frac"] * r["comm_phase_ms"] for r in rows
                if r["overlap_frac"] is not None) / phase_total, 4)
    return {"site": site, "steps": rows, "summary": summary}


def step_attribution(site, dur_ms, trace_buffer):
    """The live per-step pass `telemetry.step_event` runs: attribute the
    window that just ended ([now - dur, now] on the span clock — no step
    span lookup needed), publish ``attrib.<site>.*`` gauges, and return
    the compact record the flight recorder embeds. Returns None when the
    window saw no spans at all (nothing to attribute)."""
    from .. import telemetry as _telem
    t1 = trace_buffer.now()
    t0 = t1 - dur_ms / 1e3
    events = trace_buffer.tail(_TAIL_SPANS)
    if len(events) == _TAIL_SPANS and events[0][2] > t0:
        # the tail does not reach back to the step start — widen once
        # (flat per-param sync records one span per param), and count the
        # residual truncation instead of silently under-attributing
        events = trace_buffer.tail(_TAIL_SPANS_MAX)
        if len(events) == _TAIL_SPANS_MAX and events[0][2] > t0:
            _telem.inc("telemetry.attrib.window_truncated")
    # the step's own span (recorded just before step_event) must not
    # shadow the window; attribute_window already ignores cat "step"
    row = attribute_window(events, t0, t1)
    if not row["comm_launches"] and row["host_ms"] == 0.0 \
            and row["idle_ms"] == 0.0:
        return None
    for key in ("compute_ms", "collective_ms", "host_ms", "idle_ms"):
        _telem.set_gauge("attrib.%s.%s" % (site, key), row[key])
    if row["overlap_frac"] is not None:
        _telem.set_gauge("attrib.%s.overlap_frac" % site,
                         row["overlap_frac"])
    return {"compute_ms": row["compute_ms"],
            "collective_ms": row["collective_ms"],
            "host_ms": row["host_ms"], "idle_ms": row["idle_ms"],
            "comm_launches": row["comm_launches"],
            "overlap_frac": row["overlap_frac"]}

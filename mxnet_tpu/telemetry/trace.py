"""Host-side span recording + chrome://tracing export.

The analog of the reference's `Profiler::DumpProfile`
(src/profiler/profiler.cc), which serializes recorded ranges to the chrome
trace-event JSON format. Here spans are recorded host-side into a bounded
ring buffer (the device timeline belongs to `jax.profiler`'s XPlane dump;
these spans cover what XLA cannot see: trace/compile time, step cadence,
kvstore calls, forced syncs) and exported as complete-duration ("ph": "X")
trace events, counters appended as chrome counter ("ph": "C") samples.

Load the dump at chrome://tracing or https://ui.perfetto.dev.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque

__all__ = ["TraceBuffer", "write_chrome_trace", "write_merged_chrome_trace"]

MAX_EVENTS = 100000


class TraceBuffer:
    """Bounded ring of (name, cat, ts_s, dur_s, tid) span records."""

    def __init__(self, maxlen=MAX_EVENTS):
        self._events = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        # one session epoch so ts stays small and monotonic across threads;
        # the wall-clock stamp of the SAME instant anchors this rank's spans
        # on the fleet-shared clock (merged multi-rank dumps shift each
        # rank's events by its epoch offset)
        self.epoch = time.perf_counter()
        self.epoch_unix = time.time()

    def now(self):
        return time.perf_counter() - self.epoch

    def add(self, name, cat, ts_s, dur_s, tid=None):
        """Append one span. `tid` defaults to the recording thread's ident
        (chrome renders one row per tid); callers with their own row
        semantics — per-request trace rows — pass an explicit id."""
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            self._events.append((name, cat, ts_s, dur_s, tid))

    def events(self):
        with self._lock:
            return list(self._events)

    def tail(self, n):
        """The newest `n` spans (oldest first) without copying the whole
        ring — the per-step attribution pass runs on every step_event and
        must not pay O(ring) on a 100k-event buffer."""
        with self._lock:
            if n >= len(self._events):
                return list(self._events)
            it = reversed(self._events)
            out = [next(it) for _ in range(n)]
        out.reverse()
        return out

    def clear(self):
        with self._lock:
            self._events.clear()

    def __len__(self):
        return len(self._events)


def write_chrome_trace(path, buffer, registry=None, process_name="mxnet_tpu",
                       rank=0, trace_id=None):
    """Serialize the span buffer (+ current counter values) to a
    chrome://tracing-loadable JSON file; returns the event count.

    Every event is stamped with this worker's rank (as the chrome `pid`,
    so each rank renders as its own process row) and the run-wide trace id
    travels in the payload metadata — a dump from any rank names the run
    it belongs to, and N per-rank dumps are mergeable after the fact."""
    rank = int(rank or 0)
    events = [{"name": "process_name", "ph": "M", "pid": rank, "tid": 0,
               "args": {"name": "%s rank %d" % (process_name, rank)}}]
    last_ts = 0.0
    for name, cat, ts_s, dur_s, tid in buffer.events():
        ts_us = ts_s * 1e6
        events.append({"name": name, "cat": cat, "ph": "X",
                       "ts": ts_us, "dur": dur_s * 1e6,
                       "pid": rank, "tid": tid})
        last_ts = max(last_ts, ts_us)
    if registry is not None:
        counters = registry.snapshot()["counters"]
        for name, value in counters.items():
            events.append({"name": name, "cat": "counter", "ph": "C",
                           "ts": last_ts, "pid": rank,
                           "args": {"value": value}})
    payload = {"traceEvents": events, "displayTimeUnit": "ms",
               "metadata": {"rank": rank, "trace_id": trace_id,
                            "epoch_unix": buffer.epoch_unix}}
    with open(path, "w") as f:
        json.dump(payload, f)
    return len(events)


def write_merged_chrome_trace(path, rank_dumps, registry=None,
                              process_name="mxnet_tpu", local_rank=0):
    """Serialize per-rank trace dumps (`[{rank, epoch_unix, trace_id,
    events}]`, the `aggregate_trace()` shape) into ONE chrome trace: one
    process row per rank, every rank's spans shifted onto a shared clock.

    Each rank's span timestamps are on its own perf_counter epoch; the
    wall-clock stamp of that epoch (`epoch_unix`) re-bases them all onto
    the earliest rank's epoch, so cross-rank overlap (e.g. the comm-bucket
    collectives of a lock-stepped fleet) lines up to wall-clock skew, not
    to nothing. Returns the event count."""
    rank_dumps = sorted(rank_dumps, key=lambda d: int(d.get("rank", 0)))
    if not rank_dumps:
        raise ValueError("write_merged_chrome_trace: no rank dumps")
    # clock base over the dumps that carry an anchor; a dump WITHOUT one
    # (out-of-band, pre-v2) merges unshifted instead of throwing every
    # anchored rank ~epoch-seconds off the timeline
    anchors = [float(d["epoch_unix"]) for d in rank_dumps
               if d.get("epoch_unix") is not None]
    base = min(anchors) if anchors else 0.0
    trace_id = rank_dumps[0].get("trace_id")
    events = []
    local_last_ts = {}
    for dump in rank_dumps:
        rank = int(dump.get("rank", 0))
        epoch = dump.get("epoch_unix")
        shift_s = (float(epoch) - base) if epoch is not None else 0.0
        events.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0,
                       "args": {"name": "%s rank %d" % (process_name, rank)}})
        for name, cat, ts_s, dur_s, tid in dump.get("events", ()):
            ts_us = (ts_s + shift_s) * 1e6
            events.append({"name": name, "cat": cat, "ph": "X",
                           "ts": ts_us, "dur": dur_s * 1e6,
                           "pid": rank, "tid": tid,
                           "args": {"rank": rank}})
            local_last_ts[rank] = max(local_last_ts.get(rank, 0.0), ts_us)
    if registry is not None:
        # counters are per-process state: attach the LOCAL registry's values
        # to the local rank's row (each rank's merged dump carries its own)
        local_rank = int(local_rank or 0)
        ts = local_last_ts.get(local_rank, 0.0)
        for name, value in registry.snapshot()["counters"].items():
            events.append({"name": name, "cat": "counter", "ph": "C",
                           "ts": ts, "pid": local_rank,
                           "args": {"value": value}})
    payload = {"traceEvents": events, "displayTimeUnit": "ms",
               "metadata": {"trace_id": trace_id, "merged": True,
                            "ranks": [int(d.get("rank", 0))
                                      for d in rank_dumps]}}
    with open(path, "w") as f:
        json.dump(payload, f)
    return len(events)

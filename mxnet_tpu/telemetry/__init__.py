"""Runtime telemetry: counters, gauges, histograms, chrome-trace spans.

The observability spine of the framework (ROADMAP: every perf/robustness PR
reports through it). Instrumented hot paths:

* `gluon.CachedOp` — `cachedop.cache_hit` / `cachedop.cache_miss` /
  `cachedop.compile` / `cachedop.retrace` counters plus a
  `cachedop.compile_ms` histogram and one span per (re)trace, so silent
  recompiles become visible;
* `nd.invoke` — `ndarray.invoke` dispatch counter, and the forced
  device→host syncs `ndarray.sync.asnumpy` / `ndarray.sync.wait_to_read`
  (the classic hidden stall under async PjRt dispatch);
* `kvstore` — `kvstore.push_calls` / `pull_calls` and payload
  `push_bytes` / `pull_bytes`;
* bucketed comm engine (`mx.engine`) — `comm.collectives` (launched comm
  programs: per bucket when bucketing, per key on the escape hatch),
  `comm.bucket.count` / `comm.bucket.bytes` /
  `comm.bucket.flush_reason.{full,dtype_split,oversize,final}` /
  `comm.bucket.skipped`, plus one `comm.bucket[k0..kN]` span per launch
  (cat `comm`) so overlap is visible in chrome-trace dumps;
* dataloader — `dataloader.batchify.syncs_saved` (device→host syncs
  avoided by the batched collate);
* train steps — `trainer.step_ms`, `fused_step.step_ms`,
  `train_step.step_ms` histograms + compile counters. The last two time
  the host's dispatch (the call returns while the device runs on), not a
  step. One cat-`step` span per call (`fused_step`, `train_step`) and,
  inside it, cat-`phase` spans: `fused_step.gather` / `.stage` (with a
  mesh) / `.launch` / `.write_back`, `train_step.launch`; what the parent
  holds beyond them is its self time;
* jit — `jit.trace:<fn>` / `jit.lower:<fn>` / `jit.xla:<fn>` spans (cat
  `jit`) from jax's own duration events, one set per program built;
  `jit.xla` is a compile or a restore from the persistent cache;
* memory — best-effort `memory.*.bytes_in_use` watermark gauges from the
  PjRt allocator (memory.py);
* the compiled step's scopes — under a profiler session both train steps
  call `note_step_program`, which reads the loaded step's optimized module
  once, on a thread of its own, when the session is over;
  `module_scopes()` then maps every instruction of the step to its opcode,
  its `op_name` (the `jax.named_scope`s it was traced under) and its place
  in the module, which is what tells a device trace's `fusion.183` that it
  is the backward of the `ffn` block (hlo_scopes.py). Without a session a
  step pays one `is_enabled()` call.

One clock with the profiler: `span()` also enters a
`jax.profiler.TraceAnnotation`, so under any profiler session
(`capture_profile`, `mx.profiler`, xprof, a benchmark's `--trace 1`) the
program's spans are host events of the same `.xplane.pb` as the device's
operations. A span's parent is the innermost span that covers it on the
same thread. `span_epoch()` puts ring times on `time.perf_counter()`.

Gating: `MXNET_TPU_TELEMETRY=0` (env) or `telemetry.disable()` turns every
instrumented path into a single global-bool check — no locks, no dict
lookups, no allocation. Default is enabled (counters are cheap; spans are
bounded by a ring buffer).

Export: `snapshot()` (dict), `dumps(format='table'|'json')`,
`dump(path)` (JSON), and `dump_trace(path)` — a chrome://tracing-loadable
host-side trace, the analog of the reference's `Profiler::DumpProfile`.
`mx.profiler.dumps()` also embeds the counter snapshot, so the existing
profiler API surfaces telemetry.

Telemetry v2 — the LIVE observability plane on top of the registry:

* `telemetry.export` — a Prometheus `/metrics`+`/snapshot` HTTP endpoint
  (`MXNET_TPU_METRICS_PORT`) and a periodic JSONL snapshot streamer
  (`MXNET_TPU_METRICS_STREAM`), both off by default and fully inert when
  telemetry is disabled; `tools/mxtop.py` is the matching dashboard;
* cross-rank correlation — every chrome-trace dump is stamped with this
  worker's rank and a run-wide `trace_id()`; `aggregate_trace()` exchanges
  span events fleet-wide and `dump_trace(merged=True)` writes ONE trace
  with a process row per rank on a shared clock;
* `telemetry.flight` — a crash flight recorder: bounded ring of per-step
  records (step ms, comm deltas, compiles/retrace reasons, anomalies,
  resilience events), embedded in watchdog post-mortems and auto-dumped
  on fatal resilience errors / unhandled exceptions;
* `telemetry.anomaly` — rolling-median step-time spike + SLO detection
  (`telemetry.anomaly.*` counters, `anomaly@<site>` marker spans) and the
  rolling p50/p99 step-latency quantiles the exporter and bench rows
  report. `step_event(site, ms)` is the one call the instrumented step
  paths make to feed both.

Observability v3 — the per-request / per-step / per-fleet evidence layer:

* `telemetry.request_trace` — a `RequestTrace` travels with every
  `mx.serve` request (enqueue → admit → prefill → each decode step →
  completion/shed/recovery), its spans tiling the request's wall clock;
  completed traces land in a bounded ring (`/requests` endpoint,
  `request_traces()`, `parse_log --requests`) and replay into the chrome
  dump as one row per request;
* `telemetry.attribution` — per-step compute/collective/host/idle
  decomposition + comm overlap fraction from the spans the runtime
  already records (`overlap_report()`, `parse_log --overlap`,
  per-step `attrib` flight records, `attrib.<site>.*` gauges) — the
  measured-evidence input of ROADMAP item #4's schedule autotuner;
* `telemetry.federation` — rank 0's exporter proxies the WHOLE fleet
  (`/fleet/metrics`, `/fleet/snapshot`): out-of-band per-peer scrapes
  merged with the same host-side merge `aggregate_snapshot` uses,
  stale-rank tolerant (`telemetry.federation.stale_ranks`).
"""
from __future__ import annotations

import _thread
import json
import logging
import os
import threading
import time
import uuid
from contextlib import contextmanager

import jax.monitoring
from jax.profiler import TraceAnnotation

from .metrics import Counter, Gauge, Histogram, Registry
from .trace import (TraceBuffer, write_chrome_trace,
                    write_merged_chrome_trace)
from . import memory as _memory

_LOG = logging.getLogger("mxnet_tpu.telemetry")

__all__ = ["enabled", "enable", "disable", "registry", "counter", "gauge",
           "histogram", "inc", "set_gauge", "observe", "span", "step_span",
           "record_span",
           "snapshot", "compile_report", "reset", "dumps", "dump",
           "dump_trace", "span_events", "span_clock", "span_epoch",
           "aggregate_snapshot", "merge_snapshots", "aggregate_trace",
           "sample_memory", "maybe_sample_memory",
           "note_compile", "recent_compiles", "device_report",
           "note_step_program", "module_scopes",
           "trace_id", "set_trace_id", "safe_rank", "local_trace_dump",
           "step_event", "step_quantiles", "flight_records",
           "request_traces", "overlap_report",
           "memory_scopes", "memory_programs", "capture_profile",
           "Counter", "Gauge", "Histogram", "Registry"]

# the ONLY state instrumented code reads on the disabled fast path
ENABLED = os.environ.get("MXNET_TPU_TELEMETRY", "1").lower() not in (
    "0", "false", "off")

registry = Registry()
_trace = TraceBuffer()


def enabled():
    return ENABLED


def enable():
    """Turn telemetry on at runtime. Also (re-)checks the live-export env
    knobs: a process that started under MXNET_TPU_TELEMETRY=0 with
    MXNET_TPU_METRICS_PORT set gets its endpoint the moment telemetry is
    switched on, not never."""
    global ENABLED
    ENABLED = True
    _listen_to_jit()
    from . import export as _export
    _export.maybe_start_from_env()


def disable():
    global ENABLED
    ENABLED = False


# ---------------------------------------------------------------- metrics API
def counter(name):
    return registry.counter(name)


def gauge(name):
    return registry.gauge(name)


def histogram(name, bounds=None):
    return registry.histogram(name, bounds)


def inc(name, n=1):
    """Increment a counter; no-op (and no metric created) when disabled."""
    if not ENABLED:
        return 0
    return registry.counter(name).inc(n)


def set_gauge(name, value):
    if not ENABLED:
        return
    registry.gauge(name).set(value)


def observe(name, value):
    if not ENABLED:
        return
    registry.histogram(name).observe(value)


# ---------------------------------------------------------------- span API
class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One span on both clocks: a `TraceAnnotation` for its lifetime, so a
    profiler session that is running sees it among the host's events, and a
    tuple in the ring when it ends. `dur` is its length in seconds once it
    has ended."""
    __slots__ = ("name", "cat", "dur", "_t0", "_annotation")

    def __init__(self, name, cat):
        self.name = name
        self.cat = cat
        self.dur = None

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = _trace.now()
        return self

    def __exit__(self, *exc):
        self.dur = _trace.now() - self._t0
        self._annotation.__exit__(*exc)
        _trace.add(self.name, self.cat, self._t0, self.dur)
        return False


class _StepSpan(_Span):
    """The span of one call of a train step, which feeds what reads a
    step's length when it ends: the `<site>.step_ms` histogram, the memory
    gauges, and `step_event` (anomaly detection, attribution, the flight
    recorder)."""
    __slots__ = ()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        ms = self.dur * 1e3
        observe(self.name + ".step_ms", ms)
        maybe_sample_memory()
        step_event(self.name, ms)
        return False


def step_span(site):
    """`span(site, "step")` around one call of a train step. Under async
    dispatch it times the host's dispatch, not the step: the call returns
    while the device runs on (a call that compiles holds the compile)."""
    if not ENABLED:
        return _NULL_SPAN
    return _StepSpan(site, "step")


def span(name, cat="host"):
    """Context manager recording one span (chrome-trace ph:'X') in the ring
    and, under a profiler session, in the profiler's trace. A span's parent
    is the innermost span that covers it on the same thread."""
    if not ENABLED:
        return _NULL_SPAN
    return _Span(name, cat)


def record_span(name, cat, start_s, dur_s, tid=None):
    """Record an already-timed range. start_s is on the buffer's own
    perf_counter epoch — pair with `span_clock()`. `tid` overrides the
    chrome row (default: the recording thread) — per-request trace rows
    use it."""
    if not ENABLED:
        return
    _trace.add(name, cat, start_s, dur_s, tid=tid)


def span_clock():
    """Current timestamp on the trace buffer's epoch (seconds)."""
    return _trace.now()


def span_epoch():
    """The `time.perf_counter()` value of the ring's zero: a span's `ts_s`
    plus this is on the clock a caller's own `perf_counter` reads are on."""
    return _trace.epoch


def span_events(limit=None):
    """Recorded spans as (name, cat, ts_s, dur_s, tid) tuples, oldest first;
    `limit` keeps only the newest N. The resilience watchdog embeds this
    tail in `StallError` so a hang post-mortem starts with data."""
    events = _trace.events()
    if limit is not None and len(events) > limit:
        events = events[-limit:]
    return events


# ---------------------------------------------------------------- jit's times
# jax times the three parts of every compile itself and reports each with the
# function's name; as spans they say what a first call's seconds were, and
# which program compiled where. `jit.xla` holds a restore from the persistent
# cache as well as a compile.
_JIT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.xla",
}


def _on_jit_duration(event, duration, fun_name=None, **_kw):
    name = _JIT_SPANS.get(event)
    if name is None or not ENABLED:
        return
    if fun_name:
        name = "%s:%s" % (name, fun_name)
    _trace.add(name, "jit", _trace.now() - duration, duration)


_jit_listening = False


def _listen_to_jit():
    """Register the listener, once in the life of the process."""
    global _jit_listening
    if not _jit_listening:
        _jit_listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)


# ---------------------------------------------------------------- identity
# the run-wide trace id every span dump / flight dump / stream line carries.
# MXNET_TPU_TRACE_ID pins it fleet-wide from the launcher; otherwise each
# process draws its own and `aggregate_trace()` unifies on rank 0's at the
# first collective exchange.
_RUN_LOCK = threading.Lock()
_RUN = {"trace_id": os.environ.get("MXNET_TPU_TRACE_ID") or None}


def trace_id():
    """The run-wide trace id (lazily drawn; stable for the process life)."""
    with _RUN_LOCK:
        if _RUN["trace_id"] is None:
            _RUN["trace_id"] = uuid.uuid4().hex[:16]
        return _RUN["trace_id"]


def set_trace_id(value):
    """Adopt a trace id (rank 0's, via `aggregate_trace`; or an external
    orchestrator's)."""
    with _RUN_LOCK:
        _RUN["trace_id"] = str(value)


def safe_rank():
    """This worker's rank WITHOUT triggering backend init: the dist state
    when rendezvoused, the launcher env otherwise. (dist.rank() falls back
    to jax.process_index(), which would initialize the platform — too heavy
    for a metrics scrape or an import-time exporter.)"""
    try:
        from ..parallel.dist import _STATE
        if _STATE.get("initialized"):
            return int(_STATE["rank"])
    except Exception:  # noqa: BLE001 - identity is best-effort
        pass
    try:
        return int(os.environ.get("DMLC_WORKER_ID", "0") or 0)
    except (TypeError, ValueError):
        return 0


# ---------------------------------------------------------------- compiles
# ring of the most recent compiled executables (name, epoch-relative ts) —
# a stall post-mortem wants "what did we last hand the device", not just a
# compile *count*. Bounded; guarded by its own lock (the compile paths run
# on whatever thread dispatched).
_COMPILE_RING_LIMIT = 32
_compiles = []
_compiles_lock = threading.Lock()


def note_compile(name):
    """Record that executable `name` was just (re)compiled — called by
    CachedOp / FusedTrainStep / ShardedTrainStep next to their `*.compile`
    counters; surfaces in `recent_compiles()` and stall post-mortems."""
    if not ENABLED:
        return
    ts = _trace.now()
    with _compiles_lock:
        _compiles.append((str(name), ts))
        if len(_compiles) > _COMPILE_RING_LIMIT:
            del _compiles[:-_COMPILE_RING_LIMIT]


def recent_compiles(limit=None):
    """The newest compiled executables as (name, ts_s) tuples, oldest
    first."""
    with _compiles_lock:
        events = list(_compiles)
    if limit is not None and len(events) > limit:
        events = events[-limit:]
    return events


# ---------------------------------------------------------------- scopes
# A profiler session's trace names instructions; the module that a step
# compiled says under which scopes each was traced. {module name:
# _StepScopes}, the newest last.
_SCOPES_LIMIT = 4
_SESSION_POLL_S = 0.2   # how often a waiting worker asks if the session is on
_scopes = {}
_scopes_lock = threading.Lock()
_session_on = getattr(TraceAnnotation, "is_enabled", lambda: False)


class _StepScopes:
    """A step program's scope map on its way: a worker reads it once the
    session is over and sets `done`; `scopes` is what it read, None where
    it could not (the log says why)."""
    __slots__ = ("done", "scopes")

    def __init__(self):
        self.done = threading.Event()
        self.scopes = None


def _step_module(module_name):
    """The optimized HLO module of the newest loaded program of that name
    (the runtime lists the newest first), or None. The runtime's list and
    the executable are this call's alone, gone when it returns: a kept
    reference would keep the step's reserved temporaries loaded after the
    step is dropped."""
    for executable in jax.devices()[0].client.live_executables():
        module = executable.hlo_modules()[0]
        if module.name == module_name:
            return module
    return None


def _read_scopes(module_name, entry):
    """The worker. It sleeps through the session: the runtime takes seconds
    to hand over a step's optimized module (2.6 s for BERT-base's, 4.7 s
    for Qwen3-Next's on a v5e, PERF.md), and a window that dispatches steps
    meanwhile reads 1-2 ms a call longer. Then it takes the module, which
    holds nothing of the executable, and parses its text. A step that was
    dropped before the worker woke has no map, and the log says so."""
    try:
        while _session_on():
            time.sleep(_SESSION_POLL_S)
        if _scopes.get(module_name) is not entry:   # rebuilt or reset
            return
        # not before: the import takes the interpreter 3 ms, which a thread
        # that dispatches a window's first steps would wait for
        from . import hlo_scopes
        module = _step_module(module_name)
        if module is None:
            _LOG.warning("no scope map of %s: no loaded program bears the "
                         "name", module_name)
        else:
            entry.scopes = hlo_scopes.parse(module.to_string())
    except Exception:  # noqa: BLE001 - a record, never a failed step
        _LOG.warning("no scope map of %s", module_name, exc_info=True)
    finally:
        entry.done.set()


def note_step_program(module_name, rebuilt=False):
    """Called by a train step after it launched the program whose module
    is `module_name` (`jit_step_fn`, `jit_run`); `rebuilt` where that call
    built or restored it. Under a profiler session, and only there, the
    first such call starts the thread that reads the loaded program's scope
    map for `module_scopes()` when the session ends; a rebuilt program's
    is read again. Without a session (`TraceAnnotation.is_enabled()`, false
    on a jax that lacks it) nothing is read: a rebuilt program only forgets
    its map."""
    if not ENABLED:
        return
    if rebuilt:
        with _scopes_lock:
            _scopes.pop(module_name, None)
    if not _session_on() or module_name in _scopes:
        return
    with _scopes_lock:
        if module_name in _scopes:
            return
        entry = _scopes[module_name] = _StepScopes()
        for stale in list(_scopes)[:-_SCOPES_LIMIT]:
            del _scopes[stale]
    # the low-level start returns at once; `Thread.start()` waits for the
    # new thread to run, about a millisecond of the call that dispatches
    try:
        _thread.start_new_thread(_read_scopes, (module_name, entry))
    except RuntimeError:    # no thread to be had: no map, and nobody waits
        _LOG.warning("no scope map of %s", module_name, exc_info=True)
        entry.done.set()


def module_scopes():
    """{module name: {instruction: Instr(opcode, op_name, computation,
    calls)}} (`hlo_scopes.parse`) of the step programs that ran under a
    profiler session, the newest four. It outlives the step (nothing of the
    executable is kept) and is cleared by `reset()`. Ask after the session:
    a map whose worker is at its read is waited for, seconds for a large
    step, and while a session is on, the programs it saw have none yet."""
    with _scopes_lock:
        entries = dict(_scopes)
    if not _session_on():
        for entry in entries.values():
            entry.done.wait()
    return {name: entry.scopes for name, entry in entries.items()
            if entry.scopes is not None}


# ---------------------------------------------------------------- memory
def device_report():
    """Best-effort per-device PjRt state (allocator stats + live-buffer
    attribution) for post-mortems — see telemetry.memory.device_report."""
    return _memory.device_report()


def sample_memory():
    """Force one device-memory gauge sample; returns #devices reporting."""
    if not ENABLED:
        return 0
    return _memory.sample(registry)


def maybe_sample_memory():
    """Rate-limited sample for per-step call sites."""
    if not ENABLED:
        return 0
    return _memory.maybe_sample(registry)


def memory_scopes():
    """The HBM ledger's {scope: bytes} snapshot (params / optimizer /
    grad_buckets / kv pools / programs / unattributed — see
    telemetry/ledger.py); {} when the ledger is disabled."""
    from . import ledger as _ledger
    return _ledger.scopes()


def memory_programs():
    """Recorded per-executable static footprints
    (`compiled.memory_analysis()` harvested at compile/AOT-restore time);
    [] when the ledger is disabled."""
    from . import ledger as _ledger
    return _ledger.programs()


def capture_profile(ms=None, dir=None):     # noqa: A002 - knob name
    """Capture one on-demand profiling window (rate-limited; see
    telemetry/profiling.py). Returns the trace path or None."""
    from . import profiling as _profiling
    return _profiling.capture_profile(ms=ms, dir=dir)


# ---------------------------------------------------------------- export
def snapshot():
    return registry.snapshot()


def compile_report():
    """Metric snapshot + the recent-compiles ring as ONE json-able dict —
    the input `tools/parse_log.py --compile` tabulates (compiler/cache
    counters, lower/compile latency, fallbacks by reason, and WHICH
    executables were built, tagged [cached] vs fresh)."""
    report = snapshot()
    report["recent_compiles"] = [[name, round(ts, 6)]
                                 for name, ts in recent_compiles()]
    return report


def reset():
    """Drop all metrics, recorded spans, the compile ring, the steps' scope
    maps, the flight recorder, the request-trace ring, the anomaly windows,
    the memory ledger, and the profiling state (does not change ENABLED)."""
    registry.reset()
    _trace.clear()
    with _compiles_lock:
        del _compiles[:]
    with _scopes_lock:
        _scopes.clear()
    from . import anomaly as _anomaly, flight as _flight
    from . import ledger as _ledger, profiling as _profiling
    from . import request_trace as _reqtrace
    _anomaly.reset()
    _flight.reset()
    _reqtrace.reset()
    _ledger.reset()
    _profiling.reset()


def dumps(format="table"):
    return registry.dumps(format=format)


def dump(path, format="json"):
    """Write the metric snapshot to `path` (json/table)."""
    with open(path, "w") as f:
        f.write(registry.dumps(format=format))
    return path


def dump_trace(path=None, merged=False):
    """Write recorded spans + counters as chrome://tracing JSON, stamped
    with this worker's rank and the run trace id. Default path:
    telemetry_trace.json in the cwd. Returns the path.

    merged=True exchanges span events fleet-wide first (`aggregate_trace`
    — collective: every worker must call it in lockstep) and writes ONE
    trace with a process row per rank on a shared wall-clock base, so
    cross-rank overlap (comm buckets vs compute) is visible in a single
    chrome://tracing load. Single-process merged dumps are local-only."""
    if path is None:
        path = "telemetry_trace.json"
    if merged:
        dumps_by_rank = aggregate_trace()
        write_merged_chrome_trace(path, dumps_by_rank, registry,
                                  local_rank=safe_rank())
    else:
        write_chrome_trace(path, _trace, registry, rank=safe_rank(),
                           trace_id=trace_id())
    return path


def local_trace_dump():
    """This worker's span events + identity — the per-rank unit
    `aggregate_trace` exchanges."""
    return {"rank": safe_rank(), "trace_id": trace_id(),
            "epoch_unix": _trace.epoch_unix,
            "events": [list(e) for e in _trace.events()]}


def aggregate_trace(dump=None):
    """Fleet-wide span-event exchange (collective — lockstep like
    `aggregate_snapshot`); returns `[{rank, trace_id, epoch_unix, events}]`
    sorted by rank. See telemetry/aggregate.py."""
    from .aggregate import aggregate_trace as _agg
    return _agg(dump)


# ---------------------------------------------------------------- step plane
def step_event(site, dur_ms, info=None):
    """One call per training/serving step from the instrumented step paths
    (`trainer` / `fused_step` / `train_step` / `serve.step`): runs anomaly
    detection over the duration, attributes the step window
    (compute/collective/host/idle + overlap — telemetry.attribution), and
    appends a flight-recorder record with this step's counter deltas.
    `info` (a small JSON-able dict — e.g. the serving scheduler's
    active/completed request ids) rides into the flight record verbatim.
    No-op when disabled."""
    if not ENABLED:
        return
    from . import anomaly as _anomaly, attribution as _attrib
    from . import flight as _flight, ledger as _ledger
    fired = _anomaly.observe(site, dur_ms)
    extras = dict(info) if info else {}
    attrib = _attrib.step_attribution(site, dur_ms, _trace)
    if attrib is not None:
        extras["attrib"] = attrib
    _flight.record_step(site, dur_ms, anomalies=fired,
                        extras=extras or None)
    # per-step ledger reconcile (rate-limited inside): the unattributed
    # residual tracks the run, not just its post-mortem
    _ledger.maybe_reconcile()


def step_quantiles(site=None):
    """Rolling p50/p99 step-latency quantiles: one site's dict, or
    {site: dict} for all sites when `site` is None."""
    from . import anomaly as _anomaly
    if site is not None:
        return _anomaly.quantiles(site)
    return _anomaly.quantiles_all()


def flight_records(limit=None):
    """The flight recorder's step records, oldest first (see
    telemetry/flight.py); the watchdog embeds the tail in `StallError`."""
    from . import flight as _flight
    return _flight.records(limit=limit)


def request_traces(limit=None):
    """Completed per-request trace payloads, oldest first — the last-N
    ring `mx.serve` feeds and the `/requests` endpoint serves (see
    telemetry/request_trace.py)."""
    from . import request_trace as _reqtrace
    return _reqtrace.records(limit=limit)


def overlap_report(events=None, site=None, limit=None):
    """Per-step compute/collective/host/idle decomposition + comm overlap
    fraction from recorded spans (see telemetry/attribution.py) — the
    measured evidence the comm-schedule autotuner consumes and
    `parse_log --overlap` tabulates."""
    from . import attribution as _attrib
    return _attrib.overlap_report(events=events, site=site, limit=limit)


def aggregate_snapshot(snapshot=None):
    """Fleet-wide snapshot: this worker's (or `snapshot`) merged with every
    other worker's over one DCN allgather — counters sum, gauge watermarks
    take the fleet max, histograms merge bucket-wise. Collective on
    multi-worker runtimes; local-only (and cheap) on one process. See
    telemetry/aggregate.py."""
    from .aggregate import aggregate_snapshot as _agg
    return _agg(snapshot)


def merge_snapshots(snaps):
    """Pure merge of snapshot dicts (the host-side half of
    `aggregate_snapshot`) — usable on dumps collected out-of-band."""
    from .aggregate import merge_snapshots as _merge
    return _merge(snaps)


# ------------------------------------------------------------- live export
# start whatever live transports the env configures (MXNET_TPU_METRICS_PORT
# endpoint / MXNET_TPU_METRICS_STREAM JSONL). Both default OFF; when
# telemetry is disabled this is a pure no-op — no thread, no port — which
# tests assert. Import order matters: `export` reads this module's ENABLED
# and registry, both defined above.
from . import export  # noqa: E402  (needs ENABLED/registry above)

export.maybe_start_from_env()
if ENABLED:
    _listen_to_jit()

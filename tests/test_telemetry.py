"""Runtime telemetry subsystem tests.

Covers the registry semantics (counter/gauge/histogram, thread safety,
type conflicts), the instrumented hot paths (CachedOp JIT-cache metrics,
kvstore comm bytes, train-step histograms, sync counters), disabled-mode
no-op behavior, chrome-trace export structure, the profiler integration,
and the pause/resume + Scope + dumps-format profiler satellites.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts from an empty, enabled registry and leaves the
    global state the way the rest of the suite expects it."""
    was_enabled = telemetry.ENABLED
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.reset()
    (telemetry.enable if was_enabled else telemetry.disable)()


# ---------------------------------------------------------------- registry
def test_counter_semantics():
    c = telemetry.counter("t.calls")
    assert c.value == 0
    c.inc()
    c.inc(41)
    assert c.value == 42
    assert telemetry.snapshot()["counters"]["t.calls"] == 42
    # module-level convenience targets the same metric
    telemetry.inc("t.calls", 8)
    assert c.value == 50


def test_gauge_watermark():
    telemetry.set_gauge("t.mem", 100)
    telemetry.set_gauge("t.mem", 40)
    g = telemetry.snapshot()["gauges"]["t.mem"]
    assert g["value"] == 40
    assert g["max"] == 100


def test_histogram_semantics():
    for v in (0.5, 1.5, 1000.0):
        telemetry.observe("t.lat_ms", v)
    h = telemetry.snapshot()["histograms"]["t.lat_ms"]
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(1002.0)
    assert h["min"] == 0.5 and h["max"] == 1000.0
    assert h["avg"] == pytest.approx(334.0)
    assert sum(h["buckets"].values()) == 3


def test_registry_type_conflict():
    telemetry.counter("t.dual")
    with pytest.raises(TypeError):
        telemetry.gauge("t.dual")


def test_counter_thread_safety():
    c = telemetry.counter("t.mt")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_dumps_formats():
    telemetry.inc("t.one", 3)
    telemetry.observe("t.h", 2.0)
    table = telemetry.dumps()
    assert "t.one" in table and "t.h" in table
    js = json.loads(telemetry.dumps(format="json"))
    assert js["counters"]["t.one"] == 3
    with pytest.raises(ValueError):
        telemetry.dumps(format="xml")


# ---------------------------------------------------------------- disabled
def test_disabled_mode_is_noop():
    telemetry.disable()
    # every instrumented path: dispatch, sync, hybridized forward,
    # kvstore push/pull, trainer step
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.rand(2, 3).astype(np.float32))
    y = nd.array(np.random.rand(2, 4).astype(np.float32))
    net(x).asnumpy()
    kv = mx.kv.create("local")
    kv.init(0, nd.zeros((4,)))
    kv.push(0, nd.ones((4,)))
    out = nd.zeros((4,))
    kv.pull(0, out=out)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with autograd.record():
        loss = nd.mean(nd.square(net(x) - y))
    loss.backward()
    trainer.step(1)
    with telemetry.span("user.range"):
        pass
    snap = telemetry.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    telemetry.dump_trace("/tmp/_telem_disabled_trace.json")
    events = json.load(open("/tmp/_telem_disabled_trace.json"))["traceEvents"]
    assert all(e["ph"] != "X" for e in events)  # no spans recorded


# ---------------------------------------------------------------- CachedOp
def test_cachedop_cache_metrics():
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.rand(2, 3).astype(np.float32))
    net(x)   # first call: miss + compile
    c = telemetry.snapshot()["counters"]
    assert c["cachedop.cache_miss"] == 1
    assert c["cachedop.compile"] == 1
    assert "cachedop.cache_hit" not in c
    net(x)   # same signature: hit
    net(x)
    c = telemetry.snapshot()["counters"]
    assert c["cachedop.compile"] == 1
    assert c["cachedop.cache_hit"] == 2
    h = telemetry.snapshot()["histograms"]["cachedop.compile_ms"]
    assert h["count"] == 1 and h["sum"] > 0

    # a new input shape is a retrace — the silent recompile made visible
    x2 = nd.array(np.random.rand(5, 3).astype(np.float32))
    net(x2)
    c = telemetry.snapshot()["counters"]
    assert c["cachedop.compile"] == 2
    assert c["cachedop.retrace"] == 1


# ---------------------------------------------------------------- kvstore
def test_kvstore_push_pull_byte_counters():
    kv = mx.kv.create("local")
    kv.init("w", nd.zeros((8, 4)))
    kv.push("w", nd.ones((8, 4)))                 # 128 f32 bytes
    out = nd.zeros((8, 4))
    kv.pull("w", out=out)
    c = telemetry.snapshot()["counters"]
    assert c["kvstore.push_calls"] == 1
    assert c["kvstore.pull_calls"] == 1
    assert c["kvstore.push_bytes"] == 8 * 4 * 4
    assert c["kvstore.pull_bytes"] == 8 * 4 * 4
    # multi-replica push counts the full wire payload
    kv.push("w", [nd.ones((8, 4)), nd.ones((8, 4))])
    c = telemetry.snapshot()["counters"]
    assert c["kvstore.push_bytes"] == 3 * 8 * 4 * 4


# ---------------------------------------------------------------- trace
def test_chrome_trace_structure(tmp_path):
    with telemetry.span("outer", "user"):
        with telemetry.span("inner", "user"):
            pass
    telemetry.inc("t.count", 7)
    path = str(tmp_path / "trace.json")
    assert telemetry.dump_trace(path) == path
    trace = json.load(open(path))
    events = trace["traceEvents"]
    assert isinstance(events, list)
    spans = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in spans} >= {"outer", "inner"}
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0 and "tid" in e
    counters = [e for e in events if e["ph"] == "C"]
    assert any(e["name"] == "t.count" and e["args"]["value"] == 7
               for e in counters)


# ------------------------------------------------------------ acceptance
def test_three_step_hybridized_training_loop(tmp_path):
    """ISSUE acceptance: 3-step hybridized Gluon loop → exactly 1 CachedOp
    compile + ≥2 hits per signature, nonzero step-time histogram, loadable
    chrome trace, telemetry inside profiler.dumps()."""
    mx.random.seed(0)
    net = gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(np.random.rand(8, 3).astype(np.float32))
    y = nd.array(np.random.rand(8, 4).astype(np.float32))
    for _ in range(3):
        with autograd.record():
            loss = nd.mean(nd.square(net(x) - y))
        loss.backward()
        trainer.step(8)
    snap = telemetry.snapshot()
    c = snap["counters"]
    assert c["cachedop.compile"] == 1         # one train-mode signature
    assert c["cachedop.cache_hit"] >= 2
    assert snap["histograms"]["trainer.step_ms"]["count"] == 3
    assert c["ndarray.invoke"] > 0

    path = str(tmp_path / "trace.json")
    telemetry.dump_trace(path)
    events = json.load(open(path))["traceEvents"]
    step_spans = [e for e in events
                  if e["ph"] == "X" and e["name"] == "trainer.step"]
    assert len(step_spans) == 3
    assert all(e["dur"] > 0 for e in step_spans)

    js = json.loads(mx.profiler.dumps(format="json"))
    assert js["telemetry"]["counters"]["cachedop.compile"] == 1


def test_fused_train_step_metrics():
    net = gluon.nn.Dense(2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    fused = gluon.FusedTrainStep(
        net, gluon.loss.L2Loss(), trainer)
    x = nd.array(np.random.rand(4, 3).astype(np.float32))
    y = nd.array(np.random.rand(4, 2).astype(np.float32))
    for _ in range(2):
        fused(x, y)
    snap = telemetry.snapshot()
    assert snap["counters"]["fused_step.compile"] == 1
    assert snap["histograms"]["fused_step.step_ms"]["count"] == 2


def test_sync_counters():
    a = nd.ones((4, 4))
    a.asnumpy()
    a.wait_to_read()
    c = telemetry.snapshot()["counters"]
    assert c["ndarray.sync.asnumpy"] >= 1
    assert c["ndarray.sync.wait_to_read"] >= 1


def test_memory_sampling_best_effort():
    # CPU backend usually reports no allocator stats; the call must still
    # be safe and return a count
    n = telemetry.sample_memory()
    assert isinstance(n, int) and n >= 0


# ------------------------------------------------------- profiler satellites
def test_profiler_dumps_format_validation(tmp_path):
    with pytest.raises(ValueError):
        mx.profiler.dumps(format="csv")
    mx.profiler.set_config(filename=str(tmp_path / "prof.out"))
    try:
        mx.profiler.dump(format="table")
        text = open(str(tmp_path / "prof.out")).read()
        assert text.startswith("Name")          # the human table, not JSON
        mx.profiler.dump(format="json")
        json.load(open(str(tmp_path / "prof.out")))
        with pytest.raises(ValueError):
            mx.profiler.dump(format="yaml")
    finally:
        mx.profiler.set_config(filename="profile.json")


def test_profiler_pause_resume_aggregation():
    prof = mx.profiler
    prof.reset()
    prof.set_config(profile_all=False)
    prof.set_state("run")
    try:
        nd.dot(nd.ones((4, 4)), nd.ones((4, 4))).asnumpy()
        assert "dot" in prof.dumps()
        prof.reset()
        prof.pause()
        assert prof.state() == "run"            # paused, NOT stopped
        assert prof.is_paused()
        # pause must not tear down an active device trace
        assert not prof._trace_active           # none started here...
        prof._trace_active = True
        prof.pause()
        assert prof._trace_active               # ...and pause left it alone
        prof._trace_active = False
        nd.dot(nd.ones((4, 4)), nd.ones((4, 4))).asnumpy()
        assert "dot" not in prof.dumps()        # aggregation suspended
        prof.resume()
        assert not prof.is_paused()
        nd.dot(nd.ones((4, 4)), nd.ones((4, 4))).asnumpy()
        assert "dot" in prof.dumps()
    finally:
        prof.set_state("stop")
        prof.reset()


def test_profiler_scope_reentrant_and_decorator():
    prof = mx.profiler
    prof.reset()
    s = prof.Scope("nested")
    with s:
        with s:                                  # same instance, nested
            pass
    table = prof.dumps()
    line = [ln for ln in table.splitlines() if "scope:nested" in ln][0]
    assert int(line.split()[1]) == 2             # two ranges recorded

    @prof.scope("decorated")
    def f(a, b):
        return a + b

    assert f(2, 3) == 5
    assert "scope:decorated" in prof.dumps()
    prof.reset()


# ---------------------------------------------------------------- tooling
def test_parse_log_telemetry_mode(tmp_path):
    telemetry.inc("cachedop.compile", 2)
    telemetry.set_gauge("memory.cpu0.bytes_in_use", 1024)
    telemetry.observe("trainer.step_ms", 3.5)
    dump = str(tmp_path / "telemetry.json")
    telemetry.dump(dump)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--format", "csv"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0] == "metric,kind,count,value,max"
    body = "\n".join(lines[1:])
    assert "cachedop.compile,counter,,2," in body
    assert "memory.cpu0.bytes_in_use,gauge,,1024,1024" in body
    assert "trainer.step_ms,histogram,1," in body

    # a profiler dump embedding telemetry parses the same way
    prof_dump = str(tmp_path / "profile.json")
    with open(prof_dump, "w") as f:
        f.write(mx.profiler.dumps(format="json"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         prof_dump, "--telemetry"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "cachedop.compile" in r.stdout


# ------------------------------------------- spans on the profiler's clock
PHASES = ("fused_step.gather", "fused_step.launch", "fused_step.write_back")


def _toy_fused_step():
    net = gluon.nn.Dense(2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    fused = gluon.FusedTrainStep(net, gluon.loss.L2Loss(), trainer)
    x = nd.array(np.random.rand(4, 3).astype(np.float32))
    y = nd.array(np.random.rand(4, 2).astype(np.float32))
    return fused, x, y


def _covered(parent, events):
    """The (name, start, end) of `events` that lie inside `parent`."""
    return [e for e in events if e is not parent
            and parent[1] <= e[1] and e[2] <= parent[2]]


def test_fused_step_phases_reach_the_profilers_trace(tmp_path):
    import glob

    import jax
    fused, x, y = _toy_fused_step()
    fused(x, y).asnumpy()           # compile outside the session
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("step", step_num=i):
                fused(x, y)
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    # the thread's line is found by what it holds: it is named after the
    # interpreter as it was started
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events] for line in host.lines]
    (events,) = [evs for evs in lines
                 if any(name == "fused_step" for name, _, _ in evs)]
    steps = [e for e in events if e[0] == "fused_step"]
    assert len(steps) == 3
    for step in steps:
        inside = [name for name, _, _ in _covered(step, events)]
        assert [inside.count(p) for p in PHASES] == [1, 1, 1]
        # and the program's span inside the caller's own annotation
        assert sum(e[0] == "step" and e[1] <= step[1] and step[2] <= e[2]
                   for e in events) == 1


def test_fused_step_phases_in_the_ring():
    from mxnet_tpu.telemetry import attribution
    fused, x, y = _toy_fused_step()
    for _ in range(3):
        fused(x, y)
    events = [(n, ts, ts + dur, cat, tid)
              for n, cat, ts, dur, tid in telemetry.span_events()]
    steps = [e for e in events if e[0] == "fused_step"]
    assert len(steps) == 3 and all(e[3] == "step" for e in steps)
    for step in steps:
        inside = [e for e in _covered(step, events) if e[0] in PHASES]
        assert sorted(e[0] for e in inside) == sorted(PHASES)
        assert all(e[3] == "phase" and e[4] == step[4] for e in inside)
        assert sum(e[2] - e[1] for e in inside) <= step[2] - step[1]
    # a phase is neither communication nor host overhead: the window's
    # attribution reads as it did without them
    ring = telemetry.span_events()
    _, _, ts, dur, _ = [e for e in ring if e[0] == "fused_step"][-1]
    without = [e for e in ring if e[1] != "phase"]
    assert (attribution.attribute_window(ring, ts, ts + dur)
            == attribution.attribute_window(without, ts, ts + dur))
    assert telemetry.span_epoch() == pytest.approx(
        time.perf_counter() - telemetry.span_clock(), abs=0.05)


def test_disabled_records_no_span_annotation_or_jit_time(monkeypatch):
    import jax
    made = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, *args, **kwargs):
            made.append(args)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(telemetry, "TraceAnnotation", Counted)
    with telemetry.span("seen.when.on"):
        pass
    assert made == [("seen.when.on",)]
    telemetry.reset()
    telemetry.disable()
    fused, x, y = _toy_fused_step()
    fused(x, y).asnumpy()           # traces, lowers and compiles
    jax.jit(lambda a: a * 3)(np.ones(5, np.float32))
    assert len(made) == 1
    assert telemetry.span_events() == []
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}}


def test_jit_times_are_spans_of_the_first_call_only():
    import jax

    def tripled_once(a):
        return a * 3 + 1

    jitted = jax.jit(tripled_once)
    arg = np.ones(7, np.float32)
    jitted(arg).block_until_ready()
    first = [e for e in telemetry.span_events() if e[0].startswith("jit.")]
    # jax names the function `tripled_once` when it traces it and
    # `jit(tripled_once)` from then on
    for part in ("jit.trace:", "jit.lower:", "jit.xla:"):
        (span,) = [e for e in first
                   if e[0].startswith(part) and "tripled_once" in e[0]]
        assert span[1] == "jit" and span[3] > 0
        assert span[2] + span[3] <= telemetry.span_clock()
    jitted(arg).block_until_ready()
    again = [e for e in telemetry.span_events() if e[0].startswith("jit.")]
    assert again == first


def test_sharded_train_step_counts_what_xla_built():
    import jax.numpy as jnp

    from mxnet_tpu import parallel as par
    mesh = par.local_mesh(2, axis="data")
    params = {"w": jnp.zeros((4,), jnp.bfloat16)}

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"][:, None] - y) ** 2)

    step = par.ShardedTrainStep(loss_fn, params, mesh, optimizer="sgd",
                                lr=0.05, donate=False)
    p, s = step.init()
    batch = (jnp.ones((8, 4)), jnp.ones((8, 1)))
    counters = lambda: telemetry.snapshot()["counters"]  # noqa: E731
    p, s, _ = step(p, s, batch, 0)
    step(p, s, batch, 1)
    assert counters()["train_step.compile"] == 1
    # the same batch, parameters of another dtype: jit builds again, and the
    # batch's signature, which the retrace guard keys on, has not moved
    p32 = {"w": p["w"].astype(jnp.float32)}
    step(p32, s, batch, 2)
    assert counters()["train_step.compile"] == 2
    assert "train_step.retrace" not in counters()
    spans = [e for e in telemetry.span_events()
             if e[0] in ("train_step", "train_step.launch")]
    assert [e[0] for e in spans] == ["train_step.launch", "train_step"] * 3
    assert {e[1] for e in spans} == {"phase", "step"}

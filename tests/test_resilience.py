"""mxnet_tpu.resilience — fault injection, retry, watchdog, auto-resume.

Every scenario runs on one chip: the fault harness makes preemptions,
transport faults, and hangs deterministic, so the recovery paths
(in-place retry, StallError-instead-of-hang, restore-and-replay) are
ordinary unit tests. The kill-and-resume parity tests reuse the 6-step
trajectory pattern from test_fused_step.py.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, resilience as rz, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import faults, retry, watchdog
from mxnet_tpu.resilience.errors import (FatalTrainingError, InjectedFault,
                                         PreemptionError, RetryExhausted,
                                         StallError, TransportError,
                                         classify)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counter(name):
    return telemetry.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# faults: plan grammar + injection
# ---------------------------------------------------------------------------
def test_fault_plan_parse():
    plan = faults.FaultPlan.parse(
        "kvstore.push:error:1; collective.all_reduce:latency:2:0.01;"
        "run.step:preempt:3+;train.step:hang:*:0.1")
    kinds = [(s.site, s.kind) for s in plan.specs]
    assert kinds == [("kvstore.push", "error"),
                     ("collective.all_reduce", "latency"),
                     ("run.step", "preempt"), ("train.step", "hang")]
    assert plan.specs[1].arg == pytest.approx(0.01)
    assert plan.specs[2].from_nth_on and plan.specs[2].nth == 3
    assert plan.specs[3].every
    # nth matching
    assert not plan.specs[0].matches(2)
    assert plan.specs[2].matches(3) and plan.specs[2].matches(7)
    assert plan.specs[3].matches(1)


def test_fault_plan_parse_rejects_garbage():
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("justonefield")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("a:explode:1")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("a:error:0")


def test_inject_scoping_and_counts():
    before = faults.active_plan()
    with faults.inject("s:error:2") as plan:
        faults.check("s")              # call 1: clean
        with pytest.raises(InjectedFault):
            faults.check("s")          # call 2: fires
        faults.check("s")              # call 3: clean again
        assert plan.count("s") == 3
    assert faults.active_plan() is before


def test_env_fault_plan(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FAULT_PLAN", "e.site:preempt:1")
    try:
        faults.activate()
        with pytest.raises(PreemptionError):
            faults.check("e.site")
    finally:
        faults.deactivate()


def test_latency_injection_sleeps():
    with faults.inject("l.site:latency:1:0.05"):
        t0 = time.monotonic()
        faults.check("l.site")
        assert time.monotonic() - t0 >= 0.04


# ---------------------------------------------------------------------------
# error classification
# ---------------------------------------------------------------------------
def test_classify_taxonomy():
    assert classify(TransportError("x")) == "retriable"
    assert classify(PreemptionError("x")) == "retriable"
    assert classify(StallError("x")) == "retriable"
    assert classify(FatalTrainingError("x")) == "fatal"
    assert classify(ValueError("anything")) == "fatal"
    assert classify(ConnectionResetError("peer")) == "retriable"
    # message-based: grpc-ish runtime errors
    assert classify(RuntimeError("UNAVAILABLE: connection reset")) \
        == "retriable"
    assert classify(RuntimeError("DEADLINE_EXCEEDED while waiting")) \
        == "retriable"
    # fatal markers beat transient markers
    assert classify(RuntimeError(
        "INVALID_ARGUMENT: shape mismatch on connection")) == "fatal"
    assert classify(RuntimeError("no idea what happened")) == "fatal"


# ---------------------------------------------------------------------------
# retry engine
# ---------------------------------------------------------------------------
def test_retry_succeeds_after_injected_fault():
    base = _counter("resilience.retries")
    calls = {"n": 0}

    def flaky():
        faults.check("r.site")
        calls["n"] += 1
        return "ok"

    with faults.inject("r.site:error:1"):
        out = retry.call_with_retry(
            flaky, site="r.site",
            policy=retry.RetryPolicy(max_attempts=3, base_delay_s=0.001))
    assert out == "ok" and calls["n"] == 1
    assert _counter("resilience.retries") == base + 1
    assert _counter("resilience.retries.r.site") >= 1


def test_retry_fatal_propagates_first_attempt():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise ValueError("dtype mismatch")

    with pytest.raises(ValueError):
        retry.call_with_retry(fatal, site="f.site",
                              policy=retry.RetryPolicy(max_attempts=5,
                                                       base_delay_s=0.001))
    assert calls["n"] == 1


def test_retry_exhausted_carries_context():
    def always_down():
        raise TransportError("endpoint down")

    with pytest.raises(RetryExhausted) as ei:
        retry.call_with_retry(
            always_down, site="kvstore.push", context="key=7 shard=(4, 4)",
            policy=retry.RetryPolicy(max_attempts=3, base_delay_s=0.001))
    err = ei.value
    assert err.attempts == 3 and err.site == "kvstore.push"
    assert isinstance(err.last_error, TransportError)
    assert "key=7" in str(err) and "3 attempt" in str(err)
    # RetryExhausted is itself retriable at a coarser granularity
    assert classify(err) == "retriable"


def test_retry_on_filter():
    """A runner narrows in-place retry to TransportError: preemptions must
    reach its restore path un-retried."""
    calls = {"n": 0}

    def preempted():
        calls["n"] += 1
        raise PreemptionError("going away")

    with pytest.raises(PreemptionError):
        retry.call_with_retry(
            preempted, site="p",
            retry_on=lambda e: isinstance(e, TransportError),
            policy=retry.RetryPolicy(max_attempts=5, base_delay_s=0.001))
    assert calls["n"] == 1


def test_retriable_decorator_passes_kwargs_through():
    """site/policy bind at decoration; the wrapped function's own kwargs —
    even ones named like call_with_retry parameters — arrive untouched."""
    seen = {}

    @retry.retriable("deco.site",
                     policy=retry.RetryPolicy(max_attempts=2,
                                              base_delay_s=0.001))
    def fn(x, context=None, policy="user-policy"):
        seen.update(x=x, context=context, policy=policy)
        return x + 1

    assert fn(1, context="user-context") == 2
    assert seen == {"x": 1, "context": "user-context",
                    "policy": "user-policy"}


def test_retry_env_knob(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_RETRIES", "7")
    assert retry.RetryPolicy().max_attempts == 7
    monkeypatch.setenv("MXNET_TPU_RETRIES", "1")
    calls = {"n": 0}

    def down():
        calls["n"] += 1
        raise TransportError("down")

    with pytest.raises(RetryExhausted):
        retry.call_with_retry(down, site="k")
    assert calls["n"] == 1  # max_attempts=1 == no retry


def test_backoff_is_exponential_with_ceiling():
    pol = retry.RetryPolicy(max_attempts=10, base_delay_s=0.1,
                            max_delay_s=0.5, jitter=0.0)
    assert pol.delay(1) == pytest.approx(0.1)
    assert pol.delay(2) == pytest.approx(0.2)
    assert pol.delay(3) == pytest.approx(0.4)
    assert pol.delay(4) == pytest.approx(0.5)  # ceiling
    jittered = retry.RetryPolicy(base_delay_s=0.1, jitter=0.25)
    assert 0.074 <= jittered.delay(1) <= 0.126


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
def test_watchdog_turns_hang_into_stall_error():
    base = _counter("resilience.stalls")
    telemetry.span("warmup", "test").__enter__()  # ensure some span exists
    t0 = time.monotonic()
    with pytest.raises(StallError) as ei:
        with faults.inject("w.site:hang:1:30"):
            with watchdog.guard("w.site", deadline_s=0.25):
                faults.check("w.site")  # cooperative hang, 30s
    took = time.monotonic() - t0
    assert took < 5.0, "watchdog did not interrupt the hang (took %.1fs)" % took
    err = ei.value
    assert err.site == "w.site" and err.deadline_s == pytest.approx(0.25)
    assert err.span_dump, "StallError must carry the telemetry span dump"
    assert "recent spans" in err.format_spans()
    assert _counter("resilience.stalls") == base + 1
    assert _counter("resilience.stalls.w.site") >= 1


def test_watchdog_quiet_when_fast():
    base = _counter("resilience.stalls")
    with watchdog.guard("q.site", deadline_s=5.0):
        x = sum(range(1000))
    assert x == 499500
    assert _counter("resilience.stalls") == base


def test_watchdog_heartbeat_extends_deadline():
    base = _counter("resilience.stalls")
    with watchdog.guard("h.site", deadline_s=0.3):
        for _ in range(5):
            time.sleep(0.15)
            watchdog.heartbeat()  # 0.75s total but never 0.3s silent
    assert _counter("resilience.stalls") == base


def test_watchdog_no_deadline_is_transparent():
    with watchdog.guard("n.site", deadline_s=None):
        pass


# ---------------------------------------------------------------------------
# kvstore wiring
# ---------------------------------------------------------------------------
def test_kvstore_dist_push_retries_injected_fault():
    kv = mx.kv.create("dist_sync")
    shape = (4, 3)
    kv.init("w", nd.zeros(shape))
    base = _counter("resilience.retries")
    with faults.inject("kvstore.push:error:1"):
        kv.push("w", nd.ones(shape))
    out = nd.zeros(shape)
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(shape))
    assert _counter("resilience.retries") > base


def test_kvstore_pull_retries_injected_fault():
    kv = mx.kv.create("local")
    kv.init("p", nd.full((2, 2), 3.0))
    out = nd.zeros((2, 2))
    with faults.inject("kvstore.pull:error:1"):
        kv.pull("p", out=out)
    np.testing.assert_allclose(out.asnumpy(), 3.0 * np.ones((2, 2)))


def test_kvstore_dist_exhaustion_reports_key_and_attempts(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_RETRIES", "2")
    monkeypatch.setenv("MXNET_TPU_RETRY_BASE_S", "0.001")
    kv = mx.kv.create("dist_sync")
    kv.init("conv0_weight", nd.zeros((4,)))
    with faults.inject("kvstore.push:error:*"):
        with pytest.raises(RetryExhausted) as ei:
            kv.push("conv0_weight", nd.ones((4,)))
    msg = str(ei.value)
    assert "key=conv0_weight" in msg and "shard=(4,)" in msg
    assert "2 attempt" in msg
    assert ei.value.site == "kvstore.push"


def test_kvstore_dist_wraps_foreign_errors_with_context():
    kv = mx.kv.create("dist_sync")
    kv.init("3", nd.zeros((2,)))
    kv._updater = lambda *a: (_ for _ in ()).throw(
        RuntimeError("UNAVAILABLE: endpoint lost"))
    with pytest.raises(TransportError) as ei:
        kv.push("3", nd.ones((2,)))
    assert "key=3" in str(ei.value) and "UNAVAILABLE" in str(ei.value)


def test_collective_barrier_retries_injected_fault():
    from mxnet_tpu.parallel import collectives
    base = _counter("resilience.retries")
    with faults.inject("collective.barrier:error:1"):
        collectives.barrier()
    assert _counter("resilience.retries") > base


# ---------------------------------------------------------------------------
# snapshot checkpointer
# ---------------------------------------------------------------------------
def test_snapshot_checkpointer_roundtrip_retention_atomicity(tmp_path):
    ck = rz.SnapshotCheckpointer(str(tmp_path / "ck"), keep=2)
    for step in range(5):
        ck.save(step, {"w": np.full((3,), step), "step": step})
    assert ck.steps() == [3, 4], "keep=2 must prune older steps"
    assert ck.latest_step() == 4
    step, tree = ck.restore()
    assert step == 4 and tree["step"] == 4
    np.testing.assert_array_equal(tree["w"], np.full((3,), 4))
    # torn write simulation: a stray .tmp and a corrupt LATEST marker must
    # not lose the committed checkpoints
    (tmp_path / "ck" / "step_9.ckpt.tmp").write_bytes(b"torn")
    (tmp_path / "ck" / "LATEST").write_text("not a number")
    assert ck.latest_step() == 4
    step, tree = ck.restore()
    assert step == 4


def test_sharded_checkpoint_keep_and_latest_marker(tmp_path):
    """parallel.checkpoint satellite: keep=N retention + atomic LATEST."""
    from mxnet_tpu.parallel import checkpoint as ckpt
    path = str(tmp_path / "ck")
    for step in (1, 2, 3, 4):
        ckpt.save_sharded(path, {"w": np.ones((2,)) * step}, step=step,
                          keep=2)
    assert ckpt.latest_step(path) == 4
    committed = [d for d in os.listdir(path) if d.isdigit()]
    assert sorted(int(d) for d in committed) == [3, 4], \
        "keep=2 must retain exactly the newest two steps"
    assert (tmp_path / "ck" / "LATEST").read_text().strip() == "4"
    # corrupt marker: scan fallback still finds the newest step
    (tmp_path / "ck" / "LATEST").write_text("garbage")
    assert ckpt.latest_step(path) == 4
    restored = ckpt.restore_sharded(path)
    np.testing.assert_allclose(np.asarray(restored["w"]), 4 * np.ones((2,)))


# ---------------------------------------------------------------------------
# resilient runner: the acceptance scenario
# ---------------------------------------------------------------------------
def _build_mlp():
    mx.random.seed(42)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    return net, tr


def _six_batches():
    rng = np.random.RandomState(0)
    X = rng.rand(6, 32, 8).astype(np.float32)
    Y = rng.randint(0, 3, (6, 32)).astype(np.float32)
    return lambda i: (nd.array(X[i]), nd.array(Y[i]))


def test_kill_and_resume_matches_fault_free_run(tmp_path, monkeypatch):
    """ISSUE acceptance: MXNET_TPU_FAULT_PLAN injects a transport fault AND
    a mid-run kill; the 6-step resilient run must reproduce the fault-free
    trajectory and final params within fp32 tolerance, with nonzero
    resilience.retries and resilience.restores."""
    batch_fn = _six_batches()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    net_a, tr_a = _build_mlp()
    fused_a = gluon.FusedTrainStep(net_a, loss_fn, tr_a)
    clean = [float(fused_a(*batch_fn(i)).asnumpy()) for i in range(6)]

    net_b, tr_b = _build_mlp()
    fused_b = gluon.FusedTrainStep(net_b, loss_fn, tr_b)
    retries0 = _counter("resilience.retries")
    restores0 = _counter("resilience.restores")
    monkeypatch.setenv("MXNET_TPU_FAULT_PLAN",
                       "run.step:error:2;run.step:preempt:5")
    try:
        faults.activate()
        runner = rz.ResilientRunner.for_fused_step(
            fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2,
            max_restarts=3,
            retry_policy=retry.RetryPolicy(max_attempts=3,
                                           base_delay_s=0.001))
        report = runner.run(6)
    finally:
        faults.deactivate()

    assert report.restarts >= 1 and report.retries >= 1
    np.testing.assert_allclose(clean, report.losses, rtol=1e-5, atol=1e-6)
    for (ka, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                 sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=ka)
    assert _counter("resilience.retries") > retries0
    assert _counter("resilience.restores") > restores0


def test_kill_and_resume_with_dropout_rng_state(tmp_path):
    """RNG key table is checkpointed: even a net that CONSUMES randomness
    every step (dropout) replays the uninterrupted trajectory."""
    def build():
        mx.random.seed(9)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dropout(0.4),
                    nn.Dense(3))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01})
        return net, tr

    batch_fn = _six_batches()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net_a, tr_a = build()
    fused_a = gluon.FusedTrainStep(net_a, loss_fn, tr_a)
    clean = [float(fused_a(*batch_fn(i)).asnumpy()) for i in range(6)]

    net_b, tr_b = build()
    fused_b = gluon.FusedTrainStep(net_b, loss_fn, tr_b)
    with faults.inject("run.step:preempt:3"):
        runner = rz.ResilientRunner.for_fused_step(
            fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
            max_restarts=2)
        report = runner.run(6)
    assert report.restarts == 1
    np.testing.assert_allclose(clean, report.losses, rtol=1e-5, atol=1e-6)


def test_runner_fault_before_first_checkpoint_surfaces_cause(tmp_path):
    """A fault with an EMPTY checkpoint dir must surface the fault itself,
    not a FileNotFoundError about the missing snapshot."""
    def step_fn(i):
        faults.check("bare.step")
        return 0.0

    state = {"w": 1.0}
    with faults.inject("bare.step:preempt:1"):
        runner = rz.ResilientRunner(
            step_fn, state_get=lambda: dict(state),
            state_set=lambda t: state.update(t),
            ckpt_dir=str(tmp_path / "ck"), ckpt_every=5, max_restarts=3)
        # start_step=2 is off the ckpt cadence: nothing saved before the hit
        with pytest.raises(PreemptionError):
            runner.run(6, start_step=2)


def test_runner_restart_budget_exhausts():
    net, tr = _build_mlp()
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    batch_fn = _six_batches()
    with faults.inject("run.step:preempt:1+"):
        runner = rz.ResilientRunner.for_fused_step(
            fused, batch_fn, ckpt_dir=None, max_restarts=2)
        # no checkpointer: first preemption must surface immediately
        with pytest.raises(PreemptionError):
            runner.run(6)


def test_runner_recovers_from_stall(tmp_path):
    """A hang inside the step (dead collective) → watchdog StallError →
    restore-and-replay, run completes. The deadline is 5 s, as in
    `test_observability.py::test_runner_stall_flight_ledger` and for its
    reason: the guarded steps compile for about 0.5 s on an idle machine,
    and under the load of six test workers a deadline of 0.5 s made a
    second stall of the compile after the restore (restarts 2, not 1)."""
    net, tr = _build_mlp()
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    batch_fn = _six_batches()
    stalls0 = _counter("resilience.stalls")
    with faults.inject("train.step:hang:3:30"):
        runner = rz.ResilientRunner.for_fused_step(
            fused, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
            max_restarts=2, step_deadline_s=5.0)
        report = runner.run(4)
    assert report.restarts == 1
    assert _counter("resilience.stalls") > stalls0
    assert all(l is not None for l in report.losses)


def test_runner_step_deadline_env_knob(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_STEP_DEADLINE_S", "0.4")
    net, tr = _build_mlp()
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    runner = rz.ResilientRunner.for_fused_step(
        fused, _six_batches(), ckpt_dir=str(tmp_path / "ck"))
    assert runner.step_deadline_s == pytest.approx(0.4)


def test_runner_auto_resume_after_process_kill(tmp_path):
    """resume=True restores the newest checkpoint — the relaunch-after-kill
    path (same ckpt_dir, fresh process state)."""
    batch_fn = _six_batches()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net_a, tr_a = _build_mlp()
    fused_a = gluon.FusedTrainStep(net_a, loss_fn, tr_a)
    clean = [float(fused_a(*batch_fn(i)).asnumpy()) for i in range(6)]

    # "first boot": dies by preemption with the restart budget at 0
    net_b, tr_b = _build_mlp()
    fused_b = gluon.FusedTrainStep(net_b, loss_fn, tr_b)
    runner = rz.ResilientRunner.for_fused_step(
        fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
        max_restarts=0)
    with faults.inject("run.step:preempt:4"):
        with pytest.raises(PreemptionError):
            runner.run(6)

    # "relaunch": perturb live state to prove restore really happens
    for _, p in net_b.collect_params().items():
        p.set_data(p.data() * 0.0)
    runner2 = rz.ResilientRunner.for_fused_step(
        fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1)
    report = runner2.run(6, resume=True)
    assert report.restarts == 0  # a requested resume is not a failure
    for (ka, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                 sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=ka)
    # the tail of the trajectory (post-resume steps) matches the clean run
    resumed_tail = [l for l in report.losses if l is not None]
    np.testing.assert_allclose(clean[-len(resumed_tail):], resumed_tail,
                               rtol=1e-5, atol=1e-6)


def test_runner_mesh_shrink_degrades_gracefully(tmp_path):
    """Device set shrinks across a restore → on_shrink rebuilds the step
    for the smaller mesh and the run continues (degraded, not dead)."""
    class FakeDevices:
        def __init__(self, size):
            self.size = size

    class FakeMesh:
        def __init__(self, size):
            self.devices = FakeDevices(size)

    sizes = {"n": 8}
    meshes = []

    def mesh_factory():
        m = FakeMesh(sizes["n"])
        meshes.append(m)
        return m

    state = {"w": 0.0, "rebuilt_for": None}

    def step_fn(i):
        faults.check("fake.step")
        state["w"] += 1.0
        return state["w"]

    def on_shrink(mesh):
        state["rebuilt_for"] = mesh.devices.size
        return step_fn  # rebuilt step for the smaller mesh

    shrinks0 = _counter("resilience.mesh_shrinks")
    with faults.inject("fake.step:preempt:3"):
        runner = rz.ResilientRunner(
            step_fn, state_get=lambda: dict(state),
            state_set=lambda t: state.update(t),
            ckpt_dir=str(tmp_path / "ck"), ckpt_every=1, max_restarts=2,
            mesh_factory=mesh_factory, on_shrink=on_shrink)
        sizes["n"] = 4  # preemption takes half the fleet
        report = runner.run(5)
    assert report.restarts == 1 and report.mesh_shrinks == 1
    assert state["rebuilt_for"] == 4
    assert _counter("resilience.mesh_shrinks") == shrinks0 + 1


def test_sharded_train_step_resilient_run(tmp_path):
    """Functional path: ShardedTrainStep under the runner reproduces the
    uninterrupted trajectory through a preemption."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel import ShardedTrainStep, create_mesh

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.RandomState(3)
    X = rng.rand(6, 16, 4).astype(np.float32)
    Y = rng.rand(6, 16, 2).astype(np.float32)

    def batch_fn(i):
        return {"x": jnp.asarray(X[i]), "y": jnp.asarray(Y[i])}

    def make():
        mesh = create_mesh(data=2)
        params = {"w": jnp.zeros((4, 2))}
        step = ShardedTrainStep(loss_fn, params, mesh, optimizer="sgd",
                                lr=0.1, momentum=0.9, donate=False)
        return step, step.init()

    step_a, (pa, oa) = make()
    clean = []
    for i in range(6):
        pa, oa, l = step_a(pa, oa, batch_fn(i), i)
        clean.append(float(l))

    step_b, (pb, ob) = make()
    with faults.inject("run.step:preempt:4"):
        runner = rz.ResilientRunner.for_sharded_step(
            step_b, pb, ob, batch_fn, ckpt_dir=str(tmp_path / "ck"),
            ckpt_every=2, max_restarts=2)
        report = runner.run(6)
    assert report.restarts == 1
    np.testing.assert_allclose(clean, report.losses, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pa["w"]),
                               np.asarray(runner.holder["params"]["w"]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# telemetry aggregation (satellite)
# ---------------------------------------------------------------------------
def test_merge_snapshots_fleet_semantics():
    a = {"counters": {"kvstore.push_calls": 3, "resilience.retries": 1},
         "gauges": {"memory.dev0.bytes_in_use": {"value": 10, "max": 40}},
         "histograms": {"step_ms": {"count": 2, "sum": 10.0, "min": 4.0,
                                    "max": 6.0, "avg": 5.0,
                                    "buckets": {"le_10": 2}}}}
    b = {"counters": {"kvstore.push_calls": 5, "cachedop.compile": 2},
         "gauges": {"memory.dev0.bytes_in_use": {"value": 30, "max": 35}},
         "histograms": {"step_ms": {"count": 1, "sum": 8.0, "min": 8.0,
                                    "max": 8.0, "avg": 8.0,
                                    "buckets": {"le_10": 1}}}}
    m = telemetry.merge_snapshots([a, b])
    assert m["workers"] == 2
    assert m["counters"]["kvstore.push_calls"] == 8      # extensive: sum
    assert m["counters"]["cachedop.compile"] == 2        # union of keys
    g = m["gauges"]["memory.dev0.bytes_in_use"]
    assert g["value"] == 30 and g["max"] == 40           # fleet watermark
    h = m["histograms"]["step_ms"]
    assert h["count"] == 3 and h["sum"] == pytest.approx(18.0)
    assert h["min"] == 4.0 and h["max"] == 8.0
    assert h["avg"] == pytest.approx(6.0)
    assert h["buckets"]["le_10"] == 3


def test_aggregate_snapshot_single_process():
    telemetry.inc("agg.test.counter", 4)
    merged = telemetry.aggregate_snapshot()
    assert merged["workers"] == 1
    assert merged["counters"]["agg.test.counter"] >= 4


# ---------------------------------------------------------------------------
# tooling (satellite)
# ---------------------------------------------------------------------------
def test_parse_log_resilience_mode(tmp_path):
    telemetry.reset()  # counters are process-global; start this dump clean
    telemetry.inc("resilience.retries")
    telemetry.inc("resilience.retries.kvstore.push")
    telemetry.inc("resilience.restores", 2)
    dump = str(tmp_path / "telemetry.json")
    telemetry.dump(dump)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--resilience"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "| retries | total |" in r.stdout
    assert "| retries | kvstore.push | 1 |" in r.stdout
    assert "| restores | total |" in r.stdout
    # csv shape too
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--resilience", "--format", "csv"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "event,site,count" in r.stdout


def test_parse_log_resilience_v2_event_rows(tmp_path):
    """Satellite: elastic/commit/preempt events surface as table rows —
    shrink, grow-back, commit elections (+ elected-step gauge), proactive
    checkpoints, preemption notices."""
    telemetry.reset()
    telemetry.inc("resilience.mesh_shrinks")
    telemetry.inc("resilience.mesh_grows")
    telemetry.inc("resilience.commit.elections", 3)
    telemetry.inc("resilience.commit.elections.save", 2)
    telemetry.inc("resilience.commit.elections.restore")
    telemetry.inc("resilience.commit.rank_ahead")
    telemetry.inc("resilience.proactive_checkpoints")
    telemetry.inc("resilience.preempt.notices")
    telemetry.inc("resilience.preempt.notices.poll")
    telemetry.set_gauge("resilience.commit.elected_step", 42)
    dump = str(tmp_path / "telemetry.json")
    telemetry.dump(dump)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--resilience"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    for row in ("| mesh_shrinks | total | 1 |",
                "| mesh_grows | total | 1 |",
                "| commit.elections | total | 3 |",
                "| commit.elections | save | 2 |",
                "| commit.elections | restore | 1 |",
                "| commit.rank_ahead | total | 1 |",
                "| commit.elected_step | latest | 42 |",
                "| proactive_checkpoints | total | 1 |",
                "| preempt.notices | total | 1 |",
                "| preempt.notices | poll | 1 |"):
        assert row in r.stdout, "missing row %r in:\n%s" % (row, r.stdout)


# ---------------------------------------------------------------------------
# coordinated commit (resilience v2)
# ---------------------------------------------------------------------------
def test_commit_election_min_and_rank_ahead_counter():
    from mxnet_tpu.resilience import commit
    ahead0 = _counter("resilience.commit.rank_ahead")
    coord = commit.CommitCoordinator(gather=lambda step, rnd: [step, step - 1])
    assert coord.elect(5) == 4
    assert _counter("resilience.commit.rank_ahead") == ahead0 + 1
    assert _counter("resilience.commit.elections") >= 1
    snap = telemetry.snapshot()["gauges"]
    assert snap["resilience.commit.elected_step"]["value"] == 4


def test_commit_election_single_process_identity_and_none():
    from mxnet_tpu.resilience import commit
    assert commit.elect_step(7) == 7
    assert commit.CommitCoordinator().elect(None) is None
    # a rank with nothing durable does not drag the fleet to None
    coord = commit.CommitCoordinator(gather=lambda step, rnd: [step, None, 3])
    assert coord.elect(5) == 3


def test_checkpointer_two_phase_prepare_commit(tmp_path):
    """prepare makes the payload durable without moving a committed marker;
    commit refuses a step whose payload is missing."""
    ck = rz.SnapshotCheckpointer(str(tmp_path / "ck"), keep=None)
    ck.save(2, {"w": 2})               # committed baseline
    ck.prepare(3, {"w": 3})            # durable, NOT committed
    assert ck.latest_step() == 2, \
        "an uncommitted payload must not win over the committed marker"
    assert 3 in ck.prepared_steps()
    assert ck.commit(9) is False       # no payload -> marker unchanged
    assert ck.latest_step() == 2
    assert ck.commit(3) is True
    assert ck.latest_step() == 3


def test_mid_commit_crash_resumes_at_committed_step(tmp_path):
    """checkpoint.save fault site (satellite): a crash AFTER the payload is
    durable but BEFORE the marker moves (the rank-ahead shape) resumes at
    the last COMMITTED step; the stray newer payload is invisible."""
    batch_fn = _six_batches()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net_a, tr_a = _build_mlp()
    fused_a = gluon.FusedTrainStep(net_a, loss_fn, tr_a)
    clean = [float(fused_a(*batch_fn(i)).asnumpy()) for i in range(6)]

    net_b, tr_b = _build_mlp()
    fused_b = gluon.FusedTrainStep(net_b, loss_fn, tr_b)
    # saves land at steps 0, 2, 4: the 3rd save (step 4) dies mid-commit
    with faults.inject("checkpoint.save:preempt:3"):
        runner = rz.ResilientRunner.for_fused_step(
            fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2)
        with pytest.raises(PreemptionError):
            runner.run(6)
    ck = rz.SnapshotCheckpointer(str(tmp_path / "ck"))
    assert 4 in ck.prepared_steps(), "step-4 payload must be durable"
    assert ck.latest_step() == 2, "marker must still name the committed step"

    # relaunch: resumes from the committed step and reproduces the clean run
    runner2 = rz.ResilientRunner.for_fused_step(
        fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2)
    report = runner2.run(6, resume=True)
    tail = [l for l in report.losses if l is not None]
    np.testing.assert_allclose(clean[-len(tail):], tail,
                               rtol=1e-5, atol=1e-6)
    for (ka, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                 sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=ka)


def test_sharded_checkpoint_coordinated_mid_commit_crash(tmp_path):
    """Orbax path: `coordinated=True` + the checkpoint.save fault site —
    a crash between payload-durable and marker-flip leaves the committed
    view at the previous step, and `restore_sharded(coordinated=True)`
    restores it (the stray newer payload stays invisible)."""
    from mxnet_tpu.parallel import checkpoint as ckpt
    path = str(tmp_path / "ck")
    ckpt.save_sharded(path, {"w": np.ones((2,))}, step=1, coordinated=True)
    assert ckpt.latest_committed_step(path) == 1
    with faults.inject("checkpoint.save:preempt:1"):
        with pytest.raises(PreemptionError):
            ckpt.save_sharded(path, {"w": np.ones((2,)) * 2}, step=2,
                              coordinated=True)
    # the step-2 payload is durable (scan sees it) but NOT committed
    assert ckpt.latest_step(path) == 2
    assert ckpt.latest_committed_step(path) == 1
    restored = ckpt.restore_sharded(path, coordinated=True)
    np.testing.assert_allclose(np.asarray(restored["w"]), np.ones((2,)))


def test_commit_restore_election_agrees_across_simulated_ranks(tmp_path):
    """Two simulated ranks, rank1 a prepared step ahead (crashed
    mid-commit): the restore election lands every rank on the elected min
    step."""
    from mxnet_tpu.resilience import commit
    cks = [rz.SnapshotCheckpointer(str(tmp_path / ("rank%d" % r)))
           for r in range(2)]
    for step in (1, 2, 3, 4):
        for ck in cks:
            ck.save(step, {"w": np.full((2,), float(step)), "step": step})
    cks[1].prepare(5, {"w": np.full((2,), 5.0), "step": 5})  # rank1 ahead

    # the fleet exchange: every rank reports its newest DURABLE step
    durable = [max(ck.prepared_steps()) for ck in cks]
    assert durable == [4, 5]
    fleet = {}

    def gather_for(rank):
        def gather(step, rnd):
            fleet[rank] = step
            return [durable[0], durable[1]]
        return gather

    restored = []
    for rank, ck in enumerate(cks):
        coord = commit.CommitCoordinator(gather=gather_for(rank))
        elected = coord.elect(durable[rank], kind="restore")
        step, tree = ck.restore(elected)
        restored.append((step, tree["step"]))
    assert restored == [(4, 4), (4, 4)], restored


def test_runner_coordinated_save_commits_elected_step(tmp_path):
    """_save under a CommitCoordinator: the marker names the fleet-elected
    min, not this rank's (newer) prepared step."""
    from mxnet_tpu.resilience import commit
    state = {"w": 0.0}
    runner = rz.ResilientRunner(
        lambda i: 0.0, state_get=lambda: dict(state),
        state_set=lambda t: state.update(t),
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
        commit=commit.CommitCoordinator(
            gather=lambda step, rnd: [step, max(0, step - 1)]))
    report = runner.run(3)
    ck = runner.ckpt
    # last save prepared step 2; the fleet's laggard was at 1 -> marker 1
    assert 2 in ck.prepared_steps()
    assert ck.latest_step() == 1
    assert report.checkpoints == 3


# ---------------------------------------------------------------------------
# proactive preemption (resilience v2)
# ---------------------------------------------------------------------------
def test_preempt_listener_poll_notice_via_fault_plan():
    from mxnet_tpu.resilience.preempt import PreemptionListener
    notices0 = _counter("resilience.preempt.notices")
    with faults.inject("preempt.poll:preempt:1"):
        listener = PreemptionListener(poll_interval_s=0.01)
        listener.start()
        try:
            deadline = time.monotonic() + 5.0
            while listener.pending() is None and time.monotonic() < deadline:
                time.sleep(0.01)
            notice = listener.pending()
        finally:
            listener.stop()
    assert notice is not None, "poller never observed the planned event"
    assert notice.source == "poll"
    assert "preemption" in notice.reason
    assert _counter("resilience.preempt.notices") == notices0 + 1
    assert _counter("resilience.preempt.notices.poll") >= 1


def test_preempt_listener_sigterm_notice():
    import signal
    from mxnet_tpu.resilience.preempt import PreemptionListener
    seen = []
    listener = PreemptionListener(poll_fn=False,
                                  on_notice=lambda n: seen.append(n))
    listener.start()
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5.0
        while listener.pending() is None and time.monotonic() < deadline:
            time.sleep(0.01)
        notice = listener.pending()
    finally:
        listener.stop()
    assert notice is not None and notice.source == "sigterm"
    assert seen and seen[0] is notice
    # handler restored: a second listener can install again
    assert signal.getsignal(signal.SIGTERM) not in (listener._handle_sigterm,)


def test_runner_proactive_checkpoint_zero_replay(tmp_path):
    """ISSUE acceptance: a simulated preemption notice produces a proactive
    checkpoint — resume replays ZERO steps (vs up to ckpt_every-1 for a
    periodic-snapshot-only recovery) and the trajectory still matches the
    fault-free run."""
    from mxnet_tpu.resilience.preempt import PreemptionListener
    batch_fn = _six_batches()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net_a, tr_a = _build_mlp()
    fused_a = gluon.FusedTrainStep(net_a, loss_fn, tr_a)
    clean = [float(fused_a(*batch_fn(i)).asnumpy()) for i in range(6)]

    net_b, tr_b = _build_mlp()
    fused_b = gluon.FusedTrainStep(net_b, loss_fn, tr_b)
    proactive0 = _counter("resilience.proactive_checkpoints")
    with faults.inject("preempt.poll:preempt:1"):
        listener = PreemptionListener(poll_interval_s=0.01).start()
        try:
            # deterministic: the notice is pending BEFORE the run begins
            deadline = time.monotonic() + 5.0
            while listener.pending() is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert listener.pending() is not None
            # ckpt_every=5: without the proactive save, recovery would
            # rewind to step 0 and replay
            runner = rz.ResilientRunner.for_fused_step(
                fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"),
                ckpt_every=5, max_restarts=2, preempt_listener=listener)
            report = runner.run(6)
        finally:
            listener.stop()
    assert report.proactive_ckpts == 1
    assert report.replayed_steps == 0, \
        "proactive checkpoint must make the preemption replay-free"
    assert report.restarts == 1
    assert _counter("resilience.proactive_checkpoints") == proactive0 + 1
    np.testing.assert_allclose(clean, report.losses, rtol=1e-5, atol=1e-6)


def test_runner_reactive_preemption_replays_for_contrast(tmp_path):
    """The ledger distinguishes reactive from proactive: a hard preemption
    off the checkpoint cadence replays completed steps."""
    net, tr = _build_mlp()
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    with faults.inject("run.step:preempt:5"):
        runner = rz.ResilientRunner.for_fused_step(
            fused, _six_batches(), ckpt_dir=str(tmp_path / "ck"),
            ckpt_every=3, max_restarts=2)
        report = runner.run(6)
    # preempt at step 4; last snapshot at step 3 -> step 3 replays? no:
    # steps 0..3 completed, preempt at step 4, restore to 3, steps 3,4
    # re-run — step 3 was completed before, so exactly 1 replay
    assert report.replayed_steps == 1
    assert report.recovery_time_s > 0.0


# ---------------------------------------------------------------------------
# device-aware stall post-mortems (resilience v2)
# ---------------------------------------------------------------------------
def test_stall_post_mortem_includes_device_state():
    """ISSUE acceptance: StallError carries per-device PjRt state (live
    buffer counts/bytes) and the last-compiled executables next to the
    host span dump — one structured report."""
    import jax.numpy as jnp
    keep_alive = jnp.ones((16, 16))  # a live buffer the report must see
    telemetry.note_compile("test_executable")
    with pytest.raises(StallError) as ei:
        with faults.inject("pm.site:hang:1:30"):
            with watchdog.guard("pm.site", deadline_s=0.25):
                faults.check("pm.site")
    err = ei.value
    assert err.device_dump, "StallError must carry the device dump"
    entry = err.device_dump[0]
    assert "device" in entry and "platform" in entry
    assert any("live_buffers" in e for e in err.device_dump), \
        "at least one device must report live buffers: %r" % err.device_dump
    total_bufs = sum(e.get("live_buffers", 0) for e in err.device_dump)
    assert total_bufs >= 1
    assert any(name == "test_executable" for name, _ in err.compile_dump)
    report = err.format_report()
    assert "recent spans" in report
    assert "device state:" in report
    assert "live_buffers=" in report
    assert "test_executable" in report
    del keep_alive


def test_telemetry_device_report_shape():
    report = telemetry.device_report()
    assert isinstance(report, list) and report
    for entry in report:
        assert "device" in entry and "platform" in entry


def test_telemetry_recent_compiles_ring():
    telemetry.reset()
    for i in range(40):
        telemetry.note_compile("exe_%d" % i)
    events = telemetry.recent_compiles()
    assert len(events) <= 32
    assert events[-1][0] == "exe_39"
    assert telemetry.recent_compiles(limit=3)[0][0] == "exe_37"


# ---------------------------------------------------------------------------
# elastic re-sharding (resilience v2 tentpole)
# ---------------------------------------------------------------------------
def _exact_sharded_fixture(steps=6):
    """Binary data + dyadic hyperparameters: every sum in the train step is
    exactly representable in fp32, so ANY reduction order — any mesh —
    produces bit-identical results. That turns cross-mesh parity into an
    equality assertion instead of a tolerance."""
    import jax.numpy as jnp

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2)

    rng = np.random.RandomState(3)
    X = rng.randint(0, 2, (steps, 16, 4)).astype(np.float32)
    Y = rng.randint(0, 2, (steps, 16, 2)).astype(np.float32)

    def batch_fn(i):
        return {"x": jnp.asarray(X[i]), "y": jnp.asarray(Y[i])}

    def make(n):
        from mxnet_tpu.parallel import ShardedTrainStep, create_mesh
        import jax.numpy as jnp
        mesh = create_mesh(data=n)
        params = {"w": jnp.zeros((4, 2))}
        step = ShardedTrainStep(loss_fn, params, mesh, optimizer="sgd",
                                lr=0.5, momentum=0.5, donate=False)
        return step, step.init()

    return batch_fn, make


def test_runner_elastic_reshard_on_mesh_shrink(tmp_path):
    """ISSUE acceptance: mesh-shrink fault -> the runner re-shards the
    restored snapshot onto the smaller mesh automatically (NO on_shrink
    callback) and the final params are bit-identical to an uninterrupted
    run on that mesh."""
    from mxnet_tpu.parallel import create_mesh
    batch_fn, make = _exact_sharded_fixture()

    # the acceptance reference: uninterrupted run entirely on the small mesh
    step_a, (pa, oa) = make(1)
    clean = []
    for i in range(6):
        pa, oa, l = step_a(pa, oa, batch_fn(i), i)
        clean.append(float(l))

    sizes = {"n": 2}

    def mesh_factory():
        return create_mesh(data=sizes["n"])

    shrinks0 = _counter("resilience.mesh_shrinks")
    step_b, (pb, ob) = make(2)
    with faults.inject("run.step:preempt:4"):
        runner = rz.ResilientRunner.for_sharded_step(
            step_b, pb, ob, batch_fn, ckpt_dir=str(tmp_path / "ck"),
            ckpt_every=1, max_restarts=2, mesh_factory=mesh_factory)
        sizes["n"] = 1  # the preemption takes half the fleet
        report = runner.run(6)
    assert report.restarts == 1 and report.mesh_shrinks == 1
    assert _counter("resilience.mesh_shrinks") == shrinks0 + 1
    # params update through LINEAR gradient math (exact on binary data);
    # the loss itself squares the residual, which can round differently
    # per mesh — so params get the bit-equality assertion, losses a tight
    # tolerance
    np.testing.assert_allclose(clean, report.losses, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(runner.holder["params"]["w"]),
                                  np.asarray(pa["w"]))
    # the state really lives on the smaller mesh now
    assert len(runner.holder["params"]["w"].sharding.device_set) == 1
    # and the rebuilt step targets it
    assert runner.active["step"].mesh.devices.size == 1


def test_runner_elastic_grow_back(tmp_path):
    """Capacity returns mid-run: the checkpoint-boundary poll re-lays the
    LIVE state back onto the larger mesh (no fault, no restore) and the
    trajectory is unchanged."""
    from mxnet_tpu.parallel import create_mesh
    batch_fn, make = _exact_sharded_fixture()

    step_a, (pa, oa) = make(1)
    clean = []
    for i in range(6):
        pa, oa, l = step_a(pa, oa, batch_fn(i), i)
        clean.append(float(l))

    sizes = {"n": 1}

    def mesh_factory():
        return create_mesh(data=sizes["n"])

    grows0 = _counter("resilience.mesh_grows")
    step_b, (pb, ob) = make(1)
    runner = rz.ResilientRunner.for_sharded_step(
        step_b, pb, ob, batch_fn, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=2, mesh_factory=mesh_factory)
    sizes["n"] = 2  # capacity comes back; the step-2 boundary poll sees it
    report = runner.run(6)
    assert report.mesh_grows == 1 and report.restarts == 0
    assert _counter("resilience.mesh_grows") == grows0 + 1
    np.testing.assert_allclose(clean, report.losses, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(runner.holder["params"]["w"]),
                                  np.asarray(pa["w"]))
    assert len(runner.holder["params"]["w"].sharding.device_set) == 2
    assert runner.active["step"].mesh.devices.size == 2


def test_on_shrink_hook_still_overrides_auto_reshard(tmp_path):
    """Back-compat: a user on_shrink hook wins over the automatic
    relayout."""
    from mxnet_tpu.parallel import create_mesh
    batch_fn, make = _exact_sharded_fixture()
    sizes = {"n": 2}

    def mesh_factory():
        return create_mesh(data=sizes["n"])

    called = []
    step_b, (pb, ob) = make(2)
    with faults.inject("run.step:preempt:3"):
        runner = rz.ResilientRunner.for_sharded_step(
            step_b, pb, ob, batch_fn, ckpt_dir=str(tmp_path / "ck"),
            ckpt_every=1, max_restarts=2, mesh_factory=mesh_factory,
            on_shrink=lambda mesh: called.append(mesh.devices.size) or None)
        sizes["n"] = 1
        report = runner.run(4)
    assert called == [1]
    assert report.mesh_shrinks == 1


def test_fused_step_elastic_rebuild_on_shrink(tmp_path):
    """Gluon path: a mesh-aware FusedTrainStep is rebuilt for the smaller
    mesh automatically, optimizer state carried across; the run completes
    and matches the fault-free trajectory."""
    from mxnet_tpu.parallel import create_mesh
    batch_fn = _six_batches()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    net_a, tr_a = _build_mlp()
    fused_a = gluon.FusedTrainStep(net_a, loss_fn, tr_a,
                                   mesh=create_mesh(data=2))
    clean = [float(fused_a(*batch_fn(i)).asnumpy()) for i in range(6)]

    sizes = {"n": 2}

    def mesh_factory():
        return create_mesh(data=sizes["n"])

    net_b, tr_b = _build_mlp()
    fused_b = gluon.FusedTrainStep(net_b, loss_fn, tr_b,
                                   mesh=create_mesh(data=2))
    with faults.inject("run.step:preempt:4"):
        runner = rz.ResilientRunner.for_fused_step(
            fused_b, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
            max_restarts=2, mesh_factory=mesh_factory)
        sizes["n"] = 1
        report = runner.run(6)
    assert report.restarts == 1 and report.mesh_shrinks == 1
    assert runner.active["fused"] is not fused_b, "step must be rebuilt"
    assert runner.active["fused"]._mesh.devices.size == 1
    np.testing.assert_allclose(clean, report.losses, rtol=1e-4, atol=1e-5)
    for (ka, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                 sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                                   rtol=1e-4, atol=1e-5, err_msg=ka)


def test_sharded_step_place_and_rebuild_unit():
    """ShardedTrainStep.place re-lays host trees onto the step's mesh with
    rules-derived shardings; rebuild_for_mesh preserves knobs."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import ShardedTrainStep, create_mesh

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    mesh2 = create_mesh(data=2)
    step = ShardedTrainStep(loss_fn, {"w": jnp.zeros((4, 2))}, mesh2,
                            optimizer="sgd", lr=0.5, momentum=0.5,
                            donate=False, grad_accum=1)
    params, opt = step.init()
    mesh1 = create_mesh(data=1)
    rebuilt = step.rebuild_for_mesh(mesh1)
    assert rebuilt.mesh is mesh1
    assert rebuilt.lr == step.lr and rebuilt.donate == step.donate
    assert rebuilt.opt_kwargs == step.opt_kwargs
    p2, o2 = rebuilt.place({"w": np.ones((4, 2), np.float32)},
                           {"mom": {"w": np.zeros((4, 2), np.float32)}})
    assert len(p2["w"].sharding.device_set) == 1
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.ones((4, 2)))
    assert len(o2["mom"]["w"].sharding.device_set) == 1

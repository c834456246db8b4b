"""Bucketed gradient-communication engine (mx.engine) tests.

Coverage per ISSUE 4: bit-exact parity of bucketed vs. unbucketed gradients
(local + dist kvstore + eager collectives + both fused train-step paths),
bucket-boundary cases (grad > cap, dtype-mixed buckets split, empty grads
skipped), fault-injection retry per-bucket with key context, the
`MXNET_TPU_COMM_BUCKET_MB=0` escape hatch, the collectives-per-step drop for
a resnet18-sized gradient set, the retrace-guard routing for the functional
paths, the single-sync mp batchify, and the `parse_log.py --comm` table.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon, nd, telemetry
from mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counters():
    return dict(telemetry.snapshot()["counters"])


def _delta(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


# ===========================================================================
# GradBucketer unit behavior
# ===========================================================================

def test_bucketer_packs_in_order_and_caps():
    before = _counters()
    buckets = engine.bucketize(
        [(str(i), jnp.ones((1000,), jnp.float32)) for i in range(10)],
        cap_bytes=3 * 4000)
    assert [b.keys for b in buckets] == [
        ["0", "1", "2"], ["3", "4", "5"], ["6", "7", "8"], ["9"]]
    assert [b.reason for b in buckets] == ["full", "full", "full", "final"]
    assert all(b.nbytes <= 12000 for b in buckets)
    after = _counters()
    assert _delta(before, after, "comm.bucket.count") == 4
    assert _delta(before, after, "comm.bucket.bytes") == 40000
    assert _delta(before, after, "comm.bucket.flush_reason.full") == 3
    assert _delta(before, after, "comm.bucket.flush_reason.final") == 1


def test_bucketer_oversize_grad_travels_alone():
    buckets = engine.bucketize(
        [("small", jnp.ones((10,), jnp.float32)),
         ("big", jnp.ones((100000,), jnp.float32)),
         ("tail", jnp.ones((10,), jnp.float32))],
        cap_bytes=1000)
    assert [b.keys for b in buckets] == [["small"], ["big"], ["tail"]]
    assert buckets[1].reason == "oversize"


def test_bucketer_splits_mixed_dtypes():
    buckets = engine.bucketize(
        [("a", jnp.ones((10,), jnp.float32)),
         ("b", jnp.ones((10,), jnp.bfloat16)),
         ("c", jnp.ones((10,), jnp.bfloat16))],
        cap_bytes=1 << 20)
    assert [b.keys for b in buckets] == [["a"], ["b", "c"]]
    assert all(len({str(r.dtype) for r in b.raws}) == 1 for b in buckets)


def test_bucketer_skips_empty_grads():
    before = _counters()
    buckets = engine.bucketize(
        [("a", jnp.ones((4,), jnp.float32)),
         ("empty", jnp.zeros((0,), jnp.float32)),
         ("none", None),
         ("b", jnp.ones((4,), jnp.float32))],
        cap_bytes=1 << 20)
    assert [b.keys for b in buckets] == [["a", "b"]]
    assert _delta(before, _counters(), "comm.bucket.skipped") == 2


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(0)
    raws = [jnp.asarray(rng.randn(*s).astype(np.float32))
            for s in [(3, 4), (7,), (2, 2, 2)]]
    (bucket,) = engine.bucketize(enumerate(raws), cap_bytes=1 << 20)
    flat = engine.pack_bucket(bucket)
    assert flat.shape == (12 + 7 + 8,)
    parts = engine.unpack_bucket(bucket, flat)
    for r, p in zip(raws, parts):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


def test_reassociate_bucketed_is_identity():
    rng = np.random.RandomState(1)
    raws = [jnp.asarray(rng.randn(*s).astype(np.float32))
            for s in [(5, 5), (100,), (3,), (17, 2)]]
    out = engine.reassociate_bucketed(raws, bucket_mb=0.0001)
    for r, o in zip(raws, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))
    # and under jit (the train-step usage)
    out2 = jax.jit(lambda xs: engine.reassociate_bucketed(xs, 25))(raws)
    for r, o in zip(raws, out2):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def test_bucket_cap_knob_precedence(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMM_BUCKET_MB", "2")
    assert engine.bucket_bytes() == 2 * 1024 * 1024
    with engine.bucket_mb_scope(1):
        assert engine.bucket_bytes() == 1024 * 1024
        assert engine.bucket_bytes(4) == 4 * 1024 * 1024  # arg wins
    assert engine.bucket_bytes() == 2 * 1024 * 1024
    # the escape hatch: 0 disables bucketing entirely
    monkeypatch.setenv("MXNET_TPU_COMM_BUCKET_MB", "0")
    assert engine.bucket_bytes() == 0


# ===========================================================================
# local kvstore: bucketed vs per-key bit-exact parity
# ===========================================================================

def _local_pushpull(bucket_mb, nrep=1, n=7, shape=(5, 3), seed=0):
    with engine.bucket_mb_scope(bucket_mb):
        kv = mx.kv.create("device")
        rng = np.random.RandomState(seed)
        keys = list(range(n))
        for k in keys:
            kv.init(k, nd.zeros(shape))
        vals = [[nd.array(rng.randn(*shape).astype(np.float32))
                 for _ in range(nrep)] for _ in keys]
        outs = [[nd.zeros(shape) for _ in range(nrep)] for _ in keys]
        kv.pushpull(keys, vals, out=outs)
        return [o[0].asnumpy() for o in outs]


@pytest.mark.parametrize("nrep", [1, 3])
def test_local_kvstore_bucketed_parity(nrep):
    bucketed = _local_pushpull(25, nrep=nrep)
    flat = _local_pushpull(0, nrep=nrep)
    for a, b in zip(bucketed, flat):
        np.testing.assert_array_equal(a, b)


def test_local_kvstore_bucketed_push_with_updater_parity():
    def run(mb):
        with engine.bucket_mb_scope(mb):
            kv = mx.kv.create("device")
            kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.5,
                                                 rescale_grad=1.0))
            rng = np.random.RandomState(0)
            keys = list(range(5))
            for k in keys:
                kv.init(k, nd.array(rng.randn(4).astype(np.float32)))
            kv.push(keys, [nd.array(rng.randn(4).astype(np.float32))
                           for _ in keys])
            outs = [nd.zeros((4,)) for _ in keys]
            kv.pull(keys, out=outs)
            return [o.asnumpy() for o in outs]
    for a, b in zip(run(25), run(0)):
        np.testing.assert_array_equal(a, b)


def test_local_bucketed_launches_fewer_programs():
    before = _counters()
    _local_pushpull(25, n=10)
    mid = _counters()
    _local_pushpull(0, n=10)
    after = _counters()
    assert _delta(before, mid, "comm.collectives") == 1  # one small bucket
    assert _delta(mid, after, "comm.collectives") == 10  # one per key
    assert _delta(mid, after, "comm.bucket.count") == 0  # hatch = no buckets


def test_local_bucketed_pushpull_retry_with_aliased_outs():
    """A mid-bucket fault after some out-writes must replay on the
    ORIGINAL payloads: outs alias the pushed grads (the Trainer pushpull
    pattern), so the retry would otherwise re-merge already-merged
    values."""
    from mxnet_tpu.resilience import faults
    with engine.bucket_mb_scope(25):
        kv = mx.kv.create("device")
        keys = list(range(4))
        for k in keys:
            kv.init(k, nd.zeros((3,)))
        grads = [[nd.array(np.full(3, float(k + 1), np.float32))
                  for _ in range(2)] for k in keys]
        # error on the SECOND per-key fault check: key 0's outs (aliasing
        # its pushed replicas) are already overwritten when it fires
        with faults.inject("kvstore.push:error:2"):
            kv.pushpull(keys, grads, out=grads)
    for k in keys:
        for rep in grads[k]:
            np.testing.assert_array_equal(rep.asnumpy(),
                                          np.full(3, 2.0 * (k + 1)))


def test_trainer_step_bucketed_parity():
    """End-to-end Gluon training parity: bucketed vs per-param gradient
    sync produce bit-identical parameters after several steps."""
    def train(mb, steps=4):
        mx.random.seed(0)
        np.random.seed(0)
        with engine.bucket_mb_scope(mb):
            net = nn.HybridSequential()
            with net.name_scope():
                net.add(nn.Dense(16, activation="relu"), nn.Dense(8),
                        nn.Dense(2))
            net.initialize(mx.init.Xavier())
            tr = gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9},
                               update_on_kvstore=True)
            x = nd.array(np.random.RandomState(1).randn(8, 10)
                         .astype(np.float32))
            y = nd.array(np.ones((8,), np.float32))
            loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
            for _ in range(steps):
                with autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                tr.step(8)
            return [p.data().asnumpy()
                    for _, p in sorted(net.collect_params().items())]
    for a, b in zip(train(25), train(0)):
        np.testing.assert_array_equal(a, b)


def test_trainer_escape_hatch_env_restores_per_param(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMM_BUCKET_MB", "0")
    before = _counters()
    net = nn.Dense(3, in_units=4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                       update_on_kvstore=True)
    with autograd.record():
        loss = net(nd.ones((2, 4))).sum()
    loss.backward()
    tr.step(2)
    after = _counters()
    assert _delta(before, after, "comm.bucket.count") == 0
    # per-key path: one launch per pushed parameter (weight + bias)
    assert _delta(before, after, "comm.collectives") == 2


# ===========================================================================
# dist kvstore (single-worker in-process; the allreduce path is identical,
# the cross-worker exchange short-circuits at num_workers == 1)
# ===========================================================================

def _dist_store():
    from mxnet_tpu.kvstore.kvstore_dist import KVStoreDist
    return KVStoreDist("dist_sync")


def _dist_pushpull(bucket_mb, n=6, shape=(4, 2), seed=0):
    with engine.bucket_mb_scope(bucket_mb):
        kv = _dist_store()
        rng = np.random.RandomState(seed)
        keys = list(range(n))
        for k in keys:
            kv.init(k, nd.zeros(shape))
        vals = [nd.array(rng.randn(*shape).astype(np.float32))
                for _ in keys]
        outs = [nd.zeros(shape) for _ in keys]
        kv.pushpull(keys, vals, out=outs)
        return [o.asnumpy() for o in outs]


def test_dist_kvstore_bucketed_parity():
    for a, b in zip(_dist_pushpull(25), _dist_pushpull(0)):
        np.testing.assert_array_equal(a, b)


def test_dist_bucketed_fewer_allreduces():
    before = _counters()
    _dist_pushpull(25, n=8)
    mid = _counters()
    _dist_pushpull(0, n=8)
    after = _counters()
    assert _delta(before, mid, "comm.collectives") == 1
    assert _delta(mid, after, "comm.collectives") == 8


def test_dist_bucketed_push_retries_per_bucket_with_key_context():
    """ISSUE 4 satellite: a failed bucketed push retries per-bucket and the
    error context names the member keys."""
    from mxnet_tpu.resilience import faults
    with engine.bucket_mb_scope(25):
        kv = _dist_store()
        keys = list(range(4))
        for k in keys:
            kv.init(k, nd.zeros((3,)))
        vals = [nd.array(np.full(3, float(k + 1), np.float32))
                for k in keys]
        before = _counters()
        with faults.inject("kvstore.push:error:1"):
            kv.push(keys, vals)
        after = _counters()
        assert _delta(before, after, "resilience.retries.kvstore.push") >= 1
        # the retry replayed the WHOLE bucket: every key holds its push
        for k in keys:
            out = nd.zeros((3,))
            kv.pull(k, out=out)
            np.testing.assert_array_equal(out.asnumpy(),
                                          np.full(3, float(k + 1)))


def test_dist_bucketed_push_exhaustion_names_bucket_keys():
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.resilience.errors import RetryExhausted
    with engine.bucket_mb_scope(25):
        kv = _dist_store()
        for k in range(3):
            kv.init(k, nd.zeros((2,)))
        with faults.inject("kvstore.push:error:*"):
            with pytest.raises(RetryExhausted) as ei:
                kv.push(list(range(3)),
                        [nd.array(np.ones(2, np.float32))] * 3)
        msg = str(ei.value)
        assert "keys=[0,1,2]" in msg  # bucket keys preserved in context


def test_dist_compression_stays_per_key():
    """2-bit compression keeps per-key residual state — it must bypass the
    bucketed path and stay bit-identical with bucketing on or off, through
    BOTH push+pull and the fused pushpull entry point."""
    def run(mb, via_pushpull):
        with engine.bucket_mb_scope(mb):
            kv = _dist_store()
            kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
            for k in range(3):
                kv.init(k, nd.zeros((4,)))
            vals = [nd.array(np.array([1.0, -1.0, 0.3, 0.0], np.float32))
                    for _ in range(3)]
            outs = [nd.zeros((4,)) for _ in range(3)]
            if via_pushpull:
                kv.pushpull(list(range(3)), vals, out=outs)
            else:
                kv.push(list(range(3)), vals)
                kv.pull(list(range(3)), out=outs)
            return [o.asnumpy() for o in outs]
    for via_pushpull in (False, True):
        ref = run(0, via_pushpull)
        for a, b in zip(run(25, via_pushpull), ref):
            np.testing.assert_array_equal(a, b)
        # quantized: 1.0 -> 0.5, -1.0 -> -0.5, 0.3 below threshold -> 0
        np.testing.assert_array_equal(ref[0], [0.5, -0.5, 0.0, 0.0])


def test_bucketed_pushpull_keeps_pull_fault_site():
    """The fused pushpull must not silently drop the kvstore.pull
    fault-injection site — a pull fault fires and is recovered."""
    from mxnet_tpu.resilience import faults
    with engine.bucket_mb_scope(25):
        kv = mx.kv.create("device")
        keys = list(range(3))
        for k in keys:
            kv.init(k, nd.zeros((4,)))
        vals = [nd.array(np.full(4, float(k + 1), np.float32))
                for k in keys]
        outs = [nd.zeros((4,)) for _ in keys]
        before = _counters()
        with faults.inject("kvstore.pull:error:1"):
            kv.pushpull(keys, vals, out=outs)
        after = _counters()
        assert _delta(before, after, "resilience.faults_injected") == 1
        for k in keys:
            np.testing.assert_array_equal(outs[k].asnumpy(),
                                          np.full(4, float(k + 1)))


# ===========================================================================
# acceptance: collectives_per_step drops below the parameter count for a
# resnet18-sized gradient set
# ===========================================================================

def test_resnet18_sized_sync_collectives_below_param_count(
        resnet18_grad_shapes):
    shapes = resnet18_grad_shapes
    assert len(shapes) == 62

    def run(mb):
        with engine.bucket_mb_scope(mb):
            kv = mx.kv.create("device")
            keys = list(range(len(shapes)))
            for k, s in zip(keys, shapes):
                kv.init(k, nd.zeros(s))
            grads = [nd.array(np.ones(s, np.float32)) for s in shapes]
            outs = [nd.zeros(s) for s in shapes]
            before = _counters()
            kv.pushpull(keys, grads, out=outs)
            after = _counters()
            return (_delta(before, after, "comm.collectives"),
                    [o.asnumpy() for o in outs])

    n_bucketed, r_bucketed = run(25)
    n_flat, r_flat = run(0)
    assert n_bucketed < len(shapes), \
        "bucketed sync must launch fewer collectives than parameters"
    assert n_bucketed <= 4   # ~46.8 MB of grads / 25 MB cap
    assert n_flat == len(shapes)
    for a, b in zip(r_bucketed, r_flat):
        np.testing.assert_array_equal(a, b)


# ===========================================================================
# eager collectives
# ===========================================================================

def test_eager_all_reduce_multi_matches_per_tensor():
    from mxnet_tpu.parallel import collectives
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()
    n = mesh.devices.size
    rng = np.random.RandomState(0)
    arrs = [jnp.asarray(rng.randn(n * k, 3).astype(np.float32))
            for k in (1, 2, 3)]
    before = _counters()
    fused = collectives.all_reduce_multi(arrs, mesh=mesh)
    mid = _counters()
    with engine.bucket_mb_scope(0):
        per_tensor = collectives.all_reduce_multi(arrs, mesh=mesh)
    after = _counters()
    for f, p in zip(fused, per_tensor):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p))
    for a, r in zip(arrs, fused):
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(a).reshape(n, -1).sum(0)
            .reshape(r.shape), rtol=1e-6)
    assert _delta(before, mid, "comm.collectives") == 1
    assert _delta(mid, after, "comm.collectives") == len(arrs)


def test_eager_all_reduce_multi_zero_size_array():
    """Zero-size arrays skip the bucketer but must still get a (empty)
    result slot, matching the per-tensor path's output shape."""
    from mxnet_tpu.parallel import collectives
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()
    n = mesh.devices.size
    arrs = [jnp.zeros((0, 4), jnp.float32), jnp.ones((n * 2, 3))]
    out = collectives.all_reduce_multi(arrs, mesh=mesh)
    assert out[0] is not None and tuple(out[0].shape) == (0, 4)
    np.testing.assert_allclose(np.asarray(out[1]), np.full((2, 3), float(n)))


def test_eager_all_reduce_multi_pads_undivisible_dim():
    """Pad-and-slice: a leading dim that does not divide the axis size is
    zero-padded to the next multiple inside the fused program; the result
    has ceil(m/n) rows (the last sums fewer real contributions) instead
    of raising."""
    from mxnet_tpu.parallel import collectives
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()
    n = mesh.devices.size
    if n == 1:
        pytest.skip("needs a >1-device mesh")
    m = n + 1
    x = jnp.asarray(np.arange(m * 3, dtype=np.float32).reshape(m, 3))
    (out,) = collectives.all_reduce_multi([x], mesh=mesh)
    k = -(-m // n)
    assert tuple(out.shape) == (k, 3)
    padded = np.zeros((k * n, 3), np.float32)
    padded[:m] = np.asarray(x)
    np.testing.assert_allclose(
        np.asarray(out), padded.reshape(n, -1).sum(0).reshape(k, 3))


def test_eager_all_reduce_multi_mixed_odd_even_parity():
    """Odd- and even-leading-dim arrays in one call agree between the
    bucketed fused path and the per-tensor escape hatch (which routes odd
    arrays through the same padded program)."""
    from mxnet_tpu.parallel import collectives
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()
    n = mesh.devices.size
    if n == 1:
        pytest.skip("needs a >1-device mesh")
    rng = np.random.RandomState(3)
    arrs = [jnp.asarray(rng.randn(n + 1, 2).astype(np.float32)),
            jnp.asarray(rng.randn(2 * n, 3).astype(np.float32)),
            jnp.asarray(rng.randn(2 * n + 1).astype(np.float32))]
    fused = collectives.all_reduce_multi(arrs, mesh=mesh)
    with engine.bucket_mb_scope(0):
        per_tensor = collectives.all_reduce_multi(arrs, mesh=mesh)
    for f, p in zip(fused, per_tensor):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p))
    for a, r in zip(arrs, fused):
        m = a.shape[0]
        k = -(-m // n)
        rest = tuple(a.shape[1:])
        padded = np.zeros((k * n,) + rest, np.float32)
        padded[:m] = np.asarray(a)
        np.testing.assert_allclose(
            np.asarray(r), padded.reshape(n, -1).sum(0).reshape(r.shape),
            rtol=1e-6)


def test_psum_bucketed_inside_shard_map():
    from mxnet_tpu.parallel import collectives
    from mxnet_tpu.parallel.mesh import local_mesh
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    mesh = local_mesh()
    ax = mesh.axis_names[0]
    n = mesh.devices.size
    xs = [jnp.ones((n, 3)), jnp.ones((n, 5)), jnp.ones((n, 2))]

    def f(a, b, c):
        return tuple(collectives.psum_bucketed([a, b, c], ax))

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P(ax), out_specs=P()))(
        *xs)
    for x, o in zip(xs, out):
        np.testing.assert_allclose(np.asarray(o),
                                   np.full((1, x.shape[1]), float(n)))


# ===========================================================================
# fused train-step paths: bucket_mb knob parity + retrace guard routing
# ===========================================================================

def _fused_train(bucket_mb, steps=3):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    step = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                tr, bucket_mb=bucket_mb)
    x = nd.array(np.random.RandomState(1).randn(8, 10).astype(np.float32))
    y = nd.array(np.ones((8,), np.float32))
    losses = [float(step(x, y).asnumpy()) for _ in range(steps)]
    return losses, [p.data().asnumpy()
                    for _, p in sorted(net.collect_params().items())]


def test_fused_step_bucket_knob_parity():
    (la, pa) = _fused_train(25)
    (lb, pb) = _fused_train(None)
    (lc, pc) = _fused_train(0)
    assert la == lb == lc
    for a, b, c in zip(pa, pb, pc):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_sharded_train_step_bucket_knob_parity():
    from mxnet_tpu.parallel import ShardedTrainStep
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def run(bucket_mb):
        params = {"w": jnp.ones((5, 3)), "b": jnp.zeros((3,))}
        st = ShardedTrainStep(loss_fn, params, mesh, optimizer="adamw",
                              lr=0.1, bucket_mb=bucket_mb)
        p, s = st.init()
        size = mesh.devices.size
        batch = {"x": jnp.arange(5.0 * 4 * size).reshape(4 * size, 5),
                 "y": jnp.ones((4 * size, 3))}
        for i in range(3):
            p, s, loss = st(p, s, batch, i)
        return np.asarray(p["w"]), float(loss)

    (wa, la), (wb, lb), (wc, lc) = run(25), run(None), run(0)
    np.testing.assert_array_equal(wa, wb)
    np.testing.assert_array_equal(wa, wc)
    assert la == lb == lc


def test_fused_step_retrace_routes_through_guard(monkeypatch):
    from mxnet_tpu.analysis import guard
    monkeypatch.setenv("MXNET_TPU_TRACE_GUARD_RETRACE_LIMIT", "1")
    prev = guard.set_mode("raise")
    try:
        mx.random.seed(0)
        net = nn.Dense(1, in_units=4)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        step = gluon.FusedTrainStep(net, gluon.loss.L2Loss(), tr)
        before = _counters()
        step(nd.ones((4, 4)), nd.ones((4, 1)))
        with pytest.raises(guard.TraceGuardError, match="FusedTrainStep"):
            step(nd.ones((6, 4)), nd.ones((6, 1)))
        after = _counters()
        assert _delta(before, after, "fused_step.retrace") == 1
        assert _delta(before, after, "analysis.guard.retrace") == 1
    finally:
        guard.set_mode(prev)


def test_sharded_train_step_retrace_routes_through_guard(monkeypatch):
    from mxnet_tpu.analysis import guard
    from mxnet_tpu.parallel import ShardedTrainStep
    from mxnet_tpu.parallel.mesh import local_mesh
    monkeypatch.setenv("MXNET_TPU_TRACE_GUARD_RETRACE_LIMIT", "1")
    mesh = local_mesh()
    size = mesh.devices.size

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    params = {"w": jnp.ones((5, 3))}
    st = ShardedTrainStep(loss_fn, params, mesh, optimizer="sgd", lr=0.1)
    p, s = st.init()
    p, s, _ = st(p, s, {"x": jnp.ones((4 * size, 5))}, 0)
    prev = guard.set_mode("raise")
    try:
        before = _counters()
        with pytest.raises(guard.TraceGuardError, match="ShardedTrainStep"):
            st(p, s, {"x": jnp.ones((8 * size, 5))}, 1)
        after = _counters()
        assert _delta(before, after, "train_step.retrace") == 1
        assert _delta(before, after, "analysis.guard.retrace") == 1
    finally:
        guard.set_mode(prev)


# ===========================================================================
# dataloader satellite: batched device→host conversion
# ===========================================================================

def test_mp_batchify_single_sync():
    from mxnet_tpu.gluon.data.dataloader import default_mp_batchify_fn
    rng = np.random.RandomState(0)
    samples_np = [rng.randn(3, 4).astype(np.float32) for _ in range(8)]
    samples = [nd.array(a) for a in samples_np]
    before = _counters()
    out = default_mp_batchify_fn(samples)
    after = _counters()
    assert isinstance(out, np.ndarray)
    np.testing.assert_array_equal(out, np.stack(samples_np, axis=0))
    # ONE device→host sync for the whole batch, 7 saved
    assert _delta(before, after, "ndarray.sync.asnumpy") == 1
    assert _delta(before, after,
                  "dataloader.batchify.syncs_saved") == len(samples) - 1


def test_mp_batchify_nested_and_numpy_paths_unchanged():
    from mxnet_tpu.gluon.data.dataloader import default_mp_batchify_fn
    rng = np.random.RandomState(0)
    pairs = [(nd.array(rng.randn(2).astype(np.float32)), float(i))
             for i in range(4)]
    data, labels = default_mp_batchify_fn(pairs)
    assert data.shape == (4, 2)
    np.testing.assert_array_equal(labels, np.arange(4.0))


# ===========================================================================
# tooling: parse_log --comm
# ===========================================================================

def test_parse_log_comm_table(tmp_path):
    with engine.bucket_mb_scope(25):
        kv = mx.kv.create("device")
        keys = list(range(6))
        for k in keys:
            kv.init(k, nd.zeros((50,)))
        kv.pushpull(keys, [nd.array(np.ones(50, np.float32))
                           for _ in keys],
                    out=[nd.zeros((50,)) for _ in keys])
    dump = str(tmp_path / "telemetry.json")
    telemetry.dump(dump)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--comm"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    assert "comm.collectives" in proc.stdout
    assert "comm.bucket.count" in proc.stdout
    assert "avg_bucket_kb" in proc.stdout
    # csv mode too
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--comm", "--format", "csv"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.startswith("metric,value")


def test_bucket_spans_visible_in_trace_dump(tmp_path):
    """Per-bucket spans land in the chrome-trace dump — the overlap story
    is inspectable."""
    with engine.bucket_mb_scope(25):
        kv = mx.kv.create("device")
        for k in range(4):
            kv.init(k, nd.zeros((10,)))
        kv.pushpull(list(range(4)),
                    [nd.array(np.ones(10, np.float32)) for _ in range(4)],
                    out=[nd.zeros((10,)) for _ in range(4)])
    path = str(tmp_path / "trace.json")
    telemetry.dump_trace(path)
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    assert any(str(e.get("name", "")).startswith("comm.bucket[")
               for e in events)

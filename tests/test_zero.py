"""ZeRO-1 weight-update sharding (ISSUE 9) tests.

Coverage: persistent BucketLayout freeze/checkpoint/re-partition semantics,
pack/unpack padding round-trips, bit-exact ZeRO-vs-replicated final params
on a resnet18-sized set via single-process injectable collectives (the
CommitCoordinator fake-gather pattern — CPU tier-1 cannot run multiprocess
collectives), SGD/momentum + Adam + multi-precision fp16, one fused update
dispatch per dtype-bucket, `opt.state_bytes_per_rank` = replicated total /
world, elastic shrink/grow state migration, SnapshotCheckpointer + orbax
round-trips (incl. restore onto a different world size), Trainer(zero=)
end-to-end, the dist store's per-bucket 2-bit compression residuals parity,
the in-mesh reduce_scatter_multi/all_gather_multi collectives, fault-site
retry, and the `parse_log --comm` rows.
"""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon, nd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.optimizer import (Updater, ZeroComm, ZeroUpdater,
                                 create as opt_create)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counters():
    return dict(telemetry.snapshot()["counters"])


def _delta(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


def _hist_count(name):
    return telemetry.snapshot()["histograms"].get(name, {}).get("count", 0)


# ===========================================================================
# injectable single-process fleet (the CommitCoordinator fake-gather
# pattern): each simulated rank runs its ZeroUpdater on its own thread; the
# fleet object is the collective fabric — a barrier'd mailbox that sums
# contributions in rank order (the fixed order keeps fp32 runs bit-exact
# against a baseline summed the same way)
# ===========================================================================
class FakeFleet:
    def __init__(self, world):
        self.world = world
        self.lock = threading.Lock()
        self.barrier = threading.Barrier(world)
        self.box = {}

    def comm(self, rank):
        return _FakeComm(self, rank)


class _FakeComm:
    def __init__(self, fleet, rank):
        self._fleet = fleet
        self.rank = rank

    @property
    def world(self):
        return self._fleet.world

    def _exchange(self, tag, spec, value):
        fleet = self._fleet
        with fleet.lock:
            fleet.box.setdefault((tag, spec.index), {})[self.rank] = \
                np.asarray(value)
        fleet.barrier.wait()
        # this rank's own copy, taken between the barriers: the mailbox is
        # keyed by (tag, bucket) and not by call, so a rank that has left
        # the second barrier writes its NEXT contribution (the momentum
        # gathered for a checkpoint right after the weights, the next
        # step's gradient) into the very dict a slower rank still sums
        parts = dict(fleet.box[(tag, spec.index)])
        fleet.barrier.wait()
        return parts

    def reduce_scatter(self, spec, flat):
        parts = self._exchange("rs", spec, flat)
        total = parts[0].copy()
        for r in range(1, self.world):
            total = total + parts[r]   # rank order, matching the baseline
        lo = self.rank * spec.shard
        return jnp.asarray(total[lo:lo + spec.shard])

    def all_gather(self, spec, shard):
        parts = self._exchange("ag", spec, shard)
        return jnp.asarray(np.concatenate(
            [parts[r] for r in range(self.world)]))

    def all_reduce(self, spec, value):
        # LAMB per-segment norm completion (ISSUE 10): sum in rank order,
        # matching the replicated baseline's accumulation
        parts = self._exchange("ar", spec, value)
        total = parts[0].copy()
        for r in range(1, self.world):
            total = total + parts[r]
        return jnp.asarray(total)


def _run_fleet(world, fn):
    """Run fn(rank, comm) on `world` threads; re-raise the first error."""
    fleet = FakeFleet(world)
    errs = [None] * world

    def wrap(rank):
        try:
            fn(rank, fleet.comm(rank))
        except BaseException as e:  # noqa: BLE001 - test harness
            errs[rank] = e
            fleet.barrier.abort()

    threads = [threading.Thread(target=wrap, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errs:
        if e is not None:
            raise e


# ===========================================================================
# BucketLayout
# ===========================================================================

def test_bucket_layout_freeze_pad_and_segments():
    entries = [(str(i), jnp.ones((n,), jnp.float32))
               for i, n in enumerate((5, 3, 7))]
    layout = engine.BucketLayout.from_entries(entries, world=4,
                                              cap_bytes=1 << 20)
    assert len(layout) == 1
    spec = layout.buckets[0]
    assert spec.size == 15 and spec.padded == 16 and spec.shard == 4
    assert spec.keys == ["0", "1", "2"]
    # rank 1 owns flat [4, 8): tail of key 0 (1 elem) + all of key 1 (3)
    assert spec.shard_segments(1) == [("0", 0, 1, 4), ("1", 1, 3, 0)]
    # rank 3 owns [12, 16): 3 real elements of key 2, 1 padding elem
    assert spec.shard_segments(3) == [("2", 0, 3, 4)]


def test_bucket_layout_payload_roundtrip_and_reworld():
    entries = [(str(i), jnp.ones((6,), jnp.float32)) for i in range(4)]
    layout = engine.BucketLayout.from_entries(entries, world=4,
                                              cap_bytes=2 * 6 * 4)
    payload = layout.to_payload()
    back = engine.BucketLayout.from_payload(payload)
    assert back.world == 4
    assert [b.keys for b in back] == [b.keys for b in layout]
    assert [b.shard for b in back] == [b.shard for b in layout]
    # elastic re-partition: same buckets, new shard boundaries
    two = layout.rebuild_for_world(2)
    assert two.world == 2
    assert [b.keys for b in two] == [b.keys for b in layout]
    assert all(b2.shard == b4.shard * 2
               for b2, b4 in zip(two, layout))


def test_bucket_layout_frozen_guard():
    entries = [(str(i), jnp.ones((4,), jnp.float32)) for i in range(3)]
    layout = engine.BucketLayout.from_entries(entries, 2, 1 << 20)
    layout.assert_matches(["0", "1", "2"])
    with pytest.raises(ValueError, match="frozen"):
        layout.assert_matches(["0", "1"])
    with pytest.raises(ValueError, match="frozen"):
        layout.assert_matches(["0", "2", "1"])


def test_pack_unpack_flat_padded_roundtrip():
    rng = np.random.RandomState(0)
    raws = [jnp.asarray(rng.randn(*s).astype(np.float32))
            for s in [(3, 4), (7,)]]
    layout = engine.BucketLayout.from_entries(enumerate(raws), world=4,
                                              cap_bytes=1 << 20)
    spec = layout.buckets[0]
    flat = engine.pack_flat(spec, raws)
    assert flat.shape == (spec.padded,) == (20,)
    np.testing.assert_array_equal(np.asarray(flat[19:]), [0.0])
    parts = engine.unpack_flat(spec, flat)
    for r, p in zip(raws, parts):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))


# ===========================================================================
# acceptance: bit-exact ZeRO vs replicated on a resnet18-sized param set,
# through injectable single-process collectives
# ===========================================================================

def _replicated_phases(optname, shapes, init_w, phases, **opt_kw):
    """Replicated baseline over a SEQUENCE of (grads_per_rank, steps)
    phases with ONE continuously-carried updater (momentum/moments survive
    phase changes — the elastic baseline needs this)."""
    opt = opt_create(optname, **opt_kw)
    upd = Updater(opt)
    ws = [nd.array(w, dtype=w.dtype) for w in init_w]
    for grads_per_rank, steps in phases:
        world = len(grads_per_rank)
        for _ in range(steps):
            for i in range(len(shapes)):
                total = grads_per_rank[0][i].copy()
                for r in range(1, world):   # rank order, like the fleet
                    total = total + grads_per_rank[r][i]
                upd(i, nd.array(total, dtype=total.dtype), ws[i])
    return [w.asnumpy() for w in ws]


def _replicated_final(optname, shapes, init_w, grads_per_rank, steps,
                      **opt_kw):
    return _replicated_phases(optname, shapes, init_w,
                              [(grads_per_rank, steps)], **opt_kw)


def _zero_final(optname, shapes, init_w, grads_per_rank, steps, world,
                **opt_kw):
    keys = [str(i) for i in range(len(shapes))]
    outs = [None] * world

    def run(rank, comm):
        opt = opt_create(optname, **opt_kw)
        zu = ZeroUpdater(opt, comm=comm)
        ws = [nd.array(w, dtype=w.dtype) for w in init_w]
        for _ in range(steps):
            zu.step(keys, [jnp.asarray(g) for g in grads_per_rank[rank]],
                    ws)
        outs[rank] = [w.asnumpy() for w in ws]

    _run_fleet(world, run)
    return outs


# dyadic hyperparameters (the PR 5 exactness trick): power-of-two lr /
# momentum / betas make every scalar·tensor product exact in fp32, so the
# fused flat kernel (where XLA may contract mul+add into FMA) and the
# eager per-op path round identically on ARBITRARY data — bit parity
# without constraining the gradients
_SGD_DYADIC = {"learning_rate": 0.125, "momentum": 0.5, "rescale_grad": 1.0}
_ADAM_DYADIC = {"learning_rate": 0.125, "beta1": 0.5, "beta2": 0.5,
                "epsilon": 2.0 ** -8, "rescale_grad": 1.0}


@pytest.mark.parametrize("optname,opt_kw", [
    ("sgd", _SGD_DYADIC),
    ("adam", _ADAM_DYADIC),
])
def test_zero_resnet18_sized_parity_injectable_fleet(
        optname, opt_kw, resnet18_grad_shapes):
    """ISSUE 9 acceptance: final params bit-identical to the replicated
    update on the resnet18-sized 62-tensor param set, world=2, simulated
    on one process (dyadic lr keeps every fp32 step exactly representable;
    the fake fleet and the baseline sum ranks in the same order)."""
    shapes = resnet18_grad_shapes
    assert len(shapes) == 62
    world, steps = 2, 2
    rng = np.random.RandomState(0)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[rng.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(world)]
    ref = _replicated_final(optname, shapes, init_w, grads, steps, **opt_kw)
    zouts = _zero_final(optname, shapes, init_w, grads, steps, world,
                        **opt_kw)
    for rank in range(world):
        for a, b in zip(zouts[rank], ref):
            np.testing.assert_array_equal(a, b)


def test_zero_world4_with_padding_parity():
    """Sizes that do NOT divide the world exercise the zero-padded shard
    tail on every rank."""
    shapes = [(5, 3), (7,), (4, 4), (3,)]   # 15+7+16+3 = 41, world 4
    world, steps = 4, 3
    rng = np.random.RandomState(1)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[rng.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(world)]
    kw = {"learning_rate": 0.125, "momentum": 0.5, "rescale_grad": 1.0}
    ref = _replicated_final("sgd", shapes, init_w, grads, steps, **kw)
    zouts = _zero_final("sgd", shapes, init_w, grads, steps, world, **kw)
    for rank in range(world):
        for a, b in zip(zouts[rank], ref):
            np.testing.assert_array_equal(a, b)


def test_zero_multi_precision_fp16_parity():
    """fp16 weights with multi_precision: the fused flat kernel carries an
    fp32 master shard and stays bit-identical to mp_sgd_mom_update."""
    shapes = [(6, 2), (10,)]
    rng = np.random.RandomState(2)
    init_w = [(rng.randn(*s) * 0.1).astype(np.float16) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float16) for s in shapes]]
    kw = {"learning_rate": 0.125, "momentum": 0.5, "rescale_grad": 1.0,
          "multi_precision": True}
    ref = _replicated_final("sgd", shapes, init_w, grads, 3, **kw)
    zouts = _zero_final("sgd", shapes, init_w, grads, 3, 1, **kw)
    for a, b in zip(zouts[0], ref):
        assert a.dtype == np.float16
        np.testing.assert_array_equal(a, b)


def test_zero_multi_precision_restore_keeps_master_bits():
    """A restored fp32 master must NOT be re-derived from the rounded
    fp16 store weights: resume + 1 step == uninterrupted 3 steps,
    bitwise."""
    shapes = [(6, 2), (10,)]
    rng = np.random.RandomState(8)
    init_w = [(rng.randn(*s) * 0.1).astype(np.float16) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float16) for s in shapes]
             for _ in range(3)]
    kw = {"learning_rate": 0.125, "momentum": 0.5, "rescale_grad": 1.0,
          "multi_precision": True}
    keys = ["0", "1"]

    def steps(zu, ws, grad_steps):
        for gs in grad_steps:
            zu.step(keys, [jnp.asarray(g) for g in gs], ws)

    zu = ZeroUpdater(opt_create("sgd", **kw))
    ws = [nd.array(w, dtype=w.dtype) for w in init_w]
    steps(zu, ws, grads)
    ref = [w.asnumpy() for w in ws]

    zu2 = ZeroUpdater(opt_create("sgd", **kw))
    ws2 = [nd.array(w, dtype=w.dtype) for w in init_w]
    steps(zu2, ws2, grads[:2])
    payload = zu2.state_payload()
    saved_w = [w.asnumpy() for w in ws2]
    zu3 = ZeroUpdater(opt_create("sgd", **kw))
    zu3.optimizer._index_update_count = dict(
        zu2.optimizer._index_update_count)
    zu3.optimizer.num_update = zu2.optimizer.num_update
    zu3.load_state_payload(payload)
    ws3 = [nd.array(w, dtype=w.dtype) for w in saved_w]
    steps(zu3, ws3, grads[2:])
    for a, b in zip((w.asnumpy() for w in ws3), ref):
        np.testing.assert_array_equal(a, b)


# ===========================================================================
# LAMB through the ZeroUpdater (ISSUE 10: closes the PR 9 "fused flat
# kernels for more optimizers" follow-on — the per-segment norm kernel)
# ===========================================================================
_LAMB_KW = {"learning_rate": 0.01, "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-6, "rescale_grad": 1.0}


def test_zero_lamb_resnet18_sized_parity_vs_eager(resnet18_grad_shapes):
    """ISSUE 10 satellite: ZeRO LAMB (two-pass flat update with
    per-segment norms completed by ONE tiny all-reduce) vs the eager
    per-param LAMB updater on the resnet18-sized 62-tensor key set,
    world=2. The flat path accumulates each parameter's ‖w‖/‖g‖ in shard
    segments rather than `jnp.linalg.norm`'s single reduce, so parity is
    fp32-round-off (documented tolerance), not bitwise."""
    shapes = resnet18_grad_shapes
    assert len(shapes) == 62
    world, steps = 2, 2
    rng = np.random.RandomState(5)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float32) for s in shapes]
             for _ in range(world)]
    ref = _replicated_final("lamb", shapes, init_w, grads, steps,
                            **_LAMB_KW)
    zouts = _zero_final("lamb", shapes, init_w, grads, steps, world,
                        **_LAMB_KW)
    for rank in range(world):
        for a, b in zip(zouts[rank], ref):
            np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-6)


def test_zero_lamb_world4_cross_boundary_segments():
    """Shapes that straddle shard boundaries at world=4: each rank sees
    only a PARTIAL slice of most parameters, so the trust-ratio norms are
    only correct if the per-segment partials really complete across ranks
    through comm.all_reduce."""
    shapes = [(7, 3), (11,), (6, 5), (9,)]   # 21+11+30+9 = 71, world 4
    world, steps = 4, 3
    rng = np.random.RandomState(6)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float32) for s in shapes]
             for _ in range(world)]
    ref = _replicated_final("lamb", shapes, init_w, grads, steps,
                            **_LAMB_KW)
    zouts = _zero_final("lamb", shapes, init_w, grads, steps, world,
                        **_LAMB_KW)
    for rank in range(world):
        for a, b in zip(zouts[rank], ref):
            np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-6)
    # the norm exchange is visible in telemetry
    assert _counters().get("comm.all_reduce", 0) > 0


def test_zero_lamb_bounds_and_wd():
    """lower/upper trust-ratio bounds and weight decay follow the eager
    lamb_update_phase1/phase2 semantics through the flat path."""
    shapes = [(16,), (4, 4)]
    rng = np.random.RandomState(7)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float32) for s in shapes]]
    kw = dict(_LAMB_KW, wd=0.01, lower_bound=0.5, upper_bound=2.0)
    ref = _replicated_final("lamb", shapes, init_w, grads, 2, **kw)
    zouts = _zero_final("lamb", shapes, init_w, grads, 2, 1, **kw)
    for a, b in zip(zouts[0], ref):
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-6)


def test_zero_lamb_state_roundtrip_resume_parity():
    """save/restore mid-run: resume + 1 step == uninterrupted 2 steps
    (the lamb mean/var slots ride the generic world-portable payload)."""
    shapes = [(6, 2), (10,)]
    rng = np.random.RandomState(9)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float32) for s in shapes]
             for _ in range(2)]
    keys = ["0", "1"]

    zu = ZeroUpdater(opt_create("lamb", **_LAMB_KW))
    ws = [nd.array(w) for w in init_w]
    for gs in grads:
        zu.step(keys, [jnp.asarray(g) for g in gs], ws)
    ref = [w.asnumpy() for w in ws]

    zu2 = ZeroUpdater(opt_create("lamb", **_LAMB_KW))
    ws2 = [nd.array(w) for w in init_w]
    zu2.step(keys, [jnp.asarray(g) for g in grads[0]], ws2)
    payload = zu2.state_payload()
    zu3 = ZeroUpdater(opt_create("lamb", **_LAMB_KW))
    zu3.optimizer._index_update_count = dict(
        zu2.optimizer._index_update_count)
    zu3.optimizer.num_update = zu2.optimizer.num_update
    zu3.load_state_payload(payload)
    ws3 = [nd.array(w.asnumpy()) for w in ws2]
    zu3.step(keys, [jnp.asarray(g) for g in grads[1]], ws3)
    for a, b in zip((w.asnumpy() for w in ws3), ref):
        np.testing.assert_array_equal(a, b)


# ===========================================================================
# Pallas flat kernels through the ZeroUpdater (ISSUE 10 tentpole): the
# interpreter runs the REAL kernels on the CPU backend — parity evidence
# only, never perf evidence
# ===========================================================================
@pytest.mark.pallas
@pytest.mark.parametrize("optname,opt_kw", [
    ("sgd", _SGD_DYADIC),
    ("adam", _ADAM_DYADIC),
])
def test_zero_pallas_flat_kernels_world2_bit_parity(optname, opt_kw):
    """With the Pallas gate on, ZeroUpdater dispatches the flat-segment
    kernels (counted in ops.pallas.dispatch.*) and the world=2 sharded run
    stays BIT-identical to the replicated eager baseline (dyadic
    hyperparameters, the FMA-immunity trick above)."""
    from mxnet_tpu.ops import fused_optimizer as fo
    assert fo.use_pallas_flat()
    shapes = [(5, 3), (17,), (4, 4), (3,)]
    world, steps = 2, 2
    rng = np.random.RandomState(12)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[rng.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(world)]
    before = _counters()
    ref = _replicated_final(optname, shapes, init_w, grads, steps, **opt_kw)
    zouts = _zero_final(optname, shapes, init_w, grads, steps, world,
                        **opt_kw)
    after = _counters()
    for rank in range(world):
        for a, b in zip(zouts[rank], ref):
            np.testing.assert_array_equal(a, b)
    key = "ops.pallas.dispatch.flat_%s" % optname
    assert after.get(key, 0) > before.get(key, 0)


@pytest.mark.pallas
def test_zero_pallas_multi_precision_fp16_bit_parity():
    """fp16 + fp32-master through the Pallas flat kernel: bit-identical
    to the replicated mp_sgd_mom_update baseline."""
    shapes = [(6, 2), (10,)]
    rng = np.random.RandomState(13)
    init_w = [(rng.randn(*s) * 0.1).astype(np.float16) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float16) for s in shapes]]
    kw = {"learning_rate": 0.125, "momentum": 0.5, "rescale_grad": 1.0,
          "multi_precision": True}
    ref = _replicated_final("sgd", shapes, init_w, grads, 3, **kw)
    zouts = _zero_final("sgd", shapes, init_w, grads, 3, 1, **kw)
    for a, b in zip(zouts[0], ref):
        assert a.dtype == np.float16
        np.testing.assert_array_equal(a, b)


@pytest.mark.pallas
def test_zero_pallas_lamb_world2_parity():
    """LAMB's Pallas two-pass (phase1+norm partials, trust-ratio apply)
    through the sharded updater, world=2 — fp32-round-off parity vs the
    eager per-param baseline (norm association differs; see module
    docstring of ops/fused_optimizer.py)."""
    shapes = [(7, 3), (11,), (5, 5)]
    world, steps = 2, 2
    rng = np.random.RandomState(14)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    grads = [[(rng.randn(*s) * 0.1).astype(np.float32) for s in shapes]
             for _ in range(world)]
    before = _counters()
    ref = _replicated_final("lamb", shapes, init_w, grads, steps,
                            **_LAMB_KW)
    zouts = _zero_final("lamb", shapes, init_w, grads, steps, world,
                        **_LAMB_KW)
    after = _counters()
    for rank in range(world):
        for a, b in zip(zouts[rank], ref):
            np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-6)
    assert after.get("ops.pallas.dispatch.flat_lamb1", 0) > \
        before.get("ops.pallas.dispatch.flat_lamb1", 0)
    assert after.get("ops.pallas.dispatch.flat_lamb2", 0) > \
        before.get("ops.pallas.dispatch.flat_lamb2", 0)


def test_zero_and_compression_are_mutually_exclusive():
    from mxnet_tpu.base import MXNetError
    kv = _dist_store()
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    with pytest.raises(MXNetError, match="mutually exclusive"):
        kv.set_optimizer(opt_create("sgd", learning_rate=0.1), zero=True)
    kv2 = _dist_store()
    kv2.set_optimizer(opt_create("sgd", learning_rate=0.1), zero=True)
    with pytest.raises(MXNetError, match="compression"):
        kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})


def test_zero_skips_zero_size_grads_consistently():
    """Zero-size grads never enter a bucket — both the freeze and every
    later step must filter them the same way (a desync here broke the
    frozen-layout guard on step 2)."""
    zu = ZeroUpdater(opt_create("sgd", learning_rate=0.5))
    ws = [nd.array(np.ones(4, np.float32)), nd.zeros((0,)),
          nd.array(np.ones(2, np.float32))]
    gs = [jnp.ones((4,), jnp.float32), jnp.zeros((0,), jnp.float32),
          jnp.ones((2,), jnp.float32)]
    for _ in range(2):
        zu.step(["0", "1", "2"], gs, ws)
    assert zu.layout.keys() == ["0", "2"]
    np.testing.assert_array_equal(ws[0].asnumpy(), np.zeros(4))
    assert ws[1].asnumpy().size == 0


def test_zero_rejects_unsupported_optimizer():
    with pytest.raises(ValueError, match="SGD, Adam and LAMB"):
        ZeroUpdater(opt_create("rmsprop"))


def test_zero_frozen_layout_rejects_changed_key_set():
    zu = ZeroUpdater(opt_create("sgd", learning_rate=0.5))
    ws = [nd.array(np.ones(4, np.float32)) for _ in range(2)]
    gs = [jnp.ones((4,), jnp.float32)] * 2
    zu.step(["0", "1"], gs, ws)
    with pytest.raises(ValueError, match="frozen"):
        zu.step(["0"], gs[:1], ws[:1])


# ===========================================================================
# telemetry contract: one fused dispatch per dtype-bucket, sharded-state
# gauge = replicated total / world
# ===========================================================================

def test_one_fused_dispatch_per_bucket_not_per_param():
    shapes = [(64,)] * 6   # 256 B each
    rng = np.random.RandomState(3)
    ws = [nd.array(rng.randn(*s).astype(np.float32)) for s in shapes]
    gs = [jnp.asarray(rng.randn(*s).astype(np.float32)) for s in shapes]
    keys = [str(i) for i in range(len(shapes))]
    # cap of two grads per bucket -> 3 buckets for 6 params
    zu = ZeroUpdater(opt_create("sgd", learning_rate=0.5, momentum=0.5),
                     cap_bytes=2 * 256)
    before, h0 = _counters(), _hist_count("opt.fused_update_ms")
    zu.step(keys, gs, ws)
    after, h1 = _counters(), _hist_count("opt.fused_update_ms")
    assert len(zu.layout) == 3
    assert h1 - h0 == 3                       # per bucket, not per param
    assert _delta(before, after, "comm.reduce_scatter") == 3
    assert _delta(before, after, "comm.all_gather") == 3


def test_state_bytes_per_rank_is_total_over_world():
    # bucket sizes divisible by world -> zero padding, exact division
    shapes = [(8, 4), (16,), (4, 4)]   # 64 elements total
    world = 4
    rng = np.random.RandomState(4)
    grads = [[rng.randn(*s).astype(np.float32) for s in shapes]
             for _ in range(world)]
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    per_rank = [None] * world

    def run(rank, comm):
        zu = ZeroUpdater(opt_create("adam", learning_rate=0.125),
                         comm=comm)
        ws = [nd.array(w) for w in init_w]
        zu.step([str(i) for i in range(len(shapes))],
                [jnp.asarray(g) for g in grads[rank]], ws)
        per_rank[rank] = zu.state_bytes_per_rank()

    _run_fleet(world, run)
    replicated_total = 64 * 4 * 2              # mean+var, fp32
    assert all(b == replicated_total // world for b in per_rank)
    gauge = telemetry.snapshot()["gauges"].get("opt.state_bytes_per_rank")
    assert gauge and gauge["value"] == replicated_total // world


# ===========================================================================
# elastic shrink/grow: owned-shard state migrates bit-preserving across a
# world-size change
# ===========================================================================

def test_elastic_world_change_migrates_state_bit_preserving():
    shapes = [(5, 3), (7,), (4, 4)]
    rng = np.random.RandomState(5)
    init_w = [rng.randn(*s).astype(np.float32) for s in shapes]
    g4 = [[rng.randn(*s).astype(np.float32) for s in shapes]
          for _ in range(4)]
    g2 = [[rng.randn(*s).astype(np.float32) for s in shapes]
          for _ in range(2)]
    kw = {"learning_rate": 0.125, "momentum": 0.5, "rescale_grad": 1.0}
    keys = [str(i) for i in range(len(shapes))]

    # uninterrupted baseline: ONE carried updater — 2 steps with the
    # 4-rank sums, then 2 with the 2-rank sums (replicated math never
    # cares about world size, but momentum must survive the transition)
    ref = _replicated_phases("sgd", shapes, init_w, [(g4, 2), (g2, 2)],
                             **kw)

    # phase 1: world=4 fleet runs 2 steps, checkpoints (full-state payload)
    payload_box, w_box = {}, {}

    def phase1(rank, comm):
        zu = ZeroUpdater(opt_create("sgd", **kw), comm=comm)
        ws = [nd.array(w) for w in init_w]
        for _ in range(2):
            zu.step(keys, [jnp.asarray(g) for g in g4[rank]], ws)
        payload = zu.state_payload()   # collective: every rank gathers
        if rank == 0:   # payload is identical on every rank
            payload_box[0] = payload
            w_box[0] = [w.asnumpy() for w in ws]

    _run_fleet(4, phase1)

    # phase 2: SHRUNK world=2 fleet restores the payload and continues
    outs = [None, None]

    def phase2(rank, comm):
        zu = ZeroUpdater(opt_create("sgd", **kw), comm=comm)
        zu.load_state_payload(payload_box[0])
        assert zu.layout.world == 2     # re-partitioned shard boundaries
        ws = [nd.array(w) for w in w_box[0]]
        for _ in range(2):
            zu.step(keys, [jnp.asarray(g) for g in g2[rank]], ws)
        outs[rank] = [w.asnumpy() for w in ws]

    _run_fleet(2, phase2)
    for rank in range(2):
        for a, b in zip(outs[rank], ref):
            np.testing.assert_array_equal(a, b)


# ===========================================================================
# checkpoint round-trips: SnapshotCheckpointer (pickle) and orbax, incl.
# restore onto a different world size
# ===========================================================================

def _seed_updater(steps=2):
    rng = np.random.RandomState(6)
    shapes = [(5, 3), (7,)]
    zu = ZeroUpdater(opt_create("adam", learning_rate=0.125))
    ws = [nd.array(rng.randn(*s).astype(np.float32)) for s in shapes]
    for _ in range(steps):
        zu.step(["0", "1"],
                [jnp.asarray(rng.randn(*s).astype(np.float32))
                 for s in shapes], ws)
    return zu, ws


def test_snapshot_checkpointer_roundtrip(tmp_path):
    from mxnet_tpu.resilience import SnapshotCheckpointer
    zu, _ = _seed_updater()
    ck = SnapshotCheckpointer(str(tmp_path), keep=None)
    ck.save(1, {"zero": zu.state_payload()})
    step, tree = ck.restore(1)
    zu2 = ZeroUpdater(opt_create("adam", learning_rate=0.125))
    zu2.load_state_payload(tree["zero"])
    assert [b.keys for b in zu2.layout] == [b.keys for b in zu.layout]
    for spec in zu.layout:
        for slot in ("mean", "var"):
            np.testing.assert_array_equal(
                np.asarray(zu._states[spec.index][slot]),
                np.asarray(zu2._states[spec.index][slot]))


def test_orbax_zero_roundtrip_onto_different_world(tmp_path):
    from mxnet_tpu.parallel.checkpoint import (restore_zero_state,
                                               save_zero_state)
    zu, _ = _seed_updater()
    save_zero_state(str(tmp_path), zu, step=2)

    class TwoRankComm(ZeroComm):
        world = 2

        def __init__(self, rank):
            self.rank = rank

    restored = {}
    for rank in range(2):
        zu_r = ZeroUpdater(opt_create("adam", learning_rate=0.125),
                           comm=TwoRankComm(rank))
        restore_zero_state(str(tmp_path), zu_r)
        assert zu_r.layout.world == 2
        restored[rank] = zu_r
    # the two half-shards concatenate back to the saved full state
    for spec in zu.layout:
        spec2 = restored[0].layout.buckets[spec.index]
        for slot in ("mean", "var"):
            full = np.concatenate([
                np.asarray(restored[r]._states[spec.index][slot])
                for r in range(2)])[:spec.size]
            np.testing.assert_array_equal(
                full, np.asarray(zu._states[spec.index][slot])[:spec.size])
        assert spec2.shard * 2 == spec2.padded


# ===========================================================================
# Trainer / kvstore end-to-end
# ===========================================================================

def _train_gluon(zero, optname="sgd", steps=4, opt_kw=None, env_cap=None):
    mx.random.seed(0)
    np.random.seed(0)
    scope = engine.bucket_mb_scope(env_cap) if env_cap is not None else \
        engine.bucket_mb_scope(None)
    with scope:
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(8),
                    nn.Dense(2))
        net.initialize(mx.init.Xavier())
        kw = opt_kw or {"learning_rate": 0.125, "momentum": 0.5}
        tr = gluon.Trainer(net.collect_params(), optname, dict(kw),
                           update_on_kvstore=True, zero=zero)
        x = nd.array(np.random.RandomState(1).randn(8, 10)
                     .astype(np.float32))
        y = nd.array(np.ones((8,), np.float32))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        for _ in range(steps):
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(8)
        return net, tr, [p.data().asnumpy()
                         for _, p in sorted(net.collect_params().items())]


@pytest.mark.parametrize("optname,opt_kw", [
    ("sgd", {"learning_rate": 0.125, "momentum": 0.5}),
    ("adam", {"learning_rate": 0.125, "beta1": 0.5, "beta2": 0.5,
              "epsilon": 2.0 ** -8}),
])
def test_trainer_zero_parity_end_to_end(optname, opt_kw):
    _, _, a = _train_gluon(True, optname, opt_kw=opt_kw)
    _, _, b = _train_gluon(False, optname, opt_kw=opt_kw)
    for pa, pb in zip(a, b):
        np.testing.assert_array_equal(pa, pb)


def test_trainer_zero_env_optin(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_ZERO", "1")
    net = nn.Dense(3, in_units=4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    with autograd.record():
        loss = net(nd.ones((2, 4))).sum()
    loss.backward()
    tr.step(2)
    assert isinstance(tr._kvstore._updater, ZeroUpdater)
    assert tr._update_on_kvstore


def test_trainer_zero_bucket_escape_hatch_still_shards():
    """MXNET_TPU_COMM_BUCKET_MB=0 cannot disable ZeRO — the layout
    degrades to one bucket per dtype and the sharded update still runs."""
    net, tr, a = _train_gluon(True, env_cap=0)
    assert isinstance(tr._kvstore._updater, ZeroUpdater)
    assert len(tr._kvstore._updater.layout) == 1
    _, _, b = _train_gluon(False, env_cap=None)
    for pa, pb in zip(a, b):
        np.testing.assert_array_equal(pa, pb)


def test_trainer_zero_rejects_update_on_kvstore_false():
    net = nn.Dense(3, in_units=4)
    net.initialize()
    with pytest.raises(ValueError, match="update_on_kvstore"):
        gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                      update_on_kvstore=False, zero=True)


def test_trainer_zero_save_load_states_resumes_bit_exact(tmp_path):
    """Trainer.save_states/load_states (the SnapshotCheckpointer payload
    of the Gluon path) round-trips the sharded state: resume + 2 steps ==
    uninterrupted 4 steps."""
    fname = str(tmp_path / "trainer.states")
    net, tr, _ = _train_gluon(True, steps=2)
    tr.save_states(fname)
    saved = [p.data().asnumpy()
             for _, p in sorted(net.collect_params().items())]
    _, _, ref = _train_gluon(True, steps=4)

    # fresh net+trainer, params rewound to step 2, states reloaded
    # (match params by sorted position — the fresh net gets new name
    # prefixes from the global name scope)
    mx.random.seed(0)
    np.random.seed(0)
    net2 = nn.HybridSequential()
    with net2.name_scope():
        net2.add(nn.Dense(16, activation="relu"), nn.Dense(8), nn.Dense(2))
    net2.initialize(mx.init.Xavier())
    for (_, p), arr in zip(sorted(net2.collect_params().items()), saved):
        p.set_data(nd.array(arr))
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.125, "momentum": 0.5},
                        update_on_kvstore=True, zero=True)
    tr2.load_states(fname)
    x = nd.array(np.random.RandomState(1).randn(8, 10).astype(np.float32))
    y = nd.array(np.ones((8,), np.float32))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net2(x), y)
        loss.backward()
        tr2.step(8)
    resumed = [p.data().asnumpy()
               for _, p in sorted(net2.collect_params().items())]
    for a, b in zip(resumed, ref):
        np.testing.assert_array_equal(a, b)


def test_kvstore_zero_rejects_sparse():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.ndarray import sparse as sp
    kv = mx.kv.create("device")
    kv.set_optimizer(opt_create("sgd", learning_rate=0.1), zero=True)
    kv.init(0, nd.zeros((4, 2)))
    g = sp.row_sparse_array((np.ones((1, 2), np.float32), [0]),
                            shape=(4, 2))
    with pytest.raises(MXNetError, match="dense"):
        kv.push(0, g)


def test_zero_reduce_scatter_fault_site_retries():
    from mxnet_tpu.resilience import faults
    zu = ZeroUpdater(opt_create("sgd", learning_rate=0.5))
    ws = [nd.array(np.ones(4, np.float32))]
    before = _counters()
    with faults.inject("collective.reduce_scatter:error:1"):
        zu.step(["0"], [jnp.ones((4,), jnp.float32)], ws)
    after = _counters()
    assert _delta(before, after,
                  "resilience.retries.collective.reduce_scatter") >= 1
    np.testing.assert_array_equal(ws[0].asnumpy(), np.full(4, 0.5))


# ===========================================================================
# dist kvstore: ZeRO routing + per-bucket 2-bit compression residuals
# ===========================================================================

def _dist_store():
    from mxnet_tpu.kvstore.kvstore_dist import KVStoreDist
    return KVStoreDist("dist_sync")


def test_dist_zero_parity_single_worker():
    def run(zero):
        kv = _dist_store()
        kv.set_optimizer(opt_create("sgd", learning_rate=0.5, momentum=0.5,
                                    rescale_grad=1.0), zero=zero)
        rng = np.random.RandomState(0)
        keys = list(range(5))
        for k in keys:
            kv.init(k, nd.array(rng.randn(4).astype(np.float32)))
        for _ in range(3):
            kv.push(keys, [nd.array(rng.randn(4).astype(np.float32))
                           for _ in keys])
        outs = [nd.zeros((4,)) for _ in keys]
        kv.pull(keys, out=outs)
        return [o.asnumpy() for o in outs]

    for a, b in zip(run(True), run(False)):
        np.testing.assert_array_equal(a, b)


def test_dist_compression_bucketed_residuals_parity():
    """ISSUE 9 satellite: 2-bit residuals keyed per persistent bucket are
    bit-identical to the per-key path across multiple steps (residual
    state must track identically), through BOTH push and pushpull."""
    def run(mb, via_pushpull):
        with engine.bucket_mb_scope(mb):
            kv = _dist_store()
            kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
            rng = np.random.RandomState(1)
            for k in range(4):
                kv.init(k, nd.zeros((6,)))
            for _ in range(3):
                vals = [nd.array(rng.randn(6).astype(np.float32))
                        for _ in range(4)]
                if via_pushpull:
                    outs = [nd.zeros((6,)) for _ in range(4)]
                    kv.pushpull(list(range(4)), vals, out=outs)
                else:
                    kv.push(list(range(4)), vals)
            outs = [nd.zeros((6,)) for _ in range(4)]
            kv.pull(list(range(4)), out=outs)
            return [o.asnumpy() for o in outs]

    for via_pushpull in (False, True):
        ref = run(0, via_pushpull)          # per-key escape hatch
        for a, b in zip(run(25, via_pushpull), ref):
            np.testing.assert_array_equal(a, b)


def test_dist_compression_bucketed_residual_keys_on_bucket():
    with engine.bucket_mb_scope(25):
        kv = _dist_store()
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        for k in range(3):
            kv.init(k, nd.zeros((4,)))
        kv.push(list(range(3)),
                [nd.array(np.full(4, 0.3, np.float32)) for _ in range(3)])
        assert kv._gc_layout is not None and len(kv._gc_layout) == 1
        # ONE residual entry for the whole bucket, not one per key
        assert list(kv._gc._residual.keys()) == ["__bucket__0"]


def test_dist_compression_changed_key_set_refreezes_with_warning():
    with engine.bucket_mb_scope(25):
        kv = _dist_store()
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        for k in range(4):
            kv.init(k, nd.zeros((4,)))
        kv.push([0, 1, 2], [nd.array(np.ones(4, np.float32))] * 3)
        old_layout = kv._gc_layout
        assert old_layout is not None
        # a different key set after the freeze RE-freezes the layout
        # (warned — the old buckets' residuals are dropped) and stays on
        # the bucketed path for the new stable set
        with pytest.warns(UserWarning, match="re-frozen"):
            kv.push([0, 1, 2, 3], [nd.array(np.ones(4, np.float32))] * 4)
        assert kv._gc_layout is not None
        assert kv._gc_layout.keys() == ["0", "1", "2", "3"]
        kv.push([0, 1, 2, 3], [nd.array(np.ones(4, np.float32))] * 4)
        outs = [nd.zeros((4,)) for _ in range(4)]
        kv.pull(list(range(4)), out=outs)
        assert np.isfinite(outs[3].asnumpy()).all()


def test_dist_compression_bucketed_counts_buckets_per_step():
    with engine.bucket_mb_scope(25):
        kv = _dist_store()
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        for k in range(3):
            kv.init(k, nd.zeros((8,)))
        vals = [nd.array(np.ones(8, np.float32)) for _ in range(3)]
        kv.push(list(range(3)), vals)   # freeze step (counted by bucketize)
        before = _counters()
        kv.push(list(range(3)), vals)
        kv.push(list(range(3)), vals)
        after = _counters()
        # steady state: one bucket counted per push, like _push_bucketed
        assert _delta(before, after, "comm.bucket.count") == 2
        assert _delta(before, after, "comm.bucket.bytes") == 2 * 3 * 8 * 4


def test_reduce_scatter_multi_rejects_zero_size_arrays():
    from mxnet_tpu.parallel import collectives
    with pytest.raises(ValueError, match="zero-size"):
        collectives.reduce_scatter_multi(
            [jnp.ones((4,)), jnp.zeros((0,))], "data", axis_size=2)


# ===========================================================================
# in-mesh fused collectives
# ===========================================================================

def test_reduce_scatter_all_gather_multi_roundtrip():
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.parallel import collectives
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()
    n = mesh.devices.size
    ax = mesh.axis_names[0]
    rng = np.random.RandomState(0)
    shapes = [(5, 3), (7,), (4, 4)]
    xs = [jnp.asarray(rng.randn(n, *s).astype(np.float32)) for s in shapes]
    box = {}

    def f(*per_dev):
        # in_specs P(ax) keeps a leading length-1 block dim; drop it so
        # each device contributes its own (shape,) array
        shards, layout = collectives.reduce_scatter_multi(
            [x[0] for x in per_dev], ax, axis_size=n)
        box["layout"] = layout
        return tuple(collectives.all_gather_multi(shards, layout, ax))

    before = _counters()
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P(ax), out_specs=P(),
                            check_vma=False))(*xs)
    after = _counters()
    for x, o in zip(xs, out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(x).sum(0),
                                   rtol=1e-5)
    layout = box["layout"]
    assert len(layout) == 1          # 38 elems -> one bucket
    assert layout.buckets[0].padded % n == 0
    # trace-time counters: one per bucket per trace
    assert _delta(before, after, "comm.reduce_scatter") == 1
    assert _delta(before, after, "comm.all_gather") == 1


def test_reduce_scatter_multi_requires_axis_size_or_layout():
    from mxnet_tpu.parallel import collectives
    with pytest.raises(ValueError, match="axis_size"):
        collectives.reduce_scatter_multi([jnp.ones((4,))], "data")


# ===========================================================================
# ShardedTrainStep zero composition
# ===========================================================================

def test_sharded_train_step_zero_parity_and_state_sharding():
    from mxnet_tpu.parallel import ShardedTrainStep
    from mxnet_tpu.parallel.mesh import local_mesh
    mesh = local_mesh()
    n = mesh.devices.size
    if "data" not in mesh.axis_names or n == 1:
        pytest.skip("needs a data-axis mesh")

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    def run(zero):
        params = {"w": jnp.ones((n, 4)), "b": jnp.zeros((4,))}
        st = ShardedTrainStep(loss_fn, params, mesh, optimizer="adam",
                              lr=0.125, zero=zero)
        p, s = st.init()
        if zero:
            # state leading dims shard over 'data' where divisible;
            # indivisible leaves keep the rules' (replicated) spec
            assert "data" in tuple(s["m"]["w"].sharding.spec)
            assert tuple(s["m"]["b"].sharding.spec) in ((), (None,))
        batch = {"x": jnp.asarray(
                     np.arange(n * 16 * n).reshape(16 * n, n) % 7,
                     jnp.float32),
                 "y": jnp.ones((16 * n, 4))}
        for i in range(3):
            p, s, loss = st(p, s, batch, i)
        return np.asarray(p["w"]), float(loss)

    (wa, la), (wb, lb) = run(True), run(False)
    np.testing.assert_array_equal(wa, wb)
    assert la == lb


# ===========================================================================
# tooling: parse_log --comm carries the ZeRO rows
# ===========================================================================

def test_parse_log_comm_zero_rows(tmp_path):
    zu = ZeroUpdater(opt_create("sgd", learning_rate=0.5, momentum=0.5))
    ws = [nd.array(np.ones(64, np.float32)) for _ in range(3)]
    zu.step(["0", "1", "2"], [jnp.ones((64,), jnp.float32)] * 3, ws)
    dump = str(tmp_path / "telemetry.json")
    telemetry.dump(dump)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--comm"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
    assert "comm.reduce_scatter" in proc.stdout
    assert "comm.all_gather" in proc.stdout
    assert "opt.state_bytes_per_rank" in proc.stdout
    assert "opt.fused_update_ms_avg" in proc.stdout

"""Pallas fused optimizer kernels (ISSUE 10) — parity + dispatch tests.

Every test here runs the REAL kernels through the Pallas interpreter on
the CPU backend (this container has no chip): interpreter results are
PARITY evidence only, never perf evidence (the interpreter serializes the
grid; no benchmark cell runs these kernels yet: ROADMAP S7).

Parity contracts:
* flat SGD/Adam vs `optimizer._fused_flat_xla` — BIT-identical (same
  elementwise ops in the same order, both jitted), but for flat SGD's
  momentum line, which may be one rounding off (see the test);
* LAMB phase1/apply Pallas vs XLA — fp32 round-off only (the per-segment
  norm reduction accumulates per-tile vs per-slice);
* per-parameter tpu_impls vs the eager base ops — bit-identical under
  FMA-immune dyadic hyperparameters (the test_zero.py trick: the jitted
  kernel path may contract mul+add into FMA, the un-jitted eager
  composite does not), fp32 round-off otherwise.
"""
import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401
from mxnet_tpu import telemetry
from mxnet_tpu.ops import fused_optimizer as fo
from mxnet_tpu.ops import optimizer_ops as oo
from mxnet_tpu.optimizer.optimizer import _fused_flat_fn, _fused_flat_xla

pytestmark = pytest.mark.pallas


def _counters():
    return dict(telemetry.snapshot()["counters"])


def _vecs(n, seed=0):
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    s = jnp.asarray(rng.rand(n).astype(np.float32))
    lr = jnp.asarray((rng.rand(n) * 0.1).astype(np.float32))
    wd = jnp.asarray((rng.rand(n) * 0.01).astype(np.float32))
    return w, g, s, lr, wd


@pytest.mark.parametrize("momentum_on,clip_on,mp_on", [
    (False, False, False), (True, False, False), (True, True, False),
    (False, True, False), (True, False, True), (True, True, True),
])
@pytest.mark.parametrize("n", [50, 1024, 2000])
def test_flat_sgd_bit_identical_to_xla(momentum_on, clip_on, mp_on, n):
    """Pallas flat SGD == `_fused_flat_xla("sgd", ...)`, including the
    non-128-multiple padding path and the fp32-master multi-precision
    contract.

    BITWISE in dtypes, in which outputs are None, and in every value when
    momentum is off: each sum there has one product (`g + wd*w`,
    `w - lr*g`), which a compiler can contract into a multiply-add in one
    way only, and the interpreter and XLA do the same. The momentum line
    `mom*momentum - lr*g` has two, and which of them a host's XLA keeps
    unrounded inside the multiply-add differs between the two programs
    (4.6% of elements at n = 2000 on one machine, 6% at n = 1024 on
    another, none on a third). That is one float32 rounding of a product,
    so `mom`, and `w` and `master` after it, may differ by one ulp of the
    largest of the element's operands and its result: not of the result
    alone, because the two products cancel (a result near zero is 64 of
    its own ulps away from its twin and still one rounding off). A
    momentum of 0.90001 for 0.9 is 150 such ulps off."""
    w, g, mom, lr, wd = _vecs(n, seed=n)
    master = w.astype(jnp.float32) if mp_on else None
    ww = w.astype(jnp.float16) if mp_on else w
    args = (ww, g, mom if momentum_on else None, master, lr, wd,
            jnp.float32(0.9), jnp.float32(1.5), jnp.float32(0.25))
    ref = _fused_flat_xla("sgd", momentum_on, clip_on, mp_on)(*args)
    got = fo.flat_update_fn("sgd", momentum_on, clip_on, mp_on)(*args)
    operand = np.max(np.abs([np.asarray(w), np.asarray(g) * 1.5,
                             np.asarray(mom)]), axis=0)
    for a, b, nm in zip(got, ref, ("w", "mom", "master")):
        if b is None:
            assert a is None, nm
            continue
        assert a.dtype == b.dtype, nm
        a, b = np.asarray(a), np.asarray(b)
        if not momentum_on:
            np.testing.assert_array_equal(a, b, err_msg=nm)
            continue
        ulp = np.spacing(np.maximum(operand, np.abs(b)).astype(a.dtype)
                         ).astype(np.float64)
        off = np.abs(a.astype(np.float64) - b.astype(np.float64))
        assert (off <= ulp).all(), "%s: %d elements over one ulp, worst %g" \
            % (nm, (off > ulp).sum(), (off / ulp).max())


@pytest.mark.parametrize("clip_on,mp_on", [(False, False), (True, False),
                                           (False, True)])
def test_flat_adam_bit_identical_to_xla(clip_on, mp_on):
    n = 777
    w, g, mean, lr, wd = _vecs(n, seed=7)
    var = jnp.abs(g) * 0.1
    master = w.astype(jnp.float32) if mp_on else None
    ww = w.astype(jnp.float16) if mp_on else w
    args = (ww, g, mean, var, master, lr, wd, jnp.float32(0.9),
            jnp.float32(1.0 - 0.9), jnp.float32(0.999),
            jnp.float32(1.0 - 0.999), jnp.float32(1e-8), jnp.float32(1.0),
            jnp.float32(0.5))
    ref = _fused_flat_xla("adam", True, clip_on, mp_on)(*args)
    got = fo.flat_update_fn("adam", True, clip_on, mp_on)(*args)
    for a, b, nm in zip(got, ref, ("w", "mean", "var", "master")):
        if b is None:
            assert a is None, nm
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=nm)


def test_fused_flat_fn_dispatches_pallas_under_gate():
    """`optimizer._fused_flat_fn` (the ZeroUpdater entry) returns the
    counted Pallas wrapper when the gate is on, the XLA jit otherwise."""
    assert fo.use_pallas_flat()   # pallas marker fixture set interpret mode
    n = 64
    w, g, mom, lr, wd = _vecs(n, seed=3)
    before = _counters()
    out = _fused_flat_fn("sgd", True, False, False)(
        w, g, mom, None, lr, wd, jnp.float32(0.5), jnp.float32(1.0),
        jnp.float32(0.0))
    after = _counters()
    assert after.get("ops.pallas.dispatch.flat_sgd", 0) == \
        before.get("ops.pallas.dispatch.flat_sgd", 0) + 1
    ref = _fused_flat_xla("sgd", True, False, False)(
        w, g, mom, None, lr, wd, jnp.float32(0.5), jnp.float32(1.0),
        jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    # spans: the dispatch rides a pallas.<kernel> span for trace attribution
    assert any(ev[0] == "pallas.flat_sgd" and ev[1] == "kernel"
               for ev in telemetry.span_events())


def test_flat_fallback_counted_never_erroring():
    """Ineligible operands (integer weights) fall back to the XLA
    composite with a counted reason — never an exception."""
    n = 32
    w = jnp.arange(n, dtype=jnp.int32)
    g = jnp.ones((n,), jnp.int32)
    mom = jnp.zeros((n,), jnp.int32)
    lr = jnp.full((n,), 0.5, jnp.float32)
    wd = jnp.zeros((n,), jnp.float32)
    before = _counters()
    out = fo.flat_update_fn("sgd", True, False, False)(
        w, g, mom, None, lr, wd, jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(0.0))
    after = _counters()
    assert after.get("ops.pallas.fallback.dtype", 0) == \
        before.get("ops.pallas.fallback.dtype", 0) + 1
    ref = _fused_flat_xla("sgd", True, False, False)(
        w, g, mom, None, lr, wd, jnp.float32(0.0), jnp.float32(1.0),
        jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))


def test_flat_multi_tile_grid():
    """A vector larger than one tile runs a >1 grid; results must be
    identical to the XLA path across the tile boundary."""
    n = fo._MAX_TILE_ROWS * fo._LANES + 4321   # forces grid == 2
    w, g, mom, lr, wd = _vecs(n, seed=11)
    args = (w, g, mom, None, lr, wd, jnp.float32(0.9), jnp.float32(1.0),
            jnp.float32(0.0))
    ref = _fused_flat_xla("sgd", True, False, False)(*args)
    got = fo.flat_update_fn("sgd", True, False, False)(*args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


def test_lamb_phase1_and_apply_pallas_vs_xla():
    """LAMB two-pass: Pallas phase1 (direction + per-segment norm
    partials) and trust-ratio apply vs the XLA impls. Norm accumulation
    order differs per documented tolerance (fp32 round-off)."""
    n = 700
    segments = ((0, 0, 300), (1, 300, 300), (2, 600, 100))
    seg_ids = np.zeros((n,), np.int32)
    seg_ids[300:600] = 1
    seg_ids[600:] = 2
    seg_ids = jnp.asarray(seg_ids)
    w, g, mean, lr, wd = _vecs(n, seed=13)
    var = jnp.abs(g) * 0.1
    scal = (jnp.float32(0.9), jnp.float32(0.1), jnp.float32(0.999),
            jnp.float32(0.001), jnp.float32(1 - 0.9 ** 2),
            jnp.float32(1 - 0.999 ** 2), jnp.float32(1e-6),
            jnp.float32(1.0), jnp.float32(0.0))
    x_impl = fo._jitted(("t_lamb1x",),
                        lambda: fo._lamb1_xla_impl(False, False, True,
                                                   segments, 3))
    p_impl = fo._jitted(("t_lamb1p",),
                        lambda: fo._lamb1_pallas_impl(False, False, True, 3))
    ref = x_impl(w, g, mean, var, None, wd, seg_ids, *scal)
    got = p_impl(w, g, mean, var, None, wd, seg_ids, *scal)
    for a, b, nm in zip(got, ref, ("gdir", "mean", "var", "norms")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6,
                                   atol=1e-6, err_msg=nm)
    # the norms really are per-key sums of squares
    w32 = np.asarray(w)
    want0 = np.array([np.sum(w32[np.asarray(seg_ids) == k] ** 2)
                      for k in range(3)])
    np.testing.assert_allclose(np.asarray(got[3])[0], want0, rtol=1e-5)
    # apply pass
    scale = lr * 0.7
    ra, ma = fo.lamb_flat_apply_fn(False)(w, None, ref[0], scale)
    np.testing.assert_allclose(
        np.asarray(ra), w32 - np.asarray(scale) * np.asarray(ref[0]),
        rtol=1e-6)
    assert ma is None


# FMA-immune dyadic hyperparameters (see tests/test_zero.py): the jitted
# kernel may contract mul+add into FMA, the eager base op does not —
# power-of-two scalars make both round identically on arbitrary data
_DY = dict(lr=0.125, momentum=0.5, wd=0.125)


def test_per_param_sgd_updates_bit_identical():
    rng = np.random.RandomState(21)
    w = jnp.asarray(rng.randn(9, 11).astype(np.float32))
    g = jnp.asarray(rng.randn(9, 11).astype(np.float32))
    mom = jnp.asarray(rng.randn(9, 11).astype(np.float32))
    ref = oo.sgd_update(w, g, _DY["lr"], wd=_DY["wd"], clip_gradient=0.5)
    got = fo._sgd_update_tpu(w, g, _DY["lr"], wd=_DY["wd"],
                             clip_gradient=0.5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    ref = oo.sgd_mom_update(w, g, mom, _DY["lr"], momentum=_DY["momentum"],
                            wd=_DY["wd"])
    got = fo._sgd_mom_update_tpu(w, g, mom, _DY["lr"],
                                 momentum=_DY["momentum"], wd=_DY["wd"])
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_per_param_adam_update_parity():
    """Dyadic betas -> bitwise; arbitrary betas -> <= 1-ulp FMA skew."""
    rng = np.random.RandomState(22)
    w = jnp.asarray(rng.randn(64).astype(np.float32))
    g = jnp.asarray(rng.randn(64).astype(np.float32))
    m = jnp.asarray(rng.randn(64).astype(np.float32))
    v = jnp.abs(g) * 0.5
    kw = dict(beta1=0.5, beta2=0.5, epsilon=2.0 ** -8, wd=0.125)
    ref = oo.adam_update(w, g, m, v, 0.125, **kw)
    got = fo._adam_update_tpu(w, g, m, v, 0.125, **kw)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    kw = dict(beta1=0.9, beta2=0.999, epsilon=1e-8, wd=0.01)
    ref = oo.adam_update(w, g, m, v, 0.01, **kw)
    got = fo._adam_update_tpu(w, g, m, v, 0.01, **kw)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


def test_per_param_lamb_phases_parity():
    rng = np.random.RandomState(23)
    w = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    g = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    m = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    v = jnp.abs(g) * 0.3
    ref = oo.lamb_update_phase1(w, g, m, v, t=3, wd=0.01)
    got = fo._lamb_phase1_tpu(w, g, m, v, t=3, wd=0.01)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
    r1 = jnp.linalg.norm(w)
    r2 = jnp.linalg.norm(ref[0])
    refw = oo.lamb_update_phase2(w, ref[0], r1, r2, 0.125, lower_bound=0.1,
                                 upper_bound=10.0)
    gotw = fo._lamb_phase2_tpu(w, ref[0], r1, r2, 0.125, lower_bound=0.1,
                               upper_bound=10.0)
    np.testing.assert_allclose(np.asarray(gotw), np.asarray(refw),
                               rtol=1e-6, atol=1e-7)


def test_per_param_fp16_falls_back_counted():
    """The per-param kernels are f32-only (the base ops run native-dtype
    math): fp16 weights fall back to the base op, counted, identical."""
    rng = np.random.RandomState(24)
    w = jnp.asarray((rng.randn(32) * 0.1).astype(np.float16))
    g = jnp.asarray((rng.randn(32) * 0.1).astype(np.float16))
    before = _counters()
    got = fo._sgd_update_tpu(w, g, 0.125, wd=0.0)
    after = _counters()
    assert after.get("ops.pallas.fallback.sgd.dtype", 0) == \
        before.get("ops.pallas.fallback.sgd.dtype", 0) + 1
    ref = oo.sgd_update(w, g, 0.125, wd=0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_registry_best_fn_gates_per_param_path(monkeypatch):
    """`optimizer._run_op` resolves through registry.best_fn: on a CPU
    context the base op runs (tier-1 behavior unchanged); the tpu_impl is
    registered and reachable for accelerator contexts."""
    from mxnet_tpu.ops import registry as reg
    op = reg.get("sgd_mom_update")
    assert op.tpu_fn is fo._sgd_mom_update_tpu
    monkeypatch.setenv("MXNET_TPU_USE_PALLAS", "1")
    assert op.best_fn(False) is op.fn
    assert op.best_fn(True) is fo._sgd_mom_update_tpu


def test_use_pallas_flat_gate(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    assert fo.use_pallas_flat()
    monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    # CPU backend without interpret: never
    assert not fo.use_pallas_flat()


@pytest.mark.parametrize("value,opt_in,per_op", [
    (None, False, True),    # unset: the flat and segment-sum kernels are
    ("1", True, True),      # opt-in, the registry's tpu_impls are on
    ("0", False, False),
])
@pytest.mark.parametrize("reader", ["flat", "sparse", "per_op"])
def test_use_pallas_one_reader_three_callers(monkeypatch, reader, value,
                                             opt_in, per_op):
    """MXNET_TPU_USE_PALLAS is read in `pallas_stats.use_pallas` alone, and
    each caller on a TPU backend answers as it did when it read the
    variable itself: unset, on for `Operator.best_fn` and off for the
    flat-optimizer and segment-sum gates."""
    import jax
    from mxnet_tpu.ops import registry as reg, sparse_ops
    monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if value is None:
        monkeypatch.delenv("MXNET_TPU_USE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("MXNET_TPU_USE_PALLAS", value)
    if reader == "flat":
        assert fo.use_pallas_flat() is opt_in
    elif reader == "sparse":
        assert sparse_ops.use_pallas_sparse() is opt_in
    else:
        op = reg.get("sgd_mom_update")
        assert (op.best_fn(True) is op.tpu_fn) is per_op
        assert op.best_fn(False) is op.fn


@pytest.mark.lint
def test_fused_optimizer_lint_clean_zero_suppressions():
    """The new kernel layer must be tracelint-clean with ZERO suppression
    comments (ISSUE 10 CI satellite)."""
    import mxnet_tpu.analysis as analysis
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mxnet_tpu", "ops")
    for name in ("fused_optimizer.py", "pallas_stats.py"):
        path = os.path.join(root, name)
        findings = analysis.check(path)
        assert findings == [], "\n".join(str(f) for f in findings)
        with open(path) as f:
            assert "tpu-lint" not in f.read(), \
                "suppression found in %s" % name

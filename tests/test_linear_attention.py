"""`ops.linear_attention`: the gated delta rule in chunks against the same
rule token by token, the causal convolution and the gated RMSNorm; the chunk
inverse's kernels `gdn_inverse` / `gdn_inverse_bwd` in interpret mode against
numpy's inverse and against the XLA products. All in float32 on the CPU at
toy sizes."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.ops import linear_attention as la
from mxnet_tpu.ops.linear_attention import (causal_conv1d, gated_delta_rule,
                                            gated_delta_rule_recurrent,
                                            gated_rms_norm, l2_normalize)


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(S, Hk=2, Hv=4, dk=16, dv=8, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2_normalize(jax.random.normal(ks[0], (B, S, Hk, dk))) * dk ** -0.5
    k = l2_normalize(jax.random.normal(ks[1], (B, S, Hk, dk)))
    v = jax.random.normal(ks[2], (B, S, Hv, dv))
    # decays from nearly none to exp(-20) a token, as A_log = log(U(0, 16))
    rate = jax.random.uniform(ks[3], (Hv,), minval=0.01, maxval=16.0)
    g = -rate * jax.nn.softplus(jax.random.normal(ks[4], (B, S, Hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, Hv)))
    return q, k, v, g, beta


# 128: whole chunks; 150: a last chunk of 22 positions, padded inside;
# 40: shorter than one chunk
@pytest.mark.parametrize("S,chunk,Hk", [(128, 64, 2), (150, 64, 2),
                                        (40, 64, 4), (96, 16, 1)])
def test_chunked_rule_matches_the_recurrence(S, chunk, Hk):
    args = _inputs(S, Hk=Hk)
    got = jax.jit(lambda *a: gated_delta_rule(*a, chunk=chunk))(*args)
    want = jax.jit(gated_delta_rule_recurrent)(*args)
    assert got.shape == want.shape == (2, S, 4, 8)
    # float32 both ways; the two differ in the order of a few hundred
    # additions a number and in the inverse's six products: 1e-5 of the
    # largest output is a hundred roundings
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale


@pytest.mark.parametrize("S,chunk", [(128, 64), (150, 64)])
def test_chunked_rule_gradients_match_the_recurrence(S, chunk):
    args = _inputs(S, seed=3)

    def through(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                argnums=(0, 1, 2, 3, 4)))(*args)
    got = through(lambda *a: gated_delta_rule(*a, chunk=chunk))
    want = through(gated_delta_rule_recurrent)
    for name, a, b in zip("q k v g beta".split(), got, want):
        # as above, through a backward pass of the same length: 1e-4 of the
        # gradient's largest entry
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, name


def test_a_padded_tail_changes_nothing_before_it():
    q, k, v, g, beta = _inputs(100, seed=5)
    rule = jax.jit(lambda *a: gated_delta_rule(*a, chunk=32))
    whole = rule(q, k, v, g, beta)
    head = rule(q[:, :70], k[:, :70], v[:, :70], g[:, :70], beta[:, :70])
    # the same chunks' arithmetic up to position 64, another split after
    onp.testing.assert_allclose(whole[:, :70], head, rtol=0, atol=2e-6)


def test_convolution_is_causal_and_is_the_formula():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    got = causal_conv1d(u, w)
    want = onp.zeros((2, 12, 6), onp.float32)
    for t in range(12):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += onp.asarray(w)[:, j] * onp.asarray(u)[:, t - 3 + j]
    # four multiply-adds a number in float32
    onp.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    later = u.at[:, 7:].set(9.0)
    assert onp.array_equal(causal_conv1d(later, w)[:, :7], got[:, :7])
    assert not onp.allclose(causal_conv1d(later, w)[:, 7], got[:, 7])


def test_gated_rms_norm_and_l2_normalize():
    o = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 8))
    w = jax.random.normal(jax.random.PRNGKey(2), (8,))
    want = (o / onp.sqrt(onp.mean(onp.square(o), -1, keepdims=True) + 1e-6)
            * w * (z / (1 + onp.exp(-z))))
    # one rsqrt and three products a number in float32
    onp.testing.assert_allclose(gated_rms_norm(o, z, w), want, rtol=1e-5,
                                atol=1e-6)
    unit = l2_normalize(o)
    onp.testing.assert_allclose(jnp.sum(unit * unit, -1), 1.0, rtol=1e-5)
    assert gated_rms_norm(o.astype(jnp.bfloat16), z, w).dtype == jnp.bfloat16


# ------------------------------------------------- the chunk inverse's kernels
def _count(name):
    return telemetry.counter("ops.pallas." + name).value


def _path(monkeypatch, path):
    """The `pallas` marker has the kernels run interpreted; "xla" takes the
    flag away again, which off the TPU is the XLA products."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET",
                       "1" if path == "pallas" else "0")


def _lower_blocks(shape, seed=0):
    return jnp.tril(0.1 * jax.random.normal(jax.random.PRNGKey(seed), shape),
                    -1)


# 64 blocks of 64: two whole groups of 32; 2 x 3 x 7: a group and a third,
# padded; 11 of 128: a group of 8 and three, one block across the lanes
@pytest.mark.pallas
@pytest.mark.parametrize("lead,C", [((64,), 64), ((2, 3, 7), 64),
                                    ((11,), 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_chunk_inverse_is_numpys(monkeypatch, path, dtype, lead, C):
    _path(monkeypatch, path)
    a = _lower_blocks(lead + (C, C))
    exact = onp.linalg.inv(onp.eye(C) + onp.asarray(a, onp.float64))
    before = _count("dispatch.gdn_inverse")
    t = la._unit_lower_inverse(a, jnp.dtype(dtype))
    assert (_count("dispatch.gdn_inverse") > before) == (path == "pallas")
    assert t.shape == a.shape and t.dtype == jnp.dtype(dtype)
    error = onp.abs(onp.asarray(t.astype(jnp.float32), onp.float64) - exact)
    # float32: six or seven levels of C-term sums of products under 1;
    # bfloat16: one rounding of that, half a unit of its eight bits
    rounding = 2.0 ** -8 * onp.abs(exact) if dtype == "bfloat16" else 0.0
    assert onp.all(error <= 1e-6 + rounding), error.max()


@pytest.mark.pallas
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_chunk_inverse_backward_is_the_xla_paths(monkeypatch, dtype, tol):
    a = _lower_blocks((2, 3, 7, 64, 64), seed=1)
    g = jax.random.normal(jax.random.PRNGKey(2), a.shape).astype(dtype)

    def gradient(path):
        _path(monkeypatch, path)
        t, vjp = jax.vjp(lambda a: la._unit_lower_inverse(
            a, jnp.dtype(dtype)), a)
        return t, vjp(g)[0]
    before = _count("dispatch.gdn_inverse_bwd")
    t, got = gradient("pallas")
    assert _count("dispatch.gdn_inverse_bwd") == before + 1
    t_xla, want = gradient("xla")
    assert _count("dispatch.gdn_inverse_bwd") == before + 1
    assert got.shape == a.shape and got.dtype == jnp.float32
    # float32: the same two products in another order of the forward's
    # levels; bfloat16: T and `T^T G` are rounded on both sides, and where
    # the float32 values differ in their last bits a rounding can fall the
    # other way, 2^-8 of one entry of a 64-term sum
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale
    # the kernel alone, on the same T: only the order of the sums differs
    _path(monkeypatch, "pallas")
    alone = la._inverse_bwd(jnp.dtype(dtype), t_xla, g)[0]
    assert float(jnp.max(jnp.abs(alone - want))) <= tol / 4 * scale


@pytest.mark.pallas
@pytest.mark.parametrize("S", [128, 150])
def test_chunked_rule_with_the_kernels_matches_the_recurrence(S):
    args = _inputs(S, seed=7)
    before = [_count("dispatch.gdn_inverse"),
              _count("dispatch.gdn_inverse_bwd")]

    def through(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))
        )(*args)
    got, got_grads = through(lambda *a: gated_delta_rule(*a, chunk=64))
    assert _count("dispatch.gdn_inverse") > before[0]
    assert _count("dispatch.gdn_inverse_bwd") > before[1]
    want, want_grads = through(gated_delta_rule_recurrent)
    # the limits of the XLA path's tests above
    assert abs(float(got - want)) <= 1e-5 * abs(float(want))
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, name


@pytest.mark.pallas
@pytest.mark.parametrize("chunk", [16, 32])
def test_toy_chunks_take_the_xla_products_and_are_counted(chunk):
    args = _inputs(96, seed=9)
    before = {name: _count(name) for name in (
        "dispatch.gdn_inverse", "dispatch.gdn_inverse_bwd",
        "fallback.gdn_inverse.chunk")}
    got = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a, chunk=chunk)),
                   argnums=1)(*args)
    want = jax.grad(lambda *a: jnp.sum(gated_delta_rule_recurrent(*a)),
                    argnums=1)(*args)
    # the forward's inverse and its backward, each sent on once
    assert _count("fallback.gdn_inverse.chunk") == before[
        "fallback.gdn_inverse.chunk"] + 2
    assert _count("dispatch.gdn_inverse") == before["dispatch.gdn_inverse"]
    assert _count("dispatch.gdn_inverse_bwd") == before[
        "dispatch.gdn_inverse_bwd"]
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * scale

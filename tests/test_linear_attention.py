"""`ops.linear_attention`: the gated delta rule in chunks against the same
rule token by token, the causal convolution and the gated RMSNorm. All in
float32 on the CPU at toy sizes."""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

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

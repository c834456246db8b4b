"""`ops.moe`: routing, the dispatch without dropped pairs, the grouped
products `moe_gmm` / `moe_tgmm` in interpret mode against a loop over
experts, and the shares of a layer adding up to the uncut layer. Float32 on
the CPU at toy sizes."""
import os
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.models import qwen3_next as model
from mxnet_tpu.ops import moe

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from reference import qwen3_next as reference  # noqa: E402

pytestmark = pytest.mark.pallas     # the kernels run interpreted here


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _plan(counts, row_tile, rows_bound=None):
    """A routing of one slot a token that gives held expert e counts[e]
    tokens, in a shuffled order."""
    ids = onp.repeat(onp.arange(len(counts)), counts)
    ids = onp.random.RandomState(0).permutation(ids)[:, None]
    return moe.plan_dispatch(jnp.asarray(ids, jnp.int32), len(counts), 0,
                             rows_bound, row_tile)


def _rows_of(plan, row_tile, n_experts):
    expert = onp.repeat(onp.asarray(plan.tile_expert), row_tile)
    used = onp.arange(expert.size) < int(plan.n_used[0]) * row_tile
    return [used & (expert == e) for e in range(n_experts)]


# the third expert and the last have no row: each keeps an empty tile
@pytest.mark.parametrize("counts", [(5, 17, 0, 8, 0), (1, 1, 1, 1, 1),
                                    (40, 0, 0, 0, 0)])
def test_grouped_products_match_a_loop_over_experts(counts):
    row_tile, K, N, E = 8, 24, 16, len(counts)
    plan = _plan(counts, row_tile)
    R = plan.row_pair.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    lhs = jax.random.normal(ks[0], (R, K))
    rhs = jax.random.normal(ks[1], (E, K, N))
    dy = jax.random.normal(ks[2], (R, N))
    before = telemetry.counter("ops.pallas.dispatch.moe_gmm").value
    out, vjp = jax.vjp(lambda a, b: moe.grouped_matmul(
        a, b, plan.tile_expert, plan.n_used, row_tile), lhs, rhs)
    assert telemetry.counter("ops.pallas.dispatch.moe_gmm").value > before
    dlhs, drhs = vjp(dy)
    rows = _rows_of(plan, row_tile, E)
    assert sum(r.sum() for r in rows) == int(plan.n_used[0]) * row_tile
    for e, mine in enumerate(rows):
        # one product in float32 either way: the order of K (or of a
        # group's rows) additions differs, 1e-5 of numbers of size 10
        onp.testing.assert_allclose(out[mine], lhs[mine] @ rhs[e],
                                    rtol=1e-5, atol=1e-5)
        onp.testing.assert_allclose(dlhs[mine], dy[mine] @ rhs[e].T,
                                    rtol=1e-5, atol=1e-5)
        onp.testing.assert_allclose(drhs[e], lhs[mine].T @ dy[mine],
                                    rtol=1e-5, atol=1e-4)
    unused = ~onp.any(rows, axis=0)
    assert unused.any() and not onp.any(onp.asarray(out)[unused])
    assert not onp.any(onp.asarray(dlhs)[unused])


def test_the_xla_loop_is_the_same_function(monkeypatch):
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "0")     # off the TPU: XLA
    plan = _plan((5, 17, 0, 8), 8)
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    lhs = jax.random.normal(ks[0], (plan.row_pair.shape[0], 24))
    rhs = jax.random.normal(ks[1], (4, 24, 16))

    def both():
        return jax.vjp(lambda a, b: moe.grouped_matmul(
            a, b, plan.tile_expert, plan.n_used, 8), lhs, rhs)
    out, vjp = both()
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    kernel_out, kernel_vjp = both()
    onp.testing.assert_allclose(out, kernel_out, rtol=1e-5, atol=1e-5)
    for a, b in zip(vjp(out), kernel_vjp(out)):
        onp.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def test_no_pair_is_dropped_when_every_token_picks_one_held_expert():
    T, E, row_tile = 200, 4, 8
    ids = jnp.full((T, 1), 6, jnp.int32)        # held experts are 4..7
    plan = moe.plan_dispatch(ids, E, first_expert=4, rows_bound=T,
                             row_tile=row_tile)
    assert plan.row_pair.shape[0] == T + E * row_tile
    held = onp.asarray(plan.row_pair)[onp.asarray(plan.row_valid)]
    assert sorted(held) == list(range(T))
    # the rows lie in expert 2's tiles, and the three empty experts keep one
    # tile each
    expert = onp.repeat(onp.asarray(plan.tile_expert), row_tile)
    assert set(expert[onp.asarray(plan.row_valid)]) == {2}
    assert int(plan.n_used[0]) == T // row_tile + 3
    assert int(plan.n_dropped) == 0


def test_past_the_bound_every_expert_still_has_a_tile():
    plan = _plan((30, 30, 30, 30), 8, rows_bound=40)
    assert plan.row_pair.shape[0] == 40 + 4 * 8
    assert int(plan.n_used[0]) == 9
    assert set(onp.asarray(plan.tile_expert)) == {0, 1, 2, 3}
    held = onp.asarray(plan.row_pair)[onp.asarray(plan.row_valid)]
    assert len(set(held)) == len(held) <= 72
    # and the pairs that found no row are counted
    assert int(plan.n_dropped) == 120 - len(held) > 0


@pytest.mark.parametrize("rows_bound,over", [(40, False), (24, True)])
def test_the_layer_says_so_where_the_bound_is_passed(rows_bound, over):
    """40 tokens, all on held expert 1: every one has its row under a bound
    of 40; under 24 the buffer's five tiles of 8 leave the expert 32 rows,
    and the layer returns NaN throughout rather than the sum without the
    other 8."""
    T, d, f, E = 40, 16, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (T, d))
    router = jnp.zeros((d, 4)).at[:, 1].set(jnp.sign(x[0]))
    x = jnp.abs(x) * jnp.sign(x[0])         # x @ router[:, 1] > 0 = the rest
    out = moe.moe_routed(
        x, router, *(0.1 * jax.random.normal(k, shape) for k, shape in zip(
            ks[1:4], [(E, d, f), (E, d, f), (E, f, d)])),
        top_k=1, rows_bound=rows_bound, row_tile=8)
    assert bool(jnp.all(jnp.isnan(out))) == over
    assert bool(jnp.all(jnp.isfinite(out))) != over


def test_router_weights_sum_to_one_over_the_ten_largest():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    weights, ids = moe.route_top_k(x, w, 10)
    probs = onp.asarray(jax.nn.softmax(x @ w, -1))
    onp.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    for t in range(50):
        assert set(onp.asarray(ids[t])) == set(onp.argsort(-probs[t])[:10])


def _toy(n_held, first):
    cfg = {"hidden_size": 32, "n_experts": n_held, "first_expert": first,
           "n_experts_published": 16, "num_experts_per_tok": 3,
           "moe_intermediate_size": 24,
           "shared_expert_intermediate_size": 24}
    mine = model.Qwen3NextConfig(
        dim=32, n_routed_experts=16, n_experts=n_held, first_expert=first,
        experts_per_token=3, expert_dim=24, shared_expert_dim=24,
        dtype=jnp.float32)
    return cfg, mine


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed sums of all the shares of a toy layer (16 experts, one a
    share), with the shared expert counted once, are the uncut reference's
    layer; and each share is the reference's share."""
    ks = jax.random.split(jax.random.PRNGKey(7), 9)
    x = jax.random.normal(ks[0], (2, 20, 32))
    names = ("router", "gate", "up", "down", "shared_gate_proj", "shared_up",
             "shared_down", "shared_gate")
    shapes = ((32, 16), (16, 32, 24), (16, 32, 24), (16, 24, 32), (32, 24),
              (32, 24), (24, 32), (32, 1))
    p = {n: 0.3 * jax.random.normal(k, s)
         for n, k, s in zip(names, ks[1:], shapes)}
    whole = reference._moe(x, p, _toy(16, 0)[0], "f32")
    no_routed = dict(p, gate=p["gate"][:0], up=p["up"][:0],
                     down=p["down"][:0])
    shared = reference._moe(x, no_routed, _toy(0, 0)[0], "f32")
    total = shared
    for first in range(16):
        cfg, mine = _toy(1, first)
        part = dict(p, gate=p["gate"][first:first + 1],
                    up=p["up"][first:first + 1],
                    down=p["down"][first:first + 1])
        got = model._moe(part, x, mine)
        # float32 on both sides, sums of three experts' outputs of size 1:
        # 1e-5 is a few dozen roundings
        onp.testing.assert_allclose(got, reference._moe(x, part, cfg, "f32"),
                                    rtol=1e-5, atol=1e-5)
        total = total + (got - shared)
    onp.testing.assert_allclose(total, whole, rtol=1e-5, atol=2e-5)

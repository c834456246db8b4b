"""`ops.moe`: routing, the dispatch without dropped pairs, the grouped
products `moe_gmm` / `moe_tgmm` in interpret mode against a loop over
experts, the loop over the buffer's chunks against the layer over the whole
buffer, and the shares of a layer adding up to the uncut layer. Float32 on
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


# ------------------------------------------------------------- the chunks
# 256 tokens, two experts each of 128, the first two held, tiles of 8 rows: a
# buffer of 64 + 2 tiles, walked in chunks of 24 (so padded to 72: three)
CHUNKED_SHAPE = dict(T=256, d=32, f=16, E=2, n_experts=128, top_k=2,
                     row_tile=8)
CHUNK = 24


@pytest.fixture
def chunks_of_24(monkeypatch):
    """Three chunks of the buffer's 66 tiles, the last a quarter padding and
    reached where both experts are full; a chunk's scatter-adds go in up to
    three pieces."""
    monkeypatch.setattr(moe, "CHUNK_TILES", CHUNK)


def _full_length(x, router, w_gate, w_up, w_down, top_k, rows_bound,
                 row_tile):
    """The layer over its whole buffer, whatever the routing filled: what
    `moe_routed` was before it followed the routing, kept here as the
    reference."""
    weights, ids = moe.route_top_k(x, router, top_k)
    plan = moe.plan_dispatch(ids, w_gate.shape[0], 0, rows_bound, row_tile)
    token = plan.row_pair // top_k
    rows = x[token]
    gate = moe.grouped_matmul(rows, w_gate, plan.tile_expert, plan.n_used,
                              row_tile)
    up = moe.grouped_matmul(rows, w_up, plan.tile_expert, plan.n_used,
                            row_tile)
    hidden = jax.nn.silu(gate) * up
    out = moe.grouped_matmul(hidden, w_down, plan.tile_expert, plan.n_used,
                             row_tile)
    share = jnp.where(plan.row_valid, weights.reshape(-1)[plan.row_pair], 0.0)
    combined = jnp.zeros(x.shape, x.dtype).at[token].add(
        out * share[:, None])
    return jnp.where(plan.n_dropped > 0, jnp.nan, combined), plan


def _routed_to(on_first, on_second):
    """Tokens x (T, d) and a router under which the first `on_first` tokens
    choose held expert 0 and the first `on_second` held expert 1; a token's
    other choices are experts that are not held. The router copies the
    first d coordinates, and a token's two choices stand out in them."""
    T, d, E = (CHUNKED_SHAPE[k] for k in ("T", "d", "E"))
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (T, d))
    t = onp.arange(T)
    first = onp.where(t < on_first, 0, E + t % (d - E))
    second = onp.where(t < on_second, 1, E + (t + 1) % (d - E))
    x = x.at[t, first].add(6.0).at[t, second].add(5.0)
    return x, jnp.eye(d, CHUNKED_SHAPE["n_experts"])


def _chunked_case(name):
    if name == "uniform":
        shape = CHUNKED_SHAPE
        x = jax.random.normal(jax.random.PRNGKey(5), (shape["T"], shape["d"]))
        return x, jax.random.normal(jax.random.PRNGKey(6),
                                    (shape["d"], shape["n_experts"]))
    # tiles: expert 0's rows over 8, and expert 1's or its one empty tile
    return _routed_to(*{"exactly_one": (23 * 8, 0), "one_and_a_tile": (185, 0),
                        "two_in_part": (256, 9), "exactly_two": (256, 128),
                        "all_held": (256, 256)}[name])


def _weights(key, E, d, f):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return [0.3 * jax.random.normal(k, s) for k, s in zip(
        ks, [(E, d, f), (E, d, f), (E, f, d)])]


@pytest.mark.parametrize("interpret", ["1", "0"])
@pytest.mark.parametrize("routing,n_used,trips", [
    ("uniform", None, 1), ("exactly_one", 24, 1), ("one_and_a_tile", 25, 2),
    ("two_in_part", 34, 2), ("exactly_two", 48, 2), ("all_held", 64, 3)])
def test_the_loop_over_chunks_is_the_layer_over_the_whole_buffer(
        monkeypatch, chunks_of_24, routing, n_used, trips, interpret):
    """Value and gradients to x, the router and the three weight tensors,
    over as many chunks as the routing fills (less than one, exactly one,
    one tile more, two in part and in full with expert 0's rows on both
    sides of an edge, and all three with the last one reaching into the
    padding),
    against the one computation over all 66 tiles: through the kernels
    (interpreted) and through the loop over experts in XLA."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", interpret)
    shape = CHUNKED_SHAPE
    E, d, f, top_k, row_tile = (shape[k] for k in
                                ("E", "d", "f", "top_k", "row_tile"))
    x, router = _chunked_case(routing)
    weights = _weights(4, E, d, f)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    _, plan = _full_length(x, router, *weights, top_k, None, row_tile)
    assert plan.tile_expert.shape[0] == 66
    used = int(plan.n_used[0])
    assert n_used in (None, used) and int(plan.n_dropped) == 0
    assert int(moe._trips(plan.n_used, CHUNK)) == trips

    def value_and_grads(layer):
        return jax.value_and_grad(
            lambda *a: jnp.sum(layer(*a) * target), argnums=range(5))(
                x, router, *weights)
    mine, mine_grads = value_and_grads(
        lambda *a: moe.moe_routed(*a, top_k, row_tile=row_tile))
    full, full_grads = value_and_grads(
        lambda *a: _full_length(*a, top_k, None, row_tile)[0])
    # once a trace: the forward body and the backward one
    assert telemetry.counter("ops.moe.chunk.%d" % (CHUNK * row_tile)).value
    # float32 both ways; sums over fewer rows are added up in another order:
    # some hundred terms, 1e-5 of the largest
    onp.testing.assert_allclose(mine, full, rtol=1e-5)
    for got, want in zip(mine_grads, full_grads):
        largest = float(jnp.max(jnp.abs(want)))
        assert largest > 0
        onp.testing.assert_allclose(got, want, rtol=1e-5,
                                    atol=1e-5 * largest)


@pytest.mark.parametrize("counts,chunk", [((5, 17, 0, 8, 0), 2),
                                          ((40, 3, 9), 3), ((1, 1, 30), 4)])
def test_weight_gradients_are_summed_across_a_chunks_edge(counts, chunk):
    """`moe_tgmm` chunk by chunk into the gradient it is handed against one
    call over the whole buffer: an expert whose tiles lie on both sides of
    a chunk's edge (the 17 rows over tiles 1-3 with an edge behind tile 1,
    the 40 over five tiles and two edges, the 30 behind two lone tiles)
    adds to the block its earlier tiles started; the experts a chunk does
    not visit keep theirs; and every block is written, whatever the array
    held before (it starts as NaN here, as unwritten memory may)."""
    row_tile, K, N, E = 8, 24, 16, len(counts)
    plan = moe._whole_chunks(_plan(counts, row_tile), chunk, row_tile)
    R = plan.row_pair.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    lhs = jax.random.normal(ks[0], (R, K))
    rhs = jax.random.normal(ks[1], (E, K, N))
    dy = jax.random.normal(ks[2], (R, N))
    _, whole = moe._pulled_back(lhs, rhs, dy, plan.tile_expert, plan.n_used,
                                row_tile, True)
    trips = int(moe._trips(plan.n_used, chunk))
    edges = [c * chunk for c in range(1, trips)]
    assert any(plan.tile_expert[e] == plan.tile_expert[e - 1] for e in edges)
    summed = jnp.full(rhs.shape, jnp.nan)
    for c in range(trips):
        rows, continues = moe._chunk_of(plan, c, chunk, row_tile)
        at = slice(c * chunk * row_tile, (c + 1) * chunk * row_tile)
        _, summed = moe._pulled_back(
            lhs[at], rhs, dy[at], rows.tile_expert, rows.n_used, row_tile,
            True, summed, continues)
    # one expert's rows summed in another order: 1e-5 of numbers of size 10
    onp.testing.assert_allclose(summed, whole, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("chunk", [1, 24, 32])
def test_the_trip_count_is_the_chunks_that_hold_the_tiles_in_use(chunk):
    """`_trips`, which both loops take their bound from: ceil(n_used /
    chunk) at every edge, and a chunk's own count of tiles in use adds up
    to `n_used` over those trips and is never 0 in one of them."""
    for n_used in (1, chunk - 1, chunk, chunk + 1, 2 * chunk, 2 * chunk + 1,
                   5 * chunk - 1, 5 * chunk):
        if n_used < 1:
            continue
        trips = int(moe._trips(jnp.array([n_used]), chunk))
        assert trips == -(-n_used // chunk)
        plan = moe.Dispatch(jnp.zeros(5 * chunk * 8, jnp.int32),
                            jnp.zeros(5 * chunk * 8, bool),
                            jnp.zeros(5 * chunk, jnp.int32),
                            jnp.array([n_used]), jnp.int32(0))
        inside = [int(moe._chunk_of(plan, c, chunk, 8)[0].n_used[0])
                  for c in range(trips)]
        assert sum(inside) == n_used and min(inside) >= 1


def test_a_buffer_of_one_chunk_has_no_loop(monkeypatch, chunks_of_24):
    """A buffer no longer than a chunk (every toy shape of the models'
    tests, the benchmark's rehearsal) is the layer over all of it under
    plain autodiff: no `while` but the dispatch's own (`searchsorted`) and
    no `cond`; a longer one has a loop in each direction, still no `cond`,
    and is padded to whole chunks."""
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "0")    # no kernel's `cond`

    def loops_and_conds(T, n_experts):
        args = (jnp.zeros((T, 16)), jnp.zeros((16, n_experts)),
                jnp.zeros((2, 16, 8)), jnp.zeros((2, 16, 8)),
                jnp.zeros((2, 8, 16)))
        text = str(jax.make_jaxpr(jax.grad(lambda *a: moe.moe_routed(
            *a, top_k=1, row_tile=8).sum(), argnums=(0, 2)))(*args))
        dispatch = str(jax.make_jaxpr(lambda ids: moe.plan_dispatch(
            ids, 2, row_tile=8))(jnp.zeros((T, 1), jnp.int32)))
        return (text.count(" while[") - dispatch.count(" while["),
                text.count(" cond["))
    assert loops_and_conds(40, 4) == (0, 0)         # 5 + 2 tiles
    assert loops_and_conds(176, 4) == (0, 0)        # 22 + 2: one chunk
    # 23 + 2: two chunks, and in each direction the loop over them and the
    # loop over the pieces of its scatter-add
    assert loops_and_conds(184, 4) == (4, 0)
    plan = moe._whole_chunks(_plan((150, 38), 8), CHUNK, 8)
    assert plan.tile_expert.shape[0] == 48
    assert plan.row_pair.shape == plan.row_valid.shape == (48 * 8,)
    assert not onp.any(onp.asarray(plan.row_valid)[26 * 8:])


@pytest.mark.parametrize("routing,over", [("exactly_one", False),
                                          ("all_held", True)])
def test_a_passed_bound_is_nan_on_a_chunked_buffer(chunks_of_24, routing,
                                                   over):
    """A bound of 256 pairs under the chunked shapes: 32 + 2 tiles, two
    chunks of 24. 184 pairs on held expert 0 fit the first chunk; 512 on the
    two pass the bound, the buffer is then full (so the loop reaches its
    end) and the layer is NaN throughout."""
    shape = CHUNKED_SHAPE
    x, router = _chunked_case(routing)
    out = moe.moe_routed(
        x, router, *_weights(8, shape["E"], shape["d"], shape["f"]), top_k=2,
        rows_bound=256, row_tile=8)
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


@pytest.mark.parametrize("score,scale", [("softmax", 1.0), ("sigmoid", 2.5),
                                         ("sigmoid", 1.0)])
def test_a_score_and_a_scale_against_a_plain_top_k(score, scale):
    """`route_top_k`'s ten maxima against `lax.top_k` of the plain score:
    the same experts, in the same order, their weights the chosen scores
    over their sum times the scale."""
    x = jax.random.normal(jax.random.PRNGKey(2), (60, 32))
    w = jax.random.normal(jax.random.PRNGKey(3), (32, 64))
    weights, ids = moe.route_top_k(x, w, 10, score=score, scale=scale)
    plain = (jax.nn.sigmoid(x @ w) if score == "sigmoid"
             else jax.nn.softmax(x @ w, -1))
    top, top_ids = jax.lax.top_k(plain, 10)
    onp.testing.assert_array_equal(ids, top_ids)
    onp.testing.assert_allclose(
        weights, scale * top / top.sum(-1, keepdims=True), rtol=1e-6)
    onp.testing.assert_allclose(weights.sum(-1), scale, rtol=1e-6)


def test_fewer_experts_held_than_a_token_chooses_drops_no_pair():
    """8 held experts under `top_k` 10 of 16: a token sends the held ones at
    most 8 pairs, the buffer has tokens x 8 rows and a tile an expert, and
    every pair on a held expert has its row; the layer is the sum over the
    held experts of a plain loop."""
    T, d, f, E, held, top_k, row_tile = 48, 32, 24, 16, 8, 10, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, d))
    router = jax.random.normal(ks[1], (d, E))
    gate, up = (0.3 * jax.random.normal(k, (held, d, f)) for k in ks[2:4])
    down = 0.3 * jax.random.normal(ks[4], (held, f, d))
    weights, ids = moe.route_top_k(x, router, top_k, "sigmoid", 2.5)
    plan = moe.plan_dispatch(ids, held, first_expert=4, row_tile=row_tile)
    assert plan.row_pair.shape[0] == T * held + held * row_tile
    on_held = (onp.asarray(ids) >= 4) & (onp.asarray(ids) < 4 + held)
    # with 10 of 16 chosen at least 2 of the 8 held are chosen by every token
    assert on_held.sum(-1).min() >= 2 and on_held.sum(-1).max() <= held
    rows = onp.asarray(plan.row_pair)[onp.asarray(plan.row_valid)]
    assert sorted(rows) == sorted(onp.flatnonzero(on_held.reshape(-1)))
    assert int(plan.n_dropped) == 0
    got = moe.moe_routed(x, router, gate, up, down, top_k, first_expert=4,
                         row_tile=row_tile, score="sigmoid", scale=2.5)
    want = jnp.zeros_like(x)
    for e in range(held):
        share = jnp.sum(jnp.where(ids == 4 + e, weights, 0.0), -1)
        want = want + share[:, None] * (
            (jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


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


def test_the_trips_a_loop_made_are_read_from_a_module_and_its_events(
        monkeypatch, chunks_of_24):
    """`tools/moe_rungs.py` on the chunked toy layer compiled here: both
    loops found, forward and backward, every instruction of a body filed
    under its loop by the `rows_<R>` scope; and from events of those names
    in three steps, how many trips each step made and how long one took."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "moe_rungs", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "moe_rungs.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "0")
    shape = CHUNKED_SHAPE
    E, d, f = shape["E"], shape["d"], shape["f"]
    x, router = _chunked_case("uniform")
    weights = [jnp.ones(s) for s in [(E, d, f), (E, d, f), (E, f, d)]]

    def forward(*a):
        with jax.named_scope("forward"):
            return moe.moe_routed(*a, top_k=2, row_tile=8).sum()
    text = jax.jit(jax.value_and_grad(forward, argnums=(0, 2))).lower(
        x, router, *weights).compile().as_text()
    filed, loops, marks = tool.loop_of_instruction(
        tool.hlo_scopes.parse(text))
    assert sorted(loops.values()) == [("backward", 192), ("forward", 192)]
    forward = next(l for l, (way, _) in loops.items() if way == "forward")
    body = [n for n, l in filed.items() if l == forward]
    # the XLA loop over experts: no kernel marks a trip, so all of it does
    assert len(body) > 3 and marks[forward] == set(body)
    # three steps that start at 0, 1000 and 2000 ns: two trips, two, three;
    # 2 ns an instruction, and an instruction of no loop in between
    events = [(step * 1000 + trip * 100 + i, 2, n)
              for step, trips in enumerate((2, 2, 3)) for trip in range(trips)
              for i, n in enumerate(body)] + [(1500, 7, "fusion.outside")]
    report = tool.by_loop(events, [0, 1000, 2000], filed, loops, marks)
    assert [r["loop"] for r in report] == [forward]
    assert report[0]["pass"] == "forward" and report[0]["rows"] == 192
    assert report[0]["in_order"] == "2x2 3x1"
    assert report[0]["trips_a_step"] == pytest.approx(7 / 3)
    assert report[0]["ms_a_trip"] == pytest.approx(2e-6 * len(body))
    assert report[0]["ms_a_step"] == pytest.approx(7 * 2e-6 * len(body) / 3)
    # the body's longest instructions, ms a trip each: 2 ns every one here
    assert set(report[0]["body"]) <= set(body)
    assert len(report[0]["body"]) == min(len(body), tool.LONGEST)
    assert list(report[0]["body"].values()) == [pytest.approx(2e-6)] * len(
        report[0]["body"])

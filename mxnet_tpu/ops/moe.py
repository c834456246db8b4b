"""A mixture-of-experts layer for the chip that holds some of the experts.

The layer is told which experts it holds (`first_expert`, and as many as its
weights have): it routes over ALL experts, as every chip of the deployment
does on its own tokens, and adds up what its own experts give. What the
absent experts would add is another chip's to compute and to send; on one
chip the layer runs without that exchange, and nothing stands in for it.

    p = softmax(x W_r) over all experts, in float32 at `highest` (the choice
        of ten in 512 is discrete: a rounded product flips it); or
        p = sigmoid(x W_r), each expert by itself (`score="sigmoid"`)
    the top_k largest, their weights divided by their sum, times `scale`
    E_e(x) = (silu(x G_e) * (x U_e)) D_e
    routed(x) = sum over the chosen experts e held here of p_e E_e(x)

Dispatch has static shapes and no per-expert capacity. The (token, expert)
pairs that fall on held experts are laid out by expert in one buffer of
`rows_bound + n_held * row_tile` rows in which every expert's rows start on
a tile boundary (an expert without rows keeps one empty tile, so that its
weight gradient is written). No pair is dropped, whatever the routing, while
the pairs on held experts number at most `rows_bound`; `None` takes the most
there can be, tokens x min(top_k, n_held). Past the bound the buffer cannot
hold the pairs of the last experts: `Dispatch.n_dropped` counts them, and
`moe_routed` then returns NaN for every token, so that the step's loss says
at once that the layer computed another function (a larger bound cures it).
The buffer is described by one expert id a row tile, which the kernels read
through scalar prefetch.

The chunks. The buffer is as long as the worst routing needs, and most
routings fill a small part of it: the held experts' rows lie one after
another from row 0, so the rows in use are a prefix, `Dispatch.n_used` tiles
long. Everything that touches the buffer (the row gather, the three grouped
products, `silu(gate) * up`, the router's share, the float32 scatter-add, and
their backward passes) therefore walks it in chunks of `CHUNK_TILES` tiles,
for as many chunks as hold `n_used` tiles (`_trips`): one `lax.fori_loop` a
direction whose bound is read on the device, so one copy of the layer's code
whatever the routing, and work that follows `n_used` in steps of a chunk.
The last chunk reaches the buffer's end (the plan is padded to whole chunks
with tiles that are never in use), so no routing drops a pair that the
buffer held. Inside a trip the two scatter-adds into (T, d) sums (the
tokens' sum forward, their cotangent backward) go piece by piece of
`_SCATTER_TILES` tiles, for as many pieces as hold the chunk's tiles in use
(`_added_in_pieces`): XLA's scatter-add passes over all of its operand once
it is handed more than 1,024 rows. A buffer no longer than one chunk is
`_routed_rows` over all of it under plain autodiff, with no loop. A chunk's
body runs under `jax.named_scope("rows_<C * row_tile>")` (every instruction
of the compiled module says that it belongs to the buffer's rows:
`tools/moe_rungs.py` reads from a device trace how many trips each loop
made) and is counted once a trace as `ops.moe.chunk.<rows>`.

The derivative of the loop is one `custom_vjp` (`_routed`) with a loop in
each direction: a `while` with a traced bound has no transpose. The forward
rule keeps the layer's inputs and the plan and nothing else; the backward
rule makes a chunk's forward again and pulls the cotangent back through it
by hand, chunk by chunk. It carries the float32 cotangent of the tokens, the
router's weights' cotangent and the three weight gradients. An expert's rows
may lie on both sides of a chunk's edge, so `moe_tgmm` there writes into the
gradient it is handed (`input_output_aliases`): an expert's first tile in
the whole buffer starts its block, every later tile adds to it, in whichever
chunk it lies, and a chunk leaves the blocks of the experts it does not
visit as they were. Every held expert has a tile in use, so the loop writes
every block and the gradients start as they are allocated, not zero-filled.
Under `jax.checkpoint`, which is how the decoder calls the layer, the
recomputed forward loop is dead and the products run as often as without
the loop.

The grouped product is two Pallas kernels, named for the device trace:

    moe_gmm    out[rows of e] = lhs[rows of e] @ rhs[e]      (and @ rhs[e].T,
               the gradient to the rows)
    moe_tgmm   out[e] = lhs[rows of e].T @ dy[rows of e]     (the gradient to
               the weights)

Both visit only the tiles that hold rows (`moe_gmm` writes zeros into the
others, so nothing downstream reads an unwritten row) and take the weights as
they are stored, casting a block to the rows' type in VMEM. `grouped_matmul`
carries the `custom_vjp`. Where the kernels are on (`pallas_stats.pallas_on`)
and refuse a shape or a type, the same function is a loop over experts in
XLA, and counted (`ops.pallas.fallback.moe_gmm.<reason>`); off the TPU and
without MXNET_FLASH_INTERPRET=1 no kernel was asked for, the loop runs and
nothing is counted, as with the flash kernels.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_stats
from .pallas_stats import compiler_params, note_dispatch, note_fallback

__all__ = ["route_top_k", "plan_dispatch", "grouped_matmul", "moe_routed",
           "Dispatch"]

F32 = jnp.float32
ROW_TILE = 128          # rows of a tile: one pass of the MXU's 128 columns
_BLOCK_ELEMENTS = 1 << 19   # of a weight block: 2 MB in float32
# tiles of a chunk of the buffer: what the layer works over is `n_used` rounded
# up to whole chunks, so a routing at a chunk's edge flips the step's time by
# a chunk's work from step to step; against that, a trip's fixed cost (twelve
# kernel launches, the slices of the plan). PERF.md section 6, PR 38, has the
# lengths that were read on the chip.
CHUNK_TILES = 32
_SCATTER_TILES = 8      # of a piece of a chunk's scatter-add: 1,024 rows

# ------------------------------------------------------------------ routing
def _largest(p, k):
    """(values (T, k), indices (T, k)) of the k largest of each row of
    p >= 0, largest first, the lower index first among equals: k rounds of
    a row maximum. `lax.top_k` sorts whole rows on the TPU (8,192 rows of
    512 with their indices, once a layer and once more where it is
    recomputed); ten maxima read the 16 MB ten times."""
    column = lax.broadcasted_iota(jnp.int32, p.shape, 1)
    values, indices = [], []
    for _ in range(k):
        index = jnp.argmax(p, axis=-1).astype(jnp.int32)
        values.append(jnp.max(p, axis=-1))
        indices.append(index)
        p = jnp.where(column == index[:, None], -1.0, p)
    return jnp.stack(values, axis=-1), jnp.stack(indices, axis=-1)


_SCORES = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
           "sigmoid": jax.nn.sigmoid}


def route_top_k(x, w_router, top_k, score="softmax", scale=1.0):
    """(weights (T, top_k) float32 summing to `scale` a token, expert ids
    (T, top_k) int32) over all the experts `w_router` (d, n_experts) has.
    `score` makes an expert's score of its logit: "softmax" over all
    experts, or "sigmoid", each expert by itself."""
    logits = jnp.dot(x.astype(F32), w_router.astype(F32),
                     precision=lax.Precision.HIGHEST)
    top, ids = _largest(_SCORES[score](logits), top_k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    # no product by 1: a softmax router's program stays the one it was
    return (weights if scale == 1.0 else weights * scale), ids


class Dispatch(NamedTuple):
    """One routing laid out in the buffer of R rows."""
    row_pair: jax.Array     # (R,) the flat (token * top_k + slot) pair a
    #                         row holds, 0 where it holds none
    row_valid: jax.Array    # (R,) bool
    tile_expert: jax.Array  # (R / row_tile,) the held expert of each tile
    n_used: jax.Array       # (1,) the tiles in use
    n_dropped: jax.Array    # () the pairs on held experts that found no
    #                         row: 0 unless they number over `rows_bound`


def plan_dispatch(ids, n_held, first_expert=0, rows_bound=None,
                  row_tile=ROW_TILE):
    """Where each routed pair goes. ids (T, top_k) expert ids over all
    experts; the experts held are first_expert .. first_expert + n_held - 1.
    A counting sort: a pair's place among its expert's pairs is a running
    count, and one scatter of pair numbers fills the buffer (no sort: XLA
    takes 15 s to compile one of this length for the TPU)."""
    T, top_k = ids.shape
    if rows_bound is None:
        rows_bound = T * min(top_k, n_held)
    n_tiles = -(-rows_bound // row_tile) + n_held
    local = ids.reshape(-1) - first_expert
    held = (local >= 0) & (local < n_held)
    local = jnp.where(held, local, 0)
    running = jnp.cumsum(held[:, None] & (local[:, None] == jnp.arange(
        n_held)[None, :]), axis=0, dtype=jnp.int32)
    counts = running[-1]
    # every expert keeps a tile, the last ones too where the buffer is full
    tile_end = jnp.minimum(
        jnp.cumsum(jnp.maximum(1, -(-counts // row_tile))),
        n_tiles - (n_held - 1 - jnp.arange(n_held)))
    tiles = jnp.diff(tile_end, prepend=0)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        n_held - 1).astype(jnp.int32)
    within = jnp.take_along_axis(running, local[:, None], axis=1)[:, 0] - 1
    fits = held & (within < (tiles * row_tile)[local])
    row = jnp.where(fits, (tile_end - tiles)[local] * row_tile + within,
                    n_tiles * row_tile)
    pairs = jnp.arange(T * top_k, dtype=jnp.int32)
    row_pair = jnp.full((n_tiles * row_tile,), -1, jnp.int32).at[row].set(
        pairs, mode="drop", unique_indices=True)
    valid = row_pair >= 0
    return Dispatch(jnp.where(valid, row_pair, 0), valid, tile_expert,
                    tile_end[-1:].astype(jnp.int32),
                    jnp.sum(held & ~fits, dtype=jnp.int32))


# ------------------------------------------------------------------ kernels
def _column_block(cols, contraction):
    """Columns of a weight block: all of a narrow matrix, else the multiple
    of 128 dividing `cols` that keeps the block at `_BLOCK_ELEMENTS`."""
    if cols % 128 or cols * contraction <= _BLOCK_ELEMENTS:
        return cols
    best = 128
    for tn in range(128, cols + 1, 128):
        if cols % tn == 0 and tn * contraction <= _BLOCK_ELEMENTS:
            best = tn
    return best


def _gmm_kernel(tile_expert, n_used, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs):
    del tile_expert
    used = pl.program_id(1) < n_used[0]

    @pl.when(used)
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        out_ref[...] = lax.dot_general(
            lhs_ref[...], rhs_ref[0].astype(lhs_ref.dtype), dims,
            preferred_element_type=F32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _tgmm_kernel(tile_expert, n_used, *refs, into):
    if into:
        continues, lhs_ref, dy_ref, before_ref, out_ref = refs
    else:
        lhs_ref, dy_ref, out_ref = refs
    i = pl.program_id(1)
    first = (i == 0) | (tile_expert[i] != tile_expert[jnp.maximum(i - 1, 0)])
    # tile 0 goes on with the expert whose earlier rows another call summed
    resumes = (i == 0) & (continues[0] != 0) if into else False

    @pl.when(i < n_used[0])
    def _():
        # the transpose in float32, where Mosaic has one for every shape
        lhs_t = lhs_ref[...].astype(F32).T.astype(lhs_ref.dtype)
        acc = lax.dot_general(lhs_t, dy_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=F32)

        if into:
            @pl.when(resumes)
            def _():
                out_ref[0] = before_ref[0] + acc.astype(out_ref.dtype)

        @pl.when(first & jnp.logical_not(resumes))
        def _():
            out_ref[0] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(first))
        def _():
            out_ref[0] += acc.astype(out_ref.dtype)


def _last_used(i, n_used):
    """Tile i, or the last tile in use for the steps after it: no block is
    fetched for them."""
    return jnp.minimum(i, n_used[0] - 1)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _gmm(lhs, rhs, tile_expert, n_used, transpose_rhs, row_tile, interpret):
    """out (R, N): each tile of lhs (R, K) times its expert's rhs[e] (K, N),
    or times rhs[e].T where rhs is (E, N, K) and `transpose_rhs`."""
    R, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _column_block(N, K)

    def rhs_map(c, i, tile_expert, n_used):
        e = tile_expert[_last_used(i, n_used)]
        return (e, c, 0) if transpose_rhs else (e, 0, c)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // tn, R // row_tile),
            in_specs=[pl.BlockSpec((row_tile, K), lambda c, i, t, n:
                                   (_last_used(i, n), 0)),
                      pl.BlockSpec((1, tn, K) if transpose_rhs
                                   else (1, K, tn), rhs_map)],
            out_specs=pl.BlockSpec((row_tile, tn), lambda c, i, t, n: (i, c))),
        out_shape=jax.ShapeDtypeStruct((R, N), lhs.dtype),
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",     # the HLO instruction, and so the device trace
    )(tile_expert, n_used, lhs, rhs)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _tgmm(lhs, dy, tile_expert, n_used, n_experts, out_dtype, row_tile,
          interpret, into=None, continues=None):
    """out (E, K, N): out[e] = lhs[rows of e].T @ dy[rows of e]; the blocks
    of experts without a tile in use are not written. With `into` (E, K, N)
    the result is written into that array: the blocks of the experts these
    rows do not visit stay, and where `continues` (1,) is not 0 the expert
    of tile 0 adds to its block in place of starting it."""
    R, K = lhs.shape
    N = dy.shape[1]
    tn = _column_block(N, K)
    scalars, operands = [tile_expert, n_used], [lhs, dy]
    in_specs = [pl.BlockSpec((row_tile, K), lambda c, i, t, n, *_:
                             (_last_used(i, n), 0)),
                pl.BlockSpec((row_tile, tn), lambda c, i, t, n, *_:
                             (_last_used(i, n), c))]
    if into is not None:
        scalars.append(continues)
        operands.append(into)
        # tile 0's expert alone is read, once a column block
        in_specs.append(pl.BlockSpec((1, K, tn), lambda c, i, t, n, *_:
                                     (t[0], 0, c)))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, into=into is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(N // tn, R // row_tile),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, K, tn), lambda c, i, t, n, *_:
                                   (t[_last_used(i, n)], 0, c))),
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N), out_dtype),
        input_output_aliases=({} if into is None else
                              {len(scalars) + len(operands) - 1: 0}),
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_tgmm",
    )(*scalars, *operands)


def _row_masks(tile_expert, n_used, row_tile, n_experts):
    """(R, E) bool: the rows of each expert, tiles in use only."""
    tile = jnp.arange(tile_expert.shape[0])
    expert = jnp.where(tile < n_used[0], tile_expert, n_experts)
    return (jnp.repeat(expert, row_tile)[:, None]
            == jnp.arange(n_experts)[None, :])


def _gmm_loop(lhs, rhs, tile_expert, n_used, transpose_rhs, row_tile):
    """The grouped product as a loop over experts in XLA: every expert over
    every row, kept where the row is its own."""
    masks = _row_masks(tile_expert, n_used, row_tile, rhs.shape[0])
    out = 0.0
    for e in range(rhs.shape[0]):
        w = rhs[e].astype(lhs.dtype)
        y = jnp.dot(lhs, w.T if transpose_rhs else w,
                    preferred_element_type=F32)
        out = out + jnp.where(masks[:, e:e + 1], y, 0.0)
    return out.astype(lhs.dtype)


def _tgmm_loop(lhs, dy, tile_expert, n_used, n_experts, out_dtype, row_tile):
    masks = _row_masks(tile_expert, n_used, row_tile, n_experts)
    return jnp.stack([
        jnp.dot(jnp.where(masks[:, e:e + 1], lhs, 0).T, dy,
                preferred_element_type=F32)
        for e in range(n_experts)]).astype(out_dtype)


def _kernel_reason(lhs, rhs, row_tile):
    """None where the kernels take the shapes, else a word for the fallback
    counter."""
    if not pallas_stats.pallas_on():
        return "backend"
    if lhs.shape[0] % row_tile or row_tile % 8:
        return "row_tile"
    if lhs.dtype.itemsize > rhs.dtype.itemsize:
        return "dtype"
    return None


def _product(lhs, rhs, tile_expert, n_used, transpose_rhs, row_tile, pallas):
    if pallas:
        return _gmm(lhs, rhs, tile_expert, n_used, transpose_rhs, row_tile,
                    pallas_stats.interpret())
    return _gmm_loop(lhs, rhs, tile_expert, n_used, transpose_rhs, row_tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(lhs, rhs, tile_expert, n_used, row_tile, pallas):
    return _product(lhs, rhs, tile_expert, n_used, False, row_tile, pallas)


def _grouped_fwd(lhs, rhs, tile_expert, n_used, row_tile, pallas):
    return (_product(lhs, rhs, tile_expert, n_used, False, row_tile, pallas),
            (lhs, rhs, tile_expert, n_used))


def _pulled_back(lhs, rhs, dy, tile_expert, n_used, row_tile, pallas,
                 into=None, continues=None):
    """(the cotangent of lhs, that of rhs) of the grouped product from its
    result's cotangent dy. With `into`, rhs's is summed into that array, as
    `_tgmm` says."""
    dy = dy.astype(lhs.dtype)
    dlhs = _product(dy, rhs, tile_expert, n_used, True, row_tile, pallas)
    if pallas:
        drhs = _tgmm(lhs, dy, tile_expert, n_used, rhs.shape[0], rhs.dtype,
                     row_tile, pallas_stats.interpret(), into, continues)
    else:
        drhs = _tgmm_loop(lhs, dy, tile_expert, n_used, rhs.shape[0],
                          rhs.dtype, row_tile)
        if into is not None:
            drhs = into + drhs
    return dlhs, drhs


def _grouped_bwd(row_tile, pallas, res, dy):
    lhs, rhs, tile_expert, n_used = res
    return _pulled_back(lhs, rhs, dy, tile_expert, n_used, row_tile,
                        pallas) + (None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, tile_expert, n_used, row_tile=ROW_TILE):
    """Each row tile of lhs (R, K) times the weights of the expert it
    belongs to, rhs (E, K, N): (R, N) in lhs's type, float32 out of the MXU,
    zeros in the tiles past `n_used`. Differentiable in lhs and rhs; the
    gradient to rhs has rhs's type (float32 weights: not rounded)."""
    reason = _kernel_reason(lhs, rhs, row_tile)
    if reason is None:
        note_dispatch("moe_gmm")
    elif reason != "backend":
        note_fallback("moe_gmm", reason)
    return _grouped(lhs, rhs, tile_expert, n_used, row_tile, reason is None)


# --------------------------------------------------------------- the chunks
def _gated(gate_out, up):
    return (jax.nn.silu(gate_out.astype(F32)) * up.astype(F32)
            ).astype(gate_out.dtype)


def _rows_forward(x, weights, w_gate, w_up, w_down, rows, top_k, row_tile):
    """Over the rows of the buffer that `rows`, a `Dispatch`, describes:
    (each row's token (R,), its share of it (R,), what its expert gives it
    (R, d) float32), and the products' operands on the way (the rows picked,
    the gate's and the up projection's results, the hidden rows)."""
    token = rows.row_pair // top_k
    picked = x[token]
    gate_out = grouped_matmul(picked, w_gate, rows.tile_expert, rows.n_used,
                              row_tile)
    up = grouped_matmul(picked, w_up, rows.tile_expert, rows.n_used, row_tile)
    hidden = _gated(gate_out, up)
    out = grouped_matmul(hidden, w_down, rows.tile_expert, rows.n_used,
                         row_tile)
    share = jnp.where(rows.row_valid, weights.reshape(-1)[rows.row_pair], 0.0)
    return (token, share, out.astype(F32)), (picked, gate_out, up, hidden)


def _added_in_pieces(token, rows, n_used, row_tile, combined):
    """`combined` (T, d) float32 with `rows` (R, d) added at `token` (R,),
    for the first `n_used` (1,) tiles of rows: piece by piece of
    `_SCATTER_TILES` tiles, as many as hold them. Up to 1,024 rows XLA's
    scatter-add for the TPU costs what its rows cost (0.14 ms at d = 2,048);
    one row more and it passes over all of `combined` first (0.43 ms and
    0.09 ms a thousand rows), which was half of a trip."""
    piece = _SCATTER_TILES * row_tile
    # a slice that started short of a piece before the end would be moved
    # back, and add rows twice
    assert rows.shape[0] % piece == 0, (rows.shape, piece)

    def add(i, combined):
        return combined.at[lax.dynamic_slice(token, (i * piece,), (piece,))
                           ].add(lax.dynamic_slice(
                               rows, (i * piece, 0), (piece, rows.shape[1])))
    return lax.fori_loop(0, _trips(n_used, _SCATTER_TILES), add, combined)


@functools.partial(jax.jit, static_argnames=("top_k", "row_tile", "gate"))
def _routed_rows(x, weights, w_gate, w_up, w_down, plan, *, top_k, row_tile,
                 gate):
    """(T, d) float32: the held experts' sum over the whole buffer, for a
    buffer no longer than a chunk. `gate` is what `pallas_stats` answered
    the caller: `grouped_matmul` asks it again while this is traced, so it
    belongs to the trace's key. Jitted here so that a model's layers share
    one trace."""
    del gate
    with jax.named_scope("rows_%d" % plan.row_pair.shape[0]):
        token, share, out = _rows_forward(x, weights, w_gate, w_up, w_down,
                                          plan, top_k, row_tile)[0]
        # rows of one token lie in different experts' tiles: added up in
        # float32
        return jnp.zeros(x.shape, F32).at[token].add(out * share[:, None])


def _trips(n_used, chunk):
    """The chunks of `chunk` tiles that hold the first `n_used` (1,) tiles."""
    return (n_used[0] + chunk - 1) // chunk


def _whole_chunks(plan, chunk, row_tile):
    """`plan` with its buffer made a whole number of chunks long, by tiles
    past `n_used`: a slice that started short of a chunk before the end
    would be moved back, and count rows twice."""
    tiles = -plan.tile_expert.shape[0] % chunk
    return plan._replace(
        row_pair=jnp.pad(plan.row_pair, (0, tiles * row_tile)),
        row_valid=jnp.pad(plan.row_valid, (0, tiles * row_tile)),
        tile_expert=jnp.pad(plan.tile_expert, (0, tiles), mode="edge"))


def _chunk_of(plan, c, chunk, row_tile):
    """(chunk c of `plan` as a `Dispatch` of its own, (1,) whether its
    first tile goes on with the expert of the tile before it)."""
    first, rows = c * chunk, chunk * row_tile
    tile_expert = lax.dynamic_slice(plan.tile_expert, (first,), (chunk,))
    before = lax.dynamic_index_in_dim(
        plan.tile_expert, jnp.maximum(first - 1, 0), keepdims=False)
    continues = (c > 0) & (before == tile_expert[0])
    return Dispatch(
        lax.dynamic_slice(plan.row_pair, (c * rows,), (rows,)),
        lax.dynamic_slice(plan.row_valid, (c * rows,), (rows,)), tile_expert,
        jnp.clip(plan.n_used - first, 0, chunk),
        plan.n_dropped), continues.astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("chunk", "top_k", "row_tile",
                                             "gate"))
def _routed_chunks(x, weights, w_gate, w_up, w_down, plan, *, chunk, top_k,
                   row_tile, gate):
    """(T, d) float32: `_routed_rows` chunk by chunk over the chunks that
    hold a pair."""
    del gate
    pallas_stats.note_chunk(chunk * row_tile)

    def body(c, combined):
        with jax.named_scope("rows_%d" % (chunk * row_tile)):
            rows, _ = _chunk_of(plan, c, chunk, row_tile)
            token, share, out = _rows_forward(
                x, weights, w_gate, w_up, w_down, rows, top_k, row_tile)[0]
            return _added_in_pieces(token, out * share[:, None], rows.n_used,
                                    row_tile, combined)
    return lax.fori_loop(0, _trips(plan.n_used, chunk), body,
                         jnp.zeros(x.shape, F32))


@functools.partial(jax.jit, static_argnames=("chunk", "top_k", "row_tile",
                                             "gate"))
def _routed_chunks_vjp(inputs, plan, dy, *, chunk, top_k, row_tile, gate):
    """The cotangents of `_routed_chunks`'s five inputs: chunk by chunk, the
    chunk's forward made again and `dy` (T, d) float32 pulled back through
    it. Jitted here for the same reason: one trace of the backward loop a
    model."""
    del gate
    x, weights, w_gate, w_up, w_down = inputs
    pallas_stats.note_chunk(chunk * row_tile)
    pallas = _kernel_reason(
        jax.ShapeDtypeStruct((chunk * row_tile,), x.dtype), w_gate,
        row_tile) is None

    def body(c, carried):
        dx, dweights, dw_gate, dw_up, dw_down = carried
        with jax.named_scope("rows_%d" % (chunk * row_tile)):
            rows, continues = _chunk_of(plan, c, chunk, row_tile)
            (token, share, out), (picked, gate_out, up, hidden) = (
                _rows_forward(x, weights, w_gate, w_up, w_down, rows, top_k,
                              row_tile))

            def pulled_back(lhs, rhs, dy, into):
                return _pulled_back(lhs, rhs, dy, rows.tile_expert,
                                    rows.n_used, row_tile, pallas, into,
                                    continues)
            dy_rows = dy[token]
            dweights = dweights.at[rows.row_pair].add(jnp.where(
                rows.row_valid, jnp.sum(dy_rows * out, axis=-1), 0.0))
            dhidden, dw_down = pulled_back(
                hidden, w_down, dy_rows * share[:, None], dw_down)
            dgate_out, dup = jax.vjp(_gated, gate_out, up)[1](dhidden)
            by_gate, dw_gate = pulled_back(picked, w_gate, dgate_out, dw_gate)
            by_up, dw_up = pulled_back(picked, w_up, dup, dw_up)
            dx = _added_in_pieces(
                token, by_gate.astype(F32) + by_up.astype(F32), rows.n_used,
                row_tile, dx)
        return dx, dweights, dw_gate, dw_up, dw_down
    # every held expert has a tile in use, and its first tile starts its
    # block: the kernels' gradients need no zeros to start from
    start = (lambda w: lax.empty(w.shape, w.dtype)) if pallas else (
        jnp.zeros_like)
    dx, dweights, *dw = lax.fori_loop(
        0, _trips(plan.n_used, chunk), body,
        (jnp.zeros(x.shape, F32), jnp.zeros(weights.size, weights.dtype),
         start(w_gate), start(w_up), start(w_down)))
    return (dx.astype(x.dtype), dweights.reshape(weights.shape), *dw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _routed(x, weights, w_gate, w_up, w_down, plan, chunk, top_k, row_tile,
            gate):
    """`_routed_chunks`, with a backward loop of its own."""
    return _routed_chunks(x, weights, w_gate, w_up, w_down, plan, chunk=chunk,
                          top_k=top_k, row_tile=row_tile, gate=gate)


def _routed_fwd(x, weights, w_gate, w_up, w_down, plan, *static):
    inputs = (x, weights, w_gate, w_up, w_down)
    return _routed(*inputs, plan, *static), (inputs, plan)


def _routed_bwd(chunk, top_k, row_tile, gate, res, dy):
    inputs, plan = res
    return _routed_chunks_vjp(inputs, plan, dy, chunk=chunk, top_k=top_k,
                              row_tile=row_tile, gate=gate) + (None,)


_routed.defvjp(_routed_fwd, _routed_bwd)


# ---------------------------------------------------------------- the layer
def moe_routed(x, w_router, w_gate, w_up, w_down, top_k, first_expert=0,
               rows_bound=None, row_tile=ROW_TILE, score="softmax",
               scale=1.0):
    """What the experts held here add for the tokens x (T, d): the sum over
    a token's chosen experts e in first_expert .. first_expert + E - 1 of
    p_e E_e(x), in x's type. w_router (d, n_experts) over all experts;
    w_gate, w_up (E, d, f) and w_down (E, f, d) of the E held; `score` and
    `scale` as `route_top_k` takes them. NaN throughout where the routing
    put more than `rows_bound` pairs here."""
    weights, ids = route_top_k(x, w_router, top_k, score, scale)
    plan = plan_dispatch(ids, w_gate.shape[0], first_expert, rows_bound,
                         row_tile)
    gate = (pallas_stats.pallas_on(), pallas_stats.interpret())
    args = (x, weights, w_gate, w_up, w_down)
    if plan.tile_expert.shape[0] <= CHUNK_TILES:    # plain autodiff, no loop
        combined = _routed_rows(*args, plan, top_k=top_k, row_tile=row_tile,
                                gate=gate)
    else:
        combined = _routed(*args, _whole_chunks(plan, CHUNK_TILES, row_tile),
                           CHUNK_TILES, top_k, row_tile, gate)
    return jnp.where(plan.n_dropped > 0, jnp.nan, combined).astype(x.dtype)

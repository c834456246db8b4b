"""Fused (flash) attention for TPU — forward AND backward Pallas kernels.

The reference's fused attention is the contrib transformer op family
(`_contrib_interleaved_matmul_selfatt_qk` etc.,
src/operator/contrib/transformer.cc) — CUDA batched-GEMM fusions with O(S^2)
memory in both directions. The TPU-native answer is a flash-attention-2
kernel pair: online softmax over K/V tiles streamed through VMEM on the
forward (O(S) HBM traffic, MXU matmuls, fp32 accumulation), and a
rematerializing backward that recomputes each S-tile IN the kernel from the
saved logsumexp — dq/dk/dv each see O(S) HBM bytes instead of the S^2
probability matrix the reference's backward streams.

Layout: one algorithm, three kernels (`flash_fwd`, `flash_dq`,
`flash_dkv`), three views of the operands.

* `flash_attention_bshd` takes q, k, v as a projection leaves them,
  (B, S, H, D), and the kernels index the free (B, S, H*D) view: a block is
  `block_b` batch rows x a sequence tile x a 128-lane GROUP of heads (two
  heads at D = 64, one at D >= 128). A head's scores come from operands
  masked to its own lanes, which costs the MXU what the half-empty
  contraction over D = 64 costs anyway and keeps every load and store
  lane-dense. No transpose goes in or comes out, so autodiff has none to
  transpose back.
* `flash_attention_packed` takes the result of ONE q|k|v projection,
  (B, S, 3*H*D) in `pack_qkv`'s column order: the same blocks of the same
  kernels, picked out of the one array by the index maps. Its backward
  returns one cotangent of that shape: `flash_dq` writes q's blocks of it,
  `flash_dkv` takes the array through `input_output_aliases` and fills k's
  and v's (neighbours in the column order, one block of twice the lanes),
  and both sum their blocks' columns on the way, which is the projection's
  bias gradient. So the projection's dx and dW are one product each over
  one plain array, and nothing passes over an activation between the
  products and the kernels.
* `flash_attention` keeps the (B, H, S, D) arguments; its view is
  (B*H, S, D), a block `block_b` of those rows with D on the lanes. Ring
  attention drives the same kernels per ring block through
  `_pallas_forward` / `_pallas_backward_inner`.

The tile (`_choose_tile`, one pure function of what a call can see: the
view, B, H, Hkv, Sq, Sk, D and the item size) takes the whole sequence up to
512 rows and several batch rows at short sequences, so a grid step moves of
the order of a megabyte; the grid is (row blocks, head groups, outer
blocks, inner blocks) with the inner dimension sequential ("arbitrary"), and
the online-softmax accumulators live in VMEM scratch only where the inner
sweep has more than one block. Non-128-multiple sequence lengths are handled
by in-kernel bounds masks; causal uses the (Sk - Sq) diagonal offset
convention so Sq != Sk cross-attention decodes correctly. The per-row
statistics (logsumexp, delta) cross the kernel boundary with the sequence on
the lanes, (rows, heads, 1, Sq) in blocks of (block_b, heads, 1, block_q):
Mosaic wants a block's last two dimensions to be multiples of (8, 128) or
the array's own. dk/dv are computed transposed (scores as (k, q)), so the
statistics broadcast along sublanes and no product transposes an operand.

A window (`flash_attention_bshd(..., window=W)`: position i sees the keys
i - W < j <= i) runs the same three bodies as `swa_fwd`, `swa_dq` and
`swa_dkv`. Their grid's inner dimension is not the sequence's blocks but the
band's: `_band` gives the first and last inner block an outer block's band
touches, the index maps (`_banded`) walk from the first and stay on the last,
and a step past the last does nothing, so no block outside the band is
fetched or computed. The tile is the causal kernels'.

Products take their operands in the type they arrive in, with float32 out
of the MXU; jax's matmul precision rides into the kernels on `dot_general`
as into any other product of the program (float32 operands: one bfloat16
pass under the default, as Mosaic and XLA both do it; full float32 under
"highest"). The softmax statistics, `lse`, `delta` and the accumulators are
float32 always.

Shapes: q (B, H, Sq, D); k/v (B, Hkv, Sk, D) with H % Hkv == 0 (GQA/MQA);
the `_bshd` entry has S and H swapped. A (B, S, H, D) shape whose heads do
not fill lane groups (128 % D and D % 128 both non-zero, an odd head count
at D = 64, GQA at D < 128) is transposed to the other view, and counted
(`ops.pallas.fallback.flash.<reason>`).

Set MXNET_FLASH_INTERPRET=1 to run the Pallas kernels in interpreter mode
on CPU (the test suite uses this to pin kernel correctness without a chip).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops import pallas_stats
from ..ops.pallas_stats import compiler_params, note_dispatch, note_fallback

__all__ = ["flash_attention", "flash_attention_bshd",
           "flash_attention_packed", "pack_qkv", "paged_attention",
           "paged_attention_chunk"]

_NEG_INF = -1e30
_LANES = 128
_MAX_BLOCK = 512        # sequence rows of q or of k in a block, at most
_MAX_ROWS = 8           # batch rows in a block, at most
_STEP_SCORES = 1 << 18  # score elements a grid step computes, at most
_VMEM_LIMIT = 16 << 20  # what the kernels ask Mosaic for (its own default
#                         on the v5e), more only for a tile estimated at
#                         more. XLA keeps a step's activations in the core's
#                         128 MiB of VMEM from one operation to the next and
#                         has to clear what a Mosaic call asks for: asking
#                         for 48 MiB cost BERT-base's step 1.9-4.9 ms of 98,
#                         for 32 MiB 3.9 ms (PERF.md, PR 32)


def _ref_attention(q, k, v, causal, sm_scale, window=None):
    """Plain-XLA attention, fp32 softmax. Used for CPU fallback and as the
    recompute body of the non-Pallas backward. With `window`, query i sees
    the keys i - window < j <= i (on the causal diagonal's offset).

    GQA runs as a grouped einsum over (kv_head, group) axes rather than
    jnp.repeat of K/V: no materialized copies, and the repeat's reshape+sum
    VJP pattern reshards badly under GSPMD."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Sq, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal or window is not None:
        qi = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0) + (Sk - Sq)
        ki = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        seen = ki <= qi
        if window is not None:
            seen = seen & (ki > qi - window)
        logits = jnp.where(seen, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, Sq, D)


# ------------------------------------------------------------------ the tile
class _Tile(NamedTuple):
    """What one grid step of the three kernels works on."""
    lanes: int      # minor size of a block: `heads` heads side by side
    heads: int
    block_b: int    # rows a block: batch rows, or (batch, head) rows
    block_q: int
    block_k: int
    steps: int      # grid steps a call
    vmem: int       # bytes of VMEM a step is estimated to need


def _seq_block(S):
    """Rows of a sequence a block takes: all of a short one; else the
    multiple of 128 up to `_MAX_BLOCK` (and to S) that covers S in the fewest
    rows, a step priced at 64 rows of its own."""
    if S <= _LANES:
        return S
    sizes = range(_LANES, min(S, _MAX_BLOCK) + 1, _LANES)
    return min(sizes, key=lambda b: (-(-S // b) * (b + 64), -b))


def _lane_group(H, Hkv, D):
    """(lanes, heads) of a block of the (B, S, H*D) view, whole heads side
    by side on a multiple of 128 lanes; or the reason there is none."""
    if D % _LANES == 0:
        return D, 1
    if _LANES % D:
        return "head_dim"
    heads = _LANES // D
    if H % heads:
        return "head_group"
    if H != Hkv:
        # a q head and its k/v head sit on different lanes
        return "gqa_lane_group"
    return _LANES, heads


def _choose_tile(view, B, H, Hkv, Sq, Sk, D, itemsize):
    """The tile for one call, from what the call can see; or the reason (a
    word, for the fallback counter) why this view cannot take the shape.

    `view` "bshd": operands (B, S, H*D), heads in 128-lane groups;
    "packed": the same blocks, out of one (B, S, 3*H*D) array;
    "bhsd": operands (B*H, S, D)."""
    if D % 8 or H % Hkv:
        return "head_dim" if D % 8 else "gqa_group"
    if view == "bhsd":
        lanes, heads, rows = D, 1, B * H
        # a block's rows share one k/v row only where every q head has its
        # own
        most_rows = _MAX_ROWS if H == Hkv else 1
    else:
        group = _lane_group(H, Hkv, D)
        if isinstance(group, str):
            return group
        (lanes, heads), rows, most_rows = group, B, _MAX_ROWS
    bq, bk = _seq_block(Sq), _seq_block(Sk)
    most_rows = min(most_rows, max(1, _STEP_SCORES // (heads * bq * bk)))
    bb = max(b for b in range(1, most_rows + 1) if rows % b == 0)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    f32 = 4
    blocks = 2 * itemsize * bb * lanes * 3 * (bq + bk)   # double-buffered
    stats = 2 * 2 * bb * heads * 8 * bq * f32
    scratch = 0 if nq == nk == 1 else (
        2 * bb * max(bq, bk) * lanes * f32
        + 2 * bb * heads * bq * _LANES * f32)
    live = heads * 6 * bq * bk * f32    # s, p, dp, ds and two operand copies
    return _Tile(lanes, heads, bb, bq, bk,
                 (rows // bb) * (1 if view == "bhsd" else H // heads)
                 * nq * nk, blocks + stats + scratch + live)


# --------------------------------------------------------------- in a kernel
def _bounds_mask(s, q_start, k_start, seq_q, seq_k, causal, q_axis=0,
                 window=None):
    """Mask logits for causal structure, for keys behind the window and for
    keys past the true sequence end (non-divisible block grids read garbage
    there). `q_axis` is the dimension of `s` the queries run along."""
    qi = lax.broadcasted_iota(jnp.int32, s.shape, q_axis) + q_start
    ki = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis) + k_start
    valid = ki < seq_k
    if causal:
        valid = valid & (ki <= qi + (seq_k - seq_q))
    if window is not None:
        valid = valid & (ki > qi + (seq_k - seq_q) - window)
    return jnp.where(valid, s, _NEG_INF)


def _zero_pad_rows(x, start, seq):
    """Zero tile rows past the true sequence end. A padded block read
    returns garbage (NaN in interpret mode), and 0 * NaN = NaN would leak
    through the dots even where probabilities are exactly zero."""
    rows = lax.broadcasted_iota(jnp.int32, x.shape, 0) + start
    return jnp.where(rows < seq, x, 0.0)


def _add_column_sums(sum_ref, x, start, seq):
    """Add to a (1, 1, 1, lanes) block of sums the column sums of `x`, a
    block's (rows, lanes) result whose first row is row `start` of a
    sequence of `seq` rows (rows past its end hold garbage)."""
    if seq % x.shape[0]:
        x = _zero_pad_rows(x, start, seq)
    sum_ref[0, 0] += jnp.sum(x, axis=0, keepdims=True)


def _own_lanes(x, h, tile):
    """`x` with every lane zeroed but head `h`'s of the block's group."""
    if tile.heads == 1:
        return x
    d = tile.lanes // tile.heads
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * d) & (lane < (h + 1) * d), x, 0.0)


def _by_head(values, rows, tile):
    """(rows, lanes): on head h's lanes, `values[h]` (a (rows, lanes) array
    or a (rows, 1) column)."""
    if tile.heads == 1:
        return values[0]
    d = tile.lanes // tile.heads
    lane = lax.broadcasted_iota(jnp.int32, (rows, tile.lanes), 1)
    out = values[-1]
    for h in range(tile.heads - 2, -1, -1):
        out = jnp.where(lane < (h + 1) * d, values[h], out)
    return out


def _dot(a, b, transpose_b=False):
    """a @ b, or a @ b.T: float32 out of the MXU. float32 operands follow
    jax's matmul precision; narrower ones have one pass to give (and Mosaic
    refuses them a float32 contraction)."""
    return lax.dot_general(
        a, b, (((1,), (1 if transpose_b else 0,)), ((), ())),
        precision=None if a.dtype == jnp.float32 else lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _scaled(x, scale):
    """`x * scale` in x's type, rounded once."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _each_row(n, body):
    """`body(r)` for the n rows of a block: traced once and unrolled where
    it is lowered, so that one row's products run under another's softmax
    (a rolled loop read 11% slower on the v5e at 8 rows of 128 x 128; n
    copies made in Python cost the step 3 s of tracing). A step's scores
    are bounded by `_STEP_SCORES` whatever n is."""
    if n == 1:
        body(0)
    else:
        lax.fori_loop(0, n, lambda r, carry: body(r), None, unroll=True)


def _runs(causal, q_start, k_start, block_q, seq_q, seq_k):
    """False for a block strictly above the (offset) causal diagonal."""
    return True if not causal else (
        k_start <= q_start + (seq_k - seq_q) + block_q - 1)


def _band(outer, window, tile, seq_q, seq_k, over_keys):
    """(first, last) of the inner sweep's blocks that the band of `window`
    keys behind the causal diagonal leaves outer block `outer`: the key
    blocks its queries see where `over_keys` (forward, dq), else the query
    blocks that see its keys (dk/dv). `outer` is a Python int or a grid
    index; every numerator is kept at 0 or above, so the division is the
    scalar core's own."""
    off = seq_k - seq_q
    if over_keys:
        size, inner, rows = tile.block_q, tile.block_k, seq_k
        lo, hi = outer * size + off - window + 1, outer * size + size - 1 + off
    else:
        size, inner, rows = tile.block_k, tile.block_q, seq_q
        lo = outer * size - off
        hi = outer * size + size - 1 - off + window - 1
    if isinstance(outer, int):
        return max(lo, 0) // inner, max(min(hi, rows - 1), 0) // inner
    return (lax.div(jnp.maximum(lo, 0), jnp.int32(inner)),
            lax.div(jnp.clip(hi, 0, rows - 1), jnp.int32(inner)))


def _band_blocks(window, tile, seq_q, seq_k, over_keys):
    """Blocks in the inner sweep of a windowed call: the most that the band
    leaves any outer block."""
    n_outer = -(-(seq_q if over_keys else seq_k)
                // (tile.block_q if over_keys else tile.block_k))
    spans = [_band(o, window, tile, seq_q, seq_k, over_keys)
             for o in range(n_outer)]
    return max(last - first + 1 for first, last in spans)


def _band_step(outer, t, window, tile, seq_q, seq_k, over_keys):
    """(first row of the inner block that step `t` of outer block `outer`'s
    sweep works on, whether the band holds it, whether the sweep is one
    block long). The sweep starts on the band's first block; the steps past
    its last block (an outer block at the sequence's start has fewer) do
    nothing, and their index maps (`_banded`) stay on the last block, so
    nothing is fetched for them."""
    first, last = _band(outer, window, tile, seq_q, seq_k, over_keys)
    block = first + t
    return (block * (tile.block_k if over_keys else tile.block_q),
            block <= last,
            _band_blocks(window, tile, seq_q, seq_k, over_keys) == 1)


def _banded(window, tile, seq_q, seq_k, over_keys):
    """What takes an index map of (row block, head group, q-block, k-block)
    to that of a windowed call, whose grid is (.., outer block, step of the
    band's sweep)."""
    def at(index_map):
        def banded(b, g, outer, t):
            first, last = _band(outer, window, tile, seq_q, seq_k, over_keys)
            inner = jnp.minimum(first + t, last)
            return (index_map(b, g, outer, inner) if over_keys
                    else index_map(b, g, inner, outer))
        return banded
    return at


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, tile,
                sm_scale, causal, seq_q, seq_k, window=None):
    """One (row block, head group, q-block, k-block) grid step. The grid's
    last dim is the sequential K sweep (under `window`, over the band's
    blocks alone); with more than one block in it the accumulators live in
    VMEM scratch across it."""
    bq, bk = tile.block_q, tile.block_k
    single = seq_k <= bk
    j = pl.program_id(3)
    q_start, k_start = pl.program_id(2) * bq, j * bk
    masked = causal or seq_k % bk != 0 or window is not None
    if window is not None:
        k_start, in_band, single = _band_step(pl.program_id(2), j, window,
                                              tile, seq_q, seq_k, True)
    if not single:
        acc, m_sc, l_sc = scratch

    def write(r, o, m, l):
        l = [jnp.where(x == 0.0, 1.0, x) for x in l]
        o_ref[r] = (o * _by_head([1.0 / x for x in l], bq, tile)
                    ).astype(o_ref.dtype)
        for h in range(tile.heads):
            # logsumexp per row, consumed by the backward's in-kernel
            # recompute: a column here, a lane-dense row out there (any row
            # of the transpose of the column spread over 128 lanes)
            lse = jnp.broadcast_to(m[h] + jnp.log(l[h]), (bq, _LANES))
            lse_ref[r, h] = lse.T[:1]

    def step(r):
        q, k, v = q_ref[r], k_ref[r], v_ref[r]
        if seq_k % bk:
            v = _zero_pad_rows(v, k_start, seq_k)
        pv, m, l, alpha = [], [], [], []
        for h in range(tile.heads):
            s = _dot(_scaled(_own_lanes(q, h, tile), sm_scale), k,
                     transpose_b=True)
            if masked:
                s = _bounds_mask(s, q_start, k_start, seq_q, seq_k, causal,
                                 window=window)
            m_new = jnp.max(s, axis=1, keepdims=True)
            if not single:
                m_prev = m_sc[r, h][:, :1]
                m_new = jnp.maximum(m_prev, m_new)
            p = jnp.exp(s - m_new)
            l_new = jnp.sum(p, axis=1, keepdims=True)
            if not single:
                alpha.append(jnp.exp(m_prev - m_new))
                l_new = alpha[h] * l_sc[r, h][:, :1] + l_new
                m_sc[r, h] = jnp.broadcast_to(m_new, (bq, _LANES))
                l_sc[r, h] = jnp.broadcast_to(l_new, (bq, _LANES))
            pv.append(_dot(p.astype(v.dtype), v))
            m.append(m_new)
            l.append(l_new)
        if single:
            write(r, _by_head(pv, bq, tile), m, l)
        else:
            acc[r] = (acc[r] * _by_head(alpha, bq, tile)
                      + _by_head(pv, bq, tile))

    if single:
        _each_row(tile.block_b, step)
        return

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # causal: skip blocks strictly above the (offset) diagonal
    @pl.when(_runs(causal, q_start, k_start, bq, seq_q, seq_k)
             if window is None else in_band)
    def _step():
        _each_row(tile.block_b, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _out():
        _each_row(tile.block_b, lambda r: write(
            r, acc[r], [m_sc[r, h][:, :1] for h in range(tile.heads)],
            [l_sc[r, h][:, :1] for h in range(tile.heads)]))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               *scratch, tile, sm_scale, causal, seq_q, seq_k, sums=False,
               window=None):
    """dq = sum_j dS_ij K_j — grid (row block, head group, q-block,
    k-block), K sweep sequential, dq accumulated in VMEM where the sweep has
    more than one block. With `sums`, one more result: the column sums of
    the block of dq, while it is in VMEM (the gradient of a bias that was
    added to q, less the sum over blocks)."""
    bq, bk = tile.block_q, tile.block_k
    single = seq_k <= bk
    j = pl.program_id(3)
    q_start, k_start = pl.program_id(2) * bq, j * bk
    masked = causal or seq_k % bk != 0 or window is not None
    if window is not None:
        k_start, in_band, single = _band_step(pl.program_id(2), j, window,
                                              tile, seq_q, seq_k, True)
    if sums:
        sum_ref, *scratch = scratch
    if not single:
        dq_acc, = scratch

    def step(r):
        q, do, k, v = q_ref[r], do_ref[r], k_ref[r], v_ref[r]
        if seq_k % bk:
            k = _zero_pad_rows(k, k_start, seq_k)
            v = _zero_pad_rows(v, k_start, seq_k)
        # the scale rides on k: into the scores, and into dq = dS (scale K)
        k = _scaled(k, sm_scale)
        dq = []
        for h in range(tile.heads):
            lse = lse_ref[r, h, 0][:, None]
            delta = dl_ref[r, h, 0][:, None]
            s = _dot(_own_lanes(q, h, tile), k, transpose_b=True)
            if masked:
                s = _bounds_mask(s, q_start, k_start, seq_q, seq_k, causal,
                                 window=window)
            p = jnp.exp(s - lse)
            dp = _dot(_own_lanes(do, h, tile), v, transpose_b=True)
            dq.append(_dot((p * (dp - delta)).astype(k.dtype), k))
        dq = _by_head(dq, bq, tile)
        if single:
            dq_ref[r] = dq.astype(dq_ref.dtype)
            if sums:
                _add_column_sums(sum_ref, dq, q_start, seq_q)
        else:
            dq_acc[r] += dq

    if single:
        if sums:
            sum_ref[...] = jnp.zeros_like(sum_ref)
        _each_row(tile.block_b, step)
        return

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(_runs(causal, q_start, k_start, bq, seq_q, seq_k)
             if window is None else in_band)
    def _step():
        _each_row(tile.block_b, step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _out():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        if sums:
            sum_ref[...] = jnp.zeros_like(sum_ref)
            _each_row(tile.block_b, lambda r: _add_column_sums(
                sum_ref, dq_acc[r], q_start, seq_q))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, *scratch, tile, sm_scale, causal, seq_q,
                seq_k, sums=False, window=None):
    """dk/dv for one K-block — grid (row block, head group, k-block,
    q-block), Q sweep sequential. Scores are computed transposed, (k, q):
    the row statistics then broadcast along sublanes as they arrive, and
    dv = P^T dO, dk = dS^T Q are plain products. Emits per-ATTENTION-head
    dk/dv; the GQA group-sum happens in XLA after the call (one reshape+sum,
    no S^2 traffic). With `sums`, two more results: the column sums of the
    blocks of dk and of dv, as `_dq_kernel` gives dq's."""
    bq, bk = tile.block_q, tile.block_k
    single = seq_q <= bq
    i = pl.program_id(3)
    q_start, k_start = i * bq, pl.program_id(2) * bk
    masked = causal or seq_k % bk != 0 or window is not None
    if window is not None:
        q_start, in_band, single = _band_step(pl.program_id(2), i, window,
                                              tile, seq_q, seq_k, False)
    ragged_q = seq_q % bq != 0
    if sums:
        dk_sum_ref, dv_sum_ref, *scratch = scratch
    if not single:
        dk_acc, dv_acc = scratch

    def add_sums(dk, dv):
        _add_column_sums(dk_sum_ref, dk, k_start, seq_k)
        _add_column_sums(dv_sum_ref, dv, k_start, seq_k)

    def step(r):
        q, do, k, v = q_ref[r], do_ref[r], k_ref[r], v_ref[r]
        if ragged_q:
            q = _zero_pad_rows(q, q_start, seq_q)
            do = _zero_pad_rows(do, q_start, seq_q)
            qcol = lax.broadcasted_iota(jnp.int32, (1, bq), 1) + q_start
        # the scale rides on q: into the scores, and into dk = dS^T (scale Q)
        q = _scaled(q, sm_scale)
        dk, dv = [], []
        for h in range(tile.heads):
            lse, delta = lse_ref[r, h], dl_ref[r, h]        # (1, block_q)
            if ragged_q:
                lse = jnp.where(qcol < seq_q, lse, 0.0)
                delta = jnp.where(qcol < seq_q, delta, 0.0)
            s = _dot(_own_lanes(k, h, tile), q, transpose_b=True)
            if masked:
                s = _bounds_mask(s, q_start, k_start, seq_q, seq_k, causal,
                                 q_axis=1, window=window)
            p = jnp.exp(s - lse)
            if ragged_q:
                # queries past seq_q carry no probability mass (lse
                # sanitized above would otherwise make exp(0-0)=1 columns)
                p = jnp.where(qcol < seq_q, p, 0.0)
            dv.append(_dot(p.astype(do.dtype), do))
            dp = _dot(_own_lanes(v, h, tile), do, transpose_b=True)
            dk.append(_dot((p * (dp - delta)).astype(q.dtype), q))
        dk, dv = _by_head(dk, bk, tile), _by_head(dv, bk, tile)
        if single:
            dk_ref[r] = dk.astype(dk_ref.dtype)
            dv_ref[r] = dv.astype(dv_ref.dtype)
            if sums:
                add_sums(dk, dv)
        else:
            dk_acc[r] += dk
            dv_acc[r] += dv

    def zero_sums():
        dk_sum_ref[...] = jnp.zeros_like(dk_sum_ref)
        dv_sum_ref[...] = jnp.zeros_like(dv_sum_ref)

    if single:
        if sums:
            zero_sums()
        _each_row(tile.block_b, step)
        return

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_runs(causal, q_start, k_start, bq, seq_q, seq_k)
             if window is None else in_band)
    def _step():
        _each_row(tile.block_b, step)

    @pl.when(i == pl.num_programs(3) - 1)
    def _out():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if sums:
            zero_sums()
            _each_row(tile.block_b,
                      lambda r: add_sums(dk_acc[r], dv_acc[r]))


# ------------------------------------------------------------- the three calls
def _out_struct(shape, dtype, *args):
    """ShapeDtypeStruct carrying the union of the inputs' varying-mesh-axes
    (vma): required when the kernels run inside shard_map (the ring path)
    under check_vma."""
    vma = frozenset().union(*[jax.typeof(a).vma for a in args])
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


class _Geometry(NamedTuple):
    """Where one call's blocks lie. The index maps take (row block, head
    group, q-block, k-block)."""
    tile: _Tile
    outer: tuple        # the grid's row blocks and head groups
    q: object           # index maps of a block of q, of k, of v,
    k: object
    v: object
    o: object           # of a block of o, do or a per-head dk or dv,
    row: object         # and of a block of row statistics
    o_shape: tuple
    stats: tuple        # the row statistics' shape


def _geometry(view, q, k, H, Hkv):
    """The geometry of one call on the view's 3-D operands (under "packed"
    q and k are both the one (B, S, 3*H*D) array)."""
    rows, Sq, width = q.shape
    Sk = k.shape[1]
    if view == "bhsd":
        B, D = rows // H, width
    else:
        B, D = rows, width // (3 * H if view == "packed" else H)
    tile = _choose_tile(view, B, H, Hkv, Sq, Sk, D, q.dtype.itemsize)
    group = H // Hkv
    if view == "bhsd":
        groups = 1
        stats = (rows, 1, 1, Sq)

        def q_map(n, g, i, j):
            return (n, i, 0)

        def k_map(n, g, i, j):      # group > 1 only with one row a block
            return (n if group == 1
                    else (n // H) * Hkv + (n % H) // group, j, 0)

        def row_map(n, g, i, j):
            return (n, 0, 0, i)
        o_map = q_map
        v_map = k_map
    else:
        groups = H // tile.heads
        stats = (B, H, 1, Sq)

        def o_map(b, g, i, j):
            return (b, i, g)

        def row_map(b, g, i, j):
            return (b, g, 0, i)
        if view == "packed":
            # `pack_qkv`'s columns, in blocks a group wide:
            # [k_0 v_0 k_1 v_1 ... | q_0 q_1 ...]
            def q_map(b, g, i, j):
                return (b, i, 2 * groups + g)

            def k_map(b, g, i, j):
                return (b, j, 2 * g)

            def v_map(b, g, i, j):
                return (b, j, 2 * g + 1)
        else:
            q_map = o_map

            def k_map(b, g, i, j):  # group > 1 only with one head a block
                return (b, j, g // group)
            v_map = k_map
    return _Geometry(tile, (rows // tile.block_b, groups), q_map, k_map,
                     v_map, o_map, row_map,
                     (rows, Sq, D if view == "bhsd" else H * D), stats)


def _scratch(blocks, *shapes):
    """float32 VMEM accumulators for a sweep of `blocks` blocks: none where
    there is one block, whose result is written as it is computed."""
    return [pltpu.VMEM(shape, jnp.float32)
            for shape in shapes] if blocks > 1 else []


def _params(tile):
    return compiler_params(("parallel", "parallel", "parallel", "arbitrary"),
                           vmem_limit_bytes=max(_VMEM_LIMIT, tile.vmem))


def _windowed(kernel, window, tile, Sq, Sk, over_keys):
    """(kernel keywords with the window, blocks of the inner sweep or None
    for the whole sequence, what takes an index map to the call's, prefix of
    the call's name)."""
    if window is None:
        return kernel, None, lambda index_map: index_map, "flash"
    return (dict(kernel, window=window),
            _band_blocks(window, tile, Sq, Sk, over_keys),
            _banded(window, tile, Sq, Sk, over_keys), "swa")


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6, 7, 8, 9))
def _forward(view, q, k, v, H, Hkv, causal, sm_scale, interpret,
             window=None):
    """o, and the logsumexp in the statistics' shape, of the view's 3-D
    operands. Jitted so that a model's layers share one trace and one
    lowering of the kernel. With `window` the K sweep is the band's blocks
    (`_band`), reached through the index maps, and the call is `swa_fwd`."""
    geo = _geometry(view, q, k, H, Hkv)
    tile = geo.tile
    Sq, Sk = q.shape[1], k.shape[1]
    bb, bq, bk, lanes = tile.block_b, tile.block_q, tile.block_k, tile.lanes
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Sk, bk)
    kernel, band, at, name = _windowed(
        dict(tile=tile, sm_scale=sm_scale, causal=causal, seq_q=Sq,
             seq_k=Sk), window, tile, Sq, Sk, True)
    nk = band or nk
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **kernel),
        grid=geo.outer + (nq, nk),
        in_specs=[pl.BlockSpec((bb, bq, lanes), geo.q),
                  pl.BlockSpec((bb, bk, lanes), at(geo.k)),
                  pl.BlockSpec((bb, bk, lanes), at(geo.v))],
        out_specs=[pl.BlockSpec((bb, bq, lanes), geo.o),
                   pl.BlockSpec((bb, tile.heads, 1, bq), geo.row)],
        out_shape=[_out_struct(geo.o_shape, q.dtype, q, k, v),
                   _out_struct(geo.stats, jnp.float32, q, k, v)],
        scratch_shapes=_scratch(nk, (bb, bq, lanes),
                                (bb, tile.heads, bq, _LANES),
                                (bb, tile.heads, bq, _LANES)),
        interpret=interpret,
        compiler_params=_params(tile),
        name=name + "_fwd",     # the HLO instruction, and so the device
        #                         trace: flash_fwd, or swa_fwd under a window
    )(q, k, v)


def _dkv_packed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
                       dkv_ref, sum_ref, *scratch, tile, **kernel):
    """`_dkv_kernel` on one block [dk_g | dv_g] of the packed cotangent and
    one of its column sums. The array arrives holding `flash_dq`'s blocks
    (`dq_ref`, left in HBM and untouched: it is the result's own memory)."""
    n = tile.lanes
    _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dkv_ref.at[:, :, :n], dkv_ref.at[:, :, n:],
                sum_ref.at[:, :, :, :n], sum_ref.at[:, :, :, n:],
                *scratch, tile=tile, sums=True, **kernel)


@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9, 10, 11, 12))
def _backward(view, q, k, v, lse, delta, do, H, Hkv, causal, sm_scale,
              interpret, window=None):
    """dq like q, and dk, dv PER ATTENTION HEAD (like q along the heads,
    like k along the sequence), from the row statistics (lse, delta) in the
    statistics' shape; under "packed" the one cotangent of the packed
    array, which both kernels wrote into, and its column sums in float32.
    Jitted as `_forward` is. With `window`, `swa_dq` sweeps the band's key
    blocks and `swa_dkv` the query blocks that see a key block."""
    geo = _geometry(view, q, k, H, Hkv)
    tile = geo.tile
    Sq, Sk = q.shape[1], k.shape[1]
    bb, bq, bk, lanes = tile.block_b, tile.block_q, tile.block_k, tile.lanes
    nq, nk = pl.cdiv(Sq, bq), pl.cdiv(Sk, bk)
    kernel = dict(tile=tile, sm_scale=sm_scale, causal=causal, seq_q=Sq,
                  seq_k=Sk)
    common = dict(interpret=interpret, compiler_params=_params(tile))
    args = (q, k, v, do, lse, delta)

    def in_specs(at=lambda index_map: index_map):
        return [pl.BlockSpec((bb, bq, lanes), at(geo.q)),
                pl.BlockSpec((bb, bk, lanes), at(geo.k)),
                pl.BlockSpec((bb, bk, lanes), at(geo.v)),
                pl.BlockSpec((bb, bq, lanes), at(geo.o)),
                pl.BlockSpec((bb, tile.heads, 1, bq), at(geo.row)),
                pl.BlockSpec((bb, tile.heads, 1, bq), at(geo.row))]

    # dk/dv: grid transposed so the K-block is the parallel dim
    def swapped(index_map):
        return lambda b, g, j, i: index_map(b, g, i, j)

    dq_kernel, band_k, over_keys, name = _windowed(kernel, window, tile, Sq,
                                                   Sk, True)
    dkv_kernel, band_q, over_queries, _ = _windowed(kernel, window, tile, Sq,
                                                    Sk, False)
    dq_call = dict(grid=geo.outer + (nq, band_k or nk),
                   in_specs=in_specs(over_keys),
                   scratch_shapes=_scratch(band_k or nk, (bb, bq, lanes)),
                   name=name + "_dq", **common)
    dkv_call = dict(grid=geo.outer + (nk, band_q or nq),
                    in_specs=in_specs(swapped if window is None
                                      else over_queries),
                    scratch_shapes=_scratch(band_q or nq, (bb, bk, lanes),
                                            (bb, bk, lanes)),
                    name=name + "_dkv", **common)
    if view == "packed":
        # one cotangent for the packed array. `flash_dq` writes q's blocks
        # of it and leaves the rest unwritten; `flash_dkv` fills that array
        # in place: k's and v's blocks of a group are neighbours, one block
        # of twice the lanes. Each gives the column sums of its blocks too
        # (the projection's bias gradient, while the blocks are in VMEM),
        # (row blocks, sequence blocks, 1, columns), summed here
        width = geo.o_shape[2]
        dqkv, dq_sums = pl.pallas_call(
            functools.partial(_dq_kernel, sums=True, **dq_kernel),
            out_specs=[pl.BlockSpec((bb, bq, lanes), geo.q),
                       pl.BlockSpec((1, 1, 1, lanes),
                                    lambda b, g, i, j: (b, i, 0, g))],
            out_shape=[_out_struct(q.shape, q.dtype, *args),
                       _out_struct((geo.outer[0], nq, 1, width),
                                   jnp.float32, *args)],
            **dq_call)(*args)
        dkv_call["in_specs"].append(pl.BlockSpec(memory_space=pl.ANY))
        dqkv, dkv_sums = pl.pallas_call(
            functools.partial(_dkv_packed_kernel, **dkv_kernel),
            out_specs=[pl.BlockSpec((bb, bk, 2 * lanes),
                                    lambda b, g, j, i: (b, j, g)),
                       pl.BlockSpec((1, 1, 1, 2 * lanes),
                                    lambda b, g, j, i: (b, j, 0, g))],
            out_shape=[_out_struct(q.shape, q.dtype, *args),
                       _out_struct((geo.outer[0], nk, 1, 2 * width),
                                   jnp.float32, *args)],
            input_output_aliases={len(args): 0},
            **dkv_call)(*args, dqkv)
        return dqkv, jnp.concatenate([dkv_sums.sum(axis=(0, 1, 2)),
                                      dq_sums.sum(axis=(0, 1, 2))])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **dq_kernel),
        out_specs=pl.BlockSpec((bb, bq, lanes), geo.q),
        out_shape=_out_struct(q.shape, q.dtype, *args), **dq_call)(*args)
    # per attention head: a block of dk lies where o's would, along k
    dkv_spec = pl.BlockSpec((bb, bk, lanes), geo.o)
    dkv_shape = (q.shape[0], Sk, q.shape[2])
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **dkv_kernel),
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[_out_struct(dkv_shape, k.dtype, *args),
                   _out_struct(dkv_shape, v.dtype, *args)],
        **dkv_call)(*args)
    return dq, dk, dv


# ------------------------------------------------- the (B, H, S, D) arguments
def _pallas_forward(q, k, v, causal, sm_scale, window=None):
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    o, lse = _forward("bhsd", q.reshape(B * H, Sq, D),
                      k.reshape(B * Hkv, Sk, D), v.reshape(B * Hkv, Sk, D),
                      H, Hkv, causal, sm_scale, pallas_stats.interpret(),
                      window)
    return o.reshape(q.shape), lse.reshape(B, H, Sq)


def _pallas_backward(q, k, v, o, lse, do, causal, sm_scale, window=None):
    # delta_i = rowsum(dO_i * O_i): one fused elementwise+reduce in XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _pallas_backward_inner(q, k, v, lse, delta, do, causal, sm_scale,
                                  window)


def _pallas_backward_inner(q, k, v, lse, delta, do, causal, sm_scale,
                           window=None):
    """dq/dk/dv kernels from precomputed (lse, delta), each (B, H, Sq).
    Split out so ring attention can run per-block backwards against the
    GLOBAL logsumexp."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    dq, dk, dv = _backward(
        "bhsd", q.reshape(B * H, Sq, D), k.reshape(B * Hkv, Sk, D),
        v.reshape(B * Hkv, Sk, D), lse.reshape(B * H, 1, 1, Sq),
        delta.reshape(B * H, 1, 1, Sq), do.reshape(B * H, Sq, D), H, Hkv,
        causal, sm_scale, pallas_stats.interpret(), window)
    group = H // Hkv
    dk = dk.reshape(B, Hkv, group, Sk, D)
    dv = dv.reshape(B, Hkv, group, Sk, D)
    if group > 1:
        dk, dv = dk.sum(axis=2), dv.sum(axis=2)
    return (dq.reshape(q.shape), dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


def _use_pallas(q, k):
    # any head dim a sublane tile divides; seq lengths are masked in-kernel
    # so any Sq/Sk works. GQA requires an integer group (a non-divisible
    # head count would make the kv BlockSpec silently clamp to a wrong
    # head).
    B, H, Sq, D = q.shape
    return pallas_stats.pallas_on() and isinstance(_choose_tile(
        "bhsd", B, H, k.shape[1], Sq, k.shape[2], D, q.dtype.itemsize), _Tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, sm_scale, window=None):
    if _use_pallas(q, k):
        o, _ = _pallas_forward(q, k, v, causal, sm_scale, window)
        return o
    return _ref_attention(q, k, v, causal, sm_scale, window)


def _flash_fwd(q, k, v, causal, sm_scale, window):
    if _use_pallas(q, k):
        o, lse = _pallas_forward(q, k, v, causal, sm_scale, window)
        return o, (q, k, v, o, lse)
    return (_ref_attention(q, k, v, causal, sm_scale, window),
            (q, k, v, None, None))


def _flash_bwd(causal, sm_scale, window, res, g):
    q, k, v, o, lse = res
    if lse is not None:
        return _pallas_backward(q, k, v, o, lse, g, causal, sm_scale, window)
    # non-Pallas path: rematerialized backward under XLA (differentiates
    # the recompute; reference keeps the full S^2 prob matrix in HBM
    # instead — src/operator/contrib/transformer.cc backward)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_attention(q_, k_, v_, causal, sm_scale,
                                          window),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _window(window, causal):
    """(causal, window) as the kernels take them: a window looks back from
    the causal diagonal, so it implies `causal`."""
    if window is None:
        return bool(causal), None
    if int(window) < 1:
        raise ValueError("window must be at least 1, got %r" % (window,))
    return True, int(window)


def flash_attention(q, k, v, causal=False, sm_scale=None, window=None):
    """Fused scaled-dot-product attention.

    q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D), H divisible by Hkv. With
    `window`, position i sees the keys i - window < j <= i and no others.
    Returns (B, H, Sq, D) in q's dtype.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    causal, window = _window(window, causal)
    return _flash(q, k, v, causal, float(sm_scale), window)


# ------------------------------------------------- the (B, S, H, D) arguments
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bshd(q, k, v, H, Hkv, causal, sm_scale, window=None):
    """On the (B, S, H*D) view, always through the kernels."""
    return _forward("bshd", q, k, v, H, Hkv, causal, sm_scale,
                    pallas_stats.interpret(), window)[0]


def _flash_bshd_fwd(q, k, v, H, Hkv, causal, sm_scale, window):
    o, lse = _forward("bshd", q, k, v, H, Hkv, causal, sm_scale,
                      pallas_stats.interpret(), window)
    return o, (q, k, v, o, lse)


def _head_delta(do, o, H):
    """delta_i = rowsum(dO_i * O_i) per head, laid out like lse, (B, H, 1,
    Sq). The sum over a head's lanes is a product with a 0/1 matrix, so
    that XLA fuses the multiply into its operand and reads dO and O once:
    written as a reshape and a sum it first copied the (B, S, H*D) product
    into a layout with the sequence on the lanes (0.16 ms a layer of
    BERT-base on the v5e, 0.23 with the reduce)."""
    width = o.shape[-1]
    heads = (jnp.arange(width)[:, None] // (width // H)
             == jnp.arange(H)[None, :]).astype(jnp.float32)
    delta = jnp.einsum("bsw,wh->bhs", do.astype(jnp.float32)
                       * o.astype(jnp.float32), heads,
                       precision=lax.Precision.HIGHEST)
    return delta[:, :, None, :]


def _flash_bshd_bwd(H, Hkv, causal, sm_scale, window, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _backward("bshd", q, k, v, lse, _head_delta(do, o, H), do,
                           H, Hkv, causal, sm_scale, pallas_stats.interpret(),
                           window)
    if H != Hkv:
        B, Sk = k.shape[:2]
        dk = dk.reshape(B, Sk, Hkv, H // Hkv, -1).sum(axis=3)
        dv = dv.reshape(B, Sk, Hkv, H // Hkv, -1).sum(axis=3)
    return (dq, dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


_flash_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


def flash_attention_bshd(q, k, v, causal=False, sm_scale=None, window=None):
    """Fused scaled-dot-product attention on the layout a projection
    leaves: q (B, Sq, H, D); k, v (B, Sk, Hkv, D), H divisible by Hkv.
    Returns (B, Sq, H, D) in q's dtype, with no transpose on the way in or
    out where the heads fill 128-lane groups; a shape that does not is
    transposed to `flash_attention`'s layout and counted.

    With `window`, position i sees the keys i - window < j <= i: the same
    kernel bodies under the names `swa_fwd`, `swa_dq` and `swa_dkv`, whose
    inner sweep visits the blocks that the band touches and no others
    (counted as `flash_window`)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if sm_scale is None:
        sm_scale = D ** -0.5
    (causal, window), sm_scale = _window(window, causal), float(sm_scale)
    dispatch, fallback = (("flash_bshd", "flash") if window is None
                          else ("flash_window", "flash_window"))
    if pallas_stats.pallas_on():
        tile = _choose_tile("bshd", B, H, Hkv, Sq, Sk, D, q.dtype.itemsize)
        if isinstance(tile, _Tile):
            note_dispatch(dispatch)
            o = _flash_bshd(q.reshape(B, Sq, H * D),
                            k.reshape(B, Sk, Hkv * D),
                            v.reshape(B, Sk, Hkv * D), H, Hkv, causal,
                            sm_scale, window)
            return o.reshape(q.shape)
        note_fallback(fallback, tile)
    o = _flash(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
               v.transpose(0, 2, 1, 3), causal, sm_scale, window)
    return o.transpose(0, 2, 1, 3)


# ------------------------------------------- the packed (B, S, 3*H*D) argument
def _packed_lanes(H, D):
    """Width of a head group in the packed columns: the kernels' block
    where the heads fill one, else all of a projection."""
    group = _lane_group(H, H, D)
    return H * D if isinstance(group, str) else group[0]


def pack_qkv(q, k, v, n_heads):
    """q, k, v of one width H*D along their last axis (three projections'
    weights, biases or results) as the one array `flash_attention_packed`
    reads: columns [k_0 v_0 k_1 v_1 ... | q_0 q_1 ...] in head groups of
    128 lanes. k's and v's blocks of a group are neighbours so that one
    kernel's result block holds both; the gradient of a packed array comes
    back to q, k and v through this function by autodiff."""
    width = q.shape[-1]
    lanes = _packed_lanes(n_heads, width // n_heads)
    kv = jnp.stack([x.reshape(x.shape[:-1] + (width // lanes, lanes))
                    for x in (k, v)], axis=-2)
    return jnp.concatenate([kv.reshape(q.shape[:-1] + (2 * width,)), q],
                           axis=-1)


def _unpack_qkv(qkv, n_heads):
    """q, k, v out of `pack_qkv`'s columns."""
    width = qkv.shape[-1] // 3
    lanes = _packed_lanes(n_heads, width // n_heads)
    kv = qkv[..., :2 * width].reshape(qkv.shape[:-1]
                                      + (width // lanes, 2, lanes))
    return (qkv[..., 2 * width:],
            kv[..., 0, :].reshape(qkv.shape[:-1] + (width,)),
            kv[..., 1, :].reshape(qkv.shape[:-1] + (width,)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _flash_packed(qkv, bias, H, causal, sm_scale):
    """On the packed view of `qkv + bias`, always through the kernels."""
    return _flash_packed_fwd(qkv, bias, H, causal, sm_scale)[0]


def _flash_packed_fwd(qkv, bias, H, causal, sm_scale):
    qkv = qkv + bias        # XLA fuses it into the product that made qkv
    o, lse = _forward("packed", qkv, qkv, qkv, H, H, causal, sm_scale,
                      pallas_stats.interpret())
    return o, (qkv, o, lse, bias)


def _flash_packed_bwd(H, causal, sm_scale, res, do):
    qkv, o, lse, bias = res
    dqkv, sums = _backward("packed", qkv, qkv, qkv, lse,
                           _head_delta(do, o, H), do, H, H, causal, sm_scale,
                           pallas_stats.interpret())
    return dqkv, sums.astype(bias.dtype)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def flash_attention_packed(qkv, n_heads, causal=False, sm_scale=None,
                           bias=None):
    """Self-attention on the packed result of ONE q|k|v projection:
    `qkv` (B, S, 3*H*D) in `pack_qkv`'s column order, every head with its
    own k and v; `bias` (3*H*D,), packed likewise, is the projection's and
    is added here. Returns (B, S, H*D) in qkv's dtype.

    The three kernels read q's, k's and v's head-group blocks where the
    projection left them, and the backward returns one (B, S, 3*H*D)
    cotangent that `flash_dq` and `flash_dkv` both wrote into, so the
    projection's dx and dW each read one plain array: no slice on the way
    in, no concatenation on the way out. The bias rides in so that its
    gradient can ride out: the kernels sum the cotangent's columns while
    its blocks are in VMEM, which spares one more pass over it. A shape
    whose heads fill no 128-lane group is split and sent to
    `flash_attention_bshd`, counted
    (`ops.pallas.fallback.flash_packed.<reason>`).

    Under a mesh that shards the projection's columns (a `model` axis over
    1) GSPMD gathers `qkv` around the Mosaic calls, as it gathers q, k and v
    around `flash_attention_bshd`'s."""
    B, S, width = qkv.shape
    D = width // (3 * n_heads)
    if sm_scale is None:
        sm_scale = D ** -0.5
    causal, sm_scale = bool(causal), float(sm_scale)
    if bias is None:
        bias = jnp.zeros((width,), qkv.dtype)
    if pallas_stats.pallas_on():
        tile = _choose_tile("packed", B, n_heads, n_heads, S, S, D,
                            qkv.dtype.itemsize)
        if isinstance(tile, _Tile):
            note_dispatch("flash_packed")
            return _flash_packed(qkv, bias, n_heads, causal, sm_scale)
        note_fallback("flash_packed", tile)
    q, k, v = (x.reshape(B, S, n_heads, D)
               for x in _unpack_qkv(qkv + bias, n_heads))
    return flash_attention_bshd(q, k, v, causal, sm_scale).reshape(B, S, -1)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, sm_scale=None):
    """Single-token attention over a paged KV pool (the serving decode path).

    The KV cache lives as fixed-size blocks in one physical pool per layer
    (`mxnet_tpu.serve.KVBlockPool`); each stream owns a block table mapping
    its logical positions onto pool blocks — long contexts cost exactly the
    blocks they fill, not a max_seq_len rectangle per batch slot.

    q:            (B, H, 1, D) — one new query token per stream.
    k_pool/v_pool:(N, Hkv, bs, D) — the shared physical pool (N blocks of
                  bs tokens). H divisible by Hkv (GQA).
    block_tables: (B, nb) int32 — per-stream block ids, logical block j of
                  stream b at entry [b, j]. Entries >= N mark unallocated
                  tail blocks; the gather clamps them and the length mask
                  discards whatever they read.
    lengths:      (B,) int32 — valid context length per stream (the new
                  token's KV must already be written to the pool). Must be
                  >= 1 (inactive batch slots pass 1 and ignore the output)
                  so the softmax never normalizes over an empty row.

    Returns (B, H, 1, D) in q's dtype. Same grouped-einsum structure and
    fp32 softmax as `_ref_attention`, so paged decode matches the unpaged
    reference bit-for-bit on the positions the mask keeps.
    """
    B, H, _, D = q.shape
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    g = H // Hkv
    if sm_scale is None:
        sm_scale = D ** -0.5
    # gather each stream's pages: (B, nb, Hkv, bs, D) -> (B, Hkv, nb*bs, D)
    k = k_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    v = v_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    qg = q.reshape(B, Hkv, g, 1, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = lax.broadcasted_iota(jnp.int32, (B, 1, 1, 1, nb * bs), 4) \
        < lengths[:, None, None, None, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, 1, D).astype(q.dtype)


def paged_attention_chunk(q, k_pool, v_pool, block_tables, q_lengths,
                          sm_scale=None):
    """Multi-query attention over a paged KV pool with PER-QUERY lengths —
    the chunked-prefill / speculative-verify generalization of
    `paged_attention` (which is the C=1 special case).

    A chunk of C tokens from one stream occupies consecutive positions
    whose KV has just been scattered into the pool; query c may only see
    positions < q_lengths[b, c] (its own position + 1 — causality ACROSS
    the pool, not just within the chunk, so a chunk attends to every
    earlier chunk and to a shared prefix for free).

    q:            (B, H, C, D) — C new query tokens per stream.
    k_pool/v_pool:(N, Hkv, bs, D) — the shared physical pool.
    block_tables: (B, nb) int32 — per-stream block ids (entries >= N are
                  unallocated; the length mask discards their rows).
    q_lengths:    (B, C) int32 — valid context length per query (the
                  query's own KV already written). Rows for padded /
                  inactive queries pass 1 and ignore the output.

    Returns (B, H, C, D) in q's dtype — the same grouped-einsum fp32
    softmax as `paged_attention`, so a C=1 call and a decode call agree
    on the positions the masks keep."""
    B, H, C, D = q.shape
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    g = H // Hkv
    if sm_scale is None:
        sm_scale = D ** -0.5
    k = k_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    v = v_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    qg = q.reshape(B, Hkv, g, C, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = lax.broadcasted_iota(jnp.int32, (B, 1, 1, C, nb * bs), 4) \
        < q_lengths[:, None, None, :, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, C, D).astype(q.dtype)

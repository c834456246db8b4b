"""Pallas sparse-gradient kernels (ISSUE 17 tentpole part 2).

The "Tensor Processing Primitives" single-pass discipline applied to the
scatter-add at the heart of every sparse embedding update: given
``(nnz, D)`` gradient rows and their ``(nnz,)`` row ids, produce the
``(num_segments, D)`` dense accumulation

    out = jnp.zeros((num_segments, D)).at[ids].add(values)

in ONE pass over VMEM tiles. The destination table slab stays resident in
VMEM across the whole grid; each grid step streams one ``(tile, D)`` block
of gradient rows in and folds them into the slab row-by-row (ids ride
SMEM, so the row offset is a scalar load — no gather materialization).
Accumulation order is occurrence order — the same order XLA's
deterministic scatter-add applies duplicate updates — so the kernel is
bit-identical to the composed ``.at[ids].add()`` path (tests assert
equality in interpreter mode).

Dispatch follows the `fused_optimizer` convention exactly:

* gated by ``use_pallas_sparse()`` (interpreter runs always take the
  kernel; compiled runs need the TPU backend + ``MXNET_TPU_USE_PALLAS``);
* ineligible calls (non-float values, int64 ids, empty operands, a
  destination slab that will not fit VMEM) are counted under
  ``ops.pallas.fallback.<reason>`` and routed to the always-correct XLA
  composite — never an error;
* eligible dispatches count ``ops.pallas.dispatch(.segment_sum)`` and
  ride a ``pallas.segment_sum`` telemetry span; ``parse_log --kernels``
  and the new ``parse_log --sparse`` table render the counts.

The op also registers as ``_sparse_segment_sum`` with the Pallas wrapper
as its ``tpu_impl``, so the `registry.best_fn` dispatch surface (the
FCompute<tpu> hook) sees it like every other specialized op.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_stats as _pstats
from . import registry as _reg
from .pallas_stats import compiler_params as _compiler_params

__all__ = ["segment_sum", "use_pallas_sparse", "scatter_add_rows"]

_LANES = 128
_SUBLANES = 8
_MAX_TILE_NNZ = 512        # 512 x D gradient rows streamed per grid step
_VMEM_BUDGET = 8 << 20     # slab + one tile must fit well under 16 MB


def use_pallas_sparse():
    """Is the Pallas sparse path requested? Same gate shape as
    `fused_optimizer.use_pallas_flat`: interpreter runs always take it,
    compiled runs need the TPU backend plus the MXNET_TPU_USE_PALLAS
    opt-in."""
    if _pstats.interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    return _pstats.use_pallas(False)


# ---------------------------------------------------------------------------
# XLA composite — the always-correct reference path
# ---------------------------------------------------------------------------
def _segment_sum_xla(values, ids, num_segments):
    values = jnp.asarray(values)
    ids = jnp.asarray(ids)
    out = jnp.zeros((num_segments,) + values.shape[1:], values.dtype)
    return out.at[ids].add(values)


# ---------------------------------------------------------------------------
# Pallas kernel — destination slab resident in VMEM, gradient rows streamed
# ---------------------------------------------------------------------------
def _kernel_segment_sum(ids_ref, vals_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(j, carry):
        row = ids_ref[0, 0, j]

        @pl.when(row >= 0)
        def _add():
            out_ref[pl.ds(row, 1), :] += vals_ref[pl.ds(j, 1), :]
        return carry

    jax.lax.fori_loop(0, ids_ref.shape[2], body, 0)


def _round_up(n, mult):
    return -(-n // mult) * mult


def _segment_sum_pallas_impl(nnz, dim, num_segments, dtype):
    """Build the jittable Pallas launch for one (nnz, dim, num_segments)
    geometry. Shapes are static per trace — the serve/train callers pad to
    fixed bucket sizes, so the trace cache stays small."""
    dim_p = _round_up(max(dim, 1), _LANES)
    seg_p = _round_up(max(num_segments, 1), _SUBLANES)
    tile = min(_MAX_TILE_NNZ, _round_up(max(nnz, 1), _SUBLANES))
    nnz_p = _round_up(max(nnz, 1), tile)
    grid = nnz_p // tile

    def impl(values, ids):
        vals2d = values.reshape(nnz, -1)
        pad_r = nnz_p - nnz
        pad_c = dim_p - vals2d.shape[1]
        if pad_r or pad_c:
            vals2d = jnp.pad(vals2d, ((0, pad_r), (0, pad_c)))
        # pad ids with -1: the kernel skips negative rows, so padding rows
        # never touch the slab. (grid, 1, tile): a block's last two
        # dimensions must be the array's own or multiples of (8, 128)
        ids_p = jnp.pad(ids.astype(jnp.int32), (0, pad_r),
                        constant_values=-1).reshape(grid, 1, tile)
        out = pl.pallas_call(
            _kernel_segment_sum,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((tile, dim_p), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((seg_p, dim_p), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((seg_p, dim_p), dtype),
            compiler_params=_compiler_params(("arbitrary",)),
            interpret=_pstats.interpret(),
        )(ids_p, vals2d)
        return out[:num_segments, :dim]
    return impl


_CACHE: dict = {}


def _jitted(key, builder):
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = jax.jit(builder())
    return fn


def _gate(values, ids, num_segments):
    """Shape/dtype gate. Returns a fallback reason or None."""
    if num_segments <= 0:
        return "empty"
    if int(_np.prod(values.shape, dtype=_np.int64)) == 0:
        return "empty"
    if not jnp.issubdtype(values.dtype, jnp.floating):
        return "dtype"
    if values.ndim < 2:
        return "rank"
    if not jnp.issubdtype(ids.dtype, jnp.integer):
        return "dtype"
    dim = int(_np.prod(values.shape[1:], dtype=_np.int64))
    slab = _round_up(num_segments, _SUBLANES) * _round_up(dim, _LANES)
    tile = min(_MAX_TILE_NNZ, values.shape[0]) * _round_up(dim, _LANES)
    if (slab + tile) * values.dtype.itemsize > _VMEM_BUDGET:
        return "vmem"
    return None


def segment_sum(values, ids, num_segments):
    """Dense scatter-add of sparse rows: ``zeros((num_segments, ...))
    .at[ids].add(values)``, Pallas-fused when eligible. `values` is
    ``(nnz, *row_shape)``, `ids` is ``(nnz,)`` int; rows with negative ids
    are dropped on the kernel path and must not be passed on the XLA path
    (callers pad with a trailing all-zero row instead, or clamp)."""
    values = jnp.asarray(values)
    ids = jnp.asarray(ids)
    if not use_pallas_sparse():
        return _segment_sum_xla(values, ids, num_segments)
    reason = _gate(values, ids, num_segments)
    if reason:
        _pstats.note_fallback("segment_sum", reason)
        return _segment_sum_xla(values, ids, num_segments)
    _pstats.note_dispatch("segment_sum")
    with _pstats.kernel_span("segment_sum"):
        nnz = values.shape[0]
        dim = int(_np.prod(values.shape[1:], dtype=_np.int64))
        fn = _jitted(("segsum", nnz, dim, num_segments, str(values.dtype)),
                     lambda: _segment_sum_pallas_impl(
                         nnz, dim, num_segments, values.dtype))
        out = fn(values, ids)
        return out.reshape((num_segments,) + values.shape[1:])


def scatter_add_rows(table, ids, values):
    """``table.at[ids].add(values)`` through the same dispatch: the
    segment-sum produces the dense delta for the table's leading axis and
    one vector add applies it. Used by the embedding update path so the
    scatter rides the kernel without a separate gather."""
    table = jnp.asarray(table)
    delta = segment_sum(jnp.asarray(values), ids, table.shape[0])
    return table + delta.astype(table.dtype)


# ---------------------------------------------------------------------------
# registry surface — the FCompute<tpu> hook
# ---------------------------------------------------------------------------
@_reg.register("_sparse_segment_sum", arity=2, differentiable=False,
               doc="dense scatter-add of (ids, values) rows into a "
                   "num_segments-row table")
def _sparse_segment_sum(values, ids, num_segments=0):
    return _segment_sum_xla(values, ids, int(num_segments))


@_reg.get("_sparse_segment_sum").tpu_impl
def _sparse_segment_sum_tpu(values, ids, num_segments=0):
    return segment_sum(values, ids, int(num_segments))

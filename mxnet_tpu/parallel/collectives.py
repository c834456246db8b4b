"""Collective-communication wrappers + bandwidth benchmark.

The reference's comm layer is three backends behind KVStore (SURVEY.md §5.8):
CommDevice P2P reduce (src/kvstore/comm.h), NCCL ring allreduce
(src/kvstore/kvstore_nccl.h), ps-lite ZMQ push/pull. On TPU there is one
backend: XLA collectives over ICI/DCN. These wrappers are usable both inside
shard_map'd code (they lower to `lax.psum` etc.) and eagerly on sharded
arrays (they jit a tiny shard_map around the collective).
"""
from __future__ import annotations

import time

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "ppermute",
           "psum_bucketed", "all_reduce_multi", "reduce_scatter_multi",
           "all_gather_multi", "all_gather_rows", "psum_unique_rows",
           "merge_unique_rows", "barrier", "allreduce_bench"]


def all_reduce(x, axis_name):
    """Sum over a mesh axis (inside shard_map/jit). reference semantics:
    KVStore push+pull of a dense key == allreduce."""
    return lax.psum(x, axis_name)


def psum_bucketed(xs, axis_name, bucket_mb=None):
    """Sum a LIST of arrays over a mesh axis as few fused flat psums
    (inside shard_map/jit): arrays are packed into size-capped single-dtype
    buckets (`mx.engine`, `MXNET_TPU_COMM_BUCKET_MB`) and each bucket is
    one `lax.psum` over its concatenation — the in-trace analog of the
    kvstore's bucketed push. Returns the reduced arrays in input order;
    with bucketing disabled this is one psum per array."""
    from .. import engine as _engine
    cap = _engine.bucket_bytes(bucket_mb)
    if not cap or len(xs) < 2:
        return [lax.psum(x, axis_name) for x in xs]
    out = list(xs)
    for bucket in _engine.bucketize(enumerate(xs), cap):
        flat = jnp.concatenate([r.reshape(-1) for r in bucket.raws]) \
            if len(bucket) > 1 else bucket.raws[0].reshape(-1)
        red = lax.psum(flat, axis_name)
        _, splits = _engine._split_points(bucket.shapes)
        parts = jnp.split(red, splits) if splits else [red]
        for idx, part, shape in zip(bucket.keys, parts, bucket.shapes):
            out[idx] = part.reshape(shape)
    return out


def all_gather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ppermute(x, axis_name, perm):
    """Neighbor exchange — the ring primitive under ring attention and
    pipeline micro-batch handoff."""
    return lax.ppermute(x, axis_name, perm)


def barrier(mesh=None):
    """Device-sync barrier: a trivial psum everyone must join. Analog of the
    reference's engine WaitForAll + ps-lite Barrier (ps::Postoffice).

    Eager dispatch = a resilience site: a peer that died mid-rendezvous
    surfaces as a retriable fault (or, under a watchdog guard, a StallError)
    instead of an opaque hang."""
    from ..resilience import faults as _faults
    from ..resilience.retry import call_with_retry
    if mesh is None:
        from .mesh import current_mesh, local_mesh
        mesh = current_mesh() or local_mesh()
    axis = mesh.axis_names[0]
    ones = jnp.ones((mesh.devices.size,), jnp.int32)
    f = jax.jit(shard_map(lambda t: lax.psum(t, axis), mesh=mesh,
                          in_specs=P(axis), out_specs=P()),
                out_shardings=NamedSharding(mesh, P()))

    def dispatch():
        _faults.check("collective.barrier")
        f(ones).block_until_ready()

    call_with_retry(dispatch, site="collective.barrier")


def _eager_allreduce(arr, mesh, axis):
    from .. import telemetry as _telem
    from ..resilience import faults as _faults
    from ..resilience.retry import call_with_retry
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if arr.shape[0] % n:
        # odd leading dim: the single-array fused program pads-and-slices
        # (shard_map's in_specs would reject the ragged shard outright)
        fn = _multi_allreduce_fn(mesh, axis, [tuple(arr.shape)], arr.dtype)

        def dispatch_padded():
            _faults.check(
                "collective.all_reduce",
                context="shape=%s axis=%s (padded)"
                        % (tuple(arr.shape), axis))
            return fn(arr)[0]

        _telem.inc("comm.collectives")
        return call_with_retry(dispatch_padded,
                               site="collective.all_reduce")
    spec = P(axis)
    f = shard_map(lambda t: lax.psum(t, axis), mesh=mesh,
                  in_specs=spec, out_specs=P())

    def dispatch():
        _faults.check("collective.all_reduce",
                      context="shape=%s axis=%s" % (tuple(arr.shape), axis))
        return jax.jit(f)(arr)

    _telem.inc("comm.collectives")
    return call_with_retry(dispatch, site="collective.all_reduce")


# fused eager multi-allreduce programs, one per (mesh, axis, signature)
_MULTI_AR_CACHE = {}


def _padded_leading(m, n):
    """Smallest multiple of `n` that holds `m` leading rows."""
    return (m + n - 1) // n * n


def _multi_allreduce_fn(mesh, axis, shapes, dtype):
    key = (mesh, axis, tuple(tuple(s) for s in shapes), str(dtype))
    fn = _MULTI_AR_CACHE.get(key)
    if fn is not None:
        return fn
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    # pad-and-slice: a leading dim that does not divide the axis size is
    # zero-padded up to the next multiple INSIDE the fused program (the
    # shapes are static, so XLA folds the pad into the gather) and the
    # result unpacks to ceil(m/n) rows — the final row just sums fewer
    # real contributions. Keeps odd-sized buckets out of the error path;
    # tracelint TPU008 warns where the padding provably happens.
    padded = [(_padded_leading(s[0], n),) + tuple(s[1:]) for s in shapes]
    sizes = [int(_np.prod(p, dtype=_np.int64)) // n for p in padded]
    splits = list(_np.cumsum(sizes)[:-1])

    def run(*raws):
        # each (n*k_i, ...) array contributes its per-shard flat row; the
        # concatenated (n, K) matrix reduces in ONE psum over the axis
        flats = []
        for r, s, p in zip(raws, shapes, padded):
            if p[0] != s[0]:
                fill = jnp.zeros((p[0] - s[0],) + tuple(s[1:]), r.dtype)
                r = jnp.concatenate([r, fill], axis=0)
            flats.append(r.reshape(n, -1))
        flat = jnp.concatenate(flats, axis=1) if len(flats) > 1 else flats[0]
        red = shard_map(lambda t: lax.psum(t, axis), mesh=mesh,
                        in_specs=P(axis), out_specs=P())(flat)
        row = red.reshape(-1)
        parts = jnp.split(row, splits) if splits else [row]
        return tuple(
            q.reshape((p[0] // n,) + tuple(s[1:]))
            for q, p, s in zip(parts, padded, shapes))

    fn = jax.jit(run)
    _MULTI_AR_CACHE[key] = fn
    return fn


def all_reduce_multi(arrays, mesh=None, axis=None, bucket_mb=None):
    """Eager fused multi-tensor allreduce: sum each array's leading-dim
    shards over `axis` (the `_eager_allreduce` contract) but batched —
    arrays pack into size-capped buckets (`mx.engine`) and each bucket is
    ONE jitted flatten->psum->unflatten program, launched as soon as it
    fills so bucket N's collective overlaps bucket N+1's pack. A leading
    dim that does not divide the axis size is zero-padded up to the next
    multiple inside the fused program (pad-and-slice) — the result then
    has ceil(m/n) leading rows, the last summing fewer real
    contributions. Returns the reduced arrays in input order."""
    from .. import engine as _engine
    from .. import telemetry as _telem
    from ..resilience import faults as _faults
    from ..resilience.retry import call_with_retry
    if mesh is None:
        from .mesh import current_mesh, local_mesh
        mesh = current_mesh() or local_mesh()
    axis = axis or mesh.axis_names[0]
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    cap = _engine.bucket_bytes(bucket_mb)
    if not cap or len(arrays) < 2:
        return [_eager_allreduce(a, mesh, axis) for a in arrays]
    out = [None] * len(arrays)
    for bucket in _engine.bucketize(enumerate(arrays), cap):
        fn = _multi_allreduce_fn(mesh, axis, bucket.shapes, bucket.dtype)
        context = "bucket tensors=[%s] %dB" % (bucket.key_range(),
                                               bucket.nbytes)

        def dispatch(fn=fn, bucket=bucket, context=context):
            _faults.check("collective.all_reduce", context=context)
            return fn(*bucket.raws)

        _telem.inc("comm.collectives")
        ts = _telem.span_clock()
        t0 = time.perf_counter()
        parts = call_with_retry(dispatch, site="collective.all_reduce",
                                context=context)
        _telem.record_span(bucket.span_name(), _engine.SPAN_CAT_COMM,
                           ts, time.perf_counter() - t0)
        for idx, part in zip(bucket.keys, parts):
            out[idx] = part
    for i, a in enumerate(arrays):
        if out[i] is None:  # zero-size arrays skip the bucketer; their
            # reduction is an empty array of the shard shape —
            # ceil(m/n) rows, matching the padded per-tensor contract
            out[i] = jnp.zeros((-(-a.shape[0] // n),) + tuple(a.shape[1:]),
                               a.dtype)
    return out


# ---------------------------------------------------------------------------
# ZeRO weight-update sharding primitives: bucket-wise reduce-scatter and
# all-gather over a persistent BucketLayout (mx.engine). Each bucket is ONE
# fused flatten(+zero-pad)→collective launch — the reduce-scatter analog of
# psum_bucketed, with the bucket as the scatter segment.
# ---------------------------------------------------------------------------
def reduce_scatter_multi(xs, axis_name, axis_size=None, layout=None,
                         bucket_mb=None):
    """Reduce-scatter a LIST of per-device arrays over a mesh axis (inside
    shard_map/jit) as few fused flat collectives: arrays pack into the
    persistent buckets of `layout` (frozen from the inputs on first use —
    pass the returned layout back in on later steps), each bucket's flat
    vector is zero-padded to a multiple of the axis size (`mx.engine`
    BucketSpec padding, the PR 7 odd-leading-dim trick) and ONE
    `lax.psum_scatter` hands this device its contiguous
    ``padded/axis_size`` shard of the bucket sum.

    Returns ``(shards, layout)``: shards[b] aligns with layout.buckets[b].
    Under jit the `comm.reduce_scatter` counter ticks once per bucket per
    (re)trace — collectives-per-program, not per step."""
    from .. import engine as _engine
    from .. import telemetry as _telem
    if any(int(x.size) == 0 for x in xs):
        # the bucketer skips empties, which would silently drop slots and
        # misalign the all_gather_multi return — make the caller decide
        raise ValueError("reduce_scatter_multi: zero-size arrays have no "
                         "shard; filter them out before the call")
    if layout is None:
        if axis_size is None:
            raise ValueError(
                "reduce_scatter_multi needs axis_size (static) or a frozen "
                "layout to derive shard boundaries")
        layout = _engine.BucketLayout.from_entries(
            enumerate(xs), axis_size, _engine.bucket_bytes(bucket_mb))
    else:
        layout.assert_matches([str(i) for i in range(len(xs))])
    by_key = {str(i): x for i, x in enumerate(xs)}
    shards = []
    for spec in layout:
        flat = _engine.pack_flat(spec, [by_key[k] for k in spec.keys])
        _telem.inc("comm.reduce_scatter")
        shards.append(lax.psum_scatter(flat, axis_name,
                                       scatter_dimension=0, tiled=True))
    return shards, layout


def all_gather_multi(shards, layout, axis_name):
    """Inverse of `reduce_scatter_multi`: all-gather each bucket's
    per-device shard back to the full padded flat vector (ONE
    `lax.all_gather` per bucket) and unpack to the original shapes, pad
    dropped. Returns the arrays in the layout's key order (= the input
    order `reduce_scatter_multi` saw)."""
    from .. import engine as _engine
    from .. import telemetry as _telem
    outs = {}
    for spec, shard in zip(layout, shards):
        _telem.inc("comm.all_gather")
        flat = lax.all_gather(shard, axis_name, tiled=True)
        for k, part in zip(spec.keys, _engine.unpack_flat(spec, flat)):
            outs[k] = part
    return [outs[k] for k in layout.keys()]


# ---------------------------------------------------------------------------
# Sparse (row_sparse) comm primitives: unique-rows allgather instead of
# densifying a sparse gradient to a full-table allreduce (ISSUE 17 tentpole
# part 3). Fixed-size slabs keep shapes static: each rank contributes
# exactly `n` (id, row) pairs, padding unused slots with `pad_id` rows.
# ---------------------------------------------------------------------------
def all_gather_rows(ids, vals, axis_name):
    """All-gather fixed-size (ids, vals) row slabs over a mesh axis (inside
    shard_map/jit): every rank contributes its ``(n,)`` int32 row ids and
    ``(n, *row)`` values, and everyone receives the rank-order concatenation
    ``(world*n,)`` / ``(world*n, *row)``. Pad slots carry a negative id.
    This is the sparse analog of the dense bucket allgather — the bytes on
    the wire scale with touched rows, not table rows."""
    from .. import telemetry as _telem
    _telem.inc("comm.sparse.all_gather_rows")
    gids = lax.all_gather(ids, axis_name, axis=0, tiled=True)
    gvals = lax.all_gather(vals, axis_name, axis=0, tiled=True)
    return gids, gvals


def merge_unique_rows(ids, vals, pad_id=-1):
    """Traceable row-dedup: sum duplicate row ids in a static-shape
    ``(n,)``/``(n, *row)`` slab. Negative ids are padding. Returns
    ``(out_ids, out_vals)`` of the SAME static shape — unique real rows
    first (ids ascending), remaining slots padded with `pad_id` and zero
    rows. The reduction is a stable sort + one segment-sum (riding the
    Pallas sparse kernel when eligible), so duplicate contributions
    accumulate in a deterministic order."""
    from ..ops import sparse_ops as _sops
    n = ids.shape[0]
    ids32 = jnp.asarray(ids).astype(jnp.int32)
    vals = jnp.asarray(vals)
    sentinel = jnp.iinfo(jnp.int32).max
    valid = ids32 >= 0
    key = jnp.where(valid, ids32, sentinel)
    order = jnp.argsort(key, stable=True)
    sk = key[order]
    sv = vals[order]
    svalid = sk != sentinel
    starts = jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]]) & svalid
    seg = jnp.cumsum(starts.astype(jnp.int32)) - 1
    # invalid (pad) rows route to the last slot with zeroed values; with at
    # least one pad row present the number of real segments is < n, so the
    # last slot is never a real segment
    seg = jnp.where(svalid, seg, n - 1)
    mask = svalid.reshape((n,) + (1,) * (sv.ndim - 1))
    merged = _sops.segment_sum(jnp.where(mask, sv, 0), seg, n)
    out_ids = jnp.full((n,), pad_id, jnp.int32).at[seg].set(
        jnp.where(svalid, sk, pad_id).astype(jnp.int32), mode="drop")
    return out_ids, merged.astype(vals.dtype)


def psum_unique_rows(ids, vals, axis_name, pad_id=-1):
    """Sum row-sparse contributions over a mesh axis WITHOUT densifying to
    the full table (inside shard_map/jit): one fixed-size unique-rows
    allgather of the ``(n,)``/``(n, *row)`` slabs, then an in-trace dedup
    of the ``world*n`` gathered rows. Returns static-shape
    ``(world*n,)`` ids + values — unique rows first, `pad_id` padding.
    Replaces the full-vocab mask-allreduce + dense-union allreduce the
    densified path pays; the win grows with table size."""
    from .. import telemetry as _telem
    _telem.inc("comm.sparse.psum_unique_rows")
    gids, gvals = all_gather_rows(ids, vals, axis_name)
    return merge_unique_rows(gids, gvals, pad_id=pad_id)


def allreduce_bench(size_mb=64, iters=20, mesh=None, dtype=jnp.float32):
    """Measure allreduce algorithmic bandwidth (GB/s) over the mesh's first
    axis — the KVStore-allreduce metric from BASELINE.json. Returns
    (gbps, seconds_per_op)."""
    if mesh is None:
        from .mesh import current_mesh, local_mesh
        mesh = current_mesh() or local_mesh()
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    itemsize = jnp.dtype(dtype).itemsize
    per_dev = max(1, int(size_mb * 1e6 / itemsize / n))
    x = jnp.ones((n * per_dev,), dtype)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    f = jax.jit(shard_map(lambda t: lax.psum(t, axis), mesh=mesh,
                          in_specs=P(axis), out_specs=P(axis)))
    f(x).block_until_ready()  # warm compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    # ring allreduce moves 2*(n-1)/n of the buffer per device
    nbytes = x.size * itemsize
    algo_bytes = 2 * (n - 1) / n * nbytes
    return algo_bytes / dt / 1e9, dt

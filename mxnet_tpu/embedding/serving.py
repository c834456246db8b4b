"""Kvstore-served embedding lookups with the serve warm-up discipline.

`EmbeddingLookupService` turns `kvstore.row_sparse_pull` against a sharded
table into a COMPILED cross-shard gather: the full table is snapshotted
(all-gathered) once, placed vocab-sharded over the mesh when one is
available (GSPMD inserts the cross-shard collective inside the jitted
gather — the "compiled cross-shard gather"), and every request batch is
padded up to a fixed bucket size so the jit cache holds exactly
``len(buckets)`` signatures, all compiled at `warmup()`.

The no-retrace contract is the serve one (`ServePrograms._on_miss`): a
post-warm-up bucket miss counts ``serve.retrace``, notes the compile, and
routes through `analysis.guard.on_retrace` so the trace guard can veto —
steady-state traffic never compiles. Lookup latency lands in the
``embedding.serve.lookup_ms`` histogram; `parse_log --sparse` reports its
quantiles.

``refresh()`` re-snapshots the table after training steps — serving reads
a consistent snapshot, never a half-updated shard.
"""
from __future__ import annotations

import time

import numpy as _np

import jax
import jax.numpy as jnp

__all__ = ["EmbeddingLookupService", "default_buckets"]


def default_buckets(max_batch=1024):
    """Power-of-two id-batch buckets up to `max_batch` — the same
    fixed-signature trick as the serve prefill windows."""
    out, b = [], 8
    while b < int(max_batch):
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return tuple(out)


class EmbeddingLookupService:
    """Fixed-bucket compiled gathers over a table snapshot.

    `table` is a `ShardedEmbedding` (snapshotted via `gathered_weight`)
    or a plain (vocab, dim) array. `mesh` (optional) places the snapshot
    vocab-sharded via the table's `shard_spec`, so the jitted gather runs
    as one GSPMD program with the cross-shard collective inside."""

    def __init__(self, table, max_batch=1024, buckets=None, mesh=None):
        from .table import ShardedEmbedding
        self._table = table if isinstance(table, ShardedEmbedding) else None
        self.buckets = tuple(sorted(buckets or default_buckets(max_batch)))
        self.max_batch = self.buckets[-1]
        self._mesh = mesh
        self._fns = {}
        self._warm = False
        self._weight = None if self._table is not None else jnp.asarray(table)
        self.refresh()

    # -- snapshot --------------------------------------------------------
    def refresh(self):
        """(Re)snapshot the table — one all-gather; serving then reads a
        consistent copy while training mutates the shards."""
        if self._table is not None:
            weight = jnp.asarray(self._table.gathered_weight())
        elif self._weight is None:
            raise ValueError("EmbeddingLookupService needs a "
                             "ShardedEmbedding or a (vocab, dim) array")
        else:
            weight = self._weight
        if self._mesh is not None and self._table is not None:
            weight = jax.device_put(
                weight, self._table.shard_spec(self._mesh))
        self._weight = weight
        self.vocab, self.dim = int(weight.shape[0]), int(weight.shape[1])

    # -- programs --------------------------------------------------------
    def _bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            "lookup batch %d exceeds the largest bucket %d — size the "
            "service with max_batch at admission capacity" % (n,
                                                              self.max_batch))

    def _fn(self, bucket):
        fn = self._fns.get(bucket)
        if fn is None:
            if self._warm:
                self._on_miss(bucket)

            def gather(weight, ids):
                valid = ids >= 0
                rows = weight[jnp.clip(ids, 0, weight.shape[0] - 1)]
                return jnp.where(valid[:, None], rows, 0)

            fn = self._fns[bucket] = jax.jit(gather)
            from .. import telemetry as _telem
            _telem.note_compile("embedding.lookup[%d]" % bucket)
        return fn

    def _on_miss(self, bucket):
        """A post-warm-up bucket miss IS a retrace (serve contract)."""
        from .. import telemetry as _telem
        from ..analysis import guard as _guard
        _telem.inc("serve.retrace")
        _telem.note_compile("embedding.lookup(retrace)")
        if _guard.ACTIVE:
            _guard.on_retrace("embedding.lookup", len(self._fns) + 1,
                              "unwarmed id-batch bucket %d (warmed: %s)"
                              % (bucket, ",".join(map(str, self._fns))
                                 or "none"))

    def warmup(self):
        """Compile the gather for every bucket. After this, steady-state
        lookups never compile (the acceptance bar)."""
        from .. import telemetry as _telem
        with _telem.span("embedding.warmup", "serve"):
            for b in self.buckets:
                fn = self._fn(b)
                fn(self._weight,
                   jnp.full((b,), -1, jnp.int32)).block_until_ready()
        self._warm = True

    # -- lookup ----------------------------------------------------------
    def lookup(self, ids):
        """Gather rows for `ids` ((n,) int, n <= max_batch). Returns the
        (n, dim) rows; pads to the bucket internally."""
        from .. import telemetry as _telem
        ids = jnp.asarray(ids).astype(jnp.int32)
        n = int(ids.shape[0])
        bucket = self._bucket_for(n)
        if n < bucket:
            ids = jnp.concatenate(
                [ids, jnp.full((bucket - n,), -1, jnp.int32)])
        t0 = time.perf_counter()
        out = self._fn(bucket)(self._weight, ids)
        out = out[:n]
        if _telem.ENABLED:
            _telem.inc("embedding.serve.lookup")
            _telem.inc("embedding.serve.rows", n)
            _telem.observe("embedding.serve.lookup_ms",
                           (time.perf_counter() - t0) * 1e3)
        return out

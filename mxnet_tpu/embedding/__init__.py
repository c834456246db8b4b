"""`mx.embedding` — vocab-sharded embedding tables (ISSUE 17 tentpole).

The MXNet lineage's signature production workload — KVStore `row_sparse`
push/pull driving recsys embedding models — rebuilt TPU-native. Four
coordinated layers:

* **sharded tables** (`table.ShardedEmbedding`) — giant tables sharded
  over the mesh on the vocab axis: lookups are a local gather with
  out-of-shard rows masked, completed by one cross-rank sum; optimizer
  state (momentum / Adam moments) lives ONLY beside the rows a rank owns
  (the ZeRO pattern per table); checkpoints are world-size-independent
  layout payloads, so a world-4 snapshot restores onto world 2 (elastic).
* **sparse-gradient kernels** (`ops.sparse_ops.segment_sum`) — the
  Pallas one-pass scatter-add under every dedup/accumulate step,
  `MXNET_TPU_USE_PALLAS`-gated with a counted never-erroring XLA
  fallback, bit-identical to ``zeros().at[ids].add()``.
* **sparse comm** (`parallel.collectives.all_gather_rows` /
  `psum_unique_rows`) — gradients cross the wire as fixed-size
  (row-id, row) slabs, deduped in-trace, instead of densifying to a
  full-table allreduce; wired through the kvstore's bucketed push with
  per-bucket retry.
* **serving lookup** (`serving.EmbeddingLookupService`) — fixed-bucket
  compiled gathers with the serve-side warm-up discipline: every bucket
  compiles at warmup, steady traffic never retraces (misses count
  ``serve.retrace`` and face the trace guard).

Quickstart::

    import mxnet_tpu as mx
    from mxnet_tpu.embedding import ShardedEmbedding

    table = ShardedEmbedding(vocab=1_000_000, dim=64, optimizer="adam")
    rows = table.lookup(ids)             # (batch, dim)
    ...                                   # loss over rows
    table.apply_grads(ids, grad_rows)    # dedup + owned-row update

Observability: table + state bytes land in the HBM ledger scope
``embedding``; pushes/lookups tick ``embedding.*`` counters and the comm
layer ticks ``comm.sparse.*`` — `parse_log --sparse` renders the table.
"""
from .table import EmbeddingComm, MeshEmbeddingComm, ShardedEmbedding
from .serving import EmbeddingLookupService

__all__ = ["ShardedEmbedding", "EmbeddingComm", "MeshEmbeddingComm",
           "EmbeddingLookupService"]

"""Vocab-sharded embedding tables with row-sharded optimizer state.

`ShardedEmbedding` is the per-rank object: rank r of `comm.world` owns the
contiguous row block ``[r*rows_per_shard, (r+1)*rows_per_shard)`` of the
vocab axis (padded up to a world multiple, so a non-divisible vocab just
carries a few zero rows on the last rank — the `BucketSpec.padded` trick
applied to rows). The three legs:

* **lookup** — every rank gathers the requested ids from its OWN shard
  with out-of-shard rows masked to zero, and one cross-rank sum
  (`comm.all_reduce`) completes the batch: exactly one rank contributes
  each real row, so the sum is bit-identical to the dense gather
  (the 8B plan's one-hot-matmul embedding trick, as a masked gather).
* **apply_grads** — the sparse data-parallel update: each rank dedups its
  local (ids, grad-rows) via the traceable stable-sort merge, exchanges
  fixed-size unique-row slabs (`comm.all_gather` — rank-order concat, the
  eager analog of `collectives.all_gather_rows`), re-merges, and updates
  ONLY the touched rows it owns. Optimizer state (momentum / Adam
  moments) is allocated per owned row — the ZeRO pattern per table — and
  the update follows the reference's `lazy_update` semantics: untouched
  rows see no decay.
* **state_payload / load_state_payload** — world-size-independent
  checkpoints: the payload carries the full all-gathered table + state
  (layout header alongside, `BucketLayout.to_payload` style), and restore
  re-slices for THIS comm's world/rank — world 4 → world 2 is just
  different shard boundaries over the same bytes.

Comm backends mirror `optimizer.zero.ZeroComm`: the base `EmbeddingComm`
is the world-1 identity (machinery still exercised), `MeshEmbeddingComm`
lowers to `lax.psum`/`lax.all_gather` for use inside shard_map, and tests
inject a threaded mailbox comm (FakeFleet) that sums in rank order for
bit-exact parity. Table + state bytes are accounted to the HBM ledger
scope ``embedding`` at every (re)allocation site.
"""
from __future__ import annotations

import threading
import weakref

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["EmbeddingComm", "MeshEmbeddingComm", "ShardedEmbedding"]

_OPTIMIZERS = ("sgd", "adam")


class EmbeddingComm:
    """Collective backend contract for sharded tables — and its world-1
    implementation (identity exchanges; one rank owns every row).

    all_reduce(x): cross-rank SUM of a dense array (the lookup
        completion leg).
    all_gather(x): rank-order concatenation along axis 0 of each rank's
        equal-shape contribution (the unique-row slab exchange).
    """

    world = 1
    rank = 0

    def all_reduce(self, x):
        return x

    def all_gather(self, x):
        return x


class MeshEmbeddingComm:
    """In-trace backend: the same two legs lowered to XLA collectives over
    a named mesh axis, for a `ShardedEmbedding` driven inside shard_map
    (rank/world are static per trace)."""

    def __init__(self, axis_name, world, rank):
        self.axis_name = axis_name
        self.world = int(world)
        self.rank = int(rank)

    def all_reduce(self, x):
        return lax.psum(x, self.axis_name)

    def all_gather(self, x):
        return lax.all_gather(x, self.axis_name, axis=0, tiled=True)


# live tables in this process, for absolute ledger accounting (several
# tables — or several FakeFleet ranks — share the one "embedding" scope)
_LIVE = weakref.WeakSet()
_LIVE_LOCK = threading.Lock()


def _account_all():
    from ..telemetry import ledger as _ledger
    with _LIVE_LOCK:
        total = sum(t._nbytes() for t in _LIVE)
    _ledger.account("embedding", total)


class ShardedEmbedding:
    """One vocab-sharded table on one rank. See the module docstring for
    the three legs; hyperparameters follow the reference optimizers
    (`sgd` with optional momentum, `adam` with bias correction and the
    lazy row_sparse semantics of `optimizer._run_op`)."""

    def __init__(self, vocab, dim, comm=None, dtype=jnp.float32,
                 optimizer="sgd", learning_rate=0.01, momentum=0.0,
                 beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0,
                 weight=None, seed=0, name="embedding"):
        if optimizer not in _OPTIMIZERS:
            raise ValueError("ShardedEmbedding supports %s; got %r"
                             % ("/".join(_OPTIMIZERS), optimizer))
        self.comm = comm or EmbeddingComm()
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.dtype = jnp.dtype(dtype)
        self.name = str(name)
        self.optimizer = optimizer
        self.learning_rate = float(learning_rate)
        self.momentum = float(momentum)
        self.beta1, self.beta2 = float(beta1), float(beta2)
        self.eps = float(eps)
        self.wd = float(wd)
        world = self.comm.world
        self.padded_vocab = -(-self.vocab // world) * world
        self.rows_per_shard = self.padded_vocab // world
        self.lo = self.comm.rank * self.rows_per_shard
        if weight is None:
            # full-table init from the seed, then slice: every world size
            # (and the dense reference) sees the same bytes
            full = (jax.random.normal(jax.random.PRNGKey(seed),
                                      (self.vocab, self.dim), jnp.float32)
                    * (1.0 / _np.sqrt(self.dim))).astype(self.dtype)
        else:
            full = jnp.asarray(weight, self.dtype)
            if full.shape != (self.vocab, self.dim):
                raise ValueError("weight shape %s != (vocab, dim) %s"
                                 % (full.shape, (self.vocab, self.dim)))
        self.weight = self._slice_shard(_np.asarray(full))
        self._state = {}
        if optimizer == "sgd" and self.momentum:
            self._state["mom"] = jnp.zeros_like(self.weight)
        elif optimizer == "adam":
            self._state["mean"] = jnp.zeros_like(self.weight)
            self._state["var"] = jnp.zeros_like(self.weight)
        self._step = 0
        with _LIVE_LOCK:
            _LIVE.add(self)
        _account_all()

    # -- geometry --------------------------------------------------------
    def _slice_shard(self, full_np):
        """(vocab, dim) host array -> this rank's (rows_per_shard, dim)
        shard, zero-padding the tail rows of the last rank."""
        pad = self.padded_vocab - full_np.shape[0]
        if pad:
            full_np = _np.concatenate(
                [full_np, _np.zeros((pad, self.dim), full_np.dtype)])
        lo = self.lo
        return jnp.asarray(full_np[lo:lo + self.rows_per_shard])

    def _nbytes(self):
        n = self.weight.size * self.weight.dtype.itemsize
        for s in self._state.values():
            n += s.size * s.dtype.itemsize
        return int(n)

    def shard_spec(self, mesh=None, rules=None):
        """NamedSharding placing the FULL (padded_vocab, dim) table with
        the vocab axis sharded — derived from the existing `ShardingRules`
        engine's logical-axis table (``vocab`` -> the model axis), so a
        rule override re-routes the table like any other param."""
        from jax.sharding import NamedSharding
        from ..parallel.sharding import logical_to_spec
        if mesh is None:
            from ..parallel.mesh import current_mesh, local_mesh
            mesh = current_mesh() or local_mesh()
        if rules is not None:
            spec = rules.spec_for(self.name + ".weight",
                                  (self.padded_vocab, self.dim), mesh=mesh)
        else:
            spec = logical_to_spec(("vocab", "embed"))
        return NamedSharding(mesh, spec)

    # -- lookup ----------------------------------------------------------
    def lookup(self, ids):
        """Gather rows for `ids` ((batch,) int, any order, repeats fine):
        local masked gather + one cross-rank sum. Rows with negative ids
        (padding) come back zero."""
        from .. import telemetry as _telem
        ids = jnp.asarray(ids).astype(jnp.int32)
        if _telem.ENABLED:
            _telem.inc("embedding.lookup")
            _telem.inc("embedding.lookup.rows", int(ids.shape[0]))
        local = ids - self.lo
        in_shard = (local >= 0) & (local < self.rows_per_shard) & (ids >= 0)
        rows = self.weight[jnp.clip(local, 0, self.rows_per_shard - 1)]
        rows = jnp.where(in_shard[:, None], rows, 0)
        return self.comm.all_reduce(rows)

    # -- sparse update ---------------------------------------------------
    def apply_grads(self, ids, grads):
        """One sparse data-parallel update step: dedup local rows,
        exchange fixed-size unique-row slabs, update owned touched rows
        (lazy semantics — untouched rows see no decay, no moment update).
        `grads` is (batch, dim) aligned with `ids`; repeats accumulate."""
        from .. import telemetry as _telem
        from ..parallel.collectives import merge_unique_rows
        ids = jnp.asarray(ids).astype(jnp.int32)
        grads = jnp.asarray(grads, self.weight.dtype)
        # local dedup: unique rows first (ids ascending), -1 padding
        uids, uvals = merge_unique_rows(ids, grads)
        # fixed-size slab exchange — rank-order concat, then re-merge
        gids = self.comm.all_gather(uids)
        gvals = self.comm.all_gather(uvals)
        if self.comm.world > 1:
            uids, uvals = merge_unique_rows(gids, gvals)
        else:
            uids, uvals = gids, gvals
        if _telem.ENABLED:
            _telem.inc("embedding.push")
            _telem.inc("embedding.push.rows", int(ids.shape[0]))
            _telem.inc("embedding.push.unique_rows",
                       int(_np.asarray(jnp.sum(uids >= 0))))
        self._apply_unique(uids, uvals)

    def _apply_unique(self, uids, uvals):
        """Update owned rows from a deduped (ids, rows) slab (-1 pads)."""
        from ..ops import sparse_ops as _sops
        local = uids - self.lo
        mine = (local >= 0) & (local < self.rows_per_shard) & (uids >= 0)
        idx = jnp.where(mine, local, -1)
        # dense per-shard grad + touched mask, both through the sparse
        # kernel dispatch (negative ids drop on the kernel path; the XLA
        # path sees them routed to a scratch row that is sliced away)
        scratch = self.rows_per_shard
        safe = jnp.where(idx >= 0, idx, scratch)
        gshard = _sops.segment_sum(
            jnp.where(mine[:, None], uvals, 0), safe, scratch + 1)[:-1]
        counts = jnp.zeros((scratch + 1,), jnp.float32).at[safe].add(
            jnp.where(mine, 1.0, 0.0))[:-1]
        touched = counts > 0
        self._step += 1
        w = self.weight.astype(jnp.float32)
        g = gshard.astype(jnp.float32)
        if self.wd:
            g = g + self.wd * jnp.where(touched[:, None], w, 0)
        lr = self.learning_rate
        if self.optimizer == "sgd":
            if self.momentum:
                mom = self._state["mom"].astype(jnp.float32)
                mom = jnp.where(touched[:, None],
                                self.momentum * mom - lr * g, mom)
                self._state["mom"] = mom.astype(self.weight.dtype)
                w = jnp.where(touched[:, None], w + mom, w)
            else:
                w = jnp.where(touched[:, None], w - lr * g, w)
        else:  # adam, lazy rows
            mean = self._state["mean"].astype(jnp.float32)
            var = self._state["var"].astype(jnp.float32)
            mean = jnp.where(touched[:, None],
                             self.beta1 * mean + (1 - self.beta1) * g, mean)
            var = jnp.where(touched[:, None],
                            self.beta2 * var + (1 - self.beta2) * g * g, var)
            self._state["mean"] = mean.astype(self.weight.dtype)
            self._state["var"] = var.astype(self.weight.dtype)
            t = self._step
            coef1 = 1.0 - self.beta1 ** t
            coef2 = 1.0 - self.beta2 ** t
            lr_t = lr * _np.sqrt(coef2) / coef1
            upd = lr_t * mean / (jnp.sqrt(var) + self.eps)
            w = jnp.where(touched[:, None], w - upd, w)
        self.weight = w.astype(self.dtype)
        _account_all()

    # -- full-table views ------------------------------------------------
    def gathered_weight(self):
        """The full (vocab, dim) table, all-gathered and unpadded —
        the serving snapshot and the checkpoint body."""
        full = self.comm.all_gather(self.weight)
        return full[:self.vocab]

    # -- elastic checkpoints ---------------------------------------------
    def state_payload(self):
        """World-size-independent state dict: a layout header plus the
        full all-gathered table and optimizer state as numpy arrays
        (`ZeroUpdater.state_payload` shape: pickleable, orbax-friendly)."""
        state = {name: _np.asarray(self.comm.all_gather(s)[:self.vocab])
                 for name, s in self._state.items()}
        return {
            "embed_format": 1,
            "layout": {"vocab": self.vocab, "dim": self.dim,
                       "dtype": str(self.dtype), "optimizer": self.optimizer,
                       "world": self.comm.world},
            "table": _np.asarray(self.gathered_weight()),
            "state": state,
            "step": self._step,
        }

    def load_state_payload(self, payload):
        """Inverse of `state_payload`, re-partitioned for THIS comm's
        world/rank — restoring onto a different world size just slices
        different row boundaries out of the same full table."""
        if int(payload.get("embed_format", -1)) != 1:
            raise ValueError("not an embedding state payload: %r"
                             % (payload.get("embed_format"),))
        layout = payload["layout"]
        if (int(layout["vocab"]), int(layout["dim"])) != (self.vocab,
                                                          self.dim):
            raise ValueError(
                "payload table is %sx%s, this table is %dx%d"
                % (layout["vocab"], layout["dim"], self.vocab, self.dim))
        if layout.get("optimizer", self.optimizer) != self.optimizer:
            raise ValueError("payload optimizer %r != %r"
                             % (layout.get("optimizer"), self.optimizer))
        self.weight = self._slice_shard(
            _np.asarray(payload["table"]).astype(self.dtype))
        self._state = {
            name: self._slice_shard(
                _np.asarray(full).astype(self.dtype))
            for name, full in payload["state"].items()}
        self._step = int(payload.get("step", 0))
        _account_all()

"""KVStore: the key-value gradient/parameter aggregation API.

TPU-native analog of reference src/kvstore/ + python/mxnet/kvstore/kvstore.py.
The API (create/init/push/pull/pushpull/row_sparse_pull/set_optimizer) is
preserved verbatim. Backend mapping (SURVEY.md §5.8):

* `local` / `device` — single-process multi-device aggregation. The
  reference reduces on CPU (`KVStoreLocal`, src/kvstore/kvstore_local.h) or
  P2P on GPUs (`CommDevice`, src/kvstore/comm.h); here the reduce is a jnp
  sum over per-device replicas — XLA emits the transfer+add chain, and on a
  sharded mesh the same call lowers to an ICI all-reduce.
* `nccl` — alias of `device` (the ring-allreduce role is played by XLA
  collectives; reference: src/kvstore/kvstore_nccl.h).
* `dist_sync` / `dist_async` / `dist_device_sync` — multi-process global
  mesh over `jax.distributed` (see kvstore_dist.py). Parameter-server
  semantics (server-side optimizer via set_optimizer) are preserved with
  optimizer states sharded ZeRO-style instead of server processes.

Push/pull keeps the reference's aggregation contract: push accumulates the
sum of all pushed values per key; pull broadcasts the merged value.
"""
from __future__ import annotations

import pickle
import time

from .. import engine as _engine
from .. import ndarray as nd
from .. import optimizer as opt
from .. import telemetry as _telem
from ..base import MXNetError

__all__ = ["KVStore", "KVStoreLocal", "ReadyPushSession", "create"]


def _key_list(key):
    return key if isinstance(key, (list, tuple)) else [key]


def _payload_bytes(value):
    """Total payload bytes of a (nested) list of NDArrays — the comm-volume
    number the reference's PS path would see on the wire. Best effort:
    entries without size/dtype (symbols, raw scalars) count zero."""
    total = 0
    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, (list, tuple)):
            stack.extend(v)
        elif v is not None:
            try:
                total += int(v.size) * int(v.dtype.itemsize)
            except Exception:
                pass
    return total


def _record_comm(direction, value):
    """Telemetry hook shared by every store backend's push/pull."""
    _telem.inc("kvstore.%s_calls" % direction)
    nbytes = _payload_bytes(value)
    if nbytes:
        _telem.inc("kvstore.%s_bytes" % direction, nbytes)


def _val_list(value, nkeys):
    if isinstance(value, (list, tuple)):
        if len(value) and isinstance(value[0], (list, tuple)):
            return list(value)
        if nkeys == 1:
            return [list(value)] if isinstance(value[0], nd.NDArray) and \
                len(value) > 1 else [value[0] if len(value) == 1 else
                                     list(value)]
        return list(value)
    return [value]


class KVStore:
    """Base/abstract store. reference: python/mxnet/kvstore/kvstore.py."""

    def __init__(self):
        self._updater = None
        self._compression_params = None

    # -- interface ------------------------------------------------------
    @property
    def type(self):
        raise NotImplementedError

    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    def init(self, key, value):
        raise NotImplementedError

    def push(self, key, value, priority=0):
        raise NotImplementedError

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        raise NotImplementedError

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out=out, priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        raise NotImplementedError

    def set_gradient_compression(self, compression_params):
        """reference: KVStore::SetGradientCompression (2bit/signum).
        Stored and applied by dist backends; local stores note it only."""
        from ..optimizer.zero import ZeroUpdater
        if isinstance(self._updater, ZeroUpdater):
            raise MXNetError(
                "gradient compression cannot be enabled on a store running "
                "the ZeRO sharded update (no compressed reduce-scatter)")
        self._compression_params = dict(compression_params)

    def set_optimizer(self, optimizer, zero=None):
        """Run the optimizer on the store (server-side update semantics).
        reference: kvstore.py (set_optimizer) — pickles the optimizer to
        servers; here the updater runs wherever the merged value lives.

        zero=True (or `MXNET_TPU_ZERO=1`) swaps the replicated Updater for
        the ZeRO-1 `optimizer.zero.ZeroUpdater`: gradients leave the store
        as bucket-wise reduce-scatter, optimizer state lives only for the
        owned shards, updated weights return via all-gather (SGD/Adam
        only; the comm backend comes from `_zero_comm` — identity on a
        local store, cross-worker collectives on the dist store)."""
        from ..optimizer.zero import ZeroUpdater, zero_enabled
        if zero_enabled(zero):
            if getattr(self, "_gc", None) is not None:
                raise MXNetError(
                    "ZeRO sharded update and gradient compression are "
                    "mutually exclusive: the reduce-scatter leg has no "
                    "compressed form (quantized partial sums break the "
                    "error-feedback residual). Disable one of them.")
            self._set_updater(ZeroUpdater(opt.create(optimizer),
                                          comm=self._zero_comm()))
        else:
            self._set_updater(opt.get_updater(optimizer))

    def _zero_comm(self):
        """Collective backend for the ZeRO updater; the base store is
        single-rank (identity exchanges)."""
        from ..optimizer.zero import ZeroComm
        return ZeroComm()

    def _set_updater(self, updater):
        self._updater = updater

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "Cannot save states for distributed training"
        with open(fname, "wb") as fout:
            fout.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "Cannot load states for distributed training"
        with open(fname, "rb") as fin:
            self._updater.set_states(fin.read())

    def barrier(self):
        nd.waitall()

    def _send_command_to_servers(self, head, body):
        pass


class KVStoreLocal(KVStore):
    """Single-process aggregation store (types local/device/nccl).
    reference: src/kvstore/kvstore_local.h (KVStoreLocal) + comm.h
    (CommCPU/CommDevice)."""

    def __init__(self, type_name="local"):
        super().__init__()
        self._type = type_name
        self._store = {}          # key -> merged NDArray (master copy)
        self._updater = None
        self._embeddings = {}     # key -> ShardedEmbedding (vocab-sharded)
        self._embed_services = {}  # key -> EmbeddingLookupService

    @property
    def type(self):
        return self._type

    def init(self, key, value):
        keys = _key_list(key)
        values = _val_list(value, len(keys))
        assert len(keys) == len(values), "key/value length mismatch"
        for k, v in zip(keys, values):
            if isinstance(v, (list, tuple)):
                v = v[0]
            if str(k) in self._store:
                raise ValueError("duplicate init of key " + str(k))
            self._store[str(k)] = v.copy()

    def init_embedding(self, key, table, max_batch=1024, warmup=True):
        """Register a vocab-sharded `embedding.ShardedEmbedding` under
        `key`: pushes of `row_sparse` gradients route to the table's
        owned-row update, and `row_sparse_pull` becomes a compiled
        cross-shard gather through an `EmbeddingLookupService` (warmed
        here, so steady pull traffic never compiles — the serve
        contract)."""
        from ..embedding.serving import EmbeddingLookupService
        k = str(key)
        if k in self._store or k in self._embeddings:
            raise ValueError("duplicate init of key " + k)
        self._embeddings[k] = table
        svc = EmbeddingLookupService(table, max_batch=max_batch)
        if warmup:
            svc.warmup()
        self._embed_services[k] = svc
        return svc

    def _check_keys(self, keys):
        for k in keys:
            if str(k) not in self._store and \
                    str(k) not in self._embeddings:
                raise MXNetError("key %s has not been initialized" % str(k))

    def _merge(self, vals):
        """Sum device replicas (reference: CommDevice::Reduce). All-rsp
        pushes stay row_sparse so the updater's lazy path applies
        (reference: CommCPU::ReduceRowSparse)."""
        from ..ndarray import sparse as _sp
        if isinstance(vals, nd.NDArray):
            return vals
        if len(vals) == 1:
            return vals[0]
        if all(isinstance(v, _sp.RowSparseNDArray) for v in vals):
            acc = vals[0]
            for v in vals[1:]:
                acc = _sp.elemwise_add(acc, v)
            return acc
        ctx = self._store_ctx_for(vals)
        acc = vals[0].as_in_context(ctx)._read()
        for v in vals[1:]:
            acc = acc + v.as_in_context(ctx)._read()
        return nd.from_jax(acc, ctx=ctx)

    @staticmethod
    def _store_ctx_for(vals):
        return vals[0].context

    def push(self, key, value, priority=0):
        """Merge (sum) the pushed device values per key. Without an updater
        the merged value REPLACES the store; with an updater the store holds
        weights and the updater applies the merged gradient (reference:
        KVStoreLocal::PushImpl — updater_ path vs CopyFromTo path).

        Multi-key dense pushes ride the bucketed engine (`mx.engine`): one
        fused flatten->sum->unflatten program per size-capped bucket instead
        of one merge program per key. `MXNET_TPU_COMM_BUCKET_MB=0` restores
        the per-key path."""
        from ..resilience import faults as _faults
        keys = _key_list(key)
        values = _val_list(value, len(keys))
        assert len(keys) == len(values), "key/value length mismatch"
        self._check_keys(keys)
        if _telem.ENABLED:
            _record_comm("push", values)
        if self._embeddings:
            keys, values = self._push_embeddings(keys, values)
            if not keys:
                return
        if self._maybe_push_zero(keys, values):
            return
        cap = _engine.bucket_bytes()
        if cap and len(keys) > 1:
            entries = self._bucketable_entries(keys, values)
            if entries is not None:
                self._push_bucketed(entries, cap)
                return
            sentries = self._sparse_entries(keys, values)
            if sentries is not None:
                self._push_sparse_bucketed(sentries, cap)
                return
        inject = _faults.active_plan() is not None
        for k, v in zip(keys, values):
            # per-key comm span (the escape-hatch analog of the per-bucket
            # span): with bucketing off, N of these per step are the
            # serialized launches attribution's overlap profiler indicts
            ts = _telem.span_clock()
            t0 = time.perf_counter()
            merged = self._merge(v if isinstance(v, (list, tuple)) else [v])
            _telem.record_span(_engine.comm_span_name(str(k), "key"),
                               _engine.SPAN_CAT_COMM, ts,
                               time.perf_counter() - t0)
            k = str(k)
            stored = self._store[k]
            _telem.inc("comm.collectives")
            if inject:
                # injection-only site (no retry: the updater below mutates
                # the store, so replaying a half-applied push is NOT
                # idempotent — recovery happens one level up via
                # restore-and-replay); context formatting gated so the
                # no-plan hot path pays nothing
                _faults.check("kvstore.push",
                              context="key=%s shard=%s"
                              % (k, tuple(merged.shape)))
            if self._updater is not None:
                idx = int(k) if k.isdigit() else k
                self._updater(idx, merged, stored)
            else:
                stored._write(merged.as_in_context(
                    stored.context)._read().astype(stored.dtype))

    # -- ZeRO weight-update sharding path -------------------------------
    def _maybe_push_zero(self, keys, values):
        """Route a push through the ZeRO-1 sharded updater when one is
        set: local replica merge per key, then ONE `ZeroUpdater.step` over
        the full key set — reduce-scatter / fused shard update /
        all-gather at bucket granularity, the store ending with the
        all-gathered full weights. Returns True when handled."""
        from ..optimizer.zero import ZeroUpdater
        if not isinstance(self._updater, ZeroUpdater):
            return False
        entries = self._bucketable_entries(keys, values)
        if entries is None:
            raise MXNetError(
                "ZeRO sharded update requires dense gradients with a "
                "uniform replica count (keys %s)" % (keys,))
        zkeys, grads, weights = [], [], []
        for k, vals in entries:
            zkeys.append(k)
            grads.append(self._merge(vals)._read())
            weights.append(self._store[k])
        self._updater.step(zkeys, grads, weights)
        return True

    # -- bucketed engine path -------------------------------------------
    def _bucketable_entries(self, keys, values):
        """[(str key, [dense replica NDArrays])] when every key is dense
        with a uniform replica count — the precondition for packing into
        flat buckets; None sends the call down the per-key path."""
        from ..ndarray import sparse as _sp
        entries, nrep = [], None
        for k, v in zip(keys, values):
            vals = list(v) if isinstance(v, (list, tuple)) else [v]
            if not vals or any(not isinstance(x, nd.NDArray)
                               or isinstance(x, _sp.BaseSparseNDArray)
                               for x in vals):
                return None
            if nrep is None:
                nrep = len(vals)
            elif len(vals) != nrep:
                return None
            entries.append((str(k), vals))
        return entries

    # -- sparse (row_sparse) bucketed path ------------------------------
    def _sparse_entries(self, keys, values):
        """[(str key, [RowSparseNDArray replicas])] when every key is
        row_sparse and none is a registered embedding — the precondition
        for the sparse bucketed path; None otherwise."""
        from ..ndarray import sparse as _sp
        entries = []
        for k, v in zip(keys, values):
            if str(k) in self._embeddings:
                return None
            vals = list(v) if isinstance(v, (list, tuple)) else [v]
            if not vals or any(not isinstance(x, _sp.RowSparseNDArray)
                               for x in vals):
                return None
            entries.append((str(k), vals))
        return entries

    def _sparse_sync(self, key, ids, vals, shape):
        """Cross-worker completion of a locally-merged sparse push —
        identity on the local store (one worker owns every replica). The
        dist store overrides this with the unique-rows exchange. Returns
        the (ids, vals) of the globally-merged rows."""
        return ids, vals

    def _apply_sparse(self, k, ids, vals, shape):
        """Updater/store-write leg for one globally-merged sparse key."""
        from ..ndarray import sparse as _sp
        stored = self._store[k]
        merged = _sp.RowSparseNDArray(vals, ids, shape, ctx=stored.context)
        if self._updater is not None:
            idx = int(k) if k.isdigit() else k
            self._updater(idx, merged, stored)
        else:
            stored._write(merged.as_in_context(
                stored.context)._read().astype(stored.dtype))

    def _push_sparse_bucketed(self, entries, cap):
        """Bucketed sparse push (ISSUE 17 tentpole part 3): per-key local
        replica merge (dedup — the `merge_rows` canonicalization), then
        size-capped `SparseGradBucketer` buckets launched as they fill,
        each retried AS A UNIT in store-replace mode with the existing
        `kvstore.push` fault sites firing per key. Bucket bytes count
        TOUCHED rows, not table rows; `comm.sparse.*` counters feed
        `parse_log --sparse`."""
        from ..resilience import faults as _faults
        from ..resilience.retry import call_with_retry
        use_faults = _faults.active_plan() is not None
        shapes = {}

        def apply_bucket(bucket):
            ts = _telem.span_clock()
            t0 = time.perf_counter()
            for k, ids, vals in zip(bucket.keys, bucket.ids, bucket.vals):
                if use_faults:
                    _faults.check(
                        "kvstore.push",
                        context="key=%s bucket=[%s] sparse"
                        % (k, bucket.key_range()))
                gids, gvals = self._sparse_sync(k, ids, vals, shapes[k])
                self._apply_sparse(k, gids, gvals, shapes[k])
            _telem.record_span(bucket.span_name(), _engine.SPAN_CAT_COMM,
                               ts, time.perf_counter() - t0)

        retriable = self._updater is None and use_faults

        def dispatch(bucket):
            if not retriable:
                return apply_bucket(bucket)
            call_with_retry(
                apply_bucket, bucket, site="kvstore.push",
                context="sparse bucket keys=[%s] %dB"
                % (",".join(bucket.keys), bucket.nbytes))

        bucketer = _engine.SparseGradBucketer(cap)
        for k, vals in entries:
            merged = self._merge(vals)
            shapes[k] = merged.shape
            if _telem.ENABLED:
                _telem.inc("comm.sparse.push")
                _telem.inc("comm.sparse.rows",
                           sum(int(v._indices.shape[0]) for v in vals))
                _telem.inc("comm.sparse.unique_rows",
                           int(merged._indices.shape[0]))
            for bucket in bucketer.add(k, merged._indices, merged._values):
                dispatch(bucket)
        tail = bucketer.flush()
        if tail is not None:
            dispatch(tail)

    # -- sharded-embedding routing --------------------------------------
    def _push_embeddings(self, keys, values):
        """Apply pushes destined for registered sharded tables (row_sparse
        grads -> `ShardedEmbedding.apply_grads` on the owned rows) and
        return the remaining (keys, values) for the normal path."""
        from ..ndarray import sparse as _sp
        rest_k, rest_v = [], []
        for k, v in zip(keys, values):
            table = self._embeddings.get(str(k))
            if table is None:
                rest_k.append(k)
                rest_v.append(v)
                continue
            vals = list(v) if isinstance(v, (list, tuple)) else [v]
            if any(not isinstance(x, _sp.RowSparseNDArray) for x in vals):
                raise MXNetError(
                    "push to sharded embedding key %s requires row_sparse "
                    "gradients" % k)
            merged = self._merge(vals)
            if _telem.ENABLED:
                _telem.inc("comm.sparse.push")
                _telem.inc("comm.sparse.rows",
                           sum(int(x._indices.shape[0]) for x in vals))
                _telem.inc("comm.sparse.unique_rows",
                           int(merged._indices.shape[0]))
            table.apply_grads(merged._indices, merged._values)
            svc = self._embed_services.get(str(k))
            if svc is not None:
                svc.refresh()   # serve reads a consistent post-step snapshot
        return rest_k, rest_v

    def _launch_bucket_merge(self, bucket, raw_slots, nrep):
        """ONE fused flatten->sum(replicas)->unflatten program for the
        bucket (reference: CommDevice::Reduce, but one launch per bucket
        rather than per key). Returns the per-key merged raw arrays.
        `raw_slots` holds per-key replica payloads captured BEFORE any
        store/out mutation — jax arrays are immutable, so a per-bucket
        retry replays on identical inputs even when outs alias the pushed
        values (pushpull)."""
        tag = "kv.local.sum%d" % nrep
        if nrep == 1:
            comm_fn = _engine._identity
        else:
            def comm_fn(*flats):
                acc = flats[0]
                for f in flats[1:]:
                    acc = acc + f
                return acc
        # integrity sentinel (MXNET_TPU_INTEGRITY=1): the fused program
        # also emits an all-finite scalar over the merged flat vector —
        # one reduction riding the launch the merge already pays for. A
        # trip raises DivergenceError HERE, before any store/updater
        # write sees the poisoned values.
        from ..resilience import integrity as _integrity
        sentinel = _integrity.enabled()
        fn = _engine.fused_bucket_fn(tag, comm_fn, bucket.shapes,
                                     bucket.dtype, n_slots=nrep,
                                     with_finite=sentinel)
        raws = []
        for r in range(nrep):
            for k in bucket.keys:
                raws.append(raw_slots[k][r])
        _telem.inc("comm.collectives")
        ts = _telem.span_clock()
        t0 = time.perf_counter()
        outs = fn(*raws)
        if sentinel:
            parts, fin = outs[:-1], outs[-1]
        else:
            parts = outs
        _telem.record_span(bucket.span_name(), _engine.SPAN_CAT_COMM,
                           ts, time.perf_counter() - t0)
        if sentinel:
            _integrity.check_scalar(fin, site="kvstore.bucket",
                                    keys=bucket.keys)
        return parts

    def _push_bucketed(self, entries, cap, outs=None):
        """Bucketed push (and fused pull when `outs` is given): buckets are
        launched as soon as they fill, so bucket N's program overlaps the
        packing of bucket N+1 under async dispatch. Per-key fault-site
        semantics are preserved: `kvstore.push` checks fire per key with the
        owning bucket named in the context, and (store-replace mode only —
        the updater path mutates and must not replay) each bucket retries
        as a unit on transient faults."""
        from ..resilience import faults as _faults
        from ..resilience.retry import call_with_retry
        out_map = dict(outs) if outs is not None else None
        nrep = len(entries[0][1])
        ctx = self._store_ctx_for(entries[0][1])
        use_faults = _faults.active_plan() is not None
        raw_slots = {}

        def apply_bucket(bucket):
            parts = self._launch_bucket_merge(bucket, raw_slots, nrep)
            for k, part in zip(bucket.keys, parts):
                if use_faults:
                    _faults.check(
                        "kvstore.push",
                        context="key=%s bucket=[%s]" % (k,
                                                        bucket.key_range()))
                stored = self._store[k]
                merged = nd.from_jax(part, ctx=ctx)
                if self._updater is not None:
                    idx = int(k) if k.isdigit() else k
                    self._updater(idx, merged, stored)
                else:
                    stored._write(merged.as_in_context(
                        stored.context)._read().astype(stored.dtype))
                if out_map is not None:
                    if use_faults:
                        # the fused pull keeps its own fault site; a pull
                        # fault here is recovered by the bucket-level retry
                        _faults.check(
                            "kvstore.pull",
                            context="key=%s bucket=[%s]"
                            % (k, bucket.key_range()))
                    src = self._store[k]
                    for t in out_map[k]:
                        src.copyto(t)

        retriable = self._updater is None and use_faults
        bucketer = _engine.GradBucketer(cap)

        def dispatch(bucket):
            if not retriable:
                return apply_bucket(bucket)
            call_with_retry(
                apply_bucket, bucket, site="kvstore.push",
                context="bucket keys=[%s] %dB"
                % (",".join(bucket.keys), bucket.nbytes))

        for k, vals in entries:
            raw_slots[k] = [v.as_in_context(ctx)._read() for v in vals]
            for bucket in bucketer.add(k, raw_slots[k][0]):
                dispatch(bucket)
        tail = bucketer.flush()
        if tail is not None:
            dispatch(tail)

    # -- readiness-ordered push (ISSUE 19) ------------------------------
    def ready_session(self, canonical_keys=None):
        """Open a readiness-ordered push session: the Trainer feeds
        per-key device gradients the moment each parameter's backward
        completes (`session.push`), comm launches ride the bucket
        assembly immediately (while backward still runs), and the
        store/updater application is deferred to `session.finish()` at
        step time. `canonical_keys` is the registration-order key
        sequence — the order the non-readiness path would feed — used to
        freeze layouts deterministically."""
        return ReadyPushSession(self, canonical_keys=canonical_keys)

    def _ready_ingest(self, sess, key, vals):
        """Capture one key's replica payloads for the readiness path;
        returns the raw array the bucket assembly packs. Local mode keeps
        every replica (the fused bucket merge sums them in one program,
        exactly like `_push_bucketed`)."""
        sess.raw_slots[key] = [v.as_in_context(sess.ctx)._read()
                               for v in vals]
        return sess.raw_slots[key][0]

    def _ready_launch(self, sess, bucket):
        """Launch one readiness bucket's comm program. Pure computation on
        immutable arrays — under async dispatch the work overlaps the rest
        of backward; nothing observable mutates until `_ready_apply`."""
        if sess.cap == 0 and len(bucket.keys) == 1:
            # per-key escape hatch, readiness-ordered: the comm.key[k]
            # span now reflects the true launch order (ISSUE 19 fix)
            k = bucket.keys[0]
            _telem.inc("comm.collectives")
            ts = _telem.span_clock()
            t0 = time.perf_counter()
            raws = sess.raw_slots[k]
            acc = raws[0]
            for r in raws[1:]:
                acc = acc + r
            _telem.record_span(_engine.comm_span_name(str(k), "key"),
                               _engine.SPAN_CAT_COMM, ts,
                               time.perf_counter() - t0)
            return [acc]
        return self._launch_bucket_merge(bucket, sess.raw_slots, sess.nrep)

    def _ready_apply(self, sess, bucket, parts):
        """Apply one launched readiness bucket at step time: per-key fault
        sites, updater/store writes, and the optional out broadcast —
        the same semantics as `_push_bucketed`'s apply, minus the launch
        (already in flight). Store-replace mode retries the bucket as a
        unit; the parts are immutable, so a replay is safe."""
        from ..resilience import faults as _faults
        from ..resilience.retry import call_with_retry
        use_faults = _faults.active_plan() is not None

        def apply_bucket():
            for k, part in zip(bucket.keys, parts):
                if use_faults:
                    _faults.check(
                        "kvstore.push",
                        context="key=%s bucket=[%s]" % (k,
                                                        bucket.key_range()))
                stored = self._store[k]
                merged = nd.from_jax(part, ctx=sess.ctx)
                if self._updater is not None:
                    idx = int(k) if k.isdigit() else k
                    self._updater(idx, merged, stored)
                else:
                    stored._write(merged.as_in_context(
                        stored.context)._read().astype(stored.dtype))
                if sess.out_map is not None:
                    if use_faults:
                        _faults.check(
                            "kvstore.pull",
                            context="key=%s bucket=[%s]"
                            % (k, bucket.key_range()))
                    src = self._store[k]
                    for t in sess.out_map[k]:
                        src.copyto(t)

        if self._updater is None and use_faults:
            call_with_retry(
                apply_bucket, site="kvstore.push",
                context="bucket keys=[%s] %dB"
                % (",".join(bucket.keys), bucket.nbytes))
        else:
            apply_bucket()

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        """Broadcast merged value to all outs (reference:
        KVStoreLocal::PullImpl → comm Broadcast). A resilience fault site
        ("kvstore.pull") with retry: local broadcast is idempotent, and the
        dist backend inherits this path for its replicated store."""
        from ..resilience import faults as _faults
        from ..resilience.retry import call_with_retry
        assert out is not None, "pull requires out="
        keys = _key_list(key)
        outs = _val_list(out, len(keys))
        self._check_keys(keys)
        if _telem.ENABLED:
            _record_comm("pull", outs)
        # this broadcast is a local copyto even for the dist store (its
        # replicas are reconciled at push time by the allreduce) — it cannot
        # fail transiently, so pay the retry wrapper and per-key context
        # formatting only when a fault plan makes it injectable
        use_retry = _faults.active_plan() is not None
        for k, o in zip(keys, outs):
            src = self._store[str(k)]
            targets = o if isinstance(o, (list, tuple)) else [o]
            if not use_retry:
                for t in targets:
                    src.copyto(t)
                continue
            context = "key=%s shard=%s" % (k, tuple(src.shape))

            def broadcast(src=src, targets=targets, context=context):
                _faults.check("kvstore.pull", context=context)
                for t in targets:
                    src.copyto(t)

            call_with_retry(broadcast, site="kvstore.pull", context=context)

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull: on the bucketed path the pull costs NOTHING
        extra — each bucket's merged parts write the store and broadcast to
        the outs in the same pass, so a whole grad-sync is one program per
        bucket (the reference needed engine dependency edges between push
        and pull ops to get this close)."""
        cap = _engine.bucket_bytes()
        keys = _key_list(key)
        # gradient compression (dist subclass) carries per-key residual
        # state — its pushes must stay per-key, same guard as dist push
        if cap and out is not None and len(keys) > 1 \
                and self._updater is None \
                and getattr(self, "_gc", None) is None:
            values = _val_list(value, len(keys))
            outs = _val_list(out, len(keys))
            entries = self._bucketable_entries(keys, values)
            out_entries = self._bucketable_entries(keys, outs)
            if entries is not None and out_entries is not None:
                self._check_keys(keys)
                if _telem.ENABLED:
                    _record_comm("push", values)
                    _record_comm("pull", outs)
                self._push_bucketed(entries, cap, outs=out_entries)
                return
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out=out, priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only requested rows (reference: KVStoreLocal
        RowSparsePull). Dense-backed: gathers rows by id."""
        assert out is not None and row_ids is not None
        keys = _key_list(key)
        outs = _val_list(out, len(keys))
        rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids]
        if len(rids) == 1 and len(keys) > 1:
            rids = rids * len(keys)
        self._check_keys(keys)
        from ..ndarray import sparse as _sp
        for k, o, r in zip(keys, outs, rids):
            svc = self._embed_services.get(str(k))
            targets = o if isinstance(o, (list, tuple)) else [o]
            if svc is not None:
                # sharded table: the pull is a compiled cross-shard gather
                # (fixed-bucket jit, warmed at init_embedding — steady
                # traffic never compiles)
                for t in targets:
                    rows = r.data_jax.astype("int32") if isinstance(
                        r, nd.NDArray) else _sp.jnp.asarray(r, dtype="int32")
                    rows = _sp.jnp.unique(rows)
                    if not isinstance(t, _sp.RowSparseNDArray):
                        raise ValueError(
                            "row_sparse_pull requires row_sparse outs "
                            "(reference kvstore restriction); got stype %s"
                            % t.stype)
                    t._values = svc.lookup(rows).astype(t.dtype)
                    t._indices = rows
                continue
            src = self._store[str(k)]
            for t in targets:
                rows = r.data_jax.astype("int32") if isinstance(
                    r, nd.NDArray) else _sp.jnp.asarray(r, dtype="int32")
                # sorted unique ids: the RowSparseNDArray invariant that
                # retain()'s searchsorted relies on
                rows = _sp.jnp.unique(rows)
                if isinstance(src, _sp.RowSparseNDArray):
                    gathered = _sp.retain(src, rows)
                    vals, idx = gathered._values, gathered._indices
                else:  # dense-backed store: plain row gather
                    vals, idx = src._read()[rows], rows
                if not isinstance(t, _sp.RowSparseNDArray):
                    raise ValueError(
                        "row_sparse_pull requires row_sparse outs "
                        "(reference kvstore restriction); got stype %s"
                        % t.stype)
                t._values = vals.astype(t.dtype)
                t._indices = idx


class ReadyPushSession:
    """One readiness-ordered grad-sync round (ISSUE 19).

    The Trainer opens a session before backward, feeds `push(key, vals)`
    from the autograd grad-ready hook the moment each parameter
    finalizes, and calls `finish()` at step time. Bucket assembly is a
    `ReadyScheduler`; each completed bucket LAUNCHES its comm program
    immediately (pure computation on immutable arrays — under async
    dispatch the collective overlaps the rest of backward) while every
    observable mutation (updater calls, store writes, out broadcasts) is
    deferred to `finish()`. That split is also the safety story: an
    abandoned or aborted session has changed nothing — the caller can
    always fall back to the registration-ordered path.

    Three modes, chosen from the store's updater:

    * plain store / local Updater — free-mode scheduler; buckets apply at
      finish in launch order (per-key fault sites + per-bucket retry
      semantics identical to `_push_bucketed`).
    * `ZeroUpdater` with a frozen layout — frozen-mode scheduler; each
      completed bucket's reduce-scatter launches during backward
      (`ZeroUpdater.scatter_ready`), and `finish()` runs the fused shard
      updates + pipelined all-gathers in completion order.
    * `ZeroUpdater` before the first step (no layout yet) — grads are
      buffered and replayed in canonical registration order at finish, so
      the layout freezes exactly as the registration path would (every
      rank, either policy: same layout).

    Cross-rank contract (dist stores): readiness order is DETERMINISTIC —
    the autograd tape fires grad-ready callbacks in reverse tape order,
    so workers running the same SPMD program produce the same arrival
    order, hence identical free-mode bucket boundaries and identical
    collective launch order (the same identical-replica contract the
    frozen-layout and compression paths already assert). `finish()`
    verifies the pushed key set against `canonical_keys` as the guard.
    """

    def __init__(self, store, canonical_keys=None):
        from ..optimizer.zero import ZeroUpdater
        self.store = store
        self.cap = _engine.bucket_bytes()
        self.canonical = (None if canonical_keys is None
                          else [str(k) for k in canonical_keys])
        self.raw_slots = {}
        self.nrep = None
        self.ctx = None
        self.out_map = None
        self.launched = []     # [(bucket, handle)] in launch order
        self.arrivals = []     # zero mode: [(spec, g_shard)]
        self.pushed = []       # str keys in readiness (arrival) order
        self.finished = False
        self._zero = isinstance(store._updater, ZeroUpdater)
        self._buffer = None
        if self._zero:
            layout = store._updater.layout
            if layout is None:
                self._sched = None
                self._buffer = {}
            else:
                self._sched = _engine.ReadyScheduler(
                    self._dispatch_zero, layout=layout)
        else:
            self._sched = _engine.ReadyScheduler(
                self._dispatch, cap_bytes=self.cap)

    def _dispatch(self, bucket, spec=None):
        self.launched.append((bucket, self.store._ready_launch(self,
                                                               bucket)))

    def _dispatch_zero(self, bucket, spec):
        flat_g = _engine.pack_flat(spec, bucket.raws)
        g_shard = self.store._updater.scatter_ready(
            spec, flat_g, self.store._store)
        self.arrivals.append((spec, g_shard))

    def push(self, key, vals):
        """Feed one parameter's per-device gradients in readiness order
        (during backward). Launches whatever buckets just completed."""
        from ..ndarray import sparse as _sp
        k = str(key)
        vals = list(vals) if isinstance(vals, (list, tuple)) else [vals]
        if not vals or any(not isinstance(v, nd.NDArray)
                           or isinstance(v, _sp.BaseSparseNDArray)
                           for v in vals):
            raise MXNetError(
                "readiness push requires dense NDArray gradients (key %s)"
                % (key,))
        if self.nrep is None:
            self.nrep = len(vals)
            self.ctx = self.store._store_ctx_for(vals)
        elif len(vals) != self.nrep:
            raise MXNetError(
                "readiness push saw %d replicas for key %s, expected %d"
                % (len(vals), key, self.nrep))
        if _telem.ENABLED:
            _record_comm("push", [vals])
        self.pushed.append(k)
        if self._buffer is not None:
            self._buffer[k] = vals     # zero, first step: no early launch
            return
        if self._zero:
            raw = self.store._merge(vals)._read()
            self.raw_slots[k] = [raw]
        else:
            raw = self.store._ready_ingest(self, k, vals)
        self._sched.add(k, raw)

    def finish(self, outs=None):
        """Complete the round at step time: drain the tail buckets, then
        apply every launched bucket (updater/store writes, pulls) in
        launch order — or, for ZeRO, run the update + pipelined
        all-gather legs. `outs` is [(key, [targets])] for the fused
        pushpull flow (store-replace mode only)."""
        if self.finished:
            raise MXNetError("ReadyPushSession.finish() called twice")
        self.finished = True
        store = self.store
        if self._buffer is not None:
            order = self.canonical if self.canonical is not None \
                else list(self._buffer)
            keys = [k for k in order if k in self._buffer]
            if len(keys) != len(self._buffer):
                raise MXNetError(
                    "readiness round pushed keys outside the canonical "
                    "order (%s vs %s)" % (sorted(self._buffer),
                                          sorted(order)))
            store._maybe_push_zero(keys, [self._buffer[k] for k in keys])
            return
        self._sched.drain()   # frozen mode raises on missing members
        if self._zero:
            store._updater.finish_ready(self.arrivals, store._store)
            return
        if outs is not None:
            self.out_map = {str(k): targets for k, targets in outs}
            if _telem.ENABLED:
                _record_comm("pull", [t for _, t in outs])
        for bucket, handle in self.launched:
            store._ready_apply(self, bucket, handle)


def create(name="local"):
    """Factory. reference: python/mxnet/kvstore/kvstore.py (create)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    if name in ("local", "local_allreduce_cpu", "local_allreduce_device",
                "device", "nccl"):
        return KVStoreLocal("device" if name in ("device", "nccl") else
                            "local")
    if name.startswith("dist"):
        from .kvstore_dist import KVStoreDist
        return KVStoreDist(name)
    raise ValueError("unknown KVStore type %s" % name)

"""DataLoader: mini-batch loading with worker processes.

TPU-native analog of reference python/mxnet/gluon/data/dataloader.py. The
reference forks workers that return batches through POSIX-shm `cpu_shared`
NDArrays (src/storage/cpu_shared_storage_manager.h); here workers are a
multiprocessing pool shipping numpy batches (pickled over pipes; the native
C++ fast path lives in mxnet_tpu/native with shared-memory framing), and
the final host→device transfer is PjRt's async H2D — the analog of the
reference's pinned-memory prefetch.
"""
from __future__ import annotations

import contextlib
import multiprocessing
import os
import sys

import numpy as _np

from ... import ndarray as nd
from ... import telemetry as _telem
from ...context import Context, cpu
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Collate samples into a batch. reference: dataloader.py
    (default_batchify_fn)."""
    if isinstance(data[0], nd.NDArray):
        return nd.stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = _np.asarray(data)
    return nd.array(data, dtype=data.dtype)


def default_mp_batchify_fn(data):
    """Worker-side collate (numpy; shipped to the main process).
    reference: dataloader.py (default_mp_batchify_fn) — uses cpu_shared
    NDArrays; the numpy path here serializes via pickle, the C++ native
    loader uses shm."""
    if isinstance(data[0], nd.NDArray):
        # stack ON DEVICE, then ONE device→host copy for the whole batch —
        # a per-sample .asnumpy() loop here costs one forced sync per
        # sample (len(data)-1 saved syncs, counted below)
        batch = nd.stack(*data, axis=0).asnumpy()
        _telem.inc("dataloader.batchify.syncs_saved", len(data) - 1)
        return batch
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_mp_batchify_fn(i) for i in data]
    return _np.asarray(data)


_worker_dataset = None


def _worker_initializer(dataset):
    global _worker_dataset
    _worker_dataset = dataset


@contextlib.contextmanager
def _cpu_only_children():
    """Spawn children with JAX_PLATFORMS=cpu. The parent owns the chip, and
    a chip belongs to one process: a worker that initialised the TPU
    backend would fail or hang. A spawned child takes os.environ as it is
    at start, which is before it unpickles a dataset that may import jax;
    a pool initializer runs after. The parent's own value is put back."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


class _ShmBatch:
    """A batch living in POSIX shared memory: (name, shape, dtype) per
    array + the nesting structure. The pickled payload is ~100 bytes
    regardless of batch size — the zero-copy design point of the
    reference's cpu_shared storage manager
    (src/storage/cpu_shared_storage_manager.h)."""
    __slots__ = ("descs", "fmt")

    def __init__(self, descs, fmt):
        self.descs = descs
        self.fmt = fmt


def _flatten_np(batch):
    if isinstance(batch, _np.ndarray):
        return [batch], 0
    if isinstance(batch, (list, tuple)):
        arrays, fmt = [], []
        for b in batch:
            a, f = _flatten_np(b)
            arrays.extend(a)
            fmt.append(f)
        return arrays, fmt
    raise TypeError("shm transport expects numpy batches, got %s"
                    % type(batch))


def _regroup_np(arrays, fmt, pos=0):
    if fmt == 0:
        return arrays[pos], pos + 1
    out = []
    for f in fmt:
        item, pos = _regroup_np(arrays, f, pos)
        out.append(item)
    return out, pos


def _batch_to_shm(batch):
    """Worker side: copy each array once into a fresh shm segment. The
    worker unregisters from its resource tracker — ownership transfers to
    the parent, which unlinks after the device upload."""
    from multiprocessing import shared_memory, resource_tracker
    arrays, fmt = _flatten_np(batch)
    descs = []
    for a in arrays:
        a = _np.ascontiguousarray(a)
        shm = shared_memory.SharedMemory(create=True, size=max(1, a.nbytes))
        _np.ndarray(a.shape, a.dtype, buffer=shm.buf)[...] = a
        try:  # the parent owns the segment's lifetime now
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass
        descs.append((shm.name, a.shape, str(a.dtype)))
        shm.close()
    return _ShmBatch(descs, fmt)


def _discard_shm(sb):
    """Unlink a batch's segments without reading them."""
    from multiprocessing import shared_memory
    for name, _, _ in sb.descs:
        try:
            shm = shared_memory.SharedMemory(name=name)
            shm.close()
            shm.unlink()
        except Exception:
            pass


def _batch_from_shm(sb, ctx):
    """Parent side: map each segment and realize the array before
    unlinking. On an accelerator the device upload reads straight from the
    shared pages (no host-to-host copy, wait for H2D then unlink); the CPU
    backend may ALIAS host buffers, so there the view is copied out first
    — unmapping aliased pages is a use-after-free."""
    from multiprocessing import shared_memory
    arrays = []
    for name, shape, dtype in sb.descs:
        shm = shared_memory.SharedMemory(name=name)
        view = _np.ndarray(shape, _np.dtype(dtype), buffer=shm.buf)
        if ctx.device_type == "cpu":
            arr = nd.array(view.copy(), ctx=ctx, dtype=view.dtype)
        else:
            arr = nd.array(view, ctx=ctx, dtype=view.dtype)
            arr.wait_to_read()
        arrays.append(arr)
        shm.close()
        shm.unlink()
    out, _ = _regroup_np(arrays, sb.fmt)
    return out


def _worker_fn(samples, batchify_fn, use_shm=False):
    global _worker_dataset
    batch = batchify_fn([_worker_dataset[i] for i in samples])
    if use_shm:
        try:
            return _batch_to_shm(batch)
        except TypeError:
            pass  # non-numpy batchify output: pickle path
    return batch


def _np_mode_tag(data):
    """Under npx.set_np() delivered batches are mx.np.ndarray (reference:
    np-mode DataLoader). Batches are loader-owned fresh arrays, so the
    in-place retag is safe."""
    from ...numpy_extension import is_np_array
    if not is_np_array():
        return data
    from ...numpy.multiarray import as_np_ndarray
    return as_np_ndarray(data)


def _as_in_context(data, ctx):
    if isinstance(data, nd.NDArray):
        return _np_mode_tag(data.as_in_context(ctx))
    if isinstance(data, _np.ndarray):
        return _np_mode_tag(nd.array(data, ctx=ctx, dtype=data.dtype))
    if isinstance(data, (list, tuple)):
        return [_as_in_context(d, ctx) for d in data]
    return data


class DataLoader:
    """reference: gluon/data/dataloader.py (DataLoader)."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, timeout=120):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        self._timeout = timeout
        assert timeout > 0, "timeout must be positive"

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless " +
                                 "batch_sampler is specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(
                sampler, batch_size, last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")

        self._batch_sampler = batch_sampler
        self._num_workers = num_workers if num_workers >= 0 else 0
        self._prefetch = max(0, int(prefetch) if prefetch is not None else
                             2 * self._num_workers)
        if batchify_fn is None:
            if num_workers > 0:
                self._batchify_fn = default_mp_batchify_fn
            else:
                self._batchify_fn = default_batchify_fn
        else:
            self._batchify_fn = batchify_fn
        self._pool = None
        if self._num_workers > 0:
            if thread_pool:
                from multiprocessing.dummy import Pool as ThreadPool
                self._pool = ThreadPool(self._num_workers,
                                        initializer=_worker_initializer,
                                        initargs=(self._dataset,))
            else:
                # spawn, not fork: the parent holds a live multithreaded JAX
                # runtime, and forking it risks deadlock in the child (the
                # suite used to warn on every multiworker test). Fresh
                # interpreters also never inherit the parent's TPU handle —
                # workers are numpy-only by design (reference analog:
                # cpu_shared workers never own a CUDA context either).
                # spawn workers need a picklable dataset (fork inherited
                # closures for free; spawn cannot) — fail with a usable
                # message instead of a deep PicklingError at first batch
                import pickle
                try:
                    pickle.dumps(self._dataset)
                except Exception as e:
                    raise ValueError(
                        "DataLoader(num_workers>0) ships the dataset to "
                        "spawned worker processes, which requires it to be "
                        "picklable (%s). Use a module-level transform "
                        "function instead of a lambda, or pass "
                        "thread_pool=True." % e) from e
                ctx = multiprocessing.get_context("spawn")
                with _cpu_only_children():
                    self._pool = ctx.Pool(self._num_workers,
                                          initializer=_worker_initializer,
                                          initargs=(self._dataset,))

    def __iter__(self):
        if self._num_workers == 0:
            def same_process_iter():
                for batch in self._batch_sampler:
                    ret = self._batchify_fn(
                        [self._dataset[idx] for idx in batch])
                    yield _as_in_context(ret, cpu())
            return same_process_iter()
        return _MultiWorkerIter(self._pool, self._batchify_fn,
                                self._batch_sampler,
                                prefetch=self._prefetch,
                                timeout=self._timeout,
                                use_shm=not self._thread_pool)

    def __len__(self):
        return len(self._batch_sampler)

    def __del__(self):
        if self._pool is not None:
            self._pool.terminate()


class _MultiWorkerIter:
    """Prefetching iterator over the worker pool.
    reference: dataloader.py (_MultiWorkerIter)."""

    def __init__(self, pool, batchify_fn, batch_sampler, prefetch=0,
                 timeout=120, use_shm=False):
        self._pool = pool
        self._batchify_fn = batchify_fn
        self._batch_sampler = batch_sampler
        self._use_shm = use_shm
        self._data_buffer = {}
        self._rcvd_idx = 0
        self._sent_idx = 0
        self._iter = iter(self._batch_sampler)
        self._timeout = timeout
        for _ in range(prefetch):
            self._push_next()

    def __len__(self):
        return len(self._batch_sampler)

    def _push_next(self):
        r = next(self._iter, None)
        if r is None:
            return
        async_ret = self._pool.apply_async(
            _worker_fn, (r, self._batchify_fn, self._use_shm))
        self._data_buffer[self._sent_idx] = async_ret
        self._sent_idx += 1

    def __next__(self):
        self._push_next()
        if self._rcvd_idx == self._sent_idx:
            assert not self._data_buffer, \
                "Data buffer should be empty at this moment"
            raise StopIteration
        assert self._rcvd_idx < self._sent_idx, \
            "rcvd_idx must be smaller than sent_idx"
        assert self._rcvd_idx in self._data_buffer, \
            "fatal error in _push_next, rcvd_idx missing"
        ret = self._data_buffer.pop(self._rcvd_idx)
        batch = ret.get(self._timeout)
        self._rcvd_idx += 1
        if isinstance(batch, _ShmBatch):
            return _np_mode_tag(_batch_from_shm(batch, cpu()))
        return _as_in_context(batch, cpu())

    def __del__(self):
        # an abandoned iterator still owns its prefetched shm segments
        # (workers unregistered them from their resource trackers): drain
        # and unlink or they outlive the process in /dev/shm
        try:
            for ret in self._data_buffer.values():
                try:
                    batch = ret.get(1)
                except Exception:
                    continue
                if isinstance(batch, _ShmBatch):
                    _discard_shm(batch)
        except Exception:
            pass

    def next(self):
        return self.__next__()

    def __iter__(self):
        return self

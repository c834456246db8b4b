"""Device contexts.

TPU-native analog of the reference's `Context` (reference: include/mxnet/base.h
(Context), python/mxnet/context.py). Device types keep the reference's integer
codes and add kTPU; every Context resolves to a concrete `jax.Device`.

On this stack a "gpu" context is an alias for the accelerator (TPU) so that
reference scripts written as `mx.gpu(0)` run unchanged.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
           "num_gpus", "num_tpus", "current_context"]


class Context:
    """Device context. reference: include/mxnet/base.h (Context struct)."""

    # reference device-type codes (DEV_MASK values) + new kTPU
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # ---- jax resolution ------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        tpu()/gpu() raise MXNetError when this process has no accelerator or
        the id is past its last one (the reference raises on `mx.gpu()`
        without a GPU too). cpu(i) ids are labels on one host and wrap.
        """
        return _resolve_device(self.device_type, self.device_id)

    def empty_cache(self):
        """reference: Context::empty_cache / MXStorageEmptyCache. XLA's
        allocator pools buffers internally; live-buffer GC is automatic."""
        return None


def _accel_devices():
    # local (addressable) devices only: under the multi-controller runtime
    # each process owns its slice of the pod; committing data to another
    # process's device is invalid (reference analog: a worker only touches
    # its own GPUs)
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def _cpu_devices():
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:
        return []


def _resolve_device(device_type, device_id):
    if device_type in ("gpu", "tpu"):
        devs = _accel_devices()
        if not 0 <= device_id < len(devs):
            from .base import MXNetError
            raise MXNetError(
                "%s(%d): this process has %d accelerator device(s); jax "
                "found platforms %s"
                % (device_type, device_id, len(devs),
                   sorted({d.platform for d in jax.local_devices()})))
        return devs[device_id]
    devs = _cpu_devices()
    if devs:
        return devs[device_id % len(devs)]
    return jax.local_devices()[0]


def cpu(device_id=0):
    """reference: python/mxnet/context.py (cpu)."""
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    """Pinned host memory. PjRt H2D transfers stage internally; alias of cpu."""
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id=0):
    """POSIX-shm storage for DataLoader workers in the reference; alias of cpu."""
    return Context("cpu_shared", device_id)


def gpu(device_id=0):
    """Accelerator context; on this stack an alias for the TPU so that
    reference `mx.gpu(i)` scripts run unchanged."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """The native device context of this framework (north star: `mx.tpu()`)."""
    return Context("tpu", device_id)


def num_gpus():
    """reference: python/mxnet/context.py (num_gpus). Counts this process's
    accelerators (local, like the reference's cudaGetDeviceCount)."""
    return len(_accel_devices())


def num_tpus():
    return num_gpus()


def current_context():
    """reference: python/mxnet/context.py (current_context) — thread-local
    `with ctx:` stack, default cpu(0)."""
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value

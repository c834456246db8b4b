"""`mx.util` — misc utilities (reference: python/mxnet/util.py)."""
from __future__ import annotations

import functools
import os

__all__ = ["large_tensor_scope",
           "makedirs", "getenv", "setenv", "set_np", "reset_np",
           "is_np_array", "is_np_shape", "use_np", "np_array", "np_shape",
           "default_array", "atomic_write", "write_latest_marker",
           "read_latest_marker"]


def makedirs(d):
    os.makedirs(d, exist_ok=True)


# ---------------------------------------------------------------------------
# crash-safe file commit — shared by the checkpoint layers
# (parallel.checkpoint LATEST marker, resilience.run SnapshotCheckpointer)
# ---------------------------------------------------------------------------
def atomic_write(path, data):
    """Write `data` (bytes) to `path` via tmp + fsync + os.replace: a crash
    at any point leaves the previous content or the new one, never a torn
    file."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def write_latest_marker(root, step):
    """Commit `root`/LATEST naming the newest fully-durable checkpoint
    step. Call strictly AFTER the step's payload is on disk."""
    atomic_write(os.path.join(root, "LATEST"), ("%d\n" % int(step)).encode())


def read_latest_marker(root):
    """The step named by `root`/LATEST, or None (missing/corrupt marker —
    callers fall back to a directory scan; a lost marker never loses
    checkpoints)."""
    try:
        with open(os.path.join(root, "LATEST")) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


def getenv(name):
    return os.environ.get(name)


def setenv(name, value):
    os.environ[name] = value


# np-mode switches delegate to the npx module (reference: util.set_np etc.)
def set_np(shape=True, array=True):
    from . import numpy_extension as npx
    npx.set_np(shape=shape, array=array)


def reset_np():
    from . import numpy_extension as npx
    npx.reset_np()


def is_np_array():
    from . import numpy_extension as npx
    return npx.is_np_array()


def is_np_shape():
    from . import numpy_extension as npx
    return npx.is_np_shape()


class _NpScope:
    """Context/decorator setting np semantics inside (reference:
    util.np_array / np_shape scopes). `array`/`shape` are the target flag
    values inside the scope — False turns a mode OFF, None leaves it
    unchanged."""

    def __init__(self, array=None, shape=None):
        self._array, self._shape = array, shape

    def __enter__(self):
        from . import numpy_extension as npx
        self._saved = (npx.is_np_shape(), npx.is_np_array())
        npx.set_np(
            shape=self._saved[0] if self._shape is None else self._shape,
            array=self._saved[1] if self._array is None else self._array)
        return self

    def __exit__(self, *exc):
        from . import numpy_extension as npx
        npx.set_np(shape=self._saved[0], array=self._saved[1])
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with type(self)(self._array, self._shape):
                return fn(*args, **kwargs)
        return wrapper


def np_array(active=True):
    return _NpScope(array=bool(active), shape=None)


def np_shape(active=True):
    return _NpScope(array=None, shape=bool(active))


def use_np(fn):
    """Decorator: run `fn` under both np shape and array semantics."""
    return _NpScope(array=True, shape=True)(fn)


def default_array(source, ctx=None, dtype=None):
    """array() in whichever namespace is active (reference:
    util.default_array)."""
    if is_np_array():
        from . import numpy as np_ns
        return np_ns.array(source, dtype=dtype, ctx=ctx)
    from .ndarray import array
    return array(source, ctx=ctx, dtype=dtype)


import contextlib


@contextlib.contextmanager
def large_tensor_scope():
    """64-bit tensor indexing scope (reference: the
    MXNET_INT64_TENSOR_SIZE build flag — large-tensor support is opt-in
    upstream too). Inside the scope, index arithmetic is 64-bit, so
    writes/gathers/argmax past the 2^31 element boundary are exact.
    Kept scoped rather than global because x64 also flips jax's DEFAULT
    dtypes (python floats become float64), which the TPU-native bf16/f32
    path does not want."""
    import jax
    with jax.enable_x64(True):
        yield

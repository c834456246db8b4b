"""Base types, dtype mapping, and the env-flag catalog.

TPU-native analog of the reference's `include/mxnet/base.h` + `dmlc::GetEnv`
env-var system (see SURVEY.md §5.6: reference reads `MXNET_*` flags ad hoc via
`dmlc::GetEnv`; catalog in docs/.../env_var.md). Here the catalog is explicit.
"""
from __future__ import annotations

import os

import numpy as _np

# ---------------------------------------------------------------------------
# Version / feature identity
# ---------------------------------------------------------------------------
__version__ = "2.0.0.dev0"  # reference fork tracks MXNet 1.x; we are a rebuild

# ---------------------------------------------------------------------------
# dtype registry — mirrors the reference's mshadow type codes
# (reference: 3rdparty/mshadow/mshadow/base.h TypeFlag)
# ---------------------------------------------------------------------------
_DTYPE_NP_TO_MX = {
    _np.dtype(_np.float32): 0,
    _np.dtype(_np.float64): 1,
    _np.dtype(_np.float16): 2,
    _np.dtype(_np.uint8): 3,
    _np.dtype(_np.int32): 4,
    _np.dtype(_np.int8): 5,
    _np.dtype(_np.int64): 6,
    _np.dtype(_np.bool_): 7,
}
_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}
# bfloat16 is TPU-native; the reference gained it late (mshadow bfloat16).
try:  # ml_dtypes always ships with jax
    import ml_dtypes as _ml_dtypes

    bfloat16 = _np.dtype(_ml_dtypes.bfloat16)
    _DTYPE_NP_TO_MX[bfloat16] = 12
    _DTYPE_MX_TO_NP[12] = bfloat16
except Exception:  # pragma: no cover
    bfloat16 = None


_CANONICAL_64 = {  # TPU-first: 32-bit canonical types (jax x64 disabled)
    _np.dtype(_np.int64): _np.dtype(_np.int32),
    _np.dtype(_np.uint64): _np.dtype(_np.uint32),
    _np.dtype(_np.float64): _np.dtype(_np.float32),
    _np.dtype(_np.complex128): _np.dtype(_np.complex64),
}


def x64_enabled():
    """True inside mx.util.large_tensor_scope() (jax x64 on) — the single
    gate every 64-bit-index decision keys off."""
    try:
        import jax
        return bool(jax.config.jax_enable_x64)
    except Exception:
        return False


def np_dtype(dtype):
    """Normalize any dtype-like (str, np.dtype, jax dtype) to np.dtype.

    64-bit types canonicalize to their 32-bit counterparts (XLA x64 mode
    is off by design: the MXU is a 32/16-bit engine) — EXCEPT inside
    `mx.util.large_tensor_scope()`, where jax x64 is enabled and 64-bit
    index types are the point (reference: the opt-in
    MXNET_INT64_TENSOR_SIZE build)."""
    if dtype is None:
        return _np.dtype(_np.float32)
    if isinstance(dtype, str) and dtype == "bfloat16" and bfloat16 is not None:
        return bfloat16
    dt = _np.dtype(dtype)
    if dt in _CANONICAL_64:
        return dt if x64_enabled() else _CANONICAL_64[dt]
    return dt


# ---------------------------------------------------------------------------
# Env-flag catalog (reference: MXNET_* vars via dmlc::GetEnv)
# Single place where every supported flag is declared, typed, and documented.
# ---------------------------------------------------------------------------
_ENV_CATALOG = {}


def register_env(name, default, typ, doc):
    _ENV_CATALOG[name] = (default, typ, doc)
    return name


def get_env(name, default=None):
    """Typed env lookup against the catalog (reference: dmlc::GetEnv)."""
    if name in _ENV_CATALOG:
        cat_default, typ, _ = _ENV_CATALOG[name]
        raw = os.environ.get(name)
        if raw is None:
            return cat_default if default is None else default
        if typ is bool:
            return raw.lower() not in ("0", "false", "off", "")
        return typ(raw)
    raw = os.environ.get(name)
    return default if raw is None else raw


def env_catalog():
    """The full documented flag catalog (reference: docs env_var.md)."""
    return dict(_ENV_CATALOG)


register_env("MXNET_ENGINE_TYPE", "AsyncEngine", str,
             "AsyncEngine (jax async dispatch) or NaiveEngine (block after every op; "
             "reference: MXNET_ENGINE_TYPE=NaiveEngine serialized debugging mode).")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", True, bool,
             "Kept for API compat; XLA fuses whole jitted graphs so bulking is implicit.")
register_env("MXNET_SAFE_ACCUMULATION", True, bool,
             "Accumulate reductions of fp16/bf16 in fp32 (reference: MXNET_SAFE_ACCUMULATION).")
register_env("MXNET_DEFAULT_DTYPE", "float32", str,
             "Default dtype for array creation.")
register_env("MXNET_OPTIMIZER_AGGREGATION_SIZE", 4, int,
             "Multi-tensor (fused) optimizer update group size in Trainer; "
             "0 disables aggregation (reference: optimizer_op.cc multi_sgd).")
register_env("MXNET_TPU_USE_PALLAS", True, bool,
             "Pallas kernels on TPU. Unset: the registry's per-op tpu_impls are on, the "
             "flat optimizer (ops/fused_optimizer.py) and segment-sum (ops/sparse_ops.py) "
             "kernels are off; 1 turns those on too, 0 turns all of them off. The flash "
             "attention kernels do not read it.")
register_env("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000, int,
             "Kept for API compat (reference sharded big arrays across PS servers).")
register_env("MXNET_PROFILER_AUTOSTART", False, bool,
             "Start the profiler at import (reference: MXNET_PROFILER_AUTOSTART).")
register_env("MXNET_TPU_WHOLE_GRAPH", True, bool,
             "Lower bound Symbol graphs to ONE compiled program (constant folding/CSE/DCE "
             "at graph level, then a single XLA executable) instead of op-by-op dispatch; "
             "unsupported graphs fall back op-by-op with a counted reason, never erroring.")
register_env("MXNET_TPU_AOT_CACHE", "", str,
             "Directory for the persistent AOT executable cache (compiled whole-graph/"
             "serve/train-step programs serialized across processes); empty disables.")
register_env("MXNET_TPU_AOT_CACHE_KEEP", 32, int,
             "AOT cache retention: keep the newest N entries (oldest-mtime evicted).")


class MXNetError(RuntimeError):
    """Framework error type (reference: dmlc::Error surfaced via MXGetLastError)."""


def check_call(ok, msg=""):
    if not ok:
        raise MXNetError(msg)


# Naive-engine (fully synchronous) mode: reference's MXNET_ENGINE_TYPE=NaiveEngine.
def is_naive_engine():
    return get_env("MXNET_ENGINE_TYPE") == "NaiveEngine"


_int64_enabled = True


def numeric_types():
    return (int, float, _np.integer, _np.floating)

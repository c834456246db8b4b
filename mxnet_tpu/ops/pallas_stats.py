"""Pallas kernel-layer shared utilities: dispatch observability — the
counters/spans that make the kernel layer auditable (ISSUE 10 tentpole
part 3) — plus the Mosaic compiler params every kernel module needs.

Every Pallas kernel call site in the ops layer reports through here:

* ``ops.pallas.dispatch`` (+ ``ops.pallas.dispatch.<kernel>``) counts each
  decision to run a Pallas kernel;
* ``ops.pallas.fallback`` (+ ``ops.pallas.fallback.<reason>``) counts each
  time the Pallas path was REQUESTED (gate on) but the shape/dtype gate sent
  the call to the XLA composite instead — fallbacks are counted, never
  errors, so an ineligible tensor silently gets the always-correct path;
* ``kernel_span(name)`` wraps a dispatch in a ``pallas.<name>`` telemetry
  span (cat ``kernel``) so chrome traces show which stages ran fused.

Counting context: eager call sites count once per call; sites inside a
``custom_vjp``/``jit`` trace (the flash kernels under a compiled train
step) count once per (re)trace — dispatches-per-program, not per step, the
same convention as `engine.reassociate_bucketed`. ``parse_log --kernels``
renders the table.
"""
from __future__ import annotations

import contextlib
import os
import time

import jax

__all__ = ["note_dispatch", "note_fallback", "note_chunk", "kernel_span",
           "compiler_params", "use_pallas", "interpret", "pallas_on"]


def interpret():
    """MXNET_FLASH_INTERPRET=1: every kernel runs through the Pallas
    interpreter (the CPU tests' arithmetic check)."""
    return os.environ.get("MXNET_FLASH_INTERPRET", "0") == "1"


def pallas_on():
    """The gate of the kernels that are on by default (flash attention, the
    grouped product of `ops/moe.py`), asked at every call: on the TPU and
    under the interpreter. A tool that compiles for a described chip from a
    CPU process turns it on here, for all of them at once."""
    if os.environ.get("MXNET_FLASH_DISABLE", "0") == "1":
        return False            # force the plain-XLA path (A/B probes)
    return interpret() or jax.default_backend() == "tpu"


def compiler_params(semantics, vmem_limit_bytes=None):
    """Mosaic compiler params carrying the grid's `dimension_semantics`
    and, where a kernel's tiles outgrow Mosaic's default, the VMEM it may
    use. Shared by fused_optimizer, sparse_ops and
    parallel/flash_attention."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


def use_pallas(unset):
    """MXNET_TPU_USE_PALLAS, read here and nowhere else. `unset` is the
    caller's answer where the variable is not set, and the callers differ:
    the registry's per-op `tpu_impl`s are on (True), the flat optimizer
    and segment-sum kernels are opt-in (False). ROADMAP S7 decides whether
    they should."""
    from ..base import get_env
    return get_env("MXNET_TPU_USE_PALLAS", unset)


def note_dispatch(kernel):
    """Count one Pallas kernel dispatch (total + per-kernel)."""
    from .. import telemetry as _telem
    if _telem.ENABLED:
        _telem.inc("ops.pallas.dispatch")
        _telem.inc("ops.pallas.dispatch.%s" % kernel)


def note_fallback(kernel, reason):
    """Count one gated-but-ineligible call routed to the XLA composite."""
    from .. import telemetry as _telem
    if _telem.ENABLED:
        _telem.inc("ops.pallas.fallback")
        _telem.inc("ops.pallas.fallback.%s" % reason)
        _telem.inc("ops.pallas.fallback.%s.%s" % (kernel, reason))


def note_chunk(rows):
    """Count one traced body of `ops/moe.py`'s loop over its buffer: the
    expert layer over a chunk of `rows` rows (`ops.moe.chunk.<rows>`)."""
    from .. import telemetry as _telem
    if _telem.ENABLED:
        _telem.inc("ops.moe.chunk.%d" % rows)


@contextlib.contextmanager
def kernel_span(kernel):
    """`pallas.<kernel>` telemetry span around a dispatch. Measures host
    wall time of the dispatch (eager: launch + any sync the caller does
    inside; traced: trace time) — perf evidence comes from the bench, the
    span is for WHICH-stage-ran-fused attribution."""
    from .. import telemetry as _telem
    if not _telem.ENABLED:
        yield
        return
    ts = _telem.span_clock()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _telem.record_span("pallas.%s" % kernel, "kernel", ts,
                           time.perf_counter() - t0)

"""The operator registry.

TPU-native analog of the reference's NNVM op registry (reference:
3rdparty/tvm/nnvm/include/nnvm/op.h (NNVM_REGISTER_OP), src/operator/*
(FCompute<xpu> attrs)). One registration per op; the `mx.nd` and `mx.sym`
namespaces are both code-generated from this table (reference:
python/mxnet/ndarray/register.py, python/mxnet/symbol/register.py), so an op
defined once is available imperatively, symbolically, and inside `hybridize()`
traces.

An op's `fn` operates on raw jax arrays (or tracers) and returns an array or a
tuple of arrays. Device dispatch (the reference's FCompute<cpu>/FCompute<gpu>/
FCompute<tpu> split) collapses to XLA: the same jax fn lowers to every
platform, with optional per-op Pallas overrides for TPU registered via
`tpu_impl` (the FCompute<tpu> hook of the north star).
"""
from __future__ import annotations

import functools

__all__ = ["Operator", "register", "get", "list_ops", "alias"]

_REGISTRY: dict = {}


class Operator:
    def __init__(self, name, fn, *, arity=None, differentiable=True,
                 creation=False, random=False, num_outputs=1, doc=None):
        self.name = name
        self.fn = fn
        self.arity = arity            # number of array inputs; None = variadic
        self.differentiable = differentiable
        self.creation = creation      # takes no array inputs (zeros, uniform, ...)
        self.random = random          # consumes an RNG key kwarg
        self.num_outputs = num_outputs
        self.doc = doc or (fn.__doc__ if fn else None)
        self.tpu_fn = None            # optional Pallas/TPU-specialized impl
        self.shape_hint = None        # fn(in_shapes, kwargs) -> in_shapes
        #   fills unknown (None) input shapes from known ones — the forward
        #   half of the reference's bidirectional FInferShape
        self.vjp_rule = None          # optional FGradient-style rule
        self.record_override = None   # optional custom tape recording:
        #   f(raw_args, kwargs, nd_inputs, fn) -> (out_raw, vjp_fn,
        #   primal_fn) or None to fall back to the generic jax.vjp path.
        #   `fn` is the already-resolved forward (tpu_impl/AMP applied) —
        #   overrides must compute the output through it so specialization
        #   is never bypassed. The hook for ops whose gradient has
        #   non-dense structure (the FGradient-with-FInferStorageType
        #   analog: Embedding sparse_grad -> rowsparse).

    def tpu_impl(self, fn):
        """Register a TPU-specialized (Pallas) implementation.
        The FCompute<tpu> hook of the north star (BASELINE.json)."""
        self.tpu_fn = fn
        return fn

    def recorder(self, fn):
        """Register a custom tape-recording path (see record_override)."""
        self.record_override = fn
        return fn

    def def_grad(self, fn):
        """Register a hand-written vjp rule — the FGradient analog
        (reference: NNVM_REGISTER_OP(...).set_attr<FGradient>(...)).

        fn(cot, out_raw, raw_args, kwargs, nd_positions) -> tuple of
        cotangents aligned with nd_positions (None where undefined).
        With a rule, the eager tape records WITHOUT calling jax.vjp —
        the per-op trace (~2 ms) collapses to a plain forward, and the
        backward runs the rule's jnp math directly."""
        self.vjp_rule = fn
        return fn

    def best_fn(self, on_tpu):
        if on_tpu and self.tpu_fn is not None:
            from .pallas_stats import use_pallas
            if use_pallas(True):
                return self.tpu_fn
        return self.fn

    def __repr__(self):
        return "Operator(%s)" % self.name


def register(name, **meta):
    """Decorator: register a jax-level op implementation under `name`.

    reference: NNVM_REGISTER_OP(name).set_attr<FCompute>(...)
    """
    def deco(fn):
        if name in _REGISTRY:
            raise ValueError("op %s already registered" % name)
        _REGISTRY[name] = Operator(name, fn, **meta)
        return fn
    return deco


def alias(existing, *names):
    """Register additional names for an op (reference: .add_alias)."""
    op = _REGISTRY[existing]
    for n in names:
        _REGISTRY[n] = op


def get(name):
    return _REGISTRY[name]


def list_ops():
    return sorted(_REGISTRY)


def defun(name, **meta):
    """Register and return a plain callable (for internal reuse)."""
    def deco(fn):
        register(name, **meta)(fn)
        return fn
    return deco

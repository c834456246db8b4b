"""Hand-written vjp rules for the hot eager ops — the FGradient layer.

reference: the per-op FGradient attrs of src/operator/tensor/
elemwise_binary_op_basic.cc, elemwise_unary_op_basic.cc,
fully_connected.cc, matrix_op.cc, softmax.cc. The generic tape records
through `jax.vjp`, which re-traces the op on EVERY eager call (~2 ms/op
measured on this box vs ~70 us for the forward). These rules remove the
trace entirely: forward runs plain, backward runs the closed-form
cotangent math. Coverage targets the ops that dominate un-hybridized
training steps; everything else keeps the generic path, and
tests/test_grad_rules.py pins each rule against the generic vjp.

Rule contract (registry.Operator.def_grad):
    rule(cot, out, raw_args, kwargs, nd_positions)
      -> tuple of cotangents aligned with nd_positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import get as _get


def _unbroadcast(cot, shape):
    """Reduce a broadcasted cotangent back onto `shape` (the reference's
    broadcast backward reduce_sum)."""
    shape = tuple(shape)
    if cot.shape == shape:
        return cot
    extra = cot.ndim - len(shape)
    if extra > 0:
        cot = cot.sum(axis=tuple(range(extra)))
    axes = tuple(i for i, s in enumerate(shape) if s == 1
                 and cot.shape[i] != 1)
    if axes:
        cot = cot.sum(axis=axes, keepdims=True)
    return cot


def _per_arg(cot_fns):
    """Build a rule from per-slot cotangent lambdas f(cot, out, a, b)."""
    def rule(cot, out, raw_args, kwargs, nd_positions):
        a = raw_args[0]
        b = raw_args[1] if len(raw_args) > 1 else None
        outs = []
        for p in nd_positions:
            c = cot_fns[p](cot, out, a, b)
            tgt = raw_args[p]
            outs.append(_unbroadcast(c, jnp.shape(tgt))
                        .astype(jnp.asarray(tgt).dtype))
        return tuple(outs)
    return rule


# -- binary broadcast ------------------------------------------------------
_get("broadcast_add").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot,
    1: lambda cot, out, a, b: cot}))
_get("broadcast_sub").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot,
    1: lambda cot, out, a, b: -cot}))
_get("broadcast_mul").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot * b,
    1: lambda cot, out, a, b: cot * a}))
_get("broadcast_div").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot / b,
    1: lambda cot, out, a, b: -cot * a / (b * b)}))
# ties split 0.5/0.5, matching lax.max/min's vjp (the generic path)
_get("broadcast_maximum").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot * (jnp.asarray(a > b, cot.dtype)
                                     + 0.5 * (a == b)),
    1: lambda cot, out, a, b: cot * (jnp.asarray(b > a, cot.dtype)
                                     + 0.5 * (a == b))}))
_get("broadcast_minimum").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot * (jnp.asarray(a < b, cot.dtype)
                                     + 0.5 * (a == b)),
    1: lambda cot, out, a, b: cot * (jnp.asarray(b < a, cot.dtype)
                                     + 0.5 * (a == b))}))
_get("broadcast_power").def_grad(_per_arg({
    0: lambda cot, out, a, b: cot * b * a ** (jnp.asarray(b) - 1),
    1: lambda cot, out, a, b: cot * out * jnp.log(a)}))

# -- unary -----------------------------------------------------------------
def _unary(name, fn):
    _get(name).def_grad(
        lambda cot, out, raw_args, kwargs, nd_positions, _f=fn:
        (_f(cot, out, raw_args[0])
         .astype(jnp.asarray(raw_args[0]).dtype),))


_unary("negative", lambda cot, out, a: -cot)
_unary("exp", lambda cot, out, a: cot * out)
_unary("log", lambda cot, out, a: cot / a)
_unary("sqrt", lambda cot, out, a: cot / (2.0 * out))
_unary("square", lambda cot, out, a: cot * 2.0 * a)
_unary("tanh", lambda cot, out, a: cot * (1.0 - out * out))
_unary("sigmoid", lambda cot, out, a: cot * out * (1.0 - out))
_unary("relu", lambda cot, out, a: cot * (a > 0))
_unary("abs", lambda cot, out, a: cot * jnp.sign(a))
_unary("rsqrt", lambda cot, out, a: -0.5 * cot * out / a)
_unary("_copyto", lambda cot, out, a: cot)


def _fallback_vjp(opname, raw_args, kwargs, nd_positions, cot):
    """Backward-time jax.vjp recompute — the escape hatch for kwargs a
    closed-form rule does not model. Still removes the FORWARD trace;
    the cost lands only on the (rare) backward through that op."""
    op = _get(opname)
    fixed = list(raw_args)

    def f(*arrs):
        full = list(fixed)
        for p, a in zip(nd_positions, arrs):
            full[p] = a
        return op.fn(*full, **kwargs)
    _, vjp = jax.vjp(f, *[raw_args[p] for p in nd_positions])
    return vjp(cot)


_ACT_GRADS = {
    "relu": lambda cot, out, a: cot * (a > 0),
    "sigmoid": lambda cot, out, a: cot * out * (1.0 - out),
    "tanh": lambda cot, out, a: cot * (1.0 - out * out),
    "softrelu": lambda cot, out, a: cot * jax.nn.sigmoid(a),
    "softsign": lambda cot, out, a: cot / jnp.square(1.0 + jnp.abs(a)),
    "silu": lambda cot, out, a: cot * (lambda s: s + a * s * (1.0 - s))(
        jax.nn.sigmoid(a)),
}
_ACT_GRADS["swish"] = _ACT_GRADS["silu"]


@_get("Activation").def_grad
def _activation_grad(cot, out, raw_args, kwargs, nd_positions):
    a = raw_args[0]
    g = _ACT_GRADS.get(kwargs.get("act_type", "relu"))
    if g is None:  # gelu etc.: recompute via jax.vjp at backward time
        return _fallback_vjp("Activation", raw_args, kwargs, nd_positions,
                             cot)
    return (g(cot, out, a).astype(jnp.asarray(a).dtype),)


# -- linear algebra --------------------------------------------------------
@_get("dot").def_grad
def _dot_grad(cot, out, raw_args, kwargs, nd_positions):
    a, b = raw_args[0], raw_args[1]
    ta = kwargs.get("transpose_a", False)
    tb = kwargs.get("transpose_b", False)
    if a.ndim != 2 or b.ndim != 2:
        # N-D dot: recompute via vjp at backward (uncommon eager shape)
        return _fallback_vjp("dot", raw_args, kwargs, nd_positions, cot)
    if not ta and not tb:
        da, db = cot @ b.T, a.T @ cot
    elif ta and not tb:
        da, db = b @ cot.T, a @ cot
    elif not ta and tb:
        da, db = cot @ b, cot.T @ a
    else:
        da, db = b.T @ cot.T, cot.T @ a.T
    return (da.astype(a.dtype), db.astype(b.dtype))


@_get("FullyConnected").def_grad
def _fc_grad(cot, out, raw_args, kwargs, nd_positions):
    data, weight = raw_args[0], raw_args[1]
    flatten = kwargs.get("flatten", True)
    x = data.reshape(data.shape[0], -1) if (flatten and data.ndim > 2) \
        else data
    dx = (cot @ weight).reshape(data.shape).astype(data.dtype)
    # contracted over the rows as they lie, the product jax's own transpose
    # rule writes: `cot.T @ x` would run the transpose as a program of its own
    dw = jax.lax.dot_general(
        cot.reshape(-1, cot.shape[-1]), x.reshape(-1, x.shape[-1]),
        (((0,), (0,)), ((), ()))).astype(weight.dtype)
    outs = [dx, dw]
    if len(nd_positions) > 2:
        red = tuple(range(cot.ndim - 1))
        outs.append(cot.sum(axis=red).astype(raw_args[2].dtype))
    return tuple(outs)


# -- shape ops -------------------------------------------------------------
@_get("reshape").def_grad
def _reshape_grad(cot, out, raw_args, kwargs, nd_positions):
    return (cot.reshape(jnp.shape(raw_args[0])),)


@_get("transpose").def_grad
def _transpose_grad(cot, out, raw_args, kwargs, nd_positions):
    axes = kwargs.get("axes")
    if not axes:
        return (cot.T if cot.ndim == 2 else jnp.transpose(cot),)
    inv = [0] * len(axes)
    for i, ax in enumerate(axes):
        inv[ax] = i
    return (jnp.transpose(cot, inv),)


@_get("Flatten").def_grad
def _flatten_grad(cot, out, raw_args, kwargs, nd_positions):
    return (cot.reshape(jnp.shape(raw_args[0])),)


@_get("expand_dims").def_grad
def _expand_dims_grad(cot, out, raw_args, kwargs, nd_positions):
    return (cot.reshape(jnp.shape(raw_args[0])),)


# -- reductions ------------------------------------------------------------
def _sum_like_rule(scale_by_count):
    def rule(cot, out, raw_args, kwargs, nd_positions):
        a = raw_args[0]
        axis = kwargs.get("axis")
        keepdims = kwargs.get("keepdims", False)
        if axis is None:
            axes = tuple(range(a.ndim))
        elif isinstance(axis, (tuple, list)):
            axes = tuple(ax % a.ndim for ax in axis)
        else:
            axes = (axis % a.ndim,)
        if kwargs.get("exclude"):
            axes = tuple(i for i in range(a.ndim) if i not in axes)
        c = jnp.asarray(cot)
        if not keepdims:
            for ax in sorted(axes):
                c = jnp.expand_dims(c, ax)
        c = jnp.broadcast_to(c, a.shape)
        if scale_by_count:
            n = 1
            for ax in axes:
                n *= a.shape[ax]
            c = c / n
        return (c.astype(a.dtype),)
    return rule


_get("sum").def_grad(_sum_like_rule(False))
_get("mean").def_grad(_sum_like_rule(True))


# -- softmax family --------------------------------------------------------
@_get("softmax").def_grad
def _softmax_grad(cot, out, raw_args, kwargs, nd_positions):
    t = kwargs.get("temperature")
    if (t not in (None, 1.0)) or kwargs.get("use_length") \
            or kwargs.get("length") is not None:
        return _fallback_vjp("softmax", raw_args, kwargs, nd_positions, cot)
    axis = kwargs.get("axis", -1)
    inner = (cot * out).sum(axis=axis, keepdims=True)
    return ((out * (cot - inner)).astype(jnp.asarray(raw_args[0]).dtype),)


@_get("log_softmax").def_grad
def _log_softmax_grad(cot, out, raw_args, kwargs, nd_positions):
    t = kwargs.get("temperature")
    if (t not in (None, 1.0)) or kwargs.get("use_length") \
            or kwargs.get("length") is not None:
        return _fallback_vjp("log_softmax", raw_args, kwargs, nd_positions,
                             cot)
    axis = kwargs.get("axis", -1)
    c = cot - jnp.exp(out) * cot.sum(axis=axis, keepdims=True)
    return (c.astype(jnp.asarray(raw_args[0]).dtype),)


# -- indexing --------------------------------------------------------------
@_get("_internal_getitem").def_grad
def _getitem_grad(cot, out, raw_args, kwargs, nd_positions):
    a = raw_args[0]
    idx = kwargs.get("index")
    if idx is None:  # data[None]: a leading broadcast axis
        return (cot.reshape(jnp.shape(a)).astype(a.dtype),)
    z = jnp.zeros(jnp.shape(a), dtype=cot.dtype)
    return (z.at[idx].add(cot).astype(a.dtype),)

"""Fused conv3x3 + folded-BN + ReLU (+ residual) — the ROOFLINE.md fusion
project.

reference contrast: the reference gets this fusion from cuDNN's fused
conv-bias-activation path and its RTC pointwise fuser (SURVEY §2.1); on
TPU the XLA path already fuses the BN affine + ReLU into the conv's
epilogue, but each op boundary still round-trips activations through HBM
in the NCHW layout benchmark. This op is the explicit fused form: one
`_contrib_conv_bn_relu` node whose TPU implementation is a Pallas
implicit-GEMM kernel — the 3x3 conv becomes 9 shifted (H·W, Cin) x
(Cin, Cout-block) MXU dots accumulated in VMEM, and the scale/shift/ReLU
/residual epilogue runs on the accumulator before it ever leaves VMEM.

Layout NHWC (the TPU-native channels-last layout), stride 1, SAME pad —
the shape of every interior ResNet block conv. BN is the FOLDED
(inference) form: scale = gamma/sqrt(var+eps), shift = beta - mean*scale;
`fold_bn_params` computes them from a Gluon BatchNorm's tensors. Training
keeps the composed conv/BatchNorm ops (batch statistics need the conv
output before normalization can start).

Enable the Pallas path with MXNET_TPU_USE_PALLAS=1 (registry tpu_impl
gate); MXNET_FLASH_INTERPRET=1 runs it through the interpreter on CPU for
the test suite.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_stats as _pstats
from .registry import register, get

__all__ = ["fold_bn_params"]


def _interpret():
    return os.environ.get("MXNET_FLASH_INTERPRET", "0") == "1"


# version-tolerant Mosaic params shim — shared by every kernel module
_compiler_params = _pstats.compiler_params


def fold_bn_params(gamma, beta, moving_mean, moving_var, eps=1e-3):
    """BN(inference) == y*scale + shift with these folded tensors."""
    scale = gamma / jnp.sqrt(moving_var + eps)
    return scale, beta - moving_mean * scale


def _conv3x3_same(x, w):
    """The one conv config this module fuses: 3x3, stride 1, SAME, NHWC,
    f32 accumulation. Single definition — the training forward, its
    backward, and the inference path must never desynchronize."""
    return lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)


def _xla_conv_bn_relu(x, w, scale, shift, residual=None):
    """Reference XLA path: lax conv in NHWC + affine + relu."""
    out = _conv3x3_same(x, w)
    out = out * scale.astype(jnp.float32) + shift.astype(jnp.float32)
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    return jnp.maximum(out, 0.0).astype(x.dtype)


def _kernel(x_ref, w_ref, s_ref, b_ref, *rest, block_co, H, W, C,
            has_residual):
    if has_residual:
        r_ref, o_ref = rest
    else:
        (o_ref,) = rest
    x = x_ref[0].astype(jnp.float32)            # (H, W, C)
    acc = jnp.zeros((H * W, block_co), jnp.float32)
    # implicit GEMM: 9 shifted full-image dots, accumulator stays in VMEM
    for dh in (-1, 0, 1):
        for dw in (-1, 0, 1):
            shifted = jnp.roll(x, (-dh, -dw), axis=(0, 1))
            rows = lax.broadcasted_iota(jnp.int32, (H, W), 0)
            cols = lax.broadcasted_iota(jnp.int32, (H, W), 1)
            valid = ((rows + dh >= 0) & (rows + dh < H) &
                     (cols + dw >= 0) & (cols + dw < W))
            shifted = jnp.where(valid[..., None], shifted, 0.0)
            wk = w_ref[dh + 1, dw + 1].astype(jnp.float32)   # (C, bco)
            acc += jax.lax.dot_general(
                shifted.reshape(H * W, C), wk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    out = acc * s_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    if has_residual:
        out = out + r_ref[0].astype(jnp.float32).reshape(H * W, block_co)
    out = jnp.maximum(out, 0.0)
    o_ref[0] = out.reshape(H, W, block_co).astype(o_ref.dtype)


def _pallas_conv_bn_relu(x, w, scale, shift, residual=None, block_co=128):
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    block_co = min(block_co, Cout)
    n_co = pl.cdiv(Cout, block_co)
    has_res = residual is not None

    cparams = _compiler_params(("parallel", "parallel"))

    in_specs = [
        pl.BlockSpec((1, H, W, C), lambda n, c: (n, 0, 0, 0)),
        pl.BlockSpec((3, 3, C, block_co), lambda n, c: (0, 0, 0, c)),
        pl.BlockSpec((block_co,), lambda n, c: (c,)),
        pl.BlockSpec((block_co,), lambda n, c: (c,)),
    ]
    args = [x, w, scale, shift]
    if has_res:
        in_specs.append(pl.BlockSpec((1, H, W, block_co),
                                     lambda n, c: (n, 0, 0, c)))
        args.append(residual)

    out = pl.pallas_call(
        functools.partial(_kernel, block_co=block_co, H=H, W=W, C=C,
                          has_residual=has_res),
        grid=(N, n_co),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, W, block_co),
                               lambda n, c: (n, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
        interpret=_interpret(),
        compiler_params=cparams,
    )(*args)
    return out


def _shapes_ok(x, w):
    C, Cout = x.shape[-1], w.shape[-1]
    return (w.shape[0] == 3 and w.shape[1] == 3 and
            C % 8 == 0 and Cout % 8 == 0)


# inference-path op: differentiable=False — the Pallas kernel has no AD
# rule, and training keeps the composed Conv/BatchNorm ops anyway (batch
# statistics need the conv output before normalization)
@register("_contrib_conv_bn_relu", arity=None, differentiable=False)
def _conv_bn_relu(x, w, scale, shift, *residual):
    """x (N,H,W,C) NHWC; w (3,3,Cin,Cout) HWIO; scale/shift (Cout,);
    optional residual (N,H,W,Cout). Stride 1, SAME pad, folded-BN + ReLU
    epilogue."""
    res = residual[0] if residual else None
    return _xla_conv_bn_relu(x, w, scale, shift, res)


# the Pallas kernel registers through tpu_impl so the registry's
# MXNET_TPU_USE_PALLAS kill switch (registry.best_fn) really gates it
@get("_contrib_conv_bn_relu").tpu_impl
def _conv_bn_relu_tpu(x, w, scale, shift, *residual):
    res = residual[0] if residual else None
    if not _shapes_ok(x, w):
        _pstats.note_fallback("cbr_infer", "shape")
        return _xla_conv_bn_relu(x, w, scale, shift, res)
    _pstats.note_dispatch("cbr_infer")
    with _pstats.kernel_span("cbr_infer"):
        return _pallas_conv_bn_relu(x, w, scale, shift, res)


# ---------------------------------------------------------------------------
# TRAINING-form fusion (round-4 VERDICT weak #3 / round-5 task 2): batch
# statistics need the conv output, so training is a two-pass structure.
# The composed XLA graph pays (at least) four HBM passes over the conv
# output: write it, read it for the stats reduction, read it again for the
# normalize, write the activation. The fused form computes the stats IN
# THE CONV EPILOGUE from the f32 VMEM accumulator (pass 1 writes conv_out
# once and emits per-grid-cell partial sums — the stats reduction never
# re-reads conv_out from HBM), then one elementwise normalize pass.
# Backward recomputes xhat from conv_out + saved stats (no xhat/mask
# materialization in forward) and rides XLA's transposed convs for dx/dw.
# reference contrast: cuDNN's fused conv-bias-act serves training in the
# reference (SURVEY §2.1 cuDNN row); its BN backward fusions are
# cudnnBatchNormalizationBackwardEx.
# ---------------------------------------------------------------------------
def _stats_block_co(Cout, cap=128):
    """Largest multiple-of-8 divisor of Cout up to `cap` (partial-stat
    slabs must tile Cout exactly)."""
    best = 0
    for b in range(8, min(cap, Cout) + 1, 8):
        if Cout % b == 0:
            best = b
    return best


def _kernel_train(x_ref, w_ref, o_ref, p_ref, *, block_co, H, W, C):
    """Conv pass with stats epilogue: writes the conv output AND this grid
    cell's per-channel (sum, sum-of-squares) computed from the f32
    accumulator while it is still in VMEM."""
    x = x_ref[0].astype(jnp.float32)            # (H, W, C)
    acc = jnp.zeros((H * W, block_co), jnp.float32)
    for dh in (-1, 0, 1):
        for dw in (-1, 0, 1):
            shifted = jnp.roll(x, (-dh, -dw), axis=(0, 1))
            rows = lax.broadcasted_iota(jnp.int32, (H, W), 0)
            cols = lax.broadcasted_iota(jnp.int32, (H, W), 1)
            valid = ((rows + dh >= 0) & (rows + dh < H) &
                     (cols + dw >= 0) & (cols + dw < W))
            shifted = jnp.where(valid[..., None], shifted, 0.0)
            wk = w_ref[dh + 1, dw + 1].astype(jnp.float32)   # (C, bco)
            acc += jax.lax.dot_general(
                shifted.reshape(H * W, C), wk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(H, W, block_co).astype(o_ref.dtype)
    p_ref[0, 0, 0] = jnp.sum(acc, axis=0)
    p_ref[0, 0, 1] = jnp.sum(acc * acc, axis=0)


def _pallas_conv_stats(x, w):
    """Pass 1: conv_out (x.dtype) + f32 per-channel (sum, sumsq)."""
    N, H, W, C = x.shape
    Cout = w.shape[-1]
    block_co = _stats_block_co(Cout)
    n_co = Cout // block_co

    cparams = _compiler_params(("parallel", "parallel"))

    conv_out, partial = pl.pallas_call(
        functools.partial(_kernel_train, block_co=block_co, H=H, W=W, C=C),
        grid=(N, n_co),
        in_specs=[
            pl.BlockSpec((1, H, W, C), lambda n, c: (n, 0, 0, 0)),
            pl.BlockSpec((3, 3, C, block_co), lambda n, c: (0, 0, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, H, W, block_co), lambda n, c: (n, 0, 0, c)),
            pl.BlockSpec((1, 1, 2, block_co), lambda n, c: (n, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, W, Cout), x.dtype),
            jax.ShapeDtypeStruct((N, n_co, 2, block_co), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=cparams,
    )(x, w)
    # (N, n_co, 2, bco) -> (2, Cout); tiny host-side reduction
    sums = partial.transpose(2, 1, 3, 0).reshape(2, Cout, N).sum(axis=-1)
    return conv_out, sums[0], sums[1]


def _xla_conv_stats(x, w):
    conv_out = _conv3x3_same(x, w)
    s = jnp.sum(conv_out, axis=(0, 1, 2))
    sq = jnp.sum(conv_out * conv_out, axis=(0, 1, 2))
    return conv_out.astype(x.dtype), s, sq


def _pallas_train_gate():
    """Is the Pallas training path REQUESTED (independent of shapes)?
    Interpreter runs always request it (that is what they test); compiled
    runs need the TPU backend plus the MXNET_TPU_USE_PALLAS opt-in."""
    if _interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    return os.environ.get("MXNET_TPU_USE_PALLAS", "0") == "1"


def _use_pallas_train(x, w):
    if not _pallas_train_gate():
        return False
    return bool(_shapes_ok(x, w) and _stats_block_co(w.shape[-1]))


def _normalize_relu(conv_out, mean, invstd, gamma, beta, residual):
    xhat = (conv_out.astype(jnp.float32) - mean) * invstd
    y = xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return xhat, y


def _cbr_train_compute(eps, x, w, gamma, beta, residual):
    """Shared forward: pass-1 conv+stats, pass-2 normalize+relu."""
    if _use_pallas_train(x, w):
        _pstats.note_dispatch("cbr_train_fwd")
        with _pstats.kernel_span("cbr_train_fwd"):
            conv_out, s, sq = _pallas_conv_stats(x, w)
    else:
        if _pallas_train_gate():
            _pstats.note_fallback("cbr_train_fwd", "shape")
        conv_out, s, sq = _xla_conv_stats(x, w)
    M = x.shape[0] * x.shape[1] * x.shape[2]
    mean = s / M
    var = jnp.maximum(sq / M - mean * mean, 0.0)
    invstd = lax.rsqrt(var + eps)
    _, y = _normalize_relu(conv_out, mean, invstd, gamma, beta, residual)
    out = jnp.maximum(y, 0.0).astype(x.dtype)
    return out, mean, var, invstd, conv_out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _cbr_train(eps, has_res, x, w, gamma, beta, residual):
    out, mean, var, _, _ = _cbr_train_compute(eps, x, w, gamma, beta,
                                              residual)
    return out, mean, var


def _cbr_train_fwd_rule(eps, has_res, x, w, gamma, beta, residual):
    out, mean, var, invstd, conv_out = _cbr_train_compute(
        eps, x, w, gamma, beta, residual)
    return (out, mean, var), (x, w, conv_out, mean, invstd, gamma, beta,
                              residual)


# ---------------------------------------------------------------------------
# FUSED BACKWARD (round-6 / ISSUE 10 tentpole): the composed backward
# recomputes xhat/the relu mask and runs its per-channel reductions (dgamma,
# dbeta, the two Σdxhat moments) plus the dconv elementwise pass as separate
# XLA loops — each re-reading conv_out and dy from HBM. `_kernel_train_bwd`
# is ONE pallas_call over grid (co_block, phase, n):
#
#   phase 0  streams every (n, co) tile of conv_out/dy once, recomputes
#            xhat and the relu mask IN VMEM, and accumulates the two
#            per-channel reductions (Σg = dbeta, Σg·xhat = dgamma) in a
#            VMEM scratch accumulator — the only full reductions the BN
#            backward needs (the dxhat moments are gamma·Σg and
#            gamma·Σg·xhat, derived in-register);
#   phase 1  streams the tiles a second time (the data dependency of
#            dconv on the global sums makes a second streaming pass the
#            information-theoretic minimum — nothing is ever
#            materialized between the passes) and emits the dconv tiles
#            (+ dres = masked dy when the block has a residual input).
#
# HBM traffic: 2×(conv_out + dy [+ residual]) reads + 1×dconv (+dres)
# write + O(C) stats. The composed program additionally materializes (or
# re-derives through separate fusions) xhat and the pre-relu activation.
# The phase-0 visits of the dconv/dres output map to block (0, c) and
# write nothing, so no garbage tile ever rides back to HBM.
# dx/dw still ride XLA's transposed convs — those are MXU-optimal.
# ---------------------------------------------------------------------------
def _kernel_train_bwd(co_ref, dy_ref, m_ref, i_ref, g_ref, b_ref, *rest,
                      block_co, H, W, N, M, has_residual):
    if has_residual:
        r_ref, dco_ref, dg_ref, db_ref, dr_ref, acc = rest
    else:
        dco_ref, dg_ref, db_ref, acc = rest
    phase = pl.program_id(1)
    n = pl.program_id(2)
    conv = co_ref[0].astype(jnp.float32).reshape(H * W, block_co)
    dy = dy_ref[0].astype(jnp.float32).reshape(H * W, block_co)
    mean = m_ref[...].astype(jnp.float32)
    invstd = i_ref[...].astype(jnp.float32)
    gamma = g_ref[...].astype(jnp.float32)
    xhat = (conv - mean) * invstd
    y = xhat * gamma + b_ref[...].astype(jnp.float32)
    if has_residual:
        y = y + r_ref[0].astype(jnp.float32).reshape(H * W, block_co)
    g = jnp.where(y > 0, dy, 0.0)

    @pl.when(phase == 0)
    def _():
        @pl.when(n == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
        acc[0, :] += jnp.sum(g, axis=0)
        acc[1, :] += jnp.sum(g * xhat, axis=0)

        @pl.when(n == N - 1)
        def _():
            db_ref[...] = acc[0, :]
            dg_ref[...] = acc[1, :]

    @pl.when(phase == 1)
    def _():
        dxhat = g * gamma
        mean_dxhat = gamma * (acc[0, :] / M)
        mean_dxhat_xhat = gamma * (acc[1, :] / M)
        dconv = invstd * (dxhat - mean_dxhat - xhat * mean_dxhat_xhat)
        dco_ref[0] = dconv.reshape(H, W, block_co)
        if has_residual:
            dr_ref[0] = g.reshape(H, W, block_co).astype(dr_ref.dtype)


def _pallas_cbr_bwd(conv_out, dy, mean, invstd, gamma, beta, residual=None):
    """One fused backward launch: (dconv f32, dgamma f32, dbeta f32
    [, dres residual-dtype]) from conv_out + dy + saved stats."""
    N, H, W, Cout = conv_out.shape
    block_co = _stats_block_co(Cout)
    n_co = Cout // block_co
    has_res = residual is not None

    cparams = _compiler_params(("arbitrary", "arbitrary", "arbitrary"))

    tile = pl.BlockSpec((1, H, W, block_co), lambda c, p, n: (n, 0, 0, c))
    chan = pl.BlockSpec((block_co,), lambda c, p, n: (c,))
    # phase-0 visits of the elementwise outputs park on block (0, c):
    # consecutive same-index visits never copy out, so the only HBM write
    # is phase 1's real tile
    out_tile = pl.BlockSpec((1, H, W, block_co),
                            lambda c, p, n: (n * p, 0, 0, c))
    in_specs = [tile, tile, chan, chan, chan, chan]
    args = [conv_out, dy, mean, invstd, gamma, beta]
    out_specs = [out_tile, chan, chan]
    out_shapes = [jax.ShapeDtypeStruct((N, H, W, Cout), jnp.float32),
                  jax.ShapeDtypeStruct((Cout,), jnp.float32),
                  jax.ShapeDtypeStruct((Cout,), jnp.float32)]
    if has_res:
        in_specs.append(tile)
        args.append(residual)
        out_specs.append(out_tile)
        out_shapes.append(
            jax.ShapeDtypeStruct((N, H, W, Cout), residual.dtype))

    outs = pl.pallas_call(
        functools.partial(_kernel_train_bwd, block_co=block_co, H=H, W=W,
                          N=N, M=float(N * H * W), has_residual=has_res),
        grid=(n_co, 2, N),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((2, block_co), jnp.float32)],
        interpret=_interpret(),
        compiler_params=cparams,
    )(*args)
    dconv, dgamma, dbeta = outs[:3]
    dres = outs[3] if has_res else None
    return dconv, dgamma, dbeta, dres


def _xla_cbr_bwd(conv_out, dy, mean, invstd, gamma, beta, residual=None):
    """Composite backward epilogue (the pre-round-6 path, and the escape
    hatch): recompute xhat/mask, three reductions, dconv pass — all as
    separate XLA ops over HBM-resident tensors."""
    g_out = dy.astype(jnp.float32)
    xhat, y = _normalize_relu(conv_out, mean, invstd, gamma, beta, residual)
    g = jnp.where(y > 0, g_out, 0.0)
    axes = (0, 1, 2)
    dbeta = jnp.sum(g, axis=axes)
    dgamma = jnp.sum(g * xhat, axis=axes)
    dxhat = g * gamma.astype(jnp.float32)
    mean_dxhat = jnp.mean(dxhat, axis=axes)
    mean_dxhat_xhat = jnp.mean(dxhat * xhat, axis=axes)
    dconv = invstd * (dxhat - mean_dxhat - xhat * mean_dxhat_xhat)
    dres = g.astype(residual.dtype) if residual is not None else None
    return dconv, dgamma, dbeta, dres


def _cbr_train_bwd_rule(eps, has_res, saved, cots):
    x, w, conv_out, mean, invstd, gamma, beta, residual = saved
    # mean/var cotangents are dropped: running-stat updates are stop-grad
    # (reference BatchNorm semantics)
    if _use_pallas_train(x, w):
        _pstats.note_dispatch("cbr_train_bwd")
        with _pstats.kernel_span("cbr_train_bwd"):
            dconv, dgamma, dbeta, dres = _pallas_cbr_bwd(
                conv_out, cots[0], mean, invstd, gamma, beta,
                residual if has_res else None)
    else:
        if _pallas_train_gate():
            _pstats.note_fallback("cbr_train_bwd", "shape")
        dconv, dgamma, dbeta, dres = _xla_cbr_bwd(
            conv_out, cots[0], mean, invstd, gamma, beta,
            residual if has_res else None)

    _, conv_vjp = jax.vjp(_conv3x3_same, x.astype(jnp.float32),
                          w.astype(jnp.float32))
    dx, dw = conv_vjp(dconv)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            dres if has_res else None)


_cbr_train.defvjp(_cbr_train_fwd_rule, _cbr_train_bwd_rule)


@register("_contrib_conv_bn_relu_train", arity=None, num_outputs=3)
def _conv_bn_relu_train(x, w, gamma, beta, *residual, eps=1e-3):
    """Training-form fused conv3x3 + BatchNorm + ReLU (+ residual).

    x (N,H,W,C) NHWC; w (3,3,Cin,Cout) HWIO; gamma/beta (Cout,);
    optional residual (N,H,W,Cout). Returns (out, batch_mean, batch_var)
    — the caller updates running stats from mean/var exactly like
    BatchNorm does; gradients flow to x/w/gamma/beta/residual through the
    standard training-BN backward (mean/var outputs carry stop-grad,
    reference BatchNorm semantics).
    """
    res = residual[0] if residual else None
    return _cbr_train(eps, res is not None, x, w, gamma, beta, res)

"""Neural-net ops.

TPU-native analog of the reference's src/operator/nn/* (reference:
fully_connected.cc, convolution.cc, deconvolution.cc, pooling.cc,
batch_norm.cc, layer_norm.cc, activation.cc, leaky_relu.cc, dropout.cc,
softmax.cc) and src/operator/softmax_output.cc. Convs and matmuls lower to the
MXU via lax.conv_general_dilated / dot_general; there is no cuDNN-autotune
analog because XLA picks tilings (reference's CudnnConvolutionOp algo
selection collapses into the compiler).
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register, alias
from ..base import np_dtype


def _pair(v, n):
    if v is None:
        return (0,) * n
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    return v if len(v) == n else v * n


# ---------------------------------------------------------------------------
# FullyConnected (reference: src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------
# One program eagerly too: op by op, `transpose(weight)` ran as a program of
# its own and the product then read the other layout, which XLA's CPU rounds
# differently from the product it makes of the pair under a trace, so a dense
# graph compiled whole (`compiler/`) was not the eager one to the bit.
# `inline=True`: under a trace the body is traced in place, and a step's
# lowered text is what it was.
@register("FullyConnected")
@functools.partial(jax.jit, inline=True,
                   static_argnames=("num_hidden", "no_bias", "flatten"))
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                     flatten=True):
    """y = x W^T + b; weight is (num_hidden, in_units) like the reference."""
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, jnp.transpose(weight))
    if bias is not None and not no_bias:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# Convolution (reference: src/operator/nn/convolution.cc) — NCHW/OIHW layout
# to match the reference API; XLA relayouts internally for the MXU.
# ---------------------------------------------------------------------------
def layout_info(layout, nd, op="Convolution"):
    """Validate an MXNet layout string for nd spatial dims. Returns
    (layout, channels_last). The single source of truth for which layouts
    exist — gluon layers and ops all consult this."""
    spatial = "DHW"[3 - nd:]
    if layout is None:
        layout = "NC" + spatial
    if layout == "NC" + spatial:
        return layout, False
    if layout == "N" + spatial + "C":
        return layout, True
    raise ValueError("%s: unsupported layout %r for %dD (expected %r or %r)"
                     % (op, layout, nd, "NC" + spatial,
                        "N" + spatial + "C"))


def _conv_layouts(layout, nd):
    """layout -> (data_layout, weight_layout). Channels-first weights are
    OI+spatial; channels-last (reference: NHWC convs, GPU-only there) use
    O+spatial+I — weight (num_filter, *kernel, C/groups)."""
    layout, last = layout_info(layout, nd)
    spatial = "DHW"[3 - nd:]
    return layout, ("O" + spatial + "I") if last else ("OI" + spatial)


@register("Convolution")
def _convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, no_bias=False,
                 layout=None, cudnn_tune=None, cudnn_off=None, workspace=None):
    nd = len(kernel) if kernel is not None else data.ndim - 2
    stride = _pair(stride if stride else 1, nd)
    dilate = _pair(dilate if dilate else 1, nd)
    pad = _pair(pad if pad else 0, nd)
    dlay, wlay = _conv_layouts(layout, nd)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (dlay, wlay, dlay))
    # no preferred_element_type: the MXU accumulates bf16 convs in fp32
    # natively, and a widened output dtype breaks the conv transpose rule
    # (fp32 cotangent x bf16 weight) under autograd
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        shape = [1] * out.ndim
        shape[dlay.index("C")] = -1
        out = out + bias.reshape(shape)
    return out


@register("Deconvolution")
def _deconvolution(data, weight, bias=None, kernel=None, stride=None,
                   dilate=None, pad=None, adj=None, num_filter=None,
                   num_group=1, no_bias=True, target_shape=None, layout=None,
                   cudnn_tune=None, cudnn_off=None, workspace=None):
    """reference: src/operator/nn/deconvolution.cc (transposed conv)."""
    nd = len(kernel)
    stride = _pair(stride if stride else 1, nd)
    pad = _pair(pad if pad else 0, nd)
    adj = _pair(adj if adj else 0, nd)
    spatial = "DHW"[3 - nd:]
    _, last = layout_info(layout, nd, "Deconvolution")
    if last:
        raise NotImplementedError(
            "Deconvolution: channels-last layouts not implemented")
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ("NC" + spatial, "IO" + spatial, "NC" + spatial))
    pads = []
    for i in range(nd):
        k = (kernel[i] - 1) * 1 + 1
        lo = k - 1 - pad[i]
        hi = k - 1 - pad[i] + adj[i]
        pads.append((lo, hi))
    out = lax.conv_general_dilated(
        data, weight, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (reference: src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------
@register("Pooling")
def _pooling(data, kernel=None, pool_type="max", global_pool=False,
             stride=None, pad=None, pooling_convention="valid",
             count_include_pad=True, cudnn_off=None, layout=None, p_value=2):
    nd = data.ndim - 2
    _, channels_last = layout_info(layout, nd, "Pooling")
    spatial_axes = (tuple(range(1, 1 + nd)) if channels_last
                    else tuple(range(2, data.ndim)))
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=spatial_axes, keepdims=True)
        return jnp.mean(data, axis=spatial_axes, keepdims=True)
    kernel = _pair(kernel, nd)
    stride = _pair(stride if stride else 1, nd)
    pad = _pair(pad if pad else 0, nd)
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad high side enough that ceil division is covered
        sp_pads = []
        for i, ax in enumerate(spatial_axes):
            in_sz = data.shape[ax]
            out_sz = -(-(in_sz + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            sp_pads.append((pad[i], max(pad[i], needed)))
    else:
        sp_pads = [(p, p) for p in pad]
    if channels_last:
        pads = [(0, 0)] + sp_pads + [(0, 0)]
    else:
        pads = [(0, 0), (0, 0)] + sp_pads
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / counts
    if pool_type == "lp":
        s = lax.reduce_window(jnp.abs(data) ** p_value, 0.0, lax.add,
                              window, strides, pads)
        return s ** (1.0 / p_value)
    raise ValueError("unknown pool_type " + pool_type)


alias("Pooling", "pooling")


# ---------------------------------------------------------------------------
# Normalization (reference: batch_norm.cc, layer_norm.cc, instance_norm.cc,
# group_norm.cc, l2_normalization.cc)
# ---------------------------------------------------------------------------
def batch_moments(x32, red, running_mean, dtype):
    """Mean and biased variance of `x32` over the axes `red`, accumulated in
    float32 and with no pass over `x32` that the mathematics does not need.
    `x32` is the input widened from `dtype`, whose width chooses the order
    of evaluation; `running_mean` broadcasts against `x32`.

    Wider than 16 bits: two passes, the second about stop_gradient(mean).
    The values are jnp.mean's and jnp.var's to the bit; the gradient loses
    the cotangent of the inner mean, -(2/N) * sum(x - mean), which is zero
    in exact arithmetic and cost one read of `x32` to compute its rounding
    residue.

    16 bits or narrower: one pass about c = stop_gradient(running_mean),
    mean = E[x - c] + c and var = E[(x - c)^2] - E[x - c]^2, so both sums
    can join the epilogue of whatever produced the tensor. A square of a
    16-bit value is exact in float32; what the form adds is the sums' own
    rounding times 1 + r^2, r = |mean - c| / std (2e-5 * (1 + r^2) relative
    at most, tests/test_op_parity.py), under a 16-bit result's own step for
    r up to about 20. A non-finite running mean reaches the batch statistics
    this way; evaluation is broken by then already."""
    if jnp.dtype(dtype).itemsize > 2:
        mean = jnp.mean(x32, axis=red, keepdims=True)
        var = jnp.var(x32, axis=red, mean=lax.stop_gradient(mean))
        return jnp.squeeze(mean, red), var
    c = lax.stop_gradient(running_mean.astype(jnp.float32))
    xc = x32 - c
    m1 = jnp.mean(xc, axis=red)
    m2 = jnp.mean(xc * xc, axis=red)
    return m1 + c.reshape(m1.shape), jnp.maximum(m2 - m1 * m1, 0.0)


@register("BatchNorm")
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=None):
    """Normalization math only; the moving-average update is done by the
    caller (Gluon layer / executor) functionally — reference mutates aux
    states inside the op (batch_norm.cc), which XLA forbids.

    Training-mode statistics are `batch_moments`': accumulated in float32,
    their evaluation order chosen from the operand's width (one pass about
    the running mean for a 16-bit input, two for a wider one)."""
    red = tuple(i for i in range(data.ndim) if i != axis % data.ndim)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    # read once, for the statistics and the normalisation: autodiff then
    # rounds the input's cotangent to a 16-bit input's type once, not twice
    x32 = data.astype(jnp.float32)
    if use_global_stats:
        mean, var = moving_mean, moving_var
    else:
        mean, var = batch_moments(x32, red, moving_mean.reshape(shape),
                                  data.dtype)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    # normalize in fp32, emit in the input dtype (reference cudnn BN does
    # fp32 internal math for fp16 inputs) — keeps a bf16 conv chain bf16
    # even when gamma/beta/stats are kept fp32 by BatchNorm.cast
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    out = ((x32 - mean.reshape(shape).astype(jnp.float32)) *
           inv.reshape(shape) * g.reshape(shape).astype(jnp.float32) +
           beta.reshape(shape).astype(jnp.float32)).astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register("LayerNorm")
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """reference: src/operator/nn/layer_norm.cc."""
    x32 = data.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axis, keepdims=True)
    var = jnp.var(x32, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    out = ((x32 - mean) * inv).astype(data.dtype)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = out * gamma.reshape(shape) + beta.reshape(shape)
    if output_mean_var:
        return out, jnp.squeeze(mean, axis), jnp.squeeze(var, axis)
    return out


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("GroupNorm")
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    n, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape((n, num_groups, c // num_groups) + rest)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization")
def _l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, data.ndim))
        nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / nrm


@register("RMSNorm")
def _rms_norm(data, gamma, axis=-1, eps=1e-6):
    """TPU-era extension (used by Llama); not in the reference op set."""
    x32 = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
    out = (x32 * lax.rsqrt(ms + eps)).astype(data.dtype)
    return out * gamma


# ---------------------------------------------------------------------------
# Activations (reference: activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------
@register("Activation")
def _activation(data, act_type="relu"):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(data, approximate=True)
    if act_type == "silu" or act_type == "swish":
        return jax.nn.silu(data)
    raise ValueError("unknown act_type " + act_type)


@register("LeakyReLU")
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, key=None):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(data >= 0, data, a * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    raise ValueError("unknown act_type " + act_type)


@register("hard_sigmoid")
def _hard_sigmoid(data, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


# ---------------------------------------------------------------------------
# Softmax family (reference: softmax.cc, log_softmax, softmin, SoftmaxOutput)
# ---------------------------------------------------------------------------
@register("softmax")
def _softmax(data, axis=-1, temperature=None, length=None, use_length=False,
             dtype=None):
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if use_length and length is not None:
        steps = jnp.arange(x.shape[axis])
        mask_shape = [1] * x.ndim
        mask_shape[axis] = x.shape[axis]
        mask = steps.reshape(mask_shape) < length.reshape(
            length.shape + (1,) * (x.ndim - length.ndim))
        x = jnp.where(mask, x, -jnp.inf)
    out = jax.nn.softmax(x.astype(jnp.float32), axis=axis)
    return out.astype(np_dtype(dtype) if dtype else data.dtype)


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data if not temperature or temperature == 1.0 else data / temperature
    out = jax.nn.log_softmax(x.astype(jnp.float32), axis=axis)
    return out.astype(np_dtype(dtype) if dtype else data.dtype)


@register("softmin")
def _softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("SoftmaxOutput")
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    """reference: src/operator/softmax_output.cc — forward is softmax; the
    fused CE gradient is produced by the custom VJP below."""
    return jax.nn.softmax(data, axis=1 if multi_output else -1)


# SoftmaxOutput's gradient is (softmax - onehot(label)) * grad_scale — the
# fused form the reference hand-codes. Express it as a custom VJP.
def _softmax_output_make():
    import functools
    from .registry import get

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
    def so(data, label, grad_scale, ignore_label, multi_output, use_ignore,
           normalization, smooth_alpha):
        return jax.nn.softmax(data, axis=1 if multi_output else -1)

    def fwd(data, label, grad_scale, ignore_label, multi_output, use_ignore,
            normalization, smooth_alpha):
        out = jax.nn.softmax(data, axis=1 if multi_output else -1)
        return out, (out, label)

    def bwd(grad_scale, ignore_label, multi_output, use_ignore, normalization,
            smooth_alpha, res, g):
        out, label = res
        axis = 1 if multi_output else -1
        depth = out.shape[axis]
        oh = jax.nn.one_hot(label.astype(jnp.int32), depth, axis=axis,
                            dtype=out.dtype)
        if smooth_alpha:
            oh = oh * (1 - smooth_alpha) + smooth_alpha / depth
        grad = (out - oh) * grad_scale
        keep = None
        if use_ignore:
            keep = (label != ignore_label).astype(out.dtype)
            keep = jnp.expand_dims(keep, axis=axis)
            grad = grad * keep
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid" and keep is not None:
            n = jnp.maximum(jnp.sum(keep), 1.0)
            grad = grad / n
        return grad, jnp.zeros_like(label)

    so.defvjp(fwd, bwd)
    op = get("SoftmaxOutput")
    op.fn = lambda data, label, grad_scale=1.0, ignore_label=-1.0, \
        multi_output=False, use_ignore=False, preserve_shape=False, \
        normalization="null", out_grad=False, smooth_alpha=0.0: so(
            data, label, grad_scale, ignore_label, multi_output, use_ignore,
            normalization, smooth_alpha)


_softmax_output_make()
alias("SoftmaxOutput", "Softmax")


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    lse = jax.scipy.special.logsumexp(data, axis=-1)
    picked = jnp.take_along_axis(
        data, label.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@register("LinearRegressionOutput")
def _linear_regression_output(data, label, grad_scale=1.0):
    return data


@register("MAERegressionOutput")
def _mae_regression_output(data, label, grad_scale=1.0):
    return data


@register("LogisticRegressionOutput")
def _logistic_regression_output(data, label, grad_scale=1.0):
    return jax.nn.sigmoid(data)


# Like SoftmaxOutput, the regression heads are TRAINING-output ops: the
# backward is the hand-coded loss gradient (out - label), NOT the vjp of
# the forward (identity/sigmoid would pass the head cotangent through,
# making the "gradient" independent of the parameters — the silent-ones
# bug the SVRG tests caught). reference: src/operator/regression_output.cc
# (LinearRegressionBackward / MAERegressionBackward /
# LogisticRegressionBackward), each scaled by grad_scale / num_output.
def _regression_output_make(name, fwd_fn, residual_fn):
    import functools
    from .registry import get

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def ro(data, label, grad_scale):
        return fwd_fn(data)

    def fwd(data, label, grad_scale):
        out = fwd_fn(data)
        return out, (out, data, label)

    def bwd(grad_scale, res, g):
        out, data, label = res
        num_output = 1
        for s in data.shape[1:]:
            num_output *= s
        lab = label.reshape(data.shape).astype(out.dtype)
        grad = residual_fn(out, data, lab) * (grad_scale / num_output)
        return grad.astype(data.dtype), jnp.zeros(label.shape, label.dtype)

    ro.defvjp(fwd, bwd)
    op = get(name)
    op.fn = lambda data, label, grad_scale=1.0: ro(data, label, grad_scale)


_regression_output_make("LinearRegressionOutput", lambda d: d,
                        lambda out, d, lab: out - lab)
_regression_output_make("MAERegressionOutput", lambda d: d,
                        lambda out, d, lab: jnp.sign(out - lab))
_regression_output_make("LogisticRegressionOutput", jax.nn.sigmoid,
                        lambda out, d, lab: out - lab)


def _make_loss_core_make():
    """Identity forward; backward scales the cotangent by grad_scale with
    the reference's normalization modes (make_loss.cc): 'batch' divides
    by the batch dim, 'valid' by the count of elements whose magnitude
    exceeds valid_thresh."""
    import functools

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
    def f(data, grad_scale, valid_thresh, normalization):
        return data

    def fwd(data, grad_scale, valid_thresh, normalization):
        valid = None
        if normalization == "valid":
            valid = jnp.maximum(jnp.sum(
                (jnp.abs(data.astype(jnp.float32)) > valid_thresh)
                .astype(jnp.float32)), 1.0)
        return data, valid

    def bwd(grad_scale, valid_thresh, normalization, valid, g):
        gs = grad_scale
        if normalization == "batch":
            gs = gs / g.shape[0]
        grad = g * gs
        if valid is not None:
            grad = grad / valid.astype(g.dtype)
        return (grad,)

    f.defvjp(fwd, bwd)
    return f


_make_loss_core = _make_loss_core_make()


@register("MakeLoss")
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    if float(grad_scale) == 1.0 and normalization == "null":
        return data
    return _make_loss_core(data, float(grad_scale), float(valid_thresh),
                           str(normalization))


# ---------------------------------------------------------------------------
# Dropout (reference: src/operator/nn/dropout.cc) — consumes an RNG key from
# the per-context key table (random=True), preserving mx.random.seed semantics.
# ---------------------------------------------------------------------------
@register("Dropout", random=True)
def _dropout(data, p=0.5, mode="training", axes=None, cudnn_off=None, key=None,
             _training=None):
    """mode='always' applies dropout regardless of train/predict mode
    (reference: dropout.cc DropoutParam mode — enables MC-dropout)."""
    from .. import autograd
    training = _training if _training is not None else autograd.is_training()
    if (not training and mode != "always") or p <= 0.0:
        return data
    shape = list(data.shape)
    if axes:
        for ax in axes:
            shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# Upsampling / grid ops (reference: bilinear_sampler.cc, upsampling.cc,
# grid_generator.cc)
# ---------------------------------------------------------------------------
@register("UpSampling")
def _upsampling(data, *rest, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", workspace=None):
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


@register("BilinearSampler")
def _bilinear_sampler(data, grid, cudnn_off=None):
    """reference: src/operator/bilinear_sampler.cc — grid in [-1, 1]."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    x1 = x0 + 1; y1 = y0 + 1
    wx1 = gx - x0; wy1 = gy - y0
    wx0 = 1 - wx1; wy0 = 1 - wy1

    def gather(img, yy, xx):
        yv = jnp.clip(yy.astype(jnp.int32), 0, h - 1)
        xv = jnp.clip(xx.astype(jnp.int32), 0, w - 1)
        valid = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1))
        batch_idx = jnp.arange(n).reshape(n, 1, 1)
        vals = img[batch_idx, :, yv, xv]  # (n, ho, wo, c)
        return vals * valid[..., None]

    out = (gather(data, y0, x0) * (wy0 * wx0)[..., None] +
           gather(data, y0, x1) * (wy0 * wx1)[..., None] +
           gather(data, y1, x0) * (wy1 * wx0)[..., None] +
           gather(data, y1, x1) * (wy1 * wx1)[..., None])
    return jnp.transpose(out, (0, 3, 1, 2))


@register("GridGenerator")
def _grid_generator(data, transform_type="affine", target_shape=None):
    h, w = target_shape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx.ravel(), gy.ravel(), ones.ravel()])  # (3, h*w)
    theta = data.reshape(-1, 2, 3)
    out = jnp.einsum("nij,jk->nik", theta, base)  # (n, 2, h*w)
    return out.reshape(-1, 2, h, w)


@register("ROIPooling")
def _roi_pooling(data, rois, pooled_size=None, spatial_scale=1.0):
    """reference: src/operator/roi_pooling.cc (static-shape adaptation)."""
    ph, pw = pooled_size
    n_rois = rois.shape[0]
    _, c, h, w = data.shape

    def one_roi(roi):
        batch = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * spatial_scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * spatial_scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * spatial_scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1).astype(jnp.float32)
        rw = jnp.maximum(x2 - x1 + 1, 1).astype(jnp.float32)
        img = data[batch]
        ys = jnp.arange(h); xs = jnp.arange(w)

        def cell(py, px):
            hs = jnp.floor(py * rh / ph).astype(jnp.int32) + y1
            he = jnp.ceil((py + 1) * rh / ph).astype(jnp.int32) + y1
            ws_ = jnp.floor(px * rw / pw).astype(jnp.int32) + x1
            we = jnp.ceil((px + 1) * rw / pw).astype(jnp.int32) + x1
            m = ((ys[None, :, None] >= hs) & (ys[None, :, None] < he) &
                 (xs[None, None, :] >= ws_) & (xs[None, None, :] < we))
            masked = jnp.where(m, img, -jnp.inf)
            v = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(v), v, 0.0)

        cells = jnp.stack([jnp.stack([cell(py, px) for px in range(pw)])
                           for py in range(ph)])  # (ph, pw, c)
        return jnp.transpose(cells, (2, 0, 1))

    return jax.vmap(one_roi)(rois)


@register("Correlation")
def _correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                 stride2=1, pad_size=0, is_multiply=True):
    """FlowNet patch cross-correlation.
    reference: src/operator/correlation.cc (CorrelationOp) — for every
    displacement on a stride2 grid within ±max_displacement, the kernel-
    window patch dot product (or abs-difference) between data1 and shifted
    data2, normalized by kernel²·C. The displacement loop is a static
    Python unroll: D² shifted elementwise products + one box reduction
    each, which XLA fuses — TPU-friendlier than the reference's per-pixel
    CUDA gather."""
    n, c, h, w = data1.shape
    k = int(kernel_size)
    kr = (k - 1) // 2                       # kernel radius
    md, s1, s2 = int(max_displacement), int(stride1), int(stride2)
    pad = int(pad_size)
    border = md + kr
    ph, pw = h + 2 * pad, w + 2 * pad
    out_h = int(_np.ceil((ph - 2 * border) / float(s1)))
    out_w = int(_np.ceil((pw - 2 * border) / float(s1)))
    ngrid = 2 * (md // s2) + 1              # displacements per axis
    sublen = float(k * k * c)

    p1 = jnp.pad(data1.astype(jnp.float32),
                 ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    p2 = jnp.pad(data2.astype(jnp.float32),
                 ((0, 0), (0, 0), (pad, pad), (pad, pad)))

    def box_sum(x):
        # kernel-window sum at every position (valid), summed over C
        if k == 1:
            return jnp.sum(x, axis=1)
        y = lax.reduce_window(x, 0.0, lax.add,
                              (1, 1, k, k), (1, 1, 1, 1), "valid")
        return jnp.sum(y, axis=1)

    maps = []
    a = p1[:, :, md:ph - md, md:pw - md]
    for dy in range(-(md // s2), md // s2 + 1):
        for dx in range(-(md // s2), md // s2 + 1):
            oy, ox = dy * s2, dx * s2
            # data2 window shifted by the displacement; slices span
            # [md, ph-md) so the first valid k-window is CENTERED at
            # border = md + kr, matching the reference's x1 = x·stride1 +
            # max_displacement + kernel_radius indexing
            b = p2[:, :, md + oy:ph - md + oy, md + ox:pw - md + ox]
            prod = a * b if is_multiply else jnp.abs(a - b)
            maps.append(box_sum(prod) / sublen)
    out = jnp.stack(maps, axis=1)           # (n, ngrid², outH', outW')
    out = out[:, :, ::s1, ::s1]
    return out[:, :, :out_h, :out_w].astype(data1.dtype)


# ---------------------------------------------------------------------------
# embedding-bag style & misc
# ---------------------------------------------------------------------------
@register("dot_scaled")
def _dot_scaled(a, b, scale=1.0):
    return scale * jnp.matmul(a, b)


@register("crop")
def _crop(data, *shape_like, offset=None, h_w=None, num_args=1, center_crop=False):
    if shape_like:
        th, tw = shape_like[0].shape[2:4]
    else:
        th, tw = h_w
    h, w = data.shape[2:4]
    if center_crop:
        oy, ox = (h - th) // 2, (w - tw) // 2
    elif offset is not None:
        oy, ox = offset
    else:
        oy = ox = 0
    return data[:, :, oy:oy + th, ox:ox + tw]


alias("crop", "Crop")


# ---------------------------------------------------------------------------
# fused transformer self-attention op surface
# reference: src/operator/contrib/transformer.cc
# (_contrib_interleaved_matmul_selfatt_qk / _valatt, div_sqrt_dim)
# ---------------------------------------------------------------------------
@register("_contrib_div_sqrt_dim")
def _div_sqrt_dim(data):
    """reference: transformer.cc (DivSqrtDim) — x / sqrt(last_dim)."""
    return data / jnp.sqrt(jnp.float32(data.shape[-1])).astype(data.dtype)


def _split_interleaved(qkv, heads, which):
    """(seq, batch, heads*3*hd) interleaved per head -> (batch*heads, seq,
    hd) for which in {0:q, 1:k, 2:v} — the documented equivalent-code
    layout of the reference op."""
    s, b, e = qkv.shape
    hd = e // (heads * 3)
    t = qkv.reshape(s, b, heads, 3, hd)[:, :, :, which, :]
    return t.transpose(1, 2, 0, 3).reshape(b * heads, s, hd)


@register("_contrib_interleaved_matmul_selfatt_qk")
def _interleaved_matmul_selfatt_qk(queries_keys_values, heads=None):
    """scores[b*h, q, k] = (q . k) / sqrt(head_dim); one MXU batch-matmul
    straight off the interleaved QKV projection (no materialized
    transpose copies — XLA folds the layout into the dot)."""
    q = _split_interleaved(queries_keys_values, heads, 0)
    k = _split_interleaved(queries_keys_values, heads, 1)
    scale = 1.0 / _np.sqrt(q.shape[-1])
    return jnp.einsum("bqd,bkd->bqk", q * q.dtype.type(scale), k)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def _interleaved_matmul_selfatt_valatt(queries_keys_values, attention,
                                       heads=None):
    """(attention @ v) regrouped to (seq, batch, heads*head_dim)."""
    s, b, e = queries_keys_values.shape
    hd = e // (heads * 3)
    v = _split_interleaved(queries_keys_values, heads, 2)
    out = jnp.einsum("bqk,bkd->bqd", attention, v)
    return (out.reshape(b, heads, s, hd).transpose(2, 0, 1, 3)
            .reshape(s, b, heads * hd))

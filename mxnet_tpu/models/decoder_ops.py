"""What the functional decoders share (`qwen3_next`, `laguna`): the product,
the RMS norm, the rotary turn given a table, the SwiGLU.

Weights are float32 and activations have the model's `dtype`: every product
takes its operands in the activations' type with float32 out of the MXU, and
every statistic is float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def dot(x, w):
    """x @ w, operands in x's type, float32 out of the MXU."""
    return jnp.dot(x, w.astype(x.dtype),
                   preferred_element_type=F32).astype(x.dtype)


def rms_norm(x, w, eps, offset=0.0):
    """x * rsqrt(mean(x^2) + eps) * (offset + w) over the last axis, the
    statistic in float32; `offset` 1 for a zero-centred weight."""
    xf = x.astype(F32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * (offset + w if offset else w)).astype(x.dtype)


def rotary(x, inv_freq, factor=1.0):
    """The rotary turn of x (B, S, H, D) by the table `inv_freq` (half,):
    rotate-half pairs (t, t + half) over the first 2 * half dimensions of a
    head, position s turning pair t by s * inv_freq[t]; the rest of the head
    passes through. `factor` multiplies cos and sin (YaRN's)."""
    S, half = x.shape[1], inv_freq.shape[0]
    rot = 2 * half
    angle = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half].astype(F32), x[..., half:rot].astype(F32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    if rot == x.shape[-1]:
        return turned.astype(x.dtype)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rot:]], -1)


def gated_hidden(x, w_gate, w_up):
    """silu(x W_g) * (x W_u) in x's type, the product of the two in
    float32."""
    return (jax.nn.silu(dot(x, w_gate).astype(F32))
            * dot(x, w_up).astype(F32)).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """(silu(x W_g) * (x W_u)) W_d."""
    return dot(gated_hidden(x, w_gate, w_up), w_down)

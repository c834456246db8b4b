"""Qwen3-Next decoder, functional: three Gated DeltaNet layers to one gated
softmax-attention layer, every layer ending in a mixture of experts.

    N(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)          [zero-centred]
    layer l: h = x + Mixer_l(N(x)),  y = h + MoE_l(N(h))
    Mixer_l: gated attention where (l + 1) % full_attention_interval == 0,
             else Gated DeltaNet
    logits = N(x_L) W_head^T, head not tied; loss: mean next-token
             cross-entropy

* Gated attention: ``[q | gate]`` per head from one projection, q and k
  RMS-normed per head, rotary embedding on the first
  ``partial_rotary_factor`` of each head (rotate-half pairs), causal GQA
  through `parallel.flash_attention_bshd`, the output times
  ``sigmoid(gate)`` in front of the output projection.
* Gated DeltaNet: ``[q | k | v | z]`` and ``[b | a]`` from two projections,
  a causal depthwise convolution and SiLU over q|k|v, L2-normed q and k,
  the gated delta rule in chunks (`ops.linear_attention`), a gated RMSNorm
  with ``silu(z)``, the output projection.
* Mixture: `ops.moe.moe_routed` over the experts this chip holds (the
  router is as wide as the model's whole count, `n_routed_experts`), and
  one shared expert behind a sigmoid gate.

One chip's share of a deployment is a configuration of the same code:
`n_experts` held from `first_expert` of `n_routed_experts`, `vocab_size`
rows of the embedding and the head. Weights are float32 (what
`ShardedTrainStep`'s AdamW keeps); activations and the operands of every
product have `dtype`, with float32 out of the MXU; every norm's statistic,
the router, the decays and the scan's state are float32. Each half of a
layer (the mixer, the mixture) is recomputed in the backward pass, so a
step keeps one half-layer's activations.

Left out: the multi-token-prediction module and any auxiliary balance loss.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.linear_attention import (INVERSE_NAME, causal_conv1d,
                                    gated_delta_rule, gated_rms_norm,
                                    l2_normalize)
from ..ops.moe import moe_routed
from ..parallel.flash_attention import flash_attention_bshd
from .decoder_ops import dot as _dot, gated_hidden, rms_norm, rotary
from .losses import linear_cross_entropy

__all__ = ["Qwen3NextConfig", "qwen3_next_init", "qwen3_next_forward",
           "qwen3_next_loss"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 64
    n_routed_experts: int = 512     # the router's width: all experts
    n_experts: int = 512            # held here,
    first_expert: int = 0           # from this one on
    experts_per_token: int = 10
    expert_dim: int = 512
    shared_expert_dim: int = 512
    moe_rows_bound: int | None = None   # None: no routing drops a pair
    norm_eps: float = 1e-6
    dtype: object = jnp.bfloat16

    def is_attention(self, layer):
        return (layer + 1) % self.full_attention_interval == 0


def _conv_channels(cfg):
    return (2 * cfg.linear_key_heads * cfg.linear_key_dim
            + cfg.linear_value_heads * cfg.linear_value_dim)


def qwen3_next_init(key, cfg: Qwen3NextConfig):
    """The parameter tree, float32: matrices normal with sigma 0.02, zero-centred
    norm weights 0, the DeltaNet norm 1, ``A_log = log(U(0, 16))``,
    ``dt_bias = 1``."""
    d = cfg.dim

    def matrix(k, *shape):
        return 0.02 * jax.random.normal(k, shape, F32)

    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {"embed": matrix(keys[0], cfg.vocab_size, d),
              "head": matrix(keys[1], cfg.vocab_size, d),
              "final_norm": {"w": jnp.zeros((d,), F32)},
              "layers": {}}
    E, f, fs = cfg.n_experts, cfg.expert_dim, cfg.shared_expert_dim
    value = cfg.linear_value_heads * cfg.linear_value_dim
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i + 2], 16)
        layer = {"mixer_norm": {"w": jnp.zeros((d,), F32)},
                 "moe_norm": {"w": jnp.zeros((d,), F32)},
                 "moe": {"router": matrix(lk[0], d, cfg.n_routed_experts),
                         "gate": matrix(lk[1], E, d, f),
                         "up": matrix(lk[2], E, d, f),
                         "down": matrix(lk[3], E, f, d),
                         "shared_gate_proj": matrix(lk[4], d, fs),
                         "shared_up": matrix(lk[5], d, fs),
                         "shared_down": matrix(lk[6], fs, d),
                         "shared_gate": matrix(lk[7], d, 1)}}
        if cfg.is_attention(i):
            H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
            layer["attn"] = {"wq": matrix(lk[8], d, 2 * H * D),
                             "wk": matrix(lk[9], d, Hkv * D),
                             "wv": matrix(lk[10], d, Hkv * D),
                             "wo": matrix(lk[11], H * D, d),
                             "q_norm": jnp.zeros((D,), F32),
                             "k_norm": jnp.zeros((D,), F32)}
        else:
            Hv = cfg.linear_value_heads
            layer["gdn"] = {
                "w_qkvz": matrix(lk[8], d, _conv_channels(cfg) + value),
                "w_ba": matrix(lk[9], d, 2 * Hv),
                "conv": matrix(lk[10], _conv_channels(cfg), cfg.conv_kernel),
                "A_log": jnp.log(jax.random.uniform(
                    lk[11], (Hv,), F32, 1e-6, 16.0)),
                "dt_bias": jnp.ones((Hv,), F32),
                "norm": jnp.ones((cfg.linear_value_dim,), F32),
                "w_out": matrix(lk[12], value, d)}
        params["layers"][str(i)] = layer
    return params


def _norm(x, w, eps):
    return rms_norm(x, w, eps, offset=1.0)      # zero-centred weights


def _rotary(x, cfg):
    """Rotary embedding on the first `partial_rotary_factor` of each head of
    x (B, S, H, D), pairs (i, i + half); the rest passes through."""
    rot = int(x.shape[-1] * cfg.partial_rotary_factor)
    return rotary(x, cfg.rope_theta ** (
        -jnp.arange(rot // 2, dtype=F32) * 2.0 / rot))


def _gated_attention(p, x, cfg):
    B, S, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qg = _dot(x, p["wq"]).reshape(B, S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:].reshape(B, S, H * D)
    k = _dot(x, p["wk"]).reshape(B, S, Hkv, D)
    v = _dot(x, p["wv"]).reshape(B, S, Hkv, D)
    q = _rotary(_norm(q, p["q_norm"], cfg.norm_eps), cfg)
    k = _rotary(_norm(k, p["k_norm"], cfg.norm_eps), cfg)
    o = flash_attention_bshd(q, k, v, causal=True).reshape(B, S, H * D)
    o = (o.astype(F32) * jax.nn.sigmoid(gate.astype(F32))).astype(x.dtype)
    return _dot(o, p["wo"])


def _gated_delta_net(p, x, cfg):
    B, S, _ = x.shape
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
    conv = _conv_channels(cfg)
    qkvz = _dot(x, p["w_qkvz"])
    ba = jnp.dot(x, p["w_ba"].astype(x.dtype), preferred_element_type=F32)
    mixed = jax.nn.silu(causal_conv1d(qkvz[..., :conv], p["conv"]))
    z = qkvz[..., conv:].reshape(B, S, Hv, dv)
    q = mixed[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = mixed[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = mixed[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    q = (l2_normalize(q).astype(F32) * dk ** -0.5).astype(x.dtype)
    o = gated_delta_rule(q, l2_normalize(k), v, g, beta, chunk=cfg.chunk)
    o = gated_rms_norm(o, z, p["norm"], cfg.norm_eps)
    return _dot(o.reshape(B, S, Hv * dv), p["w_out"])


def _moe(p, x, cfg):
    B, S, d = x.shape
    x = x.reshape(B * S, d)
    routed = moe_routed(x, p["router"], p["gate"], p["up"], p["down"],
                        cfg.experts_per_token, cfg.first_expert,
                        cfg.moe_rows_bound)
    hidden = gated_hidden(x, p["shared_gate_proj"], p["shared_up"])
    gate = jax.nn.sigmoid(jnp.dot(x, p["shared_gate"].astype(x.dtype),
                                  preferred_element_type=F32))
    shared = (_dot(hidden, p["shared_down"]).astype(F32) * gate
              ).astype(x.dtype)
    return (routed + shared).reshape(B, S, d)


def _mixer_block(lp, x, cfg, attention):
    y = _norm(x, lp["mixer_norm"]["w"], cfg.norm_eps)
    if attention:
        with jax.named_scope("gated_attention"):
            return x + _gated_attention(lp["attn"], y, cfg)
    with jax.named_scope("gdn"):
        return x + _gated_delta_net(lp["gdn"], y, cfg)


def _moe_block(lp, x, cfg):
    with jax.named_scope("moe"):
        return x + _moe(lp["moe"], _norm(x, lp["moe_norm"]["w"],
                                         cfg.norm_eps), cfg)


def qwen3_next_forward(params, tokens, cfg: Qwen3NextConfig):
    """tokens (B, S) int32 -> the normed hidden states (B, S, d) in
    cfg.dtype that the head reads."""
    x = params["embed"][tokens].astype(cfg.dtype)
    # each half of a layer is made again in the backward pass, the two
    # apart: the backward then holds the activations of one of them. The
    # delta rule's chunk inverses are kept: ten products a layer to make
    mixer = jax.checkpoint(
        _mixer_block, static_argnums=(2, 3),
        policy=jax.checkpoint_policies.save_only_these_names(INVERSE_NAME))
    moe = jax.checkpoint(_moe_block, static_argnums=(2,))
    for i in range(cfg.n_layers):
        lp = params["layers"][str(i)]
        x = moe(lp, mixer(lp, x, cfg, cfg.is_attention(i)), cfg)
    return _norm(x, params["final_norm"]["w"], cfg.norm_eps)


def qwen3_next_loss(params, batch, cfg: Qwen3NextConfig):
    """Mean cross-entropy of the next token over positions 0 .. S-2 of
    batch["tokens"] (B, S)."""
    tokens = batch["tokens"]
    h = qwen3_next_forward(params, tokens, cfg)
    with jax.named_scope("lm_head"):
        return linear_cross_entropy(h[:, :-1], params["head"], tokens[:, 1:])

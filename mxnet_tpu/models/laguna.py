"""Laguna decoder, functional: full and sliding-window attention mixed at
per-layer head counts, a per-head output gate, a leading dense layer and a
sigmoid-routed mixture of experts in every other layer.

    N(x; w) = x * rsqrt(mean(x^2) + eps) * w                  [w starts at 1]
    layer l: h = x + A_l(N(x)),  y = h + M_l(N(h))
    A_l: q = x Wq (S, H_l, D), k = x Wk, v = x Wv (S, Hkv, D),
         g = softplus(x Wg) (S, H_l) in float32; q and k RMS-normed per head
         and turned by the layer kind's rotary table; causal GQA over all
         earlier keys (full) or over the last `window` (sliding);
         A_l = concat_h(g_h * o_h) Wo
    M_l: a dense SwiGLU MLP where l is in `dense_layers`, else
         sum over the chosen experts held here of w_e E_e(x) + E_shared(x),
         w from `ops.moe.route_top_k(score="sigmoid", scale=routed_scale)`
    logits = N(x_L) W_head^T, head not tied; loss: mean next-token
             cross-entropy

* A layer's kind and its query heads come from `layer_types` and
  `heads_per_layer`, the published lists: sliding layers have more query
  heads than full ones over the same key-value heads.
* Rotary tables (`rope_table`): a sliding layer turns the whole head at
  `rope_sliding_theta`; a full layer turns the first `rope_full_partial` of
  it under YaRN (the table interpolated by `rope_full_factor` between the
  correction dimensions, cos and sin times `rope_full_attention_factor`).
* Attention is `parallel.flash_attention_bshd`, with `window` in a sliding
  layer: the same kernels sweeping the band's blocks only.

One chip's share of a deployment is a configuration of the same code:
`n_kv_heads` key-value heads held of `n_kv_heads_published`, each with its
query heads (a layer's H_l * n_kv_heads / n_kv_heads_published) and their
columns of Wq and Wg and rows of Wo; `n_experts` held from `first_expert` of
`n_routed_experts`; `vocab_size` rows of the embedding and the head. What
the absent heads and experts would add is another chip's. Weights are
float32 (what `ShardedTrainStep`'s AdamW keeps); activations and the
operands of every product have `dtype`; every norm's statistic, the gate
and the router are float32. Each half of a layer is recomputed in the
backward pass.

Left out: any auxiliary balance loss or selection-bias update.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.moe import moe_routed
from ..parallel.flash_attention import flash_attention_bshd
from .decoder_ops import dot, rms_norm, rotary, swiglu
from .losses import linear_cross_entropy

__all__ = ["LagunaConfig", "laguna_init", "laguna_forward", "laguna_loss",
           "rope_table"]

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 3072
    n_layers: int = 48
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING) * 12
    heads_per_layer: tuple = (48, 72, 72, 72) * 12     # published
    n_kv_heads_published: int = 8
    n_kv_heads: int = 8             # held here, each with its query heads
    head_dim: int = 128
    window: int = 512
    rope_sliding_theta: float = 1e4
    rope_full_theta: float = 5e5
    rope_full_partial: float = 0.5
    rope_full_factor: float = 128.0
    rope_full_original_positions: int = 8192
    rope_full_beta_fast: float = 32.0
    rope_full_beta_slow: float = 1.0
    rope_full_attention_factor: float = 1.4852030263919618
    dense_layers: tuple = (0,)
    dense_dim: int = 12288
    n_routed_experts: int = 256     # the router's width: all experts
    n_experts: int = 256            # held here,
    first_expert: int = 0           # from this one on
    experts_per_token: int = 10
    routed_scale: float = 2.5
    expert_dim: int = 1024
    shared_expert_dim: int = 1024
    moe_rows_bound: int | None = None   # None: no routing drops a pair
    norm_eps: float = 1e-6
    dtype: object = jnp.bfloat16

    def is_sliding(self, layer):
        return self.layer_types[layer] == SLIDING

    def heads(self, layer):
        """Query heads of `layer` held here."""
        return (self.heads_per_layer[layer] * self.n_kv_heads
                // self.n_kv_heads_published)


def yarn_range(cfg: LagunaConfig):
    """(low, high): the rotary pairs between which YaRN's ramp runs, from
    the rotations `beta_fast` and `beta_slow` make over the original
    positions."""
    rot = int(cfg.head_dim * cfg.rope_full_partial)

    def pair(rotations):
        return (rot * math.log(cfg.rope_full_original_positions
                               / (2 * math.pi * rotations))
                / (2 * math.log(cfg.rope_full_theta)))
    return (max(math.floor(pair(cfg.rope_full_beta_fast)), 0),
            min(math.ceil(pair(cfg.rope_full_beta_slow)), rot - 1))


def rope_table(cfg: LagunaConfig, sliding):
    """(inv_freq (half,), factor on cos and sin) of a layer kind."""
    if sliding:
        half = cfg.head_dim // 2
        return cfg.rope_sliding_theta ** (
            -jnp.arange(half, dtype=F32) / half), 1.0
    half = int(cfg.head_dim * cfg.rope_full_partial) // 2
    pair = jnp.arange(half, dtype=F32)
    base = cfg.rope_full_theta ** (-pair / half)
    low, high = yarn_range(cfg)
    ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    return ((1.0 - ramp) * base + ramp * base / cfg.rope_full_factor,
            cfg.rope_full_attention_factor)


def laguna_init(key, cfg: LagunaConfig):
    """The parameter tree, float32: matrices normal with sigma 0.02, norm
    weights 1."""
    d, D = cfg.dim, cfg.head_dim

    def matrix(k, *shape):
        return 0.02 * jax.random.normal(k, shape, F32)

    def ones(*shape):
        return jnp.ones(shape, F32)

    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {"embed": matrix(keys[0], cfg.vocab_size, d),
              "head": matrix(keys[1], cfg.vocab_size, d),
              "final_norm": {"w": ones(d)}, "layers": {}}
    E, f, fs = cfg.n_experts, cfg.expert_dim, cfg.shared_expert_dim
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i + 2], 12)
        H, Hkv = cfg.heads(i), cfg.n_kv_heads
        layer = {"attn_norm": {"w": ones(d)}, "mlp_norm": {"w": ones(d)},
                 "attn": {"wq": matrix(lk[0], d, H * D),
                          "wk": matrix(lk[1], d, Hkv * D),
                          "wv": matrix(lk[2], d, Hkv * D),
                          "wg": matrix(lk[3], d, H),
                          "wo": matrix(lk[4], H * D, d),
                          "q_norm": ones(D), "k_norm": ones(D)}}
        if i in cfg.dense_layers:
            layer["mlp"] = {"gate": matrix(lk[5], d, cfg.dense_dim),
                            "up": matrix(lk[6], d, cfg.dense_dim),
                            "down": matrix(lk[7], cfg.dense_dim, d)}
        else:
            layer["moe"] = {"router": matrix(lk[5], d, cfg.n_routed_experts),
                            "gate": matrix(lk[6], E, d, f),
                            "up": matrix(lk[7], E, d, f),
                            "down": matrix(lk[8], E, f, d),
                            "shared_gate": matrix(lk[9], d, fs),
                            "shared_up": matrix(lk[10], d, fs),
                            "shared_down": matrix(lk[11], fs, d)}
        params["layers"][str(i)] = layer
    return params


def _attention(p, x, cfg, layer):
    B, S, _ = x.shape
    H, Hkv, D = cfg.heads(layer), cfg.n_kv_heads, cfg.head_dim
    sliding = cfg.is_sliding(layer)
    q = dot(x, p["wq"]).reshape(B, S, H, D)
    k = dot(x, p["wk"]).reshape(B, S, Hkv, D)
    v = dot(x, p["wv"]).reshape(B, S, Hkv, D)
    gate = jax.nn.softplus(jnp.dot(x, p["wg"].astype(x.dtype),
                                   preferred_element_type=F32))
    table, factor = rope_table(cfg, sliding)
    q = rotary(rms_norm(q, p["q_norm"], cfg.norm_eps), table, factor)
    k = rotary(rms_norm(k, p["k_norm"], cfg.norm_eps), table, factor)
    o = flash_attention_bshd(q, k, v, causal=True,
                             window=cfg.window if sliding else None)
    o = (o.astype(F32) * gate[..., None]).astype(x.dtype)
    return dot(o.reshape(B, S, H * D), p["wo"])


def _moe(p, x, cfg):
    B, S, d = x.shape
    x = x.reshape(B * S, d)
    routed = moe_routed(x, p["router"], p["gate"], p["up"], p["down"],
                        cfg.experts_per_token, cfg.first_expert,
                        cfg.moe_rows_bound, score="sigmoid",
                        scale=cfg.routed_scale)
    shared = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return (routed + shared).reshape(B, S, d)


def _attention_block(lp, x, cfg, layer):
    y = rms_norm(x, lp["attn_norm"]["w"], cfg.norm_eps)
    with jax.named_scope("window_attention" if cfg.is_sliding(layer)
                         else "full_attention"):
        return x + _attention(lp["attn"], y, cfg, layer)


def _mlp_block(lp, x, cfg, layer):
    y = rms_norm(x, lp["mlp_norm"]["w"], cfg.norm_eps)
    if layer in cfg.dense_layers:
        with jax.named_scope("dense_mlp"):
            return x + swiglu(y, lp["mlp"]["gate"], lp["mlp"]["up"],
                              lp["mlp"]["down"])
    with jax.named_scope("moe"):
        return x + _moe(lp["moe"], y, cfg)


def laguna_forward(params, tokens, cfg: LagunaConfig):
    """tokens (B, S) int32 -> the normed hidden states (B, S, d) in
    cfg.dtype that the head reads."""
    x = params["embed"][tokens].astype(cfg.dtype)
    # each half of a layer is made again in the backward pass, the two
    # apart: the backward then holds the activations of one of them
    attention = jax.checkpoint(_attention_block, static_argnums=(2, 3))
    mlp = jax.checkpoint(_mlp_block, static_argnums=(2, 3))
    for i in range(cfg.n_layers):
        lp = params["layers"][str(i)]
        x = mlp(lp, attention(lp, x, cfg, i), cfg, i)
    return rms_norm(x, params["final_norm"]["w"], cfg.norm_eps)


def laguna_loss(params, batch, cfg: LagunaConfig):
    """Mean cross-entropy of the next token over positions 0 .. S-2 of
    batch["tokens"] (B, S)."""
    tokens = batch["tokens"]
    h = laguna_forward(params, tokens, cfg)
    with jax.named_scope("lm_head"):
        return linear_cross_entropy(h[:, :-1], params["head"], tokens[:, 1:])

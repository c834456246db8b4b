"""Llama-family decoder (RMSNorm + RoPE + GQA + SwiGLU), TPU-native.

The reference has no transformer model zoo (GluonNLP was external; the only
in-tree attention helpers are the fused ops in
``src/operator/contrib/transformer.cc``). This module is the flagship model
of the TPU build: a pure-functional param-tree decoder whose parameter
naming (``layers/<i>/attn/wq`` …) is what
:data:`mxnet_tpu.parallel.sharding.LLAMA_RULES` keys on, so the same model
runs single-chip, TP+FSDP over an ICI mesh (GSPMD via ShardedTrainStep), or
sequence-parallel (ring attention under shard_map).

Design notes (TPU-first):
  * all matmuls are (B*S, D) x (D, F) shaped — large, static, MXU-friendly;
  * compute dtype bf16 with fp32 RMSNorm accumulation and fp32 softmax
    inside the Pallas flash-attention kernel;
  * the layer stack is a Python loop over per-layer param dicts (static
    unroll) — XLA pipelines it; `remat=True` wraps each layer in
    jax.checkpoint to trade FLOPs for HBM;
  * KV-cached single-token decode uses the same weights with
    `lax.dynamic_update_slice` caches, static shapes throughout.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.flash_attention import (flash_attention, paged_attention,
                                        paged_attention_chunk)
from ..parallel.ring_attention import ring_attention
from .losses import linear_cross_entropy

__all__ = ["LlamaConfig", "llama_init", "llama_forward", "llama_loss",
           "init_kv_cache", "llama_decode_step", "init_kv_pools",
           "llama_prefill_paged", "llama_decode_paged", "llama_chunk_paged",
           "llama_draft_loop", "CONFIGS"]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    max_seq_len: int = 8192
    dtype: object = jnp.bfloat16
    remat: bool = False
    tie_embeddings: bool = False
    # One-hot-matmul embedding lookup instead of gather. Used when the vocab
    # dim of tok_embeddings is sharded over the mesh: the gather's backward
    # is a scatter-add whose updates are batch-sharded while the table is
    # vocab-sharded — the SPMD partitioner fully replicates it ("Involuntary
    # full rematerialization"). As a matmul, fwd and bwd both partition
    # cleanly (reduce-scatter over the vocab axis) and run on the MXU.
    embed_onehot: bool = False

    @property
    def head_dim(self):
        return self.dim // self.n_heads


CONFIGS = {
    # Llama-3-8B — BASELINE.json configs[4] (the pod-scale north star).
    "llama3_8b": LlamaConfig(vocab_size=128256, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, hidden_dim=14336,
                             rope_theta=500000.0, max_seq_len=8192,
                             embed_onehot=True),
    # 8B layer shapes at reduced depth/vocab/context — validates the
    # v5e-64 plan's program on a host-CPU virtual mesh (every layer
    # dimension identical to llama3_8b; only depth-like axes shrink).
    "llama3_8b_dry": LlamaConfig(vocab_size=8192, dim=4096, n_layers=2,
                                 n_heads=32, n_kv_heads=8, hidden_dim=14336,
                                 rope_theta=500000.0, max_seq_len=512,
                                 remat=True, embed_onehot=True),
    # ~110M single-chip benchmark model.
    "llama_110m": LlamaConfig(vocab_size=32000, dim=768, n_layers=12,
                              n_heads=12, n_kv_heads=12, hidden_dim=2048,
                              rope_theta=10000.0, max_seq_len=2048),
    # tiny configs for tests / dryruns.
    "llama_tiny": LlamaConfig(vocab_size=256, dim=64, n_layers=2,
                              n_heads=4, n_kv_heads=2, hidden_dim=128,
                              rope_theta=10000.0, max_seq_len=128),
}


# ------------------------------------------------------------------- init
def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def llama_init(key, cfg: LlamaConfig):
    """Parameter pytree. Weight layouts chosen for MXU-natural x @ W:
    projections are (in_features, out_features); embeddings (vocab, dim)."""
    d, hd, kvd = cfg.dim, cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qd = cfg.n_heads * hd
    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {
        "tok_embeddings": _dense_init(keys[0], (cfg.vocab_size, d),
                                      cfg.dtype, scale=0.02),
        "norm": jnp.ones((d,), jnp.float32),
        "layers": {},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[1], (cfg.vocab_size, d),
                                        cfg.dtype, scale=0.02)
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i + 2], 7)
        params["layers"][str(i)] = {
            "attn_norm": jnp.ones((d,), jnp.float32),
            "attn": {
                "wq": _dense_init(lk[0], (d, qd), cfg.dtype),
                "wk": _dense_init(lk[1], (d, kvd), cfg.dtype),
                "wv": _dense_init(lk[2], (d, kvd), cfg.dtype),
                "wo": _dense_init(lk[3], (qd, d), cfg.dtype),
            },
            "ffn_norm": jnp.ones((d,), jnp.float32),
            "mlp": {
                "w1": _dense_init(lk[4], (d, cfg.hidden_dim), cfg.dtype),
                "w2": _dense_init(lk[5], (cfg.hidden_dim, d), cfg.dtype),
                "w3": _dense_init(lk[6], (d, cfg.hidden_dim), cfg.dtype),
            },
        }
    return params


# ---------------------------------------------------------------- kernels
def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope_freqs(positions, head_dim, theta):
    """positions (…,S) int32 → cos/sin (…,S, head_dim/2) fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (B,H,S,D); cos/sin (S,D/2) or (B,S,D/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:        # (S, D/2) — broadcast over batch and heads
        c, s = cos[None, None], sin[None, None]
    else:                    # (B, S, D/2)
        c, s = cos[:, None], sin[:, None]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _attention(lp, x, cos, sin, cfg, seq_axis=None):
    B, S, _ = x.shape
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["attn"]["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (h @ lp["attn"]["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["attn"]["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
    k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
    v = v.transpose(0, 2, 1, 3)
    if seq_axis is not None:
        o = ring_attention(q, k, v, axis_name=seq_axis, causal=True)
    else:
        o = flash_attention(q, k, v, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
    return x + o @ lp["attn"]["wo"]


def _mlp(lp, x, cfg):
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    gate = jax.nn.silu(h @ lp["mlp"]["w1"])
    out = (gate * (h @ lp["mlp"]["w3"])) @ lp["mlp"]["w2"]
    return x + out


def _layer(lp, x, cos, sin, cfg, seq_axis=None):
    return _mlp(lp, _attention(lp, x, cos, sin, cfg, seq_axis), cfg)


def _hidden(params, tokens, cfg: LlamaConfig, seq_axis=None, positions=None):
    """tokens (B,S) int32 → the final norm's output (B,S,dim) in cfg.dtype,
    and the (vocab, dim) head that reads it."""
    B, S = tokens.shape
    if cfg.embed_onehot:
        oh = jax.nn.one_hot(tokens, cfg.vocab_size,
                            dtype=params["tok_embeddings"].dtype)
        x = oh @ params["tok_embeddings"]
    else:
        x = params["tok_embeddings"][tokens]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
        if seq_axis is not None:
            positions = positions + lax.axis_index(seq_axis) * S
    cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
    layer = _layer
    if cfg.remat:
        layer = jax.checkpoint(
            functools.partial(_layer, cfg=cfg, seq_axis=seq_axis),
            static_argnums=())
        for i in range(cfg.n_layers):
            x = layer(params["layers"][str(i)], x, cos, sin)
    else:
        for i in range(cfg.n_layers):
            x = layer(params["layers"][str(i)], x, cos, sin, cfg, seq_axis)
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    head = params["tok_embeddings"] if cfg.tie_embeddings else params["lm_head"]
    return x, head


def llama_forward(params, tokens, cfg: LlamaConfig, seq_axis=None,
                  positions=None):
    """tokens (B,S) int32 → logits (B,S,vocab) fp32.

    seq_axis: name of a mesh axis tokens are sequence-sharded over; attention
    then runs as ring attention (call under shard_map). positions overrides
    the default iota (needed for the sequence-sharded case)."""
    x, head = _hidden(params, tokens, cfg, seq_axis, positions)
    return (x @ head.T.astype(x.dtype)).astype(jnp.float32)


def llama_loss(params, batch, cfg: LlamaConfig, seq_axis=None):
    """Next-token cross entropy. batch = {'tokens': (B,S+1) int32} or a
    (B,S+1) array; fp32 logits and statistics for numerical safety."""
    tokens = batch["tokens"] if isinstance(batch, dict) else batch
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x, head = _hidden(params, inp, cfg, seq_axis)
    return linear_cross_entropy(x, head, tgt)


# -------------------------------------------------------------- decoding
def init_kv_cache(cfg: LlamaConfig, batch, max_len=None, dtype=None):
    max_len = max_len or cfg.max_seq_len
    dtype = dtype or cfg.dtype
    shape = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {str(i): {"k": jnp.zeros(shape, dtype),
                     "v": jnp.zeros(shape, dtype)}
            for i in range(cfg.n_layers)}


# ------------------------------------------------------- paged decoding
# The serving runtime (mxnet_tpu.serve) stores KV in fixed-size blocks
# inside ONE physical pool per layer instead of a (batch, max_seq_len)
# rectangle per stream: a stream costs exactly the blocks its context
# fills, and blocks recycle through a free-list as streams finish
# (serve.kv_cache.KVBlockPool owns the bookkeeping; these functions are the
# jitted compute). Positions map to pool slots through per-stream block
# tables; table entries >= num_blocks are unallocated — their writes DROP
# (lax scatter mode) and their reads are discarded by the length mask, so
# one fixed-shape program serves every context length in the bucket.

def init_kv_pools(cfg: LlamaConfig, num_blocks, block_size, dtype=None):
    """The physical paged KV pool: per layer, (num_blocks, n_kv_heads,
    block_size, head_dim) for k (post-RoPE) and v."""
    dtype = dtype or cfg.dtype
    shape = (num_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    return {str(i): {"k": jnp.zeros(shape, dtype),
                     "v": jnp.zeros(shape, dtype)}
            for i in range(cfg.n_layers)}


def llama_prefill_paged(params, pools, tokens, length, block_table,
                        cfg: LlamaConfig, block_size):
    """Bucketed prefill: run the context through the stack once, write its
    KV into the paged pool, return the next-token logits.

    tokens (S,) int32 right-padded to the bucket size; length () int32 true
    context length; block_table (S // block_size,) int32 pool block per
    logical block (entries >= num_blocks are dropped). Returns
    (logits (vocab,) fp32 at position length-1, new pools).

    Embedding is always the gather path — `embed_onehot` exists for the
    *backward* scatter-add under vocab sharding, which inference never runs.
    """
    S = tokens.shape[0]
    num_blocks = pools["0"]["k"].shape[0]
    x = params["tok_embeddings"][tokens][None]               # (1,S,D)
    positions = jnp.arange(S, dtype=jnp.int32)
    cos, sin = rope_freqs(positions, cfg.head_dim, cfg.rope_theta)
    blk = block_table[positions // block_size]
    # pad rows write nowhere (their k/v rows are garbage-by-construction)
    blk = jnp.where(positions < length, blk, num_blocks)
    off = positions % block_size
    new_pools = {}
    for i in range(cfg.n_layers):
        lp = params["layers"][str(i)]
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["attn"]["wq"]).reshape(1, S, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["attn"]["wk"]).reshape(1, S, cfg.n_kv_heads,
                                           cfg.head_dim)
        v = (h @ lp["attn"]["wv"]).reshape(1, S, cfg.n_kv_heads,
                                           cfg.head_dim)
        q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
        v = v.transpose(0, 2, 1, 3)
        pk = pools[str(i)]["k"].at[blk, :, off].set(
            k[0].transpose(1, 0, 2), mode="drop")
        pv = pools[str(i)]["v"].at[blk, :, off].set(
            v[0].transpose(1, 0, 2), mode="drop")
        new_pools[str(i)] = {"k": pk, "v": pv}
        o = flash_attention(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(1, S, -1)
        x = x + o @ lp["attn"]["wo"]
        x = _mlp(lp, x, cfg)
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    last = lax.dynamic_index_in_dim(x[0], length - 1, axis=0,
                                    keepdims=False)
    head = params["tok_embeddings"] if cfg.tie_embeddings else params["lm_head"]
    logits = (last @ head.T.astype(last.dtype)).astype(jnp.float32)
    return logits, new_pools


def llama_decode_paged(params, pools, tokens, positions, block_tables,
                       cfg: LlamaConfig, block_size):
    """One continuous-batching decode step over the paged pool.

    tokens (B,) int32 — the token each stream feeds this step (its newest
    emitted token); positions (B,) int32 — that token's position, or -1
    for an inactive batch slot (write dropped, logits ignored by the
    caller); block_tables (B, nb) int32. Returns (logits (B, vocab) fp32,
    new pools). Shapes are fixed by (B, nb): requests join and leave the
    running batch between steps without ever changing the signature.
    """
    B = tokens.shape[0]
    num_blocks = pools["0"]["k"].shape[0]
    active = positions >= 0
    pos = jnp.maximum(positions, 0)
    x = params["tok_embeddings"][tokens][:, None, :]         # (B,1,D)
    cos, sin = rope_freqs(pos[:, None], cfg.head_dim, cfg.rope_theta)
    blk = jnp.take_along_axis(block_tables, (pos // block_size)[:, None],
                              axis=1)[:, 0]
    # inactive slots AND positions past the table drop their writes (an
    # out-of-range gather index would clamp onto the last real block —
    # the speculative draft loop can run past the reserved range)
    in_range = pos // block_size < block_tables.shape[1]
    blk = jnp.where(active & in_range, blk, num_blocks)
    off = pos % block_size
    lengths = pos + 1          # inactive slots read one masked garbage row
    new_pools = {}
    for i in range(cfg.n_layers):
        lp = params["layers"][str(i)]
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["attn"]["wq"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["attn"]["wk"]).reshape(B, 1, cfg.n_kv_heads,
                                           cfg.head_dim)
        v = (h @ lp["attn"]["wv"]).reshape(B, 1, cfg.n_kv_heads,
                                           cfg.head_dim)
        q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
        v = v.transpose(0, 2, 1, 3)
        pk = pools[str(i)]["k"].at[blk, :, off].set(k[:, :, 0, :],
                                                    mode="drop")
        pv = pools[str(i)]["v"].at[blk, :, off].set(v[:, :, 0, :],
                                                    mode="drop")
        new_pools[str(i)] = {"k": pk, "v": pv}
        o = paged_attention(q, pk, pv, block_tables, lengths)
        x = x + o.transpose(0, 2, 1, 3).reshape(B, 1, -1) @ lp["attn"]["wo"]
        x = _mlp(lp, x, cfg)
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    head = params["tok_embeddings"] if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, 0] @ head.T.astype(x.dtype)).astype(jnp.float32)
    return logits, new_pools


def llama_chunk_paged(params, pools, tokens, positions, block_tables,
                      cfg: LlamaConfig, block_size, logits_at="last"):
    """Multi-row chunk forward over the paged pool — the one program shape
    behind BOTH chunked prefill and speculative verify.

    Each row b carries a window of C consecutive context tokens for one
    stream: tokens[b, c] sits at absolute position positions[b, c]
    (position -1 = padding — its KV write drops and its output is
    garbage the caller ignores). The chunk's KV is scattered into the
    pool layer by layer BEFORE that layer's attention gathers, so queries
    see the whole causal context: earlier chunks of the same stream, a
    shared prompt prefix, and earlier tokens of this very chunk —
    processing a prompt chunk-by-chunk is bit-for-bit the same math as
    one monolithic prefill, and several rows may even be consecutive
    chunks of ONE stream (each row's queries mask by absolute position).

    tokens (B, C) int32; positions (B, C) int32; block_tables (B, nb)
    int32. Returns (logits, new_pools): logits_at="last" projects only
    each row's LAST valid position ((B, vocab) — the chunked-prefill
    next-token read, one vocab row per stream, never C); "all" projects
    every position ((B, C, vocab) — speculative verify needs the greedy
    token at each drafted position).
    """
    B, C = tokens.shape
    num_blocks = pools["0"]["k"].shape[0]
    active = positions >= 0
    pos = jnp.maximum(positions, 0)
    x = params["tok_embeddings"][tokens]                     # (B,C,D)
    cos, sin = rope_freqs(pos, cfg.head_dim, cfg.rope_theta)
    blk = jnp.take_along_axis(block_tables, pos // block_size, axis=1)
    # pads drop; so do positions past the table — the gather would CLAMP
    # an out-of-range index onto the last real block and overwrite live
    # KV rows, so out-of-range writes must vanish, not wrap
    in_range = pos // block_size < block_tables.shape[1]
    blk = jnp.where(active & in_range, blk, num_blocks)
    off = pos % block_size
    lengths = pos + 1          # per-query causal horizon (pads read row 0)
    new_pools = {}
    for i in range(cfg.n_layers):
        lp = params["layers"][str(i)]
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["attn"]["wq"]).reshape(B, C, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["attn"]["wk"]).reshape(B, C, cfg.n_kv_heads,
                                           cfg.head_dim)
        v = (h @ lp["attn"]["wv"]).reshape(B, C, cfg.n_kv_heads,
                                           cfg.head_dim)
        q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
        v = v.transpose(0, 2, 1, 3)
        # scatter the chunk's KV, THEN gather: each query's mask stops at
        # its own position, so the later rows of the window read the
        # earlier rows' keys through the pool
        pk = pools[str(i)]["k"].at[blk, :, off].set(
            k.transpose(0, 2, 1, 3), mode="drop")
        pv = pools[str(i)]["v"].at[blk, :, off].set(
            v.transpose(0, 2, 1, 3), mode="drop")
        new_pools[str(i)] = {"k": pk, "v": pv}
        o = paged_attention_chunk(q, pk, pv, block_tables, lengths)
        x = x + o.transpose(0, 2, 1, 3).reshape(B, C, -1) @ lp["attn"]["wo"]
        x = _mlp(lp, x, cfg)
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    head = params["tok_embeddings"] if cfg.tie_embeddings else params["lm_head"]
    if logits_at == "last":
        # last valid column per row (fully-padded rows read column 0 —
        # garbage the scheduler never looks at)
        last = jnp.maximum(jnp.sum(active.astype(jnp.int32), axis=1) - 1, 0)
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return (x @ head.T.astype(x.dtype)).astype(jnp.float32), new_pools


def llama_draft_loop(params, pools, tokens, positions, block_tables,
                     cfg: LlamaConfig, block_size, k):
    """k greedy decode steps in ONE program — the speculative-decoding
    draft. Statically unrolled: step i feeds step i-1's argmax, writes the
    draft model's KV as it goes (position -1 = inactive slot throughout).

    tokens/positions (B,) int32, block_tables (B, nb) int32. Returns
    (draft tokens (B, k) int32, new pools)."""
    drafted = []
    tok, pos = tokens, positions
    for _ in range(int(k)):
        logits, pools = llama_decode_paged(params, pools, tok, pos,
                                           block_tables, cfg, block_size)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        drafted.append(tok)
        pos = jnp.where(positions >= 0, pos + 1, positions)
    # one extra write-only pass: the LAST draft's KV must land too, or a
    # fully-accepted round leaves a hole the NEXT round's draft attends
    # through (stale row -> dropped accept rate, never wrong output)
    _, pools = llama_decode_paged(params, pools, tok, pos, block_tables,
                                  cfg, block_size)
    return jnp.stack(drafted, axis=1), pools


def llama_decode_step(params, cache, token, pos, cfg: LlamaConfig):
    """One token of KV-cached autoregressive decode.

    token (B,) int32, pos () int32 → (logits (B,vocab), new cache). Static
    shapes: the attention mask is derived from `pos`, so this jits once and
    runs for every position (the BucketingModule problem solved the XLA way).
    """
    B = token.shape[0]
    x = params["tok_embeddings"][token][:, None, :]          # (B,1,D)
    cos, sin = rope_freqs(pos[None], cfg.head_dim, cfg.rope_theta)
    new_cache = {}
    max_len = cache["0"]["k"].shape[2]
    mask = (jnp.arange(max_len) <= pos)[None, None, None, :]
    for i in range(cfg.n_layers):
        lp = params["layers"][str(i)]
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["attn"]["wq"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["attn"]["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["attn"]["wv"]).reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
        k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
        v = v.transpose(0, 2, 1, 3)
        ck = lax.dynamic_update_slice(cache[str(i)]["k"], k, (0, 0, pos, 0))
        cv = lax.dynamic_update_slice(cache[str(i)]["v"], v, (0, 0, pos, 0))
        new_cache[str(i)] = {"k": ck, "v": cv}
        rep = cfg.n_heads // cfg.n_kv_heads
        kk = jnp.repeat(ck, rep, axis=1) if rep > 1 else ck
        vv = jnp.repeat(cv, rep, axis=1) if rep > 1 else cv
        scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * (cfg.head_dim ** -0.5)
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs,
                       vv.astype(jnp.float32)).astype(x.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(B, 1, -1)
        x = x + o @ lp["attn"]["wo"]
        x = _mlp(lp, x, cfg)
    x = rms_norm(x, params["norm"], cfg.norm_eps)
    head = params["tok_embeddings"] if cfg.tie_embeddings else params["lm_head"]
    logits = (x[:, 0] @ head.T.astype(x.dtype)).astype(jnp.float32)
    return logits, new_cache

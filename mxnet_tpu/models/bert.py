"""BERT encoder, TPU-native (BASELINE.json configs[2]: BERT-base).

The reference served BERT through external GluonNLP built on the fused
attention ops in ``src/operator/contrib/transformer.cc``
(``_contrib_interleaved_matmul_selfatt_qk`` etc.); here the whole encoder is
first-class. Param names (``word_embed``, ``layers/<i>/attn/wq``,
``ffn/w1`` …) match :data:`mxnet_tpu.parallel.sharding.BERT_RULES` so the
same tree shards TP+FSDP on a mesh.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..parallel.flash_attention import flash_attention_packed, pack_qkv
from .llama import _dense_init
from .losses import linear_cross_entropy

__all__ = ["BertConfig", "bert_init", "bert_forward", "bert_mlm_loss",
           "CONFIGS"]


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    hidden_dim: int = 3072
    max_seq_len: int = 512
    n_types: int = 2
    norm_eps: float = 1e-12
    dtype: object = jnp.bfloat16
    remat: bool = False

    @property
    def head_dim(self):
        return self.dim // self.n_heads


CONFIGS = {
    "bert_base": BertConfig(),
    "bert_large": BertConfig(dim=1024, n_layers=24, n_heads=16,
                             hidden_dim=4096),
    "bert_tiny": BertConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                            hidden_dim=128, max_seq_len=128),
}


def bert_init(key, cfg: BertConfig):
    d = cfg.dim
    keys = jax.random.split(key, cfg.n_layers + 4)
    params = {
        "word_embed": _dense_init(keys[0], (cfg.vocab_size, d), cfg.dtype,
                                  scale=0.02),
        "position_embed": _dense_init(keys[1], (cfg.max_seq_len, d),
                                      cfg.dtype, scale=0.02),
        "token_type_embed": _dense_init(keys[2], (cfg.n_types, d),
                                        cfg.dtype, scale=0.02),
        "embed_norm": {"gamma": jnp.ones((d,), jnp.float32),
                       "beta": jnp.zeros((d,), jnp.float32)},
        "layers": {},
    }
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[i + 3], 6)
        params["layers"][str(i)] = {
            "attn": {
                "wq": _dense_init(lk[0], (d, d), cfg.dtype),
                "wk": _dense_init(lk[1], (d, d), cfg.dtype),
                "wv": _dense_init(lk[2], (d, d), cfg.dtype),
                "wo": _dense_init(lk[3], (d, d), cfg.dtype),
                "bq": jnp.zeros((d,), cfg.dtype),
                "bk": jnp.zeros((d,), cfg.dtype),
                "bv": jnp.zeros((d,), cfg.dtype),
                "bo": jnp.zeros((d,), cfg.dtype),
            },
            "attn_norm": {"gamma": jnp.ones((d,), jnp.float32),
                          "beta": jnp.zeros((d,), jnp.float32)},
            "ffn": {
                "w1": _dense_init(lk[4], (d, cfg.hidden_dim), cfg.dtype),
                "b1": jnp.zeros((cfg.hidden_dim,), cfg.dtype),
                "w2": _dense_init(lk[5], (cfg.hidden_dim, d), cfg.dtype),
                "b2": jnp.zeros((d,), cfg.dtype),
            },
            "ffn_norm": {"gamma": jnp.ones((d,), jnp.float32),
                         "beta": jnp.zeros((d,), jnp.float32)},
        }
    return params


def layer_norm(x, p, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["gamma"]
            + p["beta"]).astype(x.dtype)


def _packed_projection(a, n_heads):
    """wq|wk|wv and bq|bk|bv as the one weight and bias of the q|k|v
    projection, packed inside the step (7 MB a layer at BERT-base): the six
    leaves stay what they are, and their gradients come back through the
    packing. The barrier has them written once: left to XLA, the
    concatenation is fused into the product's operand and costs the product
    0.1 ms a layer on the v5e."""
    return jax.lax.optimization_barrier(
        (pack_qkv(a["wq"], a["wk"], a["wv"], n_heads),
         pack_qkv(a["bq"], a["bk"], a["bv"], n_heads)))


def _encoder_layer(lp, x, cfg):
    a = lp["attn"]
    # one q|k|v projection (upstream's interleaved_matmul_selfatt). The
    # kernels read their blocks out of the product's result and write its
    # cotangent in place: no slice, transpose or concatenation of an
    # activation either way. The bias is added inside the entry, which
    # returns its gradient from the kernels and spares a pass over the
    # cotangent
    # the scopes name a layer's two halves in the compiled step
    # (`telemetry.module_scopes()`); they change no operation
    with jax.named_scope("attention"):
        w, b = _packed_projection(a, cfg.n_heads)
        o = flash_attention_packed(x @ w, cfg.n_heads, bias=b)
        x = layer_norm(x + (o @ a["wo"] + a["bo"]), lp["attn_norm"],
                       cfg.norm_eps)
    with jax.named_scope("ffn"):
        f = lp["ffn"]
        h = jax.nn.gelu(x @ f["w1"] + f["b1"], approximate=True)
        return layer_norm(x + (h @ f["w2"] + f["b2"]), lp["ffn_norm"],
                          cfg.norm_eps)


def bert_forward(params, tokens, cfg: BertConfig, token_types=None):
    """tokens (B,S) int32 → hidden states (B,S,D) in cfg.dtype."""
    B, S = tokens.shape
    with jax.named_scope("embedding"):
        x = params["word_embed"][tokens]
        x = x + params["position_embed"][None, :S]
        if token_types is None:
            x = x + params["token_type_embed"][0][None, None]
        else:
            x = x + params["token_type_embed"][token_types]
        x = layer_norm(x, params["embed_norm"], cfg.norm_eps)
    layer = (jax.checkpoint(_encoder_layer, static_argnums=(2,))
             if cfg.remat else _encoder_layer)
    for i in range(cfg.n_layers):
        x = layer(params["layers"][str(i)], x, cfg)
    return x


def bert_mlm_loss(params, batch, cfg: BertConfig):
    """Masked-LM loss with weight-tied decoder (hidden @ word_embed.T).
    batch = {'tokens', 'targets', 'mask'} each (B,S); mask 1 where the
    position is an MLM prediction site."""
    h = bert_forward(params, batch["tokens"], cfg)
    # every position goes through the decoder; the scope names the head's
    # operations in a device trace
    with jax.named_scope("mlm_head"):
        return linear_cross_entropy(h, params["word_embed"],
                                    batch["targets"], batch["mask"])

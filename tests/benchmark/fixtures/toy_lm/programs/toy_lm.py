"""The toy causal language model as a user of `mxnet_tpu` writes it: the
library's flash attention, causal, inside a functional loss for
`parallel.ShardedTrainStep`."""
import jax
import jax.numpy as jnp

from mxnet_tpu.parallel.flash_attention import flash_attention_bshd


def loss_fn(cfg):
    H, eps = cfg["n_heads"], cfg["norm_eps"]

    def rms(x, norm):
        scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return x * scale * norm["g"]

    def loss(params, batch):
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = params["word_embed"][tokens] + params["position_embed"][:S]
        for i in range(cfg["n_layers"]):
            layer = params["layers"][str(i)]
            y = rms(x, layer["attn_norm"])
            q, k, v = ((y @ layer["attn"]["w" + w]).reshape(B, S, H, -1)
                       for w in "qkv")
            o = flash_attention_bshd(q, k, v, causal=True)
            x = x + o.reshape(B, S, -1) @ layer["attn"]["wo"]
            y = rms(x, layer["ffn_norm"])
            x = x + jax.nn.gelu(y @ layer["ffn"]["w1"],
                                approximate=True) @ layer["ffn"]["w2"]
        logits = rms(x, params["final_norm"]) @ params["word_embed"].T
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)
    return loss

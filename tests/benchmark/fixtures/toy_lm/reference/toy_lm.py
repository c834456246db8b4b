"""Plain reference of a toy causal language model: what a later PR's new
architecture brings, at the least size that shows every piece.

  embed  word[token] + position[:S]
  layer  x = x + (softmax(causal(q k^T / sqrt(D))) v) Wo, q, k, v = rms(x) W
         x = x + gelu_tanh(rms(x) W1) W2                       [pre-norm]
  loss   logits = rms(x) word^T; the mean of -log softmax at the next token,
         over every position but the last
rms(x) = x / sqrt(mean(x^2) + eps) * g. AdamW as `reference/bert.py` has it.
Float32 at `highest`; it imports nothing of the program.

Beside the model it brings what the harness's own tables lack: its traffic
kind (`KINDS`), its FLOPs a token (`TRAIN_FLOPS_PER_SAMPLE`) and the work of
its attention kernel (`KERNEL_WORK`).
"""
import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def leaves(cfg):
    """[(name, shape, kind)]; kind: matrix | gain."""
    d, h = cfg["dim"], cfg["hidden_dim"]
    out = [("word_embed", (cfg["vocab_size"], d), "matrix"),
           ("position_embed", (cfg["max_seq_len"], d), "matrix")]
    for i in range(cfg["n_layers"]):
        pre = "layers.%d." % i
        out += [(pre + "attn_norm.g", (d,), "gain")]
        out += [(pre + "attn.w" + w, (d, d), "matrix") for w in "qkvo"]
        out += [(pre + "ffn_norm.g", (d,), "gain"),
                (pre + "ffn.w1", (d, h), "matrix"),
                (pre + "ffn.w2", (h, d), "matrix")]
    return out + [("final_norm.g", (d,), "gain")]


def storage_dtype(kind, cfg):
    return F32


def init_params(key, cfg):
    table = leaves(cfg)
    return {name: (jnp.ones(shape, F32) if kind == "gain" else
                   0.02 * jax.random.normal(k, shape, F32))
            for k, (name, shape, kind) in zip(
                jax.random.split(key, len(table)), table)}


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _dot(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def loss_fn(params, batch, cfg, mode="f32"):
    tokens = batch["tokens"]
    B, S = tokens.shape
    H, eps = cfg["n_heads"], cfg["norm_eps"]
    x = params["word_embed"][tokens] + params["position_embed"][None, :S]
    for i in range(cfg["n_layers"]):
        p = {k.split(".", 2)[2]: v for k, v in params.items()
             if k.startswith("layers.%d." % i)}
        y = _rms(x, p["attn_norm.g"], eps)
        q, k, v = (_dot(y, p["attn.w" + w]).reshape(B, S, H, -1)
                   for w in "qkv")
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
        scores = scores * q.shape[-1] ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -1e30)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                       precision=HIGHEST).reshape(B, S, -1)
        x = x + _dot(o, p["attn.wo"])
        y = _rms(x, p["ffn_norm.g"], eps)
        x = x + _dot(jax.nn.gelu(_dot(y, p["ffn.w1"]), approximate=True),
                     p["ffn.w2"])
    logits = _dot(_rms(x, params["final_norm.g"], eps),
                  params["word_embed"].T)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def new_state(params, cfg):
    return {"m": {k: jnp.zeros_like(v) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v) for k, v in params.items()},
            "t": jnp.zeros((), jnp.int32)}


def train_step(params, state, batch, cfg, mode="f32"):
    opt = cfg["optimizer"]
    lr, wd, eps = opt["learning_rate"], opt["wd"], opt["eps"]
    b1, b2 = opt["beta1"], opt["beta2"]
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, mode))(params)
    t = state["t"] + 1
    bc1, bc2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
    new_p, new_m, new_v = {}, {}, {}
    for k, w in params.items():
        m = b1 * state["m"][k] + (1 - b1) * grads[k]
        v = b2 * state["v"][k] + (1 - b2) * grads[k] ** 2
        new_p[k] = w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * w)
        new_m[k], new_v[k] = m, v
    return new_p, {"m": new_m, "v": new_v, "t": t}, loss


def first_gradient(state, cfg):
    b1 = cfg["optimizer"]["beta1"]
    return {k: v / (1 - b1) for k, v in state["m"].items()}


# ---------------------------------------- what the harness's tables lack
def causal_tokens(key, traffic, cfg):
    """Uniform ids of the configuration's vocabulary; the targets are the
    tokens themselves, one place on."""
    return {"tokens": jax.random.randint(
        key, (traffic["batch"], traffic["seq"]), 0, cfg["vocab_size"])}


def toy_lm_train_flops(cfg, traffic):
    """One token, one training step: three times the forward's products
    (q, k, v, o: 4*d*d; the feed-forward: 2*d*h; the tied decoder: d*V) and
    the causal half of the attention core (2*S*d a layer)."""
    d, h, L = cfg["dim"], cfg["hidden_dim"], cfg["n_layers"]
    return 3 * (2 * (L * (4 * d * d + 2 * d * h) + d * cfg["vocab_size"])
                + L * 2 * traffic["seq"] * d)


def toy_lm_attention_work(cfg, traffic):
    """(flops, bytes) a step requires of the causal attention core: half of
    the full square's products; twelve passes over a (B, S, d) float32
    tensor a layer."""
    d, L = cfg["dim"], cfg["n_layers"]
    tokens = traffic["batch"] * traffic["seq"]
    return tokens * 3 * L * 2 * traffic["seq"] * d, tokens * L * 12 * d * 4


KINDS = {"causal_tokens": causal_tokens}
TRAIN_FLOPS_PER_SAMPLE = {"toy_lm": toy_lm_train_flops}
KERNEL_WORK = {"toy_lm_attention": toy_lm_attention_work}

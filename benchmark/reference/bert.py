"""Plain reference: the BERT encoder (Devlin et al. 2018, arXiv:1810.04805)
with the masked-LM loss over a tied decoder, and AdamW.

Straightforward `jax.numpy` in float32 at `highest` matmul precision; plain
softmax attention, no kernels. It imports nothing of the program and is
handed nothing the program made: the weights come from `init_params` below
and the batch from `harness/traffic.py`, both from the seed.

  embed  word[token] + position[:S] + type[0], LayerNorm
  layer  q, k, v = x W + b per head; softmax(q k^T / sqrt(D)) v, not causal;
         x = LayerNorm(x + o Wo + bo);
         x = LayerNorm(x + gelu_tanh(x W1 + b1) W2 + b2)      [post-norm]
  loss   logits = x word^T at every position; the mean of -log softmax at
         the target over the masked positions
LayerNorm normalises over the width with the biased variance (eps as the
configuration gives it) in float32.

State is stored as the configuration states it (`dtype` for matrices, biases
and AdamW's two moments of them; float32 for LayerNorm): each update is
computed in float32 and rounded once where it is written back.

`mode` is one of `modes.py`'s: "f32" is the reference proper.
"""
import jax
import jax.numpy as jnp
from jax import lax

from .modes import activation, operand

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
INIT_SIGMA = 0.02


def leaves(cfg):
    """[(name, shape, kind)] of every parameter; a name is the path of the
    leaf in the program's tree, joined by dots.
    kind: matrix | bias | gamma | beta."""
    d, h = cfg["dim"], cfg["hidden_dim"]
    out = [("word_embed", (cfg["vocab_size"], d), "matrix"),
           ("position_embed", (cfg["max_seq_len"], d), "matrix"),
           ("token_type_embed", (cfg["n_types"], d), "matrix"),
           ("embed_norm.gamma", (d,), "gamma"),
           ("embed_norm.beta", (d,), "beta")]
    for i in range(cfg["n_layers"]):
        pre = "layers.%d." % i
        for w in "qkvo":
            out.append((pre + "attn.w" + w, (d, d), "matrix"))
        for w in "qkvo":
            out.append((pre + "attn.b" + w, (d,), "bias"))
        out += [(pre + "attn_norm.gamma", (d,), "gamma"),
                (pre + "attn_norm.beta", (d,), "beta"),
                (pre + "ffn.w1", (d, h), "matrix"),
                (pre + "ffn.b1", (h,), "bias"),
                (pre + "ffn.w2", (h, d), "matrix"),
                (pre + "ffn.b2", (d,), "bias"),
                (pre + "ffn_norm.gamma", (d,), "gamma"),
                (pre + "ffn_norm.beta", (d,), "beta")]
    return out


def trainable(name):
    return True


def storage_dtype(kind, cfg):
    return jnp.dtype(cfg["dtype"]) if kind in ("matrix", "bias") else F32


def init_params(key, cfg):
    """Every leaf from the key, each in the type it is stored in (call it
    inside a jit). Matrices normal with sigma 0.02, biases and beta 0,
    gamma 1."""
    table = leaves(cfg)
    params = {}
    for k, (name, shape, kind) in zip(jax.random.split(key, len(table)),
                                      table):
        dt = storage_dtype(kind, cfg)
        if kind == "matrix":
            params[name] = (jax.random.normal(k, shape, F32)
                            * INIT_SIGMA).astype(dt)
        elif kind == "gamma":
            params[name] = jnp.ones(shape, dt)
        else:
            params[name] = jnp.zeros(shape, dt)
    return params


def _dot(x, w, mode):
    return jnp.dot(operand(x, mode), operand(w.astype(F32), mode),
                   precision=HIGHEST)


def _norm(x, p, name, eps, mode):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return activation((x - mu) * lax.rsqrt(var + eps) * p[name + ".gamma"]
                      + p[name + ".beta"], mode)


def _layer(x, p, pre, cfg, mode):
    B, S, d = x.shape
    H = cfg["n_heads"]
    D = d // H

    def heads(w):
        y = _dot(x, p[pre + "attn.w" + w], mode) \
            + p[pre + "attn.b" + w].astype(F32)
        return activation(y, mode).reshape(B, S, H, D)

    q, k, v = heads("q"), heads("k"), heads("v")
    scores = jnp.einsum("bqhd,bkhd->bhqk", operand(q, mode),
                        operand(k, mode), precision=HIGHEST) * D ** -0.5
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", operand(probs, mode), operand(v, mode),
                   precision=HIGHEST)
    o = activation(o, mode).reshape(B, S, d)
    a = _dot(o, p[pre + "attn.wo"], mode) + p[pre + "attn.bo"].astype(F32)
    x = _norm(x + activation(a, mode), p, pre + "attn_norm", cfg["norm_eps"],
              mode)
    h = _dot(x, p[pre + "ffn.w1"], mode) + p[pre + "ffn.b1"].astype(F32)
    h = activation(jax.nn.gelu(h, approximate=True), mode)
    f = _dot(h, p[pre + "ffn.w2"], mode) + p[pre + "ffn.b2"].astype(F32)
    return _norm(x + activation(f, mode), p, pre + "ffn_norm",
                 cfg["norm_eps"], mode)


def loss_fn(params, batch, cfg, mode="f32"):
    """Mean masked-LM loss of the batch."""
    tokens = batch["tokens"]
    S = tokens.shape[1]
    x = (params["word_embed"].astype(F32)[tokens]
         + params["position_embed"].astype(F32)[None, :S]
         + params["token_type_embed"].astype(F32)[0][None, None])
    x = _norm(x, params, "embed_norm", cfg["norm_eps"], mode)
    for i in range(cfg["n_layers"]):
        pre = "layers.%d." % i
        sub = {k: v for k, v in params.items() if k.startswith(pre)}
        # a layer's activations are made again in the backward pass, so that
        # float32 at the timed batch fits beside the weights
        x = jax.checkpoint(
            lambda x, sub, pre=pre: _layer(x, sub, pre, cfg, mode))(x, sub)
    logits = activation(_dot(x, params["word_embed"].T, mode), mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               axis=-1)[..., 0]
    mask = batch["mask"].astype(F32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------- the training
def new_state(params, cfg):
    """AdamW's moments, zero, stored as the weight they belong to."""
    return {"m": {k: jnp.zeros_like(v) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v) for k, v in params.items()},
            "t": jnp.zeros((), jnp.int32)}


def train_step(params, state, batch, cfg, mode="f32"):
    """One step of AdamW (decoupled decay, bias-corrected moments, eps
    outside the root), the gradient that of the mean loss. Returns
    (params, state, loss)."""
    opt = cfg["optimizer"]
    lr, wd, eps = opt["learning_rate"], opt["wd"], opt["eps"]
    b1, b2 = opt["beta1"], opt["beta2"]
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg, mode))(params)
    t = state["t"] + 1
    tf = t.astype(F32)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
    new_p, new_m, new_v = {}, {}, {}
    for k, w in params.items():
        g, w32 = grads[k].astype(F32), w.astype(F32)
        m = b1 * state["m"][k].astype(F32) + (1 - b1) * g
        v = b2 * state["v"][k].astype(F32) + (1 - b2) * g * g
        step = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps) + lr * wd * w32
        new_p[k] = (w32 - step).astype(w.dtype)
        new_m[k] = m.astype(w.dtype)
        new_v[k] = v.astype(w.dtype)
    return new_p, {"m": new_m, "v": new_v, "t": t}, loss


def first_gradient(state, cfg):
    """The first gradient as the optimizer got it, from the state after one
    step: m_1 = (1 - beta1) * grad_1."""
    b1 = cfg["optimizer"]["beta1"]
    return {k: v.astype(F32) / (1 - b1) for k, v in state["m"].items()}

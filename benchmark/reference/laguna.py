"""Plain reference: the Laguna decoder (catalog row `Laguna-S-2.1`,
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json) with the
next-token loss, and AdamW.

Straightforward `jax.numpy` in float32 at `highest`: plain softmax attention
one query head at a time over the whole (S, S) square, the window a mask on
it; every held expert over every token. It imports nothing of the program.
d = hidden_size, D = head_dim, eps = rms_norm_eps, no bias anywhere.

  N(x; w)  x * rsqrt(mean(x^2) + eps) * w, w starts at 1
  layer l  h = x + A_l(N(x; w_a)); y = h + M_l(N(h; w_m))
  A_l      q = x Wq (S, H_l, D), k = x Wk, v = x Wv (S, Hkv, D),
           g = softplus(x Wg) (S, H_l); q <- R_l(N(q; w_q)), k <- R_l(N(k;
           w_k)) per head; o_i,h = sum_j softmax_j(q_i,h . k_j,kv(h) /
           sqrt(D)) v_j,kv(h) over j <= i where layer_types[l] is
           full_attention, over i - sliding_window < j <= i where it is
           sliding_attention; kv(h) = h // (H_l / Hkv);
           A_l = concat_h(g_i,h * o_i,h) Wo
  R_l      sliding: rotate-half pairs (t, t + D/2) over the whole head,
           inv_freq_t = theta^(-2t/D). full: pairs (t, t + r/2) over the
           first r = partial_rotary_factor * D dimensions, the rest passes
           through; YaRN: b_t = theta^(-2t/r), low = floor and high = ceil
           of r ln(original_max_position_embeddings / (2 pi n)) / (2 ln
           theta) at n = beta_fast and n = beta_slow, ramp_t = clip((t -
           low) / (high - low), 0, 1), inv_freq_t = (1 - ramp_t) b_t +
           ramp_t b_t / factor; cos and sin times attention_factor
  M_l      where mlp_layer_types[l] is dense: (silu(x W_g) * (x W_u)) W_d of
           width intermediate_size. Else s = sigmoid(x W_r) over all
           n_experts_published; T = the num_experts_per_tok largest;
           w_e = moe_routed_scaling_factor s_e / sum_{e' in T} s_e';
           E(x) = (silu(x G) * (x U)) Dn;
           M_l(x) = sum over the e in T held here of w_e E_e(x) + E_shared(x)
  loss     logits = N(x_L; w_f) W_head^T; the mean of -log softmax at the
           next token over positions 0 .. S-2

One chip's share: `n_kv_heads` of the `n_kv_heads_published` key-value heads
are held, each with its query heads (H_l = num_attention_heads_per_layer[l]
* n_kv_heads / n_kv_heads_published), their columns of Wq and Wg and their
rows of Wo; `n_experts` experts, the first `first_expert` on, of the
`n_experts_published` the router covers; `vocab_size` rows of the embedding
and of the head, from which the batch draws its ids. What the absent heads
and experts would add is left out, here as in the program. Left out as in
the program: any balance loss or selection-bias update.

State is stored in float32. `mode` is one of `modes.py`'s: "f32" is the
reference proper. The router's product is float32 at `highest` in every
mode, as the configuration states it.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from .modes import activation, operand

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
INIT_SIGMA = 0.02


def _is_sliding(cfg, layer):
    return cfg["layer_types"][layer] == "sliding_attention"


def _is_dense(cfg, layer):
    return cfg["mlp_layer_types"][layer] == "dense"


def _heads(cfg, layer):
    """Query heads of `layer` held here."""
    return (cfg["num_attention_heads_per_layer"][layer] * cfg["n_kv_heads"]
            // cfg["n_kv_heads_published"])


def leaves(cfg):
    """[(name, shape, kind)] of every parameter; a name is the path of the
    leaf in the program's tree, joined by dots. kind: matrix | one."""
    d, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    E, f = cfg["n_experts"], cfg["moe_intermediate_size"]
    fs, fd = cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]
    Hkv = cfg["n_kv_heads"]
    out = [("embed", (V, d), "matrix"), ("head", (V, d), "matrix"),
           ("final_norm.w", (d,), "one")]
    for i in range(cfg["n_layers"]):
        pre, H = "layers.%d." % i, _heads(cfg, i)
        out += [(pre + "attn_norm.w", (d,), "one"),
                (pre + "mlp_norm.w", (d,), "one"),
                (pre + "attn.wq", (d, H * D), "matrix"),
                (pre + "attn.wk", (d, Hkv * D), "matrix"),
                (pre + "attn.wv", (d, Hkv * D), "matrix"),
                (pre + "attn.wg", (d, H), "matrix"),
                (pre + "attn.wo", (H * D, d), "matrix"),
                (pre + "attn.q_norm", (D,), "one"),
                (pre + "attn.k_norm", (D,), "one")]
        if _is_dense(cfg, i):
            out += [(pre + "mlp.gate", (d, fd), "matrix"),
                    (pre + "mlp.up", (d, fd), "matrix"),
                    (pre + "mlp.down", (fd, d), "matrix")]
        else:
            out += [(pre + "moe.router", (d, cfg["n_experts_published"]),
                     "matrix"),
                    (pre + "moe.gate", (E, d, f), "matrix"),
                    (pre + "moe.up", (E, d, f), "matrix"),
                    (pre + "moe.down", (E, f, d), "matrix"),
                    (pre + "moe.shared_gate", (d, fs), "matrix"),
                    (pre + "moe.shared_up", (d, fs), "matrix"),
                    (pre + "moe.shared_down", (fs, d), "matrix")]
    return out


def storage_dtype(kind, cfg):
    return F32


def init_params(key, cfg):
    """Every leaf from the key (call it inside a jit): matrices normal with
    sigma 0.02, norm weights 1."""
    table = leaves(cfg)
    params = {}
    for k, (name, shape, kind) in zip(jax.random.split(key, len(table)),
                                      table):
        params[name] = (jax.random.normal(k, shape, F32) * INIT_SIGMA
                        if kind == "matrix" else jnp.ones(shape, F32))
    return params


def _dot(x, w, mode):
    return jnp.dot(operand(x, mode), operand(w, mode), precision=HIGHEST)


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_range(rope, rot):
    """(low, high): the pairs between which YaRN's ramp runs."""
    def pair(rotations):
        return (rot * math.log(rope["original_max_position_embeddings"]
                               / (2 * math.pi * rotations))
                / (2 * math.log(rope["rope_theta"])))
    return (max(math.floor(pair(rope["beta_fast"])), 0),
            min(math.ceil(pair(rope["beta_slow"])), rot - 1))


def rope_table(cfg, sliding):
    """(inv_freq (r/2,), the factor on cos and sin) of a layer kind."""
    rope = cfg["rope_parameters"]["sliding_attention" if sliding
                                  else "full_attention"]
    rot = int(cfg["head_dim"] * rope["partial_rotary_factor"])
    pair = jnp.arange(rot // 2, dtype=F32)
    base = rope["rope_theta"] ** (-pair * 2 / rot)
    if rope["rope_type"] == "default":
        return base, 1.0
    low, high = yarn_range(rope, rot)
    ramp = jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    return ((1 - ramp) * base + ramp * base / rope["factor"],
            rope["attention_factor"])


def _rotary(x, cfg, sliding):
    inv_freq, factor = rope_table(cfg, sliding)
    S, half = x.shape[1], inv_freq.shape[0]
    angle = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[:, None, :] * factor
    sin = jnp.sin(angle)[:, None, :] * factor
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], -1)


def _attention(x, p, cfg, mode, sliding):
    """The held heads' part of A_l: as many query heads as `wg` has columns,
    as many key-value heads as `wk` has heads."""
    B, S, _ = x.shape
    D, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    H, Hkv = p["wg"].shape[1], p["wk"].shape[1] // D
    q = activation(_dot(x, p["wq"], mode), mode).reshape(B, S, H, D)
    k = activation(_dot(x, p["wk"], mode), mode).reshape(B, S, Hkv, D)
    v = activation(_dot(x, p["wv"], mode), mode).reshape(B, S, Hkv, D)
    gate = jax.nn.softplus(_dot(x, p["wg"], mode))              # (B, S, H)
    q = activation(_rotary(_norm(q, p["q_norm"], eps), cfg, sliding), mode)
    k = activation(_rotary(_norm(k, p["k_norm"], eps), cfg, sliding), mode)
    qi = jnp.arange(S)[:, None]
    kj = jnp.arange(S)[None, :]
    seen = kj <= qi
    if sliding:
        seen = seen & (kj > qi - cfg["sliding_window"])

    @jax.checkpoint
    def one_head(h):
        # one query head's (S, S) scores at a time, made again in the
        # backward pass: the whole (B, H, S, S) would not fit
        q_h = lax.dynamic_index_in_dim(q, h, 2, keepdims=False)
        kv = h // (H // Hkv)
        k_h = lax.dynamic_index_in_dim(k, kv, 2, keepdims=False)
        v_h = lax.dynamic_index_in_dim(v, kv, 2, keepdims=False)
        scores = jnp.einsum("bqd,bkd->bqk", operand(q_h, mode),
                            operand(k_h, mode), precision=HIGHEST) * D ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", operand(probs, mode),
                          operand(v_h, mode), precision=HIGHEST)

    o = lax.map(one_head, jnp.arange(H))                # (H, B, S, D)
    o = activation(jnp.moveaxis(o, 0, 2), mode) * gate[..., None]
    return _dot(activation(o, mode).reshape(B, S, H * D), p["wo"], mode)


def _swiglu(x, gate, up, down, mode):
    hidden = activation(jax.nn.silu(_dot(x, gate, mode))
                        * _dot(x, up, mode), mode)
    return activation(_dot(hidden, down, mode), mode)


def _moe(x, p, cfg, mode):
    """The held experts' part of M_l, and the shared expert."""
    B, S, d = x.shape
    x = x.reshape(B * S, d)
    E, first = p["gate"].shape[0], cfg.get("first_expert", 0)
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"], precision=HIGHEST))
    top, ids = lax.top_k(scores, cfg["num_experts_per_tok"])
    top = (cfg["moe_routed_scaling_factor"] * top
           / jnp.sum(top, -1, keepdims=True))
    # (T, E): a held expert's weight for a token, zero where not chosen
    share = jnp.sum(jnp.where(ids[:, :, None] == first + jnp.arange(E),
                              top[:, :, None], 0.0), axis=1)

    @jax.checkpoint
    def one_expert(total, held):
        # a held expert over every token, its output times its weight (zero
        # where it was not chosen); made again in the backward pass
        gate, up, down, weight = held
        return total + _swiglu(x, gate, up, down, mode) * weight[:, None], None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x),
                         (p["gate"], p["up"], p["down"], share.T))
    shared = _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"],
                     mode)
    return activation(routed + shared, mode).reshape(B, S, d)


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _attention_block(x, p, cfg, mode, sliding):
    y = activation(_norm(x, p["attn_norm.w"], cfg["rms_norm_eps"]), mode)
    return activation(x + activation(
        _attention(y, _sub(p, "attn."), cfg, mode, sliding), mode), mode)


def _mlp_block(x, p, cfg, mode, dense):
    y = activation(_norm(x, p["mlp_norm.w"], cfg["rms_norm_eps"]), mode)
    if dense:
        mlp = _sub(p, "mlp.")
        return activation(x + _swiglu(y, mlp["gate"], mlp["up"], mlp["down"],
                                      mode), mode)
    return activation(x + _moe(y, _sub(p, "moe."), cfg, mode), mode)


def _layer(x, p, cfg, mode, sliding, dense):
    """One layer. Each half's activations are made again in the backward
    pass, so that float32 at the timed batch fits beside the weights."""
    x = jax.checkpoint(
        lambda x, p: _attention_block(x, p, cfg, mode, sliding))(x, p)
    return jax.checkpoint(
        lambda x, p: _mlp_block(x, p, cfg, mode, dense))(x, p)


def loss_fn(params, batch, cfg, mode="f32"):
    """Mean next-token cross-entropy of the batch."""
    tokens = batch["tokens"]
    x = activation(params["embed"][tokens], mode)
    for i in range(cfg["n_layers"]):
        x = _layer(x, _sub(params, "layers.%d." % i), cfg, mode,
                   _is_sliding(cfg, i), _is_dense(cfg, i))
    x = activation(_norm(x, params["final_norm.w"], cfg["rms_norm_eps"]),
                   mode)
    logits = _dot(x[:, :-1], params["head"].T, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


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


# ---------------------------------------- what the harness's tables lack
def causal_tokens(key, traffic, cfg):
    """Uniform ids over the rows of the vocabulary held here; the targets
    are the tokens themselves, one place on."""
    return {"tokens": jax.random.randint(
        key, (traffic["batch"], traffic["seq"]), 0, cfg["vocab_size"])}


def _routed_share(cfg):
    """Rows a token sends to the experts held here, expected under uniform
    routing: num_experts_per_tok x n_experts / n_experts_published."""
    return (cfg["num_experts_per_tok"] * cfg["n_experts"]
            / cfg["n_experts_published"])


def _band_keys(seq, window):
    """Keys that the queries of one sequence see under the window, summed:
    sum_i min(i + 1, window)."""
    full = min(seq, window)
    return full * (full + 1) // 2 + (seq - full) * window


def laguna_forward_flops(cfg, traffic):
    """One token, forward. The matrix products of every layer over the heads
    and experts held (q, k, v, the gate and the output projection; the dense
    MLP; the router over all experts, the shared expert and the routed
    experts at their expected share here, `_routed_share` of them a token);
    a full layer's attention core at its causal half (2 S H D), a sliding
    layer's at its band counted exactly (4 H D keys a query, `_band_keys` /
    S on average); the head over the rows held, at the S - 1 positions of a
    sequence that predict."""
    d, S, D = cfg["hidden_size"], traffic["seq"], cfg["head_dim"]
    Hkv = cfg["n_kv_heads"]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    total = 2 * d * cfg["vocab_size"] * (S - 1) / S
    for i in range(cfg["n_layers"]):
        H = _heads(cfg, i)
        total += 2 * d * (H * D + 2 * Hkv * D + H) + 2 * H * D * d
        total += (4 * H * D * _band_keys(S, cfg["sliding_window"]) / S
                  if _is_sliding(cfg, i) else 2 * S * H * D)
        total += (3 * 2 * d * cfg["intermediate_size"] if _is_dense(cfg, i)
                  else 2 * d * cfg["n_experts_published"] + 3 * 2 * d * fs
                  + _routed_share(cfg) * 3 * 2 * d * f)
    return total


def laguna_train_flops(cfg, traffic):
    """One token, one training step: three forward passes' worth;
    recomputation is not counted."""
    return 3 * laguna_forward_flops(cfg, traffic)


def _itemsize(name):
    return {"bfloat16": 2, "float32": 4}[name]


def moe_grouped_work(cfg, traffic):
    """(flops, bytes) one training step requires of the grouped product over
    the experts held here, whole batch, at the expected rows
    (`_routed_share` a token), in the layers that have experts. Operations:
    gate, up and down, forward, gradient to the rows and gradient to the
    weights. Bytes: every held expert's three matrices read twice (forward,
    gradient to the rows) and their gradient written once, in the type they
    are stored in; a row's operands and results in the activations' type:
    2 d + 3 f numbers in each of the three passes."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = traffic["batch"] * traffic["seq"] * _routed_share(cfg)
    layers = sum(not _is_dense(cfg, i) for i in range(cfg["n_layers"]))
    flops = layers * rows * 3 * 3 * 2 * d * f
    weights = layers * cfg["n_experts"] * 3 * d * f
    nbytes = (3 * weights * _itemsize(cfg["param_dtype"])
              + layers * rows * 3 * (2 * d + 3 * f) * _itemsize(cfg["dtype"]))
    return flops, nbytes


def _core_bytes(cfg, layer):
    """Bytes a token of one layer's attention core moves in a training
    step: q, o, do and dq passes over H D and k, v, dk, dv over Hkv D in the
    activations' type (forward reads q, k, v and writes o; backward reads q,
    k, v, o, do and writes dq, dk, dv), and one float32 statistic a row and
    head written once and read twice."""
    H = _heads(cfg, layer)
    return (6 * (H + cfg["n_kv_heads"]) * cfg["head_dim"]
            * _itemsize(cfg["dtype"]) + 3 * H * 4)


def causal_attention_work(cfg, traffic):
    """(flops, bytes) one training step requires of the causal attention
    core of the full-attention layers, whole batch: half of the full
    square's products, three forward passes' worth."""
    tokens = traffic["batch"] * traffic["seq"]
    full = [i for i in range(cfg["n_layers"]) if not _is_sliding(cfg, i)]
    flops = sum(tokens * 3 * 2 * traffic["seq"] * _heads(cfg, i)
                * cfg["head_dim"] for i in full)
    return flops, sum(tokens * _core_bytes(cfg, i) for i in full)


def window_attention_work(cfg, traffic):
    """(flops, bytes) one training step requires of the attention core of
    the sliding layers, whole batch: the band counted exactly, `_band_keys`
    keys a head and sequence at 4 D operations a key (scores and weighted
    sum), three forward passes' worth; the same passes over q, k, v and
    their cotangents as a full layer."""
    sliding = [i for i in range(cfg["n_layers"]) if _is_sliding(cfg, i)]
    keys = traffic["batch"] * _band_keys(traffic["seq"], cfg["sliding_window"])
    flops = sum(3 * 4 * cfg["head_dim"] * _heads(cfg, i) * keys
                for i in sliding)
    tokens = traffic["batch"] * traffic["seq"]
    return flops, sum(tokens * _core_bytes(cfg, i) for i in sliding)


KINDS = {"causal_tokens": causal_tokens}
TRAIN_FLOPS_PER_SAMPLE = {"laguna": laguna_train_flops}
KERNEL_WORK = {"moe_grouped_product": moe_grouped_work,
               "causal_attention_core": causal_attention_work,
               "window_attention_core": window_attention_work}

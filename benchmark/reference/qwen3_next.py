"""Plain reference: the Qwen3-Next decoder (catalog row
`Qwen3-Next-80B-A3B-Instruct`,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
with the next-token loss, and AdamW.

Straightforward `jax.numpy` in float32 at `highest`: plain softmax attention
one query head at a time, the gated delta rule token by token, every held
expert over every token. It imports nothing of the program. d = hidden_size,
eps = rms_norm_eps, no bias anywhere.

  N(x; w)  x * rsqrt(mean(x^2) + eps) * (1 + w)
  layer l  h = x + Mixer_l(N(x)); y = h + MoE_l(N(h)); the mixer is gated
           attention where (l + 1) % full_attention_interval == 0, else
           Gated DeltaNet
  attention  [q | gate] = x Wq split per head, k = x Wk, v = x Wv; q and k
           pass N over the head with their own weights; rotary embedding on
           the first partial_rotary_factor of each head, inv_freq_j =
           theta^(-2j/rot), pairs (i, i + rot/2); o = softmax_causal(q k^T /
           sqrt(D)) v, a key-value head serving H / Hkv query heads;
           out = (o * sigmoid(gate)) Wo
  DeltaNet [q | k | v | z] = x W_qkvz, [b | a] = x W_ba; q|k|v pass a causal
           depthwise convolution (c_t = sum_j w_j u_{t-K+1+j}) and SiLU;
           beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias); q and k
           are x * rsqrt(sum(x^2) + 1e-6) over the head, q times dk^-1/2, a
           key head serving Hv / Hk value heads. Per head, S_0 = 0:
             S' = exp(g_t) S_{t-1}; r = S'^T k_t;
             S_t = S' + k_t (beta_t (v_t - r))^T; o_t = S_t^T q_t
           out = (o_t * rsqrt(mean(o_t^2) + eps) * w_n * silu(z_t)) W_out
  mixture  p = softmax(x W_r) over all n_experts_published; the
           num_experts_per_tok largest, divided by their sum;
           E(x) = (silu(x G) * (x U)) Dn;
           MoE(x) = sum over the chosen experts held here of p_e E_e(x)
                    + sigmoid(x w_s) E_shared(x)
  loss     logits = N(x_L) W_head^T; the mean of -log softmax at the next
           token over positions 0 .. S-2

One chip's share: `n_experts` experts are held, the first `first_expert`
on, of the `n_experts_published` the router covers; what the absent ones
would add is left out, here as in the program. `vocab_size` rows of the
embedding and of the head are held, and the batch draws its ids from them.
Left out as in the program: multi-token prediction and any balance loss.

State is stored in float32. `mode` is one of `modes.py`'s: "f32" is the
reference proper. The router's product is float32 at `highest` in every
mode, as the configuration states it.
"""
import jax
import jax.numpy as jnp
from jax import lax

from .modes import activation, operand

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
INIT_SIGMA = 0.02
SCAN_BLOCK = 64     # tokens between two states the backward keeps


def _is_attention(cfg, layer):
    return (layer + 1) % cfg["full_attention_interval"] == 0


def _conv_channels(cfg):
    return (2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
            + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def leaves(cfg):
    """[(name, shape, kind)] of every parameter; a name is the path of the
    leaf in the program's tree, joined by dots.
    kind: matrix | zero | one | a_log | dt_bias."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    E, f = cfg["n_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    Hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    out = [("embed", (V, d), "matrix"), ("head", (V, d), "matrix"),
           ("final_norm.w", (d,), "zero")]
    for i in range(cfg["n_layers"]):
        pre = "layers.%d." % i
        out += [(pre + "mixer_norm.w", (d,), "zero"),
                (pre + "moe_norm.w", (d,), "zero")]
        if _is_attention(cfg, i):
            out += [(pre + "attn.wq", (d, 2 * H * D), "matrix"),
                    (pre + "attn.wk", (d, Hkv * D), "matrix"),
                    (pre + "attn.wv", (d, Hkv * D), "matrix"),
                    (pre + "attn.wo", (H * D, d), "matrix"),
                    (pre + "attn.q_norm", (D,), "zero"),
                    (pre + "attn.k_norm", (D,), "zero")]
        else:
            conv = _conv_channels(cfg)
            out += [(pre + "gdn.w_qkvz", (d, conv + Hv * dv), "matrix"),
                    (pre + "gdn.w_ba", (d, 2 * Hv), "matrix"),
                    (pre + "gdn.conv", (conv, cfg["linear_conv_kernel_dim"]),
                     "matrix"),
                    (pre + "gdn.A_log", (Hv,), "a_log"),
                    (pre + "gdn.dt_bias", (Hv,), "dt_bias"),
                    (pre + "gdn.norm", (dv,), "one"),
                    (pre + "gdn.w_out", (Hv * dv, d), "matrix")]
        out += [(pre + "moe.router", (d, cfg["n_experts_published"]),
                 "matrix"),
                (pre + "moe.gate", (E, d, f), "matrix"),
                (pre + "moe.up", (E, d, f), "matrix"),
                (pre + "moe.down", (E, f, d), "matrix"),
                (pre + "moe.shared_gate_proj", (d, fs), "matrix"),
                (pre + "moe.shared_up", (d, fs), "matrix"),
                (pre + "moe.shared_down", (fs, d), "matrix"),
                (pre + "moe.shared_gate", (d, 1), "matrix")]
    return out


def storage_dtype(kind, cfg):
    return F32


def init_params(key, cfg):
    """Every leaf from the key (call it inside a jit): matrices normal with
    sigma 0.02, zero-centred norm weights 0, the DeltaNet norm 1,
    A_log = log(U(0, 16)), dt_bias = 1."""
    table = leaves(cfg)
    params = {}
    for k, (name, shape, kind) in zip(jax.random.split(key, len(table)),
                                      table):
        if kind == "matrix":
            params[name] = jax.random.normal(k, shape, F32) * INIT_SIGMA
        elif kind == "a_log":
            params[name] = jnp.log(jax.random.uniform(k, shape, F32, 1e-6,
                                                      16.0))
        elif kind == "zero":
            params[name] = jnp.zeros(shape, F32)
        else:
            params[name] = jnp.ones(shape, F32)
    return params


def _dot(x, w, mode):
    return jnp.dot(operand(x, mode), operand(w, mode), precision=HIGHEST)


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _rotary(x, cfg):
    S, D = x.shape[1], x.shape[-1]
    rot = int(D * cfg["partial_rotary_factor"])
    half = rot // 2
    inv_freq = cfg["rope_theta"] ** (-jnp.arange(half, dtype=F32) * 2 / rot)
    angle = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def _attention(x, p, cfg, mode):
    B, S, _ = x.shape
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = activation(_dot(x, p["wq"], mode), mode).reshape(B, S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:].reshape(B, S, H * D)
    k = activation(_dot(x, p["wk"], mode), mode).reshape(B, S, Hkv, D)
    v = activation(_dot(x, p["wv"], mode), mode).reshape(B, S, Hkv, D)
    q = activation(_rotary(_norm(q, p["q_norm"], eps), cfg), mode)
    k = activation(_rotary(_norm(k, p["k_norm"], eps), cfg), mode)
    causal = jnp.tril(jnp.ones((S, S), bool))

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
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", operand(probs, mode),
                          operand(v_h, mode), precision=HIGHEST)

    o = lax.map(one_head, jnp.arange(H))                # (H, B, S, D)
    o = activation(jnp.moveaxis(o, 0, 2), mode).reshape(B, S, H * D)
    return _dot(activation(o * jax.nn.sigmoid(gate), mode), p["wo"], mode)


def _delta_rule(q, k, v, g, beta, mode):
    """o (B, S, Hv, dv) of the recurrence, token by token, q and k already
    given one head a value head. Two scans, nested: the backward keeps one
    state every SCAN_BLOCK tokens and makes the states of a block again."""
    B, S, Hv, dk = q.shape
    block = SCAN_BLOCK if S % SCAN_BLOCK == 0 else 1

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        r = jnp.einsum("bhde,bhd->bhe", operand(state, mode), k_t,
                       precision=HIGHEST)
        new = operand(b_t[..., None] * (v_t - r), mode)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t, new,
                                   precision=HIGHEST)
        return state, jnp.einsum("bhde,bhd->bhe", operand(state, mode), q_t,
                                 precision=HIGHEST)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((S // block, block)
                                             + x.shape[:1] + x.shape[2:])
               for x in (operand(q, mode), operand(k, mode), v, g, beta))
    _, o = lax.scan(tokens, jnp.zeros((B, Hv, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o.reshape((S,) + o.shape[2:]), 0, 1)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_net(x, p, cfg, mode):
    B, S, _ = x.shape
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K, conv = cfg["linear_conv_kernel_dim"], _conv_channels(cfg)
    qkvz = activation(_dot(x, p["w_qkvz"], mode), mode)
    ba = _dot(x, p["w_ba"], mode)
    padded = jnp.pad(qkvz[..., :conv], ((0, 0), (K - 1, 0), (0, 0)))
    mixed = sum(padded[:, j:j + S] * p["conv"][:, j] for j in range(K))
    mixed = activation(jax.nn.silu(mixed), mode)
    z = qkvz[..., conv:].reshape(B, S, Hv, dv)
    q = mixed[..., :Hk * dk].reshape(B, S, Hk, dk)
    k = mixed[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk)
    v = mixed[..., 2 * Hk * dk:].reshape(B, S, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    q = jnp.repeat(activation(_l2(q) * dk ** -0.5, mode), Hv // Hk, axis=2)
    k = jnp.repeat(activation(_l2(k), mode), Hv // Hk, axis=2)
    o = activation(_delta_rule(q, k, v, g, beta, mode), mode)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                      + cfg["rms_norm_eps"]) * p["norm"] * jax.nn.silu(z)
    return _dot(activation(o, mode).reshape(B, S, Hv * dv), p["w_out"], mode)


def _moe(x, p, cfg, mode):
    B, S, d = x.shape
    x = x.reshape(B * S, d)
    E, first = cfg["n_experts"], cfg.get("first_expert", 0)
    probs = jax.nn.softmax(jnp.dot(x, p["router"], precision=HIGHEST), -1)
    top, ids = lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    # (T, E): a held expert's weight for a token, zero where not chosen
    share = jnp.sum(jnp.where(ids[:, :, None] == first + jnp.arange(E),
                              top[:, :, None], 0.0), axis=1)
    xo = operand(x, mode)

    @jax.checkpoint
    def one_expert(total, held):
        # a held expert over every token, its output times its weight (zero
        # where it was not chosen); made again in the backward pass: all
        # the experts' (E, T, f) at once would not fit
        gate, up, down, weight = held
        hidden = activation(jax.nn.silu(jnp.dot(xo, operand(gate, mode),
                                                precision=HIGHEST))
                            * jnp.dot(xo, operand(up, mode),
                                      precision=HIGHEST), mode)
        out = activation(jnp.dot(operand(hidden, mode), operand(down, mode),
                                 precision=HIGHEST), mode)
        return total + out * weight[:, None], None

    routed, _ = lax.scan(one_expert, jnp.zeros_like(x),
                         (p["gate"], p["up"], p["down"], share.T))
    hidden = activation(jax.nn.silu(_dot(x, p["shared_gate_proj"], mode))
                        * _dot(x, p["shared_up"], mode), mode)
    shared = (activation(_dot(hidden, p["shared_down"], mode), mode)
              * jax.nn.sigmoid(_dot(x, p["shared_gate"], mode)))
    return activation(routed + shared, mode).reshape(B, S, d)


def _mixer_block(x, p, cfg, mode, attention):
    sub = {k.split(".", 1)[1]: v for k, v in p.items()
           if k.startswith(("attn.", "gdn."))}
    y = activation(_norm(x, p["mixer_norm.w"], cfg["rms_norm_eps"]), mode)
    mixer = _attention if attention else _delta_net
    return activation(x + activation(mixer(y, sub, cfg, mode), mode), mode)


def _moe_block(x, p, cfg, mode):
    moe = {k.split(".", 1)[1]: v for k, v in p.items()
           if k.startswith("moe.")}
    y = activation(_norm(x, p["moe_norm.w"], cfg["rms_norm_eps"]), mode)
    return activation(x + _moe(y, moe, cfg, mode), mode)


def _layer(x, p, cfg, mode, attention):
    """One layer. Each half's activations are made again in the backward
    pass, so that float32 at the timed batch fits beside the weights."""
    x = jax.checkpoint(
        lambda x, p: _mixer_block(x, p, cfg, mode, attention))(x, p)
    return jax.checkpoint(lambda x, p: _moe_block(x, p, cfg, mode))(x, p)


def loss_fn(params, batch, cfg, mode="f32"):
    """Mean next-token cross-entropy of the batch."""
    tokens = batch["tokens"]
    x = activation(params["embed"][tokens], mode)
    for i in range(cfg["n_layers"]):
        pre = "layers.%d." % i
        sub = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        x = _layer(x, sub, cfg, mode, _is_attention(cfg, i))
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


def _layer_counts(cfg):
    attention = sum(_is_attention(cfg, i) for i in range(cfg["n_layers"]))
    return attention, cfg["n_layers"] - attention


def _routed_share(cfg):
    """Rows a token sends to the experts held here, expected under uniform
    routing: num_experts_per_tok x n_experts / n_experts_published."""
    return (cfg["num_experts_per_tok"] * cfg["n_experts"]
            / cfg["n_experts_published"])


def qwen3_next_forward_flops(cfg, traffic):
    """One token, forward. The matrix products of every layer (DeltaNet:
    q|k|v|z, b|a and the output projection; attention: q|gate, k, v and the
    output; mixture: the router over all experts, the shared expert and its
    gate, and the routed experts at their expected share here,
    `_routed_share` of them a token); the delta rule at its recurrence's
    three products of 2 dk dv a value head; the attention core at its causal
    half (2 S H D); the head over the rows held, at the S - 1 positions of a
    sequence that predict. The convolution's four taps are not counted."""
    d, S = cfg["hidden_size"], traffic["seq"]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    Hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    attention, delta = _layer_counts(cfg)
    per_delta = (2 * d * (_conv_channels(cfg) + Hv * dv + 2 * Hv)
                 + 2 * Hv * dv * d + 3 * 2 * dk * dv * Hv)
    per_attention = (2 * d * (2 * H * D + 2 * Hkv * D) + 2 * H * D * d
                     + 2 * S * H * D)
    per_moe = (2 * d * cfg["n_experts_published"] + 3 * 2 * d * fs + 2 * d
               + _routed_share(cfg) * 3 * 2 * d * f)
    head = 2 * d * cfg["vocab_size"] * (S - 1) / S
    return (delta * per_delta + attention * per_attention
            + cfg["n_layers"] * per_moe + head)


def qwen3_next_train_flops(cfg, traffic):
    """One token, one training step: three forward passes' worth;
    recomputation is not counted."""
    return 3 * qwen3_next_forward_flops(cfg, traffic)


def _itemsize(name):
    return {"bfloat16": 2, "float32": 4}[name]


def moe_grouped_work(cfg, traffic):
    """(flops, bytes) one training step requires of the grouped product over
    the experts held here, whole batch, at the expected rows
    (`_routed_share` a token). Operations: gate, up and down, forward,
    gradient to the rows and gradient to the weights. Bytes: every held
    expert's three matrices read twice (forward, gradient to the rows) and
    their gradient written once, in the type they are stored in; a row's
    operands and results in the activations' type: 2 d + 3 f numbers in each
    of the three passes."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = traffic["batch"] * traffic["seq"] * _routed_share(cfg)
    layers = cfg["n_layers"]
    flops = layers * rows * 3 * 3 * 2 * d * f
    weights = layers * cfg["n_experts"] * 3 * d * f
    nbytes = (3 * weights * _itemsize(cfg["param_dtype"])
              + layers * rows * 3 * (2 * d + 3 * f) * _itemsize(cfg["dtype"]))
    return flops, nbytes


def causal_attention_work(cfg, traffic):
    """(flops, bytes) one training step requires of the causal attention
    core of the gated-attention layers, whole batch: half of the full
    square's products, three forward passes' worth; q, o, do and dq passes
    over (B, S, H D) and k, v, dk, dv over (B, S, Hkv D) in the activations'
    type (forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv), and one float32 statistic a row and head written
    once and read twice."""
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attention, _ = _layer_counts(cfg)
    tokens = traffic["batch"] * traffic["seq"]
    flops = tokens * attention * 3 * 2 * traffic["seq"] * H * D
    nbytes = tokens * attention * (
        6 * (H + Hkv) * D * _itemsize(cfg["dtype"]) + 3 * H * 4)
    return flops, nbytes


KINDS = {"causal_tokens": causal_tokens}
TRAIN_FLOPS_PER_SAMPLE = {"qwen3_next": qwen3_next_train_flops}
KERNEL_WORK = {"moe_grouped_product": moe_grouped_work,
               "causal_attention_core": causal_attention_work}

"""Plain reference: ResNet v1 (He et al. 2015, arXiv:1512.03385, table 1) as
MXNet's model zoo builds it, with softmax cross-entropy and SGD with momentum.

Straightforward `jax.numpy` in float32 at `highest` matmul precision; no
kernels, no Gluon. It imports nothing of the program and is handed nothing
the program made: the weights come from `init_params` below and the batch from
`harness/traffic.py`, both from the seed.

The architecture, as the model zoo has it (NCHW):
  stem   conv 7x7/2 pad 3 (no bias), BN, relu, max-pool 3x3/2 pad 1
  stage i of `layers[i]` blocks at `channels[i+1]`, the first block with
         stride 1 (stage 1) or 2, and a 1x1 projection (no bias) + BN on the
         shortcut where the channel count changes
  bottleneck  1x1 (stride, bias) BN relu, 3x3 (no bias) BN relu,
              1x1 (bias) BN, add shortcut, relu      [stride on the first 1x1]
  basic       3x3 (stride) BN relu, 3x3 BN, add shortcut, relu (no biases)
  head   global average pool, dense with bias
BatchNorm normalises with the batch's own mean and biased variance
(eps 1e-5) in training; the moving statistics do not enter a training step
and are not followed here.

State is stored as the configuration states it (`dtype` for convolution and
dense weights, biases and their momentum; float32 for BatchNorm): each update
is computed in float32 and rounded once where it is written back.

`mode` is one of `modes.py`'s: "f32" is the reference proper.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

from .modes import activation, operand, stored

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5
BN_FIELDS = ("gamma", "beta", "mean", "var")


# ------------------------------------------------------------------ the net
def _blocks(cfg):
    """[(prefix, cin, cout, stride, projected)] in the model zoo's order."""
    out = []
    chans = cfg["channels"]
    for i, n in enumerate(cfg["layers"]):
        cin, cout = chans[i], chans[i + 1]
        for j in range(n):
            first = j == 0
            out.append(("s%d.b%d." % (i + 1, j), cin if first else cout, cout,
                        (1 if i == 0 else 2) if first else 1,
                        first and cout != cin))
    return out


def leaves(cfg):
    """[(name, shape, kind)] of every parameter, in the order the model zoo
    registers them. kind: weight | bias | gamma | beta | mean | var."""
    out = []

    def conv(name, cout, cin, k, bias=False):
        out.append((name + ".weight", (cout, cin, k, k), "weight"))
        if bias:
            out.append((name + ".bias", (cout,), "bias"))

    def bn(name, c):
        out.extend((name + "." + f, (c,), f) for f in BN_FIELDS)

    c0 = cfg["channels"][0]
    conv("stem.conv", c0, 3, 7)
    bn("stem.bn", c0)
    for pre, cin, cout, _, proj in _blocks(cfg):
        if cfg["block"] == "bottleneck":
            mid = cout // 4
            conv(pre + "c1", mid, cin, 1, bias=True)
            bn(pre + "n1", mid)
            conv(pre + "c2", mid, mid, 3)
            bn(pre + "n2", mid)
            conv(pre + "c3", cout, mid, 1, bias=True)
            bn(pre + "n3", cout)
        else:
            conv(pre + "c1", cout, cin, 3)
            bn(pre + "n1", cout)
            conv(pre + "c2", cout, cout, 3)
            bn(pre + "n2", cout)
        if proj:
            conv(pre + "ds", cout, cin, 1)
            bn(pre + "dsn", cout)
    out.append(("fc.weight", (cfg["classes"], cfg["channels"][-1]), "weight"))
    out.append(("fc.bias", (cfg["classes"],), "bias"))
    return out


def trainable(name):
    return not name.endswith((".mean", ".var"))


def storage_dtype(kind, cfg):
    return jnp.dtype(cfg["dtype"]) if kind in ("weight", "bias") else F32


def init_params(key, cfg):
    """Every leaf from the key, each in the type it is stored in (call it
    inside a jit). Weights: Xavier, gaussian, on the mean of fan-in and
    fan-out, magnitude 3 (the README quick start's); biases and beta 0,
    gamma 1, the moving statistics 0 and 1."""
    table = leaves(cfg)
    params = {}
    for k, (name, shape, kind) in zip(jax.random.split(key, len(table)),
                                      table):
        dt = storage_dtype(kind, cfg)
        if kind == "weight":
            rf = math.prod(shape[2:])
            sigma = math.sqrt(6.0 / ((shape[0] + shape[1]) * rf))
            params[name] = (jax.random.normal(k, shape, F32)
                            * sigma).astype(dt)
        elif kind in ("gamma", "var"):
            params[name] = jnp.ones(shape, dt)
        else:
            params[name] = jnp.zeros(shape, dt)
    return params


def _conv(x, w, stride, pad, mode):
    return lax.conv_general_dilated(
        operand(x, mode), operand(w.astype(F32), mode), (stride, stride),
        [(pad, pad)] * 2, dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=HIGHEST)


def _bias(x, p, name):
    return x + p[name + ".bias"].astype(F32)[None, :, None, None]


def _bn(x, p, name, mode, relu=False):
    # the convolution's output as it is stored, and two reads of it, for the
    # statistics and for the normalisation: in a bfloat16 stack each read's
    # cotangent comes back rounded by itself and their sum is rounded again
    # (`modes.stored`)
    x = stored(x, mode)
    xs, x = stored(x, mode), stored(x, mode)
    mean = jnp.mean(xs, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(xs - mean), axis=(0, 2, 3), keepdims=True)
    y = ((x - mean) * lax.rsqrt(var + BN_EPS)
         * p[name + ".gamma"][None, :, None, None]
         + p[name + ".beta"][None, :, None, None])
    return activation(jnp.maximum(y, 0) if relu else y, mode)


def _block(x, p, pre, stride, proj, kind, mode):
    r = x
    if kind == "bottleneck":
        y = _bias(_conv(x, p[pre + "c1.weight"], stride, 0, mode), p,
                  pre + "c1")
        y = _bn(y, p, pre + "n1", mode, relu=True)
        y = _bn(_conv(y, p[pre + "c2.weight"], 1, 1, mode), p, pre + "n2",
                mode, relu=True)
        y = _bias(_conv(y, p[pre + "c3.weight"], 1, 0, mode), p, pre + "c3")
        y = _bn(y, p, pre + "n3", mode)
    else:
        y = _bn(_conv(x, p[pre + "c1.weight"], stride, 1, mode), p,
                pre + "n1", mode, relu=True)
        y = _bn(_conv(y, p[pre + "c2.weight"], 1, 1, mode), p, pre + "n2",
                mode)
    if proj:
        r = _bn(_conv(x, p[pre + "ds.weight"], stride, 0, mode), p,
                pre + "dsn", mode)
    return activation(jnp.maximum(y + r, 0), mode)


def loss_fn(params, batch, cfg, mode="f32"):
    """Mean softmax cross-entropy of the batch."""
    x = batch["data"].astype(F32)
    x = _bn(_conv(x, params["stem.conv.weight"], 2, 3, mode), params,
            "stem.bn", mode, relu=True)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for pre, _, _, stride, proj in _blocks(cfg):
        sub = {k: v for k, v in params.items() if k.startswith(pre)}
        # a block's activations are made again in the backward pass, so that
        # float32 at the timed batch fits beside the weights
        x = jax.checkpoint(
            lambda x, sub, pre=pre, stride=stride, proj=proj: _block(
                x, sub, pre, stride, proj, cfg["block"], mode))(x, sub)
    x = jnp.mean(x, axis=(2, 3))
    logits = jnp.dot(operand(x, mode),
                     operand(params["fc.weight"].astype(F32), mode).T,
                     precision=HIGHEST) + params["fc.bias"].astype(F32)
    logp = jax.nn.log_softmax(activation(logits, mode), axis=-1)
    y = batch["label"].astype(jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


# ------------------------------------------------------------- the training
def new_state(params, cfg):
    """SGD's momentum, zero, one per trainable leaf, stored as its weight."""
    return {k: jnp.zeros_like(v) for k, v in params.items() if trainable(k)}


def train_step(params, state, batch, cfg, mode="f32"):
    """One step of SGD with momentum (mom = m*mom - lr*grad; w += mom; no
    weight decay), the gradient that of the mean loss. Returns
    (params, state, loss)."""
    opt = cfg["optimizer"]
    lr, mu = opt["learning_rate"], opt["momentum"]
    train = {k: v for k, v in params.items() if trainable(k)}
    rest = {k: v for k, v in params.items() if not trainable(k)}
    loss, grads = jax.value_and_grad(
        lambda t: loss_fn({**t, **rest}, batch, cfg, mode))(train)
    new_p, new_m = dict(rest), {}
    for k, w in train.items():
        mom = mu * state[k].astype(F32) - lr * grads[k].astype(F32)
        new_p[k] = (w.astype(F32) + mom).astype(w.dtype)
        new_m[k] = mom.astype(state[k].dtype)
    return new_p, new_m, loss


def first_gradient(state, cfg):
    """The first gradient as the optimizer got it, from the state after one
    step: mom_1 = -lr * grad_1."""
    lr = cfg["optimizer"]["learning_rate"]
    return {k: -v.astype(F32) / lr for k, v in state.items()}

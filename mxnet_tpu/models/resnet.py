"""Functional ResNet (v1 bottleneck), the TPU-first benchmark model.

The API-compatible Gluon model zoo (`mxnet_tpu.gluon.model_zoo.vision`,
mirroring python/mxnet/gluon/model_zoo/vision/resnet.py in the reference)
remains the user-facing surface, and the one the benchmark's ResNet cells
drive; this module is the functional twin:

  * NHWC layout — TPU convolutions want feature-minor;
  * bf16 activations/weights, fp32 BatchNorm statistics;
  * one fused jitted train step (fwd+bwd+SGD) so XLA schedules the whole
    iteration; BN running stats are updated inside the same program.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.nn import batch_moments
from .losses import linear_cross_entropy

__all__ = ["ResNetConfig", "resnet_init", "resnet_forward", "resnet_loss",
           "CONFIGS"]


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    layers: tuple = (3, 4, 6, 3)          # resnet50
    channels: tuple = (64, 256, 512, 1024, 2048)
    classes: int = 1000
    dtype: object = jnp.bfloat16
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5


CONFIGS = {
    "resnet50": ResNetConfig(),
    "resnet101": ResNetConfig(layers=(3, 4, 23, 3)),
    "resnet152": ResNetConfig(layers=(3, 8, 36, 3)),
    "resnet_tiny": ResNetConfig(layers=(1, 1), channels=(8, 16, 32),
                                classes=10),
}


def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    std = math.sqrt(2.0 / fan_in)
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
            * std).astype(dtype)


def _bn_init(c):
    return {"gamma": jnp.ones((c,), jnp.float32),
            "beta": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def resnet_init(key, cfg: ResNetConfig):
    keys = iter(jax.random.split(key, 1024))
    ch = cfg.channels
    params = {
        "stem": {"conv": _conv_init(next(keys), 7, 7, 3, ch[0], cfg.dtype),
                 "bn": _bn_init(ch[0])},
        "stages": {},
        "fc": {"w": _conv_init(next(keys), 1, 1, ch[-1],
                               cfg.classes, cfg.dtype)[0, 0],
               "b": jnp.zeros((cfg.classes,), cfg.dtype)},
    }
    cin = ch[0]
    for si, n_blocks in enumerate(cfg.layers):
        cout = ch[si + 1]
        mid = cout // 4
        stage = {}
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = {
                "conv1": _conv_init(next(keys), 1, 1, cin, mid, cfg.dtype),
                "bn1": _bn_init(mid),
                "conv2": _conv_init(next(keys), 3, 3, mid, mid, cfg.dtype),
                "bn2": _bn_init(mid),
                "conv3": _conv_init(next(keys), 1, 1, mid, cout, cfg.dtype),
                "bn3": _bn_init(cout),
            }
            if bi == 0:
                blk["down_conv"] = _conv_init(next(keys), 1, 1, cin, cout,
                                              cfg.dtype)
                blk["down_bn"] = _bn_init(cout)
            stage[str(bi)] = blk
            cin = cout
        params["stages"][str(si)] = stage
    return params


def _conv(x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, cfg, train):
    xf = x.astype(jnp.float32)
    if train:
        # the statistics of `BatchNorm` (ops/nn.py): a 16-bit activation
        # gives both moments in one pass about the running mean
        mu, var = batch_moments(xf, (0, 1, 2), p["mean"], x.dtype)
        stats = (mu, var)
    else:
        mu, var = p["mean"], p["var"]
        stats = None
    y = (xf - mu) * lax.rsqrt(var + cfg.bn_eps) * p["gamma"] + p["beta"]
    return y.astype(x.dtype), stats


def _bottleneck(x, blk, cfg, train, stride, stats_out, prefix):
    out, s = _bn(_conv(x, blk["conv1"]), blk["bn1"], cfg, train)
    if train:
        stats_out[prefix + "/bn1"] = s
    out = jax.nn.relu(out)
    out, s = _bn(_conv(out, blk["conv2"], stride), blk["bn2"], cfg, train)
    if train:
        stats_out[prefix + "/bn2"] = s
    out = jax.nn.relu(out)
    out, s = _bn(_conv(out, blk["conv3"]), blk["bn3"], cfg, train)
    if train:
        stats_out[prefix + "/bn3"] = s
    if "down_conv" in blk:
        x, s = _bn(_conv(x, blk["down_conv"], stride), blk["down_bn"],
                   cfg, train)
        if train:
            stats_out[prefix + "/down_bn"] = s
    return jax.nn.relu(out + x)


def _pooled(params, images, cfg: ResNetConfig, train):
    """images (B,H,W,3) → (pooled features (B,C) fp32, batch-stats dict)."""
    stats = {}
    x = images.astype(cfg.dtype)
    x, s = _bn(_conv(x, params["stem"]["conv"], 2), params["stem"]["bn"],
               cfg, train)
    if train:
        stats["stem/bn"] = s
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for si in range(len(cfg.layers)):
        stage = params["stages"][str(si)]
        for bi in range(cfg.layers[si]):
            stride = 2 if (bi == 0 and si > 0) else 1
            x = _bottleneck(x, stage[str(bi)], cfg, train, stride, stats,
                            "stages/%d/%d" % (si, bi))
    return jnp.mean(x.astype(jnp.float32), axis=(1, 2)), stats


def resnet_forward(params, images, cfg: ResNetConfig, train=False):
    """images (B,H,W,3) → (logits (B,classes) fp32, batch-stats dict).

    In train mode the returned stats dict maps "stages/si/bi/bnX" →
    (batch_mean, batch_var) for the running-stat EMA update (done by the
    caller, outside the grad)."""
    x, stats = _pooled(params, images, cfg, train)
    logits = x @ params["fc"]["w"].astype(jnp.float32) + \
        params["fc"]["b"].astype(jnp.float32)
    return logits, stats


def resnet_loss(params, batch, cfg: ResNetConfig):
    """Softmax CE; returns (loss, batch stats) for use with has_aux grad."""
    x, stats = _pooled(params, batch["images"], cfg, train=True)
    # the bias as the weight of one more feature that is always 1
    x = jnp.concatenate([x, jnp.ones_like(x[:, :1])], axis=1)
    w = jnp.concatenate([params["fc"]["w"], params["fc"]["b"][None]], axis=0)
    return linear_cross_entropy(x, w.T.astype(jnp.float32),
                                batch["labels"]), stats


def update_running_stats(params, stats, cfg: ResNetConfig):
    """EMA the (mean, var) batch stats captured by resnet_loss back into the
    param tree — functional analog of the reference BatchNorm aux states
    (src/operator/nn/batch_norm.cc moving_mean/moving_var)."""
    m = cfg.bn_momentum
    for key, (mu, var) in stats.items():
        parts = key.split("/")
        node = params
        if parts[0] == "stem":
            node = params["stem"]
            bn = node[parts[1]]
        else:
            node = params["stages"][parts[1]][parts[2]]
            bn = node[parts[3]]
        bn["mean"] = m * bn["mean"] + (1 - m) * mu
        bn["var"] = m * bn["var"] + (1 - m) * var
    return params

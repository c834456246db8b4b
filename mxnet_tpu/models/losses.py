"""The loss tail the model families share: a linear decoder and a softmax
cross-entropy over its classes as ONE operation with its own backward.

Written out with ``log_softmax`` and ``take_along_axis`` the tail makes XLA
write the (N, V) log-probabilities and read them back for the one column a
row uses, and autodiff answers the gather with a scatter into a tensor of
that size. At BERT's vocabulary that is 2 GB a pass (PERF.md, PR 29). Here
the forward keeps the float32 logits and one statistic a row, and the
backward builds ``softmax - onehot`` where the two products read it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["linear_cross_entropy"]


def _denominator(weights):
    return jnp.maximum(jnp.sum(weights), 1.0)


@jax.custom_vjp
def _linear_cross_entropy(h, w, targets, weights):
    return _forward(h, w, targets, weights)[0]


def _forward(h, w, targets, weights):
    # (N, d) x (V, d) -> (N, V), accumulated and kept in float32
    logits = lax.dot_general(h, w.astype(h.dtype), (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    top = jnp.max(logits, axis=-1)
    lse = top + jnp.log(jnp.sum(jnp.exp(logits - top[:, None]), axis=-1))
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    loss = jnp.sum(weights * (lse - picked)) / _denominator(weights)
    return loss, (h, w, logits, lse, targets, weights)


def _backward(res, g):
    h, w, logits, lse, targets, weights = res
    scale = g * weights / _denominator(weights)
    # the one-hot by comparison with an iota, not by a scatter into zeros
    hit = lax.broadcasted_iota(targets.dtype, logits.shape, 1) \
        == targets[:, None]
    dl = (jnp.exp(logits - lse[:, None]) - hit) * scale[:, None]
    # Not written anywhere: XLA builds it from the logits inside the operand
    # of both products (PERF.md, PR 29). Their operands have the type the
    # forward's had: bfloat16 for a bfloat16 model, and float32 ones are
    # rounded by the MXU itself under the default precision.
    dl = dl.astype(h.dtype)
    dh = lax.dot_general(dl, w.astype(h.dtype), (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dw = lax.dot_general(dl, h, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    # targets are integers and weights are data: no gradient flows to them
    return dh.astype(h.dtype), dw.astype(w.dtype), None, None


_linear_cross_entropy.defvjp(_forward, _backward)


def linear_cross_entropy(h, w, targets, weights=None):
    """``sum(weights * nll) / max(sum(weights), 1)`` of the classifier
    ``logits = h @ w.T``, with ``nll = logsumexp(logits) - logits[target]``.

    h (..., d) hidden states, w (V, d) the decoder (a tied embedding table
    as it is stored), targets (...) int class ids, weights (...) the mask
    or per-row weights (None: ones, which makes the loss the mean). The
    logits and the statistics are float32 whatever ``h`` is; the products'
    operands have ``h``'s type. Differentiable in ``h`` and ``w`` only.
    """
    targets = targets.reshape(-1)
    weights = (jnp.ones(targets.shape, jnp.float32) if weights is None
               else weights.reshape(-1).astype(jnp.float32))
    # rows flattened: one (N, V) layout of the logits whatever the batch's
    # shape (0.5 ms a step faster than (B, S, V) in bert_base_s128)
    return _linear_cross_entropy(h.reshape(-1, h.shape[-1]), w, targets,
                                 weights)

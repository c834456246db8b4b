"""Ring attention — sequence/context parallelism over a mesh axis.

Not in the reference (SURVEY.md §5.7: its longest-sequence story is
BucketingModule); this is the long-context capability the TPU build adds as
first-class. The sequence axis is sharded over mesh axis `seq`; each device
holds one Q/K/V chunk and K/V chunks rotate around the ring via
`lax.ppermute` (lowering to ICI neighbor RDMA), overlapping the next
transfer with the current block's attention. Online-softmax merging keeps
memory O(S/n) per device, so max context scales linearly with ring size.

Call inside shard_map/jit with the sequence axis sharded, e.g.::

    f = shard_map(lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
                  mesh=mesh, in_specs=P(None, None, "seq", None), ...)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (_use_pallas as _fa_use_pallas,
                              _pallas_forward as _fa_forward,
                              _pallas_backward_inner as _fa_backward,
                              _ref_attention as _fa_ref)

__all__ = ["ring_attention"]

_NEG_INF = -1e30


def _block_attend(q, k, v, mask, sm_scale):
    """One Q-chunk x K-chunk block: returns (unnormalized out, m, l) in f32.

    q is pre-grouped (B, Hkv, G, Sq, D); k/v stay at their Hkv head count —
    GQA via grouped einsum, so repeated K/V copies are never materialized
    (and never ppermuted around the ring)."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e9)  # keep fully-masked rows finite
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    return o, m, l


# ---------------------------------------------------------------------------
# Flash-kernel ring path: the Pallas forward/backward kernels run per ring
# block, so the per-device inner step is O(chunk) HBM instead of the XLA
# path's materialized (Sq/n x Sk/n) probability tile. Backward is a second
# ring pass: dk/dv accumulators travel WITH their K/V shards and arrive
# back at the home device after n rotations, while each block's kernels
# recompute probabilities from the GLOBAL logsumexp saved by the forward.
# ---------------------------------------------------------------------------


def _pvary(t, axis_name):
    """Mark a constant as device-varying under shard_map."""
    return lax.pcast(t, (axis_name,), to="varying")


def _merge_blocks(o_run, lse_run, o_blk, lse_blk):
    """Combine two normalized attention partials by their logsumexps."""
    m = jnp.maximum(lse_run, lse_blk)
    wa = jnp.exp(lse_run - m)
    wb = jnp.exp(lse_blk - m)
    l = wa + wb
    o = (o_run * wa[..., None] + o_blk * wb[..., None]) / l[..., None]
    return o, m + jnp.log(l)


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def full_blk(q_, k_, v_):
        o, lse = _fa_forward(q_, k_, v_, False, sm_scale)
        return o.astype(jnp.float32), lse

    def diag_blk(q_, k_, v_):
        o, lse = _fa_forward(q_, k_, v_, True, sm_scale)
        return o.astype(jnp.float32), lse

    def skip_blk(q_, k_, v_):
        return (jnp.zeros(q_.shape, jnp.float32),
                jnp.full((B, H, Sq), _NEG_INF, jnp.float32))

    def step(carry, step_idx):
        o_run, lse_run, k_cur, v_cur = carry
        src = (my - step_idx) % n
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        if causal:
            branch = jnp.where(src > my, 0, jnp.where(src == my, 1, 2))
            o_blk, lse_blk = lax.switch(branch,
                                        [skip_blk, diag_blk, full_blk],
                                        q, k_cur, v_cur)
        else:
            o_blk, lse_blk = full_blk(q, k_cur, v_cur)
        o_run, lse_run = _merge_blocks(o_run, lse_run, o_blk, lse_blk)
        return (o_run, lse_run, k_nxt, v_nxt), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    o0, lse0 = (_pvary(t, axis_name) for t in (o0, lse0))
    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, axis_name, causal, sm_scale):
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale)
    return o


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, sm_scale):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale)
    return o, (q, k, v, o, lse)


def _ring_flash_vjp_bwd(axis_name, causal, sm_scale, res, do):
    q, k, v, o, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def blk(q_, k_, v_, causal_):
        dq_b, dk_b, dv_b = _fa_backward(
            q_, k_, v_, lse, delta, do, causal_, sm_scale)
        return (dq_b.astype(jnp.float32), dk_b.astype(jnp.float32),
                dv_b.astype(jnp.float32))

    def full_blk(q_, k_, v_):
        return blk(q_, k_, v_, False)

    def diag_blk(q_, k_, v_):
        return blk(q_, k_, v_, True)

    def skip_blk(q_, k_, v_):
        return (jnp.zeros(q_.shape, jnp.float32),
                jnp.zeros(k_.shape, jnp.float32),
                jnp.zeros(v_.shape, jnp.float32))

    def step(carry, step_idx):
        dq_acc, k_cur, v_cur, dk_acc, dv_acc = carry
        src = (my - step_idx) % n
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        if causal:
            branch = jnp.where(src > my, 0, jnp.where(src == my, 1, 2))
            dq_b, dk_b, dv_b = lax.switch(branch,
                                          [skip_blk, diag_blk, full_blk],
                                          q, k_cur, v_cur)
        else:
            dq_b, dk_b, dv_b = full_blk(q, k_cur, v_cur)
        # dk/dv accumulators ride the ring with their K/V shards
        dk_nxt = lax.ppermute(dk_acc + dk_b, axis_name, perm)
        dv_nxt = lax.ppermute(dv_acc + dv_b, axis_name, perm)
        return (dq_acc + dq_b, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0, dk0, dv0 = (_pvary(t, axis_name) for t in (dq0, dk0, dv0))
    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(q, k, v, axis_name="seq", causal=False, sm_scale=None):
    """Attention with K/V rotating around the `axis_name` ring.

    q: (B, H, Sq/n, D); k, v: (B, Hkv, Sk/n, D) — the per-device shards.
    GQA runs as grouped einsum over (kv_head, group): only the Hkv-headed
    K/V shards travel the ring, so ICI volume and carry HBM stay 1/(H/Hkv)
    of the repeated form. On TPU (or MXNET_FLASH_INTERPRET=1) the inner
    block runs the Pallas flash kernels in both directions.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if _fa_use_pallas(q, k) and q.shape[2] == k.shape[2]:
        return _ring_flash(q, k, v, axis_name, bool(causal),
                           float(sm_scale))
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    qf = q.reshape(B, Hkv, g, Sq, D).astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, step_idx):
        acc, m_run, l_run, k_cur, v_cur = carry
        # chunk index the current K/V block originated from
        src = (my - step_idx) % n
        # rotate early so transfer overlaps this block's compute
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        if causal:
            qi = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0) + my * Sq
            ki = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1) + src * Sk
            mask = (ki <= qi)[None, None, None]
        else:
            mask = None
        o, m_blk, l_blk = _block_attend(qf, k_cur.astype(jnp.float32),
                                        v_cur, mask, sm_scale)
        m_new = jnp.maximum(m_run, m_blk)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_blk - m_new)
        acc = acc * alpha + o * beta
        l_new = l_run * alpha + l_blk * beta
        return (acc, m_new, l_new, k_nxt, v_nxt), None

    acc0 = jnp.zeros((B, Hkv, g, Sq, D), jnp.float32)
    m0 = jnp.full((B, Hkv, g, Sq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, Sq, 1), jnp.float32)
    # constants enter the scan carry device-varying (they become varying
    # through the masked block math) — mark them so under shard_map
    acc0, m0, l0 = (_pvary(t, axis_name) for t in (acc0, m0, l0))
    (acc, _, l, _, _), _ = lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).reshape(B, H, Sq, D).astype(q.dtype)

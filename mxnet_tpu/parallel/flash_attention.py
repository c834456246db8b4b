"""Fused (flash) attention for TPU — forward AND backward Pallas kernels.

The reference's fused attention is the contrib transformer op family
(`_contrib_interleaved_matmul_selfatt_qk` etc.,
src/operator/contrib/transformer.cc) — CUDA batched-GEMM fusions with O(S^2)
memory in both directions. The TPU-native answer is a flash-attention-2
kernel pair: online softmax over K/V tiles streamed through VMEM on the
forward (O(S) HBM traffic, MXU matmuls, fp32 accumulation), and a
rematerializing backward that recomputes each S-tile IN the kernel from the
saved logsumexp — dq/dk/dv each see O(S) HBM bytes instead of the S^2
probability matrix the reference's backward streams.

Layout: grid (batch, head, outer-block, inner-block) with the inner
dimension sequential ("arbitrary") so accumulators live in VMEM scratch
across the sweep. Non-128-multiple sequence lengths are handled by in-kernel
bounds masks; causal uses the (Sk - Sq) diagonal offset convention so
Sq != Sk cross-attention decodes correctly. The per-row statistics
(logsumexp, delta) cross the kernel boundary as (B, H, 1, Sq) in blocks of
(1, 1, 1, block_q): Mosaic wants a block's last two dimensions to be
multiples of (8, 128) or the array's own, which a (1, block_q) block of an
(H, Sq) array is not.

Shapes: q (B, H, Sq, D); k/v (B, Hkv, Sk, D) with H % Hkv == 0 (GQA/MQA).

Set MXNET_FLASH_INTERPRET=1 to run the Pallas kernels in interpreter mode
on CPU (the test suite uses this to pin kernel correctness without a chip).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "paged_attention", "paged_attention_chunk"]

_NEG_INF = -1e30


def _interpret():
    return os.environ.get("MXNET_FLASH_INTERPRET", "0") == "1"


def _ref_attention(q, k, v, causal, sm_scale):
    """Plain-XLA attention, fp32 softmax. Used for CPU fallback and as the
    recompute body of the non-Pallas backward.

    GQA runs as a grouped einsum over (kv_head, group) axes rather than
    jnp.repeat of K/V: no materialized copies, and the repeat's reshape+sum
    VJP pattern reshards badly under GSPMD."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Sq, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0) + (Sk - Sq)
        ki = lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        logits = jnp.where(ki <= qi, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, Sq, D)


def _bounds_mask(s, q_start, k_start, block_q, block_k, seq_q, seq_k,
                 causal):
    """Mask logits for causal structure and for rows/cols past the true
    sequence ends (non-divisible block grids read garbage there)."""
    qi = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_start
    ki = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k_start
    valid = ki < seq_k
    if causal:
        valid = valid & (ki <= qi + (seq_k - seq_q))
    return jnp.where(valid, s, _NEG_INF)


def _zero_pad_rows(x, start, seq):
    """Zero tile rows past the true sequence end. A padded block read
    returns garbage (NaN in interpret mode), and 0 * NaN = NaN would leak
    through the dots even where probabilities are exactly zero."""
    rows = lax.broadcasted_iota(jnp.int32, x.shape, 0) + start
    return jnp.where(rows < seq, x, 0.0)


def _out_struct(shape, dtype, *args):
    """ShapeDtypeStruct carrying the union of the inputs' varying-mesh-axes
    (vma): required when the kernels run inside shard_map (the ring path)
    under check_vma."""
    vma = frozenset().union(*[jax.typeof(a).vma for a in args])
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc, *,
                sm_scale, causal, block_q, block_k, seq_q, seq_k):
    """One (batch, head, q-block, k-block) grid step. Grid's last dim is the
    sequential K sweep; accumulators live in VMEM scratch across it."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q_start = i * block_q
    k_start = j * block_k
    # causal: skip blocks strictly above the (offset) diagonal
    run = True if not causal else (
        k_start <= q_start + (seq_k - seq_q) + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _bounds_mask(s, q_start, k_start, block_q, block_k,
                         seq_q, seq_k, causal)
        m_prev = m_sc[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = _zero_pad_rows(v_ref[0, 0].astype(jnp.float32), k_start,
                           seq_k)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == nk - 1)
    def _out():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / l_safe).astype(o_ref.dtype)
        # logsumexp per row, consumed by the backward's in-kernel recompute.
        # The scratch holds each row's value in all 128 lanes, so any row
        # of its transpose is the lane-dense (1, block_q) block.
        lse = m_sc[...] + jnp.log(jnp.where(l_sc[...] == 0.0, 1.0,
                                            l_sc[...]))
        lse_ref[0, 0] = lse.T[:1]


def _pallas_forward(q, k, v, causal, sm_scale, block_q=128, block_k=128):
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    group = H // Hkv

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=Sq, seq_k=Sk)

    from ..ops.pallas_stats import compiler_params

    call = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            _out_struct(q.shape, q.dtype, q, k, v),
            _out_struct((B, H, 1, Sq), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=compiler_params(("parallel", "parallel", "parallel",
                                         "arbitrary")),
        name="flash_fwd",   # the HLO instruction, and so the device trace
    )
    o, lse = call(q, k, v)
    return o, lse.reshape(B, H, Sq)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               dq_acc, *, sm_scale, causal, block_q, block_k,
               seq_q, seq_k):
    """dq = sum_j dS_ij K_j — grid (B, H, q-block, k-block), K sweep
    sequential, dq accumulated in VMEM."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = i * block_q
    k_start = j * block_k
    run = True if not causal else (
        k_start <= q_start + (seq_k - seq_q) + block_q - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = _zero_pad_rows(k_ref[0, 0].astype(jnp.float32), k_start, seq_k)
        v = _zero_pad_rows(v_ref[0, 0].astype(jnp.float32), k_start, seq_k)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]
        delta = dl_ref[0, 0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _bounds_mask(s, q_start, k_start, block_q, block_k,
                         seq_q, seq_k, causal)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _out():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                block_q, block_k, seq_q, seq_k):
    """dk/dv for one K-block — grid (B, H, k-block, q-block), Q sweep
    sequential. Emits per-ATTENTION-head dk/dv; the GQA group-sum happens
    in XLA after the call (one reshape+sum, no S^2 traffic)."""
    j = pl.program_id(2)
    i = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = i * block_q
    k_start = j * block_k
    run = True if not causal else (
        k_start <= q_start + (seq_k - seq_q) + block_q - 1)

    @pl.when(run)
    def _step():
        q = _zero_pad_rows(q_ref[0, 0].astype(jnp.float32), q_start, seq_q)
        k = k_ref[0, 0].astype(jnp.float32)
        v = _zero_pad_rows(v_ref[0, 0].astype(jnp.float32), k_start, seq_k)
        do = _zero_pad_rows(do_ref[0, 0].astype(jnp.float32), q_start,
                            seq_q)
        qrow = lax.broadcasted_iota(jnp.int32, (1, block_q), 1) + q_start
        lse = jnp.where(qrow < seq_q, lse_ref[0, 0], 0.0)[0]
        delta = jnp.where(qrow < seq_q, dl_ref[0, 0], 0.0)[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _bounds_mask(s, q_start, k_start, block_q, block_k,
                         seq_q, seq_k, causal)
        p = jnp.exp(s - lse[:, None])
        # rows past seq_q carry no probability mass (lse sanitized above
        # would otherwise make exp(0-0)=1 rows)
        qi = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_start
        p = jnp.where(qi < seq_q, p, 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _out():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _pallas_backward(q, k, v, o, lse, do, causal, sm_scale,
                     block_q=128, block_k=128):
    # delta_i = rowsum(dO_i * O_i): one fused elementwise+reduce in XLA
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _pallas_backward_inner(q, k, v, lse, delta, do, causal, sm_scale,
                                  block_q=block_q, block_k=block_k)


def _pallas_backward_inner(q, k, v, lse, delta, do, causal, sm_scale,
                           block_q=128, block_k=128):
    """dq/dk/dv kernels from precomputed (lse, delta). Split out so ring
    attention can run per-block backwards against the GLOBAL logsumexp."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)
    group = H // Hkv

    from ..ops.pallas_stats import compiler_params
    cparams = compiler_params(("parallel", "parallel", "parallel",
                               "arbitrary"))
    lse = lse.reshape(B, H, 1, Sq)
    delta = delta.reshape(B, H, 1, Sq)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, i, j, g=group: (b, h // g, j, 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, h, i, j: (b, h, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          seq_q=Sq, seq_k=Sk),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=_out_struct(q.shape, q.dtype, q, k, v, do,
                              lse, delta),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_interpret(),
        compiler_params=cparams,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: grid transposed so the K-block is the parallel dim
    q_spec_t = pl.BlockSpec((1, 1, block_q, D),
                            lambda b, h, j, i: (b, h, i, 0))
    kv_spec_t = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, j, i, g=group: (b, h // g, j, 0))
    row_spec_t = pl.BlockSpec((1, 1, 1, block_q),
                              lambda b, h, j, i: (b, h, 0, i))
    out_kv_t = pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, j, i: (b, h, j, 0))

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          seq_q=Sq, seq_k=Sk),
        grid=(B, H, nk, nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[out_kv_t, out_kv_t],
        out_shape=[
            _out_struct((B, H, Sk, D), k.dtype, q, k, v, do, lse, delta),
            _out_struct((B, H, Sk, D), v.dtype, q, k, v, do, lse, delta),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=cparams,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)

    if group > 1:
        dk = dk_h.reshape(B, Hkv, group, Sk, D).sum(axis=2)
        dv = dv_h.reshape(B, Hkv, group, Sk, D).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _use_pallas(q, k):
    # lane-friendly head dim; seq lengths are masked in-kernel so any
    # Sq/Sk works. GQA requires an integer group (a non-divisible head
    # count would make the kv BlockSpec silently clamp to a wrong head).
    if os.environ.get("MXNET_FLASH_DISABLE", "0") == "1":
        return False            # force the plain-XLA path (A/B probes)
    D = q.shape[3]
    shapes_ok = D % 8 == 0 and q.shape[1] % k.shape[1] == 0
    if _interpret():
        return shapes_ok
    if jax.default_backend() != "tpu":
        return False
    return shapes_ok


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, sm_scale):
    if _use_pallas(q, k):
        o, _ = _pallas_forward(q, k, v, causal, sm_scale)
        return o
    return _ref_attention(q, k, v, causal, sm_scale)


def _flash_fwd(q, k, v, causal, sm_scale):
    if _use_pallas(q, k):
        o, lse = _pallas_forward(q, k, v, causal, sm_scale)
        return o, (q, k, v, o, lse)
    return _ref_attention(q, k, v, causal, sm_scale), (q, k, v, None, None)


def _flash_bwd(causal, sm_scale, res, g):
    q, k, v, o, lse = res
    if lse is not None:
        return _pallas_backward(q, k, v, o, lse, g, causal, sm_scale)
    # non-Pallas path: rematerialized backward under XLA (differentiates
    # the recompute; reference keeps the full S^2 prob matrix in HBM
    # instead — src/operator/contrib/transformer.cc backward)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_attention(q_, k_, v_, causal, sm_scale),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """Fused scaled-dot-product attention.

    q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D), H divisible by Hkv.
    Returns (B, H, Sq, D) in q's dtype.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _flash(q, k, v, bool(causal), float(sm_scale))


def paged_attention(q, k_pool, v_pool, block_tables, lengths, sm_scale=None):
    """Single-token attention over a paged KV pool (the serving decode path).

    The KV cache lives as fixed-size blocks in one physical pool per layer
    (`mxnet_tpu.serve.KVBlockPool`); each stream owns a block table mapping
    its logical positions onto pool blocks — long contexts cost exactly the
    blocks they fill, not a max_seq_len rectangle per batch slot.

    q:            (B, H, 1, D) — one new query token per stream.
    k_pool/v_pool:(N, Hkv, bs, D) — the shared physical pool (N blocks of
                  bs tokens). H divisible by Hkv (GQA).
    block_tables: (B, nb) int32 — per-stream block ids, logical block j of
                  stream b at entry [b, j]. Entries >= N mark unallocated
                  tail blocks; the gather clamps them and the length mask
                  discards whatever they read.
    lengths:      (B,) int32 — valid context length per stream (the new
                  token's KV must already be written to the pool). Must be
                  >= 1 (inactive batch slots pass 1 and ignore the output)
                  so the softmax never normalizes over an empty row.

    Returns (B, H, 1, D) in q's dtype. Same grouped-einsum structure and
    fp32 softmax as `_ref_attention`, so paged decode matches the unpaged
    reference bit-for-bit on the positions the mask keeps.
    """
    B, H, _, D = q.shape
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    g = H // Hkv
    if sm_scale is None:
        sm_scale = D ** -0.5
    # gather each stream's pages: (B, nb, Hkv, bs, D) -> (B, Hkv, nb*bs, D)
    k = k_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    v = v_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    qg = q.reshape(B, Hkv, g, 1, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = lax.broadcasted_iota(jnp.int32, (B, 1, 1, 1, nb * bs), 4) \
        < lengths[:, None, None, None, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, 1, D).astype(q.dtype)


def paged_attention_chunk(q, k_pool, v_pool, block_tables, q_lengths,
                          sm_scale=None):
    """Multi-query attention over a paged KV pool with PER-QUERY lengths —
    the chunked-prefill / speculative-verify generalization of
    `paged_attention` (which is the C=1 special case).

    A chunk of C tokens from one stream occupies consecutive positions
    whose KV has just been scattered into the pool; query c may only see
    positions < q_lengths[b, c] (its own position + 1 — causality ACROSS
    the pool, not just within the chunk, so a chunk attends to every
    earlier chunk and to a shared prefix for free).

    q:            (B, H, C, D) — C new query tokens per stream.
    k_pool/v_pool:(N, Hkv, bs, D) — the shared physical pool.
    block_tables: (B, nb) int32 — per-stream block ids (entries >= N are
                  unallocated; the length mask discards their rows).
    q_lengths:    (B, C) int32 — valid context length per query (the
                  query's own KV already written). Rows for padded /
                  inactive queries pass 1 and ignore the output.

    Returns (B, H, C, D) in q's dtype — the same grouped-einsum fp32
    softmax as `paged_attention`, so a C=1 call and a decode call agree
    on the positions the masks keep."""
    B, H, C, D = q.shape
    Hkv, bs = k_pool.shape[1], k_pool.shape[2]
    nb = block_tables.shape[1]
    g = H // Hkv
    if sm_scale is None:
        sm_scale = D ** -0.5
    k = k_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    v = v_pool[block_tables].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, nb * bs, D)
    qg = q.reshape(B, Hkv, g, C, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = lax.broadcasted_iota(jnp.int32, (B, 1, 1, C, nb * bs), 4) \
        < q_lengths[:, None, None, :, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, C, D).astype(q.dtype)

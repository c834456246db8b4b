"""Linear attention with a gated delta rule (Gated DeltaNet), in chunks.

Per value head, with a state S of shape (dk, dv) that starts at zero, a
decay ``g_t <= 0`` and a write strength ``beta_t`` in (0, 1):

    S' = exp(g_t) S_{t-1};  r = S'^T k_t
    S_t = S' + k_t (beta_t (v_t - r))^T;  o_t = S_t^T q_t

`gated_delta_rule` computes it in chunks of C positions. Inside a chunk the
rows ``u_t = beta_t (v_t - r_t)`` solve a unit lower-triangular system,
``(I + A) U = beta V - (beta gamma K) S_0`` with ``A_tj = beta_t (k_t . k_j)
gamma_t / gamma_j`` for j < t and gamma the running product of the decays, so
``U = T (beta V) - T (beta gamma K) S_0`` with ``T = (I + A)^-1``; across
chunks one (dk, dv) state a head is carried by a `lax.scan`. Everything is
batched matrix products, and the backward is autodiff's but for the
inverse's: the scan keeps one state a chunk (4 MB a chunk at 2 x 32 heads of
128 x 128), which fits where a token-by-token scan's 4,096 states would not.

T comes from the nilpotence of A (``A^C = 0``): ``(I + A)^-1 = (I + N)(I +
N^2)(I + N^4)...`` with ``N = -A``, log2(C) squarings, the factors kept in
float32 and multiplied at `highest`. The decays, their running sums and the
state are float32; every other product takes its operands in the inputs'
type with float32 out of the MXU.

Where the Pallas kernels are on (`pallas_stats.pallas_on`) and C is 64 or
128, T and its backward are one kernel each, `gdn_inverse` and
`gdn_inverse_bwd`: a group of chunks' (C, C) blocks is read into VMEM once,
taken through every product there and written once. Elsewhere the same
products are XLA's, each a pass over every block in HBM.

Beside it: the causal depthwise convolution in front of the rule and the
gated RMSNorm behind it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from . import pallas_stats
from .pallas_stats import compiler_params, note_dispatch, note_fallback

__all__ = ["causal_conv1d", "l2_normalize", "gated_rms_norm",
           "gated_delta_rule", "gated_delta_rule_recurrent"]

F32 = jnp.float32


def causal_conv1d(u, w):
    """Depthwise convolution along the sequence that sees no later
    position: ``c_t = sum_j w[:, j] * u_{t-K+1+j}``, zeros before the start.
    u (B, S, C); w (C, K)."""
    S, K = u.shape[1], w.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(u.dtype)
    return sum(padded[:, j:j + S] * w[:, j] for j in range(K))


def l2_normalize(x, eps=1e-6):
    """``x * rsqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    xf = x.astype(F32)
    return (xf * lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


def gated_rms_norm(o, z, w, eps=1e-6):
    """``o * rsqrt(mean(o^2) + eps) * w * silu(z)`` over the last axis, the
    statistic in float32."""
    of = o.astype(F32)
    scale = lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    return (of * scale * w * jax.nn.silu(z.astype(F32))).astype(o.dtype)


def _bmm(a, b, spec, precision=None):
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=F32)


INVERSE_NAME = "gdn_inverse"    # for a `jax.checkpoint` policy that keeps it


def _inverse_xla(a, dtype):
    """The factors as batched XLA products: ``I + m`` in float32, ten
    products for C = 64, each a pass over every block in HBM."""
    C, hi = a.shape[-1], lax.Precision.HIGHEST
    n = -a
    m = n
    power = 2
    while power < C:
        n = _bmm(n, n, "...ij,...jk->...ik", hi)
        m = m + n + _bmm(m, n, "...ij,...jk->...ik", hi)
        power *= 2
    return (jnp.eye(C, dtype=F32) + m).astype(dtype)


def _inverse_bwd_xla(t, g):
    left = _bmm(t, g, "...ji,...jk->...ik").astype(t.dtype)
    return -_bmm(left, t, "...ij,...kj->...ik")


_LANES = 128                # the MXU's width: two blocks of 64 side by side
_GROUP = {64: 32, 128: 8}   # C: blocks a grid step (16 MiB of VMEM hold them)


def _inverse_kernel(a_ref, t_ref):
    """A group's blocks from `a` to T in VMEM. The same factors as
    `_inverse_xla`, ordered so that a level's two products share their
    right operand and run as one, stacked on rows: ``[N^p; I + M] N^p``
    gives ``N^2p`` and ``(I + M) N^p``. The refs hold the blocks `pack` at
    a time, (G / pack, pack, C, C): where C is half the MXU's width a pair
    lies side by side on lanes, (C, 2C), against its block-diagonal
    (2C, 2C), so every product is 128 wide."""
    _, pack, C, _ = a_ref.shape
    hi = lax.Precision.HIGHEST
    row = lax.broadcasted_iota(jnp.int32, (C, pack * C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, pack * C), 1)

    def times(x, y):    # x (G, rows, W) by the block-diagonal of y (G, C, W)
        if pack > 1:
            y = jnp.concatenate([jnp.where(col // C == p, y, 0.0)
                                 for p in range(pack)], axis=-2)
        return jnp.einsum("gij,gjk->gik", x, y, precision=hi,
                          preferred_element_type=F32)

    n = -jnp.concatenate([a_ref[:, p] for p in range(pack)], axis=-1)
    t = (row == col % C).astype(F32) + n                # I + N
    n = times(n, n)
    power = 4
    while power < C:
        both = times(jnp.concatenate([n, t], axis=-2), n)
        n, t = both[:, :C], t + both[:, C:]
        power *= 2
    t = t + times(t, n)
    for p in range(pack):
        t_ref[:, p] = t[:, :, p * C:(p + 1) * C].astype(t_ref.dtype)


def _inverse_bwd_kernel(t_ref, g_ref, da_ref):
    da_ref[...] = _inverse_bwd_xla(t_ref[...], g_ref[...])


def _grouped_blocks(x):
    """(..., C, C) as (M', C, C), M' the next multiple of the group: zero
    blocks behind the last (their inverse is I, and nobody reads it)."""
    x = x.reshape((-1,) + x.shape[-2:])
    return jnp.pad(x, ((0, -x.shape[0] % _GROUP[x.shape[-1]]), (0, 0),
                       (0, 0)))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _inverse_pallas(a, dtype, interpret):
    blocks = _grouped_blocks(a)
    M, C, _ = blocks.shape
    pack, group = _LANES // C, _GROUP[C]
    spec = pl.BlockSpec((group // pack, pack, C, C), lambda i: (i, 0, 0, 0))
    t = pl.pallas_call(
        _inverse_kernel, grid=(M // group,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((M // pack, pack, C, C), dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
        name="gdn_inverse",     # the HLO instruction, and so the device trace
    )(blocks.reshape(M // pack, pack, C, C))
    return t.reshape(M, C, C)[:a.size // (C * C)].reshape(a.shape)


@functools.partial(jax.jit, static_argnums=(2,))
def _inverse_bwd_pallas(t, g, interpret):
    blocks = _grouped_blocks(t)
    M, C, _ = blocks.shape
    spec = pl.BlockSpec((_GROUP[C], C, C), lambda i: (i, 0, 0))
    da = pl.pallas_call(
        _inverse_bwd_kernel, grid=(M // _GROUP[C],), in_specs=[spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(blocks.shape, F32),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret, name="gdn_inverse_bwd",
    )(blocks, _grouped_blocks(g))
    return da[:t.size // (C * C)].reshape(t.shape)


def _takes_kernel(blocks, kernel):
    """Whether `kernel` runs over these (..., C, C) blocks, counted once a
    trace: a dispatch, or where the kernels are on and have no group for
    this C a fallback to the XLA products."""
    if not pallas_stats.pallas_on():
        return False
    if blocks.shape[-1] in _GROUP:
        note_dispatch(kernel)
        return True
    note_fallback("gdn_inverse", "chunk")
    return False


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(a, dtype):
    """``(I + a)^-1`` of strictly lower-triangular (..., C, C) float32
    blocks, rounded to `dtype`. By nilpotence it is ``(I + N)(I + N^2)(I +
    N^4)...`` with ``N = -a``, kept in float32 and multiplied at `highest`,
    whatever the program's matmul precision: the powers of N cancel in the
    sum, and a squaring doubles the relative error of its operand, so one
    bfloat16 pass (2^-9) would leave the highest powers with a tenth of
    themselves. As XLA's the products are passes over every chunk's (C, C)
    block in HBM, 7.6 ms for 2 x 32 heads of 4,096 positions on a v5e at
    either precision; the kernel keeps a block in VMEM through all of them.

    Its backward is the inverse's own, ``-T^T G T^T``: two products where
    autodiff through the ten above makes twenty. The result carries
    `INVERSE_NAME`, so that a recomputing caller can keep it (34 MB a layer
    at 2 x 32 heads and 4,096 positions) and not make the ten again."""
    if _takes_kernel(a, "gdn_inverse"):
        return _inverse_pallas(a, dtype, pallas_stats.interpret())
    return _inverse_xla(a, dtype)


def _inverse_fwd(a, dtype):
    t = checkpoint_name(_unit_lower_inverse(a, dtype), INVERSE_NAME)
    return t, t


def _inverse_bwd(dtype, t, g):
    g = g.astype(t.dtype)
    if _takes_kernel(t, "gdn_inverse_bwd"):
        return (_inverse_bwd_pallas(t, g, pallas_stats.interpret()),)
    return (_inverse_bwd_xla(t, g),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """The gated delta rule over whole sequences, in chunks.

    q, k (B, S, Hk, dk), already normalised and scaled as the model wants
    them; v (B, S, Hv, dv) with Hv a multiple of Hk (key head h serves value
    heads h*Hv/Hk ...); g, beta (B, S, Hv) float32. Returns o (B, S, Hv, dv)
    in v's type. S need not be a multiple of `chunk`."""
    B, S, Hk, dk = q.shape
    Hv, dv = v.shape[2], v.shape[3]
    dt, C = v.dtype, chunk
    pad = -S % C
    if pad:
        # padded positions write nothing (beta 0, k 0) and decay nothing
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (x.ndim - 2)) for x in (q, k, v, g, beta))
    N = (S + pad) // C

    def chunks(x):     # (B, S, H, ...) -> (B, H, N, C, ...)
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    rep = Hv // Hk
    q, k = (chunks(jnp.repeat(x, rep, axis=2) if rep > 1 else x)
            for x in (q, k))
    v, g, beta = chunks(v), chunks(g.astype(F32)), chunks(beta.astype(F32))
    # two scopes for the compiled step's map (`telemetry.module_scopes()`):
    # what is local to a chunk, and the scan over chunks with what it
    # carries; the chunking above and below them is the layer's own
    with jax.named_scope("delta_chunk"):
        gsum = jnp.cumsum(g, axis=-1)                   # (B, Hv, N, C)
        lower = jnp.tril(jnp.ones((C, C), bool))
        diff = gsum[..., :, None] - gsum[..., None, :]
        # exp only where i >= j: above the diagonal the difference is
        # positive
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kk = _bmm(k, k, "bhnid,bhnjd->bhnij")
        a = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                      beta[..., None] * kk * decay, 0.0)
        t = _unit_lower_inverse(a, dt)
        gamma = jnp.exp(gsum)[..., None]
        k_beta = k.astype(F32) * beta[..., None]
        u = _bmm(t, (v.astype(F32) * beta[..., None]).astype(dt),
                 "bhnij,bhnjd->bhnid")
        w = _bmm(t, (k_beta * gamma).astype(dt),
                 "bhnij,bhnjd->bhnid").astype(dt)
        attn = (_bmm(q, k, "bhnid,bhnjd->bhnij") * decay).astype(dt)
        q_in = (q.astype(F32) * gamma).astype(dt)
        last = gsum[..., -1:]
        k_out = (k.astype(F32) * jnp.exp(last - gsum)[..., None]).astype(dt)

    def step(state, xs):
        u_n, w_n, attn_n, q_n, k_n, decay_n = xs
        s = state.astype(dt)
        new = (u_n - _bmm(w_n, s, "bhid,bhde->bhie")).astype(dt)
        o = _bmm(q_n, s, "bhid,bhde->bhie") + _bmm(attn_n, new,
                                                  "bhij,bhje->bhie")
        state = state * decay_n + _bmm(k_n, new, "bhid,bhie->bhde")
        return state, o.astype(dt)

    def by_chunk(x):
        return jnp.moveaxis(x, 2, 0)
    with jax.named_scope("delta_scan"):
        xs = tuple(by_chunk(x) for x in (u, w, attn, q_in, k_out,
                                         jnp.exp(last)[..., None]))
        _, o = lax.scan(step, jnp.zeros((B, Hv, dk, dv), F32), xs)
        o = jnp.moveaxis(o, 0, 2)                       # (B, Hv, N, C, dv)
    return jnp.moveaxis(o, 1, 3).reshape(B, N * C, Hv, dv)[:, :S]


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The same rule token by token, in float32 at `highest`: what the
    chunked form is tested against (every state is kept for the backward, so
    short sequences only)."""
    B, S, Hk, dk = q.shape
    Hv = v.shape[2]
    rep = Hv // Hk
    hi = lax.Precision.HIGHEST
    q, k = (jnp.repeat(x.astype(F32), rep, axis=2) for x in (q, k))

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        r = jnp.einsum("bhde,bhd->bhe", state, k_t, precision=hi)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t,
                                   b_t[..., None] * (v_t - r), precision=hi)
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t, precision=hi)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((B, Hv, dk, v.shape[3]), F32), xs)
    return jnp.moveaxis(o, 0, 1)

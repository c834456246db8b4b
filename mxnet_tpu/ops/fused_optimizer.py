"""Pallas fused optimizer-update kernels (ISSUE 10 tentpole part 2).

The "Tensor Processing Primitives" blueprint (PAPERS.md) applied to the
weight update: ONE streaming pass over param/grad/state tiles resident in
VMEM instead of XLA's separate elementwise loops, for the three optimizers
that matter at scale:

* **SGD(-momentum)** — `flat_update_fn("sgd", ...)`: weight, grad,
  momentum (and the fp32 master under multi-precision) are read once per
  tile, the whole update rule runs on the registers, and every output is
  written once.
* **Adam** — `flat_update_fn("adam", ...)`: same single pass over five
  tensors (w, g, mean, var [, master]).
* **LAMB** — two passes by data dependency (`lamb_flat_phase1_fn` +
  `lamb_flat_apply_fn`): pass 1 runs the moment update + direction AND
  reduces the per-SEGMENT squared norms (`BucketLayout` segment
  boundaries → per-parameter ‖w‖², ‖g‖²) from the very same VMEM tiles;
  after the tiny cross-rank norm exchange, pass 2 applies the
  trust-ratio-scaled step in one more pass.

Dispatch surfaces (both gated by `use_pallas_flat`):

* the ZeRO flat-shard path — `optimizer._fused_flat_fn` returns these
  wrappers, so `ZeroUpdater` runs them without knowing;
* the per-parameter registry path — `tpu_impl` overrides on
  `sgd_update` / `sgd_mom_update` / `adam_update` /
  `lamb_update_phase1` / `lamb_update_phase2`, taken by the eager
  `optimizer._run_op` on an accelerator context under the registry's
  `MXNET_TPU_USE_PALLAS` gate.

Every wrapper shape/dtype-gates AUTOMATICALLY: an ineligible call (non-f32
per-param weights, integer tensors, empty shards) is counted under
`ops.pallas.fallback.<reason>` and routed to the always-correct XLA
composite — never an error. Eligible dispatches count
`ops.pallas.dispatch(.<kernel>)` and ride a `pallas.<kernel>` telemetry
span (ops/pallas_stats.py); `parse_log --kernels` renders the table.

Numerics: the kernels execute the SAME elementwise operations in the same
order as the XLA composites (`optimizer._fused_flat_xla`, the
optimizer_ops), so SGD/Adam results are bit-identical in interpreter mode
(tests assert equality). LAMB's per-segment norm reduction accumulates
per-tile (Pallas) vs per-slice (XLA), so trust ratios agree only to fp32
round-off — parity tests use a documented tolerance.

Interpreter caveat: `MXNET_FLASH_INTERPRET=1` runs every kernel through
the Pallas interpreter on the CPU backend — parity evidence only, never
perf evidence (the interpreter serializes the grid).
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import optimizer_ops as _opt_ops
from . import pallas_stats as _pstats
from . import registry as _reg
from .pallas_stats import compiler_params as _compiler_params

__all__ = ["use_pallas_flat", "flat_update_fn", "lamb_flat_phase1_fn",
           "lamb_flat_apply_fn"]

_LANES = 128
_SUBLANES = 8
_MAX_TILE_ROWS = 1024    # 1024x128 f32 tile = 512 KB; <=6 operand tiles
                         # + outputs stay well inside the 16 MB VMEM


def use_pallas_flat():
    """Is the Pallas optimizer path requested? Interpreter runs always take
    it (that is what they test); compiled runs need the TPU backend plus
    the MXNET_TPU_USE_PALLAS opt-in."""
    if _pstats.interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    return _pstats.use_pallas(False)


def _flat_geometry(n):
    """(padded_rows, tile_rows, grid) for a flat length-n vector laid out
    as (rows, 128) f32-friendly tiles."""
    rows = max(_SUBLANES, -(-n // _LANES))
    rows = -(-rows // _SUBLANES) * _SUBLANES
    if rows <= _MAX_TILE_ROWS:
        return rows, rows, 1
    rows = -(-rows // _MAX_TILE_ROWS) * _MAX_TILE_ROWS
    return rows, _MAX_TILE_ROWS, rows // _MAX_TILE_ROWS


def _pad2d(flat, rows):
    pad = rows * _LANES - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES)


def _unpad(tile2d, n):
    return tile2d.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# kernels — scalars ride one (1, S) SMEM pack; lr/wd arrive either as
# per-element tiles (the ZeRO flat path: per-parameter multipliers survive
# the flattening) or as two scalar slots (the per-param registry path)
# ---------------------------------------------------------------------------
def _kernel_flat_sgd(*refs, momentum_on, clip_on, mp_on, vec_lrwd):
    it = iter(refs)
    w_ref, g_ref = next(it), next(it)
    m_ref = next(it) if momentum_on else None
    mst_ref = next(it) if mp_on else None
    lr_ref = next(it) if vec_lrwd else None
    wd_ref = next(it) if vec_lrwd else None
    s_ref = next(it)
    w_out = next(it)
    m_out = next(it) if momentum_on else None
    mst_out = next(it) if mp_on else None

    w = w_ref[...]
    w32 = mst_ref[...] if mp_on else w.astype(jnp.float32)
    g32 = g_ref[...].astype(jnp.float32) * s_ref[0, 1]
    if clip_on:
        g32 = jnp.clip(g32, -s_ref[0, 2], s_ref[0, 2])
    wd = wd_ref[...] if vec_lrwd else s_ref[0, 4]
    lr = lr_ref[...] if vec_lrwd else s_ref[0, 3]
    g32 = g32 + wd * w32
    if momentum_on:
        m = m_ref[...].astype(jnp.float32) * s_ref[0, 0] - lr * g32
        m_out[...] = m.astype(m_out.dtype)
        w32n = w32 + m
    else:
        w32n = w32 - lr * g32
    w_out[...] = w32n.astype(w_out.dtype)
    if mp_on:
        mst_out[...] = w32n


def _kernel_flat_adam(*refs, clip_on, mp_on, vec_lrwd):
    it = iter(refs)
    w_ref, g_ref, mean_ref, var_ref = next(it), next(it), next(it), next(it)
    mst_ref = next(it) if mp_on else None
    lr_ref = next(it) if vec_lrwd else None
    wd_ref = next(it) if vec_lrwd else None
    s_ref = next(it)
    w_out, m_out, v_out = next(it), next(it), next(it)
    mst_out = next(it) if mp_on else None

    w = w_ref[...]
    w32 = mst_ref[...] if mp_on else w.astype(jnp.float32)
    g32 = g_ref[...].astype(jnp.float32) * s_ref[0, 5]
    if clip_on:
        g32 = jnp.clip(g32, -s_ref[0, 6], s_ref[0, 6])
    wd = wd_ref[...] if vec_lrwd else s_ref[0, 8]
    lr = lr_ref[...] if vec_lrwd else s_ref[0, 7]
    g32 = g32 + wd * w32
    m = s_ref[0, 0] * mean_ref[...] + s_ref[0, 1] * g32
    v = s_ref[0, 2] * var_ref[...] + s_ref[0, 3] * g32 * g32
    w32n = w32 - lr * m / (jnp.sqrt(v) + s_ref[0, 4])
    w_out[...] = w32n.astype(w_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)
    if mp_on:
        mst_out[...] = w32n


def _kernel_flat_lamb1(*refs, clip_on, mp_on, bias_corr, vec_wd,
                       with_norms, n_keys, keys_padded):
    it = iter(refs)
    w_ref, g_ref, mean_ref, var_ref = next(it), next(it), next(it), next(it)
    mst_ref = next(it) if mp_on else None
    wd_ref = next(it) if vec_wd else None
    seg_ref = next(it) if with_norms else None
    s_ref = next(it)
    gd_out, m_out, v_out = next(it), next(it), next(it)
    p_out = next(it) if with_norms else None

    w = w_ref[...]
    w32 = mst_ref[...] if mp_on else w.astype(jnp.float32)
    g32 = g_ref[...].astype(jnp.float32) * s_ref[0, 7]
    if clip_on:
        g32 = jnp.clip(g32, -s_ref[0, 8], s_ref[0, 8])
    m = s_ref[0, 0] * mean_ref[...] + s_ref[0, 1] * g32
    v = s_ref[0, 2] * var_ref[...] + s_ref[0, 3] * g32 * g32
    if bias_corr:
        mh = m / s_ref[0, 4]
        vh = v / s_ref[0, 5]
    else:
        mh, vh = m, v
    wd = wd_ref[...] if vec_wd else s_ref[0, 9]
    gdir = mh / (jnp.sqrt(vh) + s_ref[0, 6]) + wd * w32
    gd_out[...] = gdir
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)
    if with_norms:
        # per-segment ||w||^2 / ||gdir||^2 partials from the tiles already
        # in VMEM: one statically-unrolled masked reduction per bucket key
        # (padding elements are zeros — they contribute nothing).
        # Scaling caveat: this is O(n_keys x tile) VPU work — fine for
        # conv-weight buckets (few keys) but a many-hundred-key bucket of
        # small BN/bias params degrades toward n_keys sweeps; a one-hot
        # contraction needs (tile, n_keys) VMEM it cannot afford at full
        # tile size. Chunked-dot variant is a kernel-layer-v2 follow-on
        # (ROADMAP item #2).
        seg = seg_ref[...]
        sqw = w32 * w32
        sqg = gdir * gdir
        wp = jnp.stack([jnp.sum(jnp.where(seg == k, sqw, 0.0))
                        for k in range(n_keys)])
        gp = jnp.stack([jnp.sum(jnp.where(seg == k, sqg, 0.0))
                        for k in range(n_keys)])
        zpad = jnp.zeros((keys_padded - n_keys,), jnp.float32)
        p_out[0, 0] = jnp.concatenate([wp, zpad]) if keys_padded > n_keys \
            else wp
        p_out[0, 1] = jnp.concatenate([gp, zpad]) if keys_padded > n_keys \
            else gp


def _kernel_flat_apply(*refs, mp_on, vec_scale):
    it = iter(refs)
    w_ref = next(it)
    mst_ref = next(it) if mp_on else None
    gd_ref = next(it)
    sc_ref = next(it) if vec_scale else None
    s_ref = next(it)
    w_out = next(it)
    mst_out = next(it) if mp_on else None

    w = w_ref[...]
    w32 = mst_ref[...] if mp_on else w.astype(jnp.float32)
    scale = sc_ref[...] if vec_scale else s_ref[0, 0]
    w32n = w32 - scale * gd_ref[...]
    w_out[...] = w32n.astype(w_out.dtype)
    if mp_on:
        mst_out[...] = w32n


# ---------------------------------------------------------------------------
# jitted wrappers: pad/reshape flat operands to (rows, 128) tiles, launch
# ONE pallas_call over the row grid, slice the padding back off
# ---------------------------------------------------------------------------
_CACHE = {}


def _tile_spec(tile_rows):
    return pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0))


def _scal_spec(n):
    return pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _scal_pack(*vals):
    return jnp.stack([jnp.asarray(v, jnp.float32).reshape(())
                      for v in vals]).reshape(1, -1)


def _launch(kernel, tiles, scal, out_dtypes, tile_rows, grid, rows,
            extra_out_specs=(), extra_out_shapes=()):
    cparams = _compiler_params(("arbitrary",))
    in_specs = [_tile_spec(tile_rows) for _ in tiles] + \
        [_scal_spec(scal.shape[1])]
    out_specs = [_tile_spec(tile_rows) for _ in out_dtypes] + \
        list(extra_out_specs)
    out_shapes = [jax.ShapeDtypeStruct((rows, _LANES), dt)
                  for dt in out_dtypes] + list(extra_out_shapes)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=_pstats.interpret(),
        compiler_params=cparams,
    )(*tiles, scal)


def _flat_sgd_impl(momentum_on, clip_on, mp_on, vec_lrwd):
    def impl(w, g, mom, master, lr, wd, momentum, rescale, clip):
        n = w.shape[0]
        rows, tr, grid = _flat_geometry(n)
        tiles = [_pad2d(w, rows), _pad2d(g, rows)]
        if momentum_on:
            tiles.append(_pad2d(mom, rows))
        if mp_on:
            tiles.append(_pad2d(master, rows))
        if vec_lrwd:
            tiles += [_pad2d(lr, rows), _pad2d(wd, rows)]
            scal = _scal_pack(momentum, rescale, clip, 0.0, 0.0)
        else:
            scal = _scal_pack(momentum, rescale, clip, lr, wd)
        out_dtypes = [w.dtype]
        if momentum_on:
            out_dtypes.append(mom.dtype)
        if mp_on:
            out_dtypes.append(jnp.float32)
        kern = functools.partial(_kernel_flat_sgd, momentum_on=momentum_on,
                                 clip_on=clip_on, mp_on=mp_on,
                                 vec_lrwd=vec_lrwd)
        outs = _launch(kern, tiles, scal, out_dtypes, tr, grid, rows)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        it = iter(outs)
        w_new = _unpad(next(it), n)
        mom_new = _unpad(next(it), n) if momentum_on else mom
        mst_new = _unpad(next(it), n) if mp_on else master
        return w_new, mom_new, mst_new
    return impl


def _flat_adam_impl(clip_on, mp_on, vec_lrwd):
    def impl(w, g, mean, var, master, lr, wd, beta1, omb1, beta2, omb2,
             eps, rescale, clip):
        n = w.shape[0]
        rows, tr, grid = _flat_geometry(n)
        tiles = [_pad2d(w, rows), _pad2d(g, rows), _pad2d(mean, rows),
                 _pad2d(var, rows)]
        if mp_on:
            tiles.append(_pad2d(master, rows))
        if vec_lrwd:
            tiles += [_pad2d(lr, rows), _pad2d(wd, rows)]
            scal = _scal_pack(beta1, omb1, beta2, omb2, eps, rescale, clip,
                              0.0, 0.0)
        else:
            scal = _scal_pack(beta1, omb1, beta2, omb2, eps, rescale, clip,
                              lr, wd)
        out_dtypes = [w.dtype, mean.dtype, var.dtype]
        if mp_on:
            out_dtypes.append(jnp.float32)
        kern = functools.partial(_kernel_flat_adam, clip_on=clip_on,
                                 mp_on=mp_on, vec_lrwd=vec_lrwd)
        outs = _launch(kern, tiles, scal, out_dtypes, tr, grid, rows)
        it = iter(outs)
        w_new = _unpad(next(it), n)
        m_new = _unpad(next(it), n)
        v_new = _unpad(next(it), n)
        mst_new = _unpad(next(it), n) if mp_on else master
        return w_new, m_new, v_new, mst_new
    return impl


def _jitted(key, builder):
    fn = _CACHE.get(key)
    if fn is None:
        fn = _CACHE[key] = jax.jit(builder())
    return fn


def _float_gate(*arrays):
    """Shape/dtype gate shared by every wrapper: floating tensors only,
    nothing empty. Returns a fallback reason or None."""
    for a in arrays:
        if a is None:
            continue
        if not jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating):
            return "dtype"
        if int(_np.prod(a.shape, dtype=_np.int64)) == 0:
            return "empty"
    return None


def flat_update_fn(kind, momentum_on, clip_on, mp_on):
    """Pallas analog of `optimizer._fused_flat_xla` — same call signature
    per kind, counted dispatch, automatic fallback to the XLA composite
    for ineligible operands."""
    name = "flat_%s" % kind

    if kind == "sgd":
        def wrapper(w, g, mom, master, lr_vec, wd_vec, momentum, rescale,
                    clip):
            reason = _float_gate(w, g, mom)
            if reason:
                from ..optimizer.optimizer import _fused_flat_xla
                _pstats.note_fallback(name, reason)
                return _fused_flat_xla(kind, momentum_on, clip_on, mp_on)(
                    w, g, mom, master, lr_vec, wd_vec, momentum, rescale,
                    clip)
            _pstats.note_dispatch(name)
            with _pstats.kernel_span(name):
                fn = _jitted(("sgd", momentum_on, clip_on, mp_on, True),
                             lambda: _flat_sgd_impl(momentum_on, clip_on,
                                                    mp_on, True))
                return fn(w, g, mom, master, lr_vec, wd_vec, momentum,
                          rescale, clip)
    elif kind == "adam":
        def wrapper(w, g, mean, var, master, lr_vec, wd_vec, beta1, omb1,
                    beta2, omb2, eps, rescale, clip):
            reason = _float_gate(w, g, mean, var)
            if reason:
                from ..optimizer.optimizer import _fused_flat_xla
                _pstats.note_fallback(name, reason)
                return _fused_flat_xla(kind, momentum_on, clip_on, mp_on)(
                    w, g, mean, var, master, lr_vec, wd_vec, beta1, omb1,
                    beta2, omb2, eps, rescale, clip)
            _pstats.note_dispatch(name)
            with _pstats.kernel_span(name):
                fn = _jitted(("adam", clip_on, mp_on, True),
                             lambda: _flat_adam_impl(clip_on, mp_on, True))
                return fn(w, g, mean, var, master, lr_vec, wd_vec, beta1,
                          omb1, beta2, omb2, eps, rescale, clip)
    else:
        raise KeyError(kind)
    return wrapper


# ---------------------------------------------------------------------------
# LAMB: pass 1 (moment update + direction + per-segment norm partials),
# tiny cross-rank norm exchange by the caller, pass 2 (trust-ratio apply)
# ---------------------------------------------------------------------------
def _keys_padded(n_keys):
    return max(_LANES, -(-n_keys // _LANES) * _LANES)


def _lamb1_xla_impl(clip_on, mp_on, bias_corr, segments, n_keys):
    def impl(w, g, mean, var, master, wd_vec, seg_ids, beta1, omb1, beta2,
             omb2, d1, d2, eps, rescale, clip):
        w32 = master if mp_on else w.astype(jnp.float32)
        g32 = g.astype(jnp.float32) * rescale
        if clip_on:
            g32 = jnp.clip(g32, -clip, clip)
        m = beta1 * mean + omb1 * g32
        v = beta2 * var + omb2 * g32 * g32
        if bias_corr:
            mh = m / d1
            vh = v / d2
        else:
            mh, vh = m, v
        gdir = mh / (jnp.sqrt(vh) + eps) + wd_vec * w32
        zero = jnp.zeros((), jnp.float32)
        wp, gp = [], []
        for k in range(n_keys):
            segs = [(s, ln) for (ki, s, ln) in segments if ki == k]
            wp.append(sum((jnp.sum(w32[s:s + ln] * w32[s:s + ln])
                           for s, ln in segs), zero))
            gp.append(sum((jnp.sum(gdir[s:s + ln] * gdir[s:s + ln])
                           for s, ln in segs), zero))
        norms = jnp.stack([jnp.stack(wp), jnp.stack(gp)])
        return gdir, m.astype(mean.dtype), v.astype(var.dtype), norms
    return impl


def _lamb1_pallas_impl(clip_on, mp_on, bias_corr, n_keys):
    kp = _keys_padded(n_keys)

    def impl(w, g, mean, var, master, wd_vec, seg_ids, beta1, omb1, beta2,
             omb2, d1, d2, eps, rescale, clip):
        n = w.shape[0]
        rows, tr, grid = _flat_geometry(n)
        tiles = [_pad2d(w, rows), _pad2d(g, rows), _pad2d(mean, rows),
                 _pad2d(var, rows)]
        if mp_on:
            tiles.append(_pad2d(master, rows))
        tiles += [_pad2d(wd_vec, rows), _pad2d(seg_ids, rows)]
        scal = _scal_pack(beta1, omb1, beta2, omb2, d1, d2, eps, rescale,
                          clip, 0.0)
        kern = functools.partial(
            _kernel_flat_lamb1, clip_on=clip_on, mp_on=mp_on,
            bias_corr=bias_corr, vec_wd=True, with_norms=True,
            n_keys=n_keys, keys_padded=kp)
        outs = _launch(
            kern, tiles, scal, [jnp.float32, mean.dtype, var.dtype],
            tr, grid, rows,
            extra_out_specs=[pl.BlockSpec((1, 2, kp), lambda i: (i, 0, 0))],
            extra_out_shapes=[
                jax.ShapeDtypeStruct((grid, 2, kp), jnp.float32)])
        gdir, m_new, v_new, partials = outs
        norms = jnp.sum(partials, axis=0)[:, :n_keys]
        return _unpad(gdir, n), _unpad(m_new, n), _unpad(v_new, n), norms
    return impl


def lamb_flat_phase1_fn(clip_on, mp_on, bias_corr, segments, n_keys):
    """LAMB pass 1 over a flat shard: moment update + raw direction + the
    per-key squared-norm partials this rank can see. `segments` is the
    static tuple of (key_index, start, length) from
    `BucketSpec.shard_segments`; `seg_ids` the matching per-element key
    index vector. Dispatches Pallas vs XLA like `flat_update_fn`."""
    name = "flat_lamb1"
    segments = tuple(tuple(s) for s in segments)

    def wrapper(w, g, mean, var, master, wd_vec, seg_ids, *scal):
        use_pallas = use_pallas_flat()
        reason = _float_gate(w, g, mean, var) if use_pallas else None
        if use_pallas and not reason:
            _pstats.note_dispatch(name)
            with _pstats.kernel_span(name):
                fn = _jitted(("lamb1p", clip_on, mp_on, bias_corr, n_keys),
                             lambda: _lamb1_pallas_impl(clip_on, mp_on,
                                                        bias_corr, n_keys))
                return fn(w, g, mean, var, master, wd_vec, seg_ids, *scal)
        if use_pallas:
            _pstats.note_fallback(name, reason)
        fn = _jitted(("lamb1x", clip_on, mp_on, bias_corr, segments,
                      n_keys),
                     lambda: _lamb1_xla_impl(clip_on, mp_on, bias_corr,
                                             segments, n_keys))
        return fn(w, g, mean, var, master, wd_vec, seg_ids, *scal)
    return wrapper


def _apply_pallas_impl(mp_on, vec_scale):
    def impl(w, master, gdir, scale):
        n = w.shape[0]
        rows, tr, grid = _flat_geometry(n)
        tiles = [_pad2d(w, rows)]
        if mp_on:
            tiles.append(_pad2d(master, rows))
        tiles.append(_pad2d(gdir, rows))
        if vec_scale:
            tiles.append(_pad2d(scale, rows))
            scal = _scal_pack(0.0)
        else:
            scal = _scal_pack(scale)
        out_dtypes = [w.dtype] + ([jnp.float32] if mp_on else [])
        kern = functools.partial(_kernel_flat_apply, mp_on=mp_on,
                                 vec_scale=vec_scale)
        outs = _launch(kern, tiles, scal, out_dtypes, tr, grid, rows)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        w_new = _unpad(outs[0], n)
        mst_new = _unpad(outs[1], n) if mp_on else master
        return w_new, mst_new
    return impl


def _apply_xla_impl(mp_on):
    def impl(w, master, gdir, scale):
        w32 = master if mp_on else w.astype(jnp.float32)
        w32n = w32 - scale * gdir
        return w32n.astype(w.dtype), (w32n if mp_on else master)
    return impl


def lamb_flat_apply_fn(mp_on, vec_scale=True):
    """LAMB pass 2: w -= scale * direction, where `scale` already carries
    lr x trust-ratio (per element on the flat path, scalar on the
    per-param path)."""
    name = "flat_lamb2"

    def wrapper(w, master, gdir, scale):
        use_pallas = use_pallas_flat()
        reason = _float_gate(w, gdir) if use_pallas else None
        if use_pallas and not reason:
            _pstats.note_dispatch(name)
            with _pstats.kernel_span(name):
                fn = _jitted(("lamb2p", mp_on, vec_scale),
                             lambda: _apply_pallas_impl(mp_on, vec_scale))
                return fn(w, master, gdir, scale)
        if use_pallas:
            _pstats.note_fallback(name, reason)
        fn = _jitted(("lamb2x", mp_on), lambda: _apply_xla_impl(mp_on))
        return fn(w, master, gdir, scale)
    return wrapper


# ---------------------------------------------------------------------------
# per-parameter registry path: tpu_impl overrides dispatched by
# `optimizer._run_op` through `registry.best_fn` on accelerator contexts.
# f32-only — the base ops run their math in the weight's native dtype,
# the kernels in f32, so anything else falls back (counted) for parity.
# ---------------------------------------------------------------------------
def _pp_gate(*arrays):
    for a in arrays:
        if a.dtype != jnp.float32:
            return "dtype"
        if int(_np.prod(a.shape, dtype=_np.int64)) == 0:
            return "empty"
    return None


def _clip_on(clip_gradient):
    return clip_gradient is not None and clip_gradient >= 0


@_reg.get("sgd_update").tpu_impl
def _sgd_update_tpu(weight, grad, lr, wd=0.0, rescale_grad=1.0,
                    clip_gradient=-1.0, lazy_update=True):
    reason = _pp_gate(weight, grad)
    if reason:
        _pstats.note_fallback("sgd", reason)
        return _opt_ops.sgd_update(weight, grad, lr, wd=wd,
                                   rescale_grad=rescale_grad,
                                   clip_gradient=clip_gradient)
    clip_on = _clip_on(clip_gradient)
    _pstats.note_dispatch("sgd")
    with _pstats.kernel_span("sgd"):
        fn = _jitted(("sgd", False, clip_on, False, False),
                     lambda: _flat_sgd_impl(False, clip_on, False, False))
        w_new, _, _ = fn(weight.reshape(-1), grad.reshape(-1), None, None,
                         lr, wd, 0.0, rescale_grad,
                         clip_gradient if clip_on else 0.0)
    return w_new.reshape(weight.shape)


@_reg.get("sgd_mom_update").tpu_impl
def _sgd_mom_update_tpu(weight, grad, mom, lr, momentum=0.0, wd=0.0,
                        rescale_grad=1.0, clip_gradient=-1.0,
                        lazy_update=True):
    reason = _pp_gate(weight, grad, mom)
    if reason:
        _pstats.note_fallback("sgd_mom", reason)
        return _opt_ops.sgd_mom_update(weight, grad, mom, lr,
                                       momentum=momentum, wd=wd,
                                       rescale_grad=rescale_grad,
                                       clip_gradient=clip_gradient)
    clip_on = _clip_on(clip_gradient)
    _pstats.note_dispatch("sgd_mom")
    with _pstats.kernel_span("sgd_mom"):
        fn = _jitted(("sgd", True, clip_on, False, False),
                     lambda: _flat_sgd_impl(True, clip_on, False, False))
        w_new, m_new, _ = fn(weight.reshape(-1), grad.reshape(-1),
                             mom.reshape(-1), None, lr, wd, momentum,
                             rescale_grad,
                             clip_gradient if clip_on else 0.0)
    return w_new.reshape(weight.shape), m_new.reshape(mom.shape)


@_reg.get("adam_update").tpu_impl
def _adam_update_tpu(weight, grad, mean, var, lr, beta1=0.9, beta2=0.999,
                     epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                     clip_gradient=-1.0, lazy_update=True):
    reason = _pp_gate(weight, grad, mean, var)
    if reason:
        _pstats.note_fallback("adam", reason)
        return _opt_ops.adam_update(weight, grad, mean, var, lr,
                                    beta1=beta1, beta2=beta2,
                                    epsilon=epsilon, wd=wd,
                                    rescale_grad=rescale_grad,
                                    clip_gradient=clip_gradient)
    clip_on = _clip_on(clip_gradient)
    _pstats.note_dispatch("adam")
    with _pstats.kernel_span("adam"):
        fn = _jitted(("adam", clip_on, False, False),
                     lambda: _flat_adam_impl(clip_on, False, False))
        w_new, m_new, v_new, _ = fn(
            weight.reshape(-1), grad.reshape(-1), mean.reshape(-1),
            var.reshape(-1), None, lr, wd, beta1, 1.0 - beta1, beta2,
            1.0 - beta2, epsilon, rescale_grad,
            clip_gradient if clip_on else 0.0)
    return (w_new.reshape(weight.shape), m_new.reshape(mean.shape),
            v_new.reshape(var.shape))


@_reg.get("lamb_update_phase1").tpu_impl
def _lamb_phase1_tpu(weight, grad, mean, var, beta1=0.9, beta2=0.999,
                     epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                     rescale_grad=1.0, clip_gradient=-1.0):
    reason = _pp_gate(weight, grad, mean, var)
    if reason:
        _pstats.note_fallback("lamb1", reason)
        return _opt_ops.lamb_update_phase1(
            weight, grad, mean, var, beta1=beta1, beta2=beta2,
            epsilon=epsilon, t=t, bias_correction=bias_correction, wd=wd,
            rescale_grad=rescale_grad, clip_gradient=clip_gradient)
    clip_on = _clip_on(clip_gradient)
    _pstats.note_dispatch("lamb1")
    with _pstats.kernel_span("lamb1"):
        def build():
            def impl(w, g, mean_, var_, b1, omb1, b2, omb2, d1, d2, eps,
                     rescale, clip, wd_):
                n = w.shape[0]
                rows, tr, grid = _flat_geometry(n)
                tiles = [_pad2d(w, rows), _pad2d(g, rows),
                         _pad2d(mean_, rows), _pad2d(var_, rows)]
                scal = _scal_pack(b1, omb1, b2, omb2, d1, d2, eps, rescale,
                                  clip, wd_)
                kern = functools.partial(
                    _kernel_flat_lamb1, clip_on=clip_on, mp_on=False,
                    bias_corr=bool(bias_correction), vec_wd=False,
                    with_norms=False, n_keys=0, keys_padded=0)
                gd, m_new, v_new = _launch(
                    kern, tiles, scal,
                    [jnp.float32, mean_.dtype, var_.dtype], tr, grid, rows)
                return (_unpad(gd, n), _unpad(m_new, n), _unpad(v_new, n))
            return impl
        fn = _jitted(("pp_lamb1", clip_on, bool(bias_correction)), build)
        # bias-corr complements in python double, exactly like the base op
        gd, m_new, v_new = fn(
            weight.reshape(-1), grad.reshape(-1), mean.reshape(-1),
            var.reshape(-1), beta1, 1.0 - beta1, beta2, 1.0 - beta2,
            1.0 - beta1 ** t, 1.0 - beta2 ** t, epsilon, rescale_grad,
            clip_gradient if clip_on else 0.0, wd)
    return (gd.reshape(weight.shape), m_new.reshape(mean.shape),
            v_new.reshape(var.shape))


@_reg.get("lamb_update_phase2").tpu_impl
def _lamb_phase2_tpu(weight, g, r1, r2, lr, lower_bound=-1.0,
                     upper_bound=-1.0):
    reason = _pp_gate(weight, g)
    if reason:
        _pstats.note_fallback("lamb2", reason)
        return _opt_ops.lamb_update_phase2(weight, g, r1, r2, lr,
                                           lower_bound=lower_bound,
                                           upper_bound=upper_bound)
    if lower_bound is not None and lower_bound > 0:
        r1 = jnp.maximum(r1, lower_bound)
    if upper_bound is not None and upper_bound > 0:
        r1 = jnp.minimum(r1, upper_bound)
    ratio = jnp.where(jnp.logical_and(r1 > 0, r2 > 0), r1 / r2,
                      jnp.ones_like(r1))
    _pstats.note_dispatch("lamb2")
    with _pstats.kernel_span("lamb2"):
        fn = _jitted(("pp_lamb2",),
                     lambda: _apply_pallas_impl(False, False))
        w_new, _ = fn(weight.reshape(-1), None, g.reshape(-1), lr * ratio)
    return w_new.reshape(weight.shape)

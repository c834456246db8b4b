"""One-dispatch Gluon training step: forward + loss + backward + optimizer
compiled into a single XLA program.

The reference gets per-step speed from three separate subsystems: CachedOp
for the forward graph (src/imperative/cached_op.cc), the NNVM Gradient pass
replay for backward, and engine-overlapped KVStore push/pull + per-param
optimizer ops (SURVEY.md §3.2). Even with all of them, every stage is its
own dispatch. The TPU-native answer fuses the entire step — the same move
`parallel.ShardedTrainStep` makes for the functional API, here surfaced for
the *Gluon* API so `model_zoo` + `Trainer` users get the fused path without
leaving Gluon:

    net = vision.resnet50_v1(classes=1000)
    net.initialize(ctx=mx.tpu())
    trainer = gluon.Trainer(net.collect_params(), 'sgd',
                            {'learning_rate': 0.1, 'momentum': 0.9})
    step = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                trainer)
    for data, label in batches:
        loss = step(data, label)        # ONE jitted call, params updated

Semantics parity with `loss.backward(); trainer.step(batch_size)`:
  * the backward cotangent is ones over the per-sample loss vector (sum), and
    `rescale_grad = scale / batch_size` — identical gradient scaling;
  * optimizer math runs through the SAME registered optimizer ops
    (ops/optimizer_ops.py) the imperative Updater calls, with lr/wd computed
    host-side per step by the optimizer's own scheduler logic (exact
    `_update_count`/`lr_scheduler` semantics) and fed as device scalars so
    one compilation serves every step. One deliberate dtype nuance: the
    scalars arrive as f32 device values (the imperative path feeds weakly
    typed python floats), so a bf16 parameter's update computes in f32 and
    rounds once at write-back — bit-identical for f32 params (the parity
    tests), and at-least-imperative precision for bf16;
  * BatchNorm moving stats update via the CachedOp aux-collector mechanism
    and are written back each step;
  * dropout draws from the per-step RNG key (mx.random.seed reproducible).

Weight/optimizer-state buffers are donated to XLA, so the step is in-place
at the HBM level — the buffer-swap NDArray mutation model at full speed.
"""
from __future__ import annotations

import math

import numpy as _np

import jax
import jax.numpy as jnp

from .. import autograd
from .. import engine as _engine
from .. import ndarray as nd
from .. import telemetry as _telem
from ..context import current_context
from .block import (_AUX_COLLECTOR, _TRACE_STATE, _flatten, _regroup,
                    _retrace_reason)

__all__ = ["FusedTrainStep"]

# the compiled step's HLO module: jit names it after `run` (`make_program`)
STEP_MODULE = "jit_run"


# ---------------------------------------------------------------------------
# per-optimizer split: host-side scalar schedule vs traced device update.
# Each entry: (host_fn(opt, indices) -> dict of (n,) f32 np arrays — the
#              per-step scalars; always at least {"lrs","wds"}, plus extras
#              such as "ts" for update-count-dependent math,
#              device_fn(opt, w, g, state, sc, rescale) -> (new_w, new_state)
#              with sc a dict of 0-d traced scalars, one per host key).
# The device fns call the registered optimizer ops so numerics are identical
# to the imperative Updater path (reference: src/operator/optimizer_op.cc).
# Scalars that depend on the update count t (Adam bias correction, FTML/
# Nadam/LAMB schedules) are either folded into lr host-side or passed as
# traced scalars — never baked into the compiled program as constants, so
# one compilation serves every step.
# ---------------------------------------------------------------------------

def _count_and_lrs(opt, indices):
    for i in indices:
        opt._update_count(i)
    return (_np.asarray(opt._get_lrs(indices), _np.float32),
            _np.asarray(opt._get_wds(indices), _np.float32))


def _sgd_host(opt, indices):
    lrs, wds = _count_and_lrs(opt, indices)
    return {"lrs": lrs, "wds": wds}


def _bias_corrected_host(opt, indices):
    """Adam-family: fold 1/(1-b1^t), sqrt(1-b2^t) into lr host-side, exactly
    as Optimizer.update does (reference: python Adam folds correction into
    lr before calling the op)."""
    lrs, wds = _count_and_lrs(opt, indices)
    for j, i in enumerate(indices):
        t = opt._index_update_count[i]
        lrs[j] *= math.sqrt(1.0 - opt.beta2 ** t) / (1.0 - opt.beta1 ** t)
    return {"lrs": lrs, "wds": wds}


def _adamax_host(opt, indices):
    """Adamax folds only the first-moment correction (Adamax.update)."""
    lrs, wds = _count_and_lrs(opt, indices)
    for j, i in enumerate(indices):
        t = opt._index_update_count[i]
        lrs[j] /= (1.0 - opt.beta1 ** t)
    return {"lrs": lrs, "wds": wds}


def _t_host(opt, indices):
    """FTML/LAMB: update count enters the op math — pass t per param."""
    lrs, wds = _count_and_lrs(opt, indices)
    ts = _np.asarray([opt._index_update_count[i] for i in indices],
                     _np.float32)
    return {"lrs": lrs, "wds": wds, "ts": ts}


def _nadam_host(opt, indices):
    """Nadam: t AND the running m_schedule product, advanced per index in
    update order — exactly Nadam.update's host bookkeeping."""
    lrs, wds = _count_and_lrs(opt, indices)
    ts, mscheds = [], []
    for i in indices:
        t = opt._index_update_count[i]
        ts.append(t)
        mscheds.append(opt.m_schedule)
        momentum_t = opt.beta1 * (
            1.0 - 0.5 * 0.96 ** (t * opt.schedule_decay))
        opt.m_schedule = opt.m_schedule * momentum_t
    return {"lrs": lrs, "wds": wds,
            "ts": _np.asarray(ts, _np.float32),
            "mscheds": _np.asarray(mscheds, _np.float32)}


def _lars_host(opt, indices):
    """LARS skips rate scaling for gamma/beta/bias params by NAME — a static
    property, shipped as a 0/1 mask so the device fn stays name-free."""
    lrs, wds = _count_and_lrs(opt, indices)
    mask = _np.asarray(
        [0.0 if opt.idx2name.get(i, str(i)).endswith(
            ("gamma", "beta", "bias")) else 1.0 for i in indices],
        _np.float32)
    return {"lrs": lrs, "wds": wds, "lars_masks": mask}


def _clipv(opt):
    from ..optimizer.optimizer import _clip
    return _clip(opt.clip_gradient)


def _get_op(name):
    from ..ops.registry import get
    return get(name)


def _sgd_device(opt, w, g, state, sc, rescale):
    kw = dict(lr=sc["lrs"], wd=sc["wds"], rescale_grad=rescale,
              clip_gradient=_clipv(opt))
    if state is None:
        return _get_op("sgd_update").fn(w, g, **kw), None
    new_w, new_m = _get_op("sgd_mom_update").fn(
        w, g, state, momentum=opt.momentum, **kw)
    return new_w, new_m


def _nag_device(opt, w, g, state, sc, rescale):
    kw = dict(lr=sc["lrs"], wd=sc["wds"], rescale_grad=rescale,
              clip_gradient=_clipv(opt))
    if state is None:
        return _get_op("sgd_update").fn(w, g, **kw), None
    new_w, new_m = _get_op("nag_mom_update").fn(
        w, g, state, momentum=opt.momentum, **kw)
    return new_w, new_m


def _adam_device(opt, w, g, state, sc, rescale):
    mean, var = state
    new_w, new_m, new_v = _get_op("adam_update").fn(
        w, g, mean, var, lr=sc["lrs"], wd=sc["wds"], beta1=opt.beta1,
        beta2=opt.beta2, epsilon=opt.epsilon, rescale_grad=rescale,
        clip_gradient=_clipv(opt))
    return new_w, (new_m, new_v)


def _adamw_device(opt, w, g, state, sc, rescale):
    mean, var = state
    new_w, new_m, new_v = _get_op("adamw_update").fn(
        w, g, mean, var, lr=sc["lrs"], wd=sc["wds"], beta1=opt.beta1,
        beta2=opt.beta2, epsilon=opt.epsilon, eta=opt.eta,
        rescale_grad=rescale, clip_gradient=_clipv(opt))
    return new_w, (new_m, new_v)


def _signum_device(opt, w, g, state, sc, rescale):
    kw = dict(lr=sc["lrs"], wd=sc["wds"], rescale_grad=rescale,
              clip_gradient=_clipv(opt))
    if state is None:
        return _get_op("signsgd_update").fn(w, g, **kw), None
    new_w, new_m = _get_op("signum_update").fn(
        w, g, state, momentum=opt.momentum, wd_lh=opt.wd_lh, **kw)
    return new_w, new_m


def _ftml_device(opt, w, g, state, sc, rescale):
    d, v, z = state
    new_w, new_d, new_v, new_z = _get_op("ftml_update").fn(
        w, g, d, v, z, lr=sc["lrs"], wd=sc["wds"], beta1=opt.beta1,
        beta2=opt.beta2, epsilon=opt.epsilon, rescale_grad=rescale,
        clip_grad=_clipv(opt), t=sc["ts"])
    return new_w, (new_d, new_v, new_z)


def _adagrad_device(opt, w, g, state, sc, rescale):
    new_w, new_h = _get_op("adagrad_update").fn(
        w, g, state, lr=sc["lrs"], wd=sc["wds"],
        epsilon=opt.float_stable_eps, rescale_grad=rescale,
        clip_gradient=_clipv(opt))
    return new_w, new_h


def _adadelta_device(opt, w, g, state, sc, rescale):
    acc_g, acc_delta = state
    new_w, new_g, new_d = _get_op("adadelta_update").fn(
        w, g, acc_g, acc_delta, rho=opt.rho, epsilon=opt.epsilon,
        wd=sc["wds"], rescale_grad=rescale, clip_gradient=_clipv(opt))
    return new_w, (new_g, new_d)


def _adamax_device(opt, w, g, state, sc, rescale):
    mean, u = state
    new_w, new_m, new_u = _get_op("adamax_update").fn(
        w, g, mean, u, lr=sc["lrs"], wd=sc["wds"], beta1=opt.beta1,
        beta2=opt.beta2, rescale_grad=rescale, clip_gradient=_clipv(opt))
    return new_w, (new_m, new_u)


def _nadam_device(opt, w, g, state, sc, rescale):
    mean, var = state
    new_w, new_m, new_v = _get_op("nadam_update").fn(
        w, g, mean, var, lr=sc["lrs"], wd=sc["wds"], beta1=opt.beta1,
        beta2=opt.beta2, epsilon=opt.epsilon,
        schedule_decay=opt.schedule_decay, rescale_grad=rescale,
        clip_gradient=_clipv(opt), t=sc["ts"], m_schedule=sc["mscheds"])
    return new_w, (new_m, new_v)


def _rmsprop_device(opt, w, g, state, sc, rescale):
    from ..optimizer.optimizer import _clip
    kw = dict(lr=sc["lrs"], wd=sc["wds"], gamma1=opt.gamma1,
              epsilon=opt.epsilon, rescale_grad=rescale,
              clip_gradient=_clipv(opt), clip_weights=_clip(opt.clip_weights))
    if not opt.centered:
        new_w, new_n = _get_op("rmsprop_update").fn(w, g, state, **kw)
        return new_w, new_n
    n, gbar, delta = state
    new_w, new_n, new_g, new_d = _get_op("rmspropalex_update").fn(
        w, g, n, gbar, delta, gamma2=opt.gamma2, **kw)
    return new_w, (new_n, new_g, new_d)


def _ftrl_device(opt, w, g, state, sc, rescale):
    z, n = state
    new_w, new_z, new_n = _get_op("ftrl_update").fn(
        w, g, z, n, lr=sc["lrs"], wd=sc["wds"], lamda1=opt.lamda1,
        beta=opt.beta, rescale_grad=rescale, clip_gradient=_clipv(opt))
    return new_w, (new_z, new_n)


def _lamb_device(opt, w, g, state, sc, rescale):
    from ..optimizer.optimizer import _clip
    mean, var = state
    g_dir, new_m, new_v = _get_op("lamb_update_phase1").fn(
        w, g, mean, var, beta1=opt.beta1, beta2=opt.beta2,
        epsilon=opt.epsilon, t=sc["ts"],
        bias_correction=opt.bias_correction, wd=sc["wds"],
        rescale_grad=rescale, clip_gradient=_clipv(opt))
    r1 = jnp.linalg.norm(w)
    r2 = jnp.linalg.norm(g_dir)
    new_w = _get_op("lamb_update_phase2").fn(
        w, g_dir, r1, r2, lr=sc["lrs"],
        lower_bound=_clip(opt.lower_bound),
        upper_bound=_clip(opt.upper_bound))
    return new_w, (new_m, new_v)


def _lars_device(opt, w, g, state, sc, rescale):
    """LARS.update: layer rate = eta*||w||/(||g||+wd*||w||+eps) on the RAW
    grad, skipped (mask=0) for gamma/beta/bias, then the plain SGD ops."""
    lr, wd = sc["lrs"], sc["wds"]
    w_norm = jnp.linalg.norm(w.astype(jnp.float32))
    g_norm = jnp.linalg.norm(g.astype(jnp.float32))
    lars = jnp.where((w_norm > 0.0) & (g_norm > 0.0),
                     opt.eta * w_norm / (g_norm + wd * w_norm + opt.eps),
                     1.0)
    lr = jnp.where(sc["lars_masks"] > 0.0, lars * lr, lr)
    kw = dict(lr=lr, wd=wd, rescale_grad=rescale,
              clip_gradient=_clipv(opt))
    if state is None:
        return _get_op("sgd_update").fn(w, g, **kw), None
    new_w, new_m = _get_op("sgd_mom_update").fn(
        w, g, state, momentum=opt.momentum, **kw)
    return new_w, new_m


def _dcasgd_device(opt, w, g, state, sc, rescale):
    """DCASGD.update's inline math (delay-compensated step), traced."""
    lr, wd = sc["lrs"], sc["wds"]
    graw = g.astype(jnp.float32) * rescale
    if opt.clip_gradient is not None:
        graw = jnp.clip(graw, -opt.clip_gradient, opt.clip_gradient)
    mom, prev_w = state
    w32 = w.astype(jnp.float32)
    pw = prev_w.astype(jnp.float32)
    step = -lr * (graw + wd * w32 + opt.lamda * graw * graw * (w32 - pw))
    if mom is not None:
        m = opt.momentum * mom.astype(jnp.float32) + step
        new_mom, step = m, m
    else:
        new_mom = None
    return (w32 + step).astype(w.dtype), (new_mom, w)


_FUSABLE = {
    "sgd": (_sgd_host, _sgd_device),
    "nag": (_sgd_host, _nag_device),
    "adam": (_bias_corrected_host, _adam_device),
    "adamw": (_bias_corrected_host, _adamw_device),
    "signum": (_sgd_host, _signum_device),
    "signsgd": (_sgd_host, _signum_device),
    "ftml": (_t_host, _ftml_device),
    "adagrad": (_sgd_host, _adagrad_device),
    "adadelta": (_sgd_host, _adadelta_device),
    "adamax": (_adamax_host, _adamax_device),
    "nadam": (_nadam_host, _nadam_device),
    "rmsprop": (_sgd_host, _rmsprop_device),
    "ftrl": (_sgd_host, _ftrl_device),
    "lamb": (_t_host, _lamb_device),
    "lars": (_lars_host, _lars_device),
    "dcasgd": (_sgd_host, _dcasgd_device),
}
# SGLD stays imperative-only: its Langevin noise draws from the global RNG
# stream per update call; a fused replay could not keep that stream's
# imperative-path reproducibility contract.


def _state_raws(state):
    """NDArray-pytree (None | NDArray | tuple) -> raw jax arrays."""
    if state is None:
        return None
    if isinstance(state, (tuple, list)):
        return tuple(_state_raws(s) for s in state)
    return state._read()


def _state_cast_like(new, ref):
    """Cast an updated state pytree to the carried state's dtypes INSIDE the
    traced program, so the host-side write-back never dispatches eager cast
    ops (bf16 momentum + f32 scalar lr promotes to f32 otherwise; at one tiny
    eager op per parameter per step those casts dominate wrapper overhead on
    a busy device)."""
    if new is None:
        return None
    if isinstance(new, (tuple, list)):
        return tuple(_state_cast_like(n, r) for n, r in zip(new, ref))
    return new.astype(new.dtype) if ref is None else new.astype(ref.dtype)


def _state_write(state, raws):
    if state is None:
        return
    if isinstance(state, (tuple, list)):
        for s, r in zip(state, raws):
            _state_write(s, r)
        return
    state._write(raws.astype(state._read().dtype))


class FusedTrainStep:
    """Compile net forward + loss + backward + optimizer into one jit.

    net: a (Hybrid)Block. loss: a gluon Loss block or callable
    (pred_nd, label_nd) -> per-sample loss NDArray. trainer: gluon.Trainer
    holding the net's params (its optimizer and schedulers drive the update;
    num_update/lr_mult/wd_mult semantics are exact).

    Restrictions (fall back to the imperative `Trainer.step` path outside
    them): single context, dense params, optimizer in %s.
    """ % sorted(_FUSABLE)

    def __init__(self, net, loss, trainer, donate=True, mesh=None,
                 rules=None, batch_spec=None, bucket_mb=None):
        """mesh: a jax.sharding.Mesh makes the fused step SPMD — params and
        optimizer state are sharded by `rules` (a parallel.ShardingRules;
        default replicated = pure data parallel), the batch is sharded over
        the mesh's 'data'/'fsdp' axes (or `batch_spec`), and XLA inserts the
        gradient allreduce (reference: multi-device Trainer + KVStore
        'device', SURVEY.md §2.3 row 1 — here the whole DP step is one
        GSPMD program over ICI instead of engine-overlapped push/pull).

        bucket_mb: route the traced gradients through `mx.engine`'s
        bucketed regrouping (`engine.reassociate_bucketed`) so the emitted
        program carries one fused flat tensor per size-capped bucket and
        GSPMD's cross-replica grad reductions combine bucket-wise.
        Numerically the identity (bit-exact); None disables, 0 is the
        explicit per-leaf escape hatch."""
        self._net = net
        self._loss = loss
        self._trainer = trainer
        self._donate = donate
        self._mesh = mesh
        self._rules = rules
        self._batch_spec = batch_spec
        self._bucket_mb = bucket_mb
        self._sig_seen = set()   # call signatures, for the retrace guard
        self._sig_last = None
        self._built = False

    def rebuild_for_mesh(self, mesh):
        """A fresh, unbuilt FusedTrainStep over the same net/loss/trainer
        targeting `mesh` — the elastic-recovery rebuild after the device
        set changed. Its `_build` re-reads the (restored) params off the
        net and re-places them per the step's ShardingRules; the caller
        (`ResilientRunner.for_fused_step`) carries the optimizer states
        across."""
        return FusedTrainStep(
            self._net, self._loss, self._trainer, donate=self._donate,
            mesh=mesh, rules=self._rules, batch_spec=self._batch_spec,
            bucket_mb=self._bucket_mb)

    # ------------------------------------------------------------------
    def _build(self, ctx, data, label):
        trainer = self._trainer
        opt = trainer._optimizer
        kind = type(opt).__name__.lower()
        if kind not in _FUSABLE:
            raise NotImplementedError(
                "FusedTrainStep supports optimizers %s; %r updates must use "
                "the imperative Trainer.step path" % (sorted(_FUSABLE), kind))
        self._host_fn, self._dev_fn = _FUSABLE[kind]
        if getattr(opt, "multi_precision", False):
            raise NotImplementedError(
                "FusedTrainStep: multi_precision state layout not wired; "
                "bf16 training needs no master copy — use dtype=bfloat16")
        if len(trainer._contexts) != 1:
            raise NotImplementedError(
                "FusedTrainStep is single-context; use kvstore/Trainer.step "
                "or parallel.ShardedTrainStep for multi-device")
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if trainer._params_to_init:
            trainer._init_params()
        if trainer._kvstore is not None and trainer._update_on_kvstore:
            raise NotImplementedError(
                "FusedTrainStep requires update_on_kvstore=False "
                "(the fused program IS the update)")

        # deferred-shape params: finish init with one eager pre-pass (the
        # same move HybridBlock.forward makes before building its CachedOp).
        # predict mode: shape inference must not touch BatchNorm moving
        # stats or consume RNG keys — step parity with the imperative path
        # starts from identical state.
        if any(p._data is None
               for p in self._net.collect_params().values()):
            args = data if isinstance(data, (list, tuple)) else [data]
            prev = getattr(_TRACE_STATE, "ctx", None)
            _TRACE_STATE.ctx = ctx   # suppress nested CachedOp compiles
            try:
                with autograd.pause(train_mode=False):
                    if hasattr(self._net, "_forward_unhybridized"):
                        self._net._forward_unhybridized(*args)
                    else:
                        self._net(*args)
            finally:
                _TRACE_STATE.ctx = prev

        # params: trainable (differentiated + updated) vs aux (inputs only;
        # BatchNorm stats update through the aux collector)
        all_params = list(self._net.collect_params().values())
        for p in all_params:
            if p._stype != "default":
                raise NotImplementedError(
                    "FusedTrainStep does not cover sparse parameters")
        self._train_params = [p for p in trainer._params
                              if p.grad_req != "null"]
        train_set = set(id(p) for p in self._train_params)
        self._other_params = [p for p in all_params
                              if id(p) not in train_set]
        self._train_idx = [trainer._param2idx[p.name]
                           for p in self._train_params]

        if self._mesh is None:
            # jit keys its executable on which arguments are committed to a
            # device, and the step's outputs always are. Parameters of a cpu
            # context are initialised in place and never device_put, so they
            # are uncommitted and the second call would compile the whole
            # program again (an accelerator context commits them when it
            # copies them over). Before the states are made, so that a state
            # that aliases its weight still shares the array.
            for p in all_params:
                nd_arr = p.data(ctx)
                nd_arr._write(jax.device_put(nd_arr._read(),
                                             ctx.jax_device))

        # optimizer state, created by the optimizer itself (same shapes and
        # dtypes as the imperative Updater would make)
        self._states = [
            opt.create_state_multi_precision(i, p.data(ctx))
            for i, p in zip(self._train_idx, self._train_params)]

        net, loss_blk = self._net, self._loss
        train_nds = [p.data(ctx) for p in self._train_params]
        other_nds = [p.data(ctx) for p in self._other_params]
        self._train_nds, self._other_nds = train_nds, other_nds
        dev_fn = self._dev_fn

        # mesh mode: place params + optimizer state on the mesh per the
        # sharding rules; jit then partitions the step program around the
        # argument shardings (GSPMD), inserting the gradient allreduce
        self._data_sharding = None
        self._label_sharding = None
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as _P
            from ..parallel.sharding import ShardingRules
            mesh = self._mesh
            rules = self._rules or ShardingRules([])

            def place(nd_arr, name):
                spec = rules.spec_for(name, nd_arr.shape, mesh)
                raw = jax.device_put(nd_arr._read(),
                                     NamedSharding(mesh, spec))
                nd_arr._write(raw)
                return NamedSharding(mesh, spec)

            def place_state(state, shd):
                if state is None:
                    return
                if isinstance(state, (tuple, list)):
                    for s in state:
                        place_state(s, shd)
                    return
                state._write(jax.device_put(state._read(), shd))

            for i, (p, nd_arr) in enumerate(zip(self._train_params,
                                                train_nds)):
                shd = place(nd_arr, p.name)
                place_state(self._states[i], shd)
            for p, nd_arr in zip(self._other_params, other_nds):
                place(nd_arr, p.name)

            if self._batch_spec is not None:
                bspec = self._batch_spec
            else:
                sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
                axes = tuple(a for a in ("data", "fsdp")
                             if sizes.get(a, 1) > 1)
                bspec = _P(axes if axes else None)
            self._data_sharding = NamedSharding(mesh, bspec)
            # labels are rank-1: shard on the batch dim only, whatever the
            # rank of the user-supplied data spec
            self._label_sharding = NamedSharding(
                mesh, _P(bspec[0] if len(bspec) else None))

        # the sentinel flag is baked into the traced program (an extra
        # output changes the signature), so it is read ONCE at build time:
        # enable MXNET_TPU_INTEGRITY before the first step (or rebuild)
        from ..resilience import integrity as _integrity
        sentinel = _integrity.enabled()
        self._sentinel = sentinel

        def make_program(in_fmt):
            # one (jitted, holder) pair per input nesting: the trace reads
            # in_fmt and records its own aux-target order, so neither may be
            # shared across traces (round-2 verdict Weak #10)
            holder = {"in_fmt": in_fmt}

            def run(train_raws, other_raws, state_raws, scal, rescale,
                    data_raws, label_raw, rng_key):
                def loss_fn(train_raws_):
                    from .. import random as _random
                    param_nds = train_nds + other_nds
                    saved = [(p._data, p._base, p._idx) for p in param_nds]
                    aux_updates = []
                    if not hasattr(_AUX_COLLECTOR, "stack"):
                        _AUX_COLLECTOR.stack = []
                    _AUX_COLLECTOR.stack.append(aux_updates)
                    prev_trace = getattr(_TRACE_STATE, "ctx", None)
                    _TRACE_STATE.ctx = ctx
                    try:
                        for p, raw in zip(train_nds, train_raws_):
                            p._data, p._base, p._idx = raw, None, None
                        for p, raw in zip(other_nds, other_raws):
                            p._data, p._base, p._idx = raw, None, None
                        _random.push_trace_key(rng_key)
                        try:
                            # inside what is differentiated: the backward
                            # then reads transpose(jvp(forward)) in every
                            # op_name, and a kernel keeps its own name
                            with autograd.pause(train_mode=True), \
                                    jax.named_scope("forward"):
                                in_nds = [nd.from_jax(r, ctx=ctx)
                                          for r in data_raws]
                                args = _regroup(in_nds, holder["in_fmt"])[0]
                                if not isinstance(args, (list, tuple)):
                                    args = [args]
                                lab = nd.from_jax(label_raw, ctx=ctx)
                                out = net(*args)
                                lvec = loss_blk(out, lab)
                        finally:
                            _random.pop_trace_key()
                    finally:
                        _TRACE_STATE.ctx = prev_trace
                        _AUX_COLLECTOR.stack.pop()
                        for p, (d, b, i) in zip(param_nds, saved):
                            p._data, p._base, p._idx = d, b, i
                    lraw = lvec._read()
                    holder["aux_targets"] = [t for t, _ in aux_updates]
                    # backward(): cotangent of ones over the loss vector = sum
                    return jnp.sum(lraw), (jnp.mean(lraw),
                                           tuple(v for _, v in aux_updates))

                (unused_total, (loss_mean, aux_new)), grads = \
                    jax.value_and_grad(loss_fn, has_aux=True)(train_raws)
                if self._bucket_mb is not None:
                    # bucket-wise grad regrouping (identity math; one fused
                    # flat tensor per bucket in the lowered program).
                    # reassociate_bucketed's float()/`if raws` act on the
                    # static bucket_mb arg and the Python list length, not
                    # on the grad tracers — the all-params-tainted summary
                    # can't see that.
                    grads = tuple(_engine.reassociate_bucketed(  # tpu-lint: disable=TPU001,TPU003
                        list(grads), self._bucket_mb))
                new_train, new_states = [], []
                with jax.named_scope("optimizer"):
                    for j in range(len(train_raws)):
                        sc = {k: v[j] for k, v in scal.items()}
                        w, s = dev_fn(opt, train_raws[j], grads[j],
                                      state_raws[j], sc, rescale)
                        new_train.append(w.astype(train_raws[j].dtype))
                        new_states.append(_state_cast_like(s, state_raws[j]))
                if sentinel:
                    # integrity sentinel (MXNET_TPU_INTEGRITY=1 at build
                    # time): one fused all-finite scalar over the raw
                    # grads + loss, emitted as an extra program output —
                    # the whole-step analog of the bucket check. The host
                    # checks it BEFORE any write-back, so a tripped step
                    # leaves params/states untouched.
                    fin = jax.tree_util.tree_reduce(
                        lambda a, g: a & jnp.isfinite(g).all(), list(grads),
                        jnp.isfinite(loss_mean))
                    return (tuple(new_train), tuple(new_states), aux_new,
                            loss_mean, fin)
                return tuple(new_train), tuple(new_states), aux_new, loss_mean

            donate = (0, 2) if self._donate else ()
            return jax.jit(run, donate_argnums=donate), holder

        self._make_program = make_program
        self._programs = {}  # repr(in_fmt) -> (jitted, holder)
        self._aot_progs = {}  # repr(in_fmt) -> (executable, sig) AOT slot
        self._scal_cache = None  # (lrs_np, wds_np, rescale) -> device arrays
        self._built = True

    # ------------------------------------------------------------------
    def __call__(self, data, label):
        """Run one fused step; returns the mean loss as an NDArray."""
        with _telem.step_span("fused_step"):
            return self._step(data, label)

    def _step(self, data, label):
        # injection-only resilience site (hang/preempt/latency testable on
        # one chip); recovery belongs to resilience.run, which owns the
        # checkpoint needed to replay a half-applied step
        from ..resilience import faults as _faults
        _faults.check("train.step")
        flat_data, in_fmt = _flatten(data, "input")
        ctx = flat_data[0].context
        if not self._built:
            self._build(ctx, data, label)
        # retrace guard (ROADMAP follow-on): the inner jit retraces silently
        # on any input shape/dtype change — route every new signature after
        # the first through analysis.guard.on_retrace so the retrace-reason
        # log and MXNET_TPU_TRACE_GUARD_RETRACE_LIMIT cover the functional
        # path, not just CachedOp
        sig = (repr(in_fmt), tuple(
            (tuple(a.shape), str(a.dtype))
            for a in list(flat_data) + [label]))
        if sig not in self._sig_seen:
            prev_sig = self._sig_last
            self._sig_seen.add(sig)
            self._sig_last = sig
            if len(self._sig_seen) > 1:
                _telem.inc("fused_step.retrace")
                from ..analysis import guard as _guard
                if _guard.ACTIVE:
                    _guard.on_retrace(
                        "FusedTrainStep",
                        len(self._sig_seen),
                        _retrace_reason((True, sig[1]),
                                        (True, prev_sig[1])
                                        if prev_sig else None))
        # programs are keyed by input nesting: a call with equal shapes but a
        # different pytree structure must not reuse a stale trace
        prog = self._programs.get(repr(in_fmt))
        fresh_program = prog is None
        pallas_before = None
        if prog is None:
            prog = self._make_program(in_fmt)
            self._programs[repr(in_fmt)] = prog
            if _telem.ENABLED:
                # ISSUE 10 dispatch observability: Pallas call sites (the
                # flash attention kernels) count ops.pallas.dispatch while
                # the first call TRACES this program — the delta across the
                # trace is the number of kernels fused into the step
                pallas_before = _telem.counter("ops.pallas.dispatch").value
        jitted, holder = prog

        from .. import random as _random
        trainer = self._trainer
        opt = trainer._optimizer
        batch_size = flat_data[0].shape[0]
        opt.rescale_grad = trainer._scale / batch_size
        scal = self._host_fn(opt, self._train_idx)

        # the step scalars (lr/wd/rescale, plus t-schedule extras for some
        # optimizers) change rarely or predictably; re-upload to device only
        # when the host values change, else each step pays H2D transfers
        cache = self._scal_cache
        if (cache is None or cache["rescale"] != opt.rescale_grad
                or cache["np"].keys() != scal.keys()
                or any(not _np.array_equal(cache["np"][k], scal[k])
                       for k in scal)):
            cache = {"rescale": opt.rescale_grad, "np": scal,
                     "dev": {k: jnp.asarray(v) for k, v in scal.items()},
                     "rescale_dev": jnp.float32(opt.rescale_grad)}
            self._scal_cache = cache
        scal_dev, rescale_dev = cache["dev"], cache["rescale_dev"]

        with _telem.span("fused_step.gather", "phase"):
            train_raws = tuple(p._read() for p in self._train_nds)
            other_raws = tuple(p._read() for p in self._other_nds)
            state_raws = tuple(_state_raws(s) for s in self._states)
            if self._donate:
                # NDArray.copy() shares the immutable buffer (copy-on-write),
                # so a state that starts as weight.copy() (DCASGD's
                # prev_weight) aliases a donated weight buffer — XLA rejects
                # donating one buffer twice. Break the alias with a real
                # device copy.
                seen = {id(r) for r in train_raws}

                def _break_alias(x):
                    if x is None:
                        return None
                    if isinstance(x, (tuple, list)):
                        return tuple(_break_alias(e) for e in x)
                    if id(x) in seen:
                        return jnp.copy(x)
                    seen.add(id(x))
                    return x

                state_raws = _break_alias(state_raws)
            rng_key = _random.take_key(ctx)
            data_raws = tuple(a._read() for a in flat_data)
            label_raw = label._read()
        if self._data_sharding is not None:
            with _telem.span("fused_step.stage", "phase"):
                # stage the batch onto the mesh
                data_raws = tuple(jax.device_put(r, self._data_sharding)
                                  for r in data_raws)
                label_raw = jax.device_put(label_raw, self._label_sharding)

        step_args = (train_raws, other_raws, state_raws,
                     scal_dev, rescale_dev,
                     data_raws, label_raw, rng_key)
        if fresh_program:
            # first dispatch of this program: give the persistent AOT
            # cache a chance to skip the compile (the trace still runs
            # inside lower() — it fills the holder's output format and
            # aux targets, which are process-local and unserializable)
            self._maybe_aot(jitted, step_args, sig, repr(in_fmt))
        aot = self._aot_progs.get(repr(in_fmt))
        with _telem.span("fused_step.launch", "phase"):
            if aot is not None and aot[1] == sig:
                outs = aot[0](*step_args)
                builds = int(fresh_program)
            else:
                # counted from jit's own cache, so the counter says what XLA
                # built: one program object builds again when an argument's
                # shape, dtype or committed-ness changes
                built = jitted._cache_size()
                outs = jitted(*step_args)
                builds = jitted._cache_size() - built
        if builds:
            _telem.inc("fused_step.compile", builds)
            _telem.note_compile(
                "fused_step:%s" % getattr(self._net, "name", "net"))
        # under a profiler session the step's scope map is read when the
        # session ends (`telemetry.module_scopes()`); else nothing
        _telem.note_step_program(STEP_MODULE, rebuilt=bool(builds))
        if pallas_before is not None:
            # unconditionally: a recompile that fuses ZERO kernels (gate
            # turned off, shapes fell back) must not leave a stale count
            _telem.set_gauge(
                "fused_step.pallas_kernels",
                _telem.counter("ops.pallas.dispatch").value - pallas_before)
        with _telem.span("fused_step.write_back", "phase"):
            if getattr(self, "_sentinel", False):
                new_train, new_states, aux_new, loss_mean, fin = outs
                from ..resilience import integrity as _integrity
                # raises DivergenceError BEFORE any write-back: a tripped
                # step leaves params, states, and aux exactly as they were
                _integrity.check_scalar(
                    fin, site="fused_step",
                    keys=[p.name for p in getattr(self, "_train_params", [])
                          if hasattr(p, "name")])
            else:
                new_train, new_states, aux_new, loss_mean = outs
            with autograd.pause():
                for p_nd, raw in zip(self._train_nds, new_train):
                    p_nd._write(raw)
                for s, raws in zip(self._states, new_states):
                    _state_write(s, raws)
                for t, v in zip(holder.get("aux_targets", ()), aux_new):
                    t._write(v)
        return nd.from_jax(loss_mean, ctx=ctx)

    def _maybe_aot(self, jitted, step_args, sig, fmt_key):
        """Route this program's COMPILE through the persistent AOT cache
        (ISSUE 11): lower() runs the trace either way (the holder metadata
        needs it), the XLA compile is skipped on a warm cache. A program
        that does not serialize is counted and left on the plain jit path
        — never an error. The executable is pinned to its input signature;
        a later shape change dispatches through the retracing jit.

        Donating fused-step programs stay OFF the cache: a deserialized
        executable with this program's many-small-donated-buffers aliasing
        corrupts the heap on XLA:CPU (observed 2026-08-04 — repeatable
        free() abort + value divergence after ~2 restored-exec steps,
        while the same program compiled in-process is fine, and the
        sharded-step / serve donated programs restore cleanly). Pass
        donate=False to FusedTrainStep to opt a deployment into the
        cold-start win; the skip is counted."""
        from ..compiler.cache import (aot_cache, cache_key, hlo_hash,
                                      load_or_compile)
        if not aot_cache().enabled:
            return
        if self._donate:
            _telem.inc("compiler.cache.skipped_donated")
            return
        try:
            lowered = jitted.lower(*step_args)
            key = cache_key(kind="fused_train_step", hlo=hlo_hash(lowered))
            ex, restored = load_or_compile(key, lambda: lowered,
                                           "fused_step")
            if restored:
                _telem.inc("fused_step.aot_restored")
            self._aot_progs[fmt_key] = (ex, sig)
        except Exception:  # noqa: BLE001 — cache is best-effort by contract
            _telem.inc("compiler.cache.unusable")

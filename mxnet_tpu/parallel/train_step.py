"""Whole-train-step compilation under shardings.

The reference splits a training step across subsystems: GraphExecutor forward
/backward, KVStore push/pull for gradient aggregation, and per-param
optimizer ops (src/operator/optimizer_op.cc), relying on engine dependencies
to overlap comm with backward (SURVEY.md §3.4). The TPU-native design fuses
the whole step — forward, backward, gradient allreduce, optimizer update —
into ONE jitted SPMD program; XLA then schedules the gradient collectives to
overlap with the remaining backward, reproducing the reference's
push-overlaps-backward property without an engine.

Functional optimizers here mirror mxnet_tpu.optimizer registry semantics
(sgd/momentum, adam, adamw, lamb) but operate on pytrees so optimizer state
shards with the parameters (ZeRO: state inherits the param's sharding — the
'server-side optimizer' of the PS path, §5.8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import engine as _engine
from .. import telemetry as _telem
from .sharding import ShardingRules, shard_pytree

__all__ = ["ShardedTrainStep", "sgd_init", "adam_init"]

# the compiled step's HLO module: jit names it after `step_fn` (`_build`)
STEP_MODULE = "jit_step_fn"


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


# ---------------------------------------------------------------- optimizers
def sgd_init(params, momentum=0.0):
    if momentum:
        return {"mom": _tmap(jnp.zeros_like, params)}
    return {}


def _sgd_update(params, grads, state, lr, momentum=0.0, wd=0.0):
    if wd:
        grads = _tmap(lambda g, p: g + wd * p, grads, params)
    if momentum:
        mom = _tmap(lambda m, g: momentum * m + g, state["mom"], grads)
        new_p = _tmap(lambda p, m: p - lr * m, params, mom)
        return new_p, {"mom": mom}
    return _tmap(lambda p, g: p - lr * g, params, grads), state


def adam_init(params):
    return {"m": _tmap(jnp.zeros_like, params),
            "v": _tmap(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def _adam_update(params, grads, state, lr, beta1=0.9, beta2=0.999,
                 eps=1e-8, wd=0.0, adamw=False):
    t = state["t"] + 1
    if wd and not adamw:
        grads = _tmap(lambda g, p: g + wd * p, grads, params)
    m = _tmap(lambda m_, g: beta1 * m_ + (1 - beta1) * g, state["m"], grads)
    v = _tmap(lambda v_, g: beta2 * v_ + (1 - beta2) * g * g,
              state["v"], grads)
    tf = t.astype(jnp.float32)
    bc1 = 1 - beta1 ** tf
    bc2 = 1 - beta2 ** tf

    def upd(p, m_, v_):
        step = lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
        if adamw and wd:
            step = step + lr * wd * p
        return p - step

    new_p = _tmap(upd, params, m, v)
    return new_p, {"m": m, "v": v, "t": t}


_OPTS = {
    "sgd": (lambda p, **kw: sgd_init(p, kw.get("momentum", 0.0)), _sgd_update),
    "adam": (lambda p, **kw: adam_init(p), _adam_update),
    "adamw": (lambda p, **kw: adam_init(p),
              functools.partial(_adam_update, adamw=True)),
}


class ShardedTrainStep:
    """Compile loss_fn + optimizer into one sharded SPMD step.

    loss_fn(params, batch) -> scalar loss (batch is a pytree whose leading
    dim is the global batch; it will be sharded over the 'data'+'fsdp' axes).

    Usage::

        step = ShardedTrainStep(loss_fn, params, mesh, rules=LLAMA_RULES,
                                optimizer="adamw", lr=1e-3)
        params, opt_state = step.init()      # shards params onto the mesh
        for batch in data:
            params, opt_state, loss = step(params, opt_state, batch)
    """

    def __init__(self, loss_fn, params, mesh, rules=None, optimizer="adamw",
                 lr=1e-3, batch_spec=None, grad_accum=1, donate=True,
                 remat=False, bucket_mb=None, zero=False, **opt_kwargs):
        self.loss_fn = loss_fn
        self._init_params = params
        self.mesh = mesh
        self.rules = rules or ShardingRules([])
        if isinstance(optimizer, str):
            self._opt_init, self._opt_update = _OPTS[optimizer]
        else:
            self._opt_init, self._opt_update = optimizer
        self.lr = lr
        self.opt_kwargs = opt_kwargs
        self.grad_accum = grad_accum
        # bucket_mb: regroup traced grads through mx.engine's size-capped
        # buckets (identity math) so GSPMD emits bucketed cross-replica
        # reductions; None disables, 0 is the per-leaf escape hatch
        self.bucket_mb = bucket_mb
        # zero: ZeRO-1 for the functional path — optimizer-state leaves
        # shard their leading dim over the DATA axis on top of the
        # existing mesh rules, and GSPMD materializes the paper's
        # automatic weight-update sharding (grads arrive reduce-scattered
        # where state lives, the weight delta all-gathers back); params
        # and the forward stay exactly as the rules say
        self.zero = bool(zero)
        self._zero_axis = None
        if self.zero:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if sizes.get("data", 1) > 1:
                self._zero_axis = ("data", sizes["data"])
        self._sig_seen = set()   # batch signatures, for the retrace guard
        self._sig_last = None
        self._batch_spec_arg = batch_spec  # user-given (None = derive)
        data_axes = tuple(a for a in ("data", "fsdp")
                          if a in mesh.axis_names and
                          dict(zip(mesh.axis_names,
                                   mesh.devices.shape)).get(a, 1) > 1)
        self.batch_spec = batch_spec if batch_spec is not None else \
            P(data_axes if data_axes else None)
        self.donate = donate
        self._remat = remat
        self._compiled = None
        self._param_specs = None
        # AOT-cached executable (ISSUE 11): when MXNET_TPU_AOT_CACHE is
        # set, the first program is lowered once, keyed by its HLO hash,
        # and the *compile* is skipped on a cache hit. A later batch-
        # signature change routes through the plain jit (which retraces),
        # never the fixed-shape executable.
        self._aot = None
        self._aot_sig = None

    # ------------------------------------------------------------------
    def init(self):
        """Shard initial params onto the mesh and build optimizer state with
        matching sharding (ZeRO: state lives where its param lives)."""
        params = shard_pytree(self._init_params, self.rules, self.mesh)
        self._param_specs = self.rules.tree_specs(params, self.mesh)
        opt_state = self._opt_init(self._init_params, **self.opt_kwargs)
        opt_specs = self._state_specs(opt_state)
        opt_state = _tmap(
            lambda x, s: jax.device_put(
                x, NamedSharding(self.mesh, s)), opt_state, opt_specs)
        from ..telemetry import ledger as _ledger
        _ledger.account("params", _ledger.tree_nbytes(params))
        _ledger.account("optimizer", _ledger.tree_nbytes(opt_state))
        return params, opt_state

    def _state_specs(self, opt_state):
        """Optimizer-state specs: per-param slots inherit the param's spec;
        scalars replicate. With ``zero=True`` each state leaf additionally
        shards its leading dim over the data axis (when free and
        divisible) — ZeRO-1 composed onto the existing rules."""
        out = {}
        for key, val in opt_state.items():
            if isinstance(val, jnp.ndarray) and val.ndim == 0:
                out[key] = P()
            else:
                specs = self.rules.tree_specs(val, self.mesh)
                if self._zero_axis is not None:
                    specs = _tmap(
                        lambda leaf, s: self._zero_spec(
                            s, getattr(leaf, "shape", ())), val, specs)
                out[key] = specs
        return out

    def _zero_spec(self, spec, shape):
        """Compose the ZeRO data-axis shard onto a rules-derived spec:
        claim the leading dim when no axis holds it yet, the data axis is
        unused elsewhere in the spec, and the dim divides evenly; anything
        else keeps the rules' spec untouched (correctness first — GSPMD
        padding surprises are not worth a silent layout change)."""
        axis, size = self._zero_axis
        if not shape or shape[0] % size:
            return spec
        entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
        used = set()
        for e in entries:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a is not None:
                    used.add(a)
        if axis in used or entries[0] is not None:
            return spec
        return P(*((axis,) + entries[1:]))

    # ------------------------------------------------------------------
    # elastic re-layout (resilience: the device set changed under the run)
    # ------------------------------------------------------------------
    def place(self, params, opt_state, donate=True):
        """Re-lay existing (params, opt_state) trees onto THIS step's mesh:
        rules-derived NamedShardings + device_put — `init()` for state that
        already has values. The elastic-recovery primitive: a restored
        snapshot (host arrays) or a live tree from a partially-dead mesh
        lands sharded across the current device set (every leaf bounces
        through host — `sharding.reshard_pytree` — because device_put
        straight off vanished source devices raises).

        donate=True (default): each source device buffer is deleted the
        moment its host copy exists, so grow-back re-layout peaks at
        max(old, new) + one leaf of HBM instead of old + new. The inputs
        are consumed — callers keep only the returned trees (the
        `ResilientRunner` relayout adapters already do). Pass donate=False
        to keep the sources alive (e.g. an A/B comparison)."""
        from .sharding import donated_device_put, reshard_pytree
        params = reshard_pytree(params, self.rules, self.mesh,
                                donate=donate)
        self._param_specs = self.rules.tree_specs(params, self.mesh)
        opt_specs = self._state_specs(opt_state)
        # PartitionSpec is a pytree leaf, so one tree_map covers both the
        # scalar slots (spec = P()) and the per-param subtrees
        opt_state = _tmap(
            lambda x, s: donated_device_put(x, s, self.mesh, donate),
            opt_state, opt_specs)
        # re-layout is exactly when residency changes — re-account both
        # scopes so the ledger tracks the move, not the stale layout
        from ..telemetry import ledger as _ledger
        _ledger.account("params", _ledger.tree_nbytes(params))
        _ledger.account("optimizer", _ledger.tree_nbytes(opt_state))
        return params, opt_state

    def rebuild_for_mesh(self, mesh):
        """A fresh step (empty compile cache, re-derived batch spec)
        targeting `mesh`, with the same loss/rules/optimizer/knobs — the
        `ResilientRunner` elastic path rebuilds through this after a mesh
        shrink or grow-back, then re-lays state via `place`."""
        return ShardedTrainStep(
            self.loss_fn, self._init_params, mesh, rules=self.rules,
            optimizer=(self._opt_init, self._opt_update), lr=self.lr,
            batch_spec=self._batch_spec_arg, grad_accum=self.grad_accum,
            donate=self.donate, remat=self._remat, bucket_mb=self.bucket_mb,
            zero=self.zero, **self.opt_kwargs)

    # ------------------------------------------------------------------
    def _build(self, params, opt_state):
        mesh = self.mesh
        p_specs = self._param_specs or self.rules.tree_specs(params, mesh)
        o_specs = self._state_specs(opt_state)
        loss_fn = self.loss_fn
        if self._remat:
            loss_fn = jax.checkpoint(loss_fn)
        lr = self.lr
        opt_update = self._opt_update
        opt_kwargs = self.opt_kwargs
        accum = self.grad_accum
        bucket_mb = self.bucket_mb
        bucket_cap = (0 if bucket_mb is None
                      else _engine.bucket_bytes(bucket_mb))

        def step_fn(params, opt_state, batch, step_num):
            def forward(params, batch):
                # inside what is differentiated: the backward then reads
                # transpose(jvp(forward)) in every op_name, and a kernel
                # keeps its own name (`%flash_dq.1`, not `%jvp_flash_dq_`)
                with jax.named_scope("forward"):
                    return loss_fn(params, batch)

            value_and_grad = jax.value_and_grad(forward)
            if accum > 1:
                def micro(carry, mb):
                    l, g = value_and_grad(params, mb)
                    return (carry[0] + l, _tmap(jnp.add, carry[1], g)), None
                zero = _tmap(jnp.zeros_like, params)
                mbatch = _tmap(
                    lambda x: x.reshape((accum, x.shape[0] // accum) +
                                        x.shape[1:]), batch)
                (loss, grads), _ = jax.lax.scan(
                    micro, (jnp.zeros(()), zero), mbatch)
                loss = loss / accum
                grads = _tmap(lambda g: g / accum, grads)
            else:
                loss, grads = value_and_grad(params, batch)
            if bucket_cap:
                # bucket-wise grad regrouping (identity math): the lowered
                # program carries one fused flat tensor per bucket, so the
                # GSPMD-inserted cross-replica reductions combine bucket-wise
                leaves, tree = jax.tree_util.tree_flatten(grads)
                # reassociate_bucketed's float()/`if raws` act on the static
                # bucket_mb arg and the Python list length, not the leaf
                # tracers — the all-params-tainted summary can't see that
                leaves = _engine.reassociate_bucketed(leaves, bucket_mb)  # tpu-lint: disable=TPU001,TPU003
                grads = jax.tree_util.tree_unflatten(tree, leaves)
            cur_lr = lr(step_num) if callable(lr) else lr
            with jax.named_scope("optimizer"):
                new_params, new_state = opt_update(
                    params, grads, opt_state, cur_lr, **opt_kwargs)
            return new_params, new_state, loss

        in_shardings = (
            _tmap(lambda s: NamedSharding(mesh, s), p_specs),
            {k: (_tmap(lambda s: NamedSharding(mesh, s), v)
                 if not isinstance(v, P) else NamedSharding(mesh, v))
             for k, v in o_specs.items()},
            _tmap(lambda _: NamedSharding(mesh, self.batch_spec), self._batch_proto),
            NamedSharding(mesh, P()),
        )
        out_shardings = (in_shardings[0], in_shardings[1],
                         NamedSharding(mesh, P()))
        return jax.jit(step_fn, in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1) if self.donate else ())

    def __call__(self, params, opt_state, batch, step_num=0):
        with _telem.step_span("train_step"):
            return self._step(params, opt_state, batch, step_num)

    def _step(self, params, opt_state, batch, step_num):
        from ..resilience import faults as _faults
        _faults.check("train.step")  # injection-only; resilience.run recovers
        # retrace guard (ROADMAP follow-on): the compiled jit silently
        # retraces on any batch shape/dtype change — route new signatures
        # through analysis.guard.on_retrace so the retrace-reason log and
        # MXNET_TPU_TRACE_GUARD_RETRACE_LIMIT cover the functional path
        sig = tuple((tuple(x.shape), str(x.dtype))
                    for x in jax.tree_util.tree_leaves(batch))
        if sig not in self._sig_seen:
            prev = self._sig_last
            self._sig_seen.add(sig)
            self._sig_last = sig
            if prev is not None:
                _telem.inc("train_step.retrace")
                from ..analysis import guard as _guard
                if _guard.ACTIVE:
                    from ..gluon.block import _retrace_reason
                    _guard.on_retrace(
                        "ShardedTrainStep", len(self._sig_seen),
                        _retrace_reason((True, sig), (True, prev)))
        pallas_before = None
        fresh = self._compiled is None
        if fresh:
            self._batch_proto = batch
            self._compiled = self._build(params, opt_state)
            self._aot = self._maybe_aot(params, opt_state, batch, step_num,
                                        sig)
            if self._aot is not None:
                _telem.inc("train_step.compile")
            elif _telem.ENABLED:
                # ISSUE 10 dispatch observability: Pallas call sites count
                # ops.pallas.dispatch while the first call TRACES this
                # program — the delta is the number of kernels fused into
                # the sharded step (mirrors fused_step.pallas_kernels)
                pallas_before = _telem.counter("ops.pallas.dispatch").value
        with _telem.span("train_step.launch", "phase"):
            step_num = jnp.asarray(step_num, jnp.int32)
            if self._aot is not None and sig == self._aot_sig:
                out = self._aot(params, opt_state, batch, step_num)
                builds = 0
            else:
                # counted from jit's own cache, so the counter says what XLA
                # built: the program builds again when the batch's signature
                # changes, and when a parameter's or a state's dtype does
                built = self._compiled._cache_size()
                out = self._compiled(params, opt_state, batch, step_num)
                builds = self._compiled._cache_size() - built
        if builds:
            _telem.inc("train_step.compile", builds)
            _telem.note_compile("ShardedTrainStep")
        # under a profiler session the step's scope map is read when the
        # session ends (`telemetry.module_scopes()`); else nothing
        _telem.note_step_program(STEP_MODULE, rebuilt=fresh or bool(builds))
        if pallas_before is not None:
            # unconditional: a zero-kernel recompile must clear a stale
            # count from an earlier gated-on program
            _telem.set_gauge(
                "train_step.pallas_kernels",
                _telem.counter("ops.pallas.dispatch").value - pallas_before)
        return out

    def _maybe_aot(self, params, opt_state, batch, step_num, sig):
        """Lower the first program and route its COMPILE through the
        persistent AOT cache: a warm cache (restarted elastic worker, a
        fleet sibling) skips XLA and loads the serialized executable.
        Returns the executable, or None when the cache is off or the
        program does not serialize (counted, never raised)."""
        from ..compiler.cache import (aot_cache, cache_key, hlo_hash,
                                      load_or_compile)
        if not aot_cache().enabled:
            return None
        try:
            before = _telem.counter("ops.pallas.dispatch").value \
                if _telem.ENABLED else 0
            lowered = self._compiled.lower(params, opt_state, batch,
                                           jnp.asarray(step_num, jnp.int32))
            if _telem.ENABLED:
                # the trace just ran inside lower(): the dispatch delta is
                # the kernel count, same meaning as the first-call gauge
                _telem.set_gauge(
                    "train_step.pallas_kernels",
                    _telem.counter("ops.pallas.dispatch").value - before)
            key = cache_key(
                kind="sharded_train_step", hlo=hlo_hash(lowered),
                mesh={"axes": list(self.mesh.axis_names),
                      "shape": list(self.mesh.devices.shape)})
            ex, restored = load_or_compile(key, lambda: lowered,
                                           "ShardedTrainStep")
            if restored:
                _telem.inc("train_step.aot_restored")
            else:
                _telem.note_compile("ShardedTrainStep")
            self._aot_sig = sig
            return ex
        except Exception:  # noqa: BLE001 — cache is best-effort by contract
            _telem.inc("compiler.cache.unusable")
            return None

    def lower_text(self, params, opt_state, batch):
        """StableHLO text of the compiled step (for inspection/tests)."""
        self._batch_proto = batch
        fn = self._build(params, opt_state)
        return fn.lower(params, opt_state, batch,
                        jnp.zeros((), jnp.int32)).as_text()

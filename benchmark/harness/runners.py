"""The entries of the program that a window drives, built as a user builds
them (the recipes of `chip_smoke.py`, copied, not imported).

A runner is made from a configuration, a mix and the benchmark's own weights
and batch. It offers:

  entry            the name of the program's entry, for host spans
  counters         telemetry counters that count compilations of the step
  call()           one training step through that entry; returns the loss,
                   a device array that is not awaited
  params()         {leaf name: array} of the current parameters, under the
                   plain reference's names
  first_gradient() {leaf name: array}: the first gradient as the optimizer
                   got it, from the state after one step
  free()           drop everything held on the device

A configuration's `entry` key names its runner in `RUNNERS`: which entry of
the program the window drives. What the entry is given comes by name too
(the model zoo's `model`; the `program` module with a functional model's
loss), so a new architecture on an entry that is here brings files and no
edit. The reference put in the program's place (`ReferenceRunner`) is how
the controls and the planted faults are read, and never a cell's runner.
"""
import functools
import gc
import json

import jax
import jax.numpy as jnp

from .spec import module


def _copy_tree(tree):
    """Fresh buffers: the program donates what it is given."""
    return jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(tree)


class GluonFusedStep:
    """`gluon.FusedTrainStep` over a model-zoo net and a `gluon.Trainer`,
    on one chip or over a data mesh (`traffic["mesh"]`)."""
    entry = "gluon.FusedTrainStep.__call__"
    counters = ("fused_step.compile", "fused_step.retrace")

    def __init__(self, cfg, traffic, reference, params, batch, rehearse):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, nd
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.parallel import create_mesh

        ctx = mx.cpu() if rehearse else mx.tpu()
        chips = traffic.get("mesh", {}).get("data", 1)
        mesh = create_mesh(data=chips) if chips > 1 else None
        table = reference.leaves(cfg)
        opt = cfg["optimizer"]
        self._lr = opt["learning_rate"]
        with mx.Context(ctx):
            net = getattr(vision, cfg["model"])(classes=cfg["classes"])
            net.initialize(ctx=ctx)
            if cfg["dtype"] != "float32":
                net.cast(cfg["dtype"])  # conv stack; BatchNorm stays float32
            net.hybridize(static_alloc=True)
            # the benchmark's weights, in the model zoo's order; with every
            # shape known no inference forward is needed to finish the
            # deferred initialisation
            mine = _copy_tree(params)
            self._by_name = {}
            for (name, _, _), p in zip(table,
                                       net.collect_params().values()):
                p.set_data(nd.from_jax(mine[name], ctx=ctx))
                p._finish_deferred_init()
                self._by_name[name] = p
            del mine
            self._x = nd.from_jax(batch["data"], ctx=ctx)
            self._y = nd.from_jax(batch["label"], ctx=ctx)
            trainer = gluon.Trainer(
                net.collect_params(), opt["name"],
                {"learning_rate": opt["learning_rate"],
                 "momentum": opt["momentum"], "wd": opt["wd"]})
            self._step = gluon.FusedTrainStep(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer, mesh=mesh)
        self._ctx, self._mx_ctx, self._net, self._trainer = (
            ctx, mx.Context(ctx), net, trainer)

    def call(self):
        with self._mx_ctx:
            return self._step(self._x, self._y).data_jax

    def params(self):
        return {name: p.data(self._ctx).data_jax
                for name, p in self._by_name.items()}

    def first_gradient(self):
        # sgd with momentum: mom_1 = -lr * grad_1
        names = {id(p): n for n, p in self._by_name.items()}
        return {names[id(p)]: -s.data_jax.astype(jnp.float32) / self._lr
                for p, s in zip(self._step._train_params,
                                self._step._states)}

    def free(self):
        self._step = self._net = self._trainer = self._by_name = None
        self._x = self._y = None
        gc.collect()


class ShardedStep:
    """`parallel.ShardedTrainStep` over a functional model's loss, on a mesh
    of the mix's size (one chip: `data=1`). The loss is the configuration's
    own: its `program` key names a module under `benchmark/programs/` whose
    `loss_fn(cfg)` returns the `loss_fn(params, batch)` that a user hands to
    the step, over the tree that the reference's dotted leaf names spell."""
    entry = "parallel.ShardedTrainStep.__call__"
    counters = ("train_step.compile", "train_step.retrace")

    def __init__(self, cfg, traffic, reference, params, batch, rehearse):
        from mxnet_tpu.parallel import ShardedTrainStep, create_mesh

        opt = cfg["optimizer"]
        self._beta1 = opt["beta1"]
        tree = {}
        for name, value in _copy_tree(params).items():
            node = tree
            *path, last = name.split(".")
            for key in path:
                node = node.setdefault(key, {})
            node[last] = value
        self._step = ShardedTrainStep(
            module("programs", cfg["program"]).loss_fn(cfg), tree,
            create_mesh(data=traffic.get("mesh", {}).get("data", 1)),
            optimizer=opt["name"], lr=opt["learning_rate"], wd=opt["wd"],
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"])
        self._params, self._state = self._step.init()
        self._batch = batch

    def call(self):
        self._params, self._state, loss = self._step(
            self._params, self._state, self._batch)
        return loss

    @staticmethod
    def _flat(tree):
        return {".".join(str(k.key) for k in path): leaf for path, leaf in
                jax.tree_util.tree_flatten_with_path(tree)[0]}

    def params(self):
        return self._flat(self._params)

    def first_gradient(self):
        # adam: m_1 = (1 - beta1) * grad_1
        return {k: v.astype(jnp.float32) / (1 - self._beta1)
                for k, v in self._flat(self._state["m"]).items()}

    def free(self):
        self._step = self._params = self._state = self._batch = None
        gc.collect()


RUNNERS = {"gluon_fused_step": GluonFusedStep,
           "sharded_train_step": ShardedStep}


_STEPS = {}     # a reference's jitted step, built once in a process


def _reference_step(reference, cfg, mode):
    key = (reference.__name__, mode, json.dumps(cfg, sort_keys=True))
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            functools.partial(reference.train_step, cfg=cfg, mode=mode),
            donate_argnums=(0, 1))
    return _STEPS[key]


class ReferenceRunner:
    """The plain reference put in the program's place: the control (a lower
    `mode`) and the planted faults.

    fault: None | "unchanged" (the step returns its state as it got it) |
    "half_batch" (half of the rows left out, the mean taken over the rest) |
    "no_exchange" (each chip keeps the gradient of its own rows: the first
    chip's share of the batch, the mean over it)
    stored: a type to keep weights and optimizer state in, instead of the
    configuration's (the control of a configuration stated in float32)
    """
    entry = "reference.train_step"
    counters = ()

    def __init__(self, cfg, traffic, reference, params, batch, rehearse,
                 mode="f32", fault=None, devices=None, stored=None):
        if stored:
            cfg = dict(cfg, dtype=stored)
            kinds = {name: kind for name, _, kind in reference.leaves(cfg)}
            params = {k: v.astype(reference.storage_dtype(kinds[k], cfg))
                      for k, v in params.items()}
        self._ref, self._cfg, self._fault = reference, cfg, fault
        rows = traffic["batch"]
        if fault == "half_batch":
            rows //= 2
        elif fault == "no_exchange":
            rows //= traffic.get("mesh", {}).get("data", 1)
        batch = {k: v[:rows] for k, v in batch.items()}
        params = _copy_tree(params)
        if devices is not None and len(devices) > 1:
            params, batch = spread(params, batch, devices)
        self._params, self._batch = params, batch
        self._state = reference.new_state(params, cfg)
        self._first = None
        self._fn = _reference_step(reference, cfg, mode)

    def call(self):
        if self._fault == "unchanged":
            keep = _copy_tree((self._params, self._state))
            _, _, loss = self._fn(self._params, self._state, self._batch)
            self._params, self._state = keep
            return loss
        self._params, self._state, loss = self._fn(
            self._params, self._state, self._batch)
        return loss

    def params(self):
        return self._params

    def first_gradient(self):
        return self._ref.first_gradient(self._state, self._cfg)

    def free(self):
        self._params = self._state = self._batch = self._fn = None
        gc.collect()


def first_gradient_in(mode, *args, **kw):
    """The reference's first gradient in `mode`: one step from the same
    weights and batch, for `correct.unresolved`."""
    stated = ReferenceRunner(*args, mode=mode, **kw)
    stated.call()
    first = stated.first_gradient()
    stated.free()
    return first


def spread(params, batch, devices):
    """The reference over several chips: rows of the batch dealt out, the
    weights on every chip. It stays one plain program; the compiler puts in
    the sums over rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("rows",))
    return (jax.device_put(params, NamedSharding(mesh, P())),
            jax.device_put(batch, NamedSharding(mesh, P("rows"))))

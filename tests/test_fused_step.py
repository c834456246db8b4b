"""FusedTrainStep: one-jit Gluon training must match the imperative
`loss.backward(); trainer.step()` path exactly (same ops, same scalars).
reference behavior: SURVEY.md §3.2 call stack."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def _mlp(seed, bn=False, dropout=0.0):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"))
        if bn:
            net.add(nn.BatchNorm())
        if dropout:
            net.add(nn.Dropout(dropout))
        net.add(nn.Dense(8))
    net.initialize(mx.init.Xavier(rnd_type="gaussian"), ctx=mx.cpu())
    return net


def _data(n=16, d=12, classes=8, seed=0):
    rng = np.random.RandomState(seed)
    x = nd.array(rng.randn(n, d).astype(np.float32))
    y = nd.array(rng.randint(0, classes, (n,)).astype(np.float32))
    return x, y


@pytest.mark.parametrize("optimizer,opt_args", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("signum", {"learning_rate": 0.01, "momentum": 0.9, "wd_lh": 1e-4}),
    ("signsgd", {"learning_rate": 0.005}),
    ("ftml", {"learning_rate": 0.01}),
    ("adagrad", {"learning_rate": 0.05}),
    ("adadelta", {"rho": 0.9, "epsilon": 1e-5}),
    ("adamax", {"learning_rate": 0.002}),
    ("nadam", {"learning_rate": 0.005}),
    ("rmsprop", {"learning_rate": 0.005}),
    ("rmsprop", {"learning_rate": 0.005, "centered": True, "gamma2": 0.85}),
    ("ftrl", {"learning_rate": 0.05, "lamda1": 0.001}),
    ("lamb", {"learning_rate": 0.01}),
    ("lars", {"learning_rate": 0.05, "momentum": 0.9, "eta": 0.001}),
    ("dcasgd", {"learning_rate": 0.05, "momentum": 0.9}),
])
def test_fused_matches_imperative(optimizer, opt_args):
    mx.random.seed(7)
    net_a = _mlp(0)
    x, y = _data()
    net_a(x)  # init shapes
    # clone params into a second net
    net_b = _mlp(1)
    net_b(x)
    for (ka, pa), (kb, pb) in zip(sorted(net_a.collect_params().items()),
                                  sorted(net_b.collect_params().items())):
        pb.set_data(pa.data().copy())

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr_a = gluon.Trainer(net_a.collect_params(), optimizer, dict(opt_args))
    tr_b = gluon.Trainer(net_b.collect_params(), optimizer, dict(opt_args))
    fused = gluon.FusedTrainStep(net_b, loss_fn, tr_b)

    for step in range(4):
        with autograd.record():
            la = loss_fn(net_a(x), y)
        la.backward()
        tr_a.step(x.shape[0])
        lb = fused(x, y)
        np.testing.assert_allclose(float(la.mean().asnumpy()),
                                   float(lb.asnumpy()), rtol=1e-5, atol=1e-6)
    for (ka, pa), (kb, pb) in zip(sorted(net_a.collect_params().items()),
                                  sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(), pb.data().asnumpy(),
                                   rtol=2e-5, atol=2e-6,
                                   err_msg="param %s diverged" % ka)


def test_fused_bn_dropout_trains():
    """BatchNorm aux stats update + dropout RNG inside the fused program."""
    mx.random.seed(11)
    net = _mlp(2, bn=True, dropout=0.3)
    x, y = _data(n=32)
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    bn = [p for name, p in net.collect_params().items()
          if "running_mean" in name][0]
    before = bn.data().asnumpy().copy()
    losses = [float(fused(x, y).asnumpy()) for _ in range(15)]
    assert losses[-1] < losses[0], losses
    assert not np.allclose(bn.data().asnumpy(), before), \
        "BatchNorm running stats did not update through the fused step"


def test_fused_lr_scheduler_advances():
    """Scheduler state (num_update) must advance per fused step — the lr is
    host-computed and fed as a device scalar each call."""
    mx.random.seed(13)
    net = _mlp(3)
    x, y = _data()
    net(x)
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.4, "lr_scheduler": sched})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    for _ in range(5):
        fused(x, y)
    assert tr._optimizer.num_update == 5
    assert tr.learning_rate < 0.4


def test_fused_hybridized_net():
    """A hybridized net inlines into the fused trace (no nested CachedOp)."""
    mx.random.seed(17)
    net = _mlp(4)
    net.hybridize()
    x, y = _data()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 0.01})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    losses = [float(fused(x, y).asnumpy()) for _ in range(10)]
    assert losses[-1] < losses[0]


def test_fused_input_nesting_retrace():
    """A call with identical shapes but different input NESTING must not
    reuse a stale trace (round-2 verdict Weak #10): programs are keyed by
    the flattened input format."""

    class TwoIn(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = nn.Dense(8)

        def hybrid_forward(self, F, a, b=None):
            return self.d(a if b is None else a + b)

    mx.random.seed(23)
    net = TwoIn()
    net.initialize(mx.init.Xavier(rnd_type="gaussian"), ctx=mx.cpu())
    x, y = _data()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    l_single = fused(x, y)                 # data = one array
    # snapshot params BEFORE the pair step: its loss is computed on these
    net_ref = TwoIn()
    net_ref.initialize(ctx=mx.cpu())
    net_ref(x, x)  # trigger deferred init so set_data has shapes
    for (name, p_ref), (_, p) in zip(
            sorted(net_ref.collect_params().items()),
            sorted(net.collect_params().items())):
        p_ref.set_data(p.data())
    l_pair = fused([x, x], y)              # data = list of two, same shapes
    assert len(fused._programs) == 2
    # the pair trace must actually consume both inputs: f(x,x) == f(2x-ish)
    out_pair = net_ref(x, x)
    loss_ref = gluon.loss.SoftmaxCrossEntropyLoss()(out_pair, y)
    np.testing.assert_allclose(float(l_pair.asnumpy()),
                               float(loss_ref.mean().asnumpy()), rtol=2e-2)


# ---------------------------------------------------------------------------
# mesh mode: fused multi-device Gluon (reference: multi-device Trainer +
# KVStore 'device' — SURVEY.md §2.3 row 1; here one GSPMD program)
# ---------------------------------------------------------------------------

def _bn_mlp(seed):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.BatchNorm(),
                nn.Dense(8))
    net.initialize(mx.init.Xavier(rnd_type="gaussian"), ctx=mx.cpu())
    return net


def test_fused_mesh_data_parallel_matches_single_device():
    """The same fused step over an 8-device DP mesh must match the
    single-device run numerically (global batch semantics)."""
    from mxnet_tpu.parallel import create_mesh
    x, y = _data(n=32, d=12)

    def run(mesh):
        mx.random.seed(3)
        net = _bn_mlp(0)
        net(x)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
        fused = gluon.FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), tr, mesh=mesh)
        losses = [float(fused(x, y).asnumpy()) for _ in range(8)]
        params = [v.data().asnumpy()
                  for _, v in sorted(net.collect_params().items())]
        return losses, params

    l_single, p_single = run(None)
    mesh = create_mesh(data=8)
    l_mesh, p_mesh = run(mesh)
    np.testing.assert_allclose(l_mesh, l_single, rtol=1e-4, atol=1e-5)
    for a, b in zip(p_mesh, p_single):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert l_single[-1] < l_single[0]


def test_fused_mesh_resnet_trains():
    """Gluon zoo resnet + Trainer trains on the 8-device virtual mesh
    (round-2 verdict task #7 done-criterion)."""
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import create_mesh
    mx.random.seed(5)
    mesh = create_mesh(data=8)
    net = vision.resnet18_v1(classes=4)
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net.hybridize()
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(16, 3, 32, 32).astype(np.float32))
    y = nd.array(rng.randint(0, 4, (16,)).astype(np.float32))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01, "momentum": 0.9})
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr, mesh=mesh)
    losses = [float(fused(x, y).asnumpy()) for _ in range(10)]
    assert losses[-1] < losses[0], losses
    # params live sharded/replicated on the mesh
    w = net.collect_params()
    any_param = next(iter(w.values())).data()
    assert len(any_param._read().sharding.device_set) == 8


def test_eager_tape_matches_fused_step_end_to_end():
    """The eager tape (FGradient rules + jitted backward cache) and the
    FusedTrainStep jit program must produce numerically matching training
    trajectories from identical inits — cross-validates the round-5
    autograd layer against the compiled path over several steps (crossing
    the backward-cache warm-up threshold)."""
    import numpy as np
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import nn

    def build():
        mx.random.seed(42)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
        return net, tr

    rng = np.random.RandomState(0)
    X = rng.rand(6, 32, 8).astype(np.float32)
    Y = rng.randint(0, 3, (6, 32)).astype(np.float32)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # eager tape path (un-hybridized: every op recorded)
    net_e, tr_e = build()
    eager_losses = []
    for i in range(6):
        x, y = nd.array(X[i]), nd.array(Y[i])
        with autograd.record():
            loss = loss_fn(net_e(x), y)
        loss.backward()
        tr_e.step(32)
        eager_losses.append(float(loss.mean().asnumpy()))

    # fused jit path
    net_f, tr_f = build()
    step = gluon.FusedTrainStep(net_f, loss_fn, tr_f)
    fused_losses = []
    for i in range(6):
        l = step(nd.array(X[i]), nd.array(Y[i]))
        fused_losses.append(float(l.mean().asnumpy()))

    np.testing.assert_allclose(eager_losses, fused_losses, rtol=2e-5,
                               atol=1e-6)
    # final parameters match too
    for (kn, pe), (_, pf) in zip(sorted(net_e.collect_params().items()),
                                 sorted(net_f.collect_params().items())):
        np.testing.assert_allclose(pe.data().asnumpy(),
                                   pf.data().asnumpy(), rtol=2e-4,
                                   atol=2e-6, err_msg=kn)


def test_second_step_reuses_the_compiled_program(caplog):
    """jit keys its executable on which arguments are committed to a device.
    Parameters of a cpu context are uncommitted and the step's outputs are
    committed, so the second call used to compile the whole program again
    (on the cpu only: an accelerator context commits what it copies over)."""
    import logging

    import jax

    mx.random.seed(0)
    net = _mlp(0, bn=True)
    net.cast("bfloat16")
    net.hybridize(static_alloc=True)
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(16, 12), dtype="bfloat16")
    y = nd.array(rng.randint(0, 8, (16,)), dtype="float32")
    net(x)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 trainer)
    fused(x, y).wait_to_read()
    with jax.log_compiles(True), caplog.at_level(logging.WARNING, "jax"):
        fused(x, y).wait_to_read()
        fused(x, y).wait_to_read()
    compiled = [r.getMessage() for r in caplog.records
                if "Compiling" in r.getMessage()]
    assert not compiled, compiled


def test_compile_counter_counts_what_xla_built():
    """`fused_step.compile` is read from jit's own cache: steady steps add
    nothing, and a new batch size builds (and counts) once more under the
    same program object."""
    from mxnet_tpu import telemetry

    def compiles():
        return telemetry.snapshot()["counters"].get("fused_step.compile", 0)

    mx.random.seed(0)
    net = _mlp(0, bn=True)
    net.cast("bfloat16")
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(16, 12), dtype="bfloat16")
    y = nd.array(rng.randint(0, 8, (16,)), dtype="float32")
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    fused = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                 trainer)
    start = compiles()
    for _ in range(3):
        fused(x, y).wait_to_read()
    assert compiles() - start == 1
    fused(x[:8], y[:8]).wait_to_read()
    fused(x[:8], y[:8]).wait_to_read()
    assert compiles() - start == 2

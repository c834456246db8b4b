"""`telemetry.hlo_scopes`: the parser of a compiled module's text and the
reading of an `op_name` as a pass and its scopes, on a toy step compiled
here; and `telemetry.note_step_program` / `module_scopes()`: both train
steps publish the map of what they compiled under a profiler session, and
only there."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.gluon import fused_step, nn
from mxnet_tpu.parallel import ShardedTrainStep, create_mesh, train_step
from mxnet_tpu.telemetry import hlo_scopes


@jax.custom_vjp
def _square(x):
    return x * x


_square.defvjp(lambda x: (x * x, x), lambda x, g: (2 * x * g,))


def _block(w, x):
    with jax.named_scope("blk"):
        return jnp.tanh(x @ w)


def _loss(p, x):
    with jax.named_scope("forward"):
        h = jax.checkpoint(_block)(p["w1"], x)
        with jax.named_scope("sw"):
            h = lax.switch(jnp.argmax(h[0]) % 2,
                           [lambda h: h * 2, jnp.sin], h)
        with jax.named_scope("loop"):
            h, _ = lax.scan(lambda c, _: (jnp.tanh(c @ p["w2"]), None), h,
                            None, length=3)
        with jax.named_scope("cv"):
            h = _square(h)
        return jnp.sum(h)


def _step(p, x):
    loss, grads = jax.value_and_grad(_loss)(p, x)
    with jax.named_scope("optimizer"):
        p = jax.tree_util.tree_map(lambda a, g: a - 0.1 * g, p, grads)
    return p, loss


@pytest.fixture(scope="module")
def toy():
    """(text, parsed) of the toy step's optimized module."""
    p = {"w1": jnp.ones((8, 8)), "w2": jnp.ones((8, 8)) * 0.1}
    text = jax.jit(_step).lower(p, jnp.ones((4, 8))).compile().as_text()
    return text, hlo_scopes.parse(text)


def test_every_instruction_of_every_computation_is_found(toy):
    text, parsed = toy
    lines = [ln for ln in text.splitlines() if " = " in ln
             and ln.lstrip().startswith(("%", "ROOT %"))]
    assert len(parsed) == len(lines) > 50
    entry = [i for i in parsed.values() if i.computation.startswith("main")]
    assert entry and all(i.calls == () or i.opcode in (
        "fusion", "while", "conditional", "call", "reduce", "sort",
        "scatter", "map") for i in entry)
    assert parsed["dot_general.9"].opcode == "dot"


def test_containers_are_told_by_opcode_and_their_bodies_are_listed(toy):
    _, parsed = toy
    held = {}
    for name, instr in parsed.items():
        held.setdefault(instr.computation, []).append(name)
    loops = [i for i in parsed.values() if i.opcode == "while"]
    switches = [i for i in parsed.values() if i.opcode == "conditional"]
    assert len(loops) == 2 and len(switches) == 2     # forward and backward
    assert {i.opcode for i in loops + switches} <= set(hlo_scopes.CONTAINERS)
    for loop in loops:
        assert len(loop.calls) == 2          # condition and body
        assert all(held[c] for c in loop.calls)
    for switch in switches:
        assert len(switch.calls) == 2        # a branch each
        assert all(held[c] for c in switch.calls)
    # a loop body's product is an instruction like any other
    body = [n for loop in loops for c in loop.calls for n in held[c]]
    assert any(parsed[n].opcode in ("dot", "fusion") for n in body)


@pytest.mark.parametrize("op_name,expected", [
    ("jit(step_fn)/jvp(forward)/gdn/delta_scan/while",
     ("forward", ("forward", "gdn", "delta_scan"))),
    ("jit(step_fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
     "rematted_computation/gdn/mul",
     ("recomputed", ("forward", "forward", "checkpoint",
                     "rematted_computation", "gdn"))),
    ("jit(step_fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/gdn/mul",
     ("backward", ("forward", "forward", "checkpoint", "gdn"))),
    ("jit(step_fn)/optimizer/sub", ("optimizer", ("optimizer",))),
    ("jit(step_fn)/jvp(forward)/moe/cond/branch_1_fun/jit(_routed_rows)/"
     "rows_43008/pjit/mul",
     ("forward", ("forward", "moe", "cond", "branch_1_fun", "rows_43008"))),
    ("jit(run)/transpose(jvp(forward))/jit(_var)/reduce_sum",
     ("backward", ("forward",))),
    ("jit(step_fn)/sub", ("none", ())),
    ("params['w']", ("none", ())),
    ("", ("none", ())),
])
def test_path_reads_an_op_name_as_pass_and_scopes(op_name, expected):
    assert hlo_scopes.path(op_name) == expected
    assert expected[0] in hlo_scopes.PASSES


RELATIVE = """HloModule jit_step_fn

%fused_scatter (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p0, %p0), metadata={op_name="while/body/rows_4096/scatter-add"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0} fusion(%get.1), kind=kLoop, calls=%fused_scatter, metadata={op_name="while/body/rows_4096/scatter-add"}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_scatter, metadata={op_name="jit(step_fn)/jvp(forward)/moe/jit(_routed_chunks)/while/body/rows_4096/mul"}
  %fusion.9 = f32[8]{0} fusion(%fusion.8), kind=kLoop, calls=%fused_scatter, metadata={op_name="transpose(jvp(jit(_rows)))/rows_4096/gather"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%get.1, %fusion.9)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %zero = s32[] constant(0)
  %tuple.2 = (s32[], f32[8]{0}) tuple(%zero, %x)
  %while.3 = (s32[], f32[8]{0}) while(%tuple.2), condition=%cond, body=%body, metadata={op_name="jit(step_fn)/jvp(forward)/moe/jit(_routed_chunks)/while"}
  ROOT %get.2 = f32[8]{0} get-tuple-element(%while.3), index=1
}
"""


def test_a_name_without_its_callers_stack_goes_behind_the_callers():
    """An `op_name` that lacks the stack it was called under (the gathers
    and scatter-adds in the body of `ops/moe.py`'s loops on a v5e) is read
    behind the `while` that calls its computation, the fusion's own
    instructions too, overlapped at `while`; one that shares no part with
    its caller goes in place of the caller's primitive; a full name and a
    parameter's stay."""
    parsed = hlo_scopes.parse(RELATIVE)
    full = "jit(step_fn)/jvp(forward)/moe/jit(_routed_chunks)/while/body/"
    assert parsed["fusion.7"].op_name == full + "rows_4096/scatter-add"
    assert parsed["add.1"].op_name == full + "rows_4096/scatter-add"
    assert parsed["fusion.8"].op_name == full + "rows_4096/mul"
    assert parsed["fusion.9"].op_name == (
        "jit(step_fn)/jvp(forward)/moe/jit(_routed_chunks)/"
        "transpose(jvp(jit(_rows)))/rows_4096/gather")
    assert parsed["x"].op_name == "x" and parsed["get.1"].op_name == ""
    assert hlo_scopes.path(parsed["fusion.7"].op_name) == (
        "forward", ("forward", "moe", "while", "body", "rows_4096"))
    assert hlo_scopes.path(parsed["fusion.9"].op_name)[0] == "backward"


def test_the_four_passes_come_out_of_the_toy_step(toy):
    _, parsed = toy
    by_pass = {}
    # inside the fusions too: here the recomputed `tanh` is fused into the
    # backward pass's first product, and the fusion is filed under its root
    for instr in parsed.values():
        which, scopes = hlo_scopes.path(instr.op_name)
        by_pass.setdefault(which, []).append(scopes)
    assert set(by_pass) == set(hlo_scopes.PASSES)
    assert all("blk" in s for s in by_pass["recomputed"])
    assert any("blk" in s for s in by_pass["backward"])
    assert any("blk" in s for s in by_pass["forward"])
    # a `custom_vjp`'s backward keeps the scope it was called under
    assert any("cv" in s for s in by_pass["backward"])
    assert all(s == ("optimizer",) for s in by_pass["optimizer"])


# ------------------------------------------------ the steps publish the map
@pytest.fixture
def clean():
    was_enabled = telemetry.ENABLED
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.reset()
    (telemetry.enable if was_enabled else telemetry.disable)()


def _sharded():
    """(step, call(rows)): `call` runs one step on a batch of `rows` rows;
    another count builds the program again."""
    def loss(p, b):
        with jax.named_scope("blk"):
            return jnp.sum(jnp.tanh(b["x"] @ p["w"]) ** 2)
    step = ShardedTrainStep(loss, {"w": jnp.ones((8, 8))},
                            create_mesh(data=1), optimizer="adamw", lr=1e-3)
    params, state = step.init()

    def call(rows=4):
        nonlocal params, state
        params, state, loss = step(params, state,
                                   {"x": jnp.ones((rows, 8))})
        return loss
    return step, call


def _fused():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    rng = np.random.RandomState(0)
    x = rng.randn(8, 6).astype(np.float32)
    y = rng.randint(0, 4, (8,)).astype(np.float32)
    net(nd.array(x))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = gluon.FusedTrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                trainer)

    def call(rows=4):
        return step(nd.array(x[:rows]), nd.array(y[:rows]))
    return step, call


# the module's name is the step's own word for it: a renamed `step_fn` or
# `run` that left the constant behind would publish no map, and fail here
STEPS = {"sharded": (_sharded, train_step.STEP_MODULE),
         "fused": (_fused, fused_step.STEP_MODULE)}


def _loaded(module_name):
    return [e for e in jax.devices()[0].client.live_executables()
            if e.hlo_modules()[0].name == module_name]


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_without_a_session_a_step_reads_nothing(kind, clean, monkeypatch):
    calls = []
    monkeypatch.setattr(hlo_scopes, "parse",
                        lambda text: calls.append(text) or {})
    monkeypatch.setattr(hlo_scopes, "path",
                        lambda op_name: calls.append(op_name) or ("none", ()))
    _, call = STEPS[kind][0]()
    for _ in range(3):
        call()
    assert telemetry.module_scopes() == {} and calls == []


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_under_a_session_the_step_publishes_its_scope_map(kind, clean,
                                                          tmp_path):
    make, module = STEPS[kind]
    _, call = make()
    call()                                  # built before the session
    with jax.profiler.trace(str(tmp_path)):
        call()
        call()
        # the programs a session saw have no map while it is on, and
        # asking does not wait for its end
        assert telemetry.module_scopes() == {}
    scopes = telemetry.module_scopes()
    assert list(scopes) == [module]
    passes = {hlo_scopes.path(i.op_name)[0] for i in scopes[module].values()
              if i.opcode == "fusion"}
    assert {"forward", "backward", "optimizer"} <= passes
    # cleared with the rest
    telemetry.reset()
    assert telemetry.module_scopes() == {}


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_the_map_outlives_the_step_and_holds_no_executable(kind, clean,
                                                           tmp_path):
    make, module = STEPS[kind]
    gc.collect()
    before = len(_loaded(module))           # other tests' steps, if any
    step, call = make()
    with jax.profiler.trace(str(tmp_path)):
        call()
    assert module in telemetry.module_scopes()
    assert len(_loaded(module)) == before + 1
    del step, call          # as the benchmark's `runner.free()` does
    gc.collect()
    assert len(_loaded(module)) == before
    assert len(telemetry.module_scopes()[module]) > 10


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_the_map_is_read_unasked_before_the_step_is_dropped(kind, clean,
                                                            tmp_path):
    """The benchmark's order: the session ends, the trace is reduced, the
    runner is freed, and only then a metric asks for the map."""
    make, module = STEPS[kind]
    gc.collect()
    before = len(_loaded(module))
    step, call = make()
    with jax.profiler.trace(str(tmp_path)):
        call()
    with telemetry._scopes_lock:
        entry = telemetry._scopes[module]
    assert entry.done.wait(60)              # the worker, on its own
    del step, call
    gc.collect()
    assert len(_loaded(module)) == before   # the worker kept nothing of it
    assert len(telemetry.module_scopes()[module]) > 10


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_a_rebuilt_program_is_read_again(kind, clean, tmp_path):
    make, module = STEPS[kind]
    _, call = make()
    with jax.profiler.trace(str(tmp_path / "a")):
        call()
        call()
    first = telemetry.module_scopes()[module]
    assert telemetry.module_scopes()[module] is first       # read once
    with jax.profiler.trace(str(tmp_path / "b")):
        call()
    assert telemetry.module_scopes()[module] is first       # and kept
    with jax.profiler.trace(str(tmp_path / "c")):
        call(6)             # another batch: the step builds again
    second = telemetry.module_scopes()[module]
    assert second is not first and second
    # with both programs loaded, the one that is read is the newer
    assert len(_loaded(module)) >= 2
    assert "f32[6," in telemetry._step_module(module).to_string()
    # outside a session a rebuild only forgets the stale map
    call(2)
    assert module not in telemetry.module_scopes()
    with jax.profiler.trace(str(tmp_path / "d")):
        call(2)
    assert module in telemetry.module_scopes()
    assert "f32[2," in telemetry._step_module(module).to_string()


def test_the_newest_four_modules_are_kept(clean, tmp_path, caplog):
    with jax.profiler.trace(str(tmp_path)):
        for i in range(6):
            telemetry.note_step_program("jit_absent_%d" % i)
    with telemetry._scopes_lock:
        assert list(telemetry._scopes) == ["jit_absent_%d" % i
                                           for i in range(2, 6)]
    # a name that no loaded program bears has no map, and the log says so
    with caplog.at_level("WARNING", logger="mxnet_tpu.telemetry"):
        assert telemetry.module_scopes() == {}
    assert sum("no loaded program" in r.getMessage()
               for r in caplog.records) == 4


def test_a_read_that_fails_is_logged_and_leaves_nobody_waiting(clean, tmp_path,
                                                               caplog,
                                                               monkeypatch):
    def broken(name):
        raise RuntimeError("the runtime hands nothing over")
    monkeypatch.setattr(telemetry, "_step_module", broken)
    with jax.profiler.trace(str(tmp_path)):
        telemetry.note_step_program("jit_absent")
    with caplog.at_level("WARNING", logger="mxnet_tpu.telemetry"):
        assert telemetry.module_scopes() == {}
    assert any("jit_absent" in r.getMessage() and r.exc_info
               for r in caplog.records)


def test_a_worker_that_cannot_start_leaves_nobody_waiting(clean, tmp_path,
                                                          monkeypatch):
    def refuse(*args):
        raise RuntimeError("can't start new thread")
    monkeypatch.setattr(telemetry._thread, "start_new_thread", refuse)
    with jax.profiler.trace(str(tmp_path)):
        telemetry.note_step_program("jit_absent")
    assert telemetry.module_scopes() == {}

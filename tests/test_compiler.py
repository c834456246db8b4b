"""Whole-graph symbolic compiler + persistent AOT executable cache.

Acceptance (ISSUE 11): a Module forward/fit on a resnet-scale symbol
graph produces identical outputs via the whole-graph program vs the
op-by-op executor, with exactly ONE compiled program (compile counters
prove no per-op dispatch after bind); a second process/instance with a
warm MXNET_TPU_AOT_CACHE reports cache hits and zero fresh compiles for
the cached programs (the in-instance restores are asserted here, in a
process with more devices than the programs were compiled for). Cache
robustness: corrupted/
truncated entries are counted misses followed by a recompile, version
skew misses, concurrent writers are atomic last-write-wins, keep=N
evicts oldest-first.
"""
import json
import os
import pickle
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import compiler, nd, telemetry
from mxnet_tpu import symbol as sym
from mxnet_tpu.compiler import cache as cache_mod
from mxnet_tpu.compiler import lower as lower_mod
from mxnet_tpu.compiler.cache import AOTCache, cache_key
from mxnet_tpu.io.io import DataBatch, NDArrayIter

pytestmark = pytest.mark.compiler


@pytest.fixture(autouse=True)
def _clean_compiler(monkeypatch):
    """Fresh telemetry + program memo per test; the AOT cache stays OFF
    unless a test points MXNET_TPU_AOT_CACHE somewhere itself."""
    monkeypatch.delenv("MXNET_TPU_AOT_CACHE", raising=False)
    monkeypatch.delenv("MXNET_TPU_WHOLE_GRAPH", raising=False)
    telemetry.enable()
    telemetry.reset()
    lower_mod._MEMO.clear()
    yield
    lower_mod._MEMO.clear()
    telemetry.reset()


def _counters():
    return telemetry.snapshot()["counters"]


# ---------------------------------------------------------------------------
# fixture graphs
# ---------------------------------------------------------------------------
def _mlp_symbol():
    data = sym.var("data")
    fc1 = sym.FullyConnected(data, name="fc1", num_hidden=16)
    act1 = sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = sym.FullyConnected(act1, name="fc2", num_hidden=3)
    return sym.SoftmaxOutput(fc2, name="softmax")


def _resnetish_symbol(n_blocks=2, channels=8):
    """A resnet-shaped graph: conv stem, residual conv+BN+relu blocks
    with identity adds, global pooling, FC head, softmax loss — the
    acceptance topology (convs, BN aux states, residual fan-out that
    exercises CSE-safe shared subgraphs, multi-consumer nodes)."""
    x = sym.var("data")
    h = sym.Convolution(x, name="stem", num_filter=channels, kernel=(3, 3),
                        pad=(1, 1), no_bias=True)
    h = sym.BatchNorm(h, name="stem_bn", fix_gamma=False)
    h = sym.Activation(h, name="stem_relu", act_type="relu")
    for i in range(n_blocks):
        s = sym.Convolution(h, name="b%d_c1" % i, num_filter=channels,
                            kernel=(3, 3), pad=(1, 1), no_bias=True)
        s = sym.BatchNorm(s, name="b%d_bn1" % i, fix_gamma=False)
        s = sym.Activation(s, name="b%d_relu1" % i, act_type="relu")
        s = sym.Convolution(s, name="b%d_c2" % i, num_filter=channels,
                            kernel=(3, 3), pad=(1, 1), no_bias=True)
        s = sym.BatchNorm(s, name="b%d_bn2" % i, fix_gamma=False)
        h = sym.Activation(h + s, name="b%d_out" % i, act_type="relu")
    h = sym.Pooling(h, name="gap", global_pool=True, pool_type="avg",
                    kernel=(1, 1))
    h = sym.Flatten(h, name="flat")
    h = sym.FullyConnected(h, name="head", num_hidden=4)
    return sym.SoftmaxOutput(h, name="softmax")


def _feed_values(net, data_shape, seed=0):
    rng = np.random.RandomState(seed)
    vals = {}
    for name, shape in zip(net.list_arguments(),
                           net.infer_shape(data=data_shape)[0]):
        if name == "data":
            vals[name] = rng.normal(size=shape).astype("float32")
        elif name == "softmax_label":
            vals[name] = rng.randint(0, 3, size=shape).astype("float32")
        elif name.endswith("gamma"):
            vals[name] = np.ones(shape, "float32")
        else:
            vals[name] = (rng.normal(size=shape) * 0.1).astype("float32")
    return vals


def _bind_and_run(net, vals, data_shape, label_shape, compile_graph,
                  steps=1, lr=0.0, grad_req="write"):
    """simple_bind + forward(is_train)/backward loop with an optional SGD
    update applied host-side — the same math on both executor paths."""
    kw = {"data": data_shape}
    if "softmax_label" in net.list_arguments():
        kw["softmax_label"] = label_shape
    ex = net.simple_bind(mx.cpu(), grad_req=grad_req,
                         compile_graph=compile_graph, **kw)
    for k, v in vals.items():
        ex.arg_dict[k][:] = v
    outs, grads = None, None
    for _ in range(steps):
        outs = ex.forward(is_train=True)[0].asnumpy()
        ex.backward()
        grads = {k: g.asnumpy() for k, g in ex.grad_dict.items()
                 if g is not None}
        if lr:
            for k, g in ex.grad_dict.items():
                if g is None or k in ("data", "softmax_label"):
                    continue
                ex.arg_dict[k][:] = ex.arg_dict[k].asnumpy() - \
                    lr * g.asnumpy()
    return outs, grads, ex


# ---------------------------------------------------------------------------
# graph passes
# ---------------------------------------------------------------------------
def test_pass_constant_folding_and_dce():
    """All-constant subgraphs evaluate at lower time (with the registry
    fns, so values match eager bit for bit) and their producers die."""
    z = sym.zeros((2, 3))
    one = sym.ones((2, 3))
    a = sym.var("a")
    net = a + (z + one * 2.0)
    ir = compiler.from_symbol(net)
    n_ops_before = ir.n_ops()
    ir, stats = compiler.run_pipeline(ir)
    assert stats["folded"] >= 2, stats
    assert stats["dce_removed"] >= 2, stats
    assert ir.n_ops() < n_ops_before
    # parity through the executor
    x = np.arange(6, dtype="float32").reshape(2, 3)
    ex = net.bind(mx.cpu(), {"a": nd.array(x)}, compile_graph=True)
    out = ex.forward()[0].asnumpy()
    np.testing.assert_array_equal(out, x + 2.0)


def test_pass_cse_merges_duplicate_subgraphs():
    a = sym.var("a")
    b = sym.var("b")
    p1 = a * b          # two structurally identical products built
    p2 = a * b          # independently — one must survive
    net = p1 + p2
    ir = compiler.from_symbol(net)
    ir, stats = compiler.run_pipeline(ir)
    assert stats["cse_merged"] == 1, stats
    x, y = np.full((2, 2), 3.0, "float32"), np.full((2, 2), 5.0, "float32")
    ex = net.bind(mx.cpu(), {"a": nd.array(x), "b": nd.array(y)},
                  compile_graph=True)
    np.testing.assert_array_equal(ex.forward()[0].asnumpy(), 2 * x * y)


def test_unsupported_random_op_reason():
    data = sym.var("data")
    net = sym.Dropout(data, name="drop", p=0.5)
    with pytest.raises(compiler.UnsupportedGraphError) as ei:
        compiler.from_symbol(net)
    assert ei.value.reason == "random_op:Dropout"


def test_graph_hash_value_exact_for_constants():
    """Two graphs differing ONLY in a folded constant's value must not
    collide — constants are baked into the emitted program, so a
    shape/dtype-only hash would hand the second graph the FIRST one's
    compiled program (wrong numerics) through the memo/AOT key."""
    a = sym.var("a")
    net2 = a * (sym.ones((4,)) * 2.0)
    net3 = a * (sym.ones((4,)) * 3.0)
    ir2, _ = compiler.run_pipeline(compiler.from_symbol(net2))
    ir3, _ = compiler.run_pipeline(compiler.from_symbol(net3))
    assert compiler.graph_hash(ir2) != compiler.graph_hash(ir3)
    outs = []
    for net in (net2, net3):
        ex = net.bind(mx.cpu(), {"a": nd.ones((4,))}, compile_graph=True)
        outs.append(ex.forward()[0].asnumpy())
    np.testing.assert_array_equal(outs[0], np.full(4, 2.0))
    np.testing.assert_array_equal(outs[1], np.full(4, 3.0))


def test_graph_hash_stable_and_distinct():
    ir1, _ = compiler.run_pipeline(compiler.from_symbol(_mlp_symbol()))
    ir2, _ = compiler.run_pipeline(compiler.from_symbol(_mlp_symbol()))
    ir3, _ = compiler.run_pipeline(compiler.from_symbol(
        _resnetish_symbol(1)))
    assert compiler.graph_hash(ir1) == compiler.graph_hash(ir2)
    assert compiler.graph_hash(ir1) != compiler.graph_hash(ir3)


# ---------------------------------------------------------------------------
# executor parity (the tentpole)
# ---------------------------------------------------------------------------
def test_mlp_forward_backward_bitexact_one_program():
    net = _mlp_symbol()
    vals = _feed_values(net, (4, 5))
    o_wg, g_wg, ex = _bind_and_run(net, vals, (4, 5), (4,), True)
    assert _counters().get("compiler.compile") == 1
    # post-bind steady state: NO per-op dispatch — the invoke counter
    # must not move across another forward+backward
    before = _counters().get("ndarray.invoke", 0)
    ex.forward(is_train=True)
    ex.backward()
    assert _counters().get("ndarray.invoke", 0) == before
    assert _counters().get("compiler.compile") == 1, \
        "second forward must reuse the ONE compiled program"
    o_ref, g_ref, _ = _bind_and_run(net, vals, (4, 5), (4,), False)
    np.testing.assert_array_equal(o_wg, o_ref)
    assert sorted(g_wg) == sorted(g_ref)
    for k in g_ref:
        np.testing.assert_array_equal(g_wg[k], g_ref[k],
                                      err_msg="grad %s" % k)


def test_resnet_scale_module_fit_parity():
    """The acceptance graph: conv/BN/residual topology through a short
    fit loop. Forward outputs are bit-identical; the whole-graph
    backward (one fused vjp program) may reassociate conv-backward
    low bits vs the chained per-op vjp, so grads and the fitted params
    assert at tight tolerance."""
    net = _resnetish_symbol()
    vals = _feed_values(net, (2, 3, 8, 8), seed=7)
    o_wg, g_wg, _ = _bind_and_run(net, vals, (2, 3, 8, 8), (2,), True,
                                  steps=3, lr=0.05)
    assert _counters().get("compiler.compile") == 1, \
        "resnet-scale fit must run as exactly ONE compiled program"
    o_ref, g_ref, _ = _bind_and_run(net, vals, (2, 3, 8, 8), (2,), False,
                                    steps=3, lr=0.05)
    np.testing.assert_allclose(o_wg, o_ref, rtol=2e-5, atol=1e-6)
    for k in g_ref:
        np.testing.assert_allclose(g_wg[k], g_ref[k], rtol=2e-4,
                                   atol=1e-6, err_msg="grad %s" % k)


def test_resnet_forward_outputs_identical():
    """Inference outputs of the conv graph match op-by-op dispatch to
    within one float32 ulp (XLA fuses the conv+BN+relu chain differently
    under whole-graph jit — the same deviation class `hybridize` accepts;
    the dense graph in test_mlp_forward_backward_bitexact_one_program
    IS bit-identical)."""
    net = _resnetish_symbol()
    vals = _feed_values(net, (2, 3, 8, 8), seed=11)
    kw = {"data": (2, 3, 8, 8), "softmax_label": (2,)}
    outs = {}
    for cg in (True, False):
        ex = net.simple_bind(mx.cpu(), grad_req="null", compile_graph=cg,
                             **kw)
        for k, v in vals.items():
            ex.arg_dict[k][:] = v
        outs[cg] = ex.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(outs[True], outs[False], rtol=2e-7,
                               atol=1e-7)


def test_module_fit_one_program_and_score():
    """Module.fit rides the whole-graph program transparently (the
    Module-level wiring) and still learns."""
    mx.random.seed(0)
    np.random.seed(0)
    x = np.random.normal(size=(96, 8)).astype("float32")
    w = np.random.normal(size=(8, 3)).astype("float32")
    y = np.argmax(x @ w, axis=1).astype("float32")
    it = NDArrayIter(x, y, batch_size=16, shuffle=True,
                     label_name="softmax_label")
    mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu(),
                        label_names=("softmax_label",), compile_graph=True)
    mod.fit(it, num_epoch=5, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5})
    assert mod.score(it, "acc")[0][1] > 0.6
    c = _counters()
    assert c.get("compiler.fallback", 0) == 0
    # fit compiles the fwd+bwd program; predict/score adds the pure
    # forward — 2 executables TOTAL, not 2 per batch
    assert c.get("compiler.compile") == 2, c.get("compiler.compile")


def test_module_multi_device_shares_one_program():
    """Two data-parallel executors with equal batch slices share ONE
    compiled program through the process memo."""
    n_dev = 2
    x = np.random.RandomState(0).normal(size=(32, 8)).astype("float32")
    y = np.zeros(32, "float32")
    it = NDArrayIter(x, y, batch_size=16, label_name="softmax_label")
    mod = mx.mod.Module(_mlp_symbol(),
                        context=[mx.cpu(i) for i in range(n_dev)],
                        label_names=("softmax_label",), compile_graph=True)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    mod.backward()
    assert _counters().get("compiler.compile") == 1


def test_grad_req_add_accumulates():
    net = _mlp_symbol()
    vals = _feed_values(net, (4, 5), seed=3)
    _, g1, ex = _bind_and_run(net, vals, (4, 5), (4,), True,
                              grad_req="add")
    ex.forward(is_train=True)
    ex.backward()
    g2 = ex.grad_dict["fc1_weight"].asnumpy()
    np.testing.assert_allclose(g2, 2 * g1["fc1_weight"], rtol=1e-6)


def test_backward_with_out_grads_parity():
    a = sym.var("a")
    net = a * 3.0 + 1.0
    cot = np.arange(6, dtype="float32").reshape(2, 3)

    def run(cg):
        ex = net.bind(mx.cpu(), {"a": nd.ones((2, 3))},
                      {"a": nd.zeros((2, 3))}, compile_graph=cg)
        ex.forward(is_train=True)
        ex.backward(out_grads=nd.array(cot))
        return ex.grad_dict["a"].asnumpy()
    np.testing.assert_array_equal(run(True), run(False))


def test_random_graph_falls_back_counted_never_errors():
    data = sym.var("data")
    net = sym.Dropout(data, name="drop", p=0.0)
    ex = net.bind(mx.cpu(), {"data": nd.ones((2, 2))}, compile_graph=True)
    out = ex.forward(is_train=False)[0]
    np.testing.assert_array_equal(out.asnumpy(), np.ones((2, 2)))
    c = _counters()
    assert c.get("compiler.fallback") == 1
    assert c.get("compiler.fallback.random_op:Dropout") == 1
    # pinned: the next forward goes straight op-by-op, no re-attempt
    ex.forward(is_train=False)
    assert _counters().get("compiler.fallback") == 1


def test_gate_off_keeps_op_by_op(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WHOLE_GRAPH", "0")
    net = _mlp_symbol()
    vals = _feed_values(net, (4, 5))
    o, _, _ = _bind_and_run(net, vals, (4, 5), (4,), None)
    assert o.shape == (4, 3)
    assert _counters().get("compiler.lower", 0) == 0


# ---------------------------------------------------------------------------
# AOT cache robustness (satellite)
# ---------------------------------------------------------------------------
def _toy_compiled(mult=2.0):
    f = jax.jit(lambda x: x * mult + 1)
    return f.lower(jax.ShapeDtypeStruct((4,), jnp.float32)).compile()


def test_cache_roundtrip(tmp_path):
    cache = AOTCache(str(tmp_path), keep=8)
    key = cache_key(kind="test", prog="toy")
    assert cache.load(key) is None
    assert _counters().get("compiler.cache.misses") == 1
    assert cache.store(key, _toy_compiled())
    out = cache.load(key)(np.ones(4, np.float32))
    np.testing.assert_array_equal(np.asarray(out), np.full(4, 3.0))
    c = _counters()
    assert c.get("compiler.cache.hits") == 1
    assert c.get("compiler.cache.writes") == 1


@pytest.mark.parametrize("ids", [[0], [3], [7, 6, 5, 4]],
                         ids=["device0", "device3", "mesh_of_4"])
def test_cache_restores_onto_the_devices_it_was_compiled_for(tmp_path, ids):
    """The process has 8 devices and the program fewer: the restored
    executable runs on the devices, in the order, it was compiled for
    (not on device 0, not over all 8) and answers as the fresh one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    by_id = {d.id: d for d in jax.devices()}
    devices = [by_id[i] for i in ids]
    where = (NamedSharding(Mesh(np.array(devices), ("d",)), P("d"))
             if len(devices) > 1 else devices[0])
    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), where)
    fresh = jax.jit(lambda x: x * 2 + jnp.sum(x)).lower(x).compile()
    cache = AOTCache(str(tmp_path), keep=8)
    key = cache_key(kind="test", prog="placed", ids=ids)
    assert cache.store(key, fresh)
    restored = cache.load(key)
    assert _counters().get("compiler.cache.hits") == 1
    out = restored(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(fresh(x)))
    assert out.sharding.device_set == set(devices)
    assert [s.device.id for s in out.addressable_shards] == \
        [s.device.id for s in fresh(x).addressable_shards]


@pytest.mark.parametrize("meta_ids", [[0, 99], None],
                         ids=["a_device_this_process_lacks", "none_recorded"])
def test_cache_entry_for_other_devices_is_counted_miss(tmp_path, monkeypatch,
                                                       meta_ids):
    """An entry whose devices this process cannot name (another host's
    ids, or an entry from before ids were recorded) is a miss and a
    compile, not a corrupt entry and not an error."""
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    key = cache_key(kind="test", prog="elsewhere", ids=meta_ids)
    lower = lambda: jax.jit(lambda x: x + 1).lower(  # noqa: E731
        jax.ShapeDtypeStruct((4,), jnp.float32))
    _, restored = cache_mod.load_or_compile(key, lower, "elsewhere")
    assert not restored
    fname = os.path.join(str(tmp_path), key + ".aotx")
    head = len(cache_mod._MAGIC) + 64
    meta, *rest = pickle.loads(open(fname, "rb").read()[head:])
    assert meta.pop("device_ids") == [jax.devices()[0].id]
    if meta_ids is not None:
        meta["device_ids"] = meta_ids
    payload = pickle.dumps((meta, *rest))
    with open(fname, "wb") as f:
        f.write(cache_mod._MAGIC
                + cache_mod.hashlib.sha256(payload).hexdigest().encode()
                + payload)
    telemetry.reset()
    ex, restored = cache_mod.load_or_compile(key, lower, "elsewhere")
    assert not restored
    c = _counters()
    assert c.get("compiler.cache.misses") == 1
    assert c.get("compiler.cache.corrupt", 0) == 0
    assert c.get("compiler.cache.writes") == 1      # compiled and healed
    np.testing.assert_array_equal(
        np.asarray(ex(np.zeros(4, np.float32))), np.ones(4))
    _, restored = cache_mod.load_or_compile(key, lower, "elsewhere")
    assert restored


@pytest.mark.parametrize("how", ["truncate", "garbage", "bad_magic",
                                 "flip_payload"])
def test_cache_corrupt_entry_is_counted_miss(tmp_path, how):
    cache = AOTCache(str(tmp_path), keep=8)
    key = cache_key(kind="test", prog="corrupt", how=how)
    assert cache.store(key, _toy_compiled())
    fname = os.path.join(str(tmp_path), key + ".aotx")
    blob = open(fname, "rb").read()
    if how == "truncate":
        blob = blob[:len(blob) // 2]
    elif how == "garbage":
        blob = b"not an executable at all"
    elif how == "bad_magic":
        blob = b"XXXXXX\n" + blob[7:]
    else:  # flip_payload: valid magic, digest now wrong
        blob = blob[:-8] + bytes(8)
    with open(fname, "wb") as f:
        f.write(blob)
    assert cache.load(key) is None
    c = _counters()
    assert c.get("compiler.cache.corrupt") == 1
    assert c.get("compiler.cache.misses") == 1
    # recompile + overwrite heals the entry
    assert cache.store(key, _toy_compiled())
    assert cache.load(key) is not None


def test_cache_version_mismatch_is_miss(tmp_path, monkeypatch):
    cache = AOTCache(str(tmp_path), keep=8)
    key = cache_key(kind="test", prog="versioned")
    assert cache.store(key, _toy_compiled())
    # a worker on a different compiler stack derives a DIFFERENT key for
    # the same program — never loads this entry
    monkeypatch.setattr(cache_mod, "_versions",
                        lambda: {"jax": "999.0", "jaxlib": "999.0",
                                 "mxnet_tpu": "x", "platform": "cpu",
                                 "device_count": 1})
    key2 = cache_key(kind="test", prog="versioned")
    assert key2 != key
    assert cache.load(key2) is None
    assert _counters().get("compiler.cache.misses") == 1


def test_cache_concurrent_writers_last_write_wins(tmp_path):
    cache = AOTCache(str(tmp_path), keep=8)
    key = cache_key(kind="test", prog="race")
    compiled = [_toy_compiled(m) for m in (2.0, 3.0, 4.0, 5.0)]
    errs = []

    def writer(c):
        try:
            for _ in range(5):
                cache.store(key, c)
        except Exception as e:  # noqa: BLE001 - the assertion target
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(c,))
               for c in compiled]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    files = [f for f in os.listdir(str(tmp_path)) if f.endswith(".aotx")]
    assert files == [key + ".aotx"], files  # no temp debris, ONE entry
    out = cache.load(key)(np.ones(4, np.float32))
    # whichever writer won, the entry is a complete valid executable
    assert float(np.asarray(out)[0]) in (3.0, 4.0, 5.0, 6.0)


def test_cache_keep_n_eviction_oldest_first(tmp_path):
    cache = AOTCache(str(tmp_path), keep=3)
    keys = [cache_key(kind="test", prog="evict", i=i) for i in range(5)]
    for i, key in enumerate(keys):
        assert cache.store(key, _toy_compiled())
        # force a strictly increasing mtime order
        os.utime(os.path.join(str(tmp_path), key + ".aotx"),
                 (1000 + i, 1000 + i))
        cache._evict()
    left = sorted(f for f in os.listdir(str(tmp_path))
                  if f.endswith(".aotx"))
    assert left == sorted(k + ".aotx" for k in keys[2:]), left
    assert _counters().get("compiler.cache.evictions") == 2


def test_executor_recompiles_after_truncated_entry(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    net = _mlp_symbol()
    vals = _feed_values(net, (4, 5))
    o1, _, _ = _bind_and_run(net, vals, (4, 5), (4,), True)
    entries = [f for f in os.listdir(str(tmp_path)) if f.endswith(".aotx")]
    assert entries, "executor program must land in the cache"
    for f in entries:
        full = os.path.join(str(tmp_path), f)
        open(full, "wb").write(open(full, "rb").read()[:100])
    lower_mod._MEMO.clear()
    telemetry.reset()
    o2, _, _ = _bind_and_run(net, vals, (4, 5), (4,), True)
    np.testing.assert_array_equal(o1, o2)
    c = _counters()
    assert c.get("compiler.cache.corrupt", 0) >= 1
    assert c.get("compiler.compile") == 1  # recompiled, did not crash


def test_executor_program_restores_across_instances(tmp_path, monkeypatch):
    """The in-process stand-in for the two-process BENCH=startup row: a
    second executor build (fresh memo = fresh 'process') restores the
    compiled program from the warm cache with zero fresh compiles."""
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    net = _mlp_symbol()
    vals = _feed_values(net, (4, 5))
    o1, g1, _ = _bind_and_run(net, vals, (4, 5), (4,), True)
    assert _counters().get("compiler.compile") == 1
    lower_mod._MEMO.clear()
    telemetry.reset()
    o2, g2, _ = _bind_and_run(net, vals, (4, 5), (4,), True)
    c = _counters()
    assert c.get("compiler.compile", 0) == 0, "warm start must not compile"
    assert c.get("compiler.cache.hits") == 1
    np.testing.assert_array_equal(o1, o2)
    for k in g1:
        np.testing.assert_array_equal(g1[k], g2[k])
    ring = [name for name, _ in telemetry.recent_compiles()]
    assert any("[cached]" in name for name in ring), ring


# ---------------------------------------------------------------------------
# serve + train-step programs ride the same cache
# ---------------------------------------------------------------------------
@pytest.mark.serve
def test_serve_warmup_restores_from_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    from mxnet_tpu.models.llama import LlamaConfig, llama_init
    from mxnet_tpu.serve.kv_cache import KVBlockPool
    from mxnet_tpu.serve.programs import ServePrograms
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=64, rope_theta=10000.0,
                      max_seq_len=32, dtype=jnp.float32)
    params = llama_init(jax.random.PRNGKey(0), cfg)

    def build():
        pool = KVBlockPool(cfg, num_blocks=16, block_size=8)
        sp = ServePrograms(params, cfg, pool, max_batch=2, max_context=16,
                           chunk_size=8, prefill_rows=2)
        sp.warmup()
        return sp

    def chunk_once(sp):
        import numpy as np
        prompt = [5, 6, 7]
        tokens = np.zeros((2, 8), np.int32)
        positions = np.full((2, 8), -1, np.int32)
        tokens[0, :3] = prompt
        positions[0, :3] = [0, 1, 2]
        tables = np.full((2, sp.blocks_per_stream), sp.pool.num_blocks,
                         np.int32)
        tables[0, 0] = 0
        return int(sp.chunk_prefill(
            tokens, positions, tables, np.zeros(2, np.uint32),
            np.asarray([3, 0], np.int32), np.zeros(2, np.float32),
            np.zeros(2, np.int32), np.ones(2, np.float32))[0])

    sp1 = build()
    n_exec = len(sp1.program_names)
    assert _counters().get("serve.compile") == n_exec
    tok1 = chunk_once(sp1)
    telemetry.reset()
    sp2 = build()
    c = _counters()
    assert c.get("serve.compile", 0) == 0, \
        "warm warmup must restore every executable"
    assert c.get("compiler.cache.hits") == n_exec
    assert tok1 == chunk_once(sp2)
    ring = [name for name, _ in telemetry.recent_compiles()]
    assert all("[cached]" in name for name in ring), ring


def test_sharded_train_step_cache_restore(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    from jax.sharding import Mesh

    from mxnet_tpu.parallel.sharding import ShardingRules
    from mxnet_tpu.parallel.train_step import ShardedTrainStep
    mesh = Mesh(np.array(jax.devices("cpu")[:2]).reshape(2), ("data",))

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    batch = {"x": jnp.ones((8, 4)), "y": jnp.zeros((8, 2))}

    def round_():
        step = ShardedTrainStep(loss_fn,
                                {"w": jnp.ones((4, 2), jnp.float32)},
                                mesh, rules=ShardingRules([]), lr=0.1)
        p, s = step.init()
        losses = []
        for i in range(3):
            p, s, l = step(p, s, batch, i)
            losses.append(float(l))
        return losses

    l1 = round_()
    assert _counters().get("compiler.cache.writes") == 1
    telemetry.reset()
    l2 = round_()
    c = _counters()
    assert c.get("train_step.aot_restored") == 1
    assert c.get("compiler.cache.hits") == 1
    assert l1 == l2  # restored executable is bit-identical


@pytest.mark.parametrize("data", [4, 8])
def test_sharded_train_step_restores_over_create_mesh(tmp_path, monkeypatch,
                                                      data):
    """The four-chip cell's shape: `create_mesh(data=4)` in a process with
    more devices than the mesh (and, for contrast, a mesh over all of
    them) restores its step from MXNET_TPU_AOT_CACHE and trains on as the
    compiled one did."""
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel.sharding import ShardingRules
    from mxnet_tpu.parallel.train_step import ShardedTrainStep
    mesh = create_mesh(data=data)

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    batch = {"x": jnp.ones((8, 4)), "y": jnp.zeros((8, 2))}

    def round_():
        step = ShardedTrainStep(loss_fn,
                                {"w": jnp.ones((4, 2), jnp.float32)},
                                mesh, rules=ShardingRules([]), lr=0.1)
        p, s = step.init()
        losses = []
        for i in range(3):
            p, s, l = step(p, s, batch, i)
            losses.append(float(l))
        assert p["w"].sharding.device_set == set(mesh.devices.flat)
        return losses

    l1 = round_()
    assert _counters().get("compiler.cache.writes") == 1
    telemetry.reset()
    l2 = round_()
    c = _counters()
    assert c.get("train_step.aot_restored") == 1
    assert c.get("compiler.cache.hits") == 1
    assert l1 == l2


def test_fused_step_cache_donation_policy(tmp_path, monkeypatch):
    """donate=False rides the cache (restore is bit-identical);
    donate=True (default) skips it with a counted reason — a deserialized
    donating fused-step program corrupts XLA:CPU (2026-08-04)."""
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    def round_(donate):
        mx.random.seed(7)
        net = nn.Sequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
        net.initialize()
        rng = np.random.RandomState(0)
        x = nd.array(rng.normal(size=(8, 5)).astype("float32"))
        y = nd.array(rng.randint(0, 3, (8,)).astype("float32"))
        net(x)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        fused = gluon.FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), tr, donate=donate)
        return [float(fused(x, y).asnumpy()) for _ in range(3)]

    l1 = round_(False)
    assert _counters().get("compiler.cache.writes") == 1
    telemetry.reset()
    l2 = round_(False)
    c = _counters()
    assert c.get("fused_step.aot_restored") == 1
    assert l1 == l2
    telemetry.reset()
    round_(True)
    c = _counters()
    assert c.get("compiler.cache.skipped_donated") == 1
    assert c.get("fused_step.aot_restored", 0) == 0


# ---------------------------------------------------------------------------
# tooling satellites
# ---------------------------------------------------------------------------
def test_parse_log_compile_table(tmp_path):
    net = _mlp_symbol()
    vals = _feed_values(net, (4, 5))
    _bind_and_run(net, vals, (4, 5), (4,), True)
    report = telemetry.compile_report()
    path = str(tmp_path / "compile.json")
    with open(path, "w") as f:
        json.dump(report, f)
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    out = subprocess.run(
        [sys.executable, os.path.join(tools, "parse_log.py"), path,
         "--compile"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "| compiler | compile | 1 |" in out.stdout, out.stdout
    assert "lower_ms" in out.stdout
    assert "compiler:" in out.stdout  # the recent-compiles ring rows


def test_large_tensor_scope_shim():
    """The x64 probe/shim (satellite): int64 survives inside the scope on
    every jax that ships either spelling of enable_x64."""
    with mx.util.large_tensor_scope():
        a = jnp.asarray([2 ** 40], dtype="int64")
        assert str(a.dtype) == "int64"
        assert int(a[0]) == 2 ** 40


@pytest.mark.lint
def test_compiler_package_lint_clean_zero_suppressions():
    """mxnet_tpu/compiler/ must be tracelint-clean with ZERO suppression
    comments (ISSUE 11 CI satellite)."""
    import mxnet_tpu.analysis as analysis
    comp_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mxnet_tpu", "compiler")
    findings = analysis.check(comp_dir)
    assert findings == [], "\n".join(str(f) for f in findings)
    for name in os.listdir(comp_dir):
        if name.endswith(".py"):
            with open(os.path.join(comp_dir, name)) as f:
                assert "tpu-lint" not in f.read(), (
                    "suppression found in %s" % name)

"""Tests for mxnet_tpu.parallel on the 8-device CPU mesh (conftest forces
JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8 — the SURVEY.md §4
local-launcher analog for distributed tests without a cluster)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from mxnet_tpu import parallel as par


def test_mesh_creation():
    mesh = par.create_mesh(data=4, model=2)
    assert mesh.devices.size == 8
    assert dict(zip(mesh.axis_names, mesh.devices.shape))["data"] == 4
    assert par.current_mesh() is None
    with par.mesh_scope(mesh) as m:
        assert par.current_mesh() is m
    assert par.current_mesh() is None


def test_local_and_auto_mesh():
    m = par.local_mesh(4)
    assert m.devices.size == 4
    m2 = par.auto_mesh(model_parallel=2)
    sizes = dict(zip(m2.axis_names, m2.devices.shape))
    assert sizes["model"] == 2 and sizes["data"] == 4


def test_sharding_rules_prune():
    mesh = par.create_mesh(data=8)  # no real model axis
    spec = par.LLAMA_RULES.spec_for("layers/0/attn/wq", (256, 512), mesh)
    # model axis has size 1 → pruned; fsdp size 1 → pruned
    assert spec == P()
    mesh2 = par.create_mesh(data=2, model=4)
    spec2 = par.LLAMA_RULES.spec_for("layers/0/attn/wq", (256, 512), mesh2)
    assert spec2 == P(None, "model")
    # non-divisible dim drops the axis rather than erroring
    spec3 = par.LLAMA_RULES.spec_for("layers/0/attn/wq", (256, 510), mesh2)
    assert spec3 == P()


def test_shard_pytree_places_params():
    mesh = par.create_mesh(data=2, model=4)
    params = {"layers": {"0": {"attn": {"wq": jnp.ones((16, 8)),
                                        "wo": jnp.ones((8, 16))}}},
              "norm": jnp.ones((16,))}
    sharded = par.shard_pytree(params, par.LLAMA_RULES, mesh)
    wq = sharded["layers"]["0"]["attn"]["wq"]
    assert wq.sharding.spec == P(None, "model")
    assert sharded["norm"].sharding.spec == P()


def test_collectives_inside_shard_map():
    mesh = par.local_mesh(8, axis="data")
    x = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P("data")))

    def f(t):
        s = par.all_reduce(t, "data")
        g = par.all_gather(t, "data")
        return s, g

    sfn = shard_map(f, mesh=mesh, in_specs=P("data"),
                    out_specs=(P(), P("data")))
    s, g = jax.jit(sfn)(x)
    assert float(s[0]) == float(jnp.sum(jnp.arange(8.0)))
    np.testing.assert_allclose(np.asarray(g)[:8], np.arange(8.0))


def test_barrier_and_bench_smoke():
    mesh = par.local_mesh(8)
    par.barrier(mesh)
    gbps, dt = par.allreduce_bench(size_mb=1, iters=2, mesh=mesh)
    assert gbps > 0 and dt > 0


def test_dist_single_process():
    par.initialize()
    assert par.is_initialized()
    assert par.rank() == 0
    assert par.num_workers() == 1


def _np_attention(q, k, v, causal=False):
    H, Hkv = q.shape[1], k.shape[1]
    if Hkv != H:
        k = np.repeat(k, H // Hkv, axis=1)
        v = np.repeat(v, H // Hkv, axis=1)
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bhqd,bhkd->bhqk", q.astype(np.float64),
                  k.astype(np.float64)) * scale
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        qi = np.arange(Sq)[:, None] + (Sk - Sq)
        ki = np.arange(Sk)[None, :]
        s = np.where(ki <= qi, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v.astype(np.float64))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_fallback(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 4, 64, 32).astype(np.float32)
    k = rng.randn(2, 2, 64, 32).astype(np.float32)  # GQA 2 kv heads
    v = rng.randn(2, 2, 64, 32).astype(np.float32)
    out = par.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal)
    ref = _np_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_flash_attention_grad():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 16, 8).astype(np.float32))

    def loss(q, k, v):
        return jnp.sum(par.flash_attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def ref_loss(q, k, v):
        from mxnet_tpu.parallel.flash_attention import _ref_attention
        return jnp.sum(_ref_attention(q, k, v, True, 8 ** -0.5) ** 2)

    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = par.local_mesh(4, axis="seq")
    rng = np.random.RandomState(2)
    B, H, S, D = 2, 2, 32, 16
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)

    f = shard_map(
        lambda q_, k_, v_: par.ring_attention(q_, k_, v_, axis_name="seq",
                                              causal=causal),
        mesh=mesh, in_specs=P(None, None, "seq", None),
        out_specs=P(None, None, "seq", None))
    out = jax.jit(f)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref = _np_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gqa_matches_full(causal):
    """Hkv < H: the grouped-einsum GQA path (no K/V repeat on the ring)."""
    mesh = par.local_mesh(4, axis="seq")
    rng = np.random.RandomState(7)
    B, H, Hkv, S, D = 2, 4, 2, 32, 16
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, Hkv, S, D).astype(np.float32)
    v = rng.randn(B, Hkv, S, D).astype(np.float32)

    f = shard_map(
        lambda q_, k_, v_: par.ring_attention(q_, k_, v_, axis_name="seq",
                                              causal=causal),
        mesh=mesh, in_specs=P(None, None, "seq", None),
        out_specs=P(None, None, "seq", None))
    out = jax.jit(f)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref = _np_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_sharded_train_step_linear_regression():
    mesh = par.create_mesh(data=2, model=4)
    rng = np.random.RandomState(3)
    w_true = rng.randn(8, 4).astype(np.float32)
    params = {"mlp": {"w1": jnp.zeros((8, 4))}}  # matched by LLAMA mlp rule

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["mlp"]["w1"]
        return jnp.mean((pred - y) ** 2)

    step = par.ShardedTrainStep(loss_fn, params, mesh,
                                rules=par.LLAMA_RULES, optimizer="adam",
                                lr=0.1)
    p, s = step.init()
    assert p["mlp"]["w1"].sharding.spec == P(None, "model")
    losses = []
    for i in range(60):
        x = rng.randn(16, 8).astype(np.float32)
        y = x @ w_true
        p, s, loss = step(p, s, (jnp.asarray(x), jnp.asarray(y)), i)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.05


def test_sharded_train_step_grad_accum():
    mesh = par.local_mesh(2, axis="data")
    params = {"w": jnp.zeros((4,))}

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"][:, None] - y) ** 2)

    step = par.ShardedTrainStep(loss_fn, params, mesh, optimizer="sgd",
                                lr=0.05, grad_accum=2, momentum=0.9)
    p, s = step.init()
    rng = np.random.RandomState(4)
    w_true = rng.randn(4).astype(np.float32)
    for i in range(150):
        x = rng.randn(8, 4).astype(np.float32)
        y = (x @ w_true)[:, None]
        p, s, loss = step(p, s, (jnp.asarray(x), jnp.asarray(y)), i)
    np.testing.assert_allclose(np.asarray(p["w"]), w_true, atol=0.05)


# ---------------------------------------------------------------------------
# elastic place() buffer donation (resilience-v2 follow-on: grow-back
# re-layout must peak at max(old, new) + one leaf, not old + new)
# ---------------------------------------------------------------------------
def test_reshard_pytree_donate_deletes_sources():
    from mxnet_tpu.parallel.sharding import LLAMA_RULES, reshard_pytree
    mesh = par.local_mesh(4, axis="data")
    params = {"layers": {"0": {"mlp": {"w1": jnp.ones((8, 16))}}},
              "norm": jnp.arange(8.0)}
    sources = jax.tree_util.tree_leaves(params)
    expect = [np.asarray(x) for x in sources]
    out = reshard_pytree(params, LLAMA_RULES, mesh, donate=True)
    assert all(x.is_deleted() for x in sources)
    for got, want in zip(jax.tree_util.tree_leaves(out), expect):
        np.testing.assert_array_equal(np.asarray(got), want)
    # default stays non-destructive
    params2 = {"w": jnp.arange(6.0)}
    src2 = jax.tree_util.tree_leaves(params2)
    reshard_pytree(params2, LLAMA_RULES, mesh)
    assert not any(x.is_deleted() for x in src2)


def test_place_donates_and_step_continues():
    """place() consumes its inputs by default (the relayout adapters drop
    them immediately); the re-laid state must be bit-identical and the
    rebuilt step must run on it."""
    mesh = par.local_mesh(2, axis="data")

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    step = par.ShardedTrainStep(loss_fn, {"w": jnp.ones((4,))}, mesh,
                                optimizer="adam", lr=0.01)
    p, s = step.init()
    p, s, _ = step(p, s, jnp.ones((4, 4)), 0)
    expect_w = np.asarray(p["w"])
    old_leaves = jax.tree_util.tree_leaves((p, s))
    rebuilt = step.rebuild_for_mesh(par.local_mesh(4, axis="data"))
    p2, s2 = rebuilt.place(p, s)
    assert all(x.is_deleted() for x in old_leaves)
    np.testing.assert_array_equal(np.asarray(p2["w"]), expect_w)
    # optimizer-state scalars survived the donated move
    assert int(s2["t"]) == 1
    p3, s3, loss = rebuilt(p2, s2, jnp.ones((8, 4)), 1)
    assert np.isfinite(float(loss))
    # opt-out keeps sources alive (A/B comparisons)
    keep = jax.tree_util.tree_leaves((p3, s3))
    rebuilt.place(p3, s3, donate=False)
    assert not any(x.is_deleted() for x in keep)

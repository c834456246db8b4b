"""`models.qwen3_next` against the benchmark's plain reference: the loss and
every leaf's gradient on seeded weights, through `parallel.ShardedTrainStep`
with AdamW, at a toy size that keeps the period of four (three Gated
DeltaNet layers, one gated attention layer, a mixture in each). Float32 on
both sides, kernels interpreted."""
import os
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import models
from mxnet_tpu.parallel import ShardedTrainStep, create_mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from programs import qwen3_next as program  # noqa: E402
from reference import qwen3_next as reference  # noqa: E402

pytestmark = pytest.mark.pallas     # the kernels run interpreted here

CFG = {
    "vocab_size": 96, "hidden_size": 64, "n_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 1e7, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "chunk": 16,
    "n_experts_published": 16, "n_experts": 4, "first_expert": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "moe_rows_bound": None,
    "rms_norm_eps": 1e-6, "dtype": "float32",
    "param_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 1e-4, "wd": 0.01,
                  "beta1": 0.9, "beta2": 0.999, "eps": 1e-8},
}
BETA1 = CFG["optimizer"]["beta1"]


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def seeded():
    # seed 24: the least router gap of the 320 is 1.9e-4 (of seeds 20 to
    # 39 five clear 1e-4)
    key = jax.random.PRNGKey(24)

    @jax.jit
    def make(key):
        # matrices five times the initialisation's, so that every path
        # carries signal; norm weights and the decays' leaves moved off
        # their starting points
        flat = reference.init_params(key, CFG)
        for i, name in enumerate(sorted(flat)):
            if flat[name].ndim > 1:
                flat[name] = flat[name] * 5
            else:
                flat[name] = flat[name] + 0.1 * jax.random.normal(
                    jax.random.fold_in(key, i), flat[name].shape)
        return flat
    flat = make(key)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(12), (2, 40), 0,
                                          CFG["vocab_size"])}
    return flat, batch


def _tree(flat):
    tree = {}
    for name, value in flat.items():
        node = tree
        *path, last = name.split(".")
        for part in path:
            node = node.setdefault(part, {})
        node[last] = value
    return tree


@jax.jit
def _router_gaps(flat, batch):
    """The least gap between the last chosen and the first unchosen router
    weight, over every token of every layer, on the reference's forward."""
    x = flat["embed"][batch["tokens"]]
    gaps = []
    for i in range(CFG["n_layers"]):
        pre = "layers.%d." % i
        p = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
        attention = reference._is_attention(CFG, i)
        mixer = reference._attention if attention else reference._delta_net
        sub = {k.split(".", 1)[1]: v for k, v in p.items()
               if k.startswith(("attn.", "gdn."))}
        h = x + mixer(reference._norm(x, p["mixer_norm.w"], 1e-6), sub, CFG,
                      "f32")
        y = reference._norm(h, p["moe_norm.w"], 1e-6)
        probs = jax.nn.softmax(y @ p["moe.router"], -1)
        top = jax.lax.top_k(probs, CFG["num_experts_per_tok"] + 1)[0]
        gaps.append(jnp.min(top[..., -2] - top[..., -1]))
        x = reference._layer(x, p, CFG, "f32", attention)
    return jnp.min(jnp.stack(gaps))


def test_loss_and_every_gradient_match_the_reference(seeded):
    flat, batch = seeded
    # a flip of the discrete choice cannot decide the comparison: no token's
    # last chosen and first unchosen router weights lie within 1e-4
    assert float(_router_gaps(flat, batch)) > 1e-4
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss_fn(p, batch, CFG)))(flat)
    # the step donates what it is given: fresh buffers
    step = ShardedTrainStep(program.loss_fn(CFG),
                            _tree(jax.tree_util.tree_map(jnp.copy, flat)),
                            create_mesh(data=1), optimizer="adamw", lr=1e-4,
                            wd=0.01, beta1=BETA1, beta2=0.999, eps=1e-8)
    params, state = step.init()
    params, state, loss = step(params, state, batch)
    # float32 at `highest` on both sides; the two differ in the order of
    # sums (chunks against tokens, sorted rows against a loop over experts):
    # 1e-5 of a loss of 4.6
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    got = {".".join(str(k.key) for k in path): leaf / (1 - BETA1) for
           path, leaf in jax.tree_util.tree_flatten_with_path(state["m"])[0]}
    assert set(got) == set(want) == {n for n, _, _ in reference.leaves(CFG)}
    for name in sorted(want):
        # as above through the backward pass, and AdamW's (1 - beta1)
        # product and its division: 2e-4 of the leaf's largest entry
        scale = float(jnp.max(jnp.abs(want[name])))
        assert scale > 0, name
        gap = float(jnp.max(jnp.abs(got[name] - want[name])))
        assert gap <= 2e-4 * scale, (name, gap, scale)


def test_the_model_is_exported_and_its_tree_is_the_references():
    assert models.qwen3_next_loss is models.qwen3_next.qwen3_next_loss
    cfg = models.Qwen3NextConfig(
        vocab_size=96, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, linear_key_heads=2, linear_value_heads=4,
        linear_key_dim=16, linear_value_dim=16, n_routed_experts=16,
        n_experts=4, experts_per_token=3, expert_dim=32,
        shared_expert_dim=32)
    tree = models.qwen3_next_init(jax.random.PRNGKey(0), cfg)
    mine = {".".join(str(k.key) for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert mine == {n: tuple(s) for n, s, _ in reference.leaves(CFG)}
    assert [cfg.is_attention(i) for i in range(4)] == [False, False, False,
                                                       True]


def test_bfloat16_activations_stay_near_float32(seeded):
    flat, batch = seeded
    loss32 = jax.jit(program.loss_fn(CFG))(_tree(flat), batch)
    loss16 = jax.jit(program.loss_fn(dict(CFG, dtype="bfloat16")))(
        _tree(flat), batch)
    # bfloat16 keeps 8 bits: a loss of 4.6 within a hundredth
    assert abs(float(loss16) - float(loss32)) < 1e-2 * float(loss32)

"""`models.laguna` against the benchmark's plain reference: the loss and every
leaf's gradient on seeded weights, through `parallel.ShardedTrainStep` with
AdamW, at a toy size that keeps the leading dense layer and one whole period
behind it (full attention, then three sliding layers at twice the query
heads, a mixture in each); the YaRN table; and the chip's share tied to the
uncut layer. Float32 on both sides, kernels interpreted."""
import os
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import models
from mxnet_tpu.models import laguna as model
from mxnet_tpu.models.decoder_ops import rms_norm
from mxnet_tpu.parallel import ShardedTrainStep, create_mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from programs import laguna as program  # noqa: E402
from reference import laguna as reference  # noqa: E402

pytestmark = pytest.mark.pallas     # the kernels run interpreted here

ROPE = {"full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
CFG = {
    "vocab_size": 96, "hidden_size": 64, "n_layers": 5,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [8, 16, 16, 16, 8],
    "n_kv_heads_published": 8, "n_kv_heads": 2, "head_dim": 16,
    "sliding_window": 12, "rope_parameters": ROPE, "intermediate_size": 96,
    "n_experts_published": 16, "n_experts": 4, "first_expert": 4,
    "num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "moe_rows_bound": None, "rms_norm_eps": 1e-6, "dtype": "float32",
    "param_dtype": "float32",
    "optimizer": {"name": "adamw", "learning_rate": 1e-4, "wd": 0.01,
                  "beta1": 0.9, "beta2": 0.999, "eps": 1e-8},
}
BETA1 = CFG["optimizer"]["beta1"]


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tree(flat):
    tree = {}
    for name, value in flat.items():
        node = tree
        *path, last = name.split(".")
        for part in path:
            node = node.setdefault(part, {})
        node[last] = value
    return tree


def _seeded(cfg, seed, scale=5.0):
    """Matrices `scale` times the initialisation's, so that every path
    carries signal; norm weights moved off 1."""
    @jax.jit
    def make(key):
        flat = reference.init_params(key, cfg)
        for i, name in enumerate(sorted(flat)):
            if flat[name].ndim > 1:
                flat[name] = flat[name] * scale
            else:
                flat[name] = flat[name] + 0.1 * jax.random.normal(
                    jax.random.fold_in(key, i), flat[name].shape)
        return flat
    return make(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def seeded():
    # seed 49: the least router gap of the 4 x 80 is 5.0e-4 (of seeds 20 to
    # 59 ten clear 1e-4)
    flat = _seeded(CFG, 49)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(12), (2, 40), 0,
                                          CFG["vocab_size"])}
    return flat, batch


@jax.jit
def _router_gaps(flat, batch):
    """The least gap between the last chosen and the first unchosen router
    score, over every token of every expert layer, on the reference's
    forward."""
    x = flat["embed"][batch["tokens"]]
    gaps = []
    for i in range(CFG["n_layers"]):
        p = reference._sub(flat, "layers.%d." % i)
        sliding, dense = reference._is_sliding(CFG, i), reference._is_dense(
            CFG, i)
        if not dense:
            h = reference._attention_block(x, p, CFG, "f32", sliding)
            y = reference._norm(h, p["mlp_norm.w"], 1e-6)
            top = jax.lax.top_k(jax.nn.sigmoid(y @ p["moe.router"]),
                                CFG["num_experts_per_tok"] + 1)[0]
            gaps.append(jnp.min(top[..., -2] - top[..., -1]))
        x = reference._layer(x, p, CFG, "f32", sliding, dense)
    return jnp.min(jnp.stack(gaps))


def test_loss_and_every_gradient_match_the_reference(seeded):
    flat, batch = seeded
    # a flip of the discrete choice cannot decide the comparison: no token's
    # last chosen and first unchosen router scores lie within 1e-4
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
    # sums (key blocks against whole rows, sorted rows against a loop over
    # experts): 1e-5 of a loss of 5
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
    assert models.laguna_loss is model.laguna_loss
    cfg = models.LagunaConfig(
        vocab_size=96, dim=64, n_layers=5,
        layer_types=tuple(CFG["layer_types"]),
        heads_per_layer=(8, 16, 16, 16, 8), n_kv_heads=2, head_dim=16,
        window=12, dense_dim=96, n_routed_experts=16, n_experts=4,
        first_expert=4, experts_per_token=3, expert_dim=32,
        shared_expert_dim=32)
    tree = models.laguna_init(jax.random.PRNGKey(0), cfg)
    mine = {".".join(str(k.key) for k in path): leaf.shape for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert mine == {n: tuple(s) for n, s, _ in reference.leaves(CFG)}
    assert [cfg.is_sliding(i) for i in range(5)] == [False, True, True, True,
                                                     False]
    assert [cfg.heads(i) for i in range(5)] == [2, 4, 4, 4, 2]
    # the published model: 48 | 72 query heads over 8 key-value heads
    whole = models.LagunaConfig()
    assert (whole.heads(0), whole.heads(1), whole.n_layers) == (48, 72, 48)
    assert sum(whole.is_sliding(i) for i in range(48)) == 36


def test_yarn_table_of_the_published_configuration():
    """Full layers: the ramp runs from pair 9 to pair 18 of the 32 that the
    first half of a head has, the table is 5e5^(-t/32) below it and that
    over 128 above it, and cos and sin carry the published factor; sliding
    layers turn all 64 pairs at 1e4^(-t/64). The reference computes the
    same from the configuration's `rope_parameters`."""
    cfg = models.LagunaConfig()
    assert model.yarn_range(cfg) == (9, 18)
    table, factor = model.rope_table(cfg, sliding=False)
    assert table.shape == (32,) and factor == 1.4852030263919618
    pair = onp.arange(32)
    base = 5e5 ** (-pair / 32)
    onp.testing.assert_allclose(table[:10], base[:10], rtol=1e-6)
    onp.testing.assert_allclose(table[18:], base[18:] / 128, rtol=1e-6)
    ramp = (pair[10:18] - 9) / 9
    onp.testing.assert_allclose(
        table[10:18], (1 - ramp) * base[10:18] + ramp * base[10:18] / 128,
        rtol=1e-6)
    sliding, one = model.rope_table(cfg, sliding=True)
    assert sliding.shape == (64,) and one == 1.0
    onp.testing.assert_allclose(sliding, 1e4 ** (-onp.arange(64) / 64),
                                rtol=1e-6)
    published = dict(CFG, head_dim=128)
    assert reference.yarn_range(ROPE["full_attention"], 64) == (9, 18)
    for kind in (False, True):
        theirs, their_factor = reference.rope_table(published, kind)
        onp.testing.assert_allclose(theirs, model.rope_table(cfg, kind)[0],
                                    rtol=1e-6)
        assert their_factor == model.rope_table(cfg, kind)[1]


@pytest.mark.parametrize("layer", [0, 1])
def test_the_shares_add_up_to_the_uncut_layer(layer):
    """A toy layer whole (4 key-value heads with 2 or 4 query heads each, 16
    experts) against its shares: the attention parts of the two head shares
    (2 key-value heads each, their columns of Wq and of the gate, their
    rows of Wo) and the routed sums of the four expert shares, with the
    residual, the shared expert and the dense MLP counted once, are the
    uncut reference's layer; and each share is the reference's share.
    Layer 0 is full attention with the dense MLP, layer 1 sliding with the
    mixture."""
    uncut = dict(CFG, n_kv_heads_published=4, n_kv_heads=4, n_experts=16,
                 first_expert=0, n_layers=2,
                 num_attention_heads_per_layer=[8, 16])
    D, Hkv = uncut["head_dim"], 4
    H = uncut["num_attention_heads_per_layer"][layer]
    sliding, dense = layer == 1, layer == 0
    p = reference._sub(_seeded(uncut, 7, scale=10.0), "layers.%d." % layer)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 30, 64))
    whole = reference._layer(x, p, uncut, "f32", sliding, dense)

    def mine(n_kv_heads, n_experts, first_expert):
        return models.LagunaConfig(
            vocab_size=96, dim=64, n_layers=2,
            layer_types=tuple(uncut["layer_types"][:2]),
            heads_per_layer=(8, 16), n_kv_heads_published=4,
            n_kv_heads=n_kv_heads, head_dim=D, window=12, dense_dim=96,
            n_routed_experts=16, n_experts=n_experts,
            first_expert=first_expert, experts_per_token=3, expert_dim=32,
            shared_expert_dim=32, dtype=jnp.float32)

    # attention: two shares of two key-value heads with their query heads
    y = rms_norm(x, p["attn_norm.w"], 1e-6)
    h = x
    for share in range(2):
        q_cols = slice(share * H // 2 * D, (share + 1) * H // 2 * D)
        kv_cols = slice(share * Hkv // 2 * D, (share + 1) * Hkv // 2 * D)
        part = {"wq": p["attn.wq"][:, q_cols], "wk": p["attn.wk"][:, kv_cols],
                "wv": p["attn.wv"][:, kv_cols],
                "wg": p["attn.wg"][:, share * H // 2:(share + 1) * H // 2],
                "wo": p["attn.wo"][q_cols], "q_norm": p["attn.q_norm"],
                "k_norm": p["attn.k_norm"]}
        got = model._attention(part, y, mine(2, 16, 0), layer)
        # float32 on both sides, sums over 12 to 30 keys of size 1: a few
        # dozen roundings
        onp.testing.assert_allclose(
            got, reference._attention(y, part, uncut, "f32", sliding),
            rtol=1e-5, atol=2e-5)
        h = h + got
    # the second half: the dense MLP once, or the shared expert once and
    # four shares of four routed experts
    y = rms_norm(h, p["mlp_norm.w"], 1e-6)
    if dense:
        total = model._mlp_block(_tree(p), h, mine(4, 16, 0), layer)
    else:
        moe = reference._sub(p, "moe.")
        none = dict(moe, gate=moe["gate"][:0], up=moe["up"][:0],
                    down=moe["down"][:0])
        shared = reference._moe(y, none, uncut, "f32")
        total = h + shared
        for first in range(0, 16, 4):
            part = dict(moe, gate=moe["gate"][first:first + 4],
                        up=moe["up"][first:first + 4],
                        down=moe["down"][first:first + 4])
            got = model._moe(part, y, mine(4, 4, first))
            onp.testing.assert_allclose(
                got, reference._moe(y, part, dict(uncut, first_expert=first),
                                    "f32"), rtol=1e-5, atol=2e-5)
            total = total + (got - shared)
    onp.testing.assert_allclose(total, whole, rtol=1e-5, atol=5e-5)


def test_bfloat16_activations_stay_near_float32(seeded):
    flat, batch = seeded
    loss32 = jax.jit(program.loss_fn(CFG))(_tree(flat), batch)
    loss16 = jax.jit(program.loss_fn(dict(CFG, dtype="bfloat16")))(
        _tree(flat), batch)
    # bfloat16 keeps 8 bits: a loss of 5 within a hundredth
    assert abs(float(loss16) - float(loss32)) < 1e-2 * float(loss32)

"""Transformer model-family tests (llama/bert) incl. sharded train step.

Mirrors the reference's test style (tests/python/unittest/test_gluon.py
forward-shape checks + tests/nightly numeric training smoke), extended with
mesh-sharded step validation the reference could not express.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models import (LlamaConfig, llama_init, llama_forward,
                              llama_loss, BertConfig, bert_init,
                              bert_forward, bert_mlm_loss)
from mxnet_tpu.models.llama import (CONFIGS, init_kv_cache,
                                    llama_decode_step)
from mxnet_tpu.parallel.mesh import create_mesh
from mxnet_tpu.parallel.sharding import LLAMA_RULES, BERT_RULES
from mxnet_tpu.parallel.train_step import ShardedTrainStep


CFG = CONFIGS["llama_tiny"]


def test_llama_forward_shape_dtype():
    params = llama_init(jax.random.PRNGKey(0), CFG)
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = llama_forward(params, toks, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama_embed_onehot_matches_gather():
    """embed_onehot (the sharded-table path, llama3_8b + dryrun) must be
    numerically identical to the default gather lookup."""
    import dataclasses
    params = llama_init(jax.random.PRNGKey(0), CFG)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                              CFG.vocab_size)
    ref = llama_forward(params, toks, CFG)
    oh = llama_forward(params, toks,
                       dataclasses.replace(CFG, embed_onehot=True))
    # the two lookups are bit-exact (one-hot rows select single table rows)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(oh),
                               rtol=1e-6, atol=1e-6)


def test_llama_loss_decreases_training():
    params = llama_init(jax.random.PRNGKey(0), CFG)
    key = jax.random.PRNGKey(1)
    toks = jax.random.randint(key, (4, 33), 0, CFG.vocab_size)

    loss_fn = lambda p, b: llama_loss(p, b, CFG)
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    first = None
    for _ in range(8):
        loss, g = grad_fn(params, {"tokens": toks})
        if first is None:
            first = float(loss)
        params = jax.tree_util.tree_map(lambda p, g_: p - 0.05 * g_.astype(p.dtype),
                                        params, g)
    assert float(loss) < first


def test_llama_decode_matches_forward():
    cfg = CFG
    params = llama_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                              cfg.vocab_size)
    full = llama_forward(params, toks, cfg)        # (2, 8, V)
    cache = init_kv_cache(cfg, batch=2, max_len=8)
    step = jax.jit(lambda p, c, t, pos: llama_decode_step(p, c, t, pos, cfg))
    for i in range(8):
        logits, cache = step(params, cache, toks[:, i],
                             jnp.asarray(i, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, -1]),
                               rtol=0.15, atol=0.15)


def test_llama_sharded_train_step_tp_fsdp():
    mesh = create_mesh(data=2, fsdp=2, model=2)
    params = llama_init(jax.random.PRNGKey(0), CFG)
    step = ShardedTrainStep(lambda p, b: llama_loss(p, b, CFG), params, mesh,
                            rules=LLAMA_RULES, optimizer="adamw", lr=1e-2)
    p, s = step.init()
    # wq got a model-sharded output dim
    wq = p["layers"]["0"]["attn"]["wq"]
    assert "model" in str(wq.sharding.spec)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                              CFG.vocab_size)
    losses = []
    for _ in range(4):
        p, s, loss = step(p, s, {"tokens": toks})
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_bert_forward_and_mlm_loss():
    cfg = BertConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                     hidden_dim=128, max_seq_len=64)
    params = bert_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    h = bert_forward(params, toks, cfg)
    assert h.shape == (2, 32, cfg.dim)
    batch = {"tokens": toks, "targets": toks,
             "mask": jnp.ones_like(toks)}
    loss = bert_mlm_loss(params, batch, cfg)
    assert np.isfinite(float(loss))


def test_bert_sharded_step():
    cfg = BertConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                     hidden_dim=128, max_seq_len=64)
    mesh = create_mesh(data=2, model=2)
    params = bert_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks, "mask": jnp.ones_like(toks)}
    step = ShardedTrainStep(lambda p, b: bert_mlm_loss(p, b, cfg), params,
                            mesh, rules=BERT_RULES, optimizer="adam",
                            lr=1e-2)
    p, s = step.init()
    losses = []
    for _ in range(3):
        p, s, loss = step(p, s, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def _three_projection_layer(lp, x, cfg):
    """A BERT encoder layer written plainly: three projections, the S x S
    probabilities in the open, no kernel."""
    from mxnet_tpu.models.bert import layer_norm
    B, S, _ = x.shape
    a = lp["attn"]
    q, k, v = ((x @ a["w" + n] + a["b" + n])
               .reshape(B, S, cfg.n_heads, cfg.head_dim) for n in "qkv")
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg.head_dim ** -0.5
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = layer_norm(x + (o.reshape(B, S, -1) @ a["wo"] + a["bo"]),
                   lp["attn_norm"], cfg.norm_eps)
    f = lp["ffn"]
    h = jax.nn.gelu(x @ f["w1"] + f["b1"], approximate=True)
    return layer_norm(x + (h @ f["w2"] + f["b2"]), lp["ffn_norm"],
                      cfg.norm_eps)


LAYER_LEAVES = {
    "attn/wq": (256, 256), "attn/wk": (256, 256), "attn/wv": (256, 256),
    "attn/wo": (256, 256), "attn/bq": (256,), "attn/bk": (256,),
    "attn/bv": (256,), "attn/bo": (256,),
    "attn_norm/gamma": (256,), "attn_norm/beta": (256,),
    "ffn/w1": (256, 512), "ffn/b1": (512,), "ffn/w2": (512, 256),
    "ffn/b2": (256,), "ffn_norm/gamma": (256,), "ffn_norm/beta": (256,)}


@pytest.mark.parametrize("remat", [False, True])
def test_bert_layer_with_one_packed_projection_is_the_plain_layer(
        remat, monkeypatch):
    """`_encoder_layer` packs wq|wk|wv inside the step and runs the flash
    kernels (interpreted here) on the packed result. Against the plain
    layer at a width with a head-group tile (H = 4, D = 64), float32: the
    hidden states and the gradient of each of the layer's 16 leaves under
    its own name; the parameter tree is what it was."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import bert
    monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    cfg = BertConfig(vocab_size=64, dim=256, n_layers=1, n_heads=4,
                     hidden_dim=512, max_seq_len=128, dtype=jnp.float32,
                     remat=remat)
    params = bert_init(jax.random.PRNGKey(0), cfg)
    # biases and norms off their initial 0 and 1, so that each one matters
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = tree.unflatten([p + 0.05 * jax.random.normal(k, p.shape)
                             for p, k in zip(leaves, keys)])
    named = {"/".join(k.key for k in path): leaf.shape for path, leaf in
             jax.tree_util.tree_leaves_with_path(params["layers"]["0"])}
    assert named == LAYER_LEAVES
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, 64)
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 256))

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: jnp.sum(bert_forward(p, toks, cfg) * w))(params)

    before = telemetry.snapshot()["counters"].get(
        "ops.pallas.dispatch.flash_packed", 0)
    loss, grads = run()
    assert telemetry.snapshot()["counters"][
        "ops.pallas.dispatch.flash_packed"] > before
    monkeypatch.setattr(bert, "_encoder_layer", _three_projection_layer)
    want_loss, want = run()
    assert jax.tree_util.tree_structure(grads) == tree
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    got, ref = grads["layers"]["0"], want["layers"]["0"]
    # bk's gradient is zero but for rounding (a constant added to every
    # key's score leaves the softmax as it was), so a leaf is held to the
    # largest gradient among the leaves of its rank
    scale = {rank: max(float(jnp.abs(ref[n.split("/")[0]][n.split("/")[1]])
                             .max())
                       for n, shape in LAYER_LEAVES.items()
                       if len(shape) == rank) for rank in (1, 2)}
    for name, shape in LAYER_LEAVES.items():
        group, leaf = name.split("/")
        np.testing.assert_allclose(got[group][leaf], ref[group][leaf],
                                   atol=2e-5 * scale[len(shape)], rtol=1e-3,
                                   err_msg=name)

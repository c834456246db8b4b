"""The shared loss tail (`models/losses.py`): one operation with a
hand-written backward, against the plain `log_softmax` / `take_along_axis`
form it replaced in `bert_mlm_loss`, `llama_loss` and `resnet_loss`."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models import bert, llama, resnet
from mxnet_tpu.models.losses import linear_cross_entropy
from mxnet_tpu.parallel.mesh import create_mesh
from mxnet_tpu.parallel.train_step import ShardedTrainStep


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def plain_tail(logits, targets, weights):
    """The three lines every loss used to end in."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    weights = weights.astype(jnp.float32)
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def plain(h, w, targets, weights):
    h, w = h.astype(jnp.float32), w.astype(jnp.float32)
    return plain_tail(h @ w.T, targets, weights)


def masks(key, shape):
    return {"ones": jnp.ones(shape, jnp.int32),
            "random": (jax.random.uniform(key, shape) < 0.3
                       ).astype(jnp.int32),
            "zeros": jnp.zeros(shape, jnp.int32)}


@pytest.mark.parametrize("rows", [(24,), (3, 8)], ids=["flat", "batched"])
@pytest.mark.parametrize("mask", ["ones", "random", "zeros"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_linear_cross_entropy_matches_plain_form(dtype, mask, rows):
    k = jax.random.split(jax.random.PRNGKey(29), 4)
    h = jax.random.normal(k[0], rows + (16,)).astype(dtype)
    w = (0.3 * jax.random.normal(k[1], (50, 16))).astype(dtype)
    targets = jax.random.randint(k[2], rows, 0, 50)
    weights = masks(k[3], rows)[mask]
    want, (want_dh, want_dw) = jax.value_and_grad(plain, (0, 1))(
        h, w, targets, weights)
    got, (dh, dw) = jax.value_and_grad(linear_cross_entropy, (0, 1))(
        h, w, targets, weights)
    assert got.dtype == jnp.float32
    assert (dh.dtype, dw.dtype) == (h.dtype, w.dtype)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # a bfloat16 model's cotangents are bfloat16: one rounding of theirs
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    for a, b in ((dh, want_dh), (dw, want_dw)):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=tol * max(np.abs(b).max(), 1.0))
    if mask == "zeros":
        assert float(got) == 0.0
        assert not np.asarray(dh.astype(jnp.float32)).any()
        assert not np.asarray(dw.astype(jnp.float32)).any()


def test_linear_cross_entropy_without_weights_is_the_mean():
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(k[0], (12, 8))
    w = jax.random.normal(k[1], (20, 8))
    targets = jax.random.randint(k[2], (12,), 0, 20)
    np.testing.assert_allclose(
        linear_cross_entropy(h, w, targets),
        plain(h, w, targets, jnp.ones((12,))), rtol=0, atol=1e-6)


# ------------------------------------------------------------ the models
def _bert_case(mask):
    cfg = dataclasses.replace(bert.CONFIGS["bert_tiny"], dtype=jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    params = bert.bert_init(k[0], cfg)
    shape = (4, 32)
    batch = {"tokens": jax.random.randint(k[1], shape, 0, cfg.vocab_size),
             "targets": jax.random.randint(k[2], shape, 0, cfg.vocab_size),
             "mask": masks(k[3], shape)[mask]}

    def before(params):
        h = bert.bert_forward(params, batch["tokens"], cfg)
        logits = (h @ params["word_embed"].T.astype(h.dtype)
                  ).astype(jnp.float32)
        return plain_tail(logits, batch["targets"], batch["mask"])

    return params, before, lambda p: bert.bert_mlm_loss(p, batch, cfg)


def _llama_case(_):
    cfg = dataclasses.replace(llama.CONFIGS["llama_tiny"], dtype=jnp.float32)
    params = llama.llama_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)

    def before(params):
        logits = llama.llama_forward(params, tokens[:, :-1], cfg)
        return plain_tail(logits, tokens[:, 1:], jnp.ones((4, 32)))

    return params, before, lambda p: llama.llama_loss(p, {"tokens": tokens},
                                                      cfg)


def _resnet_case(_):
    cfg = dataclasses.replace(resnet.CONFIGS["resnet_tiny"],
                              dtype=jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    params = resnet.resnet_init(k[0], cfg)
    batch = {"images": jax.random.normal(k[1], (4, 32, 32, 3)),
             "labels": jax.random.randint(k[2], (4,), 0, cfg.classes)}

    def before(params):
        logits, _ = resnet.resnet_forward(params, batch["images"], cfg,
                                          train=True)
        return plain_tail(logits, batch["labels"], jnp.ones((4,)))

    return params, before, lambda p: resnet.resnet_loss(p, batch, cfg)[0]


@pytest.mark.parametrize("case,arg", [
    (_bert_case, "random"), (_bert_case, "ones"), (_bert_case, "zeros"),
    (_llama_case, None), (_resnet_case, None)],
    ids=["bert-random", "bert-ones", "bert-zeros", "llama", "resnet"])
def test_model_loss_is_what_the_plain_tail_gave(case, arg):
    """Loss and every leaf's gradient, before and after, from one seed."""
    params, before, after = case(arg)
    want, want_g = jax.value_and_grad(before)(params)
    got, got_g = jax.value_and_grad(after)(params)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(got_g)):
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-6 * max(float(jnp.abs(b).max()), 1.0),
            err_msg=jax.tree_util.keystr(path))


def _eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, out)
    return out


def test_bert_gradient_scatters_nothing_of_the_logits_size():
    """The gather's backward used to be a scatter-add into (B, S, V) zeros,
    and the log-probabilities a second (B, S, V) float32 residual."""
    from jax._src.ad_checkpoint import saved_residuals
    params, before, after = _bert_case("random")
    vocab = bert.CONFIGS["bert_tiny"].vocab_size

    def of_logits(aval):        # (B, S, V) before, (B * S, V) now
        return aval.shape[-1:] == (vocab,) and aval.size == 4 * 32 * vocab

    def logit_sized(fn):
        eqns = _eqns(jax.make_jaxpr(jax.grad(fn))(params).jaxpr, [])
        scatters = [e for e in eqns if e.primitive.name == "scatter-add"
                    and of_logits(e.outvars[0].aval)]
        residuals = [aval for aval, _ in saved_residuals(fn, params)
                     if of_logits(aval) and aval.dtype == jnp.float32]
        return len(scatters), len(residuals)

    assert logit_sized(before) == (1, 1)     # what this test would catch
    assert logit_sized(after) == (0, 1)      # the logits, and nothing else


def test_sharded_step_of_float32_bert_tiny_compiles_once():
    cfg = dataclasses.replace(bert.CONFIGS["bert_tiny"], dtype=jnp.float32)
    params = bert.bert_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens,
             "mask": jnp.ones_like(tokens)}
    step = ShardedTrainStep(lambda p, b: bert.bert_mlm_loss(p, b, cfg),
                            params, create_mesh(data=1), optimizer="adamw",
                            lr=1e-3, wd=0.01)
    p, s = step.init()
    losses = []
    for _ in range(3):
        p, s, loss = step(p, s, batch)
        losses.append(float(loss))
    assert step._compiled._cache_size() == 1
    assert losses[-1] < losses[0]


def _two_pass_bn(x, p, cfg, train):
    """`models/resnet.py::_bn` as it was before PR 30: `jnp.var`'s two
    passes, differentiated through the inner mean as well."""
    xf = x.astype(jnp.float32)
    mu, var = jnp.mean(xf, axis=(0, 1, 2)), jnp.var(xf, axis=(0, 1, 2))
    y = (xf - mu) * jax.lax.rsqrt(var + cfg.bn_eps) * p["gamma"] + p["beta"]
    return y.astype(x.dtype), (mu, var)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_resnet_statistics_keep_loss_and_gradients(monkeypatch, dtype, tol):
    """`resnet_loss` on `ops.nn.batch_moments` against its own old two-pass
    statistics: loss and every leaf's gradient, to 1e-5 in float32 and to
    bfloat16's rounding where the activations are bfloat16."""
    cfg = dataclasses.replace(resnet.CONFIGS["resnet_tiny"], dtype=dtype)
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    params = resnet.resnet_init(k[0], cfg)
    batch = {"images": jax.random.normal(k[1], (8, 32, 32, 3)),
             "labels": jax.random.randint(k[2], (8,), 0, cfg.classes)}

    def loss(p):
        return resnet.resnet_loss(p, batch, cfg)[0]

    got, got_g = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(resnet, "_bn", _two_pass_bn)
    want, want_g = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(got_g)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(
            np.linalg.norm(a - b), 0, rtol=0,
            atol=tol * max(float(np.linalg.norm(b)), 1.0),
            err_msg=jax.tree_util.keystr(path))

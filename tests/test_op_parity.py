"""Op-parity audit (round-2 verdict Missing #4): the reference's
user-facing operator catalog resolves against this build's registry.

The catalog below is the curated user-facing surface of the reference's
src/operator/ registry (tests/python/unittest/test_operator.py exercises
exactly these names). The reference mount is empty (SURVEY.md §0), so the
list is reconstructed from the stable 1.x API; every name here must exist
either in the op registry or as an `mx.nd` callable.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import registry as _reg

CATALOG = """
Activation BatchNorm BatchNorm_v1 BilinearSampler BlockGrad Cast Concat
Convolution Correlation Crop Custom Deconvolution Dropout Embedding Flatten
FullyConnected GridGenerator GroupNorm IdentityAttachKLSparseReg
InstanceNorm L2Normalization LRN LayerNorm LeakyReLU LinearRegressionOutput
LogisticRegressionOutput MAERegressionOutput MakeLoss Pad Pooling RNN
ROIPooling Reshape SVMOutput SequenceLast SequenceMask SequenceReverse
SliceChannel Softmax SoftmaxActivation SoftmaxOutput SpatialTransformer
SwapAxis UpSampling abs adam_update add_n arccos arccosh arcsin arcsinh
arctan arctanh argmax argmax_channel argmin argsort batch_dot batch_take
broadcast_add broadcast_axes broadcast_axis broadcast_div broadcast_equal
broadcast_greater broadcast_greater_equal broadcast_hypot broadcast_lesser
broadcast_lesser_equal broadcast_like broadcast_logical_and
broadcast_logical_or broadcast_logical_xor broadcast_maximum
broadcast_minimum broadcast_mod broadcast_mul broadcast_not_equal
broadcast_power broadcast_sub broadcast_to cast cast_storage cbrt ceil clip
concat cos cosh cumsum degrees depth_to_space diag dot elemwise_add
elemwise_div elemwise_mul elemwise_sub erf erfinv exp expand_dims expm1
fill_element_0index fix flatten flip floor ftrl_update gamma gammaln
gather_nd hard_sigmoid identity khatri_rao lamb_update_phase1
lamb_update_phase2 linalg_det linalg_extractdiag linalg_extracttrian
linalg_gelqf linalg_gemm linalg_gemm2 linalg_inverse linalg_makediag
linalg_maketrian linalg_potrf linalg_potri linalg_slogdet
linalg_sumlogdiag linalg_syrk linalg_trmm linalg_trsm log log10 log1p log2
log_softmax logical_not make_loss max mean min moments mp_lamb_update_phase1
mp_lamb_update_phase2 mp_nag_mom_update mp_sgd_mom_update mp_sgd_update
multi_all_finite multi_lars multi_mp_sgd_mom_update multi_mp_sgd_update
multi_sgd_mom_update multi_sgd_update nag_mom_update nanprod nansum negative
norm normal one_hot ones_like pad pick preloaded_multi_mp_sgd_mom_update
prod radians rcbrt reciprocal relu repeat reshape reshape_like reverse rint
rmsprop_update rmspropalex_update round rsqrt scatter_nd sgd_mom_update
sgd_update shape_array shuffle sigmoid sign signsgd_update signum_update sin
sinh size_array slice slice_axis slice_like smooth_l1 softmax
softmax_cross_entropy softmin softsign sort space_to_depth split sqrt square
squeeze stack stop_gradient sum swapaxes take tan tanh tile topk transpose
trunc uniform unravel_index where zeros_like
""".split()

CONTRIB = """
quantize_v2 dequantize requantize quantized_fully_connected quantized_conv
interleaved_matmul_selfatt_qk interleaved_matmul_selfatt_valatt
div_sqrt_dim adamw_update
box_nms box_iou box_encode box_decode ROIAlign BilinearResize2D
AdaptiveAvgPooling2D arange_like
MultiBoxPrior MultiBoxTarget MultiBoxDetection
DeformableConvolution PSROIPooling
""".split()


def test_user_facing_op_catalog_resolves():
    ops = set(_reg.list_ops())
    missing = [n for n in CATALOG
               if n not in ops and not hasattr(nd, n)]
    assert not missing, "reference ops absent: %s" % missing


def test_contrib_op_catalog_resolves():
    ops = set(_reg.list_ops())
    missing = [n for n in CONTRIB if "_contrib_" + n not in ops]
    assert not missing, "contrib ops absent: %s" % missing
    for n in CONTRIB:
        assert hasattr(nd.contrib, n)


# -- functional spot-checks of the newly closed gaps -----------------------

def test_linalg_ops_numeric():
    rng = np.random.RandomState(0)
    a_np = rng.randn(3, 3).astype(np.float32)
    spd = a_np @ a_np.T + 3 * np.eye(3, dtype=np.float32)
    a = nd.array(spd)
    np.testing.assert_allclose(nd.invoke("linalg_det", a).asnumpy(),
                               np.linalg.det(spd), rtol=1e-4)
    np.testing.assert_allclose(nd.invoke("linalg_inverse", a).asnumpy(),
                               np.linalg.inv(spd), rtol=1e-3, atol=1e-4)
    sign, logdet = nd.invoke("linalg_slogdet", a)
    np.testing.assert_allclose(logdet.asnumpy(),
                               np.linalg.slogdet(spd)[1], rtol=1e-4)
    # potrf -> potri == inverse
    l = nd.invoke("linalg_potrf", a)
    inv = nd.invoke("linalg_potri", l)
    np.testing.assert_allclose(inv.asnumpy(), np.linalg.inv(spd),
                               rtol=1e-3, atol=1e-4)
    # gelqf: A = L Q with orthonormal Q rows
    m = nd.array(rng.randn(2, 4).astype(np.float32))
    lmat, q = nd.invoke("linalg_gelqf", m)
    np.testing.assert_allclose((lmat.asnumpy() @ q.asnumpy()), m.asnumpy(),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(q.asnumpy() @ q.asnumpy().T, np.eye(2),
                               rtol=1e-4, atol=1e-5)
    # trsm solves
    b = rng.randn(3, 2).astype(np.float32)
    x = nd.invoke("linalg_trsm", l, nd.array(b)).asnumpy()
    np.testing.assert_allclose(np.tril(l.asnumpy()) @ x, b, rtol=1e-3,
                               atol=1e-4)


def test_multi_sgd_update_matches_single():
    rng = np.random.RandomState(1)
    ws = [rng.randn(4).astype(np.float32) for _ in range(3)]
    gs = [rng.randn(4).astype(np.float32) for _ in range(3)]
    args = []
    for w, g in zip(ws, gs):
        args.extend([nd.array(w), nd.array(g)])
    outs = nd.invoke("multi_sgd_update", *args, lrs=[0.1, 0.2, 0.3],
                     wds=[0.0, 0.01, 0.0], num_weights=3)
    for i, (w, g) in enumerate(zip(ws, gs)):
        lr, wd = [0.1, 0.2, 0.3][i], [0.0, 0.01, 0.0][i]
        expect = w - lr * (g + wd * w)
        np.testing.assert_allclose(outs[i].asnumpy(), expect, rtol=1e-5)


def test_multi_all_finite_and_lars():
    good = nd.array(np.ones(3, np.float32))
    bad = nd.array(np.array([1.0, np.inf, 0.0], np.float32))
    assert float(nd.invoke("multi_all_finite", good, good).asnumpy()[0]) == 1
    assert float(nd.invoke("multi_all_finite", good, bad).asnumpy()[0]) == 0
    lrs = nd.array(np.array([0.1, 0.1], np.float32))
    wsq = nd.array(np.array([4.0, 0.0], np.float32))
    gsq = nd.array(np.array([1.0, 1.0], np.float32))
    wds = nd.array(np.array([0.0, 0.0], np.float32))
    out = nd.invoke("multi_lars", lrs, wsq, gsq, wds, eta=0.1).asnumpy()
    np.testing.assert_allclose(out[0], 0.1 * (0.1 * 2 / 1), rtol=1e-4)
    np.testing.assert_allclose(out[1], 0.1, rtol=1e-5)  # trust=1 fallback


def test_lrn_and_svm_output():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 6, 4, 4).astype(np.float32)
    out = nd.invoke("LRN", nd.array(x), nsize=5, alpha=1e-3).asnumpy()
    # direct formula at one position
    c = 2
    lo, hi = max(0, c - 2), min(6, c + 3)
    win = (x[0, lo:hi, 0, 0] ** 2).sum()
    expect = x[0, c, 0, 0] / (2.0 + (1e-3 / 5) * win) ** 0.75
    np.testing.assert_allclose(out[0, c, 0, 0], expect, rtol=1e-4)

    from mxnet_tpu import autograd
    scores = nd.array(rng.randn(4, 3).astype(np.float32))
    label = nd.array(np.array([0, 1, 2, 0], np.float32))
    scores.attach_grad()
    with autograd.record():
        y = nd.invoke("SVMOutput", scores, label, margin=1.0)
    y.backward()
    g = scores.grad.asnumpy()
    assert g.shape == (4, 3)
    assert np.abs(g).sum() > 0
    np.testing.assert_allclose(g.sum(axis=1), 0, atol=1e-5)  # zero-sum rows


def test_batch_take_reshape_like_moments():
    rng = np.random.RandomState(3)
    a = nd.array(rng.randn(3, 5).astype(np.float32))
    idx = nd.array(np.array([0, 4, 2], np.float32))
    np.testing.assert_allclose(
        nd.invoke("batch_take", a, idx).asnumpy(),
        a.asnumpy()[np.arange(3), [0, 4, 2]])
    b = nd.array(rng.randn(2, 6).astype(np.float32))
    like = nd.array(np.zeros((3, 4), np.float32))
    assert nd.invoke("reshape_like", b, like).shape == (3, 4)
    m, v = nd.invoke("moments", a, axes=(1,))
    np.testing.assert_allclose(m.asnumpy(), a.asnumpy().mean(1), rtol=1e-5)
    np.testing.assert_allclose(v.asnumpy(), a.asnumpy().var(1), rtol=1e-4)


def test_linspace_digamma_ravel():
    np.testing.assert_allclose(nd.linspace(0, 1, 5).asnumpy(),
                               [0, 0.25, 0.5, 0.75, 1.0])
    np.testing.assert_allclose(
        nd.digamma(nd.array(np.array([1.0]))).asnumpy(), [-0.57721566],
        rtol=1e-5)
    r = nd.ravel_multi_index(
        nd.array(np.array([[0, 1], [2, 3]]), dtype="int64"), shape=(3, 4))
    np.testing.assert_array_equal(r.asnumpy(), [2, 7])
    # inverse of unravel_index
    u = nd.unravel_index(r, shape=(3, 4))
    np.testing.assert_array_equal(u.asnumpy(), [[0, 1], [2, 3]])


def test_im2col_col2im():
    """reference: im2col.h ops — col2im is the exact transpose; with
    non-overlapping windows it is the exact inverse."""
    from mxnet_tpu import autograd
    x = nd.array(np.arange(2 * 3 * 4 * 4, dtype=np.float32)
                 .reshape(2, 3, 4, 4))
    cols = nd.im2col(x, kernel=(2, 2), stride=(2, 2))
    assert cols.shape == (2, 12, 4)
    back = nd.col2im(cols, output_size=(4, 4), kernel=(2, 2), stride=(2, 2))
    np.testing.assert_allclose(back.asnumpy(), x.asnumpy())
    # overlapping windows: gradient counts patch membership
    x.attach_grad()
    with autograd.record():
        loss = nd.im2col(x, kernel=(3, 3), stride=(1, 1), pad=(1, 1)).sum()
    loss.backward()
    g = x.grad.asnumpy()
    assert g[0, 0, 0, 0] == 4.0 and g[0, 0, 2, 2] == 9.0


# ---------------------------------------------------------------------------
# BatchNorm's training-mode statistics (PR 30): one pass about the running
# mean for a 16-bit input, jnp.var's two passes about stop_gradient(mean)
# for a wider one. References are float64 numpy over the same stored values.
# ---------------------------------------------------------------------------
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

_BN = _reg.get("BatchNorm").fn
_EPS = 1e-5


def _two_pass_bn(data, gamma, beta, axis=1):
    """`_batch_norm`'s training mode as it was before PR 30."""
    red = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    x32 = data.astype(jnp.float32)
    mean, var = jnp.mean(x32, axis=red), jnp.var(x32, axis=red)
    out = ((data.astype(jnp.float32) - mean.reshape(shape)) *
           jax.lax.rsqrt(var + _EPS).reshape(shape) * gamma.reshape(shape)
           + beta.reshape(shape)).astype(data.dtype)
    return out, mean, var


def _bn(data, gamma, beta, moving_mean, axis=1):
    return _BN(data, gamma, beta, moving_mean, jnp.ones_like(moving_mean),
               eps=_EPS, fix_gamma=False, output_mean_var=True, axis=axis)


def _stored(dtype, rows, channels, ratio, seed=0):
    """(the 16-bit array, its values in float64, their mean, variance)."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray((rng.standard_normal((rows, channels)) + ratio)
                    .astype(np.float32)).astype(dtype)
    x64 = np.asarray(x.astype(jnp.float32), np.float64)
    return x, x64, x64.mean(0), x64.var(0)


@pytest.mark.parametrize("rows", [8, 802816])
@pytest.mark.parametrize("moving", ["zeros", "mean", "near"])
@pytest.mark.parametrize("ratio", [0, 3, 30, 100])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_batch_norm_one_pass_statistics(dtype, ratio, moving, rows):
    """A 16-bit input at |mean| / std = `ratio`, the running mean at nought,
    at the batch mean or 0.1 std from it, 8 and 802,816 values a channel
    (ResNet-50's stage 1): what the one-pass form adds to the variance's
    error is the sums' rounding times 1 + r^2, r = |mean - moving| / std, so
    a converged running mean gives 1e-4 at any |mean| / std."""
    channels = 4
    x, x64, mean, var = _stored(dtype, rows, channels, ratio)
    std = np.sqrt(var)
    shift = {"zeros": 0 * mean, "mean": mean, "near": mean + 0.1 * std}[moving]
    shift = np.asarray(shift, np.float32)
    out, got_mean, got_var = jax.jit(_bn)(
        x, jnp.ones(channels), jnp.zeros(channels), jnp.asarray(shift))
    assert got_mean.dtype == got_var.dtype == jnp.float32
    assert out.dtype == x.dtype
    r = np.abs(mean - shift) / std
    tol = 2e-5 * (1 + r ** 2)
    np.testing.assert_array_less(np.abs(np.asarray(got_var) - var) / var, tol)
    np.testing.assert_array_less(
        np.abs(np.asarray(got_mean) - mean), 2e-5 * (np.abs(mean - shift) +
                                                     std) + 1e-7 * np.abs(mean))
    want = (x64 - mean) / np.sqrt(var + _EPS)
    step = float(jnp.finfo(x.dtype).eps)      # the stored result's own
    np.testing.assert_array_less(
        np.abs(np.asarray(out.astype(jnp.float32)) - want),
        (step + tol) * np.maximum(np.abs(want), 1.0))


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("shape,axis", [((8, 16, 1, 1), 1), ((32, 6, 14, 14), 1),
                                        ((4, 7, 5, 3), 3), ((64, 5), 1)])
def test_batch_norm_float32_forward_is_the_old_one_to_the_bit(shape, axis,
                                                              jit):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    c = shape[axis]
    x = jax.random.normal(k[0], shape) * 3 + 2
    gamma, beta = jax.random.normal(k[1], (c,)), jax.random.normal(k[2], (c,))

    def new(x, gamma, beta):
        return _bn(x, gamma, beta, jnp.full((c,), 0.5), axis=axis)

    def old(x, gamma, beta):
        return _two_pass_bn(x, gamma, beta, axis=axis)

    if jit:
        new, old = jax.jit(new), jax.jit(old)
    for got, want in zip(new(x, gamma, beta), old(x, gamma, beta)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("moving", ["zeros", "mean"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_batch_norm_gradients_are_the_two_pass_forms(dtype, moving):
    """d data, d gamma, d beta against autodiff of the old two-pass form in
    float32 on the same stored values: 1e-5 of the largest entry for a
    float32 input, the input's own step for a 16-bit one."""
    shape, c = (16, 6, 5, 5), 6
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    x = (jax.random.normal(k[0], shape) * 2 + 3).astype(dtype)
    gamma = jax.random.normal(k[1], (c,)) + 1.5
    beta = jax.random.normal(k[2], (c,))
    weight = jax.random.normal(k[3], shape)
    x32 = x.astype(jnp.float32)
    shift = (jnp.mean(x32, (0, 2, 3)) if moving == "mean"
             else jnp.zeros(c))

    def loss(fn, x, gamma, beta):
        return jnp.sum(fn(x, gamma, beta)[0].astype(jnp.float32) * weight)

    got = jax.grad(lambda *a: loss(
        lambda x, g, b: _bn(x, g, b, shift), *a), (0, 1, 2))(x, gamma, beta)
    want = jax.grad(lambda *a: loss(_two_pass_bn, *a), (0, 1, 2))(
        x32, gamma, beta)
    assert got[0].dtype == x.dtype
    tol = 1e-5 if dtype == "float32" else float(jnp.finfo(x.dtype).eps)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * np.abs(b).max())


@pytest.mark.parametrize("value", [0.0, 3.3, 1000.0])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_batch_norm_constant_channel(dtype, value):
    """A channel that is constant over the batch: the variance is not
    negative (for float32 the two-pass form's to the bit: 0, or the square
    of what the mean's own rounding left), and the output and every
    gradient are finite."""
    shape, c = (32, 3, 9, 9), 3
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    x = x.at[:, 1].set(value).astype(dtype)
    gamma, beta, shift = jnp.ones(c), jnp.zeros(c), jnp.zeros(c)
    out, _, var = _bn(x, gamma, beta, shift)
    assert np.all(np.asarray(var) >= 0)
    if dtype == "float32":
        assert float(var[1]) == float(_two_pass_bn(x, gamma, beta)[2][1])
        assert float(var[1]) <= 1e-8 * value ** 2
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
    grads = jax.grad(lambda x, g, b: jnp.sum(jnp.square(
        _bn(x, g, b, shift)[0].astype(jnp.float32))), (0, 1, 2))(
            x, gamma, beta)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g, np.float32)))


@pytest.mark.parametrize("moving", ["zeros", "mean"])
def test_batch_norm_data_gradient_is_rounded_once(moving):
    """BatchNorm's output does not change when a constant is added to a
    channel, so d data sums to nought over it: to float32 rounding in
    float32, and for a bfloat16 input the gradient is that float32 one
    rounded to bfloat16 once. Read twice and added in bfloat16, as before
    PR 30, the small mean correction was lost at every position alike and
    the sum drifted with N (PERF.md, fault 2)."""
    # rows x channels: the CPU sums a column of such an array to float32
    # rounding, which it does not do over the (0, 2, 3) of an NCHW one
    shape, c = (50176, 4), 4
    k = jax.random.split(jax.random.PRNGKey(11), 2)
    x = (jax.random.normal(k[0], shape) + 0.5).astype(jnp.bfloat16)
    x32 = x.astype(jnp.float32)
    weight = jnp.abs(jax.random.normal(k[1], shape)) + 1.0   # all one sign
    ones, zeros = jnp.ones(c), jnp.zeros(c)
    shift = jnp.mean(x32, 0) if moving == "mean" else zeros

    def d_data(fn, x):
        return jax.grad(lambda x: jnp.sum(
            fn(x)[0].astype(jnp.float32) * weight))(x)

    def over_channel(g):
        return np.asarray(g.astype(jnp.float32), np.float64).sum(0)

    # the float32 gradient under the cotangent a bfloat16 output hands back
    weight = weight.astype(jnp.bfloat16).astype(jnp.float32)
    wide = d_data(lambda x: _two_pass_bn(x, ones, zeros), x32)
    assert np.all(np.abs(over_channel(wide)) <
                  1e-6 * np.abs(np.asarray(wide)).sum(0))
    once = wide.astype(jnp.bfloat16)
    got = d_data(lambda x: _bn(x, ones, zeros, shift), x)
    assert float(jnp.mean(got == once)) > 0.999
    walk = 2.0 ** -9 * np.sqrt(np.asarray(jnp.sum(wide * wide, 0)))
    assert np.all(np.abs(over_channel(got) - over_channel(once)) < walk)
    twice = d_data(lambda x: _two_pass_bn(x, ones, zeros), x)
    assert float(jnp.mean(twice == once)) < 0.5
    assert np.all(np.abs(over_channel(twice)) > 20 * walk)

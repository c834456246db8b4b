"""Round-5 parity additions: conv RNN cells, LSTMP, FusedRNN initializer,
legacy FeedForward, kvstore_server role, contrib.tensorboard, download.

reference: gluon/contrib/rnn/conv_rnn_cell.py, contrib/rnn/rnn_cell.py
(LSTMPCell), initializer.py (FusedRNN), model.py (FeedForward),
kvstore_server.py, contrib/tensorboard.py, test_utils.py (download).
"""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, sym
from mxnet_tpu.gluon import contrib


# ---------------------------------------------------------------------------
# conv RNN cells
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cls,nstates", [
    (contrib.rnn.Conv2DLSTMCell, 2),
    (contrib.rnn.Conv2DGRUCell, 1),
    (contrib.rnn.Conv2DRNNCell, 1),
])
def test_conv2d_cells_unroll_and_grad(cls, nstates):
    cell = cls(input_shape=(3, 8, 8), hidden_channels=5,
               i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    cell.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).rand(2, 4, 3, 8, 8)
                 .astype(np.float32))
    with autograd.record():
        outs, states = cell.unroll(4, x, layout="NTC", merge_outputs=True)
        loss = outs.sum()
    loss.backward()
    assert outs.shape == (2, 4, 5, 8, 8)
    assert len(states) == nstates
    for s in states:
        assert s.shape == (2, 5, 8, 8)
    g = cell.i2h_weight.grad().asnumpy()
    assert np.abs(g).max() > 0


def test_conv_cells_1d_3d_state_shape():
    c1 = contrib.rnn.Conv1DLSTMCell(input_shape=(2, 10), hidden_channels=4,
                                    i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    c1.initialize()
    o, s = c1(nd.array(np.random.rand(2, 2, 10).astype(np.float32)),
              c1.begin_state(batch_size=2))
    assert o.shape == (2, 4, 10) and s[1].shape == (2, 4, 10)
    c3 = contrib.rnn.Conv3DGRUCell(input_shape=(1, 4, 4, 4),
                                   hidden_channels=2, i2h_kernel=3,
                                   h2h_kernel=3, i2h_pad=1)
    c3.initialize()
    o, _ = c3(nd.array(np.random.rand(2, 1, 4, 4, 4).astype(np.float32)),
              c3.begin_state(batch_size=2))
    assert o.shape == (2, 2, 4, 4, 4)


def test_conv_cell_even_h2h_kernel_rejected():
    with pytest.raises(ValueError):
        contrib.rnn.Conv2DLSTMCell(input_shape=(3, 8, 8), hidden_channels=5,
                                   i2h_kernel=3, h2h_kernel=4)


def test_conv_cell_spatial_reduction_state():
    # no i2h padding: state spatial shrinks to the conv output size
    cell = contrib.rnn.Conv2DRNNCell(input_shape=(3, 8, 8),
                                     hidden_channels=2, i2h_kernel=3,
                                     h2h_kernel=3)
    info = cell.state_info(batch_size=4)
    assert info[0]["shape"] == (4, 2, 6, 6)


def test_lstmp_cell_projection():
    p = contrib.rnn.LSTMPCell(16, 6)
    p.initialize()
    x = nd.array(np.random.rand(3, 8).astype(np.float32))
    with autograd.record():
        o, s = p(x, p.begin_state(batch_size=3))
        loss = o.sum()
    loss.backward()
    assert o.shape == (3, 6)
    assert s[0].shape == (3, 6) and s[1].shape == (3, 16)
    assert p.h2r_weight.grad().shape == (6, 16)


# ---------------------------------------------------------------------------
# FusedRNN initializer + fused sym.RNN binding
# ---------------------------------------------------------------------------
def test_fused_rnn_initializer_layout():
    init = mx.init.FusedRNN(mx.init.Xavier(), num_hidden=4, num_layers=2,
                            mode="lstm", forget_bias=1.0)
    arr = nd.zeros((352,))  # in=6: 4*4*(6+4) + 4*4*(4+4) + 2*2*16
    init("lstm_parameters", arr)
    v = arr.asnumpy()
    assert np.abs(v[:288]).max() > 0
    b = v[288:].reshape(4, 16)
    np.testing.assert_allclose(b[:, 4:8], 1.0)   # forget gates [i,f,g,o]
    np.testing.assert_allclose(b[:, :4], 0.0)
    np.testing.assert_allclose(b[:, 8:], 0.0)


def test_fused_rnn_cell_simple_bind_runs():
    """The packed-parameter shape is inferred from the data shape (RNN
    shape hint) and the bound executor runs — this path was unbindable
    before round 5."""
    import mxnet_tpu.rnn as mrnn
    cell = mrnn.FusedRNNCell(4, num_layers=2, mode="lstm")
    out, _ = cell.unroll(5, sym.Variable("data"), layout="NTC")
    ex = out.simple_bind(mx.cpu(), data=(2, 5, 6))
    assert ex.arg_dict["lstm_parameters"].shape == (352,)
    mx.init.FusedRNN(mx.init.Xavier(), 4, 2, "lstm")(
        "lstm_parameters", ex.arg_dict["lstm_parameters"])
    ex.forward(data=np.random.rand(2, 5, 6).astype(np.float32))
    assert ex.outputs[0].shape == (2, 5, 4)


# ---------------------------------------------------------------------------
# legacy FeedForward
# ---------------------------------------------------------------------------
def _ff_symbol():
    data = sym.Variable("data")
    fc = sym.FullyConnected(data, sym.Variable("fc_w"), sym.Variable("fc_b"),
                            num_hidden=16)
    act = sym.Activation(fc, act_type="relu")
    return sym.SoftmaxOutput(
        sym.FullyConnected(act, sym.Variable("o_w"), sym.Variable("o_b"),
                           num_hidden=3), name="softmax")


def test_feedforward_fit_score_predict_save_load(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(256, 8).astype(np.float32)
    W = rng.randn(8, 3).astype(np.float32)
    y = (X @ W).argmax(-1).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        model = mx.model.FeedForward(_ff_symbol(), num_epoch=12,
                                     learning_rate=0.5, numpy_batch_size=64)
        model.fit(X, y)
        acc = model.score(mx.io.NDArrayIter(X, y, batch_size=64))
        assert acc > 0.8, acc
        pred = model.predict(X)
        assert pred.shape == (256, 3)
        prefix = str(tmp_path / "ff")
        model.save(prefix, 1)
        m2 = mx.model.FeedForward.load(prefix, 1)
    assert set(m2.arg_params) == set(model.arg_params)


def test_feedforward_warns_deprecated():
    with pytest.warns(DeprecationWarning):
        mx.model.FeedForward(_ff_symbol())


# ---------------------------------------------------------------------------
# kvstore_server role contract
# ---------------------------------------------------------------------------
def test_server_role_never_runs_user_code():
    env = dict(os.environ, DMLC_ROLE="server", JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-c",
         "import mxnet_tpu; print('REACHED_USER_CODE')"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0
    assert "REACHED_USER_CODE" not in r.stdout


# ---------------------------------------------------------------------------
# contrib.tensorboard + download
# ---------------------------------------------------------------------------
def test_tensorboard_callback(tmp_path):
    pytest.importorskip("torch.utils.tensorboard")
    cb = mx.contrib.tensorboard.LogMetricsCallback(str(tmp_path), "train")
    m = mx.metric.create("acc")
    m.update([nd.array(np.array([1.0, 0.0]))],
             [nd.array(np.array([[0.1, 0.9], [0.8, 0.2]]))])

    class P:
        eval_metric = m
    cb(P())
    files = os.listdir(str(tmp_path))
    assert any("tfevents" in f for f in files), files


def test_test_utils_download_local(tmp_path):
    src = tmp_path / "weights.bin"
    src.write_bytes(b"abc123")
    out = mx.test_utils.download("file://" + str(src),
                                 dirname=str(tmp_path / "dl"),
                                 fname="w.bin")
    assert open(out, "rb").read() == b"abc123"


def test_feedforward_defaults_and_load_score(tmp_path):
    """Default optimizer params must not crash; score() must work on a
    freshly loaded model; predict() resets a consumed iterator."""
    rng = np.random.RandomState(1)
    X = rng.randn(128, 8).astype(np.float32)
    W = rng.randn(8, 3).astype(np.float32)
    y = (X @ W).argmax(-1).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        model = mx.model.FeedForward(_ff_symbol(), num_epoch=2,
                                     numpy_batch_size=64)
        model.fit(X, y)   # no optimizer kwargs: default lr path
        prefix = str(tmp_path / "ffd")
        model.save(prefix, 2)
        loaded = mx.model.FeedForward.load(prefix, 2)
        it = mx.io.NDArrayIter(X, y, batch_size=64)
        acc1 = loaded.score(it)          # score directly after load
        preds = loaded.predict(it)       # consumed iter: reset=True re-reads
    assert preds.shape == (128, 3)
    assert 0.0 <= acc1 <= 1.0


def test_fused_rnn_init_none_uses_global_init():
    """FusedRNN(None, ...) delegates weight blocks to the net's global
    initializer instead of leaving zeros (reference pattern)."""
    from mxnet_tpu.initializer import InitDesc
    init = mx.init.FusedRNN(None, num_hidden=4, num_layers=2, mode="lstm")
    arr = nd.zeros((352,))
    desc = InitDesc("lstm_parameters", global_init=mx.init.Xavier())
    init(desc, arr)
    assert np.abs(arr.asnumpy()[:288]).max() > 0


def test_feedforward_eval_data_tuple_and_predict_guard():
    rng = np.random.RandomState(2)
    X = rng.randn(128, 8).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        sym_out = _ff_symbol()
        with pytest.raises(RuntimeError):
            mx.model.FeedForward(sym_out).predict(X)
        m = mx.model.FeedForward(sym_out, num_epoch=1, numpy_batch_size=64)
        m.fit(X, y, eval_data=(X, y))  # tuple form, reference pattern


def test_image_op_namespace():
    """mx.nd.image / mx.sym.image / nd.linalg / sym.linalg / sym.sparse
    sub-namespaces (reference: python/mxnet/{ndarray,symbol}/{image,
    linalg,sparse}.py)."""
    rng = np.random.RandomState(0)
    img = nd.array((rng.rand(8, 6, 3) * 255).astype(np.uint8))
    t = mx.nd.image.to_tensor(img)
    assert t.shape == (3, 8, 6)
    np.testing.assert_allclose(t.asnumpy(),
                               img.asnumpy().transpose(2, 0, 1) / 255.0,
                               rtol=1e-6)
    nrm = mx.nd.image.normalize(t, mean=(0.5, 0.5, 0.5), std=(2, 2, 2))
    np.testing.assert_allclose(nrm.asnumpy(), (t.asnumpy() - 0.5) / 2.0,
                               rtol=1e-5)
    assert mx.nd.image.resize(img, size=(4, 5)).shape == (5, 4, 3)
    assert mx.nd.image.resize(img, size=4, keep_ratio=True).shape[1] == 4
    crop = mx.nd.image.crop(img, x=1, y=2, width=4, height=3)
    np.testing.assert_array_equal(crop.asnumpy(),
                                  img.asnumpy()[2:5, 1:5, :])
    # batched NHWC
    batch = nd.array((rng.rand(2, 8, 6, 3) * 255).astype(np.uint8))
    assert mx.nd.image.to_tensor(batch).shape == (2, 3, 8, 6)
    np.testing.assert_array_equal(
        mx.nd.image.flip_top_bottom(batch).asnumpy(),
        batch.asnumpy()[:, ::-1])
    # symbolic composition binds and runs
    s = mx.sym.image.to_tensor(mx.sym.Variable("img"))
    ex = s.simple_bind(mx.cpu(), img=(8, 6, 3))
    ex.forward(img=img.asnumpy())
    assert ex.outputs[0].shape == (3, 8, 6)
    out = mx.sym.linalg.gemm2(mx.sym.Variable("a"), mx.sym.Variable("b"))
    ex2 = out.simple_bind(mx.cpu(), a=(3, 4), b=(4, 2))
    ex2.forward(a=np.ones((3, 4), np.float32), b=np.ones((4, 2), np.float32))
    np.testing.assert_allclose(ex2.outputs[0].asnumpy(),
                               4.0 * np.ones((3, 2)))
    assert hasattr(mx.sym.sparse, "dot")


def test_conv_lstm_hybridize_parity_and_checkpoint(tmp_path):
    """Conv cells hybridize to the same numbers and roundtrip through
    save_parameters/load_parameters."""
    rng = np.random.RandomState(5)
    x = nd.array(rng.rand(2, 3, 3, 8, 8).astype(np.float32))

    def build():
        c = contrib.rnn.Conv2DLSTMCell(input_shape=(3, 8, 8),
                                       hidden_channels=4, i2h_kernel=3,
                                       h2h_kernel=3, i2h_pad=1,
                                       prefix="clstm_")
        return c
    cell = build()
    cell.initialize(mx.init.Xavier())
    out_e, _ = cell.unroll(3, x, layout="NTC", merge_outputs=True)
    cell.hybridize()
    out_h, _ = cell.unroll(3, x, layout="NTC", merge_outputs=True)
    np.testing.assert_allclose(out_e.asnumpy(), out_h.asnumpy(),
                               rtol=2e-5, atol=2e-6)
    f = str(tmp_path / "clstm.params")
    cell.save_parameters(f)
    cell2 = build()
    cell2.load_parameters(f)
    out_l, _ = cell2.unroll(3, x, layout="NTC", merge_outputs=True)
    np.testing.assert_allclose(out_l.asnumpy(), out_e.asnumpy(), rtol=2e-5,
                               atol=2e-6)


# ---------------------------------------------------------------------------
# legacy rnn: pack/unpack, checkpoints, zoneout, encode_sentences
# ---------------------------------------------------------------------------
def test_fused_unfused_weight_interchange(tmp_path):
    """Fused sym.RNN vs the unfused cell stack must agree numerically
    under exchanged (unpacked) weights — cross-validates the packed
    layout, the lax.scan kernel, and unfuse() in one assert (reference:
    FusedRNNCell.unpack_weights/unfuse)."""
    import mxnet_tpu.rnn as mrnn
    cell = mrnn.FusedRNNCell(4, num_layers=2, mode="lstm", prefix="lstm_")
    out, _ = cell.unroll(5, sym.Variable("data"), layout="NTC")
    ex = out.simple_bind(mx.cpu(), data=(2, 5, 6))
    mx.init.FusedRNN(mx.init.Xavier(), 4, 2, "lstm")(
        "lstm_parameters", ex.arg_dict["lstm_parameters"])
    x = np.random.RandomState(0).rand(2, 5, 6).astype(np.float32)
    ex.forward(data=x)
    fused_out = ex.outputs[0].asnumpy()

    args = {"lstm_parameters": ex.arg_dict["lstm_parameters"]}
    unpacked = cell.unpack_weights(args)
    stack = cell.unfuse()
    uout, _ = stack.unroll(5, sym.Variable("data"), layout="NTC",
                           merge_outputs=True)
    shapes = {"lstm_l%d_begin_state_%d" % (i, j): (2, 4)
              for i in range(2) for j in range(2)}
    ex2 = uout.simple_bind(mx.cpu(), data=(2, 5, 6), **shapes)
    for k, v in unpacked.items():
        if k in ex2.arg_dict:
            ex2.arg_dict[k][:] = v.asnumpy()
    ex2.forward(data=x)
    np.testing.assert_allclose(fused_out, ex2.outputs[0].asnumpy(),
                               rtol=2e-5, atol=2e-6)
    # pack is the exact inverse
    repacked = cell.pack_weights(unpacked)
    np.testing.assert_allclose(repacked["lstm_parameters"].asnumpy(),
                               args["lstm_parameters"].asnumpy(), rtol=1e-6)
    # checkpoint helpers roundtrip through the unpacked form
    mrnn.save_rnn_checkpoint(cell, str(tmp_path / "cp"), 3, out,
                             dict(args), {})
    _, arg2, _ = mrnn.load_rnn_checkpoint(cell, str(tmp_path / "cp"), 3)
    assert "lstm_l0_i2h_weight" in arg2 and \
        "lstm_parameters" not in arg2


def test_legacy_zoneout_and_encode():
    import mxnet_tpu.rnn as mrnn
    z = mrnn.ZoneoutCell(mrnn.LSTMCell(4, prefix="zl_"),
                         zoneout_states=0.1)
    outs, st = z.unroll(3, sym.Variable("data"))
    assert len(outs) == 3 and len(st) == 2
    coded, vocab = mrnn.encode_sentences([["a", "b"], ["b", "c"]],
                                         start_label=1)
    assert coded == [[1, 2], [2, 3]]
    # closed vocab raises on unknown without unknown_token
    with pytest.raises(ValueError):
        mrnn.encode_sentences([["zzz"]], vocab=dict(vocab))


def test_fused_unfused_bidirectional_interchange():
    """Bidirectional: fused kernel vs BidirectionalCell stack under
    exchanged weights (reference: unfuse wraps layers in
    BidirectionalCell)."""
    import mxnet_tpu.rnn as mrnn
    cell = mrnn.FusedRNNCell(3, num_layers=1, mode="lstm",
                             bidirectional=True, prefix="blstm_")
    out, _ = cell.unroll(4, sym.Variable("data"), layout="NTC")
    ex = out.simple_bind(mx.cpu(), data=(2, 4, 5))
    mx.init.FusedRNN(mx.init.Xavier(), 3, 1, "lstm", bidirectional=True)(
        "blstm_parameters", ex.arg_dict["blstm_parameters"])
    x = np.random.RandomState(1).rand(2, 4, 5).astype(np.float32)
    ex.forward(data=x)
    fused_out = ex.outputs[0].asnumpy()
    assert fused_out.shape == (2, 4, 6)   # 2*hidden concat

    unpacked = cell.unpack_weights(
        {"blstm_parameters": ex.arg_dict["blstm_parameters"]})
    assert "blstm_l0_r_i2h_weight" in unpacked
    stack = cell.unfuse()
    uout, _ = stack.unroll(4, sym.Variable("data"), layout="NTC",
                           merge_outputs=True)
    shapes = {}
    for name in uout.list_arguments():
        if "begin_state" in name:
            shapes[name] = (2, 3)
    ex2 = uout.simple_bind(mx.cpu(), data=(2, 4, 5), **shapes)
    for k, v in unpacked.items():
        if k in ex2.arg_dict:
            ex2.arg_dict[k][:] = v.asnumpy()
    ex2.forward(data=x)
    np.testing.assert_allclose(fused_out, ex2.outputs[0].asnumpy(),
                               rtol=2e-5, atol=2e-6)


def test_interval_sampler_and_test_utils():
    s = gluon.contrib.data.IntervalSampler(10, 3)
    order = list(s)
    assert sorted(order) == list(range(10)) and order[:4] == [0, 3, 6, 9]
    assert list(gluon.contrib.data.IntervalSampler(10, 3,
                                                   rollover=False)) == \
        [0, 3, 6, 9]
    arr, dense = mx.test_utils.rand_sparse_ndarray((6, 3), "row_sparse",
                                                   density=0.5)
    np.testing.assert_allclose(arr.tostype("default").asnumpy(), dense)
    a = sym.FullyConnected(sym.Variable("x"), sym.Variable("w"),
                           sym.Variable("b"), num_hidden=4)
    b = sym.FullyConnected(sym.Variable("q"), sym.Variable("r"),
                           sym.Variable("t"), num_hidden=4)
    assert mx.test_utils.same_symbol_structure(a, b)
    assert not mx.test_utils.same_symbol_structure(a, sym.softmax(a))

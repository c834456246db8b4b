"""RNN layers/cells + gluon.data tests — modeled on reference
tests/python/unittest/test_gluon_rnn.py and test_gluon_data.py."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn, rnn


def test_rnn_cells_forward():
    for cell_cls, n_states in [(rnn.RNNCell, 1), (rnn.LSTMCell, 2),
                               (rnn.GRUCell, 1)]:
        cell = cell_cls(10, input_size=6, prefix="%s_" %
                        cell_cls.__name__.lower())
        cell.initialize()
        x = mx.nd.ones((4, 6))
        states = cell.begin_state(batch_size=4)
        out, new_states = cell(x, states)
        assert out.shape == (4, 10)
        assert len(new_states) == n_states


def test_rnn_cell_unroll():
    cell = rnn.LSTMCell(8, input_size=5)
    cell.initialize()
    x = mx.nd.ones((2, 3, 5))  # NTC
    outputs, states = cell.unroll(3, x, layout="NTC", merge_outputs=False)
    assert len(outputs) == 3
    assert outputs[0].shape == (2, 8)
    assert len(states) == 2


def test_sequential_rnn_cell():
    stack = rnn.SequentialRNNCell()
    stack.add(rnn.LSTMCell(8, input_size=5))
    stack.add(rnn.LSTMCell(8, input_size=8))
    stack.initialize()
    x = mx.nd.ones((2, 5))
    states = stack.begin_state(batch_size=2)
    assert len(states) == 4
    out, new_states = stack(x, states)
    assert out.shape == (2, 8)


def test_residual_bidirectional_cells():
    cell = rnn.ResidualCell(rnn.GRUCell(5, input_size=5))
    cell.initialize()
    x = mx.nd.ones((2, 3, 5))
    outputs, _ = cell.unroll(3, x, merge_outputs=False)
    assert outputs[0].shape == (2, 5)

    bi = rnn.BidirectionalCell(rnn.LSTMCell(4, input_size=5),
                               rnn.LSTMCell(4, input_size=5))
    bi.initialize()
    outputs, states = bi.unroll(3, x, merge_outputs=False)
    assert outputs[0].shape == (2, 8)


@pytest.mark.parametrize("layer_cls,mode_states",
                         [(rnn.LSTM, 2), (rnn.GRU, 1), (rnn.RNN, 1)])
def test_fused_rnn_layer(layer_cls, mode_states):
    layer = layer_cls(hidden_size=8, num_layers=2, layout="TNC")
    layer.initialize()
    x = mx.nd.ones((5, 3, 6))  # T, N, C
    out = layer(x)
    assert out.shape == (5, 3, 8)
    states = layer.begin_state(batch_size=3)
    out, new_states = layer(x, states)
    assert out.shape == (5, 3, 8)
    assert len(new_states) == mode_states
    assert new_states[0].shape == (2, 3, 8)


def test_fused_rnn_bidirectional_ntc():
    layer = rnn.LSTM(hidden_size=4, num_layers=1, layout="NTC",
                     bidirectional=True)
    layer.initialize()
    x = mx.nd.ones((3, 5, 6))
    out = layer(x)
    assert out.shape == (3, 5, 8)


def test_fused_lstm_matches_cell():
    """Fused lax.scan LSTM must agree with the unfused cell math."""
    T, N, C, H = 4, 2, 3, 5
    layer = rnn.LSTM(hidden_size=H, num_layers=1, input_size=C)
    layer.initialize()
    cell = rnn.LSTMCell(H, input_size=C)
    cell.initialize()
    # copy fused params into the cell
    cell.i2h_weight.set_data(layer.l0_i2h_weight.data())
    cell.h2h_weight.set_data(layer.l0_h2h_weight.data())
    cell.i2h_bias.set_data(layer.l0_i2h_bias.data())
    cell.h2h_bias.set_data(layer.l0_h2h_bias.data())
    x = mx.nd.array(np.random.normal(size=(T, N, C)).astype("float32"))
    fused_out = layer(x).asnumpy()
    cell_out, _ = cell.unroll(T, x, layout="TNC", merge_outputs=False)
    for t in range(T):
        np.testing.assert_allclose(fused_out[t], cell_out[t].asnumpy(),
                                   rtol=1e-4, atol=1e-5)


def test_rnn_gradient_flows():
    layer = rnn.LSTM(hidden_size=8, num_layers=1)
    layer.initialize()
    x = mx.nd.ones((5, 3, 6))
    with autograd.record():
        out = layer(x)
        loss = out.sum()
    loss.backward()
    g = layer.l0_i2h_weight.grad()
    assert float(g.abs().sum().asscalar()) > 0


def test_dataset_and_dataloader():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    x = np.random.uniform(size=(40, 3)).astype("float32")
    y = np.arange(40).astype("float32")
    ds = ArrayDataset(x, y)
    assert len(ds) == 40
    loader = DataLoader(ds, batch_size=8, shuffle=True)
    batches = list(loader)
    assert len(batches) == 5
    assert batches[0][0].shape == (8, 3)
    # last_batch handling
    loader = DataLoader(ds, batch_size=16, last_batch="discard")
    assert len(list(loader)) == 2
    loader = DataLoader(ds, batch_size=16, last_batch="keep")
    batches = list(loader)
    assert batches[-1][0].shape[0] == 8


def test_dataloader_multiworker():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    x = np.random.uniform(size=(32, 4)).astype("float32")
    y = np.arange(32).astype("float32")
    loader = DataLoader(ArrayDataset(x, y), batch_size=8, num_workers=2)
    seen = []
    for data, label in loader:
        assert data.shape == (8, 4)
        seen.extend(label.asnumpy().tolist())
    assert sorted(seen) == list(range(32))


class _PlatformProbe:
    """Each sample is 1.0 where the process that serves it was started with
    JAX_PLATFORMS=cpu."""

    def __len__(self):
        return 4

    def __getitem__(self, idx):
        return np.float32(os.environ.get("JAX_PLATFORMS") == "cpu")


@pytest.mark.parametrize("parent", [None, "tpu,cpu"])
def test_dataloader_workers_start_cpu_only(monkeypatch, parent):
    """The parent owns the chip: a spawned worker sees JAX_PLATFORMS=cpu
    from its first instruction, and the parent's own value is put back."""
    from mxnet_tpu.gluon.data import DataLoader
    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    loader = DataLoader(_PlatformProbe(), batch_size=4, num_workers=1)
    assert os.environ.get("JAX_PLATFORMS") == parent
    (batch,) = list(loader)
    assert batch.asnumpy().tolist() == [1.0] * 4
    assert os.environ.get("JAX_PLATFORMS") == parent


def test_dataset_transform_shard():
    from mxnet_tpu.gluon.data import SimpleDataset
    ds = SimpleDataset(list(range(10)))
    t = ds.transform(lambda x: x * 2)
    assert t[3] == 6
    s = ds.shard(3, 0)
    assert len(s) == 4  # 10 = 4+3+3
    assert s[0] == 0


def test_mnist_synthetic_and_training():
    """Config-1 milestone: MLP on MNIST via gluon.data pipeline."""
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.vision import MNIST, transforms
    ds = MNIST(root="/tmp/mxtpu_mnist", train=True)
    img, label = ds[0]
    assert img.shape == (28, 28, 1)
    tfm = transforms.Compose([transforms.ToTensor()])
    ds_t = ds.transform_first(tfm)
    loader = DataLoader(ds_t.take(512), batch_size=64, shuffle=True)

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    first = last = None
    for epoch in range(2):
        for data, label in loader:
            data = data.reshape((data.shape[0], -1))
            with autograd.record():
                loss = loss_fn(net(data), label)
            loss.backward()
            trainer.step(data.shape[0])
            cur = float(loss.mean().asscalar())
            if first is None:
                first = cur
            last = cur
    assert last < first


def test_recordio_roundtrip(tmp_path):
    from mxnet_tpu import recordio
    rec_path = str(tmp_path / "test.rec")
    idx_path = str(tmp_path / "test.idx")
    writer = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    payloads = []
    for i in range(5):
        header = recordio.IRHeader(0, float(i), i, 0)
        data = recordio.pack(header, bytes([i]) * (i * 7 + 1))
        payloads.append(data)
        writer.write_idx(i, data)
    writer.close()

    reader = recordio.MXIndexedRecordIO(idx_path, rec_path, "r")
    for i in [3, 0, 4]:
        rec = reader.read_idx(i)
        header, content = recordio.unpack(rec)
        assert header.label == float(i)
        assert content == bytes([i]) * (i * 7 + 1)
    reader.close()


def test_image_record_dataset(tmp_path):
    from mxnet_tpu import recordio
    from mxnet_tpu.gluon.data.vision import ImageRecordDataset
    rec_path = str(tmp_path / "imgs.rec")
    idx_path = str(tmp_path / "imgs.idx")
    writer = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    for i in range(4):
        img = np.random.randint(0, 255, size=(8, 8, 3)).astype("uint8")
        header = recordio.IRHeader(0, float(i % 2), i, 0)
        writer.write_idx(i, recordio.pack_img(header, img))
    writer.close()
    ds = ImageRecordDataset(rec_path)
    img, label = ds[2]
    assert img.shape == (8, 8, 3)
    assert label == 0.0


def test_image_ops():
    from mxnet_tpu import image
    img = mx.nd.array(np.random.randint(0, 255, size=(20, 30, 3)),
                      dtype="uint8")
    resized = image.imresize(img, 15, 10)
    assert resized.shape == (10, 15, 3)
    short = image.resize_short(img, 10)
    assert min(short.shape[:2]) == 10
    crop, _ = image.center_crop(img, (8, 8))
    assert crop.shape == (8, 8, 3)
    augs = image.CreateAugmenter((3, 8, 8), rand_mirror=True, mean=True,
                                 std=True)
    out = img
    for aug in augs:
        out = aug(out)
    assert out.shape == (8, 8, 3)


def test_dataloader_shm_transport():
    """Spawn workers return batches through POSIX shared memory (reference:
    cpu_shared storage manager) — the pickled payload is just descriptors."""
    from mxnet_tpu.gluon.data.dataloader import (_batch_to_shm,
                                                 _batch_from_shm, _ShmBatch)
    rng = np.random.RandomState(0)
    batch = [rng.randn(8, 4).astype(np.float32),
             rng.randint(0, 5, (8,)).astype(np.float32)]
    sb = _batch_to_shm(batch)
    assert isinstance(sb, _ShmBatch)
    import pickle
    assert len(pickle.dumps(sb)) < 512  # descriptors only, not the data
    out = _batch_from_shm(sb, mx.cpu())
    np.testing.assert_array_equal(out[0].asnumpy(), batch[0])
    np.testing.assert_array_equal(out[1].asnumpy(), batch[1])


def test_dataloader_multiworker_uses_shm():
    ds = gluon.data.ArrayDataset(
        np.arange(64, dtype=np.float32).reshape(16, 4),
        np.arange(16, dtype=np.float32))
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2)
    seen = 0
    for x, y in loader:
        assert x.shape == (4, 4)
        seen += 1
    assert seen == 4

"""Gluon tests (model: tests/python/unittest/test_gluon*.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def test_dense_forward():
    layer = nn.Dense(5, in_units=3)
    layer.initialize()
    x = nd.array(np.random.rand(2, 3))
    out = layer(x)
    assert out.shape == (2, 5)
    w = layer.weight.data().asnumpy()
    b = layer.bias.data().asnumpy()
    assert np.allclose(out.asnumpy(), x.asnumpy() @ w.T + b, atol=1e-5)


def test_deferred_init():
    layer = nn.Dense(4)
    layer.initialize()
    out = layer(nd.array(np.random.rand(2, 7)))
    assert layer.weight.shape == (4, 7)
    assert out.shape == (2, 4)


def test_sequential_and_children():
    net = nn.Sequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
    net.initialize()
    assert len(net) == 2
    out = net(nd.array(np.random.rand(3, 5)))
    assert out.shape == (3, 2)


def test_hybridize_consistency():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(8))
    net.initialize()
    x = nd.array(np.random.rand(4, 10))
    eager = net(x).asnumpy()
    net.hybridize()
    jit1 = net(x).asnumpy()
    jit2 = net(x).asnumpy()
    assert np.allclose(eager, jit1, atol=1e-5)
    assert np.allclose(jit1, jit2, atol=1e-6)


def test_hybridized_gradients_match_eager():
    def run(hybridize):
        np.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(6, activation="tanh", in_units=4), nn.Dense(3, in_units=6))
        net.initialize()
        if hybridize:
            net.hybridize()
        x = nd.array(np.random.RandomState(3).rand(5, 4))
        with autograd.record():
            out = net(x).sum()
        out.backward()
        # pair by structural (insertion) order, NOT by sorted global names:
        # gluon's name counters are process-global, so sorted() pairing
        # breaks whenever earlier tests push the counter across a digit
        # boundary (dense9_ vs dense10_)
        return [p.grad().asnumpy()
                for _, p in net.collect_params().items()
                if p.grad_req != "null"]

    g_eager = run(False)
    g_jit = run(True)
    for i, (v1, v2) in enumerate(zip(g_eager, g_jit)):
        assert np.allclose(v1, v2, atol=1e-4), i


def test_conv_block():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, kernel_size=3, padding=1, activation="relu"),
            nn.MaxPool2D(2, 2),
            nn.Flatten(),
            nn.Dense(4))
    net.initialize()
    out = net(nd.array(np.random.rand(2, 3, 8, 8)))
    assert out.shape == (2, 4)


def test_batchnorm_train_vs_eval():
    net = nn.BatchNorm(in_channels=3)
    net.initialize()
    x = nd.array((np.random.rand(16, 3, 4, 4) * 5 + 2).astype(np.float32))
    with autograd.record():
        y_train = net(x)
    # training output ~ normalized per-batch
    m = y_train.asnumpy().mean(axis=(0, 2, 3))
    assert np.allclose(m, 0, atol=1e-2)
    # running stats moved toward batch stats
    rm = net.running_mean.data().asnumpy()
    assert not np.allclose(rm, 0)
    y_eval = net(x)
    assert not np.allclose(y_eval.asnumpy(), y_train.asnumpy(), atol=1e-3)


def test_trainer_step_sgd():
    net = nn.Dense(1, in_units=1, use_bias=False)
    net.initialize(mx.init.One())
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = nd.array([[2.0]])
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(1)
    # w=1, x=2 → y=2, loss=y², dL/dw = 2*y*x = 8; w' = 1 - 0.1*8 = 0.2
    assert np.allclose(net.weight.data().asnumpy(), [[0.2]], atol=1e-5)


def test_losses():
    pred = nd.array(np.random.rand(4, 5))
    label = nd.array(np.array([1.0, 0, 3, 2]))
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    assert l.shape == (4,)
    p = np.exp(pred.asnumpy() - pred.asnumpy().max(1, keepdims=True))
    p = p / p.sum(1, keepdims=True)
    expect = -np.log(p[np.arange(4), label.asnumpy().astype(int)])
    assert np.allclose(l.asnumpy(), expect, atol=1e-4)

    l2 = gluon.loss.L2Loss()(pred, nd.zeros((4, 5)))
    assert np.allclose(l2.asnumpy(), (pred.asnumpy() ** 2).mean(axis=1) / 2, atol=1e-5)

    l1 = gluon.loss.L1Loss()(pred, nd.zeros((4, 5)))
    assert np.allclose(l1.asnumpy(), np.abs(pred.asnumpy()).mean(axis=1), atol=1e-5)

    bce = gluon.loss.SigmoidBCELoss()(pred, nd.ones((4, 5)))
    x = pred.asnumpy()
    expect = (np.maximum(x, 0) - x * 1 + np.log1p(np.exp(-np.abs(x)))).mean(axis=1)
    assert np.allclose(bce.asnumpy(), expect, atol=1e-4)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net.initialize()
    f = str(tmp_path / "p.params")
    net.save_parameters(f)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(8, in_units=4), nn.Dense(2, in_units=8))
    net2.load_parameters(f)
    x = nd.array(np.random.rand(2, 4))
    assert np.allclose(net(x).asnumpy(), net2(x).asnumpy(), atol=1e-6)


def test_lstm_layer():
    layer = gluon.rnn.LSTM(hidden_size=8, num_layers=2)
    layer.initialize()
    x = nd.array(np.random.rand(5, 3, 4))  # (T, N, C)
    out = layer(x)
    assert out.shape == (5, 3, 8)
    states = layer.begin_state(batch_size=3)
    out, new_states = layer(x, states)
    assert out.shape == (5, 3, 8)
    assert new_states[0].shape == (2, 3, 8)
    assert new_states[1].shape == (2, 3, 8)


def test_gru_bidirectional():
    layer = gluon.rnn.GRU(hidden_size=6, num_layers=1, bidirectional=True)
    layer.initialize()
    x = nd.array(np.random.rand(4, 2, 5))
    out = layer(x)
    assert out.shape == (4, 2, 12)


def test_lstm_cell_unroll():
    cell = gluon.rnn.LSTMCell(hidden_size=8, input_size=4)
    cell.initialize()
    x = nd.array(np.random.rand(3, 6, 4))  # (N, T, C)
    outputs, states = cell.unroll(6, x, layout="NTC")
    assert outputs.shape == (3, 6, 8)
    assert states[0].shape == (3, 8)


def test_rnn_cell_gradient_flows():
    cell = gluon.rnn.RNNCell(hidden_size=4, input_size=3)
    cell.initialize()
    x = nd.array(np.random.rand(2, 5, 3))
    with autograd.record():
        outputs, _ = cell.unroll(5, x, layout="NTC")
        loss = outputs.sum()
    loss.backward()
    g = cell.i2h_weight.grad().asnumpy()
    assert np.abs(g).sum() > 0


def test_model_zoo_smoke():
    # squeezenet's head is the reference's fixed AvgPool2D(13), so it needs
    # a 224px input; the others accept small frames
    for name, sz in (("resnet18_v1", 32), ("resnet18_v2", 32),
                     ("mobilenet0_25", 32), ("squeezenet1_1", 224)):
        net = gluon.model_zoo.vision.get_model(name, classes=10)
        net.initialize()
        out = net(nd.array(np.random.rand(1, 3, sz, sz)))
        assert out.shape == (1, 10), name


@pytest.mark.parametrize("name,sz", [
    ("alexnet", 224), ("vgg11", 224), ("densenet121", 224),
    ("mobilenet_v2_0_25", 96), ("inception_v3", 299)])
def test_model_zoo_all_families(name, sz):
    # one representative per remaining family (reference:
    # python/mxnet/gluon/model_zoo/vision/ — alexnet/vgg/densenet/
    # mobilenet_v2/inception); string weight_initializer + HybridLambda
    # (relu6) + positional-scalar op attrs exercised here
    # sizes each architecture actually supports: densenet's head is a
    # fixed AvgPool2D(7) (reference), so inputs must reach a 7x7 final map
    net = gluon.model_zoo.vision.get_model(name, classes=10)
    net.initialize()
    out = net(nd.array(np.random.rand(1, 3, sz, sz)))
    assert out.shape == (1, 10)


def test_dataloader():
    X = np.random.rand(20, 3).astype(np.float32)
    Y = np.arange(20).astype(np.float32)
    ds = gluon.data.ArrayDataset(X, Y)
    loader = gluon.data.DataLoader(ds, batch_size=6, shuffle=False)
    batches = list(loader)
    assert len(batches) == 4
    xb, yb = batches[0]
    assert xb.shape == (6, 3)
    assert np.allclose(yb.asnumpy(), [0, 1, 2, 3, 4, 5])
    loader2 = gluon.data.DataLoader(ds, batch_size=6, shuffle=False,
                                    last_batch="discard", num_workers=2)
    assert len(list(loader2)) == 3


def test_vision_dataset_transform():
    ds = gluon.data.vision.MNIST(train=False)
    assert len(ds) > 0
    img, label = ds[0]
    assert img.shape == (28, 28, 1)
    tf = gluon.data.vision.transforms.ToTensor()
    out = tf(img)
    assert out.shape == (1, 28, 28)
    assert float(out.max()) <= 1.0


def test_clip_global_norm():
    arrays = [nd.array([3.0]), nd.array([4.0])]
    norm = gluon.utils.clip_global_norm(arrays, 1.0)
    assert abs(norm - 5.0) < 1e-5
    total = np.sqrt(sum(float((a * a).sum()) for a in arrays))
    assert total <= 1.01


def test_split_and_load():
    data = nd.array(np.arange(12).reshape(6, 2))
    parts = gluon.utils.split_and_load(data, [mx.cpu(), mx.cpu()])
    assert len(parts) == 2
    assert parts[0].shape == (3, 2)


def test_gluon_contrib_blocks():
    # reference: gluon/contrib — Concurrent, conv RNN cells, variational
    # dropout (mask fixed across steps)
    import numpy as np

    from mxnet_tpu.gluon.contrib import nn as cnn, rnn as crnn

    net = cnn.HybridConcurrent(axis=1)
    net.add(gluon.nn.Dense(3), gluon.nn.Dense(5))
    net.initialize()
    out = net(nd.array(np.random.rand(2, 4).astype(np.float32)))
    assert out.shape == (2, 8)

    cell = crnn.Conv2DLSTMCell((2, 8, 8), hidden_channels=4)
    cell.initialize()
    x = nd.array(np.random.rand(1, 2, 8, 8).astype(np.float32))
    out, st = cell(x, cell.begin_state(batch_size=1))
    assert out.shape == (1, 4, 8, 8) and len(st) == 2

    base = gluon.rnn.LSTMCell(8, input_size=4)
    vd = crnn.VariationalDropoutCell(base, drop_inputs=0.5)
    vd.initialize()
    xs = nd.array(np.random.rand(2, 5, 4).astype(np.float32))
    outs, _ = vd.unroll(5, xs, merge_outputs=True)
    assert outs.shape == (2, 5, 8)


def test_dataloader_process_workers_shm():
    """Fork-based worker pool returning batches through shared memory
    (reference: gluon/data/dataloader.py multiprocessing + shm NDArrays,
    src/storage/cpu_shared_storage_manager.h; fork safety via the
    initialize.cc-analogue handlers in mxnet_tpu._fork)."""
    X = np.arange(80, dtype=np.float32).reshape(20, 4)
    Y = np.arange(20, dtype=np.float32)
    ds = gluon.data.ArrayDataset(X, Y)
    dl = gluon.data.DataLoader(ds, batch_size=5, num_workers=2,
                               thread_pool=False)
    seen = []
    for xb, yb in dl:
        assert xb.shape == (5, 4) and yb.shape == (5,)
        seen.extend(yb.asnumpy().tolist())
    assert sorted(seen) == list(range(20))
    # second epoch reuses the pool
    n = sum(1 for _ in dl)
    assert n == 4
    # parent jax still healthy after forks (engine handlers did their job)
    assert float(nd.array(np.ones(3)).sum().asnumpy()) == 3.0


def test_contrib_sync_batch_norm_layer():
    """gluon.contrib.nn.SyncBatchNorm: reference constructor surface,
    BatchNorm semantics under one program (global batch is implicit)."""
    bn = gluon.contrib.nn.SyncBatchNorm(num_devices=8)
    bn.initialize()
    x = nd.array(np.random.RandomState(0).rand(4, 3, 5, 5)
                 .astype(np.float32) * 2)
    from mxnet_tpu import autograd
    with autograd.record():
        y = bn(x)
    ref = gluon.nn.BatchNorm()
    ref.initialize()
    with autograd.record():
        y2 = ref(x)
    assert np.allclose(y.asnumpy(), y2.asnumpy(), atol=1e-5)


def test_split_data_uneven():
    data = nd.array(np.arange(10, dtype=np.float32).reshape(10, 1))
    with pytest.raises(ValueError):
        gluon.utils.split_data(data, 3)  # 10 % 3 != 0, even_split=True
    parts = gluon.utils.split_data(data, 3, even_split=False)
    # reference semantics: equal slices, remainder on the LAST one
    assert [p.shape[0] for p in parts] == [3, 3, 4]
    got = np.concatenate([p.asnumpy() for p in parts])
    np.testing.assert_allclose(got, data.asnumpy())


def test_check_sha1_and_download_shortcircuit(tmp_path):
    import hashlib

    f = tmp_path / "blob.bin"
    f.write_bytes(b"mxtpu-test-payload")
    sha = hashlib.sha1(b"mxtpu-test-payload").hexdigest()
    assert gluon.utils.check_sha1(str(f), sha)
    assert not gluon.utils.check_sha1(str(f), "0" * 40)
    # a present file with the right hash must short-circuit (no egress)
    out = gluon.utils.download("http://invalid.invalid/blob.bin",
                               path=str(f), sha1_hash=sha)
    assert out == str(f)
    # a corrupt/absent file still refuses (no silent use of a bad blob)
    with pytest.raises(RuntimeError):
        gluon.utils.download("http://invalid.invalid/blob.bin",
                             path=str(f), sha1_hash="0" * 40)


def test_clip_global_norm_noop_below_threshold():
    arrays = [nd.array(np.array([0.3, 0.4], np.float32))]
    before = arrays[0].asnumpy().copy()
    norm = gluon.utils.clip_global_norm(arrays, 10.0)
    assert abs(norm - 0.5) < 1e-6
    np.testing.assert_allclose(arrays[0].asnumpy(), before)


def test_export_produces_real_symbol_and_roundtrips(tmp_path):
    """export() writes a TRACED symbol (not a stub) that reloads through
    SymbolBlock.imports AND binds as a plain Symbol — the deploy contract
    (reference gluon/block.py HybridBlock.export + SymbolBlock.imports)."""
    rs = np.random.RandomState(0)
    cnn = gluon.nn.HybridSequential()
    cnn.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"), gluon.nn.MaxPool2D(),
            gluon.nn.Flatten(), gluon.nn.Dense(2))
    cnn.initialize()
    x = nd.array(rs.rand(2, 3, 8, 8).astype(np.float32))
    want = cnn(x).asnumpy()  # eval-mode BN
    path = str(tmp_path / "net")
    cnn.export(path, epoch=3)

    back = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                     path + "-0003.params")
    np.testing.assert_allclose(back(x).asnumpy(), want, rtol=1e-4,
                               atol=1e-5)
    # the symbol is a real graph with aux states classified
    sym = mx.sym.load(path + "-symbol.json")
    aux = sym.list_auxiliary_states()
    # name counters are process-global: match by suffix, not exact prefix
    assert any(a.endswith("_running_mean") for a in aux), aux
    assert any(a.endswith("_running_var") for a in aux), aux
    assert len(sym.list_arguments()) > 1
    # params file uses arg:/aux: prefixes (Module.load_checkpoint format)
    loaded = mx.nd.load(path + "-0003.params")
    assert any(k.startswith("aux:") for k in loaded)
    assert any(k.startswith("arg:") for k in loaded)


def test_export_shared_subblock_single_var(tmp_path):
    """A sub-block invoked twice in one forward exports ONE variable per
    parameter (cached Parameter.var), so positional bind lists align."""
    class Twice(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = gluon.nn.Dense(4, in_units=4)

        def hybrid_forward(self, F, x):
            return self.d(x) + self.d(self.d(x))

    net = Twice()
    net.initialize()
    x = nd.array(np.random.RandomState(0).rand(2, 4).astype(np.float32))
    want = net(x).asnumpy()
    path = str(tmp_path / "twice")
    net.export(path)
    sym = mx.sym.load(path + "-symbol.json")
    args = sym.list_arguments()
    assert len(args) == len(set(args)), args  # no duplicate names
    back = gluon.SymbolBlock.imports(path + "-symbol.json", ["data"],
                                     path + "-0000.params")
    np.testing.assert_allclose(back(x).asnumpy(), want, rtol=1e-5,
                               atol=1e-6)


def test_contrib_conv_cells_1d_3d_and_lstmp():
    """The reference's full contrib cell matrix: 1D/3D conv recurrences and
    the projection LSTM (contrib/rnn/{conv_rnn_cell,rnn_cell}.py)."""
    C = gluon.contrib.rnn
    rs = np.random.RandomState(0)

    c1 = C.Conv1DGRUCell((2, 8), 3)
    c1.initialize()
    outs, states = c1.unroll(4, nd.array(rs.rand(2, 4, 2, 8)
                                         .astype(np.float32)),
                             merge_outputs=False)
    assert outs[0].shape == (2, 3, 8) and len(states) == 1

    c3 = C.Conv3DRNNCell((2, 3, 4, 5), 2)
    c3.initialize()
    outs, states = c3.unroll(3, nd.array(rs.rand(1, 3, 2, 3, 4, 5)
                                         .astype(np.float32)),
                             merge_outputs=False)
    assert outs[0].shape == (1, 2, 3, 4, 5)

    # kernel rank must match the spatial rank
    with pytest.raises(ValueError):
        C.Conv1DLSTMCell((2, 8), 3, i2h_kernel=(3, 3))

    # mismatched class/rank must raise
    with pytest.raises(ValueError):
        C.Conv3DLSTMCell((2, 8), 3)

    # LSTMP: recurrence at projection_size, memory at hidden_size,
    # DEFERRED input_size resolves on first forward, gradients flow
    p = C.LSTMPCell(hidden_size=8, projection_size=3)
    p.initialize()
    x = nd.array(rs.rand(2, 6, 4).astype(np.float32))
    outs, st = p.unroll(6, x, merge_outputs=True)
    assert outs.shape == (2, 6, 3)
    assert st[0].shape == (2, 3) and st[1].shape == (2, 8)
    for prm in p.collect_params().values():
        prm.data().attach_grad()
    with autograd.record():
        o, _ = p.unroll(6, x, merge_outputs=True)
        o.sum().backward()
    g = p.h2r_weight.data().grad
    assert g is not None and np.abs(g.asnumpy()).sum() > 0

"""Metric, IO, RecordIO, KVStore tests (model: test_metric.py, test_io.py,
test_kvstore.py in the reference)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_accuracy():
    m = mx.metric.Accuracy()
    pred = nd.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    label = nd.array([1.0, 0, 0])
    m.update([label], [pred])
    assert abs(m.get()[1] - 2.0 / 3) < 1e-6


def test_topk():
    m = mx.metric.TopKAccuracy(top_k=2)
    pred = nd.array([[0.1, 0.5, 0.4], [0.6, 0.3, 0.1]])
    label = nd.array([2.0, 2.0])
    m.update([label], [pred])
    assert abs(m.get()[1] - 0.5) < 1e-6


def test_f1_mcc():
    pred = nd.array([[0.8, 0.2], [0.3, 0.7], [0.1, 0.9], [0.6, 0.4]])
    label = nd.array([0.0, 1, 1, 1])
    f1 = mx.metric.F1()
    f1.update([label], [pred])
    assert 0 < f1.get()[1] <= 1
    mcc = mx.metric.MCC()
    mcc.update([label], [pred])
    assert -1 <= mcc.get()[1] <= 1


def test_mse_mae_rmse():
    pred = nd.array([[1.0], [2.0]])
    label = nd.array([[0.0], [0.0]])
    for name, expect in (("mse", 2.5), ("mae", 1.5)):
        m = mx.metric.create(name)
        m.update([label], [pred])
        assert abs(m.get()[1] - expect) < 1e-6


def test_composite_and_custom():
    comp = mx.metric.create(["acc", "ce"])
    pred = nd.array([[0.9, 0.1]])
    label = nd.array([0.0])
    comp.update([label], [pred])
    names, vals = comp.get()
    assert len(names) == 2
    custom = mx.metric.np(lambda l, p: float((l == p.argmax(-1)).mean()))
    custom.update([label], [pred])
    assert custom.get()[1] == 1.0


def test_perplexity_pooled():
    m = mx.metric.Perplexity(ignore_label=None)
    p = np.full((2, 4), 0.25, dtype=np.float32)
    m.update([nd.array([0.0, 1])], [nd.array(p)])
    m.update([nd.array([2.0, 3])], [nd.array(p)])
    assert abs(m.get()[1] - 4.0) < 1e-5


# ---------------------------------------------------------------------------
# io
# ---------------------------------------------------------------------------

def test_ndarray_iter_pad_and_discard():
    X = np.arange(20).reshape(10, 2).astype(np.float32)
    it = mx.io.NDArrayIter(X, np.arange(10), batch_size=4, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 2
    it2 = mx.io.NDArrayIter(X, np.arange(10), batch_size=4,
                            last_batch_handle="discard")
    assert len(list(it2)) == 2


def test_ndarray_iter_provide():
    it = mx.io.NDArrayIter(np.zeros((8, 3, 4, 4), np.float32),
                           np.zeros(8), batch_size=2)
    d = it.provide_data[0]
    assert d.name == "data" and d.shape == (2, 3, 4, 4)
    assert it.provide_label[0].name == "softmax_label"


def test_resize_iter():
    it = mx.io.NDArrayIter(np.zeros((8, 2), np.float32), np.zeros(8), batch_size=2)
    r = mx.io.ResizeIter(it, 7)
    assert len(list(r)) == 7


def test_prefetching_iter():
    it = mx.io.NDArrayIter(np.arange(16).reshape(8, 2).astype(np.float32),
                           np.arange(8), batch_size=2)
    p = mx.io.PrefetchingIter(it)
    batches = list(p)
    assert len(batches) == 4


def _plain_epochs(n, batch, shuffle, handle, seed, epochs):
    """What ``NDArrayIter`` serves, worked out here with nothing of the
    iterator's: per epoch a list of ``(source rows, pad)``, every batch's
    rows an index array for a plain ``v[rows]`` gather.  ``reset()`` runs
    once in the constructor and once before each later epoch."""
    rng = np.random.RandomState(seed)
    idx = np.arange(n)
    num = (n // batch) * batch if handle == "discard" else n
    rolled, out = 0, []
    for _ in range(epochs):
        consumed = idx[:rolled].copy() \
            if handle == "roll_over" and rolled else None
        if shuffle:
            rng.shuffle(idx)
        start = 0
        if consumed is not None:
            mask = np.isin(idx, consumed)
            idx = np.concatenate([idx[mask], idx[~mask]])
            start = len(consumed)
        rolled, epoch = 0, []
        for cur in range(start, num, batch):
            over = max(cur + batch - num, 0)
            rows = np.concatenate([idx[cur:cur + batch], idx[:over]])
            if over and handle == "roll_over":
                rolled = over
            epoch.append((rows, over if handle == "pad" else 0))
        out.append(epoch)
    return out


def _iter_source(n):
    X = np.arange(n * 6, dtype=np.float32).reshape(n, 2, 3) / 7.0
    y = np.arange(n, dtype=np.float32) * 3.0
    return X, y


def _assert_serves(it, X, y, epochs):
    for e, want in enumerate(epochs):
        if e:
            it.reset()
        got = list(it)
        assert len(got) == len(want)
        for b, (rows, pad) in zip(got, want):
            data, label = b.data[0].asnumpy(), b.label[0].asnumpy()
            assert data.dtype == X.dtype and data.shape == X[rows].shape
            np.testing.assert_array_equal(data, X[rows])
            np.testing.assert_array_equal(label, y[rows])
            assert b.pad == pad


@pytest.mark.parametrize("n,batch", [(12, 4), (10, 4), (9, 2)])
@pytest.mark.parametrize("handle", ["pad", "discard", "roll_over"])
@pytest.mark.parametrize("shuffle", [False, True])
def test_ndarray_iter_serves_what_a_plain_gather_gives(shuffle, handle, n,
                                                       batch):
    """View or gather, every batch, pad and the order over two epochs are
    the plain gather's (sizes that divide and that do not)."""
    X, y = _iter_source(n)
    np.random.seed(1234)
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=shuffle,
                           last_batch_handle=handle)
    _assert_serves(it, X, y,
                   _plain_epochs(n, batch, shuffle, handle, 1234, 2))


@pytest.mark.parametrize("shuffle", [False, True])
def test_ndarray_iter_seek_tell_fast_forward(shuffle):
    X, y = _iter_source(22)
    np.random.seed(99)
    it = mx.io.NDArrayIter(X, y, batch_size=4, shuffle=shuffle)
    (want,) = _plain_epochs(22, 4, shuffle, "pad", 99, 1)
    next(it)
    assert it.tell() == 1
    it.seek(3)
    for k in (3, 4, 5):             # 5 is the wrapped last batch
        b = next(it)
        assert it.tell() == k + 1 and b.pad == want[k][1]
        np.testing.assert_array_equal(b.data[0].asnumpy(), X[want[k][0]])
    it.seek(0)
    assert mx.io.fast_forward(it, 2) == 2 and it.tell() == 2
    np.testing.assert_array_equal(next(it).label[0].asnumpy(),
                                  y[want[2][0]])


@pytest.mark.parametrize("round_batch", [True, False])
def test_csv_iter_serves_what_a_plain_gather_gives(tmp_path, round_batch):
    X, y = _iter_source(10)
    np.savetxt(tmp_path / "d.csv", X.reshape(10, 6), delimiter=",",
               fmt="%.9g")
    np.savetxt(tmp_path / "l.csv", y, delimiter=",", fmt="%.9g")
    it = mx.io.CSVIter(str(tmp_path / "d.csv"), (2, 3),
                       label_csv=str(tmp_path / "l.csv"), batch_size=4,
                       round_batch=round_batch)
    handle = "roll_over" if round_batch else "pad"
    _assert_serves(it, X, y, _plain_epochs(10, 4, False, handle, 0, 2))


@pytest.mark.parametrize("shuffle,batch,shares", [
    (False, 4, [True, True, False]),     # two runs, then the wrapped batch
    (True, 4, [False, False, False]),    # a permuted order gathers
    (True, 1, [True] * 10),              # one row is a run whatever the order
])
def test_ndarray_iter_contiguous_batch_is_a_view(monkeypatch, shuffle, batch,
                                                 shares):
    """A contiguous batch makes no host copy: what reaches ``device_put``
    shares memory with the source; a shuffled or wrapped batch does not."""
    X, y = _iter_source(10)
    np.random.seed(5)                    # this order has no run of four
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=shuffle)
    seen = []                            # host arrays handed to nd.array

    def spy(source):
        seen.append(source)
        return mx.nd.array(source)

    monkeypatch.setattr(mx.io, "nd_array", spy)
    assert len(list(it)) == len(shares)
    data, labels = seen[0::2], seen[1::2]
    assert [np.shares_memory(a, X) for a in data] == shares
    assert [np.shares_memory(a, y) for a in labels] == shares


def test_ndarray_iter_batch_does_not_alias_the_source():
    """Writing to the source once ``next()`` has returned does not reach
    the batch served.  Batches of 16 MB: ``device_put`` reads a host array
    of that size after it has returned, so an unfenced view loses this."""
    n, batch = 48, 16
    X = np.random.RandomState(0).rand(n, 512, 512).astype(np.float32)
    it = mx.io.NDArrayIter(X, np.arange(n, dtype=np.float32),
                           batch_size=batch)
    for k, b in enumerate(it):
        rows = slice(k * batch, (k + 1) * batch)
        keep = X[rows].copy()
        X[rows] = -1.0
        np.testing.assert_array_equal(b.data[0].asnumpy(), keep)
        X[rows] = keep
    assert k == 2


def test_ndarray_iter_counts_views_and_gathers():
    def served():
        reg = mx.observability.registry()
        return [reg.counter("io_ndarrayiter_batches_total",
                            labels={"path": p}).value
                for p in ("view", "gather")]

    X, y = _iter_source(10)
    before = served()
    it = mx.io.NDArrayIter(X, y, batch_size=4)        # 2 runs + 1 wrapped
    list(it)
    it.reset()
    list(it)
    assert [a - b for a, b in zip(served(), before)] == [4, 2]
    before = served()
    np.random.seed(5)
    list(mx.io.NDArrayIter(X, y, batch_size=4, shuffle=True,
                           last_batch_handle="discard"))
    assert [a - b for a, b in zip(served(), before)] == [0, 2]
    text = mx.observability.to_prometheus()
    assert 'io_ndarrayiter_batches_total{path="view"}' in text


def test_recordio_roundtrip(tmp_path):
    from mxnet_tpu import recordio

    path = str(tmp_path / "test.rec")
    rec = recordio.MXRecordIO(path, "w")
    for i in range(5):
        rec.write(f"record-{i}".encode())
    rec.close()
    rec = recordio.MXRecordIO(path, "r")
    out = []
    while True:
        buf = rec.read()
        if buf is None:
            break
        out.append(buf.decode())
    assert out == [f"record-{i}" for i in range(5)]


def test_indexed_recordio_and_pack(tmp_path):
    from mxnet_tpu import recordio

    path = str(tmp_path / "idx.rec")
    idx_path = str(tmp_path / "idx.rec.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(4):
        header = recordio.IRHeader(0, float(i), i, 0)
        img = (np.random.rand(8, 8, 3) * 255).astype(np.uint8)
        rec.write_idx(i, recordio.pack_img(header, img))
    rec.close()
    rec = recordio.MXIndexedRecordIO(idx_path, path, "r")
    assert rec.keys == [0, 1, 2, 3]
    header, img = recordio.unpack_img(rec.read_idx(2))
    assert header.label == 2.0
    assert img.shape == (8, 8, 3)


def test_mnist_iter_synthetic():
    it = mx.io.MNISTIter(image=None, batch_size=50, flat=True)
    batch = next(iter(it))
    assert batch.data[0].shape == (50, 784)
    assert batch.label[0].shape == (50,)


# ---------------------------------------------------------------------------
# kvstore
# ---------------------------------------------------------------------------

def test_kvstore_push_pull():
    kv = mx.kv.create("local")
    kv.init("a", nd.ones((3,)))
    out = nd.zeros((3,))
    kv.pull("a", out=out)
    assert np.allclose(out.asnumpy(), 1)
    kv.push("a", nd.full((3,), 5.0))
    kv.pull("a", out=out)
    assert np.allclose(out.asnumpy(), 5)


def test_kvstore_multi_device_reduce():
    kv = mx.kv.create("tpu_sync")
    kv.init("w", nd.zeros((4,)))
    vals = [nd.ones((4,)) * (i + 1) for i in range(4)]
    kv.push("w", vals)
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    assert np.allclose(out.asnumpy(), 10.0)


def test_kvstore_updater():
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, rescale_grad=1.0))
    kv.init(0, nd.ones((2,)))
    kv.push(0, nd.ones((2,)))  # grad=1 → w = 1 - 0.1 = 0.9
    out = nd.zeros((2,))
    kv.pull(0, out=out)
    assert np.allclose(out.asnumpy(), 0.9, atol=1e-6)


def test_kvstore_list_keys():
    kv = mx.kv.create("local")
    kv.init(["x", "y"], [nd.ones((2,)), nd.zeros((2,))])
    outs = [nd.zeros((2,)), nd.zeros((2,))]
    kv.pull(["x", "y"], out=outs)
    assert np.allclose(outs[0].asnumpy(), 1)
    assert np.allclose(outs[1].asnumpy(), 0)


def test_kvstore_row_sparse_pull():
    from mxnet_tpu.ndarray import sparse as sp

    kv = mx.kv.create("local")
    w = np.arange(12).reshape(4, 3).astype(np.float32)
    kv.init("emb", nd.array(w))
    out = nd.zeros((4, 3))
    kv.row_sparse_pull("emb", out=out, row_ids=nd.array([1, 3]))
    expect = np.zeros_like(w)
    expect[[1, 3]] = w[[1, 3]]
    assert np.allclose(out.asnumpy(), expect)


# ---------------------------------------------------------------------------
# sparse ndarray
# ---------------------------------------------------------------------------

def test_row_sparse_basics():
    from mxnet_tpu.ndarray import sparse as sp

    dense = np.zeros((5, 3), np.float32)
    dense[1] = 1
    dense[3] = 2
    rsp = sp.row_sparse_array(dense)
    assert rsp.stype == "row_sparse"
    assert np.allclose(rsp.asnumpy(), dense)
    back = rsp.tostype("default")
    assert np.allclose(back.asnumpy(), dense)


def test_csr_basics():
    from mxnet_tpu.ndarray import sparse as sp

    dense = np.array([[1, 0, 2], [0, 0, 3]], np.float32)
    csr = sp.csr_matrix(dense)
    assert csr.stype == "csr"
    assert np.allclose(csr.asnumpy(), dense)
    assert csr.data.shape == (3,)
    d = sp.dot(csr, nd.array(np.ones((3, 2), np.float32)))
    assert np.allclose(d.asnumpy(), dense @ np.ones((3, 2)))


def test_cast_storage_roundtrip():
    from mxnet_tpu.ndarray import sparse as sp

    x = nd.array(np.diag([1.0, 2, 3]))
    csr = x.tostype("csr")
    rsp = x.tostype("row_sparse")
    assert np.allclose(csr.asnumpy(), x.asnumpy())
    assert np.allclose(rsp.asnumpy(), x.asnumpy())
    assert np.allclose(csr.tostype("default").asnumpy(), x.asnumpy())


def test_cross_entropy_and_nll():
    probs = np.array([[0.2, 0.7, 0.1], [0.6, 0.3, 0.1]], np.float32)
    labels = np.array([1, 0], np.float32)
    want = -np.mean(np.log([0.7, 0.6]))
    for cls in (mx.metric.CrossEntropy, mx.metric.NegativeLogLikelihood):
        m = cls()
        m.update([nd.array(labels)], [nd.array(probs)])
        assert abs(m.get()[1] - want) < 1e-5, cls.__name__


def test_pearson_correlation():
    rs = np.random.RandomState(0)
    x = rs.rand(50).astype(np.float32)
    y = (2 * x + 0.1 * rs.rand(50)).astype(np.float32)
    m = mx.metric.PearsonCorrelation()
    m.update([nd.array(y)], [nd.array(x)])
    want = np.corrcoef(x, y)[0, 1]
    assert abs(m.get()[1] - want) < 1e-4
    # perfectly anticorrelated
    m.reset()
    m.update([nd.array(-x)], [nd.array(x)])
    assert abs(m.get()[1] + 1.0) < 1e-5


def test_loss_metric_and_registry_create():
    m = mx.metric.Loss()
    m.update(None, [nd.array(np.array([1.0, 3.0], np.float32))])
    assert abs(m.get()[1] - 2.0) < 1e-6
    # string / registry round trips (reference: metric.create)
    for spec in ("accuracy", "mse", "top_k_accuracy"):
        got = mx.metric.create(spec)
        assert isinstance(got, mx.metric.EvalMetric), spec
    comp = mx.metric.create(["accuracy", "mse"])
    assert isinstance(comp, mx.metric.CompositeEvalMetric)
    again = mx.metric.create(mx.metric.Accuracy())
    assert isinstance(again, mx.metric.Accuracy)


def test_metric_reset_and_accumulation():
    m = mx.metric.Accuracy()
    m.update([nd.array(np.array([0.0]))],
             [nd.array(np.array([[0.9, 0.1]], np.float32))])
    m.update([nd.array(np.array([1.0]))],
             [nd.array(np.array([[0.9, 0.1]], np.float32))])
    assert m.get()[1] == 0.5 and m.num_inst == 2
    m.reset()
    assert m.num_inst == 0
    assert np.isnan(m.get()[1])  # no updates yet -> NaN, reference behavior

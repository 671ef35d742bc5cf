"""Parallelism tests on the virtual 8-device CPU mesh (SURVEY.md §2.3/§5.7:
the capabilities the reference lacks must be first-class here)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.parallel import make_mesh, DataParallelTrainer
from mxnet_tpu.parallel.ring_attention import (local_attention,
                                               ring_attention_sharded)
from mxnet_tpu.parallel.sequence_parallel import ulysses_attention_sharded
from mxnet_tpu.parallel.pipeline import pipeline_apply_sharded
from mxnet_tpu.parallel.compression import GradientCompression


def _qkv(B=2, T=32, H=4, D=8, seed=0):
    r = np.random.RandomState(seed)
    return (r.rand(B, T, H, D).astype(np.float32),
            r.rand(B, T, H, D).astype(np.float32),
            r.rand(B, T, H, D).astype(np.float32))


def test_ring_attention_matches_local():
    mesh = make_mesh(sp=8)
    q, k, v = _qkv()
    out = ring_attention_sharded(q, k, v, mesh=mesh)
    ref = local_attention(q, k, v)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_causal():
    mesh = make_mesh(sp=8)
    q, k, v = _qkv(T=64)
    out = ring_attention_sharded(q, k, v, mesh=mesh, causal=True)
    ref = local_attention(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ulysses_attention_matches_local():
    # head count must be divisible by axis size
    mesh = make_mesh(sp=4)
    q, k, v = _qkv(T=32, H=8)
    out = ulysses_attention_sharded(q, k, v, mesh=mesh)
    ref = local_attention(q, k, v)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_pipeline_matches_sequential():
    mesh = make_mesh(pp=4)
    S, F, M = 4, 8, 8
    r = np.random.RandomState(0)
    stage_w = jnp.asarray(r.randn(S, F, F).astype(np.float32) * 0.3)
    micro = jnp.asarray(r.rand(M, 3, F).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(jnp.dot(x, w))

    out = pipeline_apply_sharded(stage_fn, stage_w, micro, mesh=mesh)
    # sequential oracle
    ref = micro
    for s in range(S):
        ref = jnp.tanh(jnp.dot(ref, stage_w[s]))
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_data_parallel_trainer_converges():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(10))
    net.initialize()
    net(nd.array(np.random.rand(8, 20).astype(np.float32)))
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = make_mesh(dp=8)
    tr = DataParallelTrainer(net, lambda p, y: lf(NDArray(p), NDArray(y))._data,
                             lr=0.5, mesh=mesh)
    r = np.random.RandomState(0)
    Y = r.randint(0, 10, 256).astype(np.float32)
    X = r.rand(256, 20).astype(np.float32) * 0.3
    for c in range(10):
        X[Y == c, c] += 1.0
    first = float(tr.step(X, Y))
    for _ in range(30):
        last = float(tr.step(X, Y))
    assert last < first * 0.5
    tr.write_back()
    pred = net(nd.array(X)).argmax(axis=1).asnumpy()
    assert (pred == Y).mean() > 0.8


def test_dp_matches_single_device():
    """Data-parallel gradient == single-device gradient on the same batch."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=12),
            gluon.nn.Dense(4, in_units=16))
    net.initialize()
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn = lambda p, y: lf(NDArray(p), NDArray(y))._data
    r = np.random.RandomState(1)
    X = r.rand(64, 12).astype(np.float32)
    Y = r.randint(0, 4, (64,)).astype(np.float32)

    tr1 = DataParallelTrainer(net, loss_fn, lr=0.1, momentum=0.0, mesh=None,
                              donate=False)
    mesh = make_mesh(dp=8)
    tr8 = DataParallelTrainer(net, loss_fn, lr=0.1, momentum=0.0, mesh=mesh,
                              donate=False)
    l1 = float(tr1.step(X, Y))
    l8 = float(tr8.step(X, Y))
    assert abs(l1 - l8) < 1e-4
    for k in tr1.params:
        assert np.allclose(np.asarray(tr1.params[k]), np.asarray(tr8.params[k]),
                           atol=1e-4), k


def test_gradient_compression_roundtrip():
    gc = GradientCompression(type="2bit", threshold=0.5)
    r = np.random.RandomState(0)
    # error feedback converges when |g| stays below the quantization threshold
    g = jnp.asarray((r.randn(37) * 0.15).astype(np.float32))
    packed, residual = gc.quantize(g, None)
    deq = gc.dequantize(packed, (37,))
    # every dequantized value in {-0.5, 0, +0.5}
    assert set(np.unique(np.asarray(deq))).issubset({-0.5, 0.0, 0.5})
    # error feedback: deq + residual == original
    assert np.allclose(np.asarray(deq) + np.asarray(residual), np.asarray(g),
                       atol=1e-6)
    # accumulating residual over steps converges to the true gradient sum
    total = jnp.zeros_like(g)
    res = None
    for _ in range(50):
        packed, res = gc.quantize(g, res)
        total = total + gc.dequantize(packed, (37,))
    assert np.allclose(np.asarray(total) / 50, np.asarray(g), atol=0.02)


def test_collectives_allreduce_tree():
    from mxnet_tpu.parallel.collectives import allreduce_tree

    vals = [jnp.ones((4,)) * i for i in range(8)]
    mesh = make_mesh(dp=8)
    out = allreduce_tree(vals, mesh=mesh, axis="dp")
    for o in out:
        assert np.allclose(np.asarray(o), 28.0)


def test_graft_entry_dryrun():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_dp_batchnorm_aux_states():
    """BN running stats must (a) move off their init through the fused DP
    step, (b) never be touched by the optimizer (weight_decay would decay
    them toward zero), and (c) make eval-mode predictions match an
    eager-trained oracle (reference semantics: aux update inside the op,
    src/operator/nn/batch_norm.cc)."""
    r = np.random.RandomState(3)
    X = (r.rand(64, 8).astype(np.float32) * 2.0 + 1.5)  # mean well off 0
    Y = r.randint(0, 2, (64,)).astype(np.float32)
    lf = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn = lambda p, y: lf(NDArray(p), NDArray(y))._data

    def make_net():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, in_units=8), gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"), gluon.nn.Dense(2, in_units=16))
        net.initialize()
        net(nd.array(X))  # shape BN params
        return net

    # --- fused DP training with weight decay (the old corruption trigger)
    net = make_net()
    init_state = [p.data().asnumpy().copy()
                  for p in net.collect_params().values()]
    mesh = make_mesh(dp=8)
    tr = DataParallelTrainer(net, loss_fn, lr=0.05, momentum=0.9,
                             weight_decay=1e-2, mesh=mesh)
    for _ in range(20):
        tr.step(X, Y)
    tr.write_back()

    bn = [b for b in net._children.values()
          if isinstance(b, gluon.nn.BatchNorm)][0]
    rm = bn.running_mean.data().asnumpy()
    rv = bn.running_var.data().asnumpy()
    assert np.abs(rm).sum() > 1e-3, "running_mean never updated"
    assert np.abs(rv - 1.0).sum() > 1e-3, "running_var never updated"

    # --- eager oracle: same init, same schedule, running stats via eager path
    oracle = make_net()
    for p, v in zip(oracle.collect_params().values(), init_state):
        p.set_data(nd.array(v))
    from mxnet_tpu import autograd as ag
    params = oracle.collect_params()
    momenta = {k: np.zeros(params[k].shape, np.float32) for k in params
               if params[k].grad_req != "null"}
    for _ in range(20):
        with ag.record():
            loss = lf(oracle(nd.array(X)), nd.array(Y)).mean()
        loss.backward()
        for k, p in params.items():
            if p.grad_req == "null":
                continue
            g = p.grad().asnumpy()
            momenta[k] = 0.9 * momenta[k] + g
            newv = p.data().asnumpy() * (1.0 - 0.05 * 1e-2) - 0.05 * momenta[k]
            p.set_data(nd.array(newv))
    bn_o = [b for b in oracle._children.values()
            if isinstance(b, gluon.nn.BatchNorm)][0]
    assert np.allclose(rm, bn_o.running_mean.data().asnumpy(), atol=1e-3)
    assert np.allclose(rv, bn_o.running_var.data().asnumpy(), atol=1e-3)

    # --- eval-mode predictions agree
    pred_dp = net(nd.array(X)).asnumpy()
    pred_or = oracle(nd.array(X)).asnumpy()
    assert np.allclose(pred_dp, pred_or, atol=1e-2)


def test_broadcast_validates_src_and_matches():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import collectives

    mesh = make_mesh(dp=8)
    with pytest.raises(ValueError):
        jax.shard_map(
            lambda x: collectives.broadcast(x, "dp", src=12),
            mesh=mesh, in_specs=P("dp"),
            out_specs=P("dp"), check_vma=False)(jnp.arange(8.0))
    out = jax.shard_map(
        lambda x: collectives.broadcast(x, "dp", src=3),
        mesh=mesh, in_specs=P("dp"),
        out_specs=P("dp"), check_vma=False)(jnp.arange(8.0))
    assert np.allclose(np.asarray(out), 3.0)


def test_reduce_scatter_allgather_equals_allreduce():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import collectives

    mesh = make_mesh(dp=8)
    x = jnp.arange(64.0).reshape(8, 8)

    def rt(s):
        local = s[0]
        return collectives.allgather(
            collectives.reduce_scatter(local, "dp"), "dp")[None]

    y = jax.shard_map(rt, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                      check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(y),
                               np.repeat(np.asarray(x).sum(0)[None], 8, 0),
                               rtol=1e-6)


def test_pipeline_fewer_microbatches_than_stages():
    mesh = make_mesh(pp=8)
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(8, 6, 6).astype(np.float32) * 0.3)
    x = jnp.asarray(rs.rand(2, 3, 6).astype(np.float32))  # M=2 < S=8
    out = pipeline_apply_sharded(lambda p, t: jnp.tanh(t @ p), w, x,
                                 mesh=mesh)
    ref = x
    for i in range(8):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_moe_gradients_flow_through_dispatch():
    from mxnet_tpu.parallel import moe

    mesh = make_mesh(ep=4)
    rs = np.random.RandomState(0)
    D = 8
    x = jnp.asarray(rs.rand(16, D).astype(np.float32))
    rw = jnp.asarray(rs.randn(D, 4).astype(np.float32))
    ew = jnp.asarray(rs.randn(4, D, D).astype(np.float32) * 0.3)

    def loss(rw, ew, x):
        o = moe.moe_apply_sharded(x, rw, ew, lambda w, t: jnp.tanh(t @ w),
                                  mesh=mesh)
        return jnp.mean(o ** 2)

    g_rw, g_ew = jax.grad(loss, argnums=(0, 1))(rw, ew, x)
    assert np.isfinite(np.asarray(g_rw)).all()
    assert np.isfinite(np.asarray(g_ew)).all()
    assert np.abs(np.asarray(g_ew)).sum() > 0  # experts actually trained
    assert np.abs(np.asarray(g_rw)).sum() > 0  # router actually trained


def test_moe_over_capacity_drops_to_zero():
    """Switch semantics: tokens beyond expert capacity fall through with
    zero output (static shapes for XLA; reference has no MoE — §2.3)."""
    from mxnet_tpu.parallel import moe

    mesh = make_mesh(ep=4)
    D = 8
    x = jnp.ones((16, D))
    rw = jnp.zeros((D, 4)).at[:, 2].set(1.0)  # everyone routes to expert 2
    ew = jnp.stack([jnp.eye(D) * (i + 1) for i in range(4)])
    out = np.asarray(moe.moe_apply_sharded(
        x, rw, ew, lambda w, t: t @ w, mesh=mesh, capacity_factor=2.0))
    kept = (np.abs(out).sum(axis=1) > 0)
    # capacity = B_local*cf/n = 4*2/4 = 2 per source device, 4 sources -> 8
    assert kept.sum() == 8
    # kept tokens went through expert 2 (scale 3): output = 3 * ones * gate
    scaled = out[kept] / out[kept][0, 0]
    assert np.allclose(scaled, 1.0, atol=1e-5)


def test_data_parallel_accepts_gluon_loss_block():
    """gluon.loss.* blocks work directly as DataParallelTrainer loss_fn
    (wrapped over NDArray views inside the traced step)."""
    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    x = nd.array(np.random.RandomState(0).rand(2, 3).astype(np.float32))
    net(x)
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mesh=None, lr=0.1)
    y = np.random.RandomState(1).randint(0, 4, 16).astype(np.float32)
    xs = np.random.RandomState(2).rand(16, 3).astype(np.float32)
    l0 = float(tr.step(xs, y))
    for _ in range(20):
        loss = tr.step(xs, y)
    assert float(loss) < l0, (l0, float(loss))


def test_data_parallel_step_under_record_does_not_poison_tape():
    """step() inside autograd.record() (a migration habit) must not leak
    tracers onto the global eager tape via a gluon Loss block."""
    from mxnet_tpu import autograd

    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    x = nd.array(np.random.RandomState(0).rand(2, 3).astype(np.float32))
    net(x)
    tr = DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                             mesh=None, lr=0.1)
    with autograd.record():
        tr.step(np.random.RandomState(1).rand(8, 3).astype(np.float32),
                np.random.RandomState(2).randint(0, 4, 8)
                .astype(np.float32))
    # an ordinary eager record/backward afterwards must still work
    w = nd.array(np.ones(3, np.float32))
    w.attach_grad()
    with autograd.record():
        (w * w).sum().backward()
    np.testing.assert_allclose(w.grad.asnumpy(), 2 * np.ones(3))

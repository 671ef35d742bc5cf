"""Multi-device SPMD fused train step (docs/multichip.md): 1-device vs
N-device parity, compile-cache discipline, mesh-aware executor signatures,
`tpu_sync` API + in-program collectives, io sharding, and the escape hatches.

Runs on the conftest-forced 8-virtual-CPU-device backend
(XLA_FLAGS=--xla_force_host_platform_device_count=8) — the same recipe
`docs/multichip.md` documents for chip-free development.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.executor import compile_cache_stats
from mxnet_tpu.io import DataBatch

pytestmark = pytest.mark.spmd

NDEV = 8


def _mlp_sym(nh=16, classes=4):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=nh, name="fc1"),
                       act_type="relu")
    out = sym.FullyConnected(h, num_hidden=classes, name="fc2")
    return sym.SoftmaxOutput(out, label, name="softmax")


def _toy_iter(n=320, dim=8, classes=4, batch=32):
    r = np.random.RandomState(0)
    Y = r.randint(0, classes, n).astype(np.float32)
    X = r.rand(n, dim).astype(np.float32) * 0.3
    for c in range(classes):
        X[Y == c, c] += 1.0
    return mx.io.NDArrayIter(X, Y, batch_size=batch)


def _fit(ctx, kvstore, optimizer="sgd", opt_params=(("learning_rate", 0.5),),
         num_epoch=1):
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=ctx)
    mod.fit(_toy_iter(), num_epoch=num_epoch, optimizer=optimizer,
            kvstore=kvstore, optimizer_params=opt_params)
    arg, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in arg.items()}


def _ctx8():
    return [mx.cpu(i) for i in range(NDEV)]


# ---------------------------------------------------------------------------
# parity: 1-device fused == 8-device SPMD fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", (("learning_rate", 0.5),)),
    ("sgd", (("learning_rate", 0.5), ("momentum", 0.9))),
    ("adam", (("learning_rate", 0.05),)),
], ids=["sgd", "sgd_momentum", "adam"])
def test_spmd_parity_10_steps(optimizer, opt_params):
    """Same seed, 10 steps: the 8-device SPMD program (batch sharded, grads
    psum'd in-program, update per replica) matches the 1-device fused run at
    rtol 1e-5."""
    m1, p1 = _fit(mx.cpu(), "local", optimizer, opt_params)
    m8, p8 = _fit(_ctx8(), "tpu_sync", optimizer, opt_params)
    assert m1._fused_step_count == 10
    assert m8._fused_step_count == 10
    assert m8._exec._spmd_ndev() == NDEV
    for k in p1:
        np.testing.assert_allclose(p8[k], p1[k], rtol=1e-5, atol=1e-7,
                                   err_msg=f"{optimizer}: {k}")


def test_spmd_device_kvstore_also_qualifies():
    """`device` (the reference's GPU-reduce store) is collective-capable too."""
    m8, p8 = _fit(_ctx8(), "device")
    assert m8._fused_step_count == 10
    _, p1 = _fit(mx.cpu(), "local")
    for k in p1:
        np.testing.assert_allclose(p8[k], p1[k], rtol=1e-5, atol=1e-7)


def test_spmd_local_kvstore_stays_legacy():
    """A host-reduce `local` store cannot become a collective boundary: the
    multi-device fit must take the legacy path (update on the store), and
    still train."""
    m8, _ = _fit(_ctx8(), "local", num_epoch=6)
    assert m8._fused_step_count == 0
    assert m8._update_on_kvstore
    acc = dict(m8.score(_toy_iter(), "acc"))["accuracy"]
    assert acc > 0.9


def test_tpumx_dp_devices_widens_single_context(monkeypatch):
    """TPUMX_DP_DEVICES=8 on a single-context module runs the same SPMD
    program as 8 bound contexts."""
    monkeypatch.setenv("TPUMX_DP_DEVICES", str(NDEV))
    mD, pD = _fit(mx.cpu(), "tpu_sync")
    assert mD._fused_step_count == 10
    assert mD._exec._spmd_ndev() == NDEV
    monkeypatch.delenv("TPUMX_DP_DEVICES")
    _, p1 = _fit(mx.cpu(), "local")
    for k in p1:
        np.testing.assert_allclose(pD[k], p1[k], rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# escape hatches
# ---------------------------------------------------------------------------

def test_spmd_escape_hatch_restores_legacy_byte_for_byte(monkeypatch):
    """TPUMX_FUSED_STEP_SPMD=0 routes multi-device fit through the legacy
    executor-group/kvstore path — bit-identical to TPUMX_FUSED_STEP=0."""
    monkeypatch.setenv("TPUMX_FUSED_STEP_SPMD", "0")
    mS, pS = _fit(_ctx8(), "tpu_sync")
    assert mS._fused_step_count == 0
    monkeypatch.delenv("TPUMX_FUSED_STEP_SPMD")
    monkeypatch.setenv("TPUMX_FUSED_STEP", "0")
    mL, pL = _fit(_ctx8(), "tpu_sync")
    assert mL._fused_step_count == 0
    for k in pS:
        np.testing.assert_array_equal(pS[k], pL[k])


def test_spmd_indivisible_batch_falls_back():
    """Global batch 30 over 8 devices can't shard evenly: legacy path, no
    crash."""
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=_ctx8())
    mod.fit(_toy_iter(n=300, batch=30), num_epoch=1, optimizer="sgd",
            kvstore="tpu_sync", optimizer_params=(("learning_rate", 0.5),))
    assert mod._fused_step_count == 0


# ---------------------------------------------------------------------------
# compile-cache discipline & signatures
# ---------------------------------------------------------------------------

def test_spmd_compile_cache_discipline():
    """20 fused steps at fixed shapes on 8 devices: exactly ONE program
    compile (miss); the remaining 19 lookups hit."""
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=_ctx8())
    before = compile_cache_stats()
    mod.fit(_toy_iter(), num_epoch=2, optimizer="sgd", kvstore="tpu_sync",
            optimizer_params=(("learning_rate", 0.1),))
    after = compile_cache_stats()
    assert mod._fused_step_count == 20
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 19


def test_signature_includes_mesh():
    """Regression: the executor signature keys the mesh axis/device count, so
    an 8-device program is never served after a rebind to fewer devices."""
    from mxnet_tpu.parallel.mesh import dp_mesh

    ex = _mlp_sym().simple_bind(ctx=mx.cpu(), data=(32, 8),
                                softmax_label=(32,))
    sig1 = ex._signature(True)
    assert not any(isinstance(s, tuple) and s[0] == "mesh" for s in sig1)
    ex.set_spmd(dp_mesh(NDEV), batch_args=("data", "softmax_label"))
    sig8 = ex._signature(True)
    mesh_entries = [s for s in sig8 if isinstance(s, tuple)
                    and s[0] == "mesh"]
    assert mesh_entries and mesh_entries[0][2] == NDEV
    assert sig8 != sig1
    ex.set_spmd(dp_mesh(4), batch_args=("data", "softmax_label"))
    sig4 = ex._signature(True)
    assert sig4 != sig8 != sig1  # each device count keys its own programs
    ex.set_spmd(None, batch_args=())
    assert ex._signature(True) == sig1


def test_set_spmd_rejects_indivisible_batch():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel.mesh import dp_mesh

    ex = _mlp_sym().simple_bind(ctx=mx.cpu(), data=(30, 8),
                                softmax_label=(30,))
    with pytest.raises(MXNetError, match="not divisible"):
        ex.set_spmd(dp_mesh(NDEV), batch_args=("data", "softmax_label"))


# ---------------------------------------------------------------------------
# tpu_sync kvstore API + in-program collectives
# ---------------------------------------------------------------------------

def test_tpu_sync_create_rank_num_workers(monkeypatch):
    kv = mx.kv.create("tpu_sync")
    assert kv.type == "tpu_sync"
    assert kv.rank == 0
    assert kv.num_workers == 1
    assert kv.supports_spmd_fused
    assert kv._fused_step_ok()
    # nccl answers to the same store (reference alias)
    assert mx.kv.create("nccl").type == "tpu_sync"
    monkeypatch.setenv("TPUMX_NUM_WORKERS", "4")
    monkeypatch.setenv("TPUMX_RANK", "2")
    assert kv.num_workers == 4
    assert kv.rank == 2
    # a multi-worker store is no longer a single-host collective boundary
    assert not kv.supports_spmd_fused


def test_tpu_sync_in_program_collectives():
    """reduce_in_program == psum; broadcast_in_program == rank-src value —
    executed through a real shard_map over the 8-device mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.mesh import dp_mesh

    kv = mx.kv.create("tpu_sync")
    mesh = dp_mesh(NDEV)
    x = jnp.arange(float(NDEV))

    def reduce_fn(v):
        return kv.reduce_in_program({"g": v})["g"]

    out = jax.shard_map(reduce_fn, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.full(NDEV, np.arange(NDEV).sum()))

    def bcast_fn(v):
        return kv.broadcast_in_program({"w": v}, src=3)["w"]

    out = jax.shard_map(bcast_fn, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.full(NDEV, 3.0))


def test_kvstore_local_reduce_multi_device_values():
    """The batched-transfer + jitted tree-reduction hot path sums values that
    live on distinct devices."""
    import jax

    devs = jax.devices()
    kv = mx.kv.create("device")
    kv.init("w", nd.zeros((4,)))
    vals = []
    for i in range(min(NDEV, len(devs))):
        v = nd.ones((4,)) * (i + 1)
        v._data = jax.device_put(v._data, devs[i])
        vals.append(v)
    kv.push("w", vals)
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(),
                               sum(range(1, len(vals) + 1)))


def test_kvstore_pull_broadcast_batched_per_device():
    """Pull to many destinations: one transfer per distinct device, every dst
    keeps its own placement (reference CopyFromTo semantics)."""
    import jax

    devs = jax.devices()
    kv = mx.kv.create("device")
    kv.init("w", nd.array(np.arange(4, dtype=np.float32)))
    outs = []
    for i in range(4):
        o = nd.zeros((4,))
        o._data = jax.device_put(o._data, devs[i % len(devs)])
        outs.append(o)
    kv.pull("w", out=outs)
    for i, o in enumerate(outs):
        np.testing.assert_allclose(o.asnumpy(), np.arange(4))
        assert list(o._data.devices()) == [devs[i % len(devs)]]
    # same-device dsts share one broadcast buffer (no duplicate transfers)
    assert outs[0]._data is outs[len(devs) % 4]._data or len(devs) >= 4


# ---------------------------------------------------------------------------
# device-side metrics & io sharding
# ---------------------------------------------------------------------------

def test_spmd_fit_keeps_no_asnumpy_metric_property(monkeypatch):
    """Multi-device fit must never run the blocking per-batch metric update:
    per-shard counts accumulate device-side (XLA inserts the cross-device
    reduction) and drain once at get()."""
    from mxnet_tpu import metric as metric_mod

    def boom(self, labels, preds):  # pragma: no cover - must not be called
        raise AssertionError("blocking Accuracy.update called on fit path")

    monkeypatch.setattr(metric_mod.Accuracy, "update", boom)
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=_ctx8())
    mod.fit(_toy_iter(), num_epoch=6, optimizer="sgd", kvstore="tpu_sync",
            optimizer_params=(("learning_rate", 0.5),))
    assert mod._fused_step_count == 60
    monkeypatch.undo()
    acc = dict(mod.score(_toy_iter(), mx.metric.create("acc")))["accuracy"]
    assert acc > 0.9


def test_spmd_metric_values_match_single_device():
    """The device-accumulated training metric over sharded outputs equals the
    1-device value (same data, same steps)."""
    def run(ctx, kv):
        mx.random.seed(0)
        np.random.seed(0)
        mod = mx.mod.Module(_mlp_sym(), context=ctx)
        vals = []
        mod.fit(_toy_iter(), num_epoch=1, optimizer="sgd", kvstore=kv,
                optimizer_params=(("learning_rate", 0.5),),
                batch_end_callback=lambda p: vals.append(
                    dict(p.eval_metric.get_name_value()).get("accuracy")))
        return dict(mod.score(_toy_iter(), "acc"))["accuracy"]

    a1 = run(mx.cpu(), "local")
    a8 = run(_ctx8(), "tpu_sync")
    assert abs(a1 - a8) < 1e-6


def test_shard_data_batch_places_on_mesh():
    """io.shard_data_batch: one device_put per array with a batch-axis
    NamedSharding; indivisible arrays are left alone."""
    from mxnet_tpu.io import shard_data_batch
    from mxnet_tpu.parallel.mesh import dp_mesh

    mesh = dp_mesh(NDEV)
    batch = DataBatch([nd.array(np.random.rand(32, 8).astype(np.float32))],
                      [nd.array(np.random.rand(30).astype(np.float32))])
    shard_data_batch(batch, mesh)
    assert len(batch.data[0]._data.devices()) == NDEV  # sharded over the mesh
    assert len(batch.label[0]._data.devices()) == 1    # 30 % 8 != 0: untouched

"""Pipeline parallelism (docs/sharding.md §pipeline): the differentiable
scan-based ``pipeline_apply`` round-robin vs a sequential single-stage
oracle (forward AND grads), symbol stage discovery (symbol/staging.py),
and the ``pp`` axis behind ``Module.fit`` — 2-axis and 3-axis
``("dp","pp","mp")`` parity with the unpipelined fused step, compile-cache
discipline (1 miss + N-1 hits), the recompile explainer's pipeline causes,
and the graceful fallback for non-stage-stackable symbols.

Runs on the conftest-forced 8-virtual-CPU-device backend.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.executor import compile_cache_stats
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.pipeline import (pipeline_apply,
                                         pipeline_apply_sharded, psum_bcast)
from mxnet_tpu.symbol.staging import PlanError, plan_pipeline

pytestmark = pytest.mark.pp

ENVS = ("TPUMX_DP_DEVICES", "TPUMX_MP_DEVICES", "TPUMX_PP_DEVICES",
        "TPUMX_PP_MICROBATCHES", "TPUMX_SHARD_RULES", "TPUMX_MP_COMPUTE",
        "TPUMX_AMP", "TPUMX_AMP_DTYPE", "TPUMX_AMP_LOSS_SCALE")


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for k in ENVS:
        monkeypatch.delenv(k, raising=False)
    yield


# ---------------------------------------------------------------------------
# pipeline_apply vs the sequential oracle — forward AND gradients
# ---------------------------------------------------------------------------

def _stage_fn(w, x):
    return jnp.tanh(x @ w)


def test_pipeline_apply_forward_and_grads_match_sequential():
    """The round-robin schedule is just a reordering: stacked-stage forward
    equals applying the stages sequentially, and jax.grad through the whole
    scanned schedule (ppermute transposed to the inverse ring, psum_bcast
    to the identity) reproduces the oracle gradients at rtol 1e-5."""
    S, M, b, d = 4, 8, 2, 8
    mesh = make_mesh({"pp": S}, install=False)
    r = np.random.RandomState(0)
    Ws = jnp.asarray(r.randn(S, d, d) * 0.3, jnp.float32)
    X = jnp.asarray(r.randn(M * b, d), jnp.float32)
    ct = jnp.asarray(r.randn(M * b, d), jnp.float32)

    def inner(Ws, X, ct):
        my_w = lax.dynamic_index_in_dim(Ws, lax.axis_index("pp"),
                                        keepdims=False)

        def f(my_w):
            xmb = X.reshape(M, b, d)
            out = pipeline_apply(_stage_fn, my_w, xmb, "pp")
            out = psum_bcast(out, "pp")
            return jnp.sum(out.reshape(M * b, d) * ct)

        loss, g_my = jax.value_and_grad(f)(my_w)
        return loss, lax.all_gather(g_my, "pp", axis=0, tiled=False)

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    loss, g_Ws = jax.jit(fn)(Ws, X, ct)

    def oracle(Ws):
        x = X
        for s in range(S):
            x = _stage_fn(Ws[s], x)
        return jnp.sum(x * ct)

    loss_ref, g_ref = jax.value_and_grad(oracle)(Ws)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_Ws), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_apply_sharded_host_entry_parity():
    mesh = make_mesh({"pp": 4}, install=False)
    r = np.random.RandomState(1)
    Ws = jnp.asarray(r.randn(4, 8, 8) * 0.3, jnp.float32)
    micro = jnp.asarray(r.rand(6, 3, 8), jnp.float32)
    out = pipeline_apply_sharded(_stage_fn, Ws, micro, mesh=mesh)
    ref = micro
    for s in range(4):
        ref = _stage_fn(Ws[s], ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# stage discovery over the symbol DAG
# ---------------------------------------------------------------------------

def _deep_net(nh=32, classes=4, layers=4, dim=8):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.FullyConnected(data, num_hidden=nh, name="fc_in")
    h = sym.Activation(h, act_type="relu")
    for i in range(layers):
        h = sym.FullyConnected(h, num_hidden=nh, name=f"body{i}")
        h = sym.Activation(h, act_type="relu")
    out = sym.FullyConnected(h, num_hidden=classes, name="fc_out")
    return sym.SoftmaxOutput(out, label, name="softmax")


def _structs(net, batch=32, dim=8, nh=32, classes=4, layers=4):
    shapes = {"data": (batch, dim), "softmax_label": (batch,),
              "fc_in_weight": (nh, dim), "fc_in_bias": (nh,),
              "fc_out_weight": (classes, nh), "fc_out_bias": (classes,)}
    for i in range(layers):
        shapes[f"body{i}_weight"] = (nh, nh)
        shapes[f"body{i}_bias"] = (nh,)
    return {k: jax.ShapeDtypeStruct(v, jnp.float32)
            for k, v in shapes.items()}


def test_plan_discovers_isomorphic_stages():
    net = _deep_net(layers=4)
    plan = plan_pipeline(net._entries, 2, _structs(net),
                         input_names=["data", "softmax_label"])
    assert plan.n_stages == 2 and plan.units_per_stage == 2
    # stage params are the body layers, two per stage, aligned by slot
    assert plan.stage_param_names[0] != plan.stage_param_names[1]
    assert len(plan.stage_param_names[0]) == len(plan.template_param_names)
    flat = [n for s in plan.stage_param_names for n in s]
    assert {f"body{i}_weight" for i in range(4)} <= set(flat)
    # grouping: trunk-in params combine with psum, head params don't
    assert plan.pp_combine("fc_in_weight") == "psum"
    assert plan.pp_combine("body0_weight") == "psum"
    assert plan.pp_combine("fc_out_weight") == "none"
    assert plan.param_group["fc_out_weight"] == "epilogue"


def test_plan_rejects_non_stackable_graphs():
    # two layers cannot make 4 stages
    net = _deep_net(layers=2)
    with pytest.raises(PlanError):
        plan_pipeline(net._entries, 4, _structs(net, layers=2),
                      input_names=["data", "softmax_label"])
    # heterogeneous widths: no isomorphic unit at all
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=32, name="a"),
                       act_type="relu")
    h = sym.Activation(sym.FullyConnected(h, num_hidden=16, name="b"),
                       act_type="relu")
    out = sym.FullyConnected(h, num_hidden=4, name="c")
    net2 = sym.SoftmaxOutput(out, label, name="softmax")
    structs = {k: jax.ShapeDtypeStruct(v, jnp.float32) for k, v in {
        "data": (32, 8), "softmax_label": (32,), "a_weight": (32, 8),
        "a_bias": (32,), "b_weight": (16, 32), "b_bias": (16,),
        "c_weight": (4, 16), "c_bias": (4,)}.items()}
    with pytest.raises(PlanError):
        plan_pipeline(net2._entries, 2, structs,
                      input_names=["data", "softmax_label"])


# ---------------------------------------------------------------------------
# Module.fit over the pp axis
# ---------------------------------------------------------------------------

def _iter(n=320, dim=8, classes=4, batch=32):
    r = np.random.RandomState(0)
    Y = r.randint(0, classes, n).astype(np.float32)
    X = r.rand(n, dim).astype(np.float32) * 0.3
    for c in range(classes):
        X[Y == c, c] += 1.0
    return mx.io.NDArrayIter(X, Y, batch_size=batch)


def _fit(monkeypatch, env, layers=4, optimizer="sgd",
         opt_params=(("learning_rate", 0.5),), num_epoch=1):
    for k in ENVS:
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_deep_net(layers=layers), context=mx.cpu())
    mod.fit(_iter(), num_epoch=num_epoch, optimizer=optimizer,
            kvstore="tpu_sync", optimizer_params=dict(opt_params))
    arg, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in arg.items()}


def _close(pa, pb, **kw):
    kw.setdefault("rtol", 1e-5)
    kw.setdefault("atol", 1e-7)
    for k in pb:
        np.testing.assert_allclose(pa[k], pb[k], err_msg=k, **kw)


def test_fit_pp2_matches_unpipelined(monkeypatch):
    _, p0 = _fit(monkeypatch, {})
    mod, pp = _fit(monkeypatch, {"TPUMX_PP_DEVICES": "2"})
    assert mod._exec._spmd_pipeline is not None
    assert mod._fused_step_count == 10
    _close(p0, pp)


def test_fit_pp2_adam_matches(monkeypatch):
    _, p0 = _fit(monkeypatch, {}, optimizer="adam",
                 opt_params=(("learning_rate", 1e-2),))
    mod, pp = _fit(monkeypatch, {"TPUMX_PP_DEVICES": "2"}, optimizer="adam",
                   opt_params=(("learning_rate", 1e-2),))
    assert mod._exec._spmd_pipeline is not None
    _close(p0, pp)


def test_fit_3axis_dp_pp_mp_matches_oracle(monkeypatch):
    """The acceptance run: a ("dp","pp","mp") Module.fit matches the
    unpipelined oracle at rtol 1e-5 with 1 compile miss + N-1 hits over
    20 steps."""
    _, p0 = _fit(monkeypatch, {}, num_epoch=2)
    base = compile_cache_stats()["by_site"].get("fused_step",
                                                {"hits": 0, "misses": 0})
    mod, p3 = _fit(monkeypatch, {"TPUMX_DP_DEVICES": "2",
                                 "TPUMX_PP_DEVICES": "2",
                                 "TPUMX_MP_DEVICES": "2"}, num_epoch=2)
    mesh = mod._exec._spmd_mesh
    assert tuple(mesh.axis_names) == ("dp", "pp", "mp")
    assert mod._exec._spmd_pipeline is not None
    assert mod._fused_step_count == 20
    _close(p0, p3)
    after = compile_cache_stats()["by_site"]["fused_step"]
    assert after["misses"] - base["misses"] == 1
    assert after["hits"] - base["hits"] == 19


def test_fit_pp_microbatch_env(monkeypatch):
    _, p0 = _fit(monkeypatch, {})
    mod, pp = _fit(monkeypatch, {"TPUMX_PP_DEVICES": "2",
                                 "TPUMX_PP_MICROBATCHES": "4"})
    assert mod._exec._spmd_pipeline is not None
    assert mod._exec._spmd_pipeline[1] == 4
    _close(p0, pp)


def test_fit_falls_back_when_not_stackable(monkeypatch, caplog):
    """A non-stackable symbol drops the pp axis with a logged reason and
    trains dp-only — never an error mid-fit."""
    import logging

    with caplog.at_level(logging.WARNING):
        mod, pp = _fit(monkeypatch, {"TPUMX_DP_DEVICES": "2",
                                     "TPUMX_PP_DEVICES": "2"}, layers=0)
    assert mod._exec._spmd_pipeline is None
    mesh = mod._exec._spmd_mesh
    assert mesh is not None and "pp" not in mesh.axis_names
    assert any("stage-stackable" in r.message for r in caplog.records)
    _, p0 = _fit(monkeypatch, {}, layers=0)
    _close(p0, pp)


def test_signature_keys_pipeline_and_explainer_renders_drift(monkeypatch):
    """The fused-step key carries ("pp", S, M) + the 3-axis mesh map, and
    the explainer renders mesh/pipeline drift per-site:
    "mesh shape dp=4→dp=2×pp=2", "pipeline off→pp=2×mb=8"."""
    from mxnet_tpu.observability import recompile as rc

    rc.reset()
    monkeypatch.setenv("TPUMX_EXPLAIN_RECOMPILES", "1")
    _fit(monkeypatch, {"TPUMX_DP_DEVICES": "4"})
    monkeypatch.delenv("TPUMX_DP_DEVICES", raising=False)
    _fit(monkeypatch, {"TPUMX_DP_DEVICES": "2", "TPUMX_PP_DEVICES": "2"})
    causes = [c for e in rc.last_explanations() for c in e["causes"]]
    assert any("pipeline off→pp=2×mb=" in c for c in causes), causes
    assert any("mesh shape" in c and "pp=2" in c for c in causes), causes

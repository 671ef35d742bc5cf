"""Fused whole-train-step execution (docs/fused_step.md): numerical parity
with the legacy per-param path, compile-cache discipline, donation safety,
and the env/bulk satellites."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, nd, sym
from mxnet_tpu.executor import compile_cache_stats
from mxnet_tpu.io import DataBatch

pytestmark = pytest.mark.fused


def _mlp_sym(nh=16, classes=4):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=nh, name="fc1"),
                       act_type="relu")
    out = sym.FullyConnected(h, num_hidden=classes, name="fc2")
    return sym.SoftmaxOutput(out, label, name="softmax")


def _bn_sym(nh=16, classes=4):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.BatchNorm(sym.FullyConnected(data, num_hidden=nh, name="fc1"),
                      name="bn1")
    out = sym.FullyConnected(sym.Activation(h, act_type="relu"),
                             num_hidden=classes, name="fc2")
    return sym.SoftmaxOutput(out, label, name="softmax")


def _toy_iter(n=320, dim=8, classes=4, batch=32, shuffle=False):
    r = np.random.RandomState(0)
    Y = r.randint(0, classes, n).astype(np.float32)
    X = r.rand(n, dim).astype(np.float32) * 0.3
    for c in range(classes):
        X[Y == c, c] += 1.0
    return mx.io.NDArrayIter(X, Y, batch_size=batch, shuffle=shuffle)


def _fit(monkeypatch, fused, optimizer, opt_params, symbol=None, num_epoch=1):
    monkeypatch.setenv("TPUMX_FUSED_STEP", "1" if fused else "0")
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(symbol or _mlp_sym(), context=mx.cpu())
    mod.fit(_toy_iter(), num_epoch=num_epoch, optimizer=optimizer,
            optimizer_params=opt_params)
    arg, aux = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in arg.items()}, \
        {k: v.asnumpy() for k, v in aux.items()}


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", (("learning_rate", 0.5),)),
    ("sgd", (("learning_rate", 0.5), ("momentum", 0.9))),
    ("adam", (("learning_rate", 0.05),)),
    ("adagrad", (("learning_rate", 0.1),)),
    ("rmsprop", (("learning_rate", 0.01),)),
], ids=["sgd", "sgd_momentum", "adam", "adagrad", "rmsprop"])
def test_fused_parity_10_steps(monkeypatch, optimizer, opt_params):
    """Fused fit == legacy fit over 10 fixed-shape steps, rtol 1e-5."""
    m_legacy, legacy, _ = _fit(monkeypatch, False, optimizer, opt_params)
    m_fused, fused, _ = _fit(monkeypatch, True, optimizer, opt_params)
    assert m_legacy._fused_step_count == 0
    assert m_fused._fused_step_count == 10
    for k in legacy:
        np.testing.assert_allclose(fused[k], legacy[k], rtol=1e-5, atol=1e-7,
                                   err_msg=f"{optimizer}: {k}")


def test_fused_parity_batchnorm_aux(monkeypatch):
    """Through a BatchNorm net: params AND the functionally-committed aux
    running stats match the legacy path.  (SGD here: BN makes fc1_bias a
    zero-gradient parameter, and adaptive optimizers dividing by
    sqrt(state)~eps amplify ulp noise chaotically on it — see
    docs/fused_step.md; adaptive-optimizer parity is covered on the clean
    MLP above.)"""
    params = (("learning_rate", 0.1), ("momentum", 0.9))
    m0, legacy, legacy_aux = _fit(monkeypatch, False, "sgd", params, _bn_sym())
    m1, fused, fused_aux = _fit(monkeypatch, True, "sgd", params, _bn_sym())
    assert m1._fused_step_count == 10
    for k in legacy:
        np.testing.assert_allclose(fused[k], legacy[k], rtol=1e-5, atol=1e-6)
    assert legacy_aux  # BatchNorm must expose moving_mean/var
    for k in legacy_aux:
        np.testing.assert_allclose(fused_aux[k], legacy_aux[k],
                                   rtol=1e-5, atol=1e-6)


def test_fused_env_roundtrip(monkeypatch):
    """TPUMX_FUSED_STEP=0 -> legacy path -> =1 again: same results, and the
    flag actually routes (step counters prove which path ran)."""
    _, legacy1, _ = _fit(monkeypatch, False, "sgd", (("learning_rate", 0.5),))
    m, fused, _ = _fit(monkeypatch, True, "sgd", (("learning_rate", 0.5),))
    assert m._fused_step_count == 10
    _, legacy2, _ = _fit(monkeypatch, False, "sgd", (("learning_rate", 0.5),))
    for k in legacy1:
        np.testing.assert_array_equal(legacy1[k], legacy2[k])
        np.testing.assert_allclose(fused[k], legacy1[k], rtol=1e-5, atol=1e-7)


def test_fused_unsupported_optimizer_falls_back(monkeypatch):
    """A non-fused-capable optimizer must train via the legacy loop (and
    still learn)."""
    m, _, _ = _fit(monkeypatch, True, "signum", (("learning_rate", 0.05),))
    assert m._fused_step_count == 0
    acc = dict(m.score(_toy_iter(), "acc"))["accuracy"]
    assert acc > 0.5


def test_fused_compile_cache_discipline(monkeypatch):
    """N fused steps at fixed shapes: exactly ONE fused-program miss; the
    remaining N-1 lookups hit."""
    monkeypatch.setenv("TPUMX_FUSED_STEP", "1")
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    before = compile_cache_stats()
    mod.fit(_toy_iter(), num_epoch=2, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1),))
    after = compile_cache_stats()
    assert mod._fused_step_count == 20
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 19


def test_use_after_donate_safety(monkeypatch):
    """No NDArray handle the framework (or a get_params caller) holds may
    observe a donated buffer: snapshots stay valid and unchanged across
    subsequent donating steps, and every executor/updater handle stays
    readable."""
    monkeypatch.setenv("TPUMX_FUSED_STEP", "1")
    mx.random.seed(0)
    np.random.seed(0)
    it = _toy_iter()
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.5), ("momentum", 0.9)))
    assert mod._fused_step_count == 10
    arg_snap, aux_snap = mod.get_params()
    frozen = {k: v.asnumpy().copy() for k, v in arg_snap.items()}
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.5), ("momentum", 0.9)),
            force_init=False)
    # the snapshot survives further donating steps, bit-for-bit
    for k, v in arg_snap.items():
        np.testing.assert_array_equal(v.asnumpy(), frozen[k])
    # every live framework handle is readable (donation rebound them)
    for n, a in mod._exec.arg_dict.items():
        assert np.isfinite(a.asnumpy()).all(), n
    for n, g in mod._exec.grad_dict.items():
        assert g.asnumpy().shape == mod._exec.arg_dict[n].shape
    for idx, state in mod._updater.states.items():
        leaves = state if isinstance(state, tuple) else (state,)
        for leaf in leaves:
            if leaf is not None:
                assert np.isfinite(leaf.asnumpy()).all()
    # params kept training after the snapshot (donated buffers were consumed,
    # not silently reused as stale weights)
    trained, _ = mod.get_params()
    assert any(not np.array_equal(trained[k].asnumpy(), frozen[k])
               for k in frozen)


def test_signature_includes_aux_states(monkeypatch):
    """Regression (executor.py _signature): aux shapes/dtypes are part of the
    compile-cache key — a rebind changing ONLY aux shapes must not report a
    cache hit on a stale program."""
    ex = _bn_sym().simple_bind(ctx=mx.cpu(), data=(8, 8),
                               softmax_label=(8,))
    sig = ex._signature(True)
    aux_entries = [s for s in sig if isinstance(s, tuple) and s[0] == "aux"]
    assert {e[1] for e in aux_entries} == set(ex._aux_names)
    ex._get_fwd(False)
    before = compile_cache_stats()
    ex._get_fwd(False)
    mid = compile_cache_stats()
    assert mid["hits"] - before["hits"] == 1  # unchanged aux: a hit
    import jax.numpy as jnp

    name = ex._aux_names[0]
    ex.aux_dict[name]._data = jnp.zeros((32,), jnp.float32)
    ex._get_fwd(False)
    after = compile_cache_stats()
    assert after["misses"] - mid["misses"] == 1  # aux-only change: a miss


def test_engine_exports_bulk_size_and_fusion_hint():
    """Satellite: engine.bulk_size is exported, and the fusion hint is 1
    outside an explicit bulk scope, k inside."""
    assert "bulk_size" in engine.__all__
    assert engine.bulk_size() == 15  # process default untouched
    assert engine.fusion_hint() == 1
    with engine.bulk(3):
        assert engine.bulk_size() == 3
        assert engine.fusion_hint() == 3
        with engine.bulk(5):
            assert engine.fusion_hint() == 5
        assert engine.fusion_hint() == 3
    assert engine.fusion_hint() == 1
    assert engine.bulk_size() == 15


def test_fused_multi_step_bulk(monkeypatch):
    """k=3 whole steps fused into ONE dispatch via the bulk hint equal 3
    sequential legacy steps on the same batch, for one compile."""
    r = np.random.RandomState(0)
    batch = DataBatch([nd.array(r.rand(16, 8).astype(np.float32))],
                      [nd.array(r.randint(0, 4, 16).astype(np.float32))])

    def build(env):
        monkeypatch.setenv("TPUMX_FUSED_STEP", env)
        mx.random.seed(0)
        np.random.seed(0)
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (16, 8))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),))
        return mod

    m0 = build("0")
    for _ in range(3):
        m0.forward_backward(batch)
        m0.update()
    legacy, _ = m0.get_params()

    m1 = build("1")
    opt = m1._optimizer
    updates, states = [], {}
    for i, n in enumerate(m1._param_names):
        updates.append((n, i))
        states[n] = opt.create_state_multi_precision(
            i, m1._exec.arg_dict[n])
    before = compile_cache_stats()
    with engine.bulk(3):
        m1._exec.fused_step(opt, states, updates,
                            feed={"data": batch.data[0],
                                  "softmax_label": batch.label[0]})
    after = compile_cache_stats()
    assert after["misses"] - before["misses"] == 1
    assert opt.num_update == 3  # counts advanced per inner step
    for n in legacy:
        np.testing.assert_allclose(m1._exec.arg_dict[n].asnumpy(),
                                   legacy[n].asnumpy(),
                                   rtol=1e-5, atol=1e-7)


def test_module_update_routes_through_fused_updater(monkeypatch):
    """Manual forward_backward()+update() applies all params in one fused
    optimizer program (Updater batch path) and matches the per-param loop."""
    r = np.random.RandomState(0)
    batch = DataBatch([nd.array(r.rand(16, 8).astype(np.float32))],
                      [nd.array(r.randint(0, 4, 16).astype(np.float32))])

    def run(env):
        monkeypatch.setenv("TPUMX_FUSED_STEP", env)
        mx.random.seed(0)
        np.random.seed(0)
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
        mod.bind(data_shapes=[("data", (16, 8))],
                 label_shapes=[("softmax_label", (16,))])
        mod.init_params()
        mod.init_optimizer(optimizer="adam",
                           optimizer_params=(("learning_rate", 0.05),))
        for _ in range(5):
            mod.forward_backward(batch)
            mod.update()
        arg, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}

    legacy = run("0")
    fused = run("1")
    for k in legacy:
        np.testing.assert_allclose(fused[k], legacy[k], rtol=1e-5, atol=1e-7)


def test_update_metric_no_asnumpy_on_fit_path(monkeypatch):
    """Acceptance: update_metric no longer syncs per batch on the fit path —
    the blocking Accuracy.update must never run; the device accumulation
    drains once at get()."""
    from mxnet_tpu import metric as metric_mod

    def boom(self, labels, preds):  # pragma: no cover - must not be called
        raise AssertionError("blocking Accuracy.update called on fit path")

    monkeypatch.setattr(metric_mod.Accuracy, "update", boom)
    monkeypatch.setenv("TPUMX_FUSED_STEP", "1")
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(_toy_iter(shuffle=True), num_epoch=6, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.5),))
    assert mod._fused_step_count == 60
    acc = dict(mod.score(_toy_iter(), mx.metric.create("acc")))["accuracy"]
    assert acc > 0.9


def test_metric_device_accumulation_matches_blocking():
    """Device-side accumulation is lazy (no instances counted until get())
    and numerically identical to the blocking numpy path."""
    preds = nd.array(np.random.RandomState(3).rand(64, 4).astype(np.float32))
    labels = nd.array(np.random.RandomState(4).randint(0, 4, 64)
                      .astype(np.float32))
    blocking = mx.metric.create("acc")
    blocking.update([labels], [preds])
    lazy = mx.metric.create("acc")
    lazy.update_dict({"softmax_label": labels}, {"softmax_output": preds},
                     device=True)
    assert lazy.num_inst == 0  # nothing synced yet
    assert lazy.get() == blocking.get()
    lazy.reset()
    assert lazy.get()[1] != lazy.get()[1]  # NaN after reset (empty)


# ---------------------------------------------------------------------------
# the step plan (docs/fused_step.md "The step plan"): built once, used by a
# launch that prepare() has readied, dropped by whatever can change it
# ---------------------------------------------------------------------------

def _plan_counts():
    s = compile_cache_stats()
    return {k: s[k] for k in ("fused_plan_builds", "fused_plan_reuses",
                              "fused_uniquify_runs")}


def _delta(before):
    now = _plan_counts()
    return {k: now[k] - before[k] for k in now}


def _snapshot(mod):
    """Parameters and optimizer state as a callback reads them after a
    step, to the bit."""
    out = {n: mod._exec.arg_dict[n].asnumpy() for n in mod._param_names}
    for idx, st in mod._updater.states.items():
        leaves = st if isinstance(st, tuple) else (st,)
        for j, leaf in enumerate(leaves):
            if leaf is not None:
                out[f"state{idx}.{j}"] = leaf.asnumpy()
    return out


def _dropout_sym(nh=16, classes=4):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.BatchNorm(sym.FullyConnected(data, num_hidden=nh, name="fc1"),
                      name="bn1")
    h = sym.Dropout(sym.Activation(h, act_type="relu"), p=0.5)
    out = sym.FullyConnected(h, num_hidden=classes, name="fc2")
    return sym.SoftmaxOutput(out, label, name="softmax")


@pytest.mark.parametrize("draws", [False, True],
                         ids=["", "callback_draws_a_key"])
@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", (("learning_rate", 0.5), ("momentum", 0.9))),
    ("adam", (("learning_rate", 0.05),)),
], ids=["sgd_momentum", "adam"])
def test_plan_reused_through_fit_is_bit_for_bit(monkeypatch, optimizer,
                                                opt_params, draws):
    """N steps through fit, a callback reading the updater's states and the
    parameters after EVERY step: the kept plan (built at step 1, reused
    N - 1 times, aliasing checked once) gives to the bit what a step built
    from nothing every time gives — the per-step rebuild this plan
    replaced, had by dropping the plan in the callback.  The net drops
    units, so the step's key matters: the key a launch readied while the
    device ran is the key a launch draws for itself, also when the callback
    draws one in between."""
    monkeypatch.setenv("TPUMX_FUSED_STEP", "1")
    net = _dropout_sym()  # one symbol: a node's key folds the node's uid in

    def run(rebuild):
        mx.random.seed(0)
        np.random.seed(0)
        mod = mx.mod.Module(net, context=mx.cpu())
        seen = []

        def cb(param):
            seen.append(_snapshot(mod))
            if draws:
                seen[-1]["drawn"] = np.asarray(mx.random.next_key())
            if rebuild:
                mod._drop_fused_plan()

        before = _plan_counts()
        mod.fit(_toy_iter(), num_epoch=1, optimizer=optimizer,
                optimizer_params=opt_params, batch_end_callback=cb)
        return mod, seen, _delta(before)

    mod, kept, counts = run(rebuild=False)
    n = mod._fused_step_count
    assert n == 10
    assert counts == {"fused_plan_builds": 1, "fused_plan_reuses": n - 1,
                      "fused_uniquify_runs": 1}
    _, rebuilt, counts0 = run(rebuild=True)
    assert counts0["fused_plan_builds"] == n
    assert len(kept) == len(rebuilt) == n
    for step, (a, b) in enumerate(zip(kept, rebuilt)):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"step {step + 1}: {k}")
    # the callback at batch n saw step n: every snapshot differs from the
    # one before it
    assert any(not np.array_equal(kept[0][k], kept[1][k]) for k in kept[0])


def _bound(monkeypatch, fused, symbol=None, batch=32, optimizer="sgd",
           opt_params=(("learning_rate", 0.1), ("momentum", 0.9)),
           context=None, kvstore="local"):
    monkeypatch.setenv("TPUMX_FUSED_STEP", "1" if fused else "0")
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(symbol or _mlp_sym(), context=context or mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 8))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                       optimizer_params=opt_params)
    return mod


def _batches(n, batch=32, seed=1):
    r = np.random.RandomState(seed)
    return [DataBatch([nd.array(r.rand(batch, 8).astype(np.float32))],
                      [nd.array(r.randint(0, 4, batch).astype(np.float32))])
            for _ in range(n)]


def _step(mod, fused, batch):
    if fused:
        assert mod._try_fused_step(batch)
    else:
        mod.forward_backward(batch)
        mod.update()


def _set_params(mod, tmp_path):
    arg, aux = mod.get_params()
    mod.set_params({k: v * 0.5 for k, v in arg.items()}, aux)


def _write_data(mod, tmp_path):
    import jax.numpy as jnp

    arr = mod._exec.arg_dict["fc1_weight"]
    arr._data = jnp.asarray(arr.asnumpy() * 0.25)


def _load_states(mod, tmp_path):
    # the states of two steps ago come back: new holders in the updater
    mod.load_optimizer_states(str(tmp_path / "opt.states"))


def _new_optimizer(mod, tmp_path):
    mod.init_optimizer(optimizer="sgd", force_init=True,
                       optimizer_params=(("learning_rate", 0.1),
                                         ("momentum", 0.9)))
    mod._optimizer.set_lr_mult({"fc1_weight": 0.25})


def _lr_mult(mod, tmp_path):
    mod._optimizer.set_lr_mult({"fc2_weight": 0.5})


def _scaler(mod, tmp_path):
    from mxnet_tpu import amp

    # attached on the fused module alone: the legacy path trains unscaled,
    # and a power-of-two scale leaves float32 gradients as they were
    if mod._fused_step_count:
        mod._loss_scaler = amp.LossScaler(init_scale=8.0, dynamic=False)


@pytest.mark.parametrize("change,moved", [
    (_set_params, {"fused_uniquify_runs": 1}),
    (_write_data, {"fused_uniquify_runs": 1}),
    (_load_states, {"fused_plan_builds": 1}),
    (_new_optimizer, {"fused_plan_builds": 1}),
    (_lr_mult, {"fused_plan_builds": 1}),
    (_scaler, {"fused_plan_builds": 1}),
], ids=["set_params", "data_written", "load_optimizer_states",
        "init_optimizer_lr_mult", "set_lr_mult", "loss_scaler"])
def test_plan_sees_what_changes_between_steps(monkeypatch, tmp_path, change,
                                              moved):
    """Each thing that can change between two steps is seen by the step
    after it — the plan holds holders, and what replaces a holder drops
    the plan: against the legacy unfused path's result."""
    batches = _batches(6)
    data, label = sym.Variable("data"), sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=16, name="fc1"),
                       act_type="relu")
    # out_grad: the head multiplies the seed in, so a scaler's scale
    # reaches the gradients (tests/test_amp.py)
    net = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=4, name="fc2"),
                            label, name="softmax", out_grad=True)

    def run(fused):
        mod = _bound(monkeypatch, fused, symbol=net)
        for i, b in enumerate(batches):
            if i == 2:
                mod.save_optimizer_states(str(tmp_path / "opt.states"))
            if i == 4:
                before = _plan_counts()
                change(mod, tmp_path)
            _step(mod, fused, b)
            if i == 4:
                counts = _delta(before)
        arg, _ = mod.get_params()
        return mod, {k: v.asnumpy() for k, v in arg.items()}, counts

    mod, fused, counts = run(True)
    assert mod._fused_step_count == 6
    for k, v in moved.items():
        assert counts[k] >= v, (k, counts)
    # steps 1-4 and 6 reused the first plan or the rebuilt one
    assert counts["fused_plan_reuses"] == 0
    _, legacy, _ = run(False)
    for k in legacy:
        np.testing.assert_allclose(fused[k], legacy[k], rtol=2e-5, atol=1e-7,
                                   err_msg=k)


def test_plan_follows_a_reshape_to_another_batch_size(monkeypatch):
    """A batch of another size rebinds (a new executor, so a new plan) at
    the call, prepared or not, and trains as the legacy path does."""
    batches = _batches(3) + _batches(3, batch=16, seed=2)

    def run(fused):
        mod = _bound(monkeypatch, fused)
        for b in batches:
            if fused:
                mod.prepare(b)  # declines a batch of another shape
            _step(mod, fused, b)
        arg, _ = mod.get_params()
        return mod, {k: v.asnumpy() for k, v in arg.items()}

    before = _plan_counts()
    mod, fused = run(True)
    assert mod._fused_step_count == 6
    assert mod._exec.arg_dict["data"].shape == (16, 8)
    assert _delta(before)["fused_plan_builds"] == 2
    _, legacy = run(False)
    for k in legacy:
        np.testing.assert_allclose(fused[k], legacy[k], rtol=2e-5, atol=1e-7)


def test_aliased_buffers_are_checked_when_they_can_occur(monkeypatch):
    """Two parameters that share ONE zero buffer after init_params still
    train: the aliasing check runs on the first launch and after
    set_params, and on no launch in between."""
    import jax.numpy as jnp

    from mxnet_tpu import optimizer as opt_mod

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=4, name="fc1"),
                       act_type="relu")
    net = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=4, name="fc2"),
                            label, name="softmax")
    calls = []
    orig = opt_mod.uniquify_donated

    def counted(trees):
        out = orig(trees)
        calls.append(sum(a is not b for a, b in zip(trees, out)))
        return out

    monkeypatch.setattr(opt_mod, "uniquify_donated", counted)

    def run(fused):
        mod = _bound(monkeypatch, fused, symbol=net)
        z = jnp.zeros((4,), jnp.float32)
        mod._exec.arg_dict["fc1_bias"]._data = z
        mod._exec.arg_dict["fc2_bias"]._data = z
        for i, b in enumerate(_batches(6)):
            if i == 3:
                arg, aux = mod.get_params()
                mod.set_params(arg, aux)
            _step(mod, fused, b)
        arg, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}

    fused = run(True)
    # two launches checked; the first found the shared buffer and copied it
    assert len(calls) == 2 and calls[0] >= 1
    legacy = run(False)
    assert not np.array_equal(fused["fc1_bias"], fused["fc2_bias"])
    for k in legacy:
        np.testing.assert_allclose(fused[k], legacy[k], rtol=2e-5, atol=1e-7)


@pytest.mark.parametrize("ndev", [1, 2], ids=["one_device", "dp2"])
def test_unprepared_batch_takes_the_same_step(monkeypatch, ndev):
    """A hand-written loop never calls prepare(): its step is, to the bit,
    the step of a batch that fit prepared while the device ran."""
    ctx = [mx.cpu(i) for i in range(ndev)]
    kv = "local" if ndev == 1 else "tpu_sync"

    def run(prepared):
        mod = _bound(monkeypatch, True, context=ctx, kvstore=kv,
                     symbol=_bn_sym())
        before = _plan_counts()
        for b in _batches(5):
            if prepared:
                mod.prepare(b)
                # the first batch finds no plan to prepare from
                assert (mod._prepared is not None) == \
                    (mod._fused_step_count > 0)
            assert mod._try_fused_step(b)
            assert mod._prepared is None
        assert _delta(before) == {"fused_plan_builds": 1,
                                  "fused_plan_reuses": 4,
                                  "fused_uniquify_runs": 1}
        return _snapshot(mod)

    a, b = run(True), run(False)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("num_steps", [1, 3], ids=["one", "bulk3"])
def test_counts_and_schedule_advance_as_the_legacy_loop(monkeypatch,
                                                        num_steps):
    """After k steps the update counts, num_update and the scheduler's lr
    are the legacy per-param loop's (callbacks and checkpoints read them)."""
    def sched():
        return mx.lr_scheduler.FactorScheduler(step=4, factor=0.5)

    k = 7
    batches = _batches(k)
    legacy = _bound(monkeypatch, False, opt_params=(
        ("learning_rate", 0.2), ("momentum", 0.9), ("lr_scheduler", sched())))
    for b in batches:
        for _ in range(num_steps):
            _step(legacy, False, b)
    fused = _bound(monkeypatch, True, opt_params=(
        ("learning_rate", 0.2), ("momentum", 0.9), ("lr_scheduler", sched())))
    for b in batches:
        if num_steps == 1:
            _step(fused, True, b)
        else:
            opt = fused._optimizer
            updates = [(n, i) for i, n in enumerate(fused._param_names)]
            states = {n: fused._updater.states.setdefault(
                i, opt.create_state_multi_precision(
                    i, fused._exec.arg_dict[n])) for n, i in updates}
            fused._exec.fused_step(
                opt, states, updates, num_steps=num_steps,
                feed={"data": b.data[0], "softmax_label": b.label[0]})
    lo, fo = legacy._optimizer, fused._optimizer
    assert fo._index_update_count == lo._index_update_count
    assert fo.num_update == lo.num_update == k * num_steps
    assert fo.learning_rate == lo.learning_rate
    assert fo.lr_scheduler.base_lr == lo.lr_scheduler.base_lr < 0.2
    for n in legacy._param_names:
        np.testing.assert_allclose(fused._exec.arg_dict[n].asnumpy(),
                                   legacy._exec.arg_dict[n].asnumpy(),
                                   rtol=2e-5, atol=1e-7, err_msg=n)


def test_mixed_update_counts_fall_to_the_legacy_path(monkeypatch):
    """A user's partial legacy update leaves mixed counts: the step is the
    per-param loop's, the plan stays, and nothing was advanced for it."""
    mod = _bound(monkeypatch, True)
    b = _batches(1)[0]
    assert mod._try_fused_step(b)
    opt = mod._optimizer
    opt._update_count(0)  # one param one update ahead
    counts = dict(opt._index_update_count)
    assert not mod._try_fused_step(b)
    assert opt._index_update_count == counts
    assert mod._exec._fused_plan is not None
    with pytest.raises(mx.MXNetError, match="mixed update counts"):
        updates = [(n, i) for i, n in enumerate(mod._param_names)]
        mod._exec.fused_step(opt, {n: mod._updater.states[i]
                                   for n, i in updates}, updates)


def test_a_monitor_drops_the_plan(monkeypatch):
    """install_monitor wants the legacy path's per-step introspection."""
    mod = _bound(monkeypatch, True)
    b = _batches(1)[0]
    assert mod._try_fused_step(b)
    assert mod._exec._fused_plan is not None
    mod.install_monitor(mx.monitor.Monitor(1))
    assert mod._exec._fused_plan is None
    assert not mod._try_fused_step(b)


def _deep_sym(layers=50):
    h = sym.Variable("data")
    for i in range(layers):
        h = sym.Activation(sym.FullyConnected(h, num_hidden=8, name=f"fc{i}"),
                           act_type="relu")
    return sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=4, name="out"),
                             sym.Variable("softmax_label"), name="softmax")


def test_warm_step_rebuilds_nothing(monkeypatch):
    """The guard on the host cost that needs no clock: on a module with over
    100 parameters a warm step reads no buffer pointer (the aliasing check)
    and builds no signature (the program's key), through fit with its
    prepare() and through a hand-written loop."""
    import jax.numpy as jnp

    from mxnet_tpu.executor import Executor

    counts = {"pointer": 0, "signature": 0}
    array_type = type(jnp.zeros(1))
    pointer = array_type.unsafe_buffer_pointer
    signature = Executor._signature

    def counted_pointer(self):
        counts["pointer"] += 1
        return pointer(self)

    def counted_signature(self, is_train):
        counts["signature"] += 1
        return signature(self, is_train)

    monkeypatch.setattr(array_type, "unsafe_buffer_pointer", counted_pointer)
    monkeypatch.setattr(Executor, "_signature", counted_signature)
    mod = _bound(monkeypatch, True, symbol=_deep_sym())
    assert len(mod._param_names) >= 100
    batches = _batches(4)
    assert mod._try_fused_step(batches[0])
    assert counts["pointer"] >= 300 and counts["signature"] == 1
    counts.update(pointer=0, signature=0)
    before = _plan_counts()
    for b in batches[1:]:
        assert mod._try_fused_step(b)
    for b in batches:
        mod.prepare(b)
        assert mod._try_fused_step(b)
    assert counts == {"pointer": 0, "signature": 0}
    assert _delta(before) == {"fused_plan_builds": 0, "fused_plan_reuses": 7,
                              "fused_uniquify_runs": 0}


def test_plan_counters_reach_the_registry(monkeypatch):
    from mxnet_tpu import observability as obs

    def read():
        snap = obs.registry().snapshot()["counters"]
        return {k: snap.get(k + "_total", 0) for k in _plan_counts()}

    r0, c0 = read(), _plan_counts()
    mod = _bound(monkeypatch, True)
    for b in _batches(3):
        assert mod._try_fused_step(b)
    r1, c1 = read(), _plan_counts()
    assert {k: c1[k] - c0[k] for k in c1} == {
        "fused_plan_builds": 1, "fused_plan_reuses": 2,
        "fused_uniquify_runs": 1}
    assert {k: r1[k] - r0[k] for k in r1} == {k: c1[k] - c0[k] for k in c1}


@pytest.mark.parametrize("between", ["nothing", "a_draw", "a_seed"])
def test_a_split_made_ahead_keeps_the_keys_and_their_order(between):
    """random.split_ahead() does not move the stream, and next_key(ahead)
    returns what next_key() would: the ahead split when the stream stood
    still, a fresh one when somebody drew or seeded in between."""
    def run(ahead):
        mx.random.seed(11)
        keys = [mx.random.next_key()]
        token = mx.random.split_ahead() if ahead else None
        assert np.array_equal(mx.random.get_state(),
                              np.asarray(mx.random.split_ahead()[0]))
        if between == "a_draw":
            keys.append(mx.random.next_key())
        elif between == "a_seed":
            mx.random.seed(12)
        keys.append(mx.random.next_key(token))
        keys.append(mx.random.next_key())
        return [np.asarray(k) for k in keys], np.asarray(mx.random.get_state())

    (plain, state0), (ahead, state1) = run(False), run(True)
    assert len(plain) == len(ahead)
    for a, b in zip(plain, ahead):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(state0, state1)

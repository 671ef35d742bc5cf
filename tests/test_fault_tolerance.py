"""Fault-tolerant training (docs/fault_tolerance.md): async checkpointing +
resume parity, preemption via real SIGTERM, corrupt-checkpoint fallback,
kvstore retry/timeout/backoff under injected faults, serving graceful
shutdown, mesh-shape-change restore."""
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.checkpoint import CheckpointManager, verify_params_file
from mxnet_tpu.executor import compile_cache_stats
from mxnet_tpu.fault import corrupt_checkpoint, injector

pytestmark = pytest.mark.fault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAULT_ENVS = ("TPUMX_FAULT_KV_DROP", "TPUMX_FAULT_KV_DELAY_MS",
              "TPUMX_FAULT_KV_KILL_SERVER", "TPUMX_FAULT_PREEMPT_AT_STEP",
              "TPUMX_FAULT_CKPT_CORRUPT")


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    for k in FAULT_ENVS:
        monkeypatch.delenv(k, raising=False)
    injector().reset()
    yield
    for k in FAULT_ENVS:
        os.environ.pop(k, None)
    injector().reset()


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


def _fit(ckdir=None, preempt_step=None, resume=False, num_epoch=2,
         optimizer="sgd", opt_params=(("learning_rate", 0.1),), every=3,
         batch_end_callback=None):
    if preempt_step is not None:
        os.environ["TPUMX_FAULT_PREEMPT_AT_STEP"] = str(preempt_step)
    else:
        os.environ.pop("TPUMX_FAULT_PREEMPT_AT_STEP", None)
    injector().reset()
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    completed = mod.fit(_toy_iter(), num_epoch=num_epoch,
                        optimizer=optimizer, optimizer_params=opt_params,
                        checkpoint_dir=ckdir, checkpoint_every=every,
                        resume=resume, batch_end_callback=batch_end_callback)
    arg, aux = mod.get_params()
    return completed, {k: v.asnumpy() for k, v in arg.items()}, mod


# -- checkpoint manager: atomicity / retention / corruption fallback ---------------
def test_manager_save_latest_retention(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 2, 3, 4):
        m.save({"params": {"w": np.full((4,), step, np.float32)}},
               {0: (np.ones(3, np.float32),)},
               {"epoch": 0, "nbatch": step, "global_step": step},
               step=step, blocking=True)
    names = sorted(p for p in os.listdir(tmp_path)
                   if p.startswith("ckpt-"))
    assert names == ["ckpt-0000000003", "ckpt-0000000004"]  # keep=2
    info = m.latest()
    assert info.step == 4
    info2, arrays, opt = m.restore()
    assert info2.step == 4
    np.testing.assert_array_equal(arrays["params"]["w"],
                                  np.full((4,), 4, np.float32))
    np.testing.assert_array_equal(opt[0][0], np.ones(3, np.float32))
    assert info2.meta["nbatch"] == 4


@pytest.mark.parametrize("mode", ["flip", "truncate"])
def test_corrupt_newest_falls_back_to_previous(tmp_path, mode):
    m = CheckpointManager(str(tmp_path), keep=3)
    for step in (1, 2):
        m.save({"params": {"w": np.full((4,), step, np.float32)}},
               None, {"global_step": step}, step=step, blocking=True)
    corrupt_checkpoint(os.path.join(str(tmp_path), "ckpt-0000000002"), mode)
    info, arrays, _ = m.restore()
    assert info.step == 1  # newest failed checksum; previous one restored
    np.testing.assert_array_equal(arrays["params"]["w"],
                                  np.full((4,), 1, np.float32))
    from mxnet_tpu import observability as obs

    counters = obs.snapshot()["counters"]
    assert counters.get("checkpoint_restore_fallbacks_total", 0) >= 1


def test_async_save_commits_and_is_atomic(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=3)
    m.save({"params": {"w": np.arange(1024, dtype=np.float32)}},
           None, {"global_step": 5}, step=5, blocking=False)
    assert m.wait(timeout=30)
    # nothing half-written: only the committed dir, no .tmp- leftovers
    entries = os.listdir(tmp_path)
    assert "ckpt-0000000005" in entries
    assert not [e for e in entries if e.startswith(".tmp-")]
    assert m.validate(os.path.join(str(tmp_path), "ckpt-0000000005"))


def test_injected_ckpt_corruption_env(tmp_path, monkeypatch):
    """TPUMX_FAULT_CKPT_CORRUPT=truncate@2 corrupts exactly the 2nd commit."""
    monkeypatch.setenv("TPUMX_FAULT_CKPT_CORRUPT", "truncate@2")
    injector().reset()
    m = CheckpointManager(str(tmp_path), keep=3)
    for step in (1, 2):
        m.save({"params": {"w": np.full((8,), step, np.float32)}},
               None, {"global_step": step}, step=step, blocking=True)
    assert m.validate(os.path.join(str(tmp_path), "ckpt-0000000001"))
    assert m.validate(os.path.join(str(tmp_path), "ckpt-0000000002")) is None
    assert m.latest().step == 1


# -- kill-at-step-N resume parity (SGD / Adam / Adam+AMP) --------------------------
@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", (("learning_rate", 0.1), ("momentum", 0.9))),
    ("adam", (("learning_rate", 0.05),)),
], ids=["sgd_momentum", "adam"])
def test_preempt_resume_parity(tmp_path, optimizer, opt_params):
    """Preemption (a REAL SIGTERM raised by the injected fault) at step 7 of
    20 → final sync checkpoint → resume → identical params vs an
    uninterrupted run at rtol 1e-5."""
    done, ref, _ = _fit(optimizer=optimizer, opt_params=opt_params)
    assert done
    ckdir = str(tmp_path / "ck")
    done, _, _ = _fit(ckdir=ckdir, preempt_step=7, optimizer=optimizer,
                      opt_params=opt_params)
    assert not done  # exited early on the signal
    steps = [int(d.rsplit("-", 1)[1]) for d in os.listdir(ckdir)
             if d.startswith("ckpt-")]
    assert max(steps) == 7  # the final synchronous checkpoint
    done, res, mod = _fit(ckdir=ckdir, resume=True, optimizer=optimizer,
                          opt_params=opt_params)
    assert done
    assert mod._fused_step_count == 13  # 20 total - 7 already done
    for k in ref:
        np.testing.assert_allclose(res[k], ref[k], rtol=1e-5, atol=1e-7,
                                   err_msg=f"{optimizer}: {k}")


@pytest.mark.amp
def test_preempt_resume_parity_adam_amp(tmp_path, monkeypatch):
    """Adam + fp16 AMP with a dynamic loss scaler: the scaler state rides
    the checkpoint, resumed trajectory matches uninterrupted at rtol 1e-5."""
    for k, v in (("TPUMX_AMP", "1"), ("TPUMX_AMP_DTYPE", "float16"),
                 ("TPUMX_AMP_LOSS_SCALE", "dynamic")):
        monkeypatch.setenv(k, v)
    done, ref, mref = _fit(optimizer="adam",
                           opt_params=(("learning_rate", 0.05),))
    assert done and mref._loss_scaler is not None
    ckdir = str(tmp_path / "ck")
    _fit(ckdir=ckdir, preempt_step=13, optimizer="adam",
         opt_params=(("learning_rate", 0.05),), every=4)
    done, res, mod = _fit(ckdir=ckdir, resume=True, optimizer="adam",
                          opt_params=(("learning_rate", 0.05),))
    assert done
    assert mod._loss_scaler.scale_value == mref._loss_scaler.scale_value
    for k in ref:
        np.testing.assert_allclose(res[k], ref[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_resume_from_corrupt_newest_checkpoint(tmp_path):
    """fit(resume=True) skips a corrupted newest checkpoint and resumes
    from the previous retained one — still completing the full epoch
    budget (more steps re-run, same final trajectory invariants)."""
    ckdir = str(tmp_path / "ck")
    # an async save that finds the writer busy is skipped by design: let
    # the writer land step 3 before the fit reaches step 6
    _fit(ckdir=ckdir, preempt_step=7,  # checkpoints at 3, 6, final 7
         batch_end_callback=lambda p: p.locals["_ckpt"].manager.wait())
    assert sorted(os.listdir(ckdir)) == [
        "ckpt-0000000003", "ckpt-0000000006", "ckpt-0000000007"]
    corrupt_checkpoint(os.path.join(ckdir, "ckpt-0000000007"), "flip")
    done, res, mod = _fit(ckdir=ckdir, resume=True)
    assert done
    assert mod._fused_step_count == 14  # resumed from step 6, not 7
    done2, ref, _ = _fit()
    for k in ref:
        np.testing.assert_allclose(res[k], ref[k], rtol=1e-5, atol=1e-7)


def test_checkpointing_keeps_compile_cache_discipline(tmp_path, monkeypatch):
    """Async snapshots add ZERO executor-cache compiles: still exactly one
    fused-program miss across a checkpointed 2-epoch fit, and further
    checkpointed steps under TPUMX_FREEZE_COMPILES=1 stay clean."""
    from mxnet_tpu import observability as obs
    from mxnet_tpu.checkpoint import TrainCheckpointer

    before = compile_cache_stats()
    done, _, mod = _fit(ckdir=str(tmp_path / "ck"), every=2)
    after = compile_cache_stats()
    assert done and mod._fused_step_count == 20
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 19
    # freeze leg: post-warmup checkpointed steps must not compile at all
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    obs.mark_warm()
    try:
        ck = TrainCheckpointer(mod, str(tmp_path / "ck2"), every=1, keep=2)
        batch0 = next(iter(_toy_iter()))
        for i in range(3):  # every step snapshots; none may compile
            assert mod._try_fused_step(batch0)
            ck.save(0, i + 1, i + 1, blocking=False)
        ck.close()
    finally:
        obs.recompile.reset()


# -- real SIGTERM in a subprocess --------------------------------------------------
_CHILD = textwrap.dedent("""
    import os, sys, json
    import numpy as np
    import jax; jax.config.update("jax_platforms", "cpu")
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    def mlp():
        data = sym.Variable("data"); label = sym.Variable("softmax_label")
        h = sym.Activation(sym.FullyConnected(data, num_hidden=16,
                                              name="fc1"), act_type="relu")
        return sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=4,
                                                    name="fc2"),
                                 label, name="softmax")

    r = np.random.RandomState(0)
    Y = r.randint(0, 4, 320).astype(np.float32)
    X = r.rand(320, 8).astype(np.float32) * 0.3
    for c in range(4):
        X[Y == c, c] += 1.0

    ready_file = os.environ["READY_FILE"]

    def on_batch(param):
        import time
        # signal the parent once training is demonstrably mid-flight, then
        # pace the remaining batches so the SIGTERM lands MID-fit
        if param.nbatch == 4 and not os.path.exists(ready_file):
            open(ready_file, "w").write("ready")
        if os.path.exists(ready_file):
            time.sleep(0.25)

    mx.random.seed(0); np.random.seed(0)
    mod = mx.mod.Module(mlp(), context=mx.cpu())
    completed = mod.fit(
        mx.io.NDArrayIter(X, Y, batch_size=32), num_epoch=2,
        optimizer="sgd", optimizer_params=(("learning_rate", 0.1),),
        batch_end_callback=on_batch if os.environ.get("SLOW") else None,
        checkpoint_dir=os.environ["CKPT_DIR"], checkpoint_every=3,
        resume=os.environ.get("RESUME") == "1")
    arg, _ = mod.get_params()
    np.savez(os.environ["OUT_FILE"],
             **{k: v.asnumpy() for k, v in arg.items()})
    print("COMPLETED" if completed else "PREEMPTED")
""")


def _run_child(env, timeout=240, wait_ready_then_sigterm=None):
    full = dict(os.environ)
    full.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                 "MXTPU_NO_NATIVE": "1"})
    full.update(env)
    p = subprocess.Popen([sys.executable, "-c", _CHILD], env=full,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if wait_ready_then_sigterm:
        deadline = time.time() + timeout
        while not os.path.exists(wait_ready_then_sigterm):
            if time.time() > deadline or p.poll() is not None:
                out, _ = p.communicate(timeout=10)
                raise AssertionError(
                    "child never became ready:\n" + out.decode())
            time.sleep(0.05)
        p.send_signal(signal.SIGTERM)
    try:
        out, _ = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        out, _ = p.communicate()
        raise AssertionError("child timed out:\n" + out.decode())
    return p.returncode, out.decode()


def test_sigterm_mid_fit_subprocess_resume_parity(tmp_path):
    """Acceptance: a REAL SIGTERM delivered by the parent mid-fit → clean
    exit (rc 0) with a final checkpoint; restart with resume → final
    params match an uninterrupted run at rtol 1e-5."""
    ckdir = str(tmp_path / "ck")
    ref_out = str(tmp_path / "ref.npz")
    rc, out = _run_child({"CKPT_DIR": str(tmp_path / "ref_ck"),
                          "OUT_FILE": ref_out,
                          "READY_FILE": str(tmp_path / "unused")})
    assert rc == 0 and "COMPLETED" in out, out

    ready = str(tmp_path / "ready")
    mid_out = str(tmp_path / "mid.npz")
    rc, out = _run_child({"CKPT_DIR": ckdir, "OUT_FILE": mid_out,
                          "READY_FILE": ready, "SLOW": "1"},
                         wait_ready_then_sigterm=ready)
    assert rc == 0, out              # process exits cleanly on SIGTERM
    assert "PREEMPTED" in out, out   # fit returned early, ckpt written
    assert [d for d in os.listdir(ckdir) if d.startswith("ckpt-")]

    res_out = str(tmp_path / "res.npz")
    rc, out = _run_child({"CKPT_DIR": ckdir, "OUT_FILE": res_out,
                          "RESUME": "1",
                          "READY_FILE": str(tmp_path / "unused2")})
    assert rc == 0 and "COMPLETED" in out, out
    ref = np.load(ref_out)
    res = np.load(res_out)
    assert set(ref.files) == set(res.files)
    for k in ref.files:
        np.testing.assert_allclose(res[k], ref[k], rtol=1e-5, atol=1e-7,
                                   err_msg=k)


# -- mesh-shape change across restore ----------------------------------------------
@pytest.mark.sharding
def test_mp2_save_mp1_restore(tmp_path, monkeypatch):
    """Checkpoints written under an mp=2 sharded mesh hold full gathered
    arrays: restore under mp=1 (no mesh) continues training bit-correctly."""
    ckdir = str(tmp_path / "ck")
    monkeypatch.setenv("TPUMX_MP_DEVICES", "2")
    done, sharded_params, mod = _fit(ckdir=ckdir, preempt_step=5,
                                     num_epoch=1)
    assert not done
    assert mod._exec._spmd_param_specs  # really ran rule-sharded
    monkeypatch.delenv("TPUMX_MP_DEVICES")
    done, res, mod2 = _fit(ckdir=ckdir, resume=True, num_epoch=1)
    assert done
    assert mod2._fused_step_count == 5  # 10 per epoch - 5 done
    done, ref, _ = _fit(num_epoch=1)
    for k in ref:
        np.testing.assert_allclose(res[k], ref[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


# -- classic save/load_checkpoint validation ---------------------------------------
def test_load_checkpoint_detects_truncation(tmp_path):
    prefix = str(tmp_path / "model")
    net = _mlp_sym()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    arg, aux = mod.get_params()
    mx.model.save_checkpoint(prefix, 0, net, arg, aux)
    assert os.path.exists(prefix + "-0000.params.manifest.json")
    sym2, arg2, _ = mx.model.load_checkpoint(prefix, 0)  # clean load
    assert set(arg2) == set(arg)
    path = prefix + "-0000.params"
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    with pytest.raises(MXNetError, match="truncated|checksum|corrupt"):
        mx.model.load_checkpoint(prefix, 0)


def test_load_checkpoint_names_missing_key(tmp_path):
    prefix = str(tmp_path / "model")
    net = _mlp_sym()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    arg, aux = mod.get_params()
    mx.model.save_checkpoint(prefix, 0, net, arg, aux)
    # rewrite the params file WITHOUT one key, refresh only the checksum so
    # the completeness check (not the checksum) must catch it
    path = prefix + "-0000.params"
    from mxnet_tpu import nd
    from mxnet_tpu.checkpoint.integrity import manifest_path_for

    full = nd.load(path)
    dropped = sorted(full)[0]
    partial = {k: v for k, v in full.items() if k != dropped}
    nd.save(path, partial)
    mpath = manifest_path_for(path)
    manifest = json.load(open(mpath))
    from mxnet_tpu.checkpoint import file_sha256

    manifest["sha256"] = file_sha256(path)
    manifest["bytes"] = os.path.getsize(path)
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(MXNetError, match=dropped.split(":", 1)[1]):
        mx.model.load_checkpoint(prefix, 0)


def test_verify_params_file_legacy_without_manifest(tmp_path):
    path = str(tmp_path / "legacy.params")
    from mxnet_tpu import nd

    nd.save(path, {"arg:w": nd.array(np.ones((2, 2), np.float32))})
    assert verify_params_file(path) is None  # no manifest: legacy OK
    with pytest.raises(MXNetError, match="does not exist"):
        verify_params_file(str(tmp_path / "missing.params"))


# -- kvstore retry / dead peer -----------------------------------------------------
def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_KV_CHILD = textwrap.dedent("""
    import os, time
    import numpy as np
    import jax; jax.config.update("jax_platforms", "cpu")
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.base import MXNetError

    mode = os.environ["KV_CASE"]
    t0 = time.time()
    try:
        kv = mx.kv.create("dist_sync")
        kv.init("a", nd.array(np.zeros((4, 2), np.float32)))
        for _ in range(10):
            kv.push("a", nd.array(np.ones((4, 2), np.float32)))
            out = nd.zeros((4, 2))
            kv.pull("a", out=out)
        if mode == "drop":
            from mxnet_tpu import observability as obs
            counters = obs.snapshot()["counters"]
            retried = sum(v for k, v in counters.items()
                          if k.startswith("kvstore_retries_total"))
            assert retried >= 1, counters
            kv.close()
            print("DROP_RECOVERED")
        else:
            print("UNEXPECTED_SUCCESS")
    except MXNetError as e:
        dt = time.time() - t0
        msg = str(e)
        assert "127.0.0.1" in msg and "presumed dead" in msg, msg
        assert dt < 60, dt
        print("DEAD_PEER_NAMED in %.1fs" % dt)
""")


def _run_kv_child(case, extra_env, timeout=180):
    env = dict(os.environ)
    env.update({"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                "MXTPU_NO_NATIVE": "1", "KV_CASE": case,
                "MXTPU_COORDINATOR": f"127.0.0.1:{_free_port()}"})
    env.update(extra_env)
    p = subprocess.Popen([sys.executable, "-c", _KV_CHILD], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        out, _ = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        out, _ = p.communicate()
        raise AssertionError("kv child timed out (unbounded wait?):\n"
                             + out.decode())
    return p.returncode, out.decode()


def test_kv_injected_drops_recover_within_retry_budget():
    rc, out = _run_kv_child("drop", {
        "TPUMX_FAULT_KV_DROP": "push:1,2",  # two consecutive drops
        "TPUMX_KV_TIMEOUT": "3", "TPUMX_KV_RETRIES": "3",
        "TPUMX_KV_BACKOFF_MS": "20"})
    assert rc == 0 and "DROP_RECOVERED" in out, out


def test_kv_dead_server_raises_peer_naming_error_in_bounded_time():
    rc, out = _run_kv_child("kill", {
        "TPUMX_FAULT_KV_KILL_SERVER": "6",  # dies mid-run
        "TPUMX_KV_TIMEOUT": "1", "TPUMX_KV_RETRIES": "2",
        "TPUMX_KV_BACKOFF_MS": "20", "TPUMX_KV_CONNECT_TIMEOUT": "1"})
    assert rc == 0 and "DEAD_PEER_NAMED" in out, out


def test_server_bind_retries_on_eaddrinuse():
    from mxnet_tpu.kvstore_dist import KVStoreDistServer

    port = _free_port()
    blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    blocker.bind(("0.0.0.0", port))
    blocker.listen(1)

    def release():
        time.sleep(0.5)
        blocker.close()

    t = threading.Thread(target=release, daemon=True)
    t.start()
    os.environ["TPUMX_KV_BIND_TIMEOUT"] = "10"
    try:
        srv = KVStoreDistServer(host="0.0.0.0", port=port, num_workers=1)
        assert srv.port == port  # bound after the blocker released
        srv._stop = True
        srv._sock.close()
    finally:
        os.environ.pop("TPUMX_KV_BIND_TIMEOUT", None)


# -- injector semantics ------------------------------------------------------------
def test_injector_occurrence_counting(monkeypatch):
    monkeypatch.setenv("TPUMX_FAULT_KV_DROP", "push:1,3")
    injector().reset()
    inj = injector()
    assert inj.kv_fault("push") is True     # 1st: drop
    assert inj.kv_fault("push") is False    # 2nd: pass
    assert inj.kv_fault("push") is True     # 3rd: drop
    assert inj.kv_fault("push") is False
    assert inj.kv_fault("pull") is False    # other ops untouched
    monkeypatch.setenv("TPUMX_FAULT_PREEMPT_AT_STEP", "5")
    injector().reset()
    assert not injector().preempt_due(4)
    assert injector().preempt_due(5)
    assert not injector().preempt_due(6)    # one-shot


def test_fast_forward_seek_matches_consumption():
    it1 = _toy_iter()
    it2 = _toy_iter()
    from mxnet_tpu.io import fast_forward

    assert fast_forward(iter(it1), 3) == 3          # seek path
    for _ in range(3):
        next(iter(it2))                             # consume path
    b1 = next(it1)
    b2 = next(it2)
    np.testing.assert_array_equal(b1.data[0].asnumpy(),
                                  b2.data[0].asnumpy())
    assert it1.tell() == 4


# -- serving graceful shutdown -----------------------------------------------------
def test_inference_service_shutdown_rejects_queued_drains_inflight():
    from mxnet_tpu.serving import InferenceService
    from mxnet_tpu.serving.batcher import ServingClosedError, ServingConfig

    started = threading.Event()

    def slow_model(x):
        started.set()
        time.sleep(0.4)
        return x

    svc = InferenceService(slow_model, config=ServingConfig(
        max_batch_size=1, batch_timeout_ms=0.1, queue_bound=64))
    futs = [svc.submit(np.zeros((4,), np.float32)) for _ in range(6)]
    assert started.wait(10)
    svc.shutdown(timeout=30)
    completed = rejected = 0
    for f in futs:
        try:
            f.result(timeout=30)
            completed += 1
        except ServingClosedError:
            rejected += 1
    assert completed >= 1          # the in-flight batch delivered
    assert rejected >= 1           # queued ones got the shutdown error
    assert completed + rejected == 6
    with pytest.raises(ServingClosedError):
        svc.submit(np.zeros((4,), np.float32))


def test_inference_service_sigterm_installs_graceful_drain():
    """Real signal delivery through the fault hub: SIGTERM → in-flight
    completes, queued rejected (the subprocess variant of this path is
    test_sigterm_mid_fit_subprocess_resume_parity's serving sibling)."""
    from mxnet_tpu.serving import InferenceService
    from mxnet_tpu.serving.batcher import ServingClosedError, ServingConfig

    started = threading.Event()

    def slow_model(x):
        started.set()
        time.sleep(0.4)
        return x

    svc = InferenceService(slow_model, config=ServingConfig(
        max_batch_size=1, batch_timeout_ms=0.1, queue_bound=64))
    assert svc.install_signal_handlers()
    try:
        futs = [svc.submit(np.zeros((4,), np.float32)) for _ in range(5)]
        assert started.wait(10)
        signal.raise_signal(signal.SIGTERM)
        outcomes = {"done": 0, "rejected": 0}
        for f in futs:
            try:
                f.result(timeout=30)
                outcomes["done"] += 1
            except ServingClosedError:
                outcomes["rejected"] += 1
        assert outcomes["done"] >= 1 and outcomes["rejected"] >= 1
    finally:
        svc.uninstall_signal_handlers()
        svc.stop(drain=False)


@pytest.mark.generation
def test_generation_service_shutdown_finishes_slots_rejects_queue():
    import jax

    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving import ServingClosedError
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    cfg = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_len=64)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    svc = GenerationService(params, cfg, GenerationConfig(
        max_slots=1, block_size=8, num_blocks=32, seq_buckets=[16],
        max_new_tokens=6, queue_bound=8), start=False)
    prompt = [1, 2, 3]
    streams = [svc.submit(prompt, max_new_tokens=6) for _ in range(3)]
    svc.start()
    # wait until the first request actually occupies a slot
    deadline = time.time() + 30
    while not any(r is not None for r in svc._slots):
        assert time.time() < deadline
        time.sleep(0.01)
    svc.shutdown(timeout=60)
    finished = rejected = 0
    for s in streams:
        try:
            toks = s.result(timeout=30)
            assert len(toks) >= 1
            finished += 1
        except ServingClosedError:
            rejected += 1
    assert finished >= 1            # in-slot generation ran to completion
    assert rejected >= 1            # waiting requests rejected
    assert finished + rejected == 3
    with pytest.raises(ServingClosedError):
        svc.submit(prompt)


# -- observability wiring ----------------------------------------------------------
def test_checkpoint_metrics_and_spans_recorded(tmp_path):
    from mxnet_tpu import observability as obs

    m = CheckpointManager(str(tmp_path), keep=2)
    m.save({"params": {"w": np.ones((16,), np.float32)}}, None,
           {"global_step": 1}, step=1, blocking=True)
    m.restore()
    snap = obs.snapshot()
    counters, hists = snap["counters"], snap["histograms"]
    assert counters.get('checkpoint_saves_total{mode="sync"}', 0) >= 1
    assert counters.get("checkpoint_save_bytes_total", 0) > 0
    assert counters.get("checkpoint_restores_total", 0) >= 1
    assert any(k.startswith("checkpoint_save_seconds") for k in hists)
    assert any(k.startswith("checkpoint_restore_seconds") for k in hists)
    assert snap["gauges"].get("checkpoint_last_step") == 1

"""Multi-replica generation routing (mxnet_tpu.serving.router,
docs/generation.md): least-loaded dispatch, health probes + circuit
breaker, dead-replica resubmission with failure isolation, drain-aware
shutdown, and the TPUMX_FAULT_GEN_KILL_REPLICA injection.
"""
import time

import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.fault.inject import injector
from mxnet_tpu.serving import (GenerationRouter, GenerationService,
                               NoHealthyReplicaError, ReplicaDeadError,
                               RouterConfig, ServingClosedError)
from oracle import CFG, greedy_oracle, params  # noqa: F401 (fixture)
from test_generation import _gc

pytestmark = pytest.mark.router


@pytest.fixture(autouse=True)
def _fresh_state():
    yield
    obs.recompile.reset()
    injector().reset()


def _router(params, n=2, rc=None, start=True, **gc_kw):
    replicas = [GenerationService(params, CFG, _gc(**gc_kw), start=False)
                for _ in range(n)]
    return GenerationRouter(replicas=replicas,
                            config=rc or RouterConfig(
                                probe_interval_ms=10.0,
                                breaker_cooldown_ms=100.0),
                            start=start)


def test_least_loaded_dispatch_spreads_and_tokens_match_oracle(params):
    router = _router(params, n=2)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (5, 11, 17, 7, 13, 9)]
    hs = [router.submit(p, max_new_tokens=4) for p in prompts]
    outs = [h.result(120) for h in hs]
    st = router.stats()
    router.stop()
    for p, got in zip(prompts, outs):
        assert got == greedy_oracle(params, p, 4)
    per_replica = [r["dispatches"] for r in st["replicas"]]
    assert sum(per_replica) == len(prompts)
    assert all(d > 0 for d in per_replica), \
        f"least-loaded dispatch should spread, got {per_replica}"
    assert st["healthy"] == 2


def test_replica_kill_injection_resubmits_queued_work(params, monkeypatch):
    """Acceptance: TPUMX_FAULT_GEN_KILL_REPLICA kills a replica holding
    queued work; the probe detects it, opens its breaker, resubmits the
    never-streamed requests to the healthy replica — which all complete
    with no client-visible error — and fails the mid-stream request with
    a typed ReplicaDeadError."""
    monkeypatch.setenv("TPUMX_FAULT_GEN_KILL_REPLICA", "0@2")
    injector().reset()
    router = _router(params, n=2, max_slots=1)
    rs = np.random.RandomState(2)
    # 1st dispatch lands on replica 0 (both idle) and starts streaming;
    # the request after replica 0's 2nd dispatch is queued there when the
    # injection kills it
    h_streaming = router.submit(rs.randint(0, CFG.vocab, 8),
                                max_new_tokens=200 // 4)
    deadline = time.perf_counter() + 60
    while not h_streaming.started and time.perf_counter() < deadline:
        time.sleep(0.01)   # wait out the first prefill compile
    assert h_streaming.started
    handles = [router.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=4)
               for _ in range(4)]
    outs = [h.result(120) for h in handles]    # no client-visible errors
    assert all(len(o) == 4 for o in outs)
    # the dead replica is circuit-broken and flagged
    deadline = time.perf_counter() + 10
    while time.perf_counter() < deadline:
        st = router.stats()
        rep0 = st["replicas"][0]
        if rep0["dead"] and rep0["breaker"] == "open":
            break
        time.sleep(0.02)
    assert rep0["dead"] and rep0["breaker"] == "open"
    assert not rep0["health"]["alive"]
    # at least one request moved replicas
    assert sum(h.resubmits for h in handles) >= 1
    with pytest.raises(ReplicaDeadError):
        h_streaming.result(30)
    # the survivor keeps serving
    out = router.generate(rs.randint(0, CFG.vocab, 5), max_new_tokens=3,
                          timeout=60)
    assert len(out) == 3
    router.stop()


def test_breaker_reopens_after_recovery(params, monkeypatch):
    """A replica that goes unhealthy is ejected (no new dispatches) and
    probed back in through half-open once it recovers."""
    router = _router(params, n=2,
                     rc=RouterConfig(probe_interval_ms=10.0,
                                     breaker_failures=2,
                                     breaker_cooldown_ms=50.0))
    rep0 = router._replicas[0]
    orig_health = rep0.service.health
    sick = {"on": True}

    def flaky_health():
        h = orig_health()
        if sick["on"]:
            h["alive"] = False
        return h

    monkeypatch.setattr(rep0.service, "health", flaky_health)
    deadline = time.perf_counter() + 10
    while rep0.breaker == "closed" and time.perf_counter() < deadline:
        time.sleep(0.02)
    assert rep0.breaker in ("open", "half_open")
    # while broken, dispatches avoid replica 0
    hs = [router.submit(np.arange(5), max_new_tokens=2) for _ in range(3)]
    [h.result(60) for h in hs]
    assert rep0.dispatches == 0
    sick["on"] = False
    deadline = time.perf_counter() + 10
    while rep0.breaker != "closed" and time.perf_counter() < deadline:
        time.sleep(0.02)
    assert rep0.breaker == "closed"
    h = router.submit(np.arange(5), max_new_tokens=2)
    assert len(h.result(60)) == 2
    router.stop()


def test_all_replicas_broken_raises_typed(params):
    router = _router(params, n=2)
    for rep in router._replicas:
        rep.service.kill()
    deadline = time.perf_counter() + 10
    while router.stats()["healthy"] > 0 and time.perf_counter() < deadline:
        time.sleep(0.02)
    with pytest.raises(NoHealthyReplicaError):
        router.submit(np.arange(4), max_new_tokens=2)
    router.stop(drain=False)


def test_router_drain_shutdown_rejects_queued_typed(params, monkeypatch):
    """shutdown(): running slots finish, queued requests get a typed
    ServingClosedError — the PR 10 drain semantics, fleet-wide."""
    router = _router(params, n=2, max_slots=1)
    for rep in router._replicas:
        orig = rep.service._programs.run

        def slow(kind, *a, _orig=orig, **kw):
            if kind == "gen_decode":
                time.sleep(0.01)   # pin the slot: queued stays queued
            return _orig(kind, *a, **kw)

        monkeypatch.setattr(rep.service._programs, "run", slow)
    rs = np.random.RandomState(3)
    running = [router.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=20)
               for _ in range(2)]
    deadline = time.perf_counter() + 60
    while not all(h.started for h in running) and \
            time.perf_counter() < deadline:
        time.sleep(0.01)     # wait out first-prefill compiles
    queued = [router.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=20)
              for _ in range(3)]
    router.shutdown(timeout=120)
    for h in running:
        assert len(h.result(5)) == 20
    rejected = 0
    for h in queued:
        try:
            h.result(5)
        except ServingClosedError:
            rejected += 1
    assert rejected == len(queued)


def test_router_signal_handler_installs_on_main_thread(params):
    router = _router(params, n=1, start=False)
    assert router.install_signal_handlers() is True
    router.uninstall_signal_handlers()
    router.stop(drain=False)


@pytest.mark.slow
def test_router_soak_kill_midflight_no_lost_streams(params):
    """Multi-replica soak: 3 replicas, sustained load, one replica killed
    mid-flight — every stream resolves (tokens or a typed error), none
    hang."""
    router = _router(params, n=3, max_slots=2)
    rs = np.random.RandomState(4)
    handles = []
    for i in range(30):
        handles.append(router.submit(
            rs.randint(0, CFG.vocab, int(rs.choice([5, 11, 17]))),
            max_new_tokens=int(rs.choice([4, 8]))))
        if i == 10:
            router._replicas[1].service.kill()
        time.sleep(0.01)
    resolved = failed = 0
    for h in handles:
        try:
            out = h.result(180)
            assert len(out) >= 1
            resolved += 1
        except (ReplicaDeadError, ServingClosedError):
            failed += 1
    router.stop()
    assert resolved + failed == len(handles)
    assert resolved >= len(handles) - 4   # only mid-stream casualties fail

"""End-to-end request tracing, latency attribution, and the crash flight
recorder (docs/observability.md): trace-context propagation across the
router -> replica -> engine thread hops, wide-event TTFT breakdowns that
sum to measured wall time, GenerationStream.stats(), the TPUMX_TRACING=0
byte-identity gate, flight-recorder dumps on quarantine/SIGTERM/breaker
open, and collector-failure isolation in the metrics registry.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu import profiler
from mxnet_tpu.fault.inject import injector
from mxnet_tpu.observability import flight_recorder as flight
from mxnet_tpu.observability import tracing
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving import (GenerationConfig, GenerationRouter,
                               GenerationService, GenerationStepError,
                               RouterConfig)

pytestmark = pytest.mark.tracing

CFG = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64)


@pytest.fixture(autouse=True)
def _fresh_state():
    tracing.clear()
    flight.clear()
    yield
    obs.recompile.reset()
    injector().reset()
    tracing.clear()
    flight.clear()


@pytest.fixture(scope="module")
def params():
    return tr.transformer_lm_init(CFG, jax.random.PRNGKey(0))


def _gc(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("seq_buckets", [16, 32])
    kw.setdefault("max_new_tokens", 8)
    return GenerationConfig(**kw)


def _names(spans):
    return [s["name"] for s in spans]


# -- the acceptance trace: one trace id across every hop ----------------------------
def test_one_trace_id_across_dispatch_queue_rungs_decode_preempt_reply(
        params, tmp_path, monkeypatch):
    """Acceptance: a single request's spans carry ONE trace id across
    router dispatch, replica queue, every prefill rung, >= 2 decode-step
    participations, a forced preemption + re-prefill, and the reply —
    asserted via the trace buffer, and mirrored into the chrome-trace
    stream when the profiler runs."""
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER", "0")  # no breaker dumps here
    # a pool too small for both worst cases forces preemption + re-prefill
    svc = GenerationService(params, CFG,
                            _gc(num_blocks=8, preemption=True), start=False)
    router = GenerationRouter(
        replicas=[svc], config=RouterConfig(num_replicas=1,
                                            probe_interval_ms=10.0),
        start=False)
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.start()
    try:
        rs = np.random.RandomState(1)
        hs = [router.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12)
              for _ in range(2)]
        router.start()
        outs = [h.result(120) for h in hs]
    finally:
        profiler.stop()
    stats = svc.stats()
    assert all(len(o) == 12 for o in outs)
    assert stats["counts"]["preempted"] >= 1

    # the preempted-and-resumed request is the interesting trace
    preempted = [h for h in hs if h.stats()["preemptions"] >= 1]
    assert preempted, "tight pool must have preempted one request"
    h = preempted[0]
    tid = h.trace_id
    assert tid is not None and all(x.trace_id for x in hs)
    assert len({x.trace_id for x in hs}) == 2  # one trace PER request

    spans = obs.recent_spans(trace_id=tid)
    names = _names(spans)
    assert "router.dispatch" in names             # client thread
    assert "gen.queue" in names                   # engine thread: the hop
    assert "gen.admit" in names
    prefills = [s for s in spans if s["name"] == "serving.prefill"]
    assert prefills, "prefill rungs must land in the trace"
    # forced preemption + re-prefill: a preempt span and a resumed rung
    assert "serving.preempt" in names
    assert any(s["args"].get("resumed") for s in prefills), \
        "the re-prefill (resumed) rung must ride the same trace"
    participations = [s for s in spans
                      if s["name"] == "serving.decode.participate"]
    assert len(participations) >= 2
    assert names[-1] == "gen.reply" or "gen.reply" in names
    # every span of the trace shares the one id and names this replica
    assert {s["trace_id"] for s in spans} == {tid}
    # spans crossed threads: dispatch ran on the client thread, the rest
    # on the engine thread
    assert len({s["thread"] for s in spans}) >= 2

    # chrome-trace export: the same ids ride the profiler event stream,
    # so one perfetto timeline shows the request end to end
    events = json.loads(profiler.dumps(format="json"))["traceEvents"]
    traced = [e for e in events
              if e.get("args", {}).get("trace_id") == tid]
    assert {"router.dispatch", "serving.prefill",
            "serving.decode.participate"} <= {e["name"] for e in traced}
    router.stop()


def test_trace_id_survives_replica_failover(params, monkeypatch):
    """The resubmitted request continues the dead replica's trace: one
    trace id across BOTH replicas' spans, with a router.resubmit span
    marking the hop."""
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER", "0")
    monkeypatch.setenv("TPUMX_FAULT_GEN_KILL_REPLICA", "0@1")
    injector().reset()
    replicas = [GenerationService(params, CFG, _gc(max_slots=1),
                                  start=False) for _ in range(2)]
    router = GenerationRouter(replicas=replicas,
                              config=RouterConfig(probe_interval_ms=10.0,
                                                  breaker_cooldown_ms=100.0))
    rs = np.random.RandomState(2)
    # replica 0 is killed right after accepting this dispatch; the router
    # must resubmit it to replica 1 under the SAME trace
    h = router.submit(rs.randint(0, CFG.vocab, 8), max_new_tokens=4)
    out = h.result(120)
    assert len(out) == 4
    assert h.resubmits >= 1
    tid = h.trace_id
    spans = obs.recent_spans(trace_id=tid)
    names = _names(spans)
    assert "router.dispatch" in names
    assert "router.resubmit" in names
    replicas_seen = {s["args"].get("replica") for s in spans
                     if s["name"] == "serving.decode.participate"}
    assert replicas_seen == {1}, "the reply decoded on the survivor"
    ev = h.stats()
    assert ev["trace_id"] == tid and ev["replica"] == 1
    router.stop()


# -- wide events + latency attribution ----------------------------------------------
def test_ttft_breakdown_sums_to_ttft_and_total(params):
    """Acceptance: queue + admission + prefill + decode + preempted
    components sum to measured TTFT (snapshotted at first token) and the
    full breakdown to total wall time — exact partitions, tolerance is
    float rounding only."""
    svc = GenerationService(params, CFG, _gc(num_blocks=8, preemption=True),
                            start=False)
    rs = np.random.RandomState(1)
    hs = [svc.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12)
          for _ in range(2)]
    svc.start()
    for h in hs:
        h.result(120)
    evs = [h.stats() for h in hs]
    assert svc.stats()["counts"]["preempted"] >= 1
    svc.stop()
    for ev in evs:
        assert ev["outcome"] == "finished"
        comp = set(ev["ttft_breakdown_ms"]) | set(ev["breakdown_ms"])
        # prefix_reuse: the cache-bookkeeping slice a prefix-cache hit
        # inserts between admission and prefill (docs/generation.md
        # "prefix caching") — the partition stays exact with it present
        assert comp <= {"queue", "admission", "prefill", "decode",
                        "preempted", "prefix_reuse"}
        assert sum(ev["ttft_breakdown_ms"].values()) == \
            pytest.approx(ev["ttft_ms"], abs=0.05)
        assert sum(ev["breakdown_ms"].values()) == \
            pytest.approx(ev["total_ms"], abs=0.05)
        assert ev["prefill_rungs_ms"], "per-rung prefill attribution"
        assert ev["decode_steps"] >= 2
        assert len(ev["token_offsets_ms"]) == ev["output_tokens"] == 12
    preempted = [ev for ev in evs if ev["preemptions"] >= 1]
    assert preempted and preempted[0]["breakdown_ms"].get("preempted", 0) > 0


@pytest.mark.speculative
def test_ttft_breakdown_partition_with_speculation(params):
    """Speculative decoding adds NO lifetime segments (verify steps run
    inside "decode"), so the exact TTFT/total partition survives with the
    gate on — and the wide event carries the new decode_mode /
    accepted_ratio / draft-token fields."""
    svc = GenerationService(params, CFG, _gc(speculative=True),
                            start=False)
    # repetitive prompts: the n-gram drafter fires and drafts get accepted
    hs = [svc.submit([1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3, 4],
                     max_new_tokens=10),
          svc.submit([7, 8, 9, 7, 8, 9, 7, 8, 9], max_new_tokens=10)]
    svc.start()
    for h in hs:
        h.result(120)
    st = svc.stats()
    svc.stop()
    assert st["counts"]["spec_steps"] >= 1
    assert st["speculative"]["proposed_tokens"] >= 1
    for h in hs:
        ev = h.stats()
        assert ev["outcome"] == "finished"
        comp = set(ev["ttft_breakdown_ms"]) | set(ev["breakdown_ms"])
        assert comp <= {"queue", "admission", "prefill", "decode",
                        "preempted", "prefix_reuse"}
        assert sum(ev["ttft_breakdown_ms"].values()) == \
            pytest.approx(ev["ttft_ms"], abs=0.05)
        assert sum(ev["breakdown_ms"].values()) == \
            pytest.approx(ev["total_ms"], abs=0.05)
        assert ev["decode_mode"] in ("single", "spec")
        assert ev["draft_proposed_tokens"] >= 0
        assert ev["draft_accepted_tokens"] <= ev["draft_proposed_tokens"]
        if ev["draft_proposed_tokens"]:
            assert ev["accepted_ratio"] == pytest.approx(
                ev["draft_accepted_tokens"] / ev["draft_proposed_tokens"],
                abs=1e-3)
        else:
            assert ev["accepted_ratio"] is None
    assert any(ev["decode_mode"] == "spec" for ev in map(
        lambda h: h.stats(), hs))


def test_retried_then_quarantined_wide_event(params, tmp_path, monkeypatch):
    """A persistently poisoned request is retried, bisected, quarantined —
    its wide event records the retries and a breakdown that still sums to
    its total wall time, and the flight recorder dumps a valid JSON file
    containing that wide event."""
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER_DIR", str(tmp_path))
    monkeypatch.setenv("TPUMX_FAULT_GEN_STEP_FAIL", "3@1")
    injector().reset()
    svc = GenerationService(params, CFG, _gc(), start=False)
    rs = np.random.RandomState(3)
    h0 = svc.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=8, seed=1)
    h1 = svc.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=8, seed=2)
    svc.start()
    assert len(h0.result(120)) == 8          # the healthy neighbour finishes
    with pytest.raises(GenerationStepError):
        h1.result(120)
    ev = h1.stats()
    svc.stop(drain=False)
    assert ev["outcome"] == "failed"
    assert ev["retries"] >= 1
    assert "quarantined" in (ev["error"] or "")
    assert sum(ev["breakdown_ms"].values()) == \
        pytest.approx(ev["total_ms"], abs=0.05)
    # the quarantine dump: valid JSON, tagged with the reason, carrying
    # the failing request's wide event.  The dump is written by the
    # engine thread AFTER the client's result() unblocks — poll for it.
    deadline = time.perf_counter() + 10
    while flight.last_dump() is None and time.perf_counter() < deadline:
        time.sleep(0.02)
    path = flight.last_dump()
    assert path is not None and os.path.exists(path)
    with open(path) as f:
        dump = json.load(f)
    assert dump["reason"] == "gen_quarantine"
    assert dump["extra"]["rid"] == ev["request_id"]
    assert dump["extra"]["request"]["outcome"] == "failed"
    assert any(e.get("request_id") == ev["request_id"]
               for e in dump["wide_events"])
    assert dump["metrics"]["counters"].get(
        "generation_quarantines_total", 0) >= 1


def test_wide_event_ring_and_jsonl_sink(params, tmp_path, monkeypatch):
    """Every request terminates in one wide event: the in-memory ring
    (observability.recent_requests) and the TPUMX_TRACE_LOG JSONL sink
    agree."""
    log = tmp_path / "trace.jsonl"
    monkeypatch.setenv("TPUMX_TRACE_LOG", str(log))
    tracing.clear()
    svc = GenerationService(params, CFG, _gc(), start=False)
    rs = np.random.RandomState(4)
    hs = [svc.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=3)
          for _ in range(3)]
    svc.start()
    for h in hs:
        h.result(120)
    svc.stop()
    ring = [e for e in obs.recent_requests()
            if e["type"] == "generation_request"]
    assert len(ring) == 3
    lines = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert [e["request_id"] for e in lines] == \
        [e["request_id"] for e in ring]
    for ev in ring:
        assert ev["outcome"] == "finished" and ev["output_tokens"] == 3


def test_fit_batches_and_checkpoint_saves_share_one_trace(tmp_path):
    """Module.fit runs under one trace: fit.epoch/fit.batch/
    executor.fused_step/kvstore.push spans — and the async checkpoint
    writer on ITS thread — all carry the fit's trace id."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(data, num_hidden=8, name="fc"), label,
        name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.rand(16, 4).astype(np.float32),
                           rs.randint(0, 8, 16).astype(np.float32),
                           batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu())
    tracing.clear()
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1)
    spans = obs.recent_spans()
    fit_spans = [s for s in spans if s["name"].startswith("fit.")]
    assert fit_spans, "fit spans must land in the trace ring"
    tid = fit_spans[0]["trace_id"]
    assert tid is not None
    by_name = {}
    for s in spans:
        if s["trace_id"] == tid:
            by_name.setdefault(s["name"], []).append(s)
    assert "fit.batch" in by_name
    assert "executor.fused_step" in by_name or "kvstore.push" in by_name
    saves = [n for n in by_name
             if n in ("checkpoint.save_async", "checkpoint.save_sync")]
    assert saves, "checkpoint saves must join the fit trace across the " \
                  "writer-thread boundary"


# -- the TPUMX_TRACING=0 gate --------------------------------------------------------
def test_tracing_off_is_byte_identical_and_dark(params, monkeypatch):
    """TPUMX_TRACING=0: no contexts, no rings, no sink — and the engine's
    tokens and compiled program signatures are bitwise identical to the
    traced run."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (5, 11, 20)]

    def run():
        svc = GenerationService(params, CFG, _gc(num_blocks=8), start=False)
        hs = [svc.submit(p, max_new_tokens=10) for p in prompts]
        svc.start()
        outs = [h.result(120) for h in hs]
        keys = set(svc.compile_stats().keys())
        stats = [h.stats() for h in hs]
        svc.stop()
        return outs, keys, stats

    tracing.clear()
    outs_on, keys_on, _ = run()
    assert tracing.recent_spans() and tracing.recent_requests()

    tracing.clear()
    monkeypatch.setenv("TPUMX_TRACING", "0")
    assert not tracing.enabled()
    outs_off, keys_off, stats_off = run()
    assert outs_off == outs_on                      # bitwise tokens
    assert keys_off == keys_on                      # same program keys
    assert tracing.recent_spans() == []             # dark
    assert tracing.recent_requests() == []
    assert tracing.new_trace() is None
    # stream stats still work off the request's own bookkeeping
    for s in stats_off:
        assert s["trace_id"] is None
        assert s["outcome"] == "finished"
        assert sum(s["breakdown_ms"].values()) == \
            pytest.approx(s["total_ms"], abs=0.05)


# -- flight recorder ----------------------------------------------------------------
def test_flight_recorder_dump_on_real_sigterm_subprocess(tmp_path):
    """Acceptance: a real SIGTERM (through the PR 10 signal hub) dumps the
    black box before the process exits — subprocess test."""
    code = r"""
import json, os, signal, sys
import numpy as np, jax
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving import GenerationConfig, GenerationService

cfg = tr.TransformerConfig(vocab=40, d_model=16, n_heads=2, n_layers=1,
                           d_ff=32, max_len=32)
params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
svc = GenerationService(params, cfg,
                        GenerationConfig(max_slots=1, block_size=8,
                                         num_blocks=16, seq_buckets=[16],
                                         max_new_tokens=2), start=False)
assert svc.install_signal_handlers()
h = svc.submit(np.arange(4), max_new_tokens=2)
svc.start()
h.result(120)                      # one finished request -> one wide event
os.kill(os.getpid(), signal.SIGTERM)
print("SURVIVED_DRAIN")            # graceful drain: process lives to report
"""
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "TPUMX_FLIGHT_RECORDER_DIR": str(tmp_path)})
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert "SURVIVED_DRAIN" in proc.stdout, proc.stderr[-2000:]
    dumps = [p for p in os.listdir(tmp_path) if p.endswith(".json")]
    assert dumps, "SIGTERM must have written a flight dump"
    with open(os.path.join(str(tmp_path), sorted(dumps)[0])) as f:
        dump = json.load(f)
    assert dump["reason"].startswith("signal_")
    assert any(e.get("type") == "generation_request"
               for e in dump["wide_events"])
    assert any(n["kind"] == "signal" for n in dump["notes"])


def test_flight_recorder_dump_on_breaker_open(params, tmp_path, monkeypatch):
    """A replica going dark under traffic opens its breaker AND dumps the
    black box."""
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER_DIR", str(tmp_path))
    replicas = [GenerationService(params, CFG, _gc(), start=False)
                for _ in range(2)]
    router = GenerationRouter(replicas=replicas,
                              config=RouterConfig(probe_interval_ms=10.0,
                                                  breaker_cooldown_ms=10_000.0))
    replicas[0].kill()
    deadline = time.perf_counter() + 10
    while flight.last_dump() is None and time.perf_counter() < deadline:
        time.sleep(0.02)
    path = flight.last_dump()
    assert path is not None and str(tmp_path) in path
    with open(path) as f:
        dump = json.load(f)
    assert dump["reason"] == "breaker_open"
    assert dump["extra"]["replica"] == 0
    assert any(n["kind"] == "breaker" for n in dump["notes"])
    router.stop(drain=False)


def test_flight_recorder_disabled_gate(params, tmp_path, monkeypatch):
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER", "0")
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER_DIR", str(tmp_path))
    assert flight.dump("unit") is None
    assert os.listdir(tmp_path) == []


def test_flight_recorder_dump_never_raises(tmp_path, monkeypatch):
    """dump() sits on failover paths (breaker-open, quarantine): any
    failure while BUILDING the payload — not just the file write — must
    come back as None, never as an exception."""
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER_DIR", str(tmp_path))

    def _boom(*a, **kw):
        raise RuntimeError("deque mutated during iteration")

    monkeypatch.setattr(tracing, "recent_spans", _boom)
    assert flight.dump("unit") is None
    assert os.listdir(tmp_path) == []


def test_flight_recorder_install_refcounted():
    """Two owners (router + standalone service) install the crash hooks;
    the first uninstall must NOT disarm the black box for the second."""
    orig_hook = sys.excepthook
    flight.install()
    flight.install()
    try:
        assert sys.excepthook is not orig_hook
        flight.uninstall()                       # first owner tears down
        assert sys.excepthook is not orig_hook   # still armed
    finally:
        flight.uninstall()                       # last owner tears down
    assert sys.excepthook is orig_hook
    flight.uninstall()                           # extra uninstall: harmless
    assert sys.excepthook is orig_hook


def test_breaker_dump_failure_never_blocks_failover(params, tmp_path,
                                                    monkeypatch):
    """Regression: a flight-recorder dump blowing up mid-capture while a
    breaker opens must not swallow dead-replica handling — the dead
    replica's queued work still moves to the healthy replica."""
    monkeypatch.setenv("TPUMX_FLIGHT_RECORDER_DIR", str(tmp_path))
    # kill replica 0 right after its 2nd accepted dispatch, leaving that
    # request queued on a corpse (same choreography as test_router.py)
    monkeypatch.setenv("TPUMX_FAULT_GEN_KILL_REPLICA", "0@2")
    injector().reset()

    def _boom(*a, **kw):
        raise RuntimeError("deque mutated during iteration")

    monkeypatch.setattr(tracing, "recent_spans", _boom)
    replicas = [GenerationService(params, CFG, _gc(max_slots=1),
                                  start=False) for _ in range(2)]
    router = GenerationRouter(
        replicas=replicas,
        config=RouterConfig(probe_interval_ms=10.0,
                            breaker_cooldown_ms=10_000.0))
    rs = np.random.RandomState(3)
    h0 = router.submit(rs.randint(0, CFG.vocab, 8), max_new_tokens=50)
    deadline = time.perf_counter() + 60
    while not h0.started and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert h0.started
    handles = [router.submit(rs.randint(0, CFG.vocab, 6), max_new_tokens=4)
               for _ in range(4)]
    outs = [h.result(120) for h in handles]   # no client-visible errors
    assert all(len(o) == 4 for o in outs)
    assert sum(h.resubmits for h in handles) >= 1
    assert flight.last_dump() is None         # the dump itself failed...
    assert os.listdir(tmp_path) == []         # ...and wrote nothing
    router.stop(drain=False)


def test_span_ring_snapshot_safe_under_concurrent_append():
    """recent_spans()/recent_requests() vs concurrent appenders: a
    snapshot racing an engine-thread append must never raise ('deque
    mutated during iteration')."""
    errs = []
    stop = threading.Event()

    def _reader():
        try:
            while not stop.is_set():
                tracing.recent_spans()
                tracing.recent_requests()
        except Exception as exc:  # noqa: BLE001 — the assertion payload
            errs.append(exc)

    t = threading.Thread(target=_reader)
    t.start()
    try:
        for i in range(20_000):
            tracing.record_event("hammer", "test", 0.0, 1.0)
            if i % 4 == 0:
                tracing.record_wide_event({"type": "hammer", "i": i})
    finally:
        stop.set()
        t.join()
    assert not errs


# -- satellite: collector-failure isolation ------------------------------------------
def test_poisoned_collector_is_isolated_and_counted():
    """One raising pull collector must not break snapshot()/scrape: the
    rest keep serving and the failure is counted per collector."""
    reg = obs.metrics.MetricsRegistry()
    reg.gauge("healthy_gauge").set(7.0)
    calls = {"good": 0}

    def poisoned():
        raise RuntimeError("collector went bad")

    def good():
        calls["good"] += 1
        reg.gauge("pull_gauge").set(1.0)

    reg.add_collector(poisoned)
    reg.add_collector(good)
    snap = reg.snapshot()
    assert snap["gauges"]["healthy_gauge"] == 7.0
    assert snap["gauges"]["pull_gauge"] == 1.0 and calls["good"] == 1
    errs = [(k, v) for k, v in snap["counters"].items()
            if k.startswith("observability_collector_errors_total")]
    assert errs and errs[0][1] == 1.0 and "poisoned" in errs[0][0]
    # exposition also survives and counts again
    text = reg.to_prometheus()
    assert "healthy_gauge 7" in text
    assert "observability_collector_errors_total" in text
    snap2 = reg.snapshot()
    errs2 = [v for k, v in snap2["counters"].items()
             if k.startswith("observability_collector_errors_total")]
    assert errs2[0] == 3.0  # one per snapshot/scrape since registration


# -- satellite: concurrent Prometheus scrape under decode ---------------------------
def test_concurrent_scrape_while_engine_decodes(params):
    """Hammer the exposition endpoint from N threads while the engine
    decodes: no exceptions, no torn exposition output, bounded scrape
    latency."""
    svc = GenerationService(params, CFG, _gc(max_new_tokens=16), start=False)
    rs = np.random.RandomState(6)
    hs = [svc.submit(rs.randint(0, CFG.vocab, 8), max_new_tokens=16)
          for _ in range(4)]
    srv = obs.exposition.start_http_server(port=0)
    errors, latencies = [], []

    def scraper(tid):
        try:
            for _ in range(20):
                t0 = time.perf_counter()
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/metrics",
                        timeout=30) as resp:
                    body = resp.read().decode()
                latencies.append(time.perf_counter() - t0)
                assert resp.status == 200
                # torn output would break the line discipline: every line
                # is a comment or a "name{labels} value" sample, and the
                # body terminates cleanly
                assert body.endswith("\n")
                for line in body.splitlines():
                    assert line.startswith("#") or \
                        len(line.rsplit(" ", 1)) == 2, f"torn line: {line!r}"
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(repr(e))

    svc.start()
    threads = [threading.Thread(target=scraper, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    for h in hs:
        h.result(120)
    srv.close()
    svc.stop()
    assert not errors, errors[:3]
    assert len(latencies) == 8 * 20
    lat = sorted(latencies)
    assert lat[int(len(lat) * 0.99)] < 5.0, "scrape latency unbounded"


# -- InferenceService micro-batch attribution ---------------------------------------
def test_inference_service_batch_execute_attributed_per_request():
    """The micro-batcher's shared execute fans out one participation span
    per rider's trace, across the queue/worker-thread boundary."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving, sym

    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=4, name="fc")
    mod = mx.mod.Module(net, data_names=("data",), label_names=None)
    mod.bind(data_shapes=[("data", (4, 8))], for_training=False)
    mod.init_params(mx.init.Uniform(0.05))
    svc = serving.InferenceService(
        mod, serving.ServingConfig(max_batch_size=4, batch_timeout_ms=20.0,
                                   shape_buckets=[(8,)]))
    svc.warmup([(8,)])
    tracing.clear()
    rs = np.random.RandomState(7)
    futs = [svc.submit(rs.rand(8).astype(np.float32)) for _ in range(4)]
    for f in futs:
        f.result(60)
    parts = obs.recent_spans(name="serving.execute.participate")
    svc.stop()
    assert len(parts) == 4
    assert len({p["trace_id"] for p in parts}) == 4  # one trace per request
    enq = obs.recent_spans(name="serving.enqueue")
    assert {p["trace_id"] for p in parts} == {e["trace_id"] for e in enq}, \
        "participations continue the traces minted at enqueue"


# -- stream stats live view ----------------------------------------------------------
def test_stream_stats_live_then_final(params, monkeypatch):
    """GenerationStream.stats() serves a live snapshot mid-flight and the
    wide event once finished — callers no longer wall-clock their own
    TTFT."""
    svc = GenerationService(params, CFG, _gc(), start=False)
    h = svc.submit(np.arange(6), max_new_tokens=4)
    live = h.stats()
    assert live["outcome"] == "waiting" and live["ttft_ms"] is None
    assert live["breakdown_ms"].get("queue", 0) >= 0
    svc.start()
    out = h.result(120)
    final = h.stats()
    svc.stop()
    assert len(out) == 4
    assert final["outcome"] == "finished"
    assert final["ttft_ms"] is not None and final["ttft_ms"] > 0
    assert final["ttft_ms"] == pytest.approx(h.ttft_ms, abs=0.01)
    assert len(final["token_offsets_ms"]) == 4
    assert final["token_offsets_ms"] == sorted(final["token_offsets_ms"])
    assert final["requeues"] == 0 and final["retries"] == 0


def test_stream_stats_live_snapshot_consistent_under_load(params):
    """Hammer stats() from a foreign thread while the engine decodes: the
    live snapshot must never raise or show a torn breakdown (a negative
    segment means seg_state/seg_t0 were read across a transition), and it
    reports the real replica id instead of None."""
    svc = GenerationService(params, CFG, _gc(), start=False)
    h = svc.submit(np.arange(6), max_new_tokens=32)
    assert h.stats()["replica"] == 0
    errs = []
    stop = threading.Event()

    def _poll():
        try:
            while not stop.is_set():
                s = h.stats()
                assert all(v >= 0 for v in s["breakdown_ms"].values()), s
        except Exception as exc:  # noqa: BLE001 — the assertion payload
            errs.append(exc)

    t = threading.Thread(target=_poll)
    t.start()
    try:
        svc.start()
        out = h.result(120)
    finally:
        stop.set()
        t.join()
        svc.stop()
    assert not errs
    assert len(out) == 32
    assert h.stats()["replica"] == 0  # the final wide event agrees


# -- phase spans of the two hot loops (docs/observability.md section 2) -------------
def _tiny_fit(n_batches=3, callback=None):
    """Module.fit on a one-layer symbol; returns the per-step losses the
    callback read (the metric read is the step's sync, as in a benchmark
    cell)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=8, name="fc"),
        sym.Variable("softmax_label"), name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.rand(8 * n_batches, 4).astype(np.float32),
                           rs.randint(0, 8, 8 * n_batches).astype(np.float32),
                           batch_size=8)
    losses = []

    def on_batch(param):
        losses.append(float(param.eval_metric.get()[1]))
        if callback is not None:
            callback(param)

    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="ce",
            optimizer_params={"learning_rate": 0.1},
            batch_end_callback=on_batch)
    assert mod._fused_step_count == n_batches
    return losses


def _profiled(fn):
    """Run ``fn`` under mx.profiler; the spans it emitted as chrome-trace
    events (``args['parent']`` names the enclosing span of the thread)."""
    profiler.dumps(reset=True, format="json")
    profiler.set_state("run")
    try:
        out = fn()
    finally:
        profiler.set_state("stop")
    events = json.loads(profiler.dumps(reset=True, format="json"))
    spans = [e for e in events["traceEvents"] if e["ph"] == "X"]
    for e in spans:
        e.setdefault("args", {})
    return out, spans


def test_fit_loop_emits_phase_spans_with_parents():
    n = 3
    _, events = _profiled(lambda: _tiny_fit(n))
    parents = {}
    for e in events:
        parents.setdefault(e["name"], []).append(e["args"].get("parent"))
    want = {"fit.input_wait": "fit.epoch[0]", "fit.prepare": "fit.epoch[0]",
            "fit.callbacks": "fit.epoch[0]", "fit.batch": "fit.epoch[0]",
            "fit.update_metric": "fit.batch", "executor.feed": "fit.batch",
            "executor.fused_step": "fit.batch"}
    for name, parent in want.items():
        assert set(parents[name]) == {parent}, (name, parents[name])
    # once per step; the iterator is asked once more, for the batch that
    # is not there, and nothing is prepared after that
    per_step = {k: len(v) for k, v in parents.items() if k in want}
    assert per_step == dict({k: n for k in want}, **{
        "fit.input_wait": n + 1, "fit.prepare": n - 1})
    # feed ends where the dispatch starts: no host work between them is
    # left without a name
    by = {k: sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                    if e["name"] == k)
          for k in ("executor.feed", "executor.fused_step", "fit.batch")}
    for feed, step, batch in zip(*by.values()):
        assert batch[0] <= feed[0] <= feed[1] <= step[0] <= step[1] \
            <= batch[1]


def _serve(params, prompts, **kw):
    svc = GenerationService(params, CFG, _gc(**kw), start=False)
    svc.warmup()
    svc.start()
    try:
        outs = [h.result(120) for h in
                [svc.submit(p, max_new_tokens=10) for p in prompts]]
        time.sleep(0.12)           # the loop retires the last slot and idles
        return outs, svc.stats()
    finally:
        svc.stop()


def test_engine_iteration_spans_children_and_phase_ms(params):
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, CFG.vocab, n)
               for n in (5, 11, 20, 7, 13, 9, 17, 6)]
    # a pool this small crosses its high watermark as the rows grow: the
    # prefix index then evicts under a span of its own
    (_, stats), events = _profiled(lambda: _serve(
        params, prompts, preemption=True, num_blocks=10))
    its = [e for e in events if e["name"] == "serving.iteration"]
    # the engine thread's spans (warm-up ran the programs on this one)
    mine = [e for e in events if e["name"].startswith("serving.")
            and e["tid"] == its[0]["tid"]]
    # one span per engine iteration: a pass that only retires finished
    # requests repeats the number of the iteration that follows it
    numbers = {e["args"]["iteration"] for e in its}
    assert abs(len(numbers) - stats["iterations"]) <= 1
    assert all(e["args"].get("parent") is None for e in its)
    parent_of = {}
    for e in mine:
        parent_of.setdefault(e["name"], set()).add(e["args"].get("parent"))
    assert parent_of["serving.idle_wait"] == {None}
    for child in ("serving.schedule", "serving.decode.build",
                  "serving.prefill", "serving.decode", "serving.emit"):
        assert parent_of[child] == {"serving.iteration"}, child
    for child in ("serving.evict", "serving.watermark", "serving.admit"):
        assert parent_of[child] == {"serving.schedule"}, child
    crossings = [e["args"] for e in mine if e["name"] == "serving.watermark"]
    assert all(c["asked"] == c["freed"] > 0 for c in crossings)
    assert sum(c["freed"] for c in crossings) \
        <= stats["prefix_cache"]["evictions"]
    assert len(crossings) <= stats["prefix_cache"]["evict_walks"]
    assert parent_of["serving.step.dispatch"] == parent_of[
        "serving.step.sync"] == {"serving.prefill", "serving.decode"}
    # the children of an iteration lie inside it and add up to no more
    children = [e for e in mine
                if e["args"].get("parent") == "serving.iteration"]
    for it in its:
        t0, t1 = it["ts"], it["ts"] + it["dur"]
        inside = [c for c in children if t0 <= c["ts"] < t1]
        assert inside, "an iteration without a schedule span"
        assert sum(c["dur"] for c in inside) <= it["dur"] + 1.0
        assert all(c["ts"] + c["dur"] <= t1 + 1.0 for c in inside)
    steps = [e for e in children
             if e["name"] in ("serving.prefill", "serving.decode")]
    assert len([e for e in steps if e["name"] == "serving.decode"]) \
        <= stats["iterations"]


def test_phase_ms_accounts_for_the_iterations(params):
    """stats()["phase_ms"] is fed by the phase spans' own clock reads:
    its busy parts (everything but the waits outside a pass, and the wait
    for the device, which lies inside ``step``) add up to the wall time
    of the iterations, read from the span ring, within 5%."""
    # wide enough that a step outweighs the loop's unnamed glue (list
    # building, span bookkeeping: some tens of microseconds a pass)
    cfg = tr.TransformerConfig(vocab=40, d_model=512, n_heads=4, n_layers=6,
                               d_ff=2048, max_len=64)
    big = tr.transformer_lm_init(cfg, jax.random.PRNGKey(1))
    svc = GenerationService(big, cfg, _gc(), start=False)
    svc.warmup()
    before = svc.stats()["phase_ms"]
    assert set(before) == {"schedule", "build", "step", "emit", "idle_wait",
                           "sync_wait", "lock_wait", "gc"}
    tracing.clear()
    svc.start()
    try:
        rs = np.random.RandomState(3)
        hs = [svc.submit(rs.randint(0, cfg.vocab, n), max_new_tokens=24)
              for n in (6, 13, 9)]
        mid = None
        for h in hs:
            h.result(120)
            mid = mid or svc.stats()["phase_ms"]
        time.sleep(0.12)
    finally:
        svc.stop()
    after = svc.stats()["phase_ms"]
    assert all(after[k] >= mid[k] >= before[k] for k in before)
    assert after["step"] > mid["step"] > 0 and after["idle_wait"] > 0
    # counter and span are one pair of clock reads: each part is the sum
    # of its spans in the ring
    phase_of = {"serving.schedule": "schedule", "serving.emit": "emit",
                "serving.decode.build": "build",
                "serving.prefill.build": "build",
                "serving.prefill": "step", "serving.decode": "step",
                "serving.idle_wait": "idle_wait",
                "serving.step.sync": "sync_wait",
                "serving.lock_wait": "lock_wait"}
    ring = [s for s in tracing.recent_spans() if s["name"] in phase_of]
    # (the collector's pauses are no spans of the ring: GcWatch)
    for k in set(before) - {"gc"}:
        spans_ms = sum(s["dur_us"] for s in ring
                       if phase_of[s["name"]] == k) / 1e3
        assert after[k] - before[k] == pytest.approx(spans_ms, abs=0.01), k
    # a pass holds neither the idle wait nor the wait for the lock, and
    # its waits for the device are part of its steps
    outside = ("idle_wait", "lock_wait", "sync_wait")
    # and the busy parts cover an iteration: never more than its wall
    # time, and in the median iteration within 5% of it (one pass that the
    # scheduler of a loaded test host interrupts must not decide this)
    shares = []
    for it in tracing.recent_spans(name="serving.iteration"):
        t0, t1 = it["ts_us"], it["ts_us"] + it["dur_us"]
        parts = sum(s["dur_us"] for s in ring if t0 <= s["ts_us"] < t1
                    and phase_of[s["name"]] not in outside)
        assert parts <= it["dur_us"] + 1.0
        shares.append(parts / it["dur_us"])
    assert len(shares) >= 24 and np.median(shares) >= 0.95, shares


def test_tracing_off_same_tokens_same_losses_phase_ms_still_counts(
        params, monkeypatch):
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (4, 9, 17)]
    outs_on, _ = _serve(params, prompts)
    losses_on = _tiny_fit(3)
    assert tracing.recent_spans(name="serving.iteration")
    assert tracing.recent_spans(name="fit.input_wait")
    tracing.clear()
    monkeypatch.setenv("TPUMX_TRACING", "0")
    outs_off, stats_off = _serve(params, prompts)
    losses_off = _tiny_fit(3)
    assert outs_off == outs_on and losses_off == losses_on   # bitwise
    assert tracing.recent_spans() == []
    phases = stats_off["phase_ms"]
    assert phases["step"] > 0 and phases["schedule"] > 0 \
        and phases["emit"] > 0 and phases["build"] > 0


def _pallas_sites():
    """(case, function, arguments, static keywords, the names its
    pallas_calls must carry) — every ``pallas_call`` site of ``ops/``."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops import pallas_kernels as pk

    f32 = jnp.float32
    hd, nb, bs, w = 128, 8, 8, 4

    def paged(b, t):
        return (jnp.zeros((b, w), jnp.int32), jnp.zeros((b,), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.zeros((b, t, hd), f32),
                jnp.zeros((b, t), jnp.int32),
                jnp.zeros((1, nb, bs, hd), f32),
                jnp.zeros((1, nb, bs, hd), f32))

    x2d = jnp.ones((16, 128), f32)
    row = jnp.ones((128,), f32)
    q3 = jnp.ones((2, 16, 128), f32)
    col = jnp.ones((2, 16, 1), f32)
    flash = dict(t_real=16, causal=True, bq=8, bk=8, scale=0.1)
    g2d = jnp.ones((8, 16 * 128), f32)
    return [
        ("paged_decode", pa._paged_call, paged(2, 1),
         dict(n_heads=2, scale=0.1), ["_paged_call_w4_decode"]),
        ("paged_prefill", pa._paged_call, paged(1, 8),
         dict(n_heads=2, scale=0.1), ["_paged_call_w4_t8_prefill"]),
        ("ln", pk._ln_call, (x2d, row, row),
         dict(eps=1e-5, gelu=False, block_m=8), ["_ln_call"]),
        ("ln_gelu", pk._ln_call, (x2d, row, row),
         dict(eps=1e-5, gelu=True, block_m=8), ["_ln_call_gelu"]),
        ("flash_fwd", fa._fwd_call, (q3, q3, q3), flash,
         ["_fwd_call_flash"]),
        ("flash_bwd", fa._bwd_call, (q3, q3, q3, q3, col, col), flash,
         ["_bwd_call_flash_dq", "_bwd_call_flash_dkv"]),
        ("bn_stats", pk._bn_stats_call, (x2d, row), dict(block_m=8),
         ["_bn_stats_call"]),
        ("bn_norm", pk._bn_norm_call, (x2d, row, row), dict(block_m=8),
         ["_bn_norm_call"]),
        ("twobit_pack", pk._pack_call, (g2d, g2d, jnp.ones((1, 1), f32)),
         {}, ["_pack_call"]),
        ("twobit_unpack", pk._unpack_call,
         (jnp.ones((8, 128), jnp.uint32), jnp.ones((1, 1), f32)),
         dict(dtype=f32), ["_unpack_call"]),
    ]


def _pallas_call_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _pallas_call_names(inner, out)
    return out


@pytest.mark.parametrize("case", [
    "paged_decode", "paged_prefill", "ln", "ln_gelu", "flash_fwd",
    "flash_bwd", "bn_stats", "bn_norm", "twobit_pack", "twobit_unpack"])
def test_every_pallas_call_carries_its_trace_name(case):
    """The names a device trace shows (PERF.md): each starts with its
    wrapper's name, no two kernels share one, and the paged kernel tells
    decode from prefill and one table width from another."""
    import functools

    _, fn, args, kw, names = next(s for s in _pallas_sites()
                                  if s[0] == case)
    fn = functools.partial(fn, interpret=True, **kw)
    assert _pallas_call_names(jax.make_jaxpr(fn)(*args).jaxpr, []) == names
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert all(n in text for n in names)
    everyone = [n for s in _pallas_sites() for n in s[4]]
    assert len(set(everyone)) == len(everyone) == 11


def test_profiler_starts_jax_trace_without_python_frames(tmp_path,
                                                         monkeypatch):
    """TPUMX_JAX_TRACE_DIR: the device trace is started with jax's Python
    tracer off (it slows the host loop), unless API frames were asked
    for with set_config(profile_api=True)."""
    seen = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options=None: seen.append(
                            (d, profiler_options.python_tracer_level)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setenv("TPUMX_JAX_TRACE_DIR", str(tmp_path))
    try:
        for api in (False, True):
            profiler.set_config(profile_api=api)
            profiler.start()
            profiler.start()       # idempotent: one trace per run
            profiler.stop()
    finally:
        profiler.set_config()
    assert seen == [(str(tmp_path), 0), (str(tmp_path), 1)]


# -- the loop's whole window from inside (docs/observability.md section 2) ----------
def _until(cond, timeout=30.0):
    t_end = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < t_end, "timed out"
        time.sleep(0.005)


def _clock_counts(stats):
    """The loop's clocks among ``stats()["counts"]``."""
    return {k: v for k, v in stats["counts"].items()
            if k.startswith(("phase_us_", "iter_us", "iters_", "gc_"))}


def test_a_collection_is_a_span_and_a_counter_and_the_hook_goes(params):
    """While a started service's profiler runs, a forced collection is ONE
    ``serving.gc`` record (two services watch under one name), the
    services' ``gc`` clocks grow by its duration, and the process's one
    hook leaves ``gc.callbacks`` with the last of them."""
    import gc

    from mxnet_tpu.observability import gc_watch

    watching = gc_watch.watchers()
    a, b = (GenerationService(params, CFG, _gc(), start=False)
            for _ in range(2))
    assert _clock_counts(a.stats())["gc_pause_us"] == 0
    was_enabled = gc.isenabled()

    def run():
        a.start()
        b.start()
        try:
            _until(lambda: gc_watch.watchers() == watching + 2)
            assert gc.callbacks.count(gc_watch._hook) == 1
            gc.disable()                 # the forced one is the only one
            before = [s.stats() for s in (a, b)]
            gc.collect()
            after = [s.stats() for s in (a, b)]
            a.stop()
            assert gc_watch.watchers() == watching + 1
            assert gc_watch._hook in gc.callbacks
            gc.collect()                 # a's clock has stopped, b's has not
            late = [s.stats() for s in (a, b)]
        finally:
            if was_enabled:
                gc.enable()
            a.stop()
            b.stop()
        return before, after, late

    (before, after, late), events = _profiled(run)
    assert gc_watch.watchers() == watching
    assert (gc_watch._hook in gc.callbacks) == (watching > 0)
    first, second = [e for e in events if e["name"] == "serving.gc"]
    assert first["args"]["generation"] == second["args"]["generation"] == 2
    assert first["args"]["collected"] >= 0 and first["cat"] == "serving"
    assert first["tid"] == threading.get_ident()
    # the hook stays out of the span ring
    assert tracing.recent_spans(name="serving.gc") == []
    for was, now in zip(before, after):
        grew = now["counts"]["gc_pause_us"] - was["counts"]["gc_pause_us"]
        assert grew == pytest.approx(first["dur"], abs=1.5) and grew > 0
        assert now["phase_ms"]["gc"] == now["counts"]["gc_pause_us"] / 1e3
        assert now["counts"]["gc_collections_gen2"] \
            - was["counts"]["gc_collections_gen2"] == 1
    assert _clock_counts(late[0])["gc_pause_us"] \
        == _clock_counts(after[0])["gc_pause_us"]
    assert late[1]["counts"]["gc_pause_us"] \
        - after[1]["counts"]["gc_pause_us"] \
        == pytest.approx(second["dur"], abs=1.5)


@pytest.mark.parametrize("under", ["span_ring", "profiler"])
def test_a_collection_under_a_tracing_lock_does_not_hang(under, monkeypatch):
    """A collection can start on the thread that holds the span ring's
    lock or the profiler's, inside the append: the hook takes neither."""
    import gc
    from collections import deque

    from mxnet_tpu.observability import GcWatch

    collected = []

    def collecting(base):
        class Collecting(base):
            def append(self, record):
                if not collected:
                    collected.append(gc.collect())
                base.append(self, record)
        return Collecting

    if under == "span_ring":
        monkeypatch.setattr(tracing, "_SPAN_RING",
                            collecting(deque)(maxlen=16))
    else:
        monkeypatch.setattr(profiler, "_events", collecting(list)())

    def spanning():
        with GcWatch("serving.gc"), obs.span("serving.emit", cat="serving"):
            pass

    profiler.set_state("run")
    try:
        t = threading.Thread(target=spanning, daemon=True)
        t.start()
        t.join(20)
        assert not t.is_alive(), "the hook waited for a lock its thread holds"
    finally:
        profiler.set_state("stop")
    assert collected
    names = [e["name"] for e in profiler._events]
    assert "serving.gc" in names and "serving.emit" in names


def test_a_held_lock_is_a_lock_wait_span_and_an_uncontended_pass_is_none(
        params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    tracing.clear()
    h = svc.submit(np.arange(6) % CFG.vocab, max_new_tokens=6)
    held, hold_s = threading.Event(), 0.03

    def holder():
        with svc._lock:
            held.set()
            time.sleep(hold_s)

    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert held.wait(10)
    t0 = time.perf_counter()
    assert svc._pass()                    # the loop's turn, by hand
    waited_s = time.perf_counter() - t0
    t.join(10)
    assert not t.is_alive()
    while not h.finished:
        assert svc._pass()
    (wait,) = tracing.recent_spans(name="serving.lock_wait")
    # from the call to the holder's release: what is left of its 30 ms
    assert 0.5 * hold_s * 1e6 <= wait["dur_us"] <= waited_s * 1e6
    stats = svc.stats()
    assert stats["counts"]["phase_us_lock_wait"] == int(wait["dur_us"])
    assert stats["phase_ms"]["lock_wait"] \
        == stats["counts"]["phase_us_lock_wait"] / 1e3
    # the wait is the loop's own and no part of a pass
    first = tracing.recent_spans(name="serving.iteration")[0]
    assert wait["ts_us"] + wait["dur_us"] <= first["ts_us"]
    assert len(tracing.recent_spans(name="serving.iteration")) >= 6


def test_sync_wait_is_the_sync_spans_and_lies_inside_step(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()                          # its reads are nobody's wait
    tracing.clear()
    assert svc.stats()["counts"]["phase_us_sync_wait"] == 0
    svc.start()
    try:
        rs = np.random.RandomState(11)
        for h in [svc.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=10)
                  for n in (5, 12, 20, 7)]:
            h.result(120)
    finally:
        svc.stop()
    stats = svc.stats()
    syncs = tracing.recent_spans(name="serving.step.sync")
    assert {s["args"].get("of") for s in syncs} == {None, "prefill"}
    assert stats["counts"]["phase_us_sync_wait"] \
        == pytest.approx(sum(s["dur_us"] for s in syncs), abs=1.0)
    assert 0 < stats["counts"]["phase_us_sync_wait"] \
        <= stats["counts"]["phase_us_step"]


@pytest.mark.speculative
def test_sync_wait_counts_a_verify_steps_read(params):
    svc = GenerationService(params, CFG, _gc(speculative=True, draft_k=2),
                            start=False)
    svc.warmup()
    tracing.clear()
    svc.start()
    try:
        svc.generate(np.asarray([3, 7, 3, 7, 3, 7, 3]), max_new_tokens=10,
                     timeout=120)
    finally:
        svc.stop()
    stats = svc.stats()
    assert stats["speculative"]["spec_steps"] > 0
    syncs = tracing.recent_spans(name="serving.step.sync")
    assert stats["counts"]["phase_us_sync_wait"] \
        == pytest.approx(sum(s["dur_us"] for s in syncs), abs=1.0)


def test_a_pass_says_whether_it_decoded_alone(params):
    """One long prompt behind two decoding rows: the pass that prefills
    AND the pass after it (whose step was queued behind the chunks) are
    admitting; the others decoded alone."""
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    svc.warmup()
    tracing.clear()
    rs = np.random.RandomState(2)
    hs = [svc.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=12)
          for n in (4, 6)]
    alone = []

    def turn():
        was = svc.stats()["counts"]["iters_decode_only"]
        assert svc._pass()
        alone.append(svc.stats()["counts"]["iters_decode_only"] - was)

    for _ in range(4):
        turn()
    hs.append(svc.submit(rs.randint(0, CFG.vocab, 30), max_new_tokens=4))
    for _ in range(3):
        turn()
    #                admits, after, alone x 2, admits, after, alone
    assert alone == [0, 0, 1, 1, 0, 0, 1]
    while not all(h.finished for h in hs):
        assert svc._pass()
    c = svc.stats()["counts"]
    its = tracing.recent_spans(name="serving.iteration")
    assert c["iter_us_decode_only"] + c["iter_us_admitting"] == c["iter_us"]
    assert c["iter_us"] == pytest.approx(sum(s["dur_us"] for s in its),
                                         abs=2.0)
    assert 0 < c["iters_decode_only"] <= len(its) - 4
    # a pass that decodes nothing (it lands the last step) is not alone
    assert c["iter_us_decode_only"] < c["iter_us"]


def test_the_loops_clocks_are_whole_microseconds_that_never_fall(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    new = {"phase_us_schedule", "phase_us_build", "phase_us_step",
           "phase_us_emit", "phase_us_idle_wait", "phase_us_sync_wait",
           "phase_us_lock_wait", "gc_pause_us", "gc_collections_gen2",
           "iter_us", "iter_us_decode_only", "iters_decode_only",
           "iter_us_admitting"}
    # there from the construction, at 0: the benchmark's drivers subtract
    # the counts at a window's open from those at its close key by key
    born = _clock_counts(svc.stats())
    assert set(born) == new and not any(born.values())
    svc.warmup()
    svc.start()
    reads = [svc.stats()]
    try:
        rs = np.random.RandomState(4)
        hs = [svc.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=12)
              for n in (5, 9, 14, 6)]
        while not all(h.finished for h in hs):
            reads.append(svc.stats())
            time.sleep(0.002)
        time.sleep(0.08)
    finally:
        svc.stop()
    reads.append(svc.stats())
    for was, now in zip(reads, reads[1:]):
        a, b = _clock_counts(was), _clock_counts(now)
        assert all(type(v) is int for v in b.values()), b
        assert all(b[k] >= a[k] for k in new), (a, b)
        for k, ms in now["phase_ms"].items():
            assert ms == b["gc_pause_us" if k == "gc"
                           else "phase_us_" + k] / 1e3
        assert b["iter_us"] == b["iter_us_decode_only"] \
            + b["iter_us_admitting"]
    last = _clock_counts(reads[-1])
    assert last["iters_decode_only"] > 0 and last["phase_us_idle_wait"] > 0


def test_a_collection_under_fit_is_a_fit_gc_span():
    import gc

    from mxnet_tpu.observability import gc_watch

    watching = gc_watch.watchers()
    seen = []

    def on_batch(param):
        seen.append(gc_watch.watchers())
        gc.collect()

    n = 3
    _, events = _profiled(lambda: _tiny_fit(n, callback=on_batch))
    assert seen == [watching + 1] * n and gc_watch.watchers() == watching
    forced = [e for e in events if e["name"] == "fit.gc"
              and e["args"]["generation"] == 2]
    assert len(forced) >= n and all(e["cat"] == "fit" for e in forced)
    # each lies inside the callbacks span that set it off
    callbacks = [(e["ts"], e["ts"] + e["dur"]) for e in events
                 if e["name"] == "fit.callbacks"]
    inside = [e for e in forced
              if any(t0 <= e["ts"] and e["ts"] + e["dur"] <= t1
                     for t0, t1 in callbacks)]
    assert len(inside) >= n

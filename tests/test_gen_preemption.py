"""Serving that survives (docs/generation.md, docs/fault_tolerance.md):
incremental KV allocation + victim preemption, overload admission control,
decode-step failure isolation (retry → bisect-quarantine), strict
TPUMX_FAULT_* spec parsing, and stream/deadline expiry under a stalled
worker.
"""
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.fault.inject import injector
from mxnet_tpu.serving import (DeadlineExceededError, QueueFullError,
                               RequestShedError, ServingClosedError)
from mxnet_tpu.serving.generation import (GenerationService,
                                          GenerationStepError, blocks_for)
from oracle import CFG, greedy_oracle, params  # noqa: F401 (fixture)
from test_generation import _gc

pytestmark = pytest.mark.generation


@pytest.fixture(autouse=True)
def _fresh_state():
    """Warmups call mark_warm() and fault tests flip TPUMX_FAULT_* vars:
    reset both between cases (env monkeypatches are undone first)."""
    yield
    obs.recompile.reset()
    injector().reset()


# -- incremental allocation ---------------------------------------------------------
def test_incremental_admission_allocates_context_only(params):
    """Admission under preemption takes blocks for prompt+1 positions, not
    the reserve-ahead worst case; reserve-ahead (preemption=False) keeps
    the old accounting byte-for-byte."""
    svc = GenerationService(params, CFG, _gc(preemption=True), start=False)
    h = svc.submit(np.arange(20) % CFG.vocab, max_new_tokens=12)
    with svc._lock:
        admitted = svc._admit_locked()
    assert len(admitted) == 1
    req = admitted[0]
    assert len(req.blocks) == blocks_for(21, 8)          # 3, not 4
    svc.stop(drain=False)

    old = GenerationService(params, CFG, _gc(preemption=False), start=False)
    old.submit(np.arange(20) % CFG.vocab, max_new_tokens=12)
    with old._lock:
        admitted = old._admit_locked()
    assert len(admitted[0].blocks) == blocks_for(20 + 12, 8)   # 4: worst case
    old.stop(drain=False)
    del h


def test_preempted_and_resumed_greedy_bit_identical(params):
    """Two requests on a pool too small for both worst cases: incremental
    admission co-schedules them, pool pressure preempts the newest, it
    resumes via re-prefill — and every token matches the uncontended
    greedy oracle bit-for-bit (the overload acceptance criterion)."""
    # 7 allocatable blocks of 8 positions; each request grows to 4 blocks
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=8, preemption=True),
                            start=False)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, CFG.vocab, 20) for _ in range(2)]
    hs = [svc.submit(p, max_new_tokens=12) for p in prompts]
    svc.start()
    outs = [h.result(120) for h in hs]
    stats = svc.stats()
    svc.stop()
    for p, got in zip(prompts, outs):
        assert got == greedy_oracle(params, p, 12)
    assert stats["counts"]["preempted"] >= 1, \
        "the tight pool must have forced at least one preemption"
    # both were co-scheduled at some point (reserve-ahead could not)
    member = [set(m) for _, m in svc.membership_history()]
    assert {0, 1} in member


def test_reserve_ahead_never_co_schedules_oversized_pair(params):
    """The same tight-pool workload under TPUMX_GEN_PREEMPTION=0 semantics:
    worst-case reservation serializes the two requests (the occupancy gap
    incremental allocation closes) and never preempts."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=8, preemption=False),
                            start=False)
    rs = np.random.RandomState(1)
    hs = [svc.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12)
          for _ in range(2)]
    svc.start()
    [h.result(120) for h in hs]
    stats = svc.stats()
    svc.stop()
    member = [set(m) for _, m in svc.membership_history()]
    assert {0, 1} not in member
    assert stats["counts"]["preempted"] == 0


def test_watermark_preempts_newest_victim(params):
    """Crossing the high watermark preempts the newest-admitted request
    down to the low watermark (direct scheduling-phase unit test)."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=32, preemption=True,
                                watermark_high=0.5, watermark_low=0.25),
                            start=False)
    svc.submit(np.arange(9), max_new_tokens=4)
    svc.submit(np.arange(9), max_new_tokens=4)
    alloc = svc._cache.allocator
    with svc._lock:
        admitted = svc._admit_locked()
        assert len(admitted) == 2
        # inflate occupancy past the high watermark (31 * 0.5 = 15.5)
        admitted[0].blocks.extend(alloc.allocate(8))
        admitted[1].blocks.extend(alloc.allocate(8))
        assert alloc.above_high()
        svc._watermark_preempt_locked()
        assert not alloc.above_low() or alloc.occupancy() <= 0.5
        # the NEWEST admission was the victim; the older one kept its slot
        assert admitted[1].state == "waiting"
        assert admitted[0].state == "running"
    assert svc.stats()["counts"]["preempted"] >= 1
    svc.stop(drain=False)


def test_priority_class_beats_fifo_and_picks_victims(params):
    """Admission prefers the higher priority class; victim selection
    preempts the lowest class even when it was admitted first."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=1, num_blocks=32), start=False)
    svc.submit(np.arange(5), max_new_tokens=3)                   # occupies
    low = svc.submit(np.arange(5), max_new_tokens=3, priority=0)
    high = svc.submit(np.arange(5), max_new_tokens=3, priority=5)
    svc.start()
    high_out = high.result(60)
    low_out = low.result(60)
    svc.stop()
    assert len(high_out) == 3 and len(low_out) == 3
    member = [m for _, m in svc.membership_history() if m]
    # rid 2 (high) decodes before rid 1 (low) despite arriving later
    first_high = next(i for i, m in enumerate(member) if 2 in m)
    first_low = next(i for i, m in enumerate(member) if 1 in m)
    assert first_high < first_low

    vic = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=8, preemption=True),
                            start=False)
    rs = np.random.RandomState(2)
    h_low = vic.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12,
                       priority=0)
    h_high = vic.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12,
                        priority=5)
    with vic._lock:
        admitted = vic._admit_locked()
        assert [r.priority for r in admitted] == [5, 0] or \
            [r.priority for r in admitted] == [0, 5]
        # exhaust the pool, then ask the high-priority request to grow:
        # the LOW priority one must be the victim even though it could be
        # older
        spare = vic._cache.allocator.allocate(vic._cache.allocator.num_free)
        v = vic._pick_victim_locked()
        assert vic._slots[v] is not None
        assert vic._slots[v].priority == 0
        vic._cache.allocator.free(spare)
    vic.stop(drain=False)
    del h_low, h_high


def test_zero_recompiles_with_preemption_under_freeze(params, monkeypatch):
    """Acceptance: warmup enumerates the re-prefill rungs too — a run that
    preempts and resumes shows exactly 1 miss per signature under
    TPUMX_FREEZE_COMPILES=1 (no new program shapes)."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=8, preemption=True),
                            start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(1)
    hs = [svc.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12)
          for _ in range(2)]
    svc.start()
    [h.result(120) for h in hs]
    stats = svc.compile_stats()
    preempted = svc.stats()["counts"]["preempted"]
    svc.stop()
    assert preempted >= 1, "workload must exercise the re-prefill path"
    for key, st in stats.items():
        assert st["misses"] == 1, f"recompile at {key}: {st}"


# -- overload control ---------------------------------------------------------------
def test_admission_budget_rejects_before_pool_thrash(params):
    """The token-budget estimator fires the reject policy on projected
    blocks, long before the queue bound."""
    svc = GenerationService(params, CFG,
                            _gc(backpressure="reject", admission_budget=1.0,
                                num_blocks=32),
                            start=False)
    # each request projects blocks_for(20 + 12, 8) = 4 of the 31-block pool
    for _ in range(7):
        svc.submit(np.arange(20), max_new_tokens=12)
    with pytest.raises(QueueFullError, match="admission budget"):
        svc.submit(np.arange(20), max_new_tokens=12)
    assert svc.stats()["counts"]["rejected"] == 1
    svc.stop(drain=False)


def test_admission_budget_shed_oldest(params):
    svc = GenerationService(params, CFG,
                            _gc(backpressure="shed_oldest",
                                admission_budget=1.0, num_blocks=32),
                            start=False)
    hs = [svc.submit(np.arange(20), max_new_tokens=12) for _ in range(7)]
    extra = svc.submit(np.arange(20), max_new_tokens=12)
    with pytest.raises(RequestShedError):
        hs[0].result(5)
    assert not extra.finished
    svc.stop(drain=False)


def test_overload_soak_no_lost_or_hung_streams(params):
    """Acceptance: arrival rate above capacity with a tight pool — every
    submitted request either completes or carries a typed error; nothing
    hangs and greedy completions stay oracle-exact."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=8, queue_bound=6,
                                backpressure="shed_oldest", preemption=True),
                            start=False)
    svc.warmup()   # no compile stall: arrivals race real decode iterations
    rs = np.random.RandomState(3)
    # two guaranteed-colliding heavy requests (each grows to 4 of the 7
    # blocks) are queued BEFORE the loop starts so they co-admit into the
    # slots and force the preemption path; the unpaced random burst then
    # floods the bounded queue for shed/expiry pressure
    handles = []
    for _ in range(2):
        p = rs.randint(0, CFG.vocab, 20)
        handles.append((svc.submit(p, max_new_tokens=12), p, 12))
    svc.start()
    deadline_t = time.perf_counter() + 10
    while svc.stats()["running"] < 2 and time.perf_counter() < deadline_t:
        time.sleep(0.002)
    for i in range(16):
        n = int(rs.choice([6, 12, 20]))
        p = rs.randint(0, CFG.vocab, n)
        mn = int(rs.choice([4, 8, 12]))
        deadline = 3000.0 if i % 5 == 4 else None
        handles.append((svc.submit(p, max_new_tokens=mn,
                                   deadline_ms=deadline), p, mn))
    completed = shed = expired = 0
    for h, p, mn in handles:
        try:
            out = h.result(180)       # a hang here fails the test
            assert out == greedy_oracle(params, p, mn)
            completed += 1
        except RequestShedError:
            shed += 1
        except DeadlineExceededError:
            expired += 1
    stats = svc.stats()
    svc.stop()
    assert completed + shed + expired == len(handles)
    assert completed > 0
    assert stats["counts"]["preempted"] >= 1


# -- failure isolation --------------------------------------------------------------
def test_transient_step_failure_retries_with_zero_blast_radius(
        params, monkeypatch):
    """Regression (engine.py step-exception blast radius): one injected
    decode-step failure — every stream still completes; nothing is failed
    or lost, the retry absorbs it."""
    monkeypatch.setenv("TPUMX_FAULT_GEN_STEP_FAIL", "2")
    injector().reset()
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (5, 11, 17)]
    hs = [svc.submit(p, max_new_tokens=6) for p in prompts]
    svc.start()
    outs = [h.result(60) for h in hs]
    stats = svc.stats()
    svc.stop()
    for p, got in zip(prompts, outs):
        assert got == greedy_oracle(params, p, 6)
    assert stats["counts"]["step_failures"] == 1
    assert stats["counts"]["quarantined"] == 0
    assert stats["counts"]["failed"] == 0


def test_poisoned_request_bisect_quarantined_others_survive(
        params, monkeypatch):
    """A persistently poisoned request (N@rid) is isolated by bisection
    and fails with GenerationStepError; co-scheduled requests complete
    with oracle-exact tokens."""
    monkeypatch.setenv("TPUMX_FAULT_GEN_STEP_FAIL", "1@1")
    injector().reset()
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (7, 13, 9)]
    hs = [svc.submit(p, max_new_tokens=6) for p in prompts]
    svc.start()
    with pytest.raises(GenerationStepError, match="quarantined"):
        hs[1].result(60)
    out0 = hs[0].result(60)
    out2 = hs[2].result(60)
    stats = svc.stats()
    svc.stop()
    assert out0 == greedy_oracle(params, prompts[0], 6)
    assert out2 == greedy_oracle(params, prompts[2], 6)
    assert stats["counts"]["quarantined"] == 1
    assert stats["counts"]["step_failures"] >= 2   # original + retry at least
    assert hs[1].finish_reason == "error"


def test_prefill_error_requeues_then_fails_typed(params, monkeypatch):
    """A request whose prefill keeps blowing up consumes its requeue
    budget and then fails with GenerationStepError — it never takes the
    engine loop down."""
    svc = GenerationService(params, CFG, _gc(), start=False)
    orig = svc._programs.run

    def explode(kind, *a, **kw):
        if kind == "gen_prefill":
            raise RuntimeError("boom")
        return orig(kind, *a, **kw)

    monkeypatch.setattr(svc._programs, "run", explode)
    h = svc.submit(np.arange(5), max_new_tokens=2)
    svc.start()
    with pytest.raises(GenerationStepError, match="error requeues"):
        h.result(60)
    stats = svc.stats()
    svc.stop()
    assert stats["counts"]["requeued"] == svc._max_error_requeues


def test_preemption_and_failed_step_land_the_step_in_flight_first(
        params, monkeypatch):
    """With a step in flight, a preemption and an injected step failure
    each read and emit that step before anything else happens to its
    rows: every stream is the uncontended oracle's, every token reaches
    its callback once, and no row leaves its slot with a token unread."""
    from mxnet_tpu.serving.generation.engine import _LandFirst

    # the 3rd decode invocation fails before its dispatch, step 2 unread
    monkeypatch.setenv("TPUMX_FAULT_GEN_STEP_FAIL", "3")
    injector().reset()
    # 7 allocatable blocks of 8 positions; each request grows to 4 blocks
    svc = GenerationService(params, CFG,
                            _gc(max_slots=2, num_blocks=8, preemption=True),
                            start=False)
    svc.warmup()
    preempt, calls = svc._preempt_slot_locked, []

    def preempting(i, counter="preempted"):
        r = svc._slots[i]
        try:
            preempt(i, counter)
        except _LandFirst:
            calls.append(("land first", r.rid, r.n_generated))
            raise
        calls.append((counter, r.rid, r.n_generated))

    monkeypatch.setattr(svc, "_preempt_slot_locked", preempting)
    note, failed_at = svc._note_step_failure, []
    monkeypatch.setattr(svc, "_note_step_failure", lambda exc: (
        failed_at.append(svc._flight is not None), note(exc))[1])
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, CFG.vocab, 20) for _ in range(2)]
    seen = [[], []]
    hs = [svc.submit(p, max_new_tokens=12,
                     on_token=lambda rid, t, s=s: s.append(t))
          for p, s in zip(prompts, seen)]
    svc.start()
    outs = [h.result(120) for h in hs]
    time.sleep(0.12)
    stats = svc.stats()
    svc.stop()
    for p, got, cb in zip(prompts, outs, seen):
        assert got == cb == greedy_oracle(params, p, 12)
    c = stats["counts"]
    assert c["preempted"] >= 1 and c["step_failures"] == 1
    assert c["failed"] == 0 and c["tokens"] == 24
    assert c["steps_ahead"] >= 1 and c["steps_drained"] >= 3
    # the failure met a step in flight, and so did the first preemption:
    # it was refused, the step landed, and the same row was preempted at
    # rest with one token more
    assert failed_at == [True]
    first = calls.index(next(c for c in calls if c[0] == "land first"))
    _, rid, n = calls[first]
    assert calls[first + 1] == ("preempted", rid, n + 1)


@pytest.mark.parametrize("times", [1, 2])
def test_failed_read_of_the_step_in_flight_costs_no_token(
        params, monkeypatch, times):
    """The read of step n, made after step n + 1 was dispatched, raises —
    once (the landing's second read succeeds) or twice (the step is given
    up and its rows are fed from the host's view again): either way every
    stream is the oracle's and nothing fails."""
    from mxnet_tpu.serving.generation import engine as engine_mod

    svc = GenerationService(params, CFG, _gc(max_slots=2), start=False)
    svc.warmup()
    synced, reads, raised = engine_mod._synced, [0], []

    def failing(*outs, **kw):
        f = svc._flight
        if f is not None and outs[0] is f.tokens:
            reads[0] += 1
            if reads[0] >= 3 and len(raised) < times:
                raised.append(reads[0])
                raise RuntimeError("injected read failure")
        return synced(*outs, **kw)

    monkeypatch.setattr(engine_mod, "_synced", failing)
    rs = np.random.RandomState(8)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (6, 15)]
    seen = [[], []]
    hs = [svc.submit(p, max_new_tokens=10,
                     on_token=lambda rid, t, s=s: s.append(t))
          for p, s in zip(prompts, seen)]
    svc.start()
    outs = [h.result(120) for h in hs]
    time.sleep(0.12)
    stats = svc.stats()
    svc.stop()
    assert len(raised) == times
    for p, got, cb in zip(prompts, outs, seen):
        assert got == cb == greedy_oracle(params, p, 10)
    c = stats["counts"]
    assert c["step_failures"] == times and c["failed"] == 0
    assert c["quarantined"] == 0 and c["tokens"] == 20


# -- satellite: stream expiry under a stalled worker --------------------------------
def test_result_timeout_expiry_while_worker_stalled(params):
    """GenerationStream.result(timeout=) raises TimeoutError when the
    engine never gets to the request (stalled/unstarted worker)."""
    svc = GenerationService(params, CFG, _gc(), start=False)
    h = svc.submit(np.arange(4), max_new_tokens=2)
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError, match="still running"):
        h.result(0.2)
    assert time.perf_counter() - t0 < 5.0
    svc.stop(drain=False)
    with pytest.raises(ServingClosedError):
        h.result(1)


def test_queued_deadline_expires_while_worker_stalled(params, monkeypatch):
    """A deadline-bearing QUEUED request behind a stalled slot gets a
    typed DeadlineExceededError while the worker is still mid-decode."""
    svc = GenerationService(params, CFG, _gc(max_slots=1), start=False)
    orig = svc._programs.run

    def slow(kind, *a, **kw):
        if kind == "gen_decode":
            time.sleep(0.05)      # stall every decode step
        return orig(kind, *a, **kw)

    monkeypatch.setattr(svc._programs, "run", slow)
    h_long = svc.submit(np.arange(8), max_new_tokens=30)
    h_queued = svc.submit(np.arange(8), max_new_tokens=4, deadline_ms=200.0)
    svc.start()
    with pytest.raises(DeadlineExceededError, match="in queue"):
        h_queued.result(60)
    assert len(h_long.result(120)) == 30
    stats = svc.stats()
    svc.stop()
    assert stats["counts"]["expired"] == 1


# -- satellite: strict TPUMX_FAULT_* spec parsing -----------------------------------
@pytest.mark.parametrize("var,val,frag", [
    ("TPUMX_FAULT_KV_DROP", "push:x", "'x'"),
    ("TPUMX_FAULT_KV_DROP", "pushonly", "'pushonly'"),
    ("TPUMX_FAULT_KV_DROP", ":1", "':1'"),
    ("TPUMX_FAULT_KV_DROP", "push:", "'push:'"),
    ("TPUMX_FAULT_KV_DELAY_MS", "push:abc", "'abc'"),
    ("TPUMX_FAULT_KV_DELAY_MS", "push:10@", "'push:10@'"),
    ("TPUMX_FAULT_KV_KILL_SERVER", "soon", "'soon'"),
    ("TPUMX_FAULT_PREEMPT_AT_STEP", "n", "'n'"),
    ("TPUMX_FAULT_CKPT_CORRUPT", "melt", "'melt'"),
    ("TPUMX_FAULT_CKPT_CORRUPT", "flip@x", "'x'"),
    ("TPUMX_FAULT_GEN_STEP_FAIL", "x@1", "'x'"),
    ("TPUMX_FAULT_GEN_STEP_FAIL", "1@rid7", "'rid7'"),
    ("TPUMX_FAULT_GEN_KILL_REPLICA", "0@z", "'z'"),
])
def test_fault_spec_strict_parsing_names_var_and_token(
        monkeypatch, var, val, frag):
    monkeypatch.setenv(var, val)
    with pytest.raises(MXNetError) as ei:
        injector().reset()
    msg = str(ei.value)
    assert var in msg and frag in msg


def test_fault_spec_good_tokens_still_parse(monkeypatch):
    monkeypatch.setenv("TPUMX_FAULT_KV_DROP", "push:1,2;pull:3")
    monkeypatch.setenv("TPUMX_FAULT_KV_DELAY_MS", "push:200@1,2")
    monkeypatch.setenv("TPUMX_FAULT_GEN_STEP_FAIL", "4@2")
    monkeypatch.setenv("TPUMX_FAULT_GEN_KILL_REPLICA", "1@3")
    injector().reset()
    inj = injector()
    assert inj._drops == {"push": [1, 2], "pull": [3]}
    assert inj._delays == {"push": (200.0, [1, 2])}
    assert inj._gen_step_fail == (4, 2)
    assert inj._kill_replica == (1, 3)

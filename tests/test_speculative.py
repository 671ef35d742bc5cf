"""Speculative + multi-token decoding (mxnet_tpu.serving.generation.
speculative, docs/generation.md "Speculative decoding"): n-gram and
draft-model proposers, exact-match rejection sampling parity, the
multi-query verify step vs the greedy oracle across batch-membership
changes, preemption mid-speculation, int8 shared-block isolation under
rejection, and zero post-warmup recompiles with every speculative program
frozen.
"""
import time

import jax
import numpy as np
import pytest

from mxnet_tpu.ops import sampling as smp
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving.generation import GenerationService
from mxnet_tpu.serving.generation.speculative import DraftModel, propose_ngram
from oracle import CFG, greedy_oracle, params  # noqa: F401 (fixture)
from test_generation import _fresh_observability, _gc  # noqa: F401 (fixture)

pytestmark = [pytest.mark.generation, pytest.mark.speculative]


# repetitive prompts so the n-gram proposer actually fires
REP = [np.array(([1, 2, 3, 4] * 5)[:17]),
       np.array([7, 8, 9] * 4),
       np.array([3, 1, 4, 1, 5, 9, 2, 6] * 3)]


# -- n-gram proposer ----------------------------------------------------------------
def test_propose_ngram_basic_match():
    # tail [1,2] recurs at index 1; continuation is [9,1,2]
    assert propose_ngram([5, 1, 2, 9, 1, 2], 3, 3) == [9, 1, 2]
    # longest n-gram wins: 3-gram [2,9,1] only matches via the 2-gram here
    assert propose_ngram([5, 1, 2, 9, 1, 2], 2, 3) == [9, 1]


def test_propose_ngram_most_recent_occurrence_wins():
    toks = [1, 2, 7, 1, 2, 8, 1, 2]
    # both i=0 and i=3 match the [1,2] tail; the later one supplies drafts
    assert propose_ngram(toks, 2, 2) == [8, 1]


def test_propose_ngram_no_match_and_truncation():
    assert propose_ngram([1, 2, 3, 4, 5], 4, 3) == []
    # match at the very end: fewer than k tokens available
    assert propose_ngram([9, 1, 2, 1, 2], 4, 2) == [1, 2]
    assert propose_ngram([3], 4, 3) == []
    assert propose_ngram([1, 2, 3], 0, 3) == []


# -- exact-match verification vs sample_logits --------------------------------------
def test_speculative_verify_numpy_parity():
    """The verify op's per-position targets are exactly sample_logits at
    (seed, position), and acceptance is the cumulative left-to-right
    exact match bounded by each row's fed length."""
    rs = np.random.RandomState(11)
    B, T, V = 3, 4, 13
    logits = rs.randn(B, T, V).astype(np.float32)
    seeds = np.array([5, 6, 7], np.uint32)
    counters = np.array([10, 3, 21], np.uint32)
    temp = np.array([0.0, 0.9, 0.7], np.float32)
    top_k = np.array([0, 5, 0], np.int32)
    top_p = np.array([1.0, 1.0, 0.9], np.float32)

    # reference target per position: one sample_logits call per column
    ref = np.zeros((B, T), np.int32)
    for t in range(T):
        ref[:, t] = np.asarray(smp.sample_logits(
            logits[:, t, :], seeds, counters + t, temp, top_k, top_p))

    # row 0: all drafts match -> full acceptance (lengths-1)
    # row 1: first draft wrong -> 0 accepted
    # row 2: accept 1 then diverge; garbage beyond lengths must not count
    fed = np.zeros((B, T), np.int32)
    fed[0, 1:] = ref[0, :-1]
    fed[1, 1] = (ref[1, 0] + 1) % V
    fed[1, 2:] = ref[1, 1:-1]
    fed[2, 1] = ref[2, 0]
    fed[2, 2] = (ref[2, 1] + 3) % V
    lengths = np.array([4, 4, 3], np.int32)

    target, accepted = smp.speculative_verify(
        logits, fed, seeds, counters, temp, top_k, top_p, lengths)
    np.testing.assert_array_equal(np.asarray(target), ref)
    np.testing.assert_array_equal(np.asarray(accepted), [3, 0, 1])


# the verify batch's knobs -> the body its B x T rows take at once
_VERIFY_MIXES = {
    "greedy": ([0.0, 0.0, 0.0], [0, 4, 0], [1.0, 1.0, 0.5]),
    "draw": ([0.0, 0.9, 0.7], [0, 13, 0], [1.0, 1.0, 1.0]),
    "filter": ([0.8, 0.9, 0.7], [3, 5, 0], [1.0, 0.95, 0.9]),
    "filter_beside_greedy_and_draw": ([0.0, 0.9, 0.7], [0, 0, 4],
                                      [1.0, 1.0, 0.9]),
}


@pytest.mark.parametrize("mix", sorted(_VERIFY_MIXES))
def test_verify_targets_are_the_target_only_stream_in_every_body(mix):
    """Whatever body the verify step's B x T rows take together, each
    target token is the one a single-row, single-position call — the
    target-only stream's own step, which may take another body — gives at
    that (seed, position)."""
    rs = np.random.RandomState(19)
    B, T, V = 3, 4, 13
    logits = (np.round(rs.randn(B, T, V) * 4) / 2).astype(np.float32)
    seeds = np.array([5, 6, 7], np.uint32)
    counters = np.array([10, 3, 21], np.uint32)
    temp, top_k, top_p = (np.asarray(a, dt) for a, dt in zip(
        _VERIFY_MIXES[mix], (np.float32, np.int32, np.float32)))
    assert smp.SAMPLER_BODIES[int(smp.sampler_body(
        temp, top_k, top_p, V))] == mix.split("_")[0]
    target, _ = smp.speculative_verify(
        logits, np.zeros((B, T), np.int32), seeds, counters, temp, top_k,
        top_p, np.full(B, T, np.int32))
    for b in range(B):
        for t in range(T):
            alone = smp.sample_logits(
                logits[b:b + 1, t], seeds[b:b + 1], counters[b:b + 1] + t,
                temp[b:b + 1], top_k[b:b + 1], top_p[b:b + 1])
            assert int(target[b, t]) == int(alone[0]), (b, t)


def test_speculative_verify_t1_degenerates_to_plain_step():
    rs = np.random.RandomState(3)
    logits = rs.randn(2, 1, 9).astype(np.float32)
    seeds = np.array([1, 2], np.uint32)
    counters = np.array([4, 5], np.uint32)
    temp = np.array([0.0, 1.0], np.float32)
    zk = np.zeros(2, np.int32)
    op = np.ones(2, np.float32)
    target, accepted = smp.speculative_verify(
        logits, np.zeros((2, 1), np.int32), seeds, counters, temp, zk, op,
        np.ones(2, np.int32))
    ref = np.asarray(smp.sample_logits(logits[:, 0, :], seeds, counters,
                                       temp, zk, op))
    np.testing.assert_array_equal(np.asarray(target)[:, 0], ref)
    np.testing.assert_array_equal(np.asarray(accepted), [0, 0])


# -- draft model: windowed forward parity -------------------------------------------
def test_draft_model_propose_matches_full_oracle(params):
    """With the window covering the full context, the draft's k greedy
    proposals equal the full-sequence greedy oracle — the windowed
    re-forward is the same transformer."""
    draft = DraftModel(params, CFG, k=4, window=16)
    toks = np.array([4, 7, 1, 9, 2, 6])
    n = len(toks)
    w = draft.window
    window = np.zeros((1, w), np.int32)
    positions = np.zeros((1, w), np.int32)
    window[0, w - n:] = toks
    positions[0] = np.arange(n - w, n)
    props = draft.propose(window, np.clip(positions, 0, CFG.max_len - 1),
                          np.array([n], np.int32))
    assert props.shape == (1, 4)
    assert list(props[0]) == greedy_oracle(params, toks, 4)
    st = draft.compile_stats()
    assert len(st) == 1 and next(iter(st))[0] == "gen_draft"


def test_draft_model_validation(params):
    with pytest.raises(ValueError, match="window"):
        DraftModel(params, CFG, k=2, window=CFG.max_len + 1)
    cfg = _gc(speculative=True, draft_mode="model")
    with pytest.raises(ValueError, match="draft_params"):
        GenerationService(params, CFG, cfg, start=False)
    bad = tr.TransformerConfig(vocab=CFG.vocab + 1, d_model=16, n_heads=2,
                               n_layers=1, d_ff=32, max_len=64)
    with pytest.raises(ValueError, match="vocab"):
        GenerationService(
            params, CFG, cfg, start=False,
            draft_params=tr.transformer_lm_init(bad, jax.random.PRNGKey(1)),
            draft_cfg=bad)
    with pytest.raises(ValueError, match="draft_mode"):
        _gc(speculative=True, draft_mode="oracle")


# -- acceptance: greedy bitwise parity under speculation ----------------------------
def test_spec_greedy_bitwise_matches_oracle_across_membership(params):
    """Staggered arrivals and mixed prompt lengths with the n-gram
    proposer on: every request's greedy tokens equal the uncontended
    full-sequence oracle bit-for-bit even as the verify batch's
    membership changes under it."""
    svc = GenerationService(
        params, CFG, _gc(max_slots=3, speculative=True, draft_k=4),
        start=False)
    svc.warmup()
    svc.start()
    handles = []
    for i, p in enumerate(REP + [np.array([11, 5, 11, 5, 11, 5, 2])]):
        handles.append(svc.submit(p, max_new_tokens=6 + (i % 4)))
        if i % 2 == 0:
            time.sleep(0.01)
    outs = [h.result(180) for h in handles]
    req_stats = [h.stats() for h in handles]
    stats = svc.stats()
    svc.stop()
    for i, p in enumerate(REP + [np.array([11, 5, 11, 5, 11, 5, 2])]):
        assert outs[i] == greedy_oracle(params, p, 6 + (i % 4)), \
            f"request {i} diverged from the greedy oracle"
    spec = stats["speculative"]
    assert spec["spec_steps"] >= 1 and spec["proposed_tokens"] >= 1
    assert stats["decode_mode"] == "spec"
    # a greedy draft verify never leaves the sampler's greedy body
    counts = stats["counts"]
    assert counts["sampler_steps_greedy"] >= spec["spec_steps"]
    assert counts["sampler_steps_draw"] == counts["sampler_steps_filter"] == 0
    # per-request wide-event fields surface on the stream handle too
    for st in req_stats:
        assert st["decode_mode"] in ("spec", "single")
        assert st["draft_proposed_tokens"] >= 0
        if st["draft_proposed_tokens"]:
            assert st["accepted_ratio"] == pytest.approx(
                st["draft_accepted_tokens"] / st["draft_proposed_tokens"],
                abs=1e-3)
    assert any(st["decode_mode"] == "spec" for st in req_stats)


def test_spec_sampled_bitwise_matches_baseline(params):
    """Sampled requests (temperature/top-k/top-p) under speculation draw
    the SAME tokens as the single-token baseline: sampling is keyed on
    (seed, position), so the verify step's draws are literally the
    target-only draws."""
    def run(speculative):
        svc = GenerationService(
            params, CFG, _gc(speculative=speculative, draft_k=4),
            start=False)
        svc.start()
        outs = [svc.generate(p, max_new_tokens=8, temperature=0.9,
                             top_k=10, top_p=0.95, seed=100 + i,
                             timeout=180)
                for i, p in enumerate(REP)]
        stats = svc.stats()
        svc.stop()
        return outs, stats

    spec, st_on = run(True)
    base, st_off = run(False)
    assert spec == base
    assert st_on["speculative"]["spec_steps"] >= 1
    for st in (st_on, st_off):
        assert st["counts"]["sampler_steps_filter"] >= 1
        assert st["counts"]["sampler_steps_greedy"] == 0
    assert st_off["speculative"] is None


def test_spec_draft_model_full_acceptance(params):
    """Draft model == target model: every proposal is the target's own
    greedy token, so acceptance is total and outputs still match the
    oracle (the self-draft upper bound)."""
    svc = GenerationService(
        params, CFG,
        _gc(speculative=True, draft_mode="model", draft_k=3,
            draft_window=32),
        start=False, draft_params=params, draft_cfg=CFG)
    svc.warmup()
    svc.start()
    prompts = [np.array([4, 7, 1, 9, 2, 6]), np.array([12, 3, 12, 3, 5])]
    outs = [svc.generate(p, max_new_tokens=8, timeout=180) for p in prompts]
    stats = svc.stats()
    svc.stop()
    for p, got in zip(prompts, outs):
        assert got == greedy_oracle(params, p, 8)
    spec = stats["speculative"]
    assert spec["draft_mode"] == "model"
    assert spec["proposed_tokens"] >= 1
    assert spec["accepted_ratio"] == 1.0


# -- preemption mid-speculation -----------------------------------------------------
def test_preemption_mid_speculation_bit_identical(params):
    """A pool too small for both worst cases forces preemption while
    speculative decoding is active; the preempted request resumes via
    re-prefill and still matches the greedy oracle bit-for-bit."""
    # admission secures the context plus one verify chunk (20 + 5 positions
    # = 4 blocks of 8) per request: 9 usable blocks admit both, and growing
    # to the 5 blocks each needs by the end (20 + 20 positions) cannot fit
    svc = GenerationService(
        params, CFG,
        _gc(max_slots=2, num_blocks=10, preemption=True, speculative=True,
            draft_k=4),
        start=False)
    prompts = [np.tile([1, 2, 3, 4, 5], 4), np.tile([7, 8, 9, 2], 5)]
    hs = [svc.submit(p, max_new_tokens=20) for p in prompts]
    svc.start()
    outs = [h.result(180) for h in hs]
    stats = svc.stats()
    svc.stop()
    for p, got in zip(prompts, outs):
        assert got == greedy_oracle(params, p, 20)
    assert stats["counts"]["preempted"] >= 1, \
        "the tight pool must have forced at least one preemption"
    assert stats["speculative"]["spec_steps"] >= 1


# -- int8 + prefix cache: rejection never touches shared blocks ---------------------
def test_int8_shared_blocks_untouched_by_rejecting_verify(params):
    """Speculative rejection with the int8 pool and the prefix cache on:
    indexed (shared) blocks' device bits — payload AND scales — are
    bitwise unchanged after a speculating sharer runs, and all sharers
    decode identically (the CoW rollback guarantee)."""
    svc = GenerationService(
        params, CFG,
        _gc(kv_dtype="int8", prefix_cache=True, speculative=True,
            draft_k=4, num_blocks=64),
        start=False)
    svc.start()
    prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6] * 3)   # 24 = 3 full blocks
    a = svc.generate(prompt, timeout=180)
    shared = sorted(e.block for e in svc._prefix._entries.values())
    assert shared, "finished request must leave its full blocks indexed"
    before = svc._cache.snapshot_blocks(shared)
    assert set(before) == {"k", "v", "k_scale", "v_scale"}
    b = svc.generate(prompt, timeout=180)              # hit -> speculate
    after = svc._cache.snapshot_blocks(shared)
    for name in before:
        np.testing.assert_array_equal(
            before[name], after[name],
            err_msg=f"shared {name} blocks mutated by a speculating sharer")
    c = svc.generate(prompt, timeout=180)
    stats = svc.stats()
    svc.stop()
    assert a == b == c
    assert stats["speculative"]["spec_steps"] >= 1
    assert stats["prefix_cache"]["hits"] >= 2


# -- zero post-warmup recompiles ----------------------------------------------------
def test_zero_recompiles_speculative_under_freeze(params, monkeypatch):
    """Warmup enumerates the verify (Tk, W) ladder; a mixed speculative
    workload then runs under TPUMX_FREEZE_COMPILES=1 with one miss per
    signature."""
    svc = GenerationService(
        params, CFG, _gc(max_slots=3, speculative=True, draft_k=4),
        start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    kinds = {k[0] for k in svc.compile_stats()}
    assert "gen_verify" in kinds
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    svc.start()
    handles = []
    rs = np.random.RandomState(5)
    for i in range(6):
        p = REP[i % len(REP)] if i % 2 == 0 \
            else rs.randint(0, CFG.vocab, 5 + 3 * i)
        handles.append(svc.submit(p, max_new_tokens=4 + (i % 4),
                                  temperature=0.5 * (i % 2), seed=i))
        if i % 2 == 0:
            time.sleep(0.01)
    for h in handles:
        h.result(180)
    stats = svc.compile_stats()
    svc.stop()
    for key, st in stats.items():
        assert st["misses"] == 1, f"recompile at {key}: {st}"
    assert sum(st["hits"] for k, st in stats.items()
               if k[0].startswith("gen_verify")) >= 1


def test_zero_recompiles_draft_model_under_freeze(params, monkeypatch):
    """The draft proposer is one frozen program too: model-mode
    speculation post-warmup never compiles."""
    svc = GenerationService(
        params, CFG,
        _gc(speculative=True, draft_mode="model", draft_k=3,
            draft_window=32),
        start=False, draft_params=params, draft_cfg=CFG)
    svc.warmup()
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    svc.start()
    outs = [svc.generate(p, max_new_tokens=6, timeout=180)
            for p in (np.array([4, 7, 1, 9, 2, 6]), np.array([12, 3, 5]))]
    dstats = svc._draft.compile_stats()
    stats = svc.compile_stats()
    svc.stop()
    assert all(o for o in outs)
    for key, st in list(stats.items()) + list(dstats.items()):
        assert st["misses"] == 1, f"recompile at {key}: {st}"
    assert sum(st["hits"] for st in dstats.values()) >= 1


# -- gate off: byte identity --------------------------------------------------------
def test_speculative_off_is_byte_identical(params, monkeypatch):
    """TPUMX_GEN_SPECULATIVE=0 (the default) keeps the engine's program
    set, growth arithmetic and tokens exactly as before the feature:
    no verify/draft signatures exist, the reserve span is 1,
    and the dispatcher runs the classic single-token step."""
    monkeypatch.setenv("TPUMX_GEN_SPECULATIVE", "0")
    cfg = _gc()
    assert cfg.speculative is False
    monkeypatch.delenv("TPUMX_GEN_SPECULATIVE")
    svc = GenerationService(params, CFG, cfg, start=False)
    assert svc._verify_buckets == []
    assert svc._iter_span == 1 and svc._draft is None
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    kinds = {k[0] for k in svc.compile_stats()}
    assert kinds.isdisjoint({"gen_verify", "gen_draft"})
    svc.start()
    outs = [svc.generate(p, max_new_tokens=6, timeout=180) for p in REP]
    stats = svc.stats()
    svc.stop()
    for p, got in zip(REP, outs):
        assert got == greedy_oracle(params, p, 6)
    assert stats["decode_mode"] == "single"
    assert stats["speculative"] is None
    assert stats["counts"]["spec_steps"] == 0


def test_env_gates_parse(monkeypatch):
    monkeypatch.setenv("TPUMX_GEN_SPECULATIVE", "1")
    monkeypatch.setenv("TPUMX_GEN_DRAFT_MODE", "ngram")
    monkeypatch.setenv("TPUMX_GEN_DRAFT_K", "6")
    monkeypatch.setenv("TPUMX_GEN_DRAFT_NGRAM", "2")
    monkeypatch.setenv("TPUMX_GEN_DRAFT_WINDOW", "24")
    cfg = _gc()
    assert cfg.speculative is True and cfg.draft_mode == "ngram"
    assert cfg.draft_k == 6 and cfg.draft_ngram == 2
    assert cfg.draft_window == 24
    assert "speculative=True" in repr(cfg)
    with pytest.raises(ValueError):
        _gc(speculative=True, draft_k=0)

"""The SambaY model (parallel/sambay_lm.py) through the generation engine and
its cache of THREE kinds — a slot's state, a window ring and one shared full
pool — against the plain reference (perfbench/reference/phi4_flash.py: the
whole sequence at once, the scan position by position, no cache, no state,
no skip) on seeded weights, at a tiny preset on the CPU: d 64, 8 query
heads over 4 KV heads of 8 (2 KV pairs 16 wide), a feed-forward layer of
128, 128 channels of 16 state entries, window 8, blocks of 4, vocabulary
97, 10 layers in the published layout scaled: 4 of the self-decoder, the
last Mamba, the full layer, 2 cross-decoder pairs.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerance is that of float32 sums taken in
another order (the chunked scan carries a state the reference never cuts;
attention is summed over gathered pages): 2e-4 on logits whose spread is
about 1.  Each planted fault misses it by more than a hundred times, the
reference in bfloat16 by more than fifty.
"""
import logging

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.parallel import sambay_lm as sl
from mxnet_tpu.serving.bucketing import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import (PagedKVCache, blocks_for,
                                                   window_blocks)
from oracle import greedy
from perfbench.reference import phi4_flash as ref

KINDS = ("ssm", "swa", "ssm", "swa", "ssm", "full", "gmu", "cross", "gmu",
         "cross")
C = dict(vocab_size=97, hidden_size=64, intermediate_size=128,
         num_hidden_layers=len(KINDS), num_attention_heads=8,
         num_key_value_heads=4, sliding_window=8, layer_norm_eps=1e-5,
         layer_kinds=list(KINDS), assumed_values=dict(dt_rank=4))
MAX_LEN, V, SLOTS, BS, WIN = 256, 97, 4, 4, 8
TOL = 2e-4      # float32 sums in another order, logits of spread ~1


def _config():
    return sl.SambaYConfig(
        max_position_embeddings=MAX_LEN, layer_kinds=KINDS, dt_rank=4,
        **{k: v for k, v in C.items()
           if k not in ("layer_kinds", "assumed_values")})


def _model(**kw):
    kw.setdefault("longest_chunk", 16)
    return sl.SambaYLM(_config(), max_len=MAX_LEN, kv_dtype=jnp.float32,
                       **kw)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


def _service(params, kernel="gather", model=None, **kw):
    gc = dict(max_slots=SLOTS, block_size=BS, num_blocks=256,
              seq_buckets=[8, 16, 200])
    gc.update(kw)
    with pytest.MonkeyPatch.context() as mp:
        # read once, when the service is made
        mp.setenv("TPUMX_PALLAS", "1" if kernel == "paged" else "0")
        return GenerationService(params, model or _model(),
                                 GenerationConfig(**gc), start=False)


@pytest.fixture(scope="module")
def svc(params):
    """ONE service for every test that does not need its own (a test that
    needs the engine running starts it, and leaves it idle)."""
    made = _service(params)
    yield made
    made.stop(drain=False, timeout=30)


def _ref_logits(params, tokens, at0, n_at=1, **kw):
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(ref.logits(params, C, toks, len(tokens), at0, n_at,
                                 **kw))


def _ref_greedy(params, prompt, n):
    return greedy(lambda seq: _ref_logits(params, seq, len(seq) - 1)[0],
                  prompt, n)


def _sampler(n, counter):
    z = np.zeros(n, np.int32)
    return (z.astype(np.uint32), np.full(n, counter, np.uint32),
            z.astype(np.float32), z, np.ones(n, np.float32))


class _Row:
    """What the engine's code for the kinds behind the first reads of a
    request."""
    rid, wins = -1, None


def _prefill(svc, toks, blocks, row):
    """``toks`` through the engine's chunk plan as the engine runs it (every
    chunk but the last through the fill program where the model skips);
    the last chunk's sampled token and last-position logits."""
    n = len(toks)
    for off, take, tb, wp in svc._chunk_plan(n):
        table = np.zeros((1, wp), np.int32)
        table[0, :min(wp, len(blocks))] = blocks[:wp]
        svc._slide(row, off, off + take)
        args = (pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                                 tb)[None, :],
                np.arange(off, off + tb, dtype=np.int32)[None, :],
                np.asarray([take], np.int32),
                (table, *svc._ring_tables([(0, row)], 1, tb)))
        if svc._fills and off + take < n:
            svc._programs.run_fill(svc._cache, *args)
        else:
            nxt, last = svc._programs.run("gen_prefill", svc._cache, *args,
                                          *_sampler(1, n))
        svc._slide(row, off + take, off + take)
    return int(nxt[0]), np.asarray(last[0])


def _decode(svc, at, tok, pos, blocks, row):
    """One decode step with the row at batch index ``at``: every other row
    is idle."""
    w = svc._width_buckets[-1]
    tokens = np.zeros((SLOTS, 1), np.int32)
    positions = np.zeros((SLOTS, 1), np.int32)
    lengths = np.zeros(SLOTS, np.int32)
    table = np.zeros((SLOTS, w), np.int32)
    tokens[at, 0], positions[at, 0], lengths[at] = tok, pos, 1
    table[at, :min(w, len(blocks))] = blocks[:w]
    svc._slide(row, pos, pos + 1)
    nxt, last = svc._programs.run(
        "gen_decode", svc._cache, tokens, positions, lengths,
        (table, *svc._ring_tables([(at, row)], SLOTS, 1)),
        *_sampler(SLOTS, pos + 1))
    return int(nxt[at]), np.asarray(last[at])


def _logits_through_the_cache(svc, seq, n_decode=4, row=None):
    """Last-position logits of the prefill of ``seq`` and of ``n_decode``
    greedy steps behind it, with the sequences they belong to."""
    seq = list(seq)
    row = row or _Row()
    blocks = svc._alloc_reclaiming(blocks_for(len(seq) + n_decode + 1, BS))
    nxt, last = _prefill(svc, seq, blocks, row)
    out = [(list(seq), last)]
    for _ in range(n_decode):
        seq.append(nxt)
        nxt, last = _decode(svc, 2, seq[-1], len(seq) - 1, blocks, row)
        out.append((list(seq), last))
    svc._drop_windows(row)
    svc._cache.allocator.free(blocks)
    svc._programs.take_aux()
    return out


def _prompt(plen, seed=None):
    rng = np.random.default_rng(plen if seed is None else seed)
    return [int(t) for t in rng.integers(0, V, plen)]


def test_the_reference_makes_the_models_parameters(params):
    assert {k: tuple(v.shape) for k, v in params.items()} == \
        sl.sambay_param_shapes(_config())
    assert ref.layer_kinds(C) == _config().kinds == KINDS


def test_the_published_layout_and_its_parameter_count():
    """32 layers: Mamba on the even ones up to 16, windows on the odd ones
    up to 15, the full layer at 17, then GMUs and cross attention; 3.85 B
    parameters (the published "3.8B") from the shapes alone."""
    cfg = sl.SambaYConfig()
    assert cfg.layers_of("ssm") == (0, 2, 4, 6, 8, 10, 12, 14, 16)
    assert cfg.layers_of("swa") == (1, 3, 5, 7, 9, 11, 13, 15)
    assert cfg.layers_of("full") == (17,)
    assert cfg.layers_of("cross") == (19, 21, 23, 25, 27, 29, 31)
    assert cfg.layers_of("gmu") == (18, 20, 22, 24, 26, 28, 30)
    assert (cfg.head_dim, cfg.d_inner, cfg.rank) == (64, 5120, 160)
    n = sum(int(np.prod(s)) for s in sl.sambay_param_shapes(cfg).values())
    assert abs(n / 3.85e9 - 1) < 0.01, n
    assert abs(cfg.lam0(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12


@pytest.mark.parametrize("part", ["prefill", "decode"])
@pytest.mark.parametrize("plen", [3, 16, 37, 70, 141])
def test_chunked_prefill_then_decode_match_reference_logits(svc, params,
                                                            plen, part):
    """Prefill through the engine's chunk plan (chunks of 16, a leftover of
    every kind: none, under a rung, over it; fill programs and a last
    chunk) then one-token steps through the three kinds, a window crossed,
    every other row of the batch idle: the reference's full forward at
    every compared position."""
    compared = _logits_through_the_cache(svc, _prompt(plen),
                                         0 if part == "prefill" else 4)
    for toks, last in compared[0 if part == "prefill" else 1:]:
        np.testing.assert_allclose(
            last, _ref_logits(params, toks, len(toks) - 1)[0], atol=TOL,
            rtol=0)


def test_with_and_without_the_prefill_skip_the_logits_agree(svc, params):
    """The cross-decoder at a prompt's last position alone, or every layer
    at every position: the same last-position logits to rounding, and the
    same K and V of the full layer behind them (the decode steps agree)."""
    whole = _service(params, model=_model(prefill_skip=False))
    assert svc._fills and not whole._fills
    seq = _prompt(53)
    for (_, a), (_, b) in zip(_logits_through_the_cache(svc, seq, 3),
                              _logits_through_the_cache(whole, seq, 3)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=0)
    # five programs a table width where the model skips: fill and
    # prefill-final a rung, and decode
    kinds = [k[0] for k in svc._programs.compile_stats()]
    assert "gen_fill" in kinds
    assert "gen_fill" not in [k[0] for k in whole._programs.compile_stats()]


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_the_references_planted_faults_move_its_logits(params, fault):
    seq = _prompt(70)
    sound = _ref_logits(params, seq, len(seq) - 1)[0]
    moved = _ref_logits(params, seq, len(seq) - 1, fault=fault)[0]
    assert np.abs(moved - sound).max() > 100 * TOL


def test_one_precision_down_is_outside_the_tolerance(params):
    seq = _prompt(70)
    sound = _ref_logits(params, seq, len(seq) - 1)[0]
    low = _ref_logits(params, seq, len(seq) - 1, dtype="bfloat16")[0]
    assert np.abs(low - sound).max() > 50 * TOL


def test_cache_is_built_from_the_models_three_kinds(svc):
    """``full`` under the cache's own allocator and ``num_blocks``; the
    window kind and the state kind behind it, each with an allocator of its
    own, sized by slots; one tuple of pools, kind after kind; the full
    kind's ONE pool row is the writer's and every cross layer's."""
    cache = svc._cache
    full, window, state = cache.kinds
    assert [k.name for k in cache.kinds] == ["full", "window", "state"]
    assert (full.n_layers, window.n_layers, state.n_layers) == (1, 2, 3)
    assert full.allocator is cache.allocator and full.num_blocks == 256
    assert window.window == WIN and not window.state and state.state
    assert window.num_blocks == 1 + SLOTS * window_blocks(WIN, 1, BS) \
        + window_blocks(WIN, 16, BS)
    assert state.num_blocks == SLOTS + 1
    assert [tuple(p.shape) for p in cache.pools] == [
        (1, 256, BS, 32), (1, 256, BS, 32),
        (2, window.num_blocks, BS, 32), (2, window.num_blocks, BS, 32),
        (3, SLOTS + 1, 16 + 8, 128)]
    assert cache.pools[4].dtype == jnp.float32
    assert (full.span, window.span, state.span) == (
        slice(0, 2), slice(2, 4), slice(4, 5))
    # one writer, many readers: a reader's pool row is the writer's
    assert full.writers == (5,) and full.readers == (5, 7, 9)
    assert [full.pool_row(i) for i in full.readers] == [0, 0, 0]
    assert [window.pool_row(i) for i in (1, 3)] == [0, 1]
    assert [state.pool_row(i) for i in (0, 2, 4)] == [0, 1, 2]
    with pytest.raises(ValueError, match="reads no row"):
        full.pool_row(6)
    assert (full.blocks_for(9), state.blocks_for(900)) == (3, 1)


def test_what_a_spec_may_not_combine():
    kv = (("k", 8), ("v", 8))
    state = dict(name="state", n_layers=1, state=(("s", (8, 128)),))
    with pytest.raises(ValueError, match="first cache kind keeps every"):
        PagedKVCache(num_blocks=8, block_size=4, window_rows=(2, 8),
                     kinds=(state, dict(name="full", n_layers=1, pools=kv)))
    with pytest.raises(ValueError, match="window kind or a slot's state"):
        PagedKVCache(num_blocks=8, block_size=4, window_rows=(2, 8), kinds=(
            dict(name="full", n_layers=1, pools=kv),
            dict(name="more", n_layers=1, pools=kv)))
    with pytest.raises(ValueError, match="writers for"):
        PagedKVCache(num_blocks=8, block_size=4, window_rows=(2, 8), kinds=(
            dict(name="full", n_layers=1, pools=kv, writers=(1, 2)),))
    # a state kind alone is still a cache (a recurrent model's)
    alone = PagedKVCache(block_size=4, window_rows=(2, 8), kinds=(state,))
    assert alone.kinds[0].state and alone.num_blocks == 3


def test_an_idle_row_and_a_padded_position_leave_the_state_as_it_was(svc):
    """A decode step feeds one row: the other slots' states are bit-equal
    behind it, and so is the fed row's after a chunk of padding alone."""
    row, blocks = _Row(), svc._alloc_reclaiming(8)
    _prefill(svc, _prompt(13), blocks, row)
    other = _Row()
    svc._slide(other, 0, 1)
    mine, theirs = row.wins[1][1][0], other.wins[1][1][0]
    assert mine != theirs and 0 not in (mine, theirs)
    before = np.asarray(svc._cache.pools[4])
    _decode(svc, 1, 5, 13, blocks, row)
    after = np.asarray(svc._cache.pools[4])
    assert np.array_equal(before[:, theirs], after[:, theirs])
    assert not np.array_equal(before[:, mine, :16], after[:, mine, :16])
    for r in (row, other):
        svc._drop_windows(r)
    svc._cache.allocator.free(blocks)
    svc._programs.take_aux()


def test_a_reused_slot_starts_from_zero_inside_the_program(svc, params):
    """A chunk at position 0 starts from the zero state whatever its slot
    held: the logits behind a throw-away prompt in the same slot are those
    of a service nothing ran on, bit for bit."""
    fresh = _service(params)
    seq = _prompt(29)
    want = _logits_through_the_cache(fresh, seq, 2)
    row = _Row()
    _logits_through_the_cache(svc, _prompt(45, seed=9), 1, row=row)
    assert row.wins is None
    got = _logits_through_the_cache(svc, seq, 2)
    for (_, a), (_, b) in zip(got, want):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("plen", [5, 21, 47])
def test_service_generation_matches_reference_greedy(params, svc, plen):
    svc.start()
    prompt = _prompt(plen, seed=100 + plen)
    assert svc.generate(prompt, max_new_tokens=12, timeout=300) == \
        _ref_greedy(params, prompt, 12)
    assert all(k.allocator.num_used == 0 for k in svc._cache.kinds)


def test_admitting_passes_carry_first_tokens_and_serve_the_reference(
        params, svc):
    """Ten clients on four slots with outputs of 2 to 5 tokens, so that
    most passes admit, prompts of one chunk and of several: every first
    token stays on the device for the decode step of the pass that
    admitted its row (docs/generation.md "The step in flight"), the fill program
    running every chunk but a prompt's last,
    and every request is served the reference's tokens."""
    svc.start()
    before = svc.stats()["counts"]
    rng = np.random.default_rng(45)
    prompts = [[int(t) for t in rng.integers(0, V, n)]
               for n in (5, 41, 8, 23, 3, 70, 16, 33, 7, 19)]
    news = (3, 2, 5, 4, 2, 3, 5, 2, 4, 3)
    streams = [svc.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    for st, p, n in zip(streams, prompts, news):
        assert st.result(300) == _ref_greedy(params, p, n)
    after = svc.stats()["counts"]
    ahead = after["prefills_ahead"] - before["prefills_ahead"]
    read = after["prefills_read"] - before["prefills_read"]
    # (a request that ends a pass alone has nothing to decode after it)
    assert ahead + read == 10 and ahead >= 8
    assert after["failed"] == before["failed"]


def test_admission_is_by_a_free_slot_and_by_blocks_at_once(params):
    """Two slots and blocks for one long row under the watermark: the
    second request waits for BLOCKS while a slot is free, and a third
    waits for a SLOT while blocks are free."""
    svc = _service(params, max_slots=2, num_blocks=24, watermark_high=0.9,
                   watermark_low=0.6)
    state = svc._cache.kinds[2].allocator
    long_, short = _prompt(60), _prompt(6)
    first = svc.submit(long_, max_new_tokens=4)
    second = svc.submit(_prompt(40, seed=2), max_new_tokens=4)
    svc._iterate()
    # 16 of 23 blocks are the first row's: the second (11 more) would
    # cross the watermark, so it waits though a slot is free
    assert svc.stats()["running"] == 1 and svc.stats()["waiting"] == 1
    assert state.num_used == 1 and state.num_free == 1
    while not (first.finished and second.finished):
        svc._iterate()
    a, b, c = (svc.submit(short, max_new_tokens=8) for _ in range(3))
    svc._iterate()
    # blocks are plenty now: the third waits for a slot (and its state)
    assert svc.stats()["running"] == 2 and svc.stats()["waiting"] == 1
    assert state.num_used == 2 and state.num_free == 0
    assert svc._cache.allocator.num_free > 10
    while not (a.finished and b.finished and c.finished):
        svc._iterate()
    assert a.result(1) == b.result(1) == c.result(1) == \
        _ref_greedy(params, short, 8)
    assert first.result(1) == _ref_greedy(params, long_, 4)
    assert all(k.allocator.num_used == 0 for k in svc._cache.kinds)
    svc.stop(drain=False, timeout=30)


def test_preemption_gives_all_three_kinds_back_and_resumes(params):
    """A preempted row gives back its blocks of both paged kinds and its
    state; the state has no snapshot, so its resume re-prefills every token
    from the zero state — through the skipping prefill — and serves the
    tokens an undisturbed run serves."""
    svc = _service(params)
    full, window, state = (k.allocator for k in svc._cache.kinds)
    prompt = _prompt(21, seed=11)
    stream = svc.submit(prompt, max_new_tokens=30)
    for _ in range(12):
        svc._iterate()
    r = stream._req
    svc._land()
    assert window.num_used > 0 and state.num_used == 1 and full.num_used > 0
    with svc._lock:
        svc._preempt_slot_locked(svc._slots.index(r))
    assert (full.num_used, window.num_used, state.num_used) == (0, 0, 0)
    assert r.wins is None and r.blocks is None
    ctx = r.ctx_len
    before = svc.stats()["counts"]["prefill_tokens"]
    while not stream.finished:
        svc._iterate()
    assert stream.result(1) == _ref_greedy(params, prompt, 30)
    counts = svc.stats()["counts"]
    assert counts["preempted"] == 1
    # every token of the context went through prefill again
    assert counts["prefill_tokens"] - before == ctx > r.prompt_len
    assert counts["ssm_rows_started"] == 2
    assert (full.num_used, window.num_used, state.num_used) == (0, 0, 0)
    svc.stop(drain=False, timeout=30)


def test_pool_pressure_preempts_and_every_request_still_matches(params):
    """The full kind is the one that runs out (it alone is sized by
    tokens): its watermark preempts, all three kinds go back, everything
    resumes."""
    svc = _service(params, num_blocks=26, watermark_high=0.9,
                   watermark_low=0.6)
    prompts = [_prompt(n, seed=21 + n) for n in (20, 18, 22, 17)]
    streams = [svc.submit(p, max_new_tokens=20) for p in prompts]
    svc.start()
    for st, p in zip(streams, prompts):
        assert st.result(300) == _ref_greedy(params, p, 20)
    assert svc.stats()["counts"]["preempted"] >= 1
    assert all(k.allocator.num_used == 0 for k in svc._cache.kinds)
    svc.stop(drain=False, timeout=30)


def test_the_prefix_cache_is_declined_and_the_service_says_so(params,
                                                              caplog):
    with caplog.at_level(logging.INFO,
                         logger="mxnet_tpu.serving.generation.engine"):
        made = _service(params, prefix_cache=True)
    assert "no prefix reuse" in caplog.text
    assert "['window']" in caplog.text and "['state']" in caplog.text
    assert made._prefix is None and made.stats()["prefix_cache"] is None
    for what in ("speculative", "amp_dtype", "kv_dtype", "mp_devices"):
        with pytest.raises(ValueError, match="does not offer"):
            _service(params, **{what: {"speculative": True, "amp_dtype":
                                       "bfloat16", "kv_dtype": "int8",
                                       "mp_devices": 2}[what]})


def test_the_programs_counts_and_the_gauges_reach_stats(params):
    """``aux`` of every fill program, last chunk and decode step, summed
    once its step's tokens were read; the slot gauge beside the block
    gauges, in ``stats()`` and in the registry."""
    from mxnet_tpu import observability as obs

    svc = _service(params)
    svc.start()
    svc.generate(np.arange(21), max_new_tokens=6, timeout=300)
    st = svc.stats()
    counts = st["counts"]
    assert svc._runs_ahead and counts["steps_ahead"] >= 1
    # a chunk of 16 through the fill program and a last chunk of 5
    assert counts["ssm_prefill_tokens"] == 21
    assert counts["ssm_prefill_chunks"] == 2
    assert counts["ssm_rows_started"] == 1
    assert counts["cross_positions_run"] == 1
    assert counts["cross_positions_skipped"] == 20
    assert counts["window_prefill_pairs"] == sum(
        min(p, WIN) for p in range(1, 22))
    # the full layer attends at the prompt's last position alone
    assert counts["full_prefill_pairs"] == 21
    # decode steps at contexts 21..25 (the sixth token needs no sixth step
    # read; a step dispatched ahead of the end is dropped unread)
    assert counts["ssm_decode_rows"] == 5
    assert counts["full_ctx_tokens"] == sum(range(22, 27))
    assert counts["window_ctx_tokens"] == 5 * WIN
    assert counts["window_decode_trips"] == 0       # no kernel, no trip
    assert counts["window_blocks_freed"] >= 3
    # one sampling program a prompt (its last chunk) and a decode step
    assert counts["sampler_steps_greedy"] >= 1 + 5
    assert counts["state_slots_live"] == 0
    assert counts["state_bytes_per_slot"] == 3 * 24 * 128 * 4
    assert st["cache_kinds"]["state"] == {
        "layers": 3, "window": 0, "total": SLOTS, "used": 0, "free": SLOTS}
    assert st["cache_kinds"]["window"]["total"] == 23
    assert st["cache_kinds"]["full"]["total"] == 255
    text = obs.registry().to_prometheus()
    for kind in ("full", "window", "state"):
        assert f'generation_kv_kind_blocks_used{{kind="{kind}"}}' in text
    svc.stop(drain=False, timeout=30)


def test_warmup_covers_every_program_the_traffic_needs(params,
                                                       no_compile_cache):
    """Decode a table width, and a fill and a last-chunk program a (rung,
    width): nothing compiles once traffic runs, preemption's resumes
    among it."""
    from mxnet_tpu.executor import compile_cache_stats

    svc = _service(params, seq_buckets=[8, 16, 40],
                   model=sl.SambaYLM(_config(), max_len=64,
                                     kv_dtype=jnp.float32, longest_chunk=16))
    sigs = svc._prefill_signatures()
    assert svc.warmup() == 2 * len(sigs) + len(svc._width_buckets)
    before = compile_cache_stats()["misses"]
    svc.start()
    for plen in (3, 16, 23, 40):
        svc.generate(_prompt(plen), max_new_tokens=5, timeout=300)
    assert compile_cache_stats()["misses"] == before
    svc.stop(drain=False, timeout=30)


@pytest.fixture(scope="module")
def paged(params):
    """``TPUMX_PALLAS=1``: the scan's two calls, the convolution's read and
    the tiles body through the interpreter (minutes to warm: shared)."""
    made = _service(params, kernel="paged", seq_buckets=[8, 16, 200])
    yield made
    made.stop(drain=False, timeout=30)


def test_the_kernels_behind_the_service_serve_the_references_tokens(params,
                                                                    paged):
    """ONE table width: five programs (decode, a fill and a last chunk a
    rung)."""
    assert paged.stats()["decode_kernel"] == "paged"
    assert len(paged._width_buckets) == 1
    assert paged.warmup() == 5
    for plen in (3, 37):
        got = _logits_through_the_cache(paged, _prompt(plen), 2)
        for toks, last in got:
            np.testing.assert_allclose(
                last, _ref_logits(params, toks, len(toks) - 1)[0], atol=TOL,
                rtol=0)


def test_the_window_calls_trips_reach_stats_one_a_row_a_layer(paged):
    """``window_decode_trips``: the tiles body's trips over the window
    layers' decode calls, counted by the program from the call's own
    geometry — one a live row a window layer a step."""
    paged.start()
    paged.generate(np.arange(21), max_new_tokens=6, timeout=600)
    counts = paged.stats()["counts"]
    assert counts["ssm_decode_rows"] >= 5
    assert counts["window_decode_trips"] == \
        _config().kinds.count("swa") * counts["ssm_decode_rows"]

"""The Granite 4.0-H model (parallel/granite_hybrid.py) through the
generation engine and its cache of TWO kinds — the attention layers' paged K
and V and, the larger, a slot's Mamba-2 states — against the plain reference
(perfbench/reference/granite_hybrid.py: the whole sequence at once, the scan
ONE recurrence position by position, no cache, no state, no chunk) on seeded
weights, at a tiny preset on the CPU: d 64, 8 query heads over 2 KV heads of
8, a feed-forward layer of 128, 4 Mamba-2 heads of 32 over 16 state entries,
blocks of 4, vocabulary 97, 6 layers of which the third and the last are
attention.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerance is that of float32 sums taken in
another order (the chunked scan carries a state the reference never cuts;
attention is summed over gathered pages), RELATIVE to the logits' standard
deviation (~0.01: the tied embedding is small, reference/granite_hybrid.py
``init_params``).  Each planted fault misses it by more than a hundred
times, the reference in bfloat16 by more than fifty.
"""
import logging

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.parallel import granite_hybrid as gh
from mxnet_tpu.serving.bucketing import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import blocks_for
from oracle import greedy
from perfbench.reference import granite_hybrid as ref

KINDS = ("mamba", "mamba", "attention", "mamba", "mamba", "attention")
C = dict(vocab_size=97, hidden_size=64, shared_intermediate_size=128,
         num_hidden_layers=len(KINDS), num_attention_heads=8,
         num_key_value_heads=2, rms_norm_eps=1e-5, layer_types=list(KINDS),
         mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
         mamba_expand=2, mamba_n_groups=1, attention_multiplier=0.125,
         embedding_multiplier=12.0, residual_multiplier=0.22,
         logits_scaling=8.0, service={"seq_buckets": [8, 16, 200]})
MAX_LEN, V, SLOTS, BS = 256, 97, 4, 4
TOL = 3e-4      # float32 sums in another order, of the logits' deviation


def _config():
    return gh.GraniteHybridConfig(
        max_position_embeddings=MAX_LEN, layer_types=KINDS,
        **{k: v for k, v in C.items() if k not in ("layer_types", "service")})


def _model(**kw):
    kw.setdefault("longest_chunk", 16)
    return gh.GraniteHybridLM(_config(), max_len=MAX_LEN,
                              kv_dtype=jnp.float32, **kw)


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


def _off(got, want):
    """The largest difference over the reference's logits' deviation."""
    return float(np.abs(np.asarray(got) - want).max() / want.std())


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
    chunk but the last through the fill program, which has no head); the
    last chunk's sampled token and last-position logits."""
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


def _decode(svc, rows):
    """One decode step of ``rows`` — ``(batch index, token, position,
    blocks, row)`` each —, every other row of the batch idle."""
    w = svc._width_buckets[-1]
    tokens = np.zeros((SLOTS, 1), np.int32)
    positions = np.zeros((SLOTS, 1), np.int32)
    lengths = np.zeros(SLOTS, np.int32)
    counters = np.zeros(SLOTS, np.uint32)
    table = np.zeros((SLOTS, w), np.int32)
    for at, tok, pos, blocks, row in rows:
        tokens[at, 0], positions[at, 0], lengths[at] = tok, pos, 1
        counters[at] = pos + 1
        table[at, :min(w, len(blocks))] = blocks[:w]
        svc._slide(row, pos, pos + 1)
    z = np.zeros(SLOTS, np.int32)
    nxt, last = svc._programs.run(
        "gen_decode", svc._cache, tokens, positions, lengths,
        (table, *svc._ring_tables([(r[0], r[4]) for r in rows], SLOTS, 1)),
        z.astype(np.uint32), counters, z.astype(np.float32), z,
        np.ones(SLOTS, np.float32))
    return np.asarray(nxt), np.asarray(last)


def _logits_through_the_cache(svc, seqs, n_decode=4, at=(2, 0)):
    """Last-position logits of the prefills of ``seqs`` (one or two
    sequences, of unlike length) and of ``n_decode`` greedy steps behind
    them IN ONE BATCH, with the sequences they belong to."""
    seqs = [list(s) for s in seqs]
    rows = [_Row() for _ in seqs]
    tables = [svc._alloc_reclaiming(blocks_for(len(s) + n_decode + 1, BS))
              for s in seqs]
    out, nxt = [], []
    for seq, blocks, row in zip(seqs, tables, rows):
        tok, last = _prefill(svc, seq, blocks, row)
        out.append((list(seq), last))
        nxt.append(tok)
    for _ in range(n_decode):
        for seq, tok in zip(seqs, nxt):
            seq.append(tok)
        toks, last = _decode(svc, [
            (i, seq[-1], len(seq) - 1, blocks, row)
            for i, seq, blocks, row in zip(at, seqs, tables, rows)])
        nxt = [int(toks[i]) for i in at[:len(seqs)]]
        out += [(list(seq), last[i]) for i, seq in zip(at, seqs)]
    for blocks, row in zip(tables, rows):
        svc._drop_windows(row)
        svc._cache.allocator.free(blocks)
    svc._programs.take_aux()
    return out


def _prompt(plen, seed=None):
    rng = np.random.default_rng(plen if seed is None else seed)
    return [int(t) for t in rng.integers(0, V, plen)]


def test_the_reference_makes_the_models_parameters(params):
    assert {k: tuple(v.shape) for k, v in params.items()} == \
        gh.granite_hybrid_param_shapes(_config())
    assert ref.layer_types(C) == _config().kinds == KINDS


def test_the_published_layout_and_its_parameter_count():
    """40 layers, attention at 5, 15, 25 and 35; 3,191,396,096 parameters
    (the published "3B") from the shapes alone: a Mamba-2 mixer 25.85 M, an
    attention mixer 10.49 M, the feed-forward 50.33 M, the tied embedding
    205.5 M."""
    cfg = gh.GraniteHybridConfig()
    assert cfg.layers_of("attention") == (5, 15, 25, 35)
    assert len(cfg.layers_of("mamba")) == 36
    assert (cfg.head_dim, cfg.d_inner, cfg.d_conv_in) == (64, 4096, 4352)
    shapes = gh.granite_hybrid_param_shapes(cfg)
    def size(of):
        return sum(int(np.prod(s)) for k, s in shapes.items()
                   if k.startswith(of))

    assert size("") == 3191396096
    assert size("l0_") == 76182976 and size("l5_") == 60821504
    assert size("l0_w_in") == 17432576 and size("l0_w_out") == 8388608
    assert size("l5_w") - size("l5_w_") == 10485760     # wq, wk, wv, wo
    assert size("l0_w_up") + size("l0_w_down") == 50331648
    assert size("tok_emb") == 205520896


@pytest.mark.parametrize("part", ["prefill", "decode"])
@pytest.mark.parametrize("plen", [3, 16, 37, 70, 141])
def test_chunked_prefill_then_decode_match_reference_logits(svc, params,
                                                            plen, part):
    """Prefill through the engine's chunk plan (chunks of 16, a leftover of
    every kind: none, under a rung, over it; fill programs and a last
    chunk: several chunk plans) then one-token steps through both kinds,
    every other row of the batch idle: the reference's full forward at
    every compared position."""
    compared = _logits_through_the_cache(svc, [_prompt(plen)],
                                         0 if part == "prefill" else 4)
    for toks, last in compared[0 if part == "prefill" else 1:]:
        assert _off(last, _ref_logits(params, toks, len(toks) - 1)[0]) < TOL


@pytest.mark.parametrize("chunk", [8, 200])
def test_another_chunk_plan_gives_the_same_logits(params, chunk):
    """Chunks of 8, and the whole prompt as ONE chunk: the same logits as
    the reference, to rounding."""
    made = _service(params, model=_model(longest_chunk=chunk))
    for toks, last in _logits_through_the_cache(made, [_prompt(53)], 2):
        assert _off(last, _ref_logits(params, toks, len(toks) - 1)[0]) < TOL


def test_two_requests_of_unlike_length_in_one_batch(svc, params):
    seqs = [_prompt(9, seed=1), _prompt(75, seed=2)]
    for toks, last in _logits_through_the_cache(svc, seqs, 3):
        assert _off(last, _ref_logits(params, toks, len(toks) - 1)[0]) < TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_the_references_planted_faults_move_its_logits(params, fault):
    """A state not carried across a chunk boundary, the residual multiplier
    dropped, ``1/sqrt(head size)`` for the attention multiplier, the norm
    before the gate: each moves the last position's logits by more than a
    hundred tolerances."""
    seq = _prompt(70)
    sound = _ref_logits(params, seq, len(seq) - 1)[0]
    moved = _ref_logits(params, seq, len(seq) - 1, fault=fault)[0]
    assert _off(moved, sound) > 100 * TOL


def test_one_precision_down_is_outside_the_tolerance(params):
    seq = _prompt(70)
    sound = _ref_logits(params, seq, len(seq) - 1)[0]
    low = _ref_logits(params, seq, len(seq) - 1, dtype="bfloat16")[0]
    assert _off(low, sound) > 50 * TOL


def test_cache_is_built_from_the_models_two_kinds(svc):
    """``full`` under the cache's own allocator and ``num_blocks``; the
    state kind behind it with an allocator of its own, sized by slots, and
    the LARGER of the two a slot: one tuple of pools, kind after kind."""
    cache = svc._cache
    full, state = cache.kinds
    assert [k.name for k in cache.kinds] == ["full", "state"]
    assert (full.n_layers, state.n_layers) == (2, 4)
    assert full.allocator is cache.allocator and full.num_blocks == 256
    assert state.state and not full.state and state.window == 0
    assert state.num_blocks == SLOTS + 1
    assert [tuple(p.shape) for p in cache.pools] == [
        (2, 256, BS, 16), (2, 256, BS, 16), (4, SLOTS + 1, 16 + 8, 128)]
    assert cache.pools[2].dtype == jnp.float32
    assert (full.span, state.span) == (slice(0, 2), slice(2, 3))
    assert (full.writers, state.writers) == ((2, 5), (0, 1, 3, 4))
    assert [state.pool_row(i) for i in (0, 1, 3, 4)] == [0, 1, 2, 3]
    assert (full.blocks_for(9), state.blocks_for(900)) == (3, 1)
    # a slot's state against a token's K and V: 49,152 B to 256
    assert svc.stats()["counts"]["state_bytes_per_slot"] == 4 * 24 * 128 * 4


def test_an_idle_row_and_a_padded_position_leave_the_state_as_it_was(svc):
    """A decode step feeds one row: the other slots' states are bit-equal
    behind it (and the scratch, which the idle rows point at)."""
    row, blocks = _Row(), svc._alloc_reclaiming(8)
    _prefill(svc, _prompt(13), blocks, row)
    other = _Row()
    svc._slide(other, 0, 1)
    mine, theirs = row.wins[0][1][0], other.wins[0][1][0]
    assert mine != theirs and 0 not in (mine, theirs)
    before = np.asarray(svc._cache.pools[2])
    _decode(svc, [(1, 5, 13, blocks, row)])
    after = np.asarray(svc._cache.pools[2])
    assert np.array_equal(before[:, theirs], after[:, theirs])
    assert np.array_equal(before[:, 0, :16], after[:, 0, :16])
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
    want = _logits_through_the_cache(fresh, [seq], 2)
    _logits_through_the_cache(svc, [_prompt(45, seed=9)], 1)
    got = _logits_through_the_cache(svc, [seq], 2)
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
    most passes admit and every slot is reused by a second request, prompts
    of one chunk and of several: every request is served the reference's
    tokens."""
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
    assert after["ssd_rows_started"] - before["ssd_rows_started"] == 10
    assert after["failed"] == before["failed"]


def test_admission_is_by_a_free_slot_and_by_blocks_at_once(params):
    """Two slots and blocks for one long row under the watermark: the
    second request waits for BLOCKS while a slot (and its state) is free,
    and a third waits for a SLOT while blocks are free."""
    svc = _service(params, max_slots=2, num_blocks=24, watermark_high=0.9,
                   watermark_low=0.6)
    state = svc._cache.kinds[1].allocator
    long_, short = _prompt(60), _prompt(6)
    first = svc.submit(long_, max_new_tokens=4)
    second = svc.submit(_prompt(40, seed=2), max_new_tokens=4)
    svc._iterate()
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


def test_preemption_gives_both_kinds_back_and_resumes(params):
    """A preempted row gives back its blocks and its state; the state has
    no snapshot, so its resume re-prefills every token from the zero state
    and serves the tokens an undisturbed run serves."""
    svc = _service(params)
    full, state = (k.allocator for k in svc._cache.kinds)
    prompt = _prompt(21, seed=11)
    stream = svc.submit(prompt, max_new_tokens=30)
    for _ in range(12):
        svc._iterate()
    r = stream._req
    svc._land()
    assert state.num_used == 1 and full.num_used > 0
    with svc._lock:
        svc._preempt_slot_locked(svc._slots.index(r))
    assert (full.num_used, state.num_used) == (0, 0)
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
    assert counts["ssd_rows_started"] == 2
    assert (full.num_used, state.num_used) == (0, 0)
    svc.stop(drain=False, timeout=30)


def test_the_prefix_cache_is_declined_and_the_service_says_so(params,
                                                              caplog):
    with caplog.at_level(logging.INFO,
                         logger="mxnet_tpu.serving.generation.engine"):
        made = _service(params, prefix_cache=True)
    assert "no prefix reuse" in caplog.text and "['state']" in caplog.text
    assert made._prefix is None and made.stats()["prefix_cache"] is None
    for what, value in (("speculative", True), ("amp_dtype", "bfloat16"),
                        ("kv_dtype", "int8"), ("mp_devices", 2)):
        with pytest.raises(ValueError, match="does not offer"):
            _service(params, **{what: value})


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
    assert counts["ssd_prefill_tokens"] == 21
    assert counts["ssd_prefill_chunks"] == 2 == counts["prefill_chunks"]
    assert counts["ssd_rows_started"] == 1
    assert counts["full_prefill_pairs"] == sum(range(1, 22))
    # decode steps at contexts 21..25 (the sixth token needs no sixth step
    # read; a step dispatched ahead of the end is dropped unread)
    assert counts["ssd_decode_rows"] == 5
    assert counts["full_ctx_tokens"] == sum(range(22, 27))
    assert counts["state_slots_live"] == 0
    assert counts["state_bytes_per_slot"] == 4 * 24 * 128 * 4
    assert st["cache_kinds"]["state"] == {
        "layers": 4, "window": 0, "total": SLOTS, "used": 0, "free": SLOTS}
    assert st["cache_kinds"]["full"]["total"] == 255
    text = obs.registry().to_prometheus()
    for kind in ("full", "state"):
        assert f'generation_kv_kind_blocks_used{{kind="{kind}"}}' in text
    svc.stop(drain=False, timeout=30)


def test_warmup_covers_every_program_the_traffic_needs(params,
                                                       no_compile_cache):
    """Decode, and a fill and a last-chunk program a rung: nothing compiles
    once traffic runs."""
    from mxnet_tpu.executor import compile_cache_stats

    svc = _service(params, seq_buckets=[8, 16, 40],
                   model=gh.GraniteHybridLM(_config(), max_len=64,
                                            kv_dtype=jnp.float32,
                                            longest_chunk=16))
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
    the tiles body through the interpreter (slow to warm: shared)."""
    made = _service(params, kernel="paged", seq_buckets=[8, 16, 200])
    yield made
    made.stop(drain=False, timeout=30)


def test_the_kernels_behind_the_service_serve_the_references_logits(params,
                                                                    paged):
    """ONE table width: five programs (decode, a fill and a last chunk a
    rung)."""
    assert paged.stats()["decode_kernel"] == "paged"
    assert len(paged._width_buckets) == 1
    assert paged.warmup() == 5
    for plen in (3, 37):
        for toks, last in _logits_through_the_cache(paged, [_prompt(plen)],
                                                    2):
            assert _off(last, _ref_logits(params, toks,
                                          len(toks) - 1)[0]) < TOL

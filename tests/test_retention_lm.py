"""The power-retention model (parallel/retention_lm.py) through the
generation engine and its cache kind that is a slot's recurrent state,
against the plain reference (perfbench/reference/brumby.py: the ATTENTION
form, no state) on seeded weights, at a tiny preset on the CPU: d 64, 8
query heads over 2 KV heads of 8 (``phi`` is 36 entries by the mathematics,
5 rows of 8 lanes as stored), a feed-forward layer of 96, 3 layers,
vocabulary 97.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerance is that of float32 sums taken in
another order — and the order differs more than a paged model's does: the
reference sums ``exp(G_t - G_j) (q . k)^2 v_j`` over positions, the program
sums ``phi(q) . S`` over the 40 stored entries of a state that was itself
summed over positions and decayed step by step, and the entries' terms
cancel (their absolute sum is several times ``(q . k)^2``).  3e-4 on logits
whose spread is about 1; the gate left out and the normaliser left out each
miss it by more than a hundred times, the reference in bfloat16 by more
than fifty.
"""
import logging

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.parallel import retention_lm as rl
from mxnet_tpu.serving.bucketing import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import PagedKVCache
from oracle import greedy
from perfbench.reference import brumby as ref

C = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=8,
         num_key_value_heads=2, head_dim=8, intermediate_size=96,
         vocab_size=97, rms_norm_eps=1e-6, rope_theta=1e6)
MAX_LEN, V, SLOTS = 256, 97, 4
TOL = 3e-4      # float32 sums in another order, logits of spread ~1


def _config(**over):
    return rl.RetentionConfig(max_position_embeddings=MAX_LEN,
                              **dict(C, **over))


def _model(**kw):
    kw.setdefault("longest_chunk", 16)
    return rl.RetentionLM(_config(), max_len=MAX_LEN, **kw)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


def _service(params, kernel="gather", model=None, **kw):
    gc = dict(max_slots=SLOTS, seq_buckets=[8, 16, 200])
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


def _prefill(svc, toks, slot):
    """``toks`` through the engine's chunk plan into the state ``slot``
    names; the last chunk's sampled token and last-position logits."""
    for off, take, tb, wp in svc._chunk_plan(len(toks)):
        assert wp == 1          # a state kind's table is its one slot
        nxt, last = svc._programs.run(
            "gen_prefill", svc._cache,
            pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                             tb)[None, :],
            np.arange(off, off + tb, dtype=np.int32)[None, :],
            np.asarray([take], np.int32), np.asarray([[slot]], np.int32),
            *_sampler(1, len(toks)))
    return int(nxt[0]), np.asarray(last[0])


def _decode(svc, at, tok, pos, slot):
    tokens = np.zeros((SLOTS, 1), np.int32)
    positions = np.zeros((SLOTS, 1), np.int32)
    lengths = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, 1), np.int32)
    tokens[at, 0], positions[at, 0], lengths[at], tables[at, 0] = \
        tok, pos, 1, slot
    nxt, last = svc._programs.run(
        "gen_decode", svc._cache, tokens, positions, lengths, tables,
        *_sampler(SLOTS, pos + 1))
    return int(nxt[at]), np.asarray(last[at])


def _logits_through_the_state(svc, seq, n_decode=4, slot=None):
    """Last-position logits of the prefill of ``seq`` and of ``n_decode``
    greedy steps behind it, with the sequences they belong to."""
    seq = list(seq)
    own = svc._alloc_reclaiming(1) if slot is None else [slot]
    nxt, last = _prefill(svc, seq, own[0])
    out = [(list(seq), last)]
    for _ in range(n_decode):
        seq.append(nxt)
        nxt, last = _decode(svc, 2, seq[-1], len(seq) - 1, own[0])
        out.append((list(seq), last))
    if slot is None:
        svc._cache.allocator.free(own)
    return out


def _prompt(plen, seed=None):
    rng = np.random.default_rng(plen if seed is None else seed)
    return [int(t) for t in rng.integers(0, V, plen)]


@pytest.mark.parametrize("part", ["prefill", "decode"])
@pytest.mark.parametrize("plen", [3, 16, 37, 70, 141])
def test_chunked_prefill_then_decode_match_reference_logits(svc, params,
                                                            plen, part):
    """The three forms agree: the reference's attention form = the chunked
    scan through the engine's chunk plan (chunks of 16, a leftover of every
    kind: none, under a rung, over it) = prefill then one-token steps
    through the state."""
    compared = _logits_through_the_state(svc, _prompt(plen),
                                         0 if part == "prefill" else 4)
    for toks, last in compared[0 if part == "prefill" else 1:]:
        np.testing.assert_allclose(
            last, _ref_logits(params, toks, len(toks) - 1)[0], atol=TOL,
            rtol=0)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunk_length_does_not_move_the_logits(params, chunk):
    """The same prompt through chunks of 8, 16 and 64 (one chunk and a
    leftover): each within the tolerance of the reference."""
    made = _service(params, model=_model(longest_chunk=chunk),
                    seq_buckets=[8, 16, 64, 200])
    seq = _prompt(77)
    plan = made._chunk_plan(len(seq))
    assert max(tb for _, _, tb, _ in plan) == chunk
    (toks, last), = _logits_through_the_state(made, seq, 0)
    np.testing.assert_allclose(
        last, _ref_logits(params, toks, len(toks) - 1)[0], atol=TOL, rtol=0)


@pytest.mark.parametrize("fault", ["no_gate", "no_norm"])
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


def test_cache_is_built_from_the_state_kind(svc):
    """One kind, ``state``: no paged pool, no allocator of token blocks —
    a fixed array a layer a slot, sized by ``max_slots`` alone; the
    allocator hands out slots, a row owns one whatever its length."""
    cache = svc._cache
    (kind,) = cache.kinds
    assert kind.name == "state" and kind.state and kind.window == 0
    assert kind.allocator is cache.allocator
    assert cache.num_blocks == SLOTS + 1 == kind.num_blocks
    # (layers, slots + 1, KV heads, rows of phi, dv + 8, d) float32: S over
    # its normaliser z and the padding of z's tile
    assert [tuple(p.shape) for p in cache.pools] == [
        (3, SLOTS + 1, 2, 5, 16, 8)]
    assert all(p.dtype == jnp.float32 for p in cache.pools)
    assert cache.blocks_for(1) == cache.blocks_for(MAX_LEN) == 1
    assert svc._width_buckets == [1] and svc._windows == ()
    assert sorted(svc._prefill_signatures()) == [(8, 1), (16, 1)]
    stats = svc.stats()
    assert stats["cache_kinds"]["state"] == {
        "layers": 3, "window": 0, "total": SLOTS, "used": 0, "free": SLOTS}
    assert stats["counts"]["state_bytes_per_slot"] == \
        3 * 2 * 5 * 16 * 8 * 4
    # a spec without a state kind builds exactly what it built before
    classic = PagedKVCache(n_layers=2, n_heads=2, d_head=8, num_blocks=9,
                           block_size=4)
    assert not classic.kinds[0].state and classic.blocks_for(9) == 3
    # since PR 44 a state kind may stand BEHIND a paged kind (the slots'
    # indices under an allocator of its own); it may not lead one
    kinds = (dict(name="full", n_layers=1, pools=(("k", 8), ("v", 8))),
             dict(name="state", n_layers=1,
                  state=(("state", (2, 5, 16, 8)),)))
    beside = PagedKVCache(num_blocks=9, block_size=4, window_rows=(2, 8),
                          kinds=kinds)
    assert [k.state for k in beside.kinds] == [False, True]
    assert beside.kinds[1].num_blocks == 3 \
        and beside.kinds[1].allocator is not beside.allocator
    with pytest.raises(ValueError, match="first cache kind keeps every"):
        PagedKVCache(num_blocks=9, block_size=4, window_rows=(2, 8),
                     kinds=kinds[::-1])


def test_a_padded_position_and_an_idle_row_leave_the_state_bit_equal(
        svc, params):
    """A chunk's padding (9 positions of a 16-rung behind 7 valid ones) is
    an identity on ``S`` and ``z`` — the state is the one the same 7 tokens
    leave through the 8-rung (1 padded) — and a decode step's idle rows
    leave every state they could name as it was."""
    seq = _prompt(7)
    a, b = svc._alloc_reclaiming(2)

    def fill(slot, tb):
        svc._programs.run(
            "gen_prefill", svc._cache,
            pad_tokens_right(np.asarray(seq, np.int32), tb)[None, :],
            np.arange(tb, dtype=np.int32)[None, :],
            np.asarray([7], np.int32), np.asarray([[slot]], np.int32),
            *_sampler(1, 7))

    fill(a, 8)
    fill(b, 16)
    (s,) = (np.asarray(p) for p in svc._cache.pools)
    assert np.abs(s[:, a]).max() > 0
    # (the sums inside a chunk are taken in one order whatever its length)
    np.testing.assert_allclose(s[:, a], s[:, b], rtol=1e-6, atol=1e-7)
    # a decode step in which only row 2 is live, on slot ``a``: slot ``b``
    # and the scratch are bit-equal after it; an idle row whose table still
    # names ``b`` changes nothing either
    tokens = np.zeros((SLOTS, 1), np.int32)
    positions = np.zeros((SLOTS, 1), np.int32)
    lengths = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, 1), np.int32)
    tokens[2, 0], positions[2, 0], lengths[2], tables[2, 0] = 5, 7, 1, a
    tables[1, 0] = b                # idle (length 0), its table left behind
    svc._programs.run("gen_decode", svc._cache, tokens, positions, lengths,
                      tables, *_sampler(SLOTS, 8))
    (s2,) = (np.asarray(p) for p in svc._cache.pools)
    assert np.array_equal(s2[:, b], s[:, b])
    assert np.array_equal(s2[:, 0], s[:, 0]) and not s2[:, 0].any()
    assert not np.array_equal(s2[:, a], s[:, a])
    svc._cache.allocator.free([a, b])


def test_a_reused_slot_starts_from_zero(svc, params):
    """A row whose chunk starts at position 0 starts from the zero state
    INSIDE the program: a slot that held another request's state gives the
    logits a fresh service gives, bit for bit."""
    (slot,) = svc._alloc_reclaiming(1)
    _logits_through_the_state(svc, _prompt(50, seed=1), 3, slot=slot)
    assert np.abs(np.asarray(svc._cache.pools[0])[:, slot]).max() > 0
    again = _logits_through_the_state(svc, _prompt(37), 3, slot=slot)
    svc._cache.allocator.free([slot])
    fresh = _service(params)
    (first,) = fresh._alloc_reclaiming(1)
    want = _logits_through_the_state(fresh, _prompt(37), 3, slot=first)
    for (_, got), (_, exp) in zip(again, want):
        assert np.array_equal(got, exp)


@pytest.mark.parametrize("plen,n_new", [(3, 4), (16, 8), (23, 13), (70, 30)])
def test_service_generation_matches_reference_greedy(params, svc, plen,
                                                     n_new):
    svc.start()
    prompt = np.random.default_rng(100 + plen).integers(0, V, plen)
    assert svc.generate(prompt, max_new_tokens=n_new, timeout=120) == \
        _ref_greedy(params, prompt, n_new)


def test_admitting_passes_carry_first_tokens_and_serve_the_reference(
        params, svc):
    """Ten clients on four slots with outputs of 2 to 5 tokens, so that
    most passes admit, prompts of one chunk and of several: every first
    token stays on the device for the decode step of the pass that
    admitted its row (docs/generation.md "The step in flight"), a slot's state its
    whole cache,
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


def test_the_kernels_serve_the_reference_s_tokens(params):
    """The Pallas calls (through the interpreter) behind the same service:
    chunks of 16 and a leftover, then the one-token steps."""
    made = _service(params, kernel="paged")
    assert made.stats()["decode_kernel"] == "paged"
    made.start()
    prompt = np.random.default_rng(9).integers(0, V, 41)
    assert made.generate(prompt, max_new_tokens=10, timeout=300) == \
        _ref_greedy(params, prompt, 10)
    made.stop(drain=False, timeout=30)


def test_admission_is_by_free_slot_alone(params):
    """Nine requests on four slots: a row owns its one state from
    admission to release, nothing grows, no watermark preempts, and the
    occupancy the service reports is slots live over slots."""
    made = _service(params, watermark_high=0.5, watermark_low=0.4)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, V, n) for n in (20, 5, 33, 17, 9, 40, 12, 26,
                                               3)]
    streams = [made.submit(p, max_new_tokens=12) for p in prompts]
    made.start()
    for st, p in zip(streams, prompts):
        assert st.result(300) == _ref_greedy(params, p, 12)
    stats = made.stats()
    assert stats["counts"]["preempted"] == 0
    assert stats["kv_blocks"]["peak_occupancy"] == 1.0
    assert stats["kv_blocks"]["used"] == 0
    assert stats["counts"]["state_slots_live"] == 0
    made.stop(drain=False, timeout=30)


def test_preemption_resumes_to_the_same_tokens(params):
    """A preempted row loses its state whole; its resume re-prefills the
    whole context from the zero state through the same chunk plan, and
    serves the tokens an undisturbed run serves."""
    made = _service(params)
    prompt = np.random.default_rng(11).integers(0, V, 21)
    stream = made.submit(prompt, max_new_tokens=30)
    for _ in range(12):
        made._iterate()
    r = stream._req
    made._land()
    assert made._cache.allocator.num_used == 1 and r.n_generated > 3
    started = made.stats()["counts"]["retention_rows_started"]
    with made._lock:
        made._preempt_slot_locked(made._slots.index(r))
    assert made._cache.allocator.num_used == 0 and r.blocks is None
    while not stream.finished:
        made._iterate()
    assert stream.result(1) == _ref_greedy(params, prompt, 30)
    counts = made.stats()["counts"]
    assert counts["preempted"] == 1
    assert counts["retention_rows_started"] == started + 1
    made.stop(drain=False, timeout=30)


def test_cancel_mid_flight_leaves_the_others_tokens_as_they_were(params):
    """A row cancelled while it decodes gives its slot back; the rows
    beside it and the request that takes the slot next serve the tokens of
    an undisturbed run."""
    made = _service(params)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, V, n) for n in (19, 27, 8)]
    streams = [made.submit(p, max_new_tokens=24) for p in prompts]
    for _ in range(10):
        made._iterate()
    streams[1].cancel()
    late = rng.integers(0, V, 30)
    streams.append(made.submit(late, max_new_tokens=10))
    while not all(s.finished for s in streams):
        made._iterate()
    assert streams[0].result(1) == _ref_greedy(params, prompts[0], 24)
    assert streams[2].result(1) == _ref_greedy(params, prompts[2], 24)
    assert streams[3].result(1) == _ref_greedy(params, late, 10)
    assert made.stats()["counts"]["cancelled"] == 1
    assert made._cache.allocator.num_used == 0
    made.stop(drain=False, timeout=30)


def test_the_prefix_cache_is_declined_and_the_service_says_so(params, svc,
                                                              caplog):
    """A hit at position p would need the state as it stood AT p, and a
    row keeps the state at its last position alone: a model with a state
    kind declines prefix reuse, with the message a window kind's decline
    has."""
    with caplog.at_level(logging.INFO,
                         logger="mxnet_tpu.serving.generation.engine"):
        made = _service(params, prefix_cache=True)
    assert "no prefix reuse" in caplog.text and "state" in caplog.text
    assert made._prefix is None and made.stats()["prefix_cache"] is None
    assert made._prefill_signatures() == \
        _service(params, prefix_cache=False)._prefill_signatures()
    svc.start()
    before = svc.stats()["counts"]
    prompt = np.random.default_rng(5).integers(0, V, 24)
    first = svc.generate(prompt, max_new_tokens=6, timeout=120)
    second = svc.generate(prompt, max_new_tokens=6, timeout=120)
    counts = svc.stats()["counts"]
    assert first == second == _ref_greedy(params, prompt, 6)
    assert counts["prefix_hits"] == 0
    assert counts["prefill_tokens"] - before["prefill_tokens"] == 48


@pytest.mark.parametrize("what,kw", [
    ("speculative", dict(speculative=True)),
    ("int8", dict(kv_dtype="int8")),
    ("mp", dict(mp_devices=2))])
def test_what_a_state_cannot_do_is_declined(params, what, kw):
    """A rejected draft cannot be rolled out of a sum: speculation (and
    int8 and a mesh, which nothing here builds) is refused with
    the message every model's decline has."""
    with pytest.raises(ValueError, match=f"does not offer '{what}'"):
        _service(params, **kw)


def test_the_programs_counts_and_the_slots_gauges_reach_stats(params):
    """``aux`` of every prefill chunk and decode step, summed once its
    step's tokens were read, and the two gauges of the state kind."""
    made = _service(params)
    prompt = np.random.default_rng(7).integers(0, V, 37)
    stream = made.submit(prompt, max_new_tokens=9)
    for _ in range(5):
        made._iterate()
    made._land()
    live = made.stats()["counts"]
    assert live["state_slots_live"] == 1
    while not stream.finished:
        made._iterate()
    made._land()
    counts = made.stats()["counts"]
    assert counts["retention_prefill_tokens"] == 37
    assert counts["retention_rows_started"] == 1
    # the first token comes from the prefill's last position
    assert counts["retention_decode_rows"] >= 8
    assert counts["state_slots_live"] == 0
    assert counts["steps_ahead"] > 0        # the step in flight is ridden
    made.stop(drain=False, timeout=30)


def test_warmup_covers_every_program_the_traffic_needs(params):
    """Two chunk lengths and the decode step: three programs and the carry,
    whatever the prompts' lengths — a state kind has one table width."""
    from mxnet_tpu.executor import compile_cache_stats

    made = _service(params, seq_buckets=[8, 16, 100])
    assert made.warmup() == 3
    before = compile_cache_stats()["misses"]
    made.start()
    rng = np.random.default_rng(41)
    streams = [made.submit(rng.integers(0, V, n), max_new_tokens=7)
               for n in (1, 8, 9, 16, 17, 40, 99)]
    for st in streams:
        st.result(300)
    assert compile_cache_stats()["misses"] == before
    made.stop(drain=False, timeout=30)

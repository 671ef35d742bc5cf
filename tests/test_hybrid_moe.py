"""The window-and-full-attention model (parallel/hybrid_moe.py) through the
generation engine and its cache of two kinds, against the plain reference
(perfbench/reference/mimo_v2.py) on seeded weights, at a tiny preset on the
CPU: d 64, 8 query heads over 1 (full) and 2 (window) KV heads, key 24 /
rotary 8 / value 16, a window of 8, cache blocks of 4, a dense layer of 96,
16 experts of 32 top-2, 7 layers in the published pattern [0,1,1,1,1,0,1],
vocabulary 97.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerance is that of float32 sums taken in
another order (the program batches, pages, groups the query heads of a KV
head, walks a ring of window blocks and starts its softmax at the sink; the
reference does none of that): 1e-4 on logits whose spread is about 1.  A
window one position short, a sink left out and values left unscaled each
miss it by more than twenty times, the reference in bfloat16 by more than a
hundred.
"""
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import hybrid_moe as hm
from mxnet_tpu.parallel.latent_moe import route_sigmoid_groups
from mxnet_tpu.parallel.sdar_moe import expert_products
from mxnet_tpu.serving.bucketing import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import (PagedKVCache, blocks_for,
                                                   window_blocks)
from oracle import greedy
from perfbench.reference import mimo_v2 as ref

C = dict(hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
         moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], num_hidden_layers=7,
         hidden_size=64, num_attention_heads=8, num_key_value_heads=1,
         swa_num_attention_heads=8, swa_num_key_value_heads=2, head_dim=24,
         swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16, sliding_window=8,
         partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4,
         attention_value_scale=0.707, add_swa_attention_sink_bias=True,
         add_full_attention_sink_bias=False, intermediate_size=96,
         moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=2,
         n_group=1, topk_group=1, norm_topk_prob=True,
         routed_scaling_factor=None, vocab_size=97, layernorm_epsilon=1e-5)
MAX_LEN, V, BS, WIN = 512, 97, 4, 8
TOL = 1e-4      # float32 sums in another order, logits of spread ~1


def _config(**over):
    kw = {k: (tuple(v) if isinstance(v, list) else v) for k, v in C.items()
          if k != "routed_scaling_factor"}
    kw.update(over)
    return hm.HybridMoeConfig(max_position_embeddings=MAX_LEN, **kw)


def _model(cfg=None, **kw):
    kw.setdefault("longest_chunk", 16)
    return hm.HybridMoeLM(cfg or _config(), max_len=MAX_LEN,
                          kv_dtype=jnp.float32, **kw)


# the dense layer and two window expert layers: what a test that needs a
# service of its own compiles (a service compiles its own programs, and the
# interpreted kernel is slow to compile)
C3 = dict(C, hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1],
          num_hidden_layers=3)
CUT = dict(hybrid_layer_pattern=(0, 1, 1), moe_layer_freq=(0, 1, 1),
           num_hidden_layers=3)
# the published head: 192 lanes of which the first 64 rotate (0.334 x 192),
# beside values of 128 — not whole 128-lane tiles, which is what made the
# chip's compiler turn ``wq`` round (docs/generation.md "A weight reaches
# its product as stored"); two query heads are enough to catch a rotary cut
# or a head's edge moved by a lane
C192 = dict(C3, num_attention_heads=2, swa_num_attention_heads=2,
            num_key_value_heads=1, swa_num_key_value_heads=2, head_dim=192,
            swa_head_dim=192, v_head_dim=128, swa_v_head_dim=128)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


@pytest.fixture(scope="module")
def params3():
    return ref.init_params(3, C3, "float32")


def _service(params, kernel="gather", model=None, **kw):
    gc = dict(max_slots=4, block_size=BS, num_blocks=256,
              seq_buckets=[8, 16, 400])
    gc.update(kw)
    with pytest.MonkeyPatch.context() as mp:
        # read once, when the service is made
        mp.setenv("TPUMX_PALLAS", "1" if kernel == "paged" else "0")
        return GenerationService(params, model or _model(),
                                 GenerationConfig(**gc), start=False)


@pytest.fixture(scope="module")
def params192():
    return ref.init_params(3, C192, "float32")


@pytest.fixture(scope="module")
def svc192(params192):
    """Three layers at the published head sizes, without the kernel."""
    cut = {k: (tuple(v) if isinstance(v, list) else v)
           for k, v in C192.items() if k in CUT or "head" in k}
    assert _config(**cut).rotary_dim == 64
    svc = _service(params192, model=_model(_config(**cut)))
    yield svc
    svc.stop(drain=False, timeout=30)


@pytest.fixture(scope="module")
def svc7(params):
    """The 7 published-pattern layers without the kernel: ONE service for
    every test that does not need its own (a test that needs the engine
    running starts it, and leaves it idle)."""
    svc = _service(params)
    yield svc
    svc.stop(drain=False, timeout=30)


# a chunk no configured rung names, between 16 and the ladder's top: the
# model's own longest chunk is then the top rung of every walk
LONG = 32


@pytest.fixture(scope="module")
def svc7_long(params):
    """``svc7`` with a model whose prefill program takes chunks of 32:
    rungs 8, 16 and 32, a ring and a window pool for the longer chunk."""
    svc = _service(params, model=_model(longest_chunk=LONG))
    assert svc._seq_buckets == [8, 16, LONG]
    yield svc
    svc.stop(drain=False, timeout=30)


def _ref_logits(params, tokens, at0, n_at=1, c=C, **kw):
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(ref.logits(params, c, toks, len(tokens), at0, n_at,
                                 **kw))


def _ref_greedy(params, prompt, n):
    return greedy(lambda seq: _ref_logits(params, seq, len(seq) - 1)[0],
                  prompt, n)


def _sampler(n, counter):
    z = np.zeros(n, np.int32)
    return (z.astype(np.uint32), np.full(n, counter, np.uint32),
            z.astype(np.float32), z, np.ones(n, np.float32))


class _Row:
    """What the engine's window code reads of a request."""
    rid, wins = 0, None


def _prefill(svc, toks, blocks, row):
    """``toks`` through the engine's chunk plan and both kinds' tables; the
    last chunk's sampled token and last-position logits."""
    for off, take, tb, wp in svc._chunk_plan(len(toks)):
        table = np.zeros((1, wp), np.int32)
        table[0, :min(wp, len(blocks))] = blocks[:wp]
        svc._slide(row, off, off + take)
        nxt, last = svc._programs.run(
            "gen_prefill", svc._cache,
            pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                             tb)[None, :],
            np.arange(off, off + tb, dtype=np.int32)[None, :],
            np.asarray([take], np.int32),
            (table, *svc._ring_tables([(0, row)], 1, tb)),
            *_sampler(1, len(toks)))
        svc._slide(row, off + take, off + take)
    return int(nxt[0]), np.asarray(last[0])


def _decode(svc, at, tok, pos, blocks, row, S=4):
    tokens, positions = np.zeros((S, 1), np.int32), np.zeros((S, 1), np.int32)
    lengths = np.zeros(S, np.int32)
    tables = np.zeros((S, svc._width_buckets[-1]), np.int32)
    tokens[at, 0], positions[at, 0], lengths[at] = tok, pos, 1
    tables[at, :len(blocks)] = blocks
    svc._slide(row, pos, pos + 1)
    nxt, last = svc._programs.run(
        "gen_decode", svc._cache, tokens, positions, lengths,
        (tables, *svc._ring_tables([(at, row)], S, 1)), *_sampler(S, pos + 1))
    return int(nxt[at]), np.asarray(last[at])


def _logits_through_the_cache(svc, seq, n_decode=4):
    """Last-position logits of the prefill of ``seq`` and of ``n_decode``
    greedy steps behind it, with the sequences they belong to."""
    seq, row = list(seq), _Row()
    blocks = svc._alloc_reclaiming(blocks_for(len(seq) + n_decode + 1, BS))
    nxt, last = _prefill(svc, seq, blocks, row)
    out = [(list(seq), last)]
    for _ in range(n_decode):
        seq.append(nxt)
        nxt, last = _decode(svc, 2, seq[-1], len(seq) - 1, blocks, row)
        out.append((list(seq), last))
    svc._cache.allocator.free(blocks)
    svc._drop_windows(row)
    return out


def _check_logits_through_the_cache(svc, p, c, kernel, plen, part):
    """Prefill through the chunk plan (every leftover length; past the
    window and a chunk, 8 + 16 positions at ``svc7``'s rungs, window blocks
    have been freed and reused), then greedy decode
    steps through both cache kinds, against the reference's full forward
    over the whole sequence; ``part`` says which of the two is compared (a
    program of the interpreted kernel is most of a minute to compile, and
    the two parts need a program each)."""
    assert svc.stats()["decode_kernel"] == kernel
    freed = svc.stats()["counts"]["window_blocks_freed"]
    seq = [int(t) for t in np.random.default_rng(plen).integers(0, V, plen)]
    if part == "prefill":
        compared = _logits_through_the_cache(svc, seq, 0)
    else:
        compared = _logits_through_the_cache(svc, seq)[1:]
    for toks, last in compared:
        np.testing.assert_allclose(
            last, _ref_logits(p, toks, len(toks) - 1, c=c)[0], atol=TOL,
            rtol=0)
    if plen > WIN + svc._seq_buckets[-1]:
        assert svc.stats()["counts"]["window_blocks_freed"] > freed


@pytest.mark.parametrize("part", ["prefill", "decode"])
@pytest.mark.parametrize("widths,plen", [
    ("tiny", 3), ("tiny", 16), ("tiny", 37), ("tiny", 70),
    ("long", 32), ("long", 37), ("long", 70), ("long", 100),
    ("head192", 16), ("head192", 37)])
def test_chunked_prefill_then_decode_match_reference_logits(request, widths,
                                                            plen, part):
    """The sums over the gathered pages and the gathered ring, at the 7
    layers of the published pattern (the tiles body:
    tests/test_hybrid_moe_kernel.py), and at the published HEAD — 192 =
    64 rotary + 128, values 128 — on three layers, a chunk and a decode
    step: the flat products' cut into heads and the rotary cut.  ``long``:
    the same 7 layers cut at the model's longer chunk (32: one chunk, one
    and a leftover, two and a leftover past window + chunk, three)."""
    svc, p, c = {"tiny": ("svc7", "params", C),
                 "long": ("svc7_long", "params", C),
                 "head192": ("svc192", "params192", C192)}[widths]
    _check_logits_through_the_cache(
        request.getfixturevalue(svc), request.getfixturevalue(p), c,
        "gather", plen, part)


@pytest.mark.parametrize("fault", ["window_one_short", "no_sink",
                                   "unscaled_values"])
def test_a_wrong_window_a_missing_sink_and_unscaled_values_fail(params3,
                                                                fault):
    """The comparison above must FAIL a program that reads a window one
    position short, leaves the sink out of the softmax, or does not scale
    its values by ``attention_value_scale``."""
    cfg = _config(**CUT, **{
        "window_one_short": dict(sliding_window=WIN - 1),
        "no_sink": dict(add_swa_attention_sink_bias=False),
        "unscaled_values": dict(attention_value_scale=1.0)}[fault])
    svc = _service(params3, model=_model(cfg))
    seq = [int(t) for t in np.random.default_rng(37).integers(0, V, 37)]
    worst = max(np.abs(last - _ref_logits(params3, toks, len(toks) - 1,
                                          c=C3)[0]).max()
                for toks, last in _logits_through_the_cache(svc, seq, 1))
    assert worst > 20 * TOL


def test_one_precision_down_is_outside_the_tolerance(params):
    seq = [int(t) for t in np.random.default_rng(1).integers(0, V, 37)]
    want = _ref_logits(params, seq, 30, 7)
    low = _ref_logits(params, seq, 30, 7, dtype="bfloat16")
    assert np.abs(low - want).max() > 100 * TOL


def test_the_references_planted_faults_move_its_logits(params):
    """What the benchmark's driver plants on the reference's side: the
    sink left out, the window one block short."""
    seq = [int(t) for t in np.random.default_rng(1).integers(0, V, 37)]
    want = _ref_logits(params, seq, 30, 7)
    for fault in ("no_sink", "short_window"):
        c = dict(C, sliding_window=32) if fault == "short_window" else C
        base = want if c is C else _ref_logits(params, seq, 30, 7, c=c)
        assert np.abs(_ref_logits(params, seq, 30, 7, c=c, fault=fault)
                      - base).max() > 20 * TOL


# -- the experts ---------------------------------------------------------------
@pytest.mark.parametrize("pallas", [False, True], ids=["ragged", "kernel"])
def test_the_sixteen_shares_of_an_expert_layer_sum_to_the_uncut_layer(
        params, monkeypatch, pallas):
    """Sixteen chips hold one expert each (``n_group`` 1: one group,
    always kept): their parts of the routed result are the uncut layer's
    — in the program (grouped products told ``experts_held``) and in the
    reference (given the same share).  No shared expert."""
    monkeypatch.setenv("TPUMX_PALLAS", "1" if pallas else "0")
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(0, 1, (23, 64)), jnp.float32)
    g = lambda n: params[f"l1_{n}"]  # noqa: E731
    w, e = route_sigmoid_groups(h @ g("router"), g("router_bias"), 2, 1, 1,
                                True, 1.0)
    w_ref, e_ref = ref.route(h @ g("router"), g("router_bias"), k=2,
                             norm_topk=True, scaling=1.0)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=1e-6)
    whole, sizes, _ = expert_products(h, w, e, g("wg"), g("wu"), g("wd"),
                                      pallas=pallas)
    assert int(sizes.sum()) == 23 * 2
    parts, ref_parts = 0, 0
    for lo in range(16):
        one = slice(lo, lo + 1)
        y, sz, _ = expert_products(h, w, e, g("wg")[one], g("wu")[one],
                                   g("wd")[one], (lo, lo + 1), pallas=pallas)
        np.testing.assert_array_equal(np.asarray(sz), np.asarray(sizes[one]))
        parts = parts + y
        ref_parts = ref_parts + ref._experts(
            h, w, e, g("wg")[one], g("wu")[one], g("wd")[one], lo,
            jnp.float32)
    uncut = ref._experts(h, w, e, g("wg"), g("wu"), g("wd"), 0, jnp.float32)
    for got in (parts, ref_parts, whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(uncut),
                                   atol=2e-5, rtol=0)


def test_a_share_of_the_model_is_the_reference_given_the_same_share():
    """The whole model with experts 4-7 held: the program told
    ``experts_held`` against the reference given the same share."""
    c = dict(C3, experts_held=[4, 8])
    p = ref.init_params(3, c, "float32")
    full = ref.init_params(3, C3, "float32")
    np.testing.assert_array_equal(np.asarray(p["l1_wg"]),
                                  np.asarray(full["l1_wg"][4:8]))
    svc = _service(p, model=_model(_config(**CUT), experts_held=(4, 8)))
    seq = [int(t) for t in np.random.default_rng(2).integers(0, V, 21)]
    _, last = _prefill(svc, seq, svc._alloc_reclaiming(8), _Row())
    np.testing.assert_allclose(last, _ref_logits(p, seq, 20, c=c)[0],
                               atol=TOL, rtol=0)
    aux = {k: int(v) for k, v in svc._programs.take_aux()[-1].items()}
    assert aux["expert_assignments"] == 2 * 2 * 5     # layers x k x tokens
    assert 0 < aux["expert_assignments_held"] < aux["expert_assignments"]


# -- the cache of two kinds ----------------------------------------------------
def test_cache_is_built_from_the_models_kinds(svc7):
    """Two kinds, each with its layers, its pools at their own widths, its
    own block count and allocator: ``full`` sized by ``num_blocks``,
    ``window`` by slots — what every row owns at rest and the one row being
    prefilled owns besides."""
    cache = svc7._cache
    full, window = cache.kinds
    assert (full.name, full.n_layers, full.window) == ("full", 2, 0)
    assert (window.name, window.n_layers, window.window) == ("window", 5, WIN)
    assert full.allocator is cache.allocator is not window.allocator
    assert full.num_blocks == 256
    assert window.num_blocks == 1 + 4 * window_blocks(WIN, 1, BS) \
        + window_blocks(WIN, 16, BS) == 1 + 4 * 4 + 7
    assert [p.shape for p in cache.pools[full.span]] == \
        [(2, 256, BS, 24), (2, 256, BS, 16)]
    assert [p.shape for p in cache.pools[window.span]] == \
        [(5, 24, BS, 48), (5, 24, BS, 32)]
    assert cache.nbytes() == sum(int(p.nbytes) for p in cache.pools)
    with pytest.raises(ValueError, match="first cache kind"):
        PagedKVCache(num_blocks=8, block_size=BS, window_rows=(4, 16), kinds=(
            dict(name="w", n_layers=1, pools=(("k", 8),), window=8),))
    with pytest.raises(ValueError, match="kind by kind"):
        PagedKVCache(num_blocks=8, block_size=BS, kv_dtype="int8",
                     **_model().cache_spec())


@pytest.mark.parametrize("plen,n_new", [(3, 4), (16, 8), (23, 13), (70, 30)])
def test_service_generation_matches_reference_greedy(params, svc7, plen,
                                                     n_new):
    """Whole generations through submit / the scheduler / the step in
    flight / both cache kinds, token for token (float32 on both sides; the
    seeds give no tie)."""
    svc7.start()
    prompt = np.random.default_rng(100 + plen).integers(0, V, plen)
    assert svc7.generate(prompt, max_new_tokens=n_new, timeout=300) \
        == _ref_greedy(params, prompt, n_new)


@pytest.mark.parametrize("plen,n_new", [(37, 6), (70, 30), (100, 12)])
def test_a_longer_chunk_serves_the_shorter_chunks_tokens_and_logits(
        params, svc7, svc7_long, plen, n_new):
    """The same prompt cut at 32 and at 16: the chunks differ, the last
    position's logits agree within the tolerance both hold against the
    reference, and the services' greedy tokens are the reference's; the
    longer chunk's ring and window pool are the wider ones, and its row
    frees the blocks behind its window as it goes."""
    long, short = svc7_long, svc7
    prompt = [int(t) for t in
              np.random.default_rng(200 + plen).integers(0, V, plen)]
    plans = [[c[:3] for c in s._chunk_plan(plen)] for s in (long, short)]
    assert plans[0] != plans[1] and plans[0][0] == (0, LONG, LONG)
    assert {tb for _, _, tb in plans[1]} <= {8, 16}
    wl, ws = long._cache.kinds[1], short._cache.kinds[1]
    assert wl.num_blocks - ws.num_blocks == \
        window_blocks(WIN, LONG, BS) - window_blocks(WIN, 16, BS) == 4
    assert long._ring_tables([], 1, LONG)[0].shape == (1, 16)
    assert short._ring_tables([], 1, 16)[0].shape == (1, 8)
    freed = long.stats()["counts"]["window_blocks_freed"]
    (_, at_long), = _logits_through_the_cache(long, prompt, 0)
    (_, at_short), = _logits_through_the_cache(short, prompt, 0)
    np.testing.assert_allclose(at_long, at_short, atol=TOL, rtol=0)
    if plen > WIN + LONG:
        assert long.stats()["counts"]["window_blocks_freed"] > freed
    assert wl.allocator.num_used == 0 == ws.allocator.num_used
    long.start()
    short.start()
    want = _ref_greedy(params, prompt, n_new)
    assert long.generate(prompt, max_new_tokens=n_new, timeout=300) == want
    assert short.generate(prompt, max_new_tokens=n_new, timeout=300) == want


def test_admitting_passes_carry_first_tokens_and_serve_the_reference(
        params, svc7):
    """Ten clients on four slots with outputs of 2 to 5 tokens, so that
    most passes admit, prompts of one chunk and of several: every first
    token stays on the device for the decode step of the pass that
    admitted its row (docs/generation.md "The step in flight"), window blocks sliding
    under the chunks beside it,
    and every request is served the reference's tokens."""
    svc7.start()
    before = svc7.stats()["counts"]
    rng = np.random.default_rng(45)
    prompts = [[int(t) for t in rng.integers(0, V, n)]
               for n in (5, 41, 8, 23, 3, 70, 16, 33, 7, 19)]
    news = (3, 2, 5, 4, 2, 3, 5, 2, 4, 3)
    streams = [svc7.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    for st, p, n in zip(streams, prompts, news):
        assert st.result(300) == _ref_greedy(params, p, n)
    after = svc7.stats()["counts"]
    ahead = after["prefills_ahead"] - before["prefills_ahead"]
    read = after["prefills_read"] - before["prefills_read"]
    # (a request that ends a pass alone has nothing to decode after it)
    assert ahead + read == 10 and ahead >= 8
    assert after["failed"] == before["failed"]


def test_a_long_row_never_owns_more_window_blocks_than_the_bound(
        params, svc7, monkeypatch):
    """A row 40 windows long: at every token it owns at most
    ``window_blocks(window, longest chunk)`` blocks of the window kind
    (at rest: ``window_blocks(window, 1)``), while its full-kind blocks
    grow with it as they always did; the blocks it freed serve the next
    row."""
    svc = svc7
    window = svc._cache.kinds[1]
    owned, freed, taken = [], [], []
    free, allocate = window.allocator.free, window.allocator.allocate
    monkeypatch.setattr(window.allocator, "free",
                        lambda b: (freed.extend(b), free(b))[1])

    def recording(n):
        got = allocate(n)
        taken.extend(got or ())
        return got
    monkeypatch.setattr(window.allocator, "allocate", recording)
    before = svc.stats()["counts"]["window_blocks_freed"]
    prompt = np.random.default_rng(8).integers(0, V, 40)
    n_new = 40 * WIN - 40
    stream = svc.submit(prompt, max_new_tokens=n_new,
                        on_token=lambda *_: owned.append(
                            (window.allocator.num_used,
                             svc._cache.allocator.num_used)))
    svc.start()
    tokens = stream.result(600)
    assert len(tokens) == n_new
    assert max(w for w, _ in owned) <= window_blocks(WIN, 16, BS)
    assert max(w for w, _ in owned[2:]) <= window_blocks(WIN, 1, BS) == 4
    assert max(f for _, f in owned) == blocks_for(40 * WIN, BS)
    counts = svc.stats()["counts"]
    assert counts["window_blocks_freed"] - before \
        >= blocks_for(40 * WIN, BS) - 4
    assert window.allocator.num_used == 0
    # the window kind has 23 blocks: the row walked through 80
    assert len(taken) >= 80 and len(set(taken)) <= window.num_blocks - 1
    mine = set(freed)
    del taken[:]
    other = np.random.default_rng(9).integers(0, V, 11)
    assert svc.generate(other, max_new_tokens=5, timeout=120) \
        == _ref_greedy(params, other, 5)
    assert set(taken) & mine
    # what the long row generated is what the reference's last steps give
    seq = [int(t) for t in prompt] + tokens
    np.testing.assert_array_equal(
        _ref_logits(params, seq[:-1], len(seq) - 9, 8).argmax(-1), seq[-8:])


def test_a_freed_window_block_reused_while_a_step_is_in_flight(
        params, svc7, monkeypatch):
    """The step in flight stays on: a window block one row freed is handed
    to another row while the step that last read it is still queued.  That
    is sound because programs run in order on the device — the tokens are
    the reference's, which reads nothing late."""
    svc = svc7
    assert svc._runs_ahead
    window = svc._cache.kinds[1].allocator
    log = []        # (freed or taken, block, a step was in flight)
    free, allocate = window.free, window.allocate
    monkeypatch.setattr(window, "free", lambda b: (log.extend(
        ("freed", x, svc._flight is not None) for x in b), free(b))[1])

    def recording(n):
        got = allocate(n)
        log.extend(("taken", x, svc._flight is not None) for x in got or ())
        return got
    monkeypatch.setattr(window, "allocate", recording)
    prompts = [np.random.default_rng(s).integers(0, V, n)
               for s, n in ((31, 9), (32, 14), (33, 6))]
    ahead = svc.stats()["counts"]["steps_ahead"]
    streams = [svc.submit(p, max_new_tokens=40) for p in prompts]
    svc.start()
    served = [st.result(300) for st in streams]
    assert svc.stats()["counts"]["steps_ahead"] - ahead > 30
    reused = [b for i, (what, b, flying) in enumerate(log)
              if what == "taken" and flying and ("freed", b, True) in log[:i]]
    assert reused
    assert served == [_ref_greedy(params, p, 40) for p in prompts]


def test_preemption_resumes_to_the_same_tokens(params3):
    """A preempted row gives back the blocks of BOTH kinds; its resume
    re-prefills the whole context, the window kind's blocks taken anew as
    the chunks go, and serves the tokens an undisturbed run serves."""
    svc = _service(params3, model=_model(_config(**CUT)))
    window = svc._cache.kinds[1].allocator
    prompt = np.random.default_rng(11).integers(0, V, 21)
    stream = svc.submit(prompt, max_new_tokens=30)
    for _ in range(12):
        svc._iterate()
    r = stream._req
    svc._land()
    assert window.num_used > 0 and r.wins is not None
    with svc._lock:
        svc._preempt_slot_locked(svc._slots.index(r))
    assert window.num_used == 0 and svc._cache.allocator.num_used == 0
    assert r.wins is None and r.blocks is None
    while not stream.finished:
        svc._iterate()
    want, seq = [], [int(t) for t in prompt]
    for _ in range(30):
        want.append(int(_ref_logits(params3, seq, len(seq) - 1, c=C3
                                    )[0].argmax()))
        seq.append(want[-1])
    assert stream.result(1) == want
    assert svc.stats()["counts"]["preempted"] == 1
    assert window.num_used == 0
    svc.stop(drain=False, timeout=30)


def test_pool_pressure_preempts_and_every_request_still_matches(params):
    # (the 7 layers: the tokens are the reference's for the shared service)
    """The full kind is the one that runs out (it is sized by tokens): its
    watermark preempts, both kinds' blocks go back, everything resumes."""
    svc = _service(params, num_blocks=26, watermark_high=0.9,
                   watermark_low=0.6)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, V, n) for n in (20, 18, 22, 17)]
    streams = [svc.submit(p, max_new_tokens=20) for p in prompts]
    svc.start()
    for st, p in zip(streams, prompts):
        assert st.result(300) == _ref_greedy(params, p, 20)
    assert svc.stats()["counts"]["preempted"] >= 1
    assert svc._cache.kinds[1].allocator.num_used == 0
    svc.stop(drain=False, timeout=30)


def test_the_prefix_cache_is_declined_and_the_service_says_so(
        params, svc7, caplog):
    """A hit at position p would also need the window layers' last
    positions before p, which their row freed: a model with a window kind
    declines prefix reuse.  The service says so at construction (a log
    line, ``stats()["prefix_cache"]`` None), enumerates no cache-hit
    program, and a prompt sent twice is prefilled twice and served the same
    tokens."""
    with caplog.at_level(logging.INFO,
                         logger="mxnet_tpu.serving.generation.engine"):
        made = _service(params, prefix_cache=True)
    assert "no prefix reuse" in caplog.text and "window" in caplog.text
    assert made._prefix is None and made.stats()["prefix_cache"] is None
    assert made._prefill_signatures() == \
        _service(params, prefix_cache=False)._prefill_signatures()
    svc = svc7                              # made with the default: on
    assert svc._config.prefix_cache and svc._prefix is None
    svc.start()
    before = svc.stats()["counts"]
    prompt = np.random.default_rng(5).integers(0, V, 24)
    first = svc.generate(prompt, max_new_tokens=6, timeout=120)
    second = svc.generate(prompt, max_new_tokens=6, timeout=120)
    counts = svc.stats()["counts"]
    assert first == second == _ref_greedy(params, prompt, 6)
    assert counts["prefix_hits"] == 0
    assert counts["prefill_tokens"] - before["prefill_tokens"] == 48


def test_the_programs_counts_and_the_kinds_gauges_reach_stats(params3):
    """``aux`` of every prefill chunk and decode step, summed once its
    step's tokens were read: cache positions the decode calls were asked
    to read and query-key pairs inside the chunk calls' masks, a kind;
    assignments and experts touched.  The manager's: blocks a kind, the
    window blocks freed, the gauge in the registry."""
    from mxnet_tpu import observability as obs

    svc = _service(params3, model=_model(_config(**CUT)))
    svc.start()
    svc.generate(np.arange(21), max_new_tokens=6, timeout=120)
    st = svc.stats()
    counts = st["counts"]
    assert svc._runs_ahead and counts["steps_ahead"] >= 1
    assert counts["full_prefill_pairs"] == sum(range(1, 22))
    assert counts["window_prefill_pairs"] == sum(
        min(p, WIN) for p in range(1, 22))
    # decode steps at contexts 21..25 (the sixth token needs no sixth step
    # read; a step dispatched ahead of the end is dropped unread)
    assert counts["full_ctx_tokens"] == sum(range(22, 27))
    assert counts["window_ctx_tokens"] == 5 * WIN
    assert counts["window_decode_trips"] == 0       # no kernel, no trip
    assert counts["expert_assignments"] == 2 * 2 * (21 + 5)
    assert counts["expert_assignments_held"] == counts["expert_assignments"]
    assert counts["window_blocks_freed"] >= 3
    assert st["cache_kinds"]["window"] == {
        "layers": 2, "window": WIN, "total": 23, "used": 0, "free": 23}
    assert st["cache_kinds"]["full"]["total"] == 255
    assert 'generation_kv_kind_blocks_used{kind="window"}' in \
        obs.registry().to_prometheus()
    svc.stop(drain=False, timeout=30)


def test_the_expert_layers_trips_reach_stats_and_a_second_trip_is_exact():
    """Experts 4-7 of 16 held and their router bias raised, so that every
    token (a padded one too) chooses two of them: a 128-token chunk's 256
    assignments are all this chip's, two tiles of 128 rows an expert layer,
    a decode step's 8 one.  The program counts the tiles it walked, the
    engine sums them, and the tokens are the reference's."""
    c = dict(C3, experts_held=[4, 8])
    p = dict(ref.init_params(3, c, "float32"))
    for i in (1, 2):
        p[f"l{i}_router_bias"] = p[f"l{i}_router_bias"].at[4:8].add(100.0)
    model = hm.HybridMoeLM(_config(**CUT), max_len=MAX_LEN,
                           kv_dtype=jnp.float32, longest_chunk=128,
                           experts_held=(4, 8))
    assert model.counters == hm.COUNTERS + ("expert_trips",
                                            "expert_trips_extra")
    assert _model().counters == hm.COUNTERS
    svc = _service(p, model=model, seq_buckets=[8, 128, 400])
    prompt = [int(t) for t in np.random.default_rng(4).integers(0, V, 133)]
    chunks = [tb for _, _, tb, _ in svc._chunk_plan(len(prompt))]
    assert chunks == [128, 8]
    svc.start()
    got = svc.generate(prompt, max_new_tokens=6, timeout=300)
    counts = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(_ref_logits(p, seq, len(seq) - 1, c=c)[0].argmax()))
    assert list(got) == seq[len(prompt):]
    assert counts["expert_assignments_held"] == counts["expert_assignments"]
    # two expert layers; a chunk of 128 x top-2 = 256 rows in tiles of 128,
    # one of 8 and the 5 decode steps read (4 slots x 2) in one tile each
    assert counts["expert_trips"] == 2 * (2 + 1 + 5)
    assert counts["expert_trips_extra"] == 2 * 1


def _check_warmup_covers_the_traffic(svc, n_width):
    """``warmup()`` makes a decode program a table width and a prefill
    program a chunk length, each with the window kind's ring at its own
    width; the traffic behind it compiles nothing."""
    from mxnet_tpu.executor import compile_cache_stats

    assert len(svc._width_buckets) == n_width
    done = svc._programs.compiled_signatures()
    n = svc.warmup()
    assert n + done == len(svc._prefill_signatures()) + n_width
    misses = compile_cache_stats()["misses"]
    svc.start()
    rng = np.random.default_rng(2)
    streams = [svc.submit(rng.integers(0, V, n), max_new_tokens=5)
               for n in (3, 16, 25, 37)]
    for st in streams:
        assert len(st.result(300)) == 5
    assert compile_cache_stats()["misses"] == misses


def test_warmup_covers_every_program_the_traffic_needs(params3):
    """Over gathered pages a table's width is a shape: five widths."""
    _check_warmup_covers_the_traffic(
        _service(params3, seq_buckets=[8, 16, 40], model=hm.HybridMoeLM(
            _config(**CUT), max_len=64, kv_dtype=jnp.float32,
            longest_chunk=16)), 5)


def test_the_published_layers_are_the_arithmetic_of_the_cut():
    """The parameter counts ISSUE 32 sizes the cell by, from the shapes
    the program makes at the published widths with 16 experts held."""
    cfg = hm.HybridMoeConfig(
        vocab_size=19072, num_hidden_layers=7,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1))
    assert cfg.rotary_dim == 64
    shapes = hm.hybrid_moe_param_shapes(cfg, (0, 16))
    size = lambda i: sum(int(np.prod(s)) for n, s in shapes.items()  # noqa: E731
                         if n.startswith(f"l{i}_"))
    near = lambda n, millions: abs(n / 1e6 - millions) < 0.1  # noqa: E731
    assert near(size(0), 290.4)          # dense, full attention
    assert near(size(1), 498.1)          # window expert layer
    assert near(size(5), 492.8)          # full expert layer
    assert abs(sum(int(np.prod(s)) for s in shapes.values()) / 1e6
               - 3430.5) < 1.0
    full, window = hm.HybridMoeLM(cfg, max_len=16384).cache_spec()["kinds"]
    per_token = lambda k: k["n_layers"] * sum(w for _, w in k["pools"]) * 2  # noqa: E731
    assert (per_token(full), per_token(window)) == (5120, 25600)

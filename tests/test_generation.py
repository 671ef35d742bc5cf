"""Continuous-batching LM generation engine (mxnet_tpu.serving.generation,
docs/generation.md): paged-KV-cache correctness vs the full-sequence
transformer oracle, iteration-level scheduling, zero steady-state
recompiles under TPUMX_FREEZE_COMPILES, sampling ops, block allocator,
backpressure/deadline/cancellation semantics.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import observability as obs
from mxnet_tpu.ops import get_op
from mxnet_tpu.ops import sampling as smp
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving import (DeadlineExceededError, QueueFullError,
                               ServingClosedError, bucket_seq_len,
                               pad_tokens_right, seq_buckets)
from mxnet_tpu.serving.generation import (BlockAllocator, GenerationConfig,
                                          GenerationService, PagedKVCache,
                                          blocks_for)
from oracle import CFG, greedy_oracle, params  # noqa: F401 (fixture)

pytestmark = pytest.mark.generation


@pytest.fixture(autouse=True)
def _fresh_observability():
    """Generation warmup calls mark_warm(); keep the freeze/explainer state
    from leaking across tests."""
    yield
    obs.recompile.reset()


def _gc(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("seq_buckets", [16, 32])
    kw.setdefault("max_new_tokens", 8)
    return GenerationConfig(**kw)


# -- satellite: seq-len ladder ------------------------------------------------------
def test_seq_bucket_ladder():
    assert seq_buckets(128) == [16, 32, 64, 128]
    assert seq_buckets(100) == [16, 32, 64, 100]   # cap kept, like batch ladder
    assert seq_buckets(8) == [8]
    assert bucket_seq_len(1, [16, 32]) == 16
    assert bucket_seq_len(16, [16, 32]) == 16
    assert bucket_seq_len(17, [16, 32]) == 32


def test_seq_bucket_overlong_raises():
    with pytest.raises(ValueError, match="exceeds the largest"):
        bucket_seq_len(33, [16, 32])
    with pytest.raises(ValueError):
        bucket_seq_len(0, [16, 32])


def test_pad_tokens_right():
    out = pad_tokens_right(np.array([3, 4, 5]), 6)
    np.testing.assert_array_equal(out, [3, 4, 5, 0, 0, 0])
    with pytest.raises(ValueError):
        pad_tokens_right(np.arange(7), 6)


# -- satellite: sampling ops --------------------------------------------------------
def test_top_k_mask_numpy_parity():
    rs = np.random.RandomState(3)
    logits = rs.randn(4, 12).astype(np.float32)
    ks = np.array([1, 3, 0, 50], np.int32)  # 0 / >vocab disable
    out = np.asarray(smp.top_k_mask(logits, ks))
    for row, k in zip(range(4), ks):
        kept = out[row] > smp.NEG_INF / 2
        k_eff = 12 if (k <= 0 or k > 12) else k
        expected = np.zeros(12, bool)
        expected[np.argsort(-logits[row])[:k_eff]] = True
        np.testing.assert_array_equal(kept, expected)
        np.testing.assert_allclose(out[row][kept], logits[row][expected])


def test_top_p_mask_numpy_parity():
    rs = np.random.RandomState(4)
    logits = rs.randn(3, 10).astype(np.float32)
    ps = np.array([0.5, 0.9, 1.0], np.float32)
    out = np.asarray(smp.top_p_mask(logits, ps))
    for row, p in zip(range(3), ps):
        order = np.argsort(-logits[row])
        probs = np.exp(logits[row][order] - logits[row].max())
        probs = probs / probs.sum()
        exclusive = np.cumsum(probs) - probs
        keep_sorted = (exclusive < p)
        keep_sorted[0] = True
        expected = np.zeros(10, bool)
        expected[order[keep_sorted]] = True
        kept = out[row] > smp.NEG_INF / 2
        np.testing.assert_array_equal(kept, expected)


def test_temperature_scale_and_greedy():
    logits = np.array([[1.0, 5.0, 2.0]], np.float32)
    np.testing.assert_allclose(
        np.asarray(smp.temperature_scale(logits, 2.0)), logits / 2.0)
    # temperature <= 0 passes through (greedy branch uses raw logits)
    np.testing.assert_allclose(
        np.asarray(smp.temperature_scale(logits, 0.0)), logits)
    assert int(get_op("sample_greedy").fn(logits)[0]) == 1


def test_sample_logits_deterministic_and_in_support():
    rs = np.random.RandomState(5)
    logits = rs.randn(6, 20).astype(np.float32)
    seeds = np.arange(6, dtype=np.uint32)
    counters = np.full(6, 7, np.uint32)
    t = np.full(6, 0.8, np.float32)
    k = np.full(6, 4, np.int32)
    p = np.full(6, 1.0, np.float32)
    a = np.asarray(smp.sample_logits(logits, seeds, counters, t, k, p))
    b = np.asarray(smp.sample_logits(logits, seeds, counters, t, k, p))
    np.testing.assert_array_equal(a, b)      # same key -> same draw
    c = np.asarray(smp.sample_logits(logits, seeds, counters + 1, t, k, p))
    assert not np.array_equal(a, c)          # next position -> fresh draw
    for row in range(6):                     # only top-4 tokens are eligible
        assert a[row] in np.argsort(-logits[row])[:4]
    # temperature 0 rows are exact greedy regardless of k/p
    g = np.asarray(smp.sample_logits(logits, seeds, counters,
                                     np.zeros(6, np.float32), k, p))
    np.testing.assert_array_equal(g, np.argmax(logits, axis=-1))


def test_sampling_registry_ops():
    rs = np.random.RandomState(6)
    logits = rs.randn(3, 16).astype(np.float32)
    key = jax.random.PRNGKey(0)
    for name in ("sample_temperature", "sample_top_k", "sample_top_p",
                 "_sampling_top_k", "_sampling_top_p"):
        op = get_op(name)
        assert op.rng and not op.differentiable
    tk = get_op("sample_top_k").fn(logits, rng_key=key, k=2, temperature=1.0)
    for row in range(3):
        assert int(tk[row]) in np.argsort(-logits[row])[:2]
    a = get_op("sample_temperature").fn(logits, rng_key=key, temperature=0.7)
    b = get_op("sample_temperature").fn(logits, rng_key=key, temperature=0.7)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the sampler's three bodies (ops/sampling.py) -----------------------------------
def _two_sort_masks(logits, k, p):
    """The oracle: top-k then top-p as the sampler composed them before it
    had bodies, a sort over the vocabulary each (``p >= 1`` returns the
    top-k mask as it is: the documented bypass)."""
    logits = jnp.asarray(logits, jnp.float32)
    vocab = logits.shape[-1]
    kk = jnp.broadcast_to(jnp.asarray(k, jnp.int32), logits.shape[:-1])
    kk = jnp.where((kk <= 0) | (kk > vocab), vocab, kk)
    sorted_desc = -jnp.sort(-logits, axis=-1)
    thresh = jnp.take_along_axis(sorted_desc, (kk - 1)[..., None], axis=-1)
    top_k = jnp.where(logits >= thresh, logits, smp.NEG_INF)

    pp = jnp.broadcast_to(jnp.asarray(p, jnp.float32),
                          logits.shape[:-1])[..., None]
    sorted_desc = -jnp.sort(-top_k, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    keep = (exclusive < pp) | (jnp.arange(vocab) == 0)
    count = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)
    thresh = jnp.take_along_axis(sorted_desc, count - 1, axis=-1)
    top_p = jnp.where(top_k >= thresh, top_k, smp.NEG_INF)
    return np.asarray(jnp.where(pp >= 1, top_k, top_p))


def _tied_logits(seed, rows=6, vocab=48):
    """Random logits on a grid of 0.5, so that most values repeat, with a
    run of equal values planted around every rank a filter can cut at."""
    rs = np.random.RandomState(seed)
    logits = (np.round(rs.randn(rows, vocab) * 4) / 2).astype(np.float32)
    logits[1, :8] = logits[1].max() + 1.0           # eight tied maxima
    logits[2, 3:9] = np.sort(logits[2])[-4]         # ties at the 4th value
    logits[3] = 0.25                                # a flat row
    return logits


_SAMPLER_VOCAB = 48


@pytest.mark.parametrize("p", [0.0, 0.5, 0.9, 1.0])
@pytest.mark.parametrize("k", [0, 1, 4, _SAMPLER_VOCAB, _SAMPLER_VOCAB + 1])
def test_one_sort_masks_are_the_two_sort_composition(k, p):
    """(a) body ``filter``'s masks, from ONE sort, equal top-k then top-p
    with a sort each, bit for bit, ties included."""
    logits = _tied_logits(17 + k)
    want = _two_sort_masks(logits, k, p)
    np.testing.assert_array_equal(
        np.asarray(smp.top_k_top_p_mask(logits, k, p)), want)
    # per-row knobs: every row its own, the off values among them
    ks = np.asarray([k, 0, 4, k, 1, _SAMPLER_VOCAB + 1], np.int32)
    ps = np.asarray([p, p, 0.9, 1.0, p, 0.5], np.float32)
    np.testing.assert_array_equal(
        np.asarray(smp.top_k_top_p_mask(logits, ks, ps)),
        _two_sort_masks(logits, ks, ps))
    # the masks one at a time keep their results
    np.testing.assert_array_equal(np.asarray(smp.top_k_mask(logits, k)),
                                  _two_sort_masks(logits, k, 1.0))
    np.testing.assert_array_equal(np.asarray(smp.top_p_mask(logits, p)),
                                  _two_sort_masks(logits, 0, p))


def _rounding_tail_row(vocab=_SAMPLER_VOCAB):
    """Two likely tokens and a tail whose whole mass is ~1e-7: a tail
    token's exclusive prefix mass rounds to 1.0, so ``exclusive < 1.0``
    would cut what ``top_p = 1`` must keep."""
    row = np.full(vocab, -10.0, np.float32)
    row[[5, 11]] = 10.0
    return row


def test_top_p_one_keeps_a_tail_whose_mass_rounds_to_one():
    row = _rounding_tail_row()[None]
    sorted_desc = -np.sort(-row, axis=-1)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(sorted_desc), axis=-1))
    assert (np.cumsum(probs, axis=-1) - probs)[0, -1] >= 1.0   # the case
    np.testing.assert_array_equal(np.asarray(smp.top_p_mask(row, 1.0)), row)
    np.testing.assert_array_equal(
        np.asarray(smp.top_k_top_p_mask(row, 0, 1.0)), row)
    cut = np.asarray(smp.top_p_mask(row, 0.999))
    assert (cut > smp.NEG_INF / 2).sum() == 2


# the row under test -> (temperature, top_k, top_p); the hot temperature
# makes the rounding tail likely once the filter has let it through
_SAMPLER_ROWS = {
    "temperature": (0.8, 0, 1.0),
    "top_p_one_rounding_tail": (50.0, 0, 1.0),
    "top_k": (0.9, 4, 1.0),
    "top_k_and_top_p": (1.3, 12, 0.9),
}
# its neighbours -> their (temperature, top_k, top_p), and the body the
# batch then takes when the row itself has no filter on
_SAMPLER_NEIGHBOURS = {
    "none": ([], "draw"),
    "greedy": ([(0.0, 3, 0.5), (0.0, 0, 1.0)], "draw"),
    "temperature": ([(0.7, 0, 1.0), (1.5, _SAMPLER_VOCAB, 1.0)], "draw"),
    "top_p": ([(0.0, 0, 1.0), (0.7, 0, 0.9)], "filter"),
}


@pytest.mark.parametrize("neighbours", sorted(_SAMPLER_NEIGHBOURS))
@pytest.mark.parametrize("row", sorted(_SAMPLER_ROWS))
def test_sampled_token_is_its_rows_alone_whatever_body_the_batch_takes(
        row, neighbours):
    """(b) a sampling row's token is Gumbel-max over ITS filtered, scaled
    logits under ITS (seed, position) key — the same under ``draw`` and
    ``filter``, alone and beside greedy, sampling and filtering rows; a
    greedy neighbour's is the raw argmax beside any of them."""
    t, k, p = _SAMPLER_ROWS[row]
    others, body = _SAMPLER_NEIGHBOURS[neighbours]
    rs = np.random.RandomState(23)
    logits = (np.round(rs.randn(1 + len(others), _SAMPLER_VOCAB) * 4)
              / 2).astype(np.float32)
    if row == "top_p_one_rounding_tail":
        logits[0] = _rounding_tail_row()
    knobs = [(t, k, p)] + others
    ts, ks, ps = (np.asarray(a, dt) for a, dt in zip(
        zip(*knobs), (np.float32, np.int32, np.float32)))
    if k == 0 and p == 1.0:
        assert smp.SAMPLER_BODIES[int(smp.sampler_body(
            ts, ks, ps, _SAMPLER_VOCAB))] == body
    seeds = np.arange(40, 40 + len(knobs), dtype=np.uint32)
    tails = []
    for counter in range(1, 9):
        counters = np.full(len(knobs), counter, np.uint32)
        got = np.asarray(smp.sample_logits(logits, seeds, counters, ts, ks,
                                           ps))
        key = jax.random.fold_in(jax.random.PRNGKey(40), counter)
        gumbel = jax.random.gumbel(key, (_SAMPLER_VOCAB,))
        kept = _two_sort_masks(logits[:1], k, p)[0]
        want = int(jnp.argmax(jnp.asarray(kept) / np.float32(t) + gumbel))
        assert got[0] == want
        tails.append(logits[0, want] < 0)
        for j, (tj, _, _) in enumerate(others, 1):
            if tj <= 0:
                assert got[j] == int(np.argmax(logits[j]))
    if row == "top_p_one_rounding_tail":
        assert any(tails)       # the tail is drawn from, in every body


def _count_sorts(jaxpr, in_cond=False):
    """(sort equations outside any conditional, inside one) of a jaxpr,
    walked through every sub-jaxpr an equation carries."""
    outside = inside = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            outside, inside = (outside + (not in_cond), inside + in_cond)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    o, i = _count_sorts(
                        sub, in_cond or eqn.primitive.name == "cond")
                    outside, inside = outside + o, inside + i
    return outside, inside


@pytest.mark.parametrize("kind", ["gen_decode", "gen_verify"])
def test_a_sampling_program_sorts_once_and_only_in_a_branch(params, kind,
                                                             monkeypatch):
    """(c) a sampling program holds ONE sort over the vocabulary, inside a
    conditional's branch (two, unconditional, before the bodies); the
    greedy branch of the same program has none."""
    import functools

    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS", "0")
    progs = gp.GenerationPrograms(params, CFG)
    cache = PagedKVCache(num_blocks=16, block_size=8, n_layers=CFG.n_layers,
                         n_heads=CFG.n_heads, d_head=CFG.d_head,
                         dtype=jnp.float32)
    S, T = 3, {"gen_decode": 1, "gen_verify": 4}[kind]
    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    fn, _ = progs._kinds[kind]
    args = (progs._params, cache.pools, z(S, T), z(S, T), z(S), z(S, 4),
            z(S).astype(np.uint32), z(S).astype(np.uint32),
            z(S).astype(np.float32), z(S), np.ones(S, np.float32))
    step = functools.partial(fn, **progs._step_kw)
    assert _count_sorts(jax.make_jaxpr(step)(*args).jaxpr) == (0, 1)
    text = jax.jit(step).lower(*args).as_text()
    assert text.count("stablehlo.sort") == 1 and "stablehlo.case" in text


def test_greedy_step_returns_the_first_of_tied_maxima():
    """(c) an all-greedy call is ``argmax`` of the raw logits: the first
    of the tied, whatever ``top_k`` / ``top_p`` the rows carry."""
    logits = _tied_logits(29)
    n = len(logits)
    assert (logits[1] == logits[1].max()).sum() == 8
    got = np.asarray(smp.sample_logits(
        logits, np.arange(n, dtype=np.uint32), np.full(n, 3, np.uint32),
        np.zeros(n, np.float32), np.full(n, 4, np.int32),
        np.full(n, 0.5, np.float32)))
    np.testing.assert_array_equal(got, np.argmax(logits, axis=-1))
    assert got[1] == 0 and got[3] == 0


def _sampler_steps(where):
    if isinstance(where, dict):
        return {b: where["counts"][f"sampler_steps_{b}"]
                for b in smp.SAMPLER_BODIES}
    text = obs.registry().to_prometheus()
    return {b: float(next(
        line.rsplit(" ", 1)[1] for line in text.splitlines()
        if line.startswith(f'serving_sampler_steps_total{{body="{b}"}}')))
        for b in smp.SAMPLER_BODIES}


@pytest.mark.parametrize("traffic", ["greedy", "mixed"])
def test_sampler_steps_are_counted_by_body(params, traffic):
    """(d) ``stats()["counts"]["sampler_steps_*"]`` and
    ``serving_sampler_steps_total{body=...}``: a greedy-only service
    counts ``greedy`` steps alone; one that is sent every kind of request
    counts each body, as often as its programs were called."""
    svc = GenerationService(params, CFG, _gc(max_slots=1), start=False)
    svc.warmup()
    assert sum(_sampler_steps(svc.stats()).values()) == 0   # not warm-up's
    before = _sampler_steps("registry")
    svc.start()
    try:
        prompt = np.arange(1, 8)
        svc.generate(prompt, max_new_tokens=5, timeout=120)
        if traffic == "mixed":
            svc.generate(prompt, max_new_tokens=4, temperature=0.8, seed=2,
                         top_k=CFG.vocab, timeout=120)
            svc.generate(prompt, max_new_tokens=3, temperature=0.8, seed=2,
                         top_p=0.9, timeout=120)
            svc.generate(prompt, max_new_tokens=2, temperature=0.8, seed=2,
                         top_k=5, timeout=120)
    finally:
        svc.stop()
    # a request of n tokens: its prefill chunk and n - 1 decode steps
    want = {"greedy": 5, "draw": 0, "filter": 0} if traffic == "greedy" \
        else {"greedy": 5, "draw": 4, "filter": 5}
    assert _sampler_steps(svc.stats()) == want
    after = _sampler_steps("registry")
    assert {b: after[b] - before[b] for b in want} == want


# -- satellite/acceptance: paged-cache correctness ----------------------------------
@pytest.mark.parametrize("compute_dtype", [None, "bfloat16"])
def test_decode_with_cache_matches_full_apply(params, compute_dtype):
    """Prefill + single-token decode steps across block boundaries
    reproduce full-sequence transformer_lm_apply logits (rtol 1e-5), in
    f32 and under the bf16 AMP dtype."""
    dt = None if compute_dtype is None else jnp.dtype(compute_dtype)
    oracle_params = params if dt is None else jax.tree_util.tree_map(
        lambda p: p.astype(dt), params)
    rs = np.random.RandomState(0)
    plen, n_steps, bs = 13, 7, 8      # prompt spans blocks 0-1, decode
    prompt = rs.randint(0, CFG.vocab, plen)   # crosses into block 2 (pos 16)
    pool_dt = dt or jnp.float32
    kp = jnp.zeros((CFG.n_layers, 16, bs, CFG.d_model), pool_dt)
    vp = jnp.zeros_like(kp)
    table = np.array([[1, 2, 3]], np.int32)
    tb = 16

    def apply_padded(seq):
        # op by op like the decode calls (a jitted pass rounds bf16
        # elsewhere), at ONE length: a new length compiles every op anew
        out = tr.transformer_lm_apply(
            oracle_params,
            pad_tokens_right(np.asarray(seq, np.int32), CFG.max_len)[None, :],
            jnp.arange(CFG.max_len, dtype=jnp.int32), CFG)
        return np.asarray(out[0, :len(seq)].astype(jnp.float32))

    logits, kp, vp = tr.transformer_lm_decode(
        params, pad_tokens_right(prompt.astype(np.int32), tb)[None, :],
        np.arange(tb, dtype=np.int32)[None, :],
        np.asarray([plen], np.int32), kp, vp, table[:, :2], CFG,
        compute_dtype=dt)
    full = apply_padded(prompt)
    np.testing.assert_allclose(np.asarray(logits[0, :plen]), full,
                               rtol=1e-5, atol=1e-5)
    toks = list(prompt)
    last = logits[0, plen - 1]
    for step in range(n_steps):
        nxt = int(jnp.argmax(last))
        toks.append(nxt)
        pos = len(toks) - 1
        logits, kp, vp = tr.transformer_lm_decode(
            params, np.asarray([[nxt]], np.int32),
            np.asarray([[pos]], np.int32), np.asarray([1], np.int32),
            kp, vp, table, CFG, compute_dtype=dt)
        last = logits[0, 0]
        np.testing.assert_allclose(np.asarray(last), apply_padded(toks)[-1],
                                   rtol=1e-5, atol=1e-5)
    assert len(toks) > 16, "test must cross a block boundary"


def test_inactive_slots_do_not_corrupt_cache(params):
    """A decode step with inactive (length 0) slots writes only to the
    reserved null block 0."""
    bs = 8
    kp = jnp.zeros((CFG.n_layers, 8, bs, CFG.d_model))
    vp = jnp.zeros_like(kp)
    # fill block 1 via an active row, with a garbage inactive row alongside
    toks = np.array([[5], [7]], np.int32)
    pos = np.array([[0], [3]], np.int32)
    lengths = np.array([1, 0], np.int32)
    tables = np.array([[1], [2]], np.int32)
    _, kp, vp = tr.transformer_lm_decode(params, toks, pos, lengths,
                                         kp, vp, tables, CFG)
    assert float(jnp.abs(kp[:, 1, 0]).sum()) > 0   # active row wrote
    assert float(jnp.abs(kp[:, 2]).sum()) == 0.0   # inactive row did NOT


# -- block allocator ----------------------------------------------------------------
def test_block_allocator_semantics():
    a = BlockAllocator(8)                  # blocks 1..7 allocatable
    assert a.num_free == 7
    got = a.allocate(3)
    assert len(got) == 3 and 0 not in got
    assert a.allocate(5) is None           # all-or-nothing
    assert a.num_free == 4
    a.free(got)
    assert a.num_free == 7
    with pytest.raises(ValueError):
        a.free(got)                        # double free
    with pytest.raises(ValueError):
        a.free([0])                        # null block is unallocatable
    assert blocks_for(17, 8) == 3 and blocks_for(16, 8) == 2
    assert blocks_for(1, 8) == 1


def test_paged_cache_shapes():
    c = PagedKVCache(n_layers=2, n_heads=4, d_head=8, num_blocks=16,
                     block_size=4)
    assert c.shape == (2, 16, 4, 4 * 8)
    assert c.max_positions() == 15 * 4
    assert c.blocks_for(5) == 2


# -- acceptance: continuous batching ------------------------------------------------
def test_continuous_batching_membership_and_greedy_parity(params):
    """>= 3 overlapping requests on 2 slots: the short request finishes
    and the queued one is admitted while the long one is still decoding,
    and every streamed token equals single-request greedy decoding."""
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (11, 20, 5)]
    new = [8, 3, 6]
    handles = [svc.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new)]
    svc.start()
    results = [h.result(60) for h in handles]
    svc.stop()

    for got, p, n in zip(results, prompts, new):
        assert got == greedy_oracle(params, p, n)

    member = [set(m) for _, m in svc.membership_history()]
    # requests 0 and 1 share the batch; 2 joins only after 1 leaves
    assert {0, 1} in member
    assert {0, 2} in member
    # iteration-level: the transition happens while 0 is STILL decoding
    i01 = member.index({0, 1})
    i02 = member.index({0, 2})
    assert i02 > i01
    assert all(0 in m for m in member[i01:i02 + 1])


def test_streaming_iterator_and_callback(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    seen = []
    h = svc.submit(np.arange(5) % CFG.vocab, max_new_tokens=4,
                   on_token=lambda rid, tok: seen.append((rid, tok)))
    svc.start()
    streamed = list(h)
    svc.stop()
    assert streamed == h.result()
    assert [t for _, t in seen] == streamed
    assert h.finish_reason == "max_new_tokens"
    assert h.ttft_ms is not None and h.ttft_ms >= 0


def test_eos_token_stops_early(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    # discover what greedy emits first, then use it as the eos token
    probe = svc.submit(np.arange(7) % CFG.vocab, max_new_tokens=1)
    svc.start()
    first = probe.result(60)[0]
    h = svc.submit(np.arange(7) % CFG.vocab, max_new_tokens=8,
                   eos_token=first)
    out = h.result(60)
    svc.stop()
    assert out == [first]
    assert h.finish_reason == "eos"


def test_seeded_sampling_independent_of_batch_composition(params):
    """A sampled request's tokens depend only on (seed, position) — never
    on which requests share its decode slots."""
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, CFG.vocab, 9)
    kw = dict(max_new_tokens=6, temperature=0.9, top_k=8, seed=123)

    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    h = svc.submit(prompt, **kw)
    svc.start()
    alone = h.result(60)
    svc.stop()

    svc2 = GenerationService(params, CFG, _gc(), start=False)
    svc2.warmup()
    hs = [svc2.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=5,
                      temperature=0.5, seed=n)
          for n in (6, 14)]
    h2 = svc2.submit(prompt, **kw)
    svc2.start()
    crowded = h2.result(60)
    [h.result(60) for h in hs]
    svc2.stop()
    assert alone == crowded


# -- acceptance: zero steady-state recompiles ---------------------------------------
def test_zero_recompiles_under_freeze(params, monkeypatch):
    """After warmup, a mixed stream of staggered-length concurrent requests
    runs under TPUMX_FREEZE_COMPILES=1 with every (prefill-bucket, decode)
    program site showing 1 miss + N hits."""
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(2)
    lens = [3, 16, 29, 9, 22, 5, 31, 12]
    handles = []
    svc.start()
    for i, n in enumerate(lens):
        handles.append(svc.submit(rs.randint(0, CFG.vocab, n),
                                  max_new_tokens=3 + (i % 5),
                                  temperature=0.5 * (i % 2), seed=i))
        if i % 3 == 0:
            time.sleep(0.01)     # stagger arrivals across iterations
    for h in handles:
        h.result(120)
    stats = svc.compile_stats()
    svc.stop()
    assert stats, "no programs recorded"
    for key, st in stats.items():
        assert st["misses"] == 1, f"recompile at {key}: {st}"
    # every prefill (one per request) and every decode iteration was a hit
    prefill_hits = sum(st["hits"] for key, st in stats.items()
                       if key[0] == "gen_prefill")
    decode_hits = sum(st["hits"] for key, st in stats.items()
                      if key[0] == "gen_decode")
    assert prefill_hits >= len(lens)
    assert decode_hits >= max(3 + (i % 5) for i in range(len(lens))) - 1


def test_post_warmup_miss_raises_under_freeze(params, monkeypatch):
    """A program signature outside the warmed set must raise (not compile)
    when frozen — the watchdog guards the decode loop."""
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    with pytest.raises(obs.FreezeCompilesError):
        # a batch-3 prefill was never warmed (service always uses B=1)
        svc._programs.run(
            "gen_prefill", svc._cache, np.zeros((3, 16), np.int32),
            np.zeros((3, 16), np.int32), np.zeros(3, np.int32),
            np.zeros((3, 2), np.int32), np.zeros(3, np.uint32),
            np.zeros(3, np.uint32), np.zeros(3, np.float32),
            np.zeros(3, np.int32), np.ones(3, np.float32))
    svc.stop()


# -- scheduling: waiting on cache space, deadlines, backpressure --------------------
def test_admission_waits_for_kv_blocks(params):
    """With a pool too small for two concurrent requests, the second waits
    until the first finishes and frees its blocks — not an error."""
    # 9 allocatable blocks of 8 positions; each request reserves
    # blocks_for(20 + 12) = 4 -> two fit, three do not
    svc = GenerationService(params, CFG,
                            _gc(max_slots=3, num_blocks=10), start=False)
    svc.warmup()
    rs = np.random.RandomState(3)
    hs = [svc.submit(rs.randint(0, CFG.vocab, 20), max_new_tokens=12)
          for _ in range(3)]
    svc.start()
    outs = [h.result(120) for h in hs]
    svc.stop()
    assert all(len(o) == 12 for o in outs)
    member = [set(m) for _, m in svc.membership_history()]
    assert not any({0, 1, 2} <= m for m in member), \
        "all three requests should never decode together (blocks don't fit)"
    assert any(2 in m for m in member)


def test_overlong_prompt_rejected_at_submit(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    with pytest.raises(ValueError, match="exceeds the largest"):
        svc.submit(np.zeros(33, np.int32))         # > top bucket 32
    with pytest.raises(ValueError, match="max_len"):
        svc.submit(np.zeros(30, np.int32), max_new_tokens=120)
    with pytest.raises(ValueError):
        svc.submit(np.zeros(0, np.int32))
    svc.stop()


def test_backpressure_reject_and_deadline(params):
    svc = GenerationService(params, CFG,
                            _gc(queue_bound=2, backpressure="reject"),
                            start=False)
    svc.warmup()
    h1 = svc.submit(np.arange(4), max_new_tokens=2)
    h2 = svc.submit(np.arange(4), max_new_tokens=2)
    with pytest.raises(QueueFullError):
        svc.submit(np.arange(4), max_new_tokens=2)
    # an already-expired deadline fails in queue without touching the device
    h3 = None
    svc._waiting.popleft()    # make room for the deadline probe
    svc._waiting.popleft()
    h3 = svc.submit(np.arange(4), max_new_tokens=2, deadline_ms=0.0)
    svc.start()
    with pytest.raises(DeadlineExceededError):
        h3.result(60)
    svc.stop()
    assert h1 is not None and h2 is not None


def test_cancel_waiting_and_running(params):
    svc = GenerationService(params, CFG, _gc(max_slots=1), start=False)
    svc.warmup()
    h1 = svc.submit(np.arange(8), max_new_tokens=40)
    h2 = svc.submit(np.arange(8), max_new_tokens=4)   # queued behind h1
    h2.cancel()
    svc.start()
    time.sleep(0.05)
    h1.cancel()
    assert h2.result(60) == []
    assert h2.finish_reason == "cancelled"
    out1 = h1.result(60)
    svc.stop()
    assert h1.finish_reason in ("cancelled", "max_new_tokens")
    assert len(out1) <= 40


def test_submit_after_stop_raises(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.stop()
    with pytest.raises(ServingClosedError):
        svc.submit(np.arange(4))


def test_drain_completes_backlog(params):
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    hs = [svc.submit(np.arange(5), max_new_tokens=3) for _ in range(4)]
    svc.start()
    svc.stop(drain=True, timeout=120)
    assert all(h.finished for h in hs)
    assert all(len(h.result(1)) == 3 for h in hs)


# -- amp + observability integration ------------------------------------------------
def test_amp_bf16_service_matches_bf16_oracle(params):
    """amp_dtype='bfloat16' serves the cast graph: engine tokens equal
    greedy decoding over the bf16-cast full-sequence model."""
    svc = GenerationService(params, CFG, _gc(amp_dtype="bfloat16"),
                            start=False)
    assert str(svc._cache.dtype) == "bfloat16"
    svc.warmup()
    rs = np.random.RandomState(4)
    prompt = rs.randint(0, CFG.vocab, 10)
    h = svc.submit(prompt, max_new_tokens=5)
    svc.start()
    got = h.result(60)
    svc.stop()
    cast = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), params)
    assert got == greedy_oracle(cast, prompt, 5)


def test_observability_wiring(params):
    obs.reset()
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    h = svc.submit(np.arange(6), max_new_tokens=4)
    svc.start()
    h.result(60)
    svc.stop()
    snap = obs.snapshot()
    names = {m["name"] for m in snap["metrics"]} \
        if isinstance(snap.get("metrics"), list) else set(snap)
    flat = repr(snap)
    for metric in ("generation_tokens_total", "generation_ttft_seconds",
                   "generation_kv_block_occupancy",
                   "generation_running_requests"):
        assert metric in flat, f"{metric} missing from registry snapshot"
    st = svc.stats()
    assert st["counts"]["tokens"] == 4
    assert st["ttft_ms"]["p50"] is not None
    assert st["kv_blocks"]["used"] == 0      # all freed after finish
    del names


def test_service_stats_and_compile_sites(params):
    from mxnet_tpu import executor as _executor

    _executor.reset_compile_cache_stats()
    svc = GenerationService(params, CFG, _gc(), start=False)
    svc.warmup()
    h = svc.submit(np.arange(9), max_new_tokens=3)
    svc.start()
    h.result(60)
    svc.stop()
    by_site = _executor.compile_cache_stats()["by_site"]
    assert "gen_prefill" in by_site and "gen_decode" in by_site
    assert by_site["gen_prefill"]["hits"] >= 1     # the real prefill
    assert by_site["gen_decode"]["hits"] >= 1

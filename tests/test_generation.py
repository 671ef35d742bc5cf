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

pytestmark = pytest.mark.generation

CFG = tr.TransformerConfig(vocab=40, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64)


@pytest.fixture(autouse=True)
def _fresh_observability():
    """Generation warmup calls mark_warm(); keep the freeze/explainer state
    from leaking across tests."""
    yield
    obs.recompile.reset()


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


def _greedy_oracle(params, prompt, n_new):
    """Full-sequence greedy decoding via transformer_lm_apply — no cache."""
    toks = [int(t) for t in prompt]
    for _ in range(n_new):
        logits = tr.transformer_lm_apply(
            params, jnp.asarray([toks], dtype=jnp.int32),
            jnp.arange(len(toks), dtype=jnp.int32), CFG)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


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


@pytest.mark.parametrize("kind", ["gen_decode", "gen_verify",
                                  "gen_multistep"])
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
    S, T = 3, {"gen_decode": 1, "gen_verify": 4}.get(kind)
    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    shape = (S, T) if T else (S,)
    fn, _ = progs._kinds[kind]
    kw = dict(progs._step_kw, **({} if T else {"k": 4}))
    args = (progs._params, cache.pools, z(*shape), z(*shape), z(S), z(S, 4),
            z(S).astype(np.uint32), z(S).astype(np.uint32),
            z(S).astype(np.float32), z(S), np.ones(S, np.float32))
    step = functools.partial(fn, **kw)
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
    logits, kp, vp = tr.transformer_lm_decode(
        params, pad_tokens_right(prompt.astype(np.int32), tb)[None, :],
        np.arange(tb, dtype=np.int32)[None, :],
        np.asarray([plen], np.int32), kp, vp, table[:, :2], CFG,
        compute_dtype=dt)
    full = tr.transformer_lm_apply(
        oracle_params, jnp.asarray([prompt], dtype=jnp.int32),
        jnp.arange(plen, dtype=jnp.int32), CFG).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(logits[0, :plen]),
                               np.asarray(full[0]), rtol=1e-5, atol=1e-5)
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
        full = tr.transformer_lm_apply(
            oracle_params, jnp.asarray([toks], dtype=jnp.int32),
            jnp.arange(len(toks), dtype=jnp.int32), CFG
        ).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(last), np.asarray(full[0, -1]),
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
        assert got == _greedy_oracle(params, p, n)

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
    assert got == _greedy_oracle(cast, prompt, 5)


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


# -- satellite: chunked prefill (docs/generation.md, PR 8) --------------------------
def test_chunk_plan_shapes(params):
    """Long prompts split into rung-sized chunks; short prompts and
    chunking-off stay on the legacy single-rung plan."""
    svc = GenerationService(params, CFG, _gc(chunked_prefill=True),
                            start=False)
    assert svc._chunk_plan(9) == [(0, 9, 16, blocks_for(16, 8))]
    plan = svc._chunk_plan(30)
    assert [c[:2] for c in plan] == [(0, 16), (16, 14)]
    assert all(take <= tb for (_, take, tb, _) in plan)
    # chunk widths cover every written position
    for (off, take, tb, w) in plan:
        assert w * 8 >= off + take
    off_svc = GenerationService(params, CFG, _gc(chunked_prefill=False),
                                start=False)
    assert off_svc._chunk_plan(30) == [(0, 30, 32, blocks_for(32, 8))]
    svc.stop()
    off_svc.stop()


def test_chunked_prefill_matches_unchunked_and_oracle(params):
    """Greedy generations are identical with chunking on and off, and both
    match the no-cache full-sequence oracle."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (3, 17, 25, 30, 16)]

    def run(chunked):
        svc = GenerationService(params, CFG,
                                _gc(chunked_prefill=chunked), start=False)
        svc.warmup()
        svc.start()
        outs = [svc.generate(p, max_new_tokens=6, temperature=0.0)
                for p in prompts]
        svc.stop()
        return outs

    on, off = run(True), run(False)
    assert on == off
    for p, toks in zip(prompts, on):
        assert toks == _greedy_oracle(params, p, 6)


def test_chunked_prefill_sampled_tokens_identical(params):
    """The final chunk samples with the same seed/counter as the unchunked
    program — temperature>0 tokens are bit-identical too."""
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, CFG.vocab, 29)

    def run(chunked):
        svc = GenerationService(params, CFG,
                                _gc(chunked_prefill=chunked), start=False)
        svc.start()
        out = svc.generate(prompt, max_new_tokens=8, temperature=0.9,
                           top_k=10, seed=123)
        svc.stop()
        return out

    assert run(True) == run(False)


def test_chunked_prefill_zero_postwarmup_compiles(params, monkeypatch):
    """Warmup enumerates every (T, W) pair the chunk planner can emit:
    long prompts then run under TPUMX_FREEZE_COMPILES=1 with 1 miss per
    signature."""
    svc = GenerationService(params, CFG, _gc(chunked_prefill=True),
                            start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(11)
    svc.start()
    handles = [svc.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=4)
               for n in (31, 17, 24, 30, 5)]
    for h in handles:
        assert len(h.result(60)) == 4
    stats = svc.compile_stats()
    svc.stop()
    monkeypatch.delenv("TPUMX_FREEZE_COMPILES")
    assert all(v["misses"] == 1 for v in stats.values())


def test_generation_mp_axis_matches_single_device(params):
    """GenerationConfig(mp_devices=2): params live sharded over the mp
    mesh (docs/sharding.md) and greedy decoding matches mp=1."""
    rs = np.random.RandomState(7)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (4, 19, 30)]

    def run(mp):
        svc = GenerationService(params, CFG, _gc(mp_devices=mp),
                                start=False)
        if mp > 1:
            emb = svc._programs._params["tok_emb"]
            assert len(emb.sharding.device_set) == mp
        svc.start()
        outs = [svc.generate(p, max_new_tokens=5, temperature=0.0)
                for p in prompts]
        svc.stop()
        return outs

    assert run(2) == run(1)


def test_gpt2_programs_through_the_model_seam_are_the_parents():
    """GPT-2's block is the first model behind the engine's seam
    (``programs.as_model``): at the benchmark cell's ladder and pool
    geometry it warms the 15 programs the parent compiled — the same
    kinds and signatures, no block-diffusion program among them."""
    from mxnet_tpu.parallel.transformer import TransformerLM
    from mxnet_tpu.serving.generation.programs import as_model

    cfg = tr.TransformerConfig(vocab=61, d_model=16, n_heads=2,
                               n_layers=1, d_ff=32, max_len=1024)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    model = as_model(cfg)
    assert isinstance(model, TransformerLM) and as_model(model) is model
    assert (model.vocab, model.max_len, model.heads, model.block_len) \
        == (61, 1024, 2, 0)
    assert model.cache_spec() == dict(n_layers=1, n_heads=2, d_head=8,
                                      dtype=jnp.float32)
    with pytest.raises(TypeError):
        as_model(object())
    svc = GenerationService(params, cfg, GenerationConfig(
        max_slots=32, block_size=16, num_blocks=64,
        seq_buckets=(128, 512, 1023)), start=False)
    assert svc.warmup() == 15
    pool = ("kv_pool", (1, 64, 16, 16), "float32")
    want = {("gen_prefill", (("tokens", (1, t), "int32"),
                             ("block_tables", (1, w), "int32"), pool))
            for t, w in ((128, 8), (128, 16), (128, 32), (128, 64),
                         (512, 32), (512, 64), (1023, 64))}
    want |= {("gen_decode", (("tokens", (32, 1), "int32"),
                             ("block_tables", (32, w), "int32"), pool))
             for w in (1, 2, 4, 8, 16, 32, 64)}
    want |= {("gen_block_copy", (pool,))}
    assert set(svc.compile_stats()) == want
    assert svc.stats()["decode_mode"] == "single"
    assert svc.stats()["block_diffusion"] is None


# -- one serving step: the batch builder and the program kinds ----------------------
_X12, _Z30, _M = 13, 31, 39     # the pending tokens of rows X and Z; a MASK id

# case -> (T, what a row feeds, positions written, sampler arrays asked for,
#          tokens and lengths of rows X (slot 0) and Z (slot 3), table width)
_BUILDER_CASES = {
    "single": (1, lambda r: [r.seq_tokens[r.ctx_len]], None, True,
               [_X12], 1, [_Z30], 1, 4),
    # drafts of unequal length: X proposes two, Z none; Tk buckets to 4
    "verify": (4, lambda r: [r.seq_tokens[r.ctx_len]] + {0: [7, 8]}.get(
        r.rid, []), None, True, [_X12, 7, 8, 0], 3, [_Z30, 0, 0, 0], 1, 4),
    # k = 4 writes 30 .. 33: a fifth block, so the width buckets to 8
    "multistep": (1, lambda r: [r.seq_tokens[r.ctx_len]], 4, True,
                  [_X12], 1, [_Z30], 1, 8),
    "block": (4, lambda r: r.block, None, False,
              [_X12, _M, _M, _M], 4, [_Z30, _M, _M, _M], 4, 8),
}


@pytest.mark.parametrize("case", sorted(_BUILDER_CASES))
def test_step_builder_against_arrays_written_by_hand(params, case):
    """The one batch builder of the four step kinds, on four slots — X, an
    empty one, Y outside the batch, Z — against every operand written out
    by hand: the rows, tokens / positions / lengths, the sampler arrays
    (none for the block step), the widest table bucketed on the pow2
    ladder, and the copy-on-write of X's shared tail block BEFORE it is
    written."""
    from mxnet_tpu.serving.generation.engine import _RUNNING, _GenRequest

    T, feed, writes, sampler, x_tok, x_len, z_tok, z_len, w = \
        _BUILDER_CASES[case]
    svc = GenerationService(params, CFG, _gc(max_slots=4), start=False)
    alloc = svc._cache.allocator

    def request(rid, ctx, n_blocks, **kw):
        r = _GenRequest(rid, list(range(1, ctx + 2)), 32, 16,
                        kw.get("temperature", 0.0), kw.get("top_k", 0),
                        kw.get("top_p", 1.0), kw.get("seed", 0), None, None,
                        None)
        r.state, r.ctx_len, r.blocks = _RUNNING, ctx, alloc.allocate(n_blocks)
        r.block = [r.seq_tokens[ctx]] + [_M] * 3
        return r

    x = request(0, 12, 3, seed=5, temperature=0.7, top_k=3, top_p=0.9)
    y = request(1, 20, 3, seed=7)
    z = request(2, 30, 5, seed=9)
    assert (x.blocks, y.blocks, z.blocks) == (
        [1, 2, 3], [4, 5, 6], [7, 8, 9, 10, 11])
    svc._slots[:] = [x, None, y, z]
    # X's tail block (positions 8 .. 15) is shared and holds history
    alloc.incref([2])
    k, v = svc._cache.pools
    svc._cache.swap((k.at[:, 2].set(1.5), v.at[:, 2].set(-2.5)))

    b = svc._build_step([x, z], T, feed, writes=writes, sampler=sampler)

    assert b.rows == [(0, x), (3, z)] and b.width == w
    assert x.blocks == [1, 12, 3] and x.cow_copies == 1
    assert alloc.refcount(2) == 1 and alloc.refcount(12) == 1
    np.testing.assert_array_equal(np.asarray(svc._cache.k)[:, 12], 1.5)
    np.testing.assert_array_equal(np.asarray(svc._cache.v)[:, 12], -2.5)
    zero = [0] * T
    np.testing.assert_array_equal(b.tokens, [x_tok, zero, zero, z_tok])
    np.testing.assert_array_equal(
        b.positions, [list(range(12, 12 + T)), zero, zero,
                      list(range(30, 30 + T))])
    np.testing.assert_array_equal(b.lengths, [x_len, 0, 0, z_len])
    pad = [0] * (w - 4)
    np.testing.assert_array_equal(
        b.tables, [([1, 12, 3, 0] + pad), [0] * w, [0] * w,
                   ([7, 8, 9, 10] + [11, 0, 0, 0][:w - 4])])
    for a in (b.tokens, b.positions, b.lengths, b.tables):
        assert a.dtype == np.int32
    if not sampler:
        assert b.sampler == () and len(b.operands) == 4
        return
    seeds, counters, temperature, top_k, top_p = b.sampler
    assert b.operands[4:] == b.sampler
    np.testing.assert_array_equal(seeds, np.asarray([5, 0, 0, 9], np.uint32))
    np.testing.assert_array_equal(counters,
                                  np.asarray([13, 0, 0, 31], np.uint32))
    np.testing.assert_array_equal(temperature,
                                  np.asarray([0.7, 0, 0, 0], np.float32))
    np.testing.assert_array_equal(top_k, np.asarray([3, 0, 0, 0], np.int32))
    np.testing.assert_array_equal(top_p,
                                  np.asarray([0.9, 1, 1, 1], np.float32))
    assert [a.dtype for a in b.sampler] == [
        np.uint32, np.uint32, np.float32, np.int32, np.float32]


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_a_program_kind_is_one_function_and_notes_the_parents_keys(
        params, monkeypatch, kv_dtype):
    """Every program kind is traced from ONE function whatever the pool
    (the cache's arrays travel as one operand), and a fixed script of calls
    feeds ``executor._note_cache`` the (hit, site, key) sequence the twin
    functions did (the tuples are the parent commit's output)."""
    from mxnet_tpu import executor
    from mxnet_tpu.serving.generation import programs as gp

    monkeypatch.setenv("TPUMX_PALLAS", "0")
    progs = gp.GenerationPrograms(params, CFG, kv_dtype=kv_dtype)
    assert {kind: fn for kind, (fn, _) in progs._kinds.items()} == {
        "gen_prefill": gp._model_step, "gen_decode": gp._model_step,
        "gen_verify": gp._verify_step, "gen_multistep": gp._multistep,
        "gen_block": gp._block_step, "gen_block_copy": gp.block_copy_pools}
    assert not any(hasattr(gp, name) for name in (
        "_model_step_q", "_verify_step_q", "_multistep_q"))
    cache = PagedKVCache(num_blocks=16, block_size=8, kv_dtype=kv_dtype,
                         n_layers=CFG.n_layers, n_heads=CFG.n_heads,
                         d_head=CFG.d_head, dtype=jnp.float32)
    assert len(cache.pools) == (4 if kv_dtype else 2)
    assert cache.pools[:2] == (cache.k, cache.v)
    seen = []
    note = executor._note_cache
    monkeypatch.setattr(
        executor, "_note_cache",
        lambda hit, site, key: (seen.append((hit, site, key)),
                                note(hit=hit, site=site, key=key))[1])
    S = 3
    z = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    knobs = lambda n: (z(n), z(n), z(n).astype(np.float32), z(n),  # noqa: E731
                       np.ones(n, np.float32))
    progs.run("gen_prefill", cache, z(1, 16), z(1, 16), z(1), z(1, 2),
              *knobs(1))
    for _ in range(2):
        progs.run("gen_decode", cache, z(S, 1), z(S, 1), z(S), z(S, 4),
                  *knobs(S))
    progs.run_verify(cache, z(S, 4), z(S, 4), z(S), z(S, 4), *knobs(S))
    progs.run_multistep(4, cache, z(S), z(S), z(S), z(S, 4), *knobs(S))
    for _ in range(2):
        progs.copy_block(cache, 0, 0)
    q = "_int8" if kv_dtype else ""
    pool = (("kv_pool", (2, 16, 8, 32), "int8" if kv_dtype else "float32"),)
    fam = pool + ((("kv_dtype", "int8"),) if kv_dtype else ())
    sig = lambda t, w: (("tokens", t, "int32"),  # noqa: E731
                        ("block_tables", w, "int32"))
    decode = ("gen_decode", sig((3, 1), (3, 4)) + fam)
    copy = ("gen_block_copy", fam)
    assert seen == [
        (False, ("gen_prefill" + q, ("lm",)),
         ("gen_prefill", sig((1, 16), (1, 2)) + fam)),
        (False, ("gen_decode" + q, ("lm",)), decode),
        (True, ("gen_decode" + q, ("lm",)), decode),
        (False, ("gen_verify" + q, ("lm",)),
         ("gen_verify", sig((3, 4), (3, 4)) + fam)),
        (False, ("gen_multistep" + q, ("lm",)),
         ("gen_multistep", sig((3,), (3, 4)) + fam + (("k", 4),))),
        (False, ("gen_block_copy" + q, ("lm",)), copy),
        (True, ("gen_block_copy" + q, ("lm",)), copy)]
    assert progs.compiled_signatures() == 5


# -- the step in flight (docs/generation.md) ----------------------------------------
_XLA_COMPILES = []


def _xla_compiles():
    """Backend compiles of this process since the first call, by jax's own
    monitoring event (what the benchmark's ``compiles_after_warmup``
    counts); the listener is registered once."""
    if not _XLA_COMPILES:
        import jax.monitoring as mon

        _XLA_COMPILES.append(0)

        def on_duration(event, secs, **_):
            if event.endswith("backend_compile_duration"):
                _XLA_COMPILES[0] += 1

        mon.register_event_duration_secs_listener(on_duration)
    return _XLA_COMPILES[0]


def _mixed_prompts():
    rs = np.random.RandomState(11)
    shared = rs.randint(0, CFG.vocab, 16)
    return {"greedy": rs.randint(0, CFG.vocab, 12),
            "sampled": rs.randint(0, CFG.vocab, 7),
            "eos": np.arange(3, 12) % CFG.vocab,
            "cancel": rs.randint(0, CFG.vocab, 9),
            "chunked": rs.randint(0, CFG.vocab, 30),
            "shared_a": shared, "shared_b": shared,
            "shared_long": np.concatenate([shared, [5, 6, 7]])}


def _mixed_workload(params, ahead, eos, watch=None):
    """Greedy and seeded-sampling rows on three slots; requests that end
    by ``max_new_tokens``, by an end-of-sequence id and by a cancel from
    their own callback; a chunked prompt and two prefix-cache hits (one a
    whole cached prompt: copy-on-write) admitted mid-run from a callback.
    Returns ``(streams, callback streams, finish reasons, stats)`` by
    request name; ``watch(svc)`` runs on the warmed service before it
    starts."""
    svc = GenerationService(params, CFG,
                            _gc(max_slots=3, num_blocks=48), start=False)
    svc.warmup()
    if not ahead:
        svc._runs_ahead = False          # every step is read at once
    if watch is not None:
        watch(svc)
    prompts = _mixed_prompts()
    handles, seen = {}, {name: [] for name in prompts}

    def submit(name, n, **kw):
        def on_token(rid, tok):
            seen[name].append(tok)
            if name == "cancel" and len(seen[name]) == 4:
                handles[name].cancel()
            if name == "greedy" and len(seen[name]) == 3:
                submit("chunked", 9)
                submit("shared_b", 6)
            if name == "greedy" and len(seen[name]) == 9:
                submit("shared_long", 5, temperature=0.7, seed=21)
        handles[name] = svc.submit(prompts[name], max_new_tokens=n,
                                   on_token=on_token, **kw)

    submit("greedy", 16)
    submit("sampled", 12, temperature=0.8, top_k=10, seed=7)
    submit("shared_a", 8)
    submit("eos", 12, temperature=1.0, seed=3, eos_token=eos)
    submit("cancel", 14)
    svc.start()
    try:
        deadline = time.perf_counter() + 120
        while len(handles) < len(prompts) and time.perf_counter() < deadline:
            time.sleep(0.01)
        out = {name: h.result(120) for name, h in handles.items()}
        reasons = {name: h.finish_reason for name, h in handles.items()}
        time.sleep(0.12)          # the loop retires the last slot and idles
        return out, seen, reasons, svc.stats()
    finally:
        svc.stop()


def _eos_of_the_mixed_workload(params):
    """The "eos" row's sampled stream without an end-of-sequence id, and
    the index of an id it first samples at a decode step, not at its
    prefill."""
    svc = GenerationService(params, CFG, _gc(max_slots=1))
    try:
        alone = svc.generate(_mixed_prompts()["eos"], max_new_tokens=12,
                             temperature=1.0, seed=3, timeout=120)
    finally:
        svc.stop()
    return alone, next(j for j in range(2, 12) if alone[j] not in alone[:j])


def test_step_in_flight_serves_the_drained_streams(params):
    """(a) token for token, whatever ends a request and whenever one joins:
    the engine that reads step n after it dispatched step n + 1 serves
    what the engine that reads every step at once serves."""
    alone, j = _eos_of_the_mixed_workload(params)
    got, got_cb, got_why, stats = _mixed_workload(params, True, alone[j])
    ref, ref_cb, ref_why, ref_stats = _mixed_workload(params, False, alone[j])
    assert got == ref and got_cb == ref_cb and got_why == ref_why
    assert got_cb == got                      # every token delivered, once
    assert got["eos"] == alone[:j + 1] and got_why["eos"] == "eos"
    assert len(got["cancel"]) == 4 and got_why["cancel"] == "cancelled"
    prompts = _mixed_prompts()
    for name, n in (("greedy", 16), ("chunked", 9), ("shared_b", 6)):
        assert got[name] == _greedy_oracle(params, prompts[name], n), name
    for s in (stats, ref_stats):
        assert s["counts"]["prefix_hits"] >= 2
        assert s["counts"]["cow_copies"] >= 1
        assert s["counts"]["failed"] == 0
    c, rc = stats["counts"], ref_stats["counts"]
    assert c["steps_ahead"] > c["steps_drained"] >= 1
    assert rc["steps_ahead"] == 0 and rc["steps_drained"] > 0
    assert c["tokens"] == rc["tokens"] == sum(len(t) for t in got.values())


def test_next_step_is_dispatched_before_the_last_one_is_read(
        params, monkeypatch):
    """(b) with rows running and nothing to drain, step n + 1's dispatch
    comes before step n's read; ``steps_ahead`` and ``steps_drained``
    count every dispatched decode step between them."""
    from mxnet_tpu.serving.generation import engine as engine_mod

    log, steps = [], {}
    synced = engine_mod._synced

    def reading(*outs):
        if id(outs[0]) in steps:
            log.append(("read", steps[id(outs[0])]))
        return synced(*outs)

    monkeypatch.setattr(engine_mod, "_synced", reading)
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    svc.warmup()
    run = svc._programs.run
    kept = []                            # ids stay unique while these live

    def dispatching(kind, *args, **kw):
        out = run(kind, *args, **kw)
        if kind == "gen_decode":
            kept.append(out[0])
            steps[id(out[0])] = len(steps)
            log.append(("dispatch", steps[id(out[0])]))
        return out

    monkeypatch.setattr(svc._programs, "run", dispatching)
    rs = np.random.RandomState(2)
    hs = [svc.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=12)
          for n in (5, 9, 14)]
    svc.start()
    try:
        outs = [h.result(120) for h in hs]
        time.sleep(0.12)
        stats = svc.stats()
    finally:
        svc.stop()
    assert all(len(o) == 12 for o in outs)
    n = len(steps)
    # all three are admitted in the first pass and end together: 11 decode
    # steps, each but the first dispatched with the one before it unread
    assert n == 11 and [e for e in log if e[0] == "read"] == [
        ("read", i) for i in range(n)]
    for i in range(n - 1):
        assert log.index(("dispatch", i + 1)) < log.index(("read", i)), i
    c = stats["counts"]
    assert (c["steps_ahead"], c["steps_drained"]) == (n - 1, 1)
    # the last pass dispatches nothing: it reads step n - 1
    assert stats["iterations"] == n + 1


@pytest.mark.parametrize("news", [(6, 6), (3, 9)])
def test_lead_counts_positions_on_the_one_token_path(params, news):
    """``_lead`` speaks of positions since a block pass rides the step in
    flight too: on the one-token path it reads 1 for a row of the step in
    flight and 0 for any other, a row whose token in flight is its last
    is not fed again, and the tokens and the ``steps_ahead`` share are
    what they were."""
    svc = GenerationService(params, CFG, _gc(max_slots=2), start=False)
    svc.warmup()
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (5, 9)]
    hs = [svc.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    reqs = [h._req for h in hs]
    for _ in range(40):
        if all(h.finished for h in hs):
            break
        svc._iterate()
        f = svc._flight
        for r in reqs:
            flies = f is not None and r.rid in f.lead
            assert svc._flies(r) == flies and svc._lead(r) == int(flies)
            assert svc._ends_in_flight(r) == (
                flies and r.n_generated + 1 >= r.max_new)
    svc._iterate()                       # retires the last slot
    c = svc.stats()["counts"]
    fed = [set(rids) for _, rids in svc.membership_history()]
    svc.stop(drain=False, timeout=30)
    for h, p, n in zip(hs, prompts, news):
        assert h.result(1) == _greedy_oracle(params, p, n)
    # a request's first token is its prefill's: n - 1 decode steps feed it
    for r, n in zip(reqs, news):
        assert sum(r.rid in rids for rids in fed) == n - 1
    steps = max(news) - 1
    assert (c["steps_ahead"], c["steps_drained"]) == (steps - 1, 1)
    assert svc._flight is None and c["failed"] == 0


def test_end_of_sequence_row_takes_nothing_after_it(params):
    """(c) a row that ends on an end-of-sequence id is found a step late:
    the token of its extra step is dropped, and the prefix index is shown
    its context without the position that step wrote."""
    alone, j = _eos_of_the_mixed_workload(params)
    prompt = _mixed_prompts()["eos"]
    svc = GenerationService(params, CFG, _gc(max_slots=2), start=False)
    svc.warmup()
    shown = []
    insert = svc._prefix.insert
    svc._prefix.insert = lambda toks, blocks: (
        shown.append(list(toks)), insert(toks, blocks))[1]
    fed = []                             # rows of every decode dispatch
    run = svc._programs.run

    def dispatching(kind, cache, tokens, positions, lengths, *rest):
        if kind == "gen_decode":
            fed.append([int(p) for p, n in zip(positions[:, 0], lengths)
                        if n])
        return run(kind, cache, tokens, positions, lengths, *rest)

    svc._programs.run = dispatching
    seen = []
    other = svc.submit(np.arange(20) % CFG.vocab, max_new_tokens=16)
    h = svc.submit(prompt, max_new_tokens=12, temperature=1.0, seed=3,
                   eos_token=alone[j], on_token=lambda rid, t: seen.append(t))
    svc.start()
    try:
        out = h.result(120)
        other.result(120)
        time.sleep(0.12)
    finally:
        svc.stop()
    assert out == seen == alone[:j + 1] and h.finish_reason == "eos"
    req = h._req
    assert req.n_generated == j + 1 and req.decode_steps == j
    assert req.ctx_len == len(prompt) + j
    # the extra step did run: the row was fed at the position after its
    # last token's, which nothing was emitted for
    assert any(len(prompt) + j in row for row in fed)
    mine = [t for t in shown if t[:len(prompt)] == list(prompt)]
    assert mine and max(len(t) for t in mine) == len(prompt) + j
    assert mine[-1] == list(prompt) + out[:-1]


def test_warmup_compiles_the_parents_programs_and_nothing_after(
        params, monkeypatch):
    """(e) the step in flight adds no model program — ten for this
    configuration, as on the parent commit: five prefill signatures, four
    table widths of the decode step, the block copy — and a warmed service
    compiles nothing under the mixed workload, by this repo's count and by
    XLA's own."""
    from mxnet_tpu.executor import compile_cache_stats

    alone, j = _eos_of_the_mixed_workload(params)
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    _xla_compiles()
    marks = {}

    def watch(svc):
        kinds = sorted(k[0] for k in svc.compile_stats())
        assert len(kinds) == 10 and kinds.count("gen_decode") == 4 \
            and kinds.count("gen_prefill") == 5
        marks["warm"] = (compile_cache_stats()["misses"], _xla_compiles())

    got, _, _, stats = _mixed_workload(params, True, alone[j], watch=watch)
    assert (compile_cache_stats()["misses"], _xla_compiles()) \
        == marks["warm"]
    assert stats["counts"]["steps_ahead"] > 0 \
        and stats["counts"]["failed"] == 0
    assert stats["compiled_signatures"] == 10

"""One serving step of the generation engine (docs/generation.md): chunked
prefill, the mp axis, the model seam, the batch builder and the program
kinds, the step in flight.  The service's lifecycle, its cache and its
samplers: tests/test_generation.py."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving.generation import (GenerationConfig, GenerationService,
                                          PagedKVCache, blocks_for)
from oracle import CFG, greedy_oracle, params  # noqa: F401 (fixture)
from test_generation import _fresh_observability, _gc  # noqa: F401 (fixture)

pytestmark = pytest.mark.generation


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
        assert toks == greedy_oracle(params, p, 6)


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


# -- the model's longest chunk is the ladder's top rung (PR 50) ---------------------
@dataclasses.dataclass(frozen=True)
class _ChunkedLM(tr.TransformerLM):
    """GPT-2's block naming the longest chunk its prefill program takes,
    as the expert models do."""
    longest_chunk: int = 24


def _chunked_service(params, model=None, **kw):
    kw.setdefault("seq_buckets", [8, 16, 56])
    return GenerationService(params, model or _ChunkedLM(CFG), _gc(**kw),
                             start=False)


@pytest.mark.parametrize("what, args, chunks", [
    ("under", dict(prompt_len=20), [(0, 16, 16), (16, 4, 8)]),
    ("at", dict(prompt_len=24), [(0, 24, 24)]),
    ("over", dict(prompt_len=50), [(0, 24, 24), (24, 24, 24), (48, 2, 8)]),
    ("a prefix hit's suffix", dict(prompt_len=50, start=16),
     [(16, 24, 24), (40, 8, 8), (48, 2, 8)]),
    ("a re-prefill", dict(prompt_len=60, force_chunked=True),
     [(0, 24, 24), (24, 24, 24), (48, 8, 8), (56, 4, 8)]),
])
def test_the_models_longest_chunk_is_the_top_rung(params, what, args,
                                                   chunks):
    """A ``longest_chunk`` above every configured rung but the top is
    itself a rung: every walk cuts in it, and warm-up knows its (T, W)."""
    svc = _chunked_service(params, preemption=True, prefix_cache=True)
    assert svc._seq_buckets == [8, 16, 24]
    assert svc._prompt_buckets == [8, 16, 56]
    plan = svc._chunk_plan(**args)
    assert [c[:3] for c in plan] == chunks, what
    assert {(tb, w) for _, _, tb, w in plan} <= set(svc._prefill_signatures())
    svc.stop()


@pytest.mark.parametrize("longest_chunk, rungs", [
    (16, [8, 16]),          # a configured rung: the ladder up to it
    (24, [8, 16, 24]),      # between two: a rung of its own
    (4, [4]),               # under every rung: the one chunk the model takes
    (56, [8, 16, 56]),      # the ladder's top
    (512, [8, 16, 56]),     # past the longest prompt: no rung to add
])
def test_the_ladder_follows_the_models_attribute(params, longest_chunk,
                                                 rungs):
    model = _ChunkedLM(CFG, longest_chunk=longest_chunk)
    svc = _chunked_service(params, model)
    assert svc._seq_buckets == rungs == svc.stats()["seq_buckets"]
    unchunked = _chunked_service(params, model, chunked_prefill=False)
    assert unchunked._seq_buckets == [8, 16, 56]
    svc.stop()
    unchunked.stop()


def test_a_model_that_names_no_chunk_keeps_the_configured_ladder(params):
    """``TransformerLM`` has no ``longest_chunk``: the rungs, the plans and
    the warm-up set are the configured ladder's."""
    svc = _chunked_service(params, CFG, preemption=True)
    assert svc._seq_buckets == svc._prompt_buckets == [8, 16, 56]
    assert [c[:3] for c in svc._chunk_plan(50)] == [
        (0, 16, 16), (16, 16, 16), (32, 16, 16), (48, 2, 8)]
    assert svc._chunk_plan(56) == [(0, 56, 56, blocks_for(56, 8))]
    assert [c[:3] for c in svc._chunk_plan(60, force_chunked=True)] == [
        (0, 56, 56), (56, 4, 8)]
    assert {tb for tb, _ in svc._prefill_signatures()} == {8, 16, 56}
    svc.stop()


def test_the_longest_chunk_compiles_nothing_after_warmup(params, monkeypatch):
    """Prompts under, at and over the model's chunk, a prefix hit's suffix
    and a preempted row's re-prefill run under TPUMX_FREEZE_COMPILES=1
    with one miss a signature, tokens equal to the oracle's, and the
    host's counts of tokens and chunks agree with the plans."""
    svc = _chunked_service(params, max_slots=2, num_blocks=12,
                           preemption=True, prefix_cache=True)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    assert any(k[0] == "gen_prefill" and k[1][0][1] == (1, 24)
               for k in svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(50)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (20, 24, 50)]
    svc.start()
    for p in prompts:
        assert svc.generate(p, max_new_tokens=4, timeout=180) \
            == greedy_oracle(params, p, 4)
    counts = svc.stats()["counts"]
    plans = [svc._chunk_plan(len(p)) for p in prompts]
    assert counts["prefill_tokens"] == 20 + 24 + 50
    assert counts["prefill_chunks"] == sum(map(len, plans)) == 2 + 1 + 3
    # the same long prompt again: its full blocks are cached
    assert svc.generate(prompts[2], max_new_tokens=4, timeout=180) \
        == greedy_oracle(params, prompts[2], 4)
    after = svc.stats()["counts"]
    hit = svc._chunk_plan(50, start=48)
    assert after["prefix_hits"] == 1
    assert after["prefill_tokens"] - counts["prefill_tokens"] == 2
    assert after["prefill_chunks"] - counts["prefill_chunks"] == len(hit) == 1
    # two rows that outgrow the pool: one is preempted and prefilled anew
    hs = [svc.submit(rs.randint(0, CFG.vocab, 30), max_new_tokens=24)
          for _ in range(2)]
    for h in hs:
        assert len(h.result(180)) == 24
    stats = svc.compile_stats()
    assert svc.stats()["counts"]["preempted"] >= 1
    svc.stop()
    for key, st in stats.items():
        assert st["misses"] == 1, f"recompile at {key}: {st}"


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

# case -> (T, what a row feeds, sampler arrays asked for, tokens and lengths
#          of rows X (slot 0) and Z (slot 3), table width)
_BUILDER_CASES = {
    "single": (1, lambda r: [r.seq_tokens[r.ctx_len]], True,
               [_X12], 1, [_Z30], 1, 4),
    # drafts of unequal length: X proposes two, Z none; Tk buckets to 4
    "verify": (4, lambda r: [r.seq_tokens[r.ctx_len]] + {0: [7, 8]}.get(
        r.rid, []), True, [_X12, 7, 8, 0], 3, [_Z30, 0, 0, 0], 1, 4),
    # the block writes 30 .. 33: a fifth page, so the width buckets to 8
    "block": (4, lambda r: r.block, False,
              [_X12, _M, _M, _M], 4, [_Z30, _M, _M, _M], 4, 8),
}


@pytest.mark.parametrize("case", sorted(_BUILDER_CASES))
def test_step_builder_against_arrays_written_by_hand(params, case):
    """The one batch builder of the three step kinds, on four slots — X, an
    empty one, Y outside the batch, Z — against every operand written out
    by hand: the rows, tokens / positions / lengths, the sampler arrays
    (none for the block step), the widest table bucketed on the pow2
    ladder, and the copy-on-write of X's shared tail block BEFORE it is
    written."""
    from mxnet_tpu.serving.generation.engine import _RUNNING, _GenRequest

    T, feed, sampler, x_tok, x_len, z_tok, z_len, w = _BUILDER_CASES[case]
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

    b = svc._build_step([x, z], T, feed, sampler=sampler)

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
        "gen_verify": gp._verify_step,
        "gen_block": gp._block_step, "gen_fill": gp._fill_step,
        "gen_block_copy": gp.block_copy_pools}
    assert not any(hasattr(gp, name) for name in (
        "_model_step_q", "_verify_step_q"))
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
        (False, ("gen_block_copy" + q, ("lm",)), copy),
        (True, ("gen_block_copy" + q, ("lm",)), copy)]
    assert progs.compiled_signatures() == 4


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
        assert got[name] == greedy_oracle(params, prompts[name], n), name
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

    def reading(*outs, **kw):
        if id(outs[0]) in steps:
            log.append(("read", steps[id(outs[0])]))
        return synced(*outs, **kw)

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
        assert h.result(1) == greedy_oracle(params, p, n)
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
        params, monkeypatch, no_compile_cache):
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


# -- a prefill's first token stays on the device (docs/generation.md) ---------------
def _drive(svc, streams, limit=400):
    """The loop by hand, a pass a call, until every stream has ended or
    the loop says it is over."""
    for _ in range(limit):
        if all(h.finished for h in streams) or not svc._iterate():
            break


def _admitting_workload(params, ahead, sampled):
    """Twelve clients on three slots with outputs of 2 to 5 tokens, so that
    most passes admit: prompts of one chunk and of several, three of them
    the same two full blocks (the later ones hit the prefix index whole:
    one position recomputed, copy-on-write), one of a single token to
    generate.  Returns ``(tokens by request, stats)``."""
    svc = GenerationService(params, CFG, _gc(max_slots=3, num_blocks=64),
                            start=False)
    if not ahead:
        svc._runs_ahead = False          # every first token is read at once
    rs = np.random.RandomState(17)
    shared = rs.randint(0, CFG.vocab, 16)
    lens = (5, 30, 16, 9, 31, 16, 7, 23, 1, 16, 12, 27)
    news = (3, 2, 4, 5, 2, 3, 1, 4, 5, 2, 3, 2)
    prompts = [shared if n == 16 else rs.randint(0, CFG.vocab, n)
               for n in lens]
    kw = [dict(temperature=0.8, top_k=10, seed=100 + i) if sampled else {}
          for i in range(len(prompts))]
    hs = [svc.submit(p, max_new_tokens=n, **k)
          for p, n, k in zip(prompts, news, kw)]
    _drive(svc, hs)
    svc._iterate()                       # retires the last slot
    outs = [h.result(1) for h in hs]
    stats = svc.stats()
    svc.stop(drain=False, timeout=30)
    assert [len(o) for o in outs] == list(news)
    return prompts, outs, stats


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
def test_first_tokens_carried_serve_the_synchronous_streams(params, sampled):
    """(1) over a schedule in which most passes admit, the engine that
    leaves a prefill's first token on the device for the pass's decode
    step serves, token for token, what the engine that reads every first
    token at once serves."""
    prompts, got, stats = _admitting_workload(params, True, sampled)
    _, ref, ref_stats = _admitting_workload(params, False, sampled)
    assert got == ref
    if not sampled:
        for p, toks in zip(prompts, got):
            assert toks == greedy_oracle(params, p, len(toks))
    c, rc = stats["counts"], ref_stats["counts"]
    for s in (c, rc):
        assert s["prefills_ahead"] + s["prefills_read"] == s["submitted"] \
            == 12
        assert s["prefix_hits"] >= 2 and s["cow_copies"] >= 2
        assert s["failed"] == 0 and s["tokens"] == sum(map(len, got))
    assert rc["prefills_ahead"] == 0
    # a request of one token to generate is not fed to a decode step; the
    # others all joined the step of the pass that admitted them
    assert c["prefills_ahead"] >= 10
    assert c["prefill_tokens"] == rc["prefill_tokens"]
    assert c["cached_tokens"] == rc["cached_tokens"]


def test_nothing_is_read_between_a_prompts_chunks_and_the_decode_dispatch(
        params, monkeypatch):
    """(2) in a pass that admits and decodes, no read lies between the
    first chunk's dispatch and the decode step's; the first tokens are
    read after it, behind the last step's; ``prefills_ahead`` counts the
    admissions of the passes that decoded and ``prefills_read`` the
    others'."""
    from mxnet_tpu.serving.generation import engine as engine_mod

    log = []
    synced = engine_mod._synced
    monkeypatch.setattr(engine_mod, "_synced", lambda *outs, **kw: (
        log.append(("read", kw.get("of") or "step")), synced(*outs, **kw))[1])
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    svc.warmup()
    run, prefill = svc._programs.run, svc._prefill
    monkeypatch.setattr(svc._programs, "run", lambda kind, *a, **kw: (
        log.append(("dispatch", kind)), run(kind, *a, **kw))[1])
    monkeypatch.setattr(svc, "_prefill", lambda r: (
        log.append(("admit", r.rid)), prefill(r))[1])
    rs = np.random.RandomState(23)
    waves = [[(6, 1)],                   # alone, one token: nothing decodes
             [(9, 6), (30, 5), (14, 4)],  # the cold pass: all slots
             [(31, 3)], [(5, 2), (20, 3)]]
    hs, ahead, read, passes = [], 0, 0, []
    for wave in waves:
        hs += [svc.submit(rs.randint(0, CFG.vocab, n), max_new_tokens=m)
               for n, m in wave]
        for _ in range(60):
            if not svc._waiting:
                break
            del log[:]
            svc._iterate()
            admits = [e for e in log if e[0] == "admit"]
            if not admits:
                continue
            decode = ("dispatch", "gen_decode")
            passes.append((len(admits), decode in log))
            if decode not in log:
                read += len(admits)
                assert ("read", "prefill") in log
                continue
            ahead += len(admits)
            first = log.index(("dispatch", "gen_prefill"))
            at = log.index(decode)
            assert not [e for e in log[first:at] if e[0] == "read"], log
            # the last step's tokens are read first (they were ready
            # first), then each admitted prompt's token on its own
            reads = [e for e in log[at + 1:] if e[0] == "read"]
            assert reads[-len(admits):] == [("read", "prefill")] * len(admits)
            assert reads.count(("read", "prefill")) == len(admits)
    _drive(svc, hs)
    svc._iterate()
    c = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    assert all(h.result(1) for h in hs)
    assert (c["prefills_ahead"], c["prefills_read"]) == (ahead, read)
    assert read == 1 and ahead == 6 and (3, True) in passes
    assert c["steps_ahead"] + c["steps_drained"] == sum(
        1 for _, rids in svc.membership_history() if rids)


_EDGES = ["max_new_1", "eos_first", "cancel", "deadline", "preempt",
          "read_fails", "read_fails_past_budget", "stop_no_drain", "kill"]


@pytest.mark.parametrize("edge", _EDGES)
def test_a_first_token_left_on_the_device_at_the_edges(params, monkeypatch,
                                                      edge):
    """(3) two rows decode; a third is admitted beside them and ``edge``
    happens while its first token is on the device unread (at the
    dispatch of the decode step of the pass that admitted it).  Whatever
    it is, the other rows are served the oracle's tokens, each once."""
    from mxnet_tpu.serving import ServingClosedError
    from mxnet_tpu.serving.batcher import DeadlineExceededError
    from mxnet_tpu.serving.generation import GenerationStepError
    from mxnet_tpu.serving.generation import engine as engine_mod

    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    rs = np.random.RandomState(13)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (6, 11, 19)]
    want = [greedy_oracle(params, p, 8) for p in prompts]
    seen = [[] for _ in prompts]

    def submit(i, **kw):
        kw.setdefault("max_new_tokens", 8)
        return svc.submit(prompts[i], on_token=lambda rid, t: seen[i].append(
            t), **kw)

    hs = [submit(0), submit(1)]
    svc._iterate()
    svc._iterate()
    assert [len(s) for s in seen] == [2, 2, 0] and svc._flight is not None
    the = submit(2, **{"max_new_1": dict(max_new_tokens=1),
                       "eos_first": dict(eos_token=want[2][0])}.get(edge, {}))
    hs.append(the)
    req, fired, at_kill = the._req, [], []
    run, synced = svc._programs.run, engine_mod._synced

    def dispatching(kind, *a, **kw):
        if kind == "gen_decode" and req.rid in svc._firsts and not fired:
            fired.append(svc._slots.index(req))
            assert svc._flies(req) and len(req.seq_tokens) == req.ctx_len
            if edge == "cancel":
                the.cancel()
            elif edge == "deadline":
                req.deadline = time.perf_counter() - 1.0
            elif edge == "preempt":
                with svc._lock, pytest.raises(engine_mod._LandFirst):
                    svc._preempt_slot_locked(fired[0])
            elif edge == "stop_no_drain":
                svc.stop(drain=False)
            elif edge == "kill":
                svc.kill()
                at_kill.extend(len(s) for s in seen)
        return run(kind, *a, **kw)

    def reading(*outs, **kw):
        if edge.startswith("read_fails") and kw.get("of") == "prefill" \
                and len(fired) == 1:
            fired.append("raised")
            raise RuntimeError("injected read failure")
        return synced(*outs, **kw)

    monkeypatch.setattr(svc._programs, "run", dispatching)
    monkeypatch.setattr(engine_mod, "_synced", reading)
    if edge == "read_fails_past_budget":
        svc._max_error_requeues = 0
    svc._iterate()                       # admits the third beside the two
    assert fired and not svc._firsts
    if edge == "preempt":
        # its first token has landed; the step it fed is in flight: the
        # preemption is refused until that has landed too
        assert seen[2] == want[2][:1] and svc._flies(req)
        svc._land()
        with svc._lock:
            svc._preempt_slot_locked(fired[0])
    _drive(svc, hs)
    svc._iterate()
    c = svc.stats()["counts"]
    fed = sum(req.rid in rids for _, rids in svc.membership_history())
    for i, h in enumerate(hs[:2]):
        if edge == "stop_no_drain":
            with pytest.raises(ServingClosedError):
                h.result(1)
        elif edge == "kill":
            assert not h.finished and len(seen[i]) == at_kill[i]
        else:
            assert h.result(1) == want[i]
        assert seen[i] == want[i][:len(seen[i])]       # each token once
    if edge == "max_new_1":
        assert the.result(1) == seen[2] == want[2][:1] and fed == 0
        assert the.finish_reason == "max_new_tokens"
        assert c["prefills_ahead"] == 3
    elif edge == "eos_first":
        # found a step late: fed once, that token dropped, and its K/V
        # past what the prefix index is shown
        assert the.result(1) == seen[2] == want[2][:1] and fed == 1
        assert the.finish_reason == "eos" and req.decode_steps == 0
        assert req.ctx_len == len(prompts[2])
    elif edge == "cancel":
        assert the.result(1) == seen[2] == want[2][:1]
        assert the.finish_reason == "cancelled" and c["cancelled"] == 1
    elif edge == "deadline":
        # (the step its token fed lands before the row leaves its slot,
        # as it does for any row of a step in flight)
        with pytest.raises(DeadlineExceededError):
            the.result(1)
        assert seen[2] == want[2][:2] and c["expired"] == 1
    elif edge == "preempt":
        assert the.result(1) == seen[2] == want[2]
        assert req.n_preempted == 1 and c["preempted"] == 1
    elif edge == "read_fails":
        # requeued with nothing of it served, prefilled anew; the step
        # that was fed its token dropped its row
        assert the.result(1) == seen[2] == want[2]
        assert (req.n_requeues, c["requeued"], c["failed"]) == (1, 1, 0)
        assert req.decode_steps == 7 and c["prefills_ahead"] == 3
    elif edge == "read_fails_past_budget":
        with pytest.raises(GenerationStepError):
            the.result(1)
        assert seen[2] == [] and c["failed"] == 1
    elif edge == "stop_no_drain":
        with pytest.raises(ServingClosedError):
            the.result(1)
        assert seen[2] == want[2][:2]
    elif edge == "kill":
        assert not the.finished and seen[2] == []
    assert svc._flight is None or edge == "kill"
    if edge not in ("stop_no_drain", "kill"):
        assert c["tokens"] == sum(map(len, seen))
        # nothing leaks: what is left in the pool is what the index keeps
        assert svc._cache.allocator.num_used == svc._prefix.num_blocks
        svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("admits", [1, 2, 3])
def test_admissions_of_any_size_compile_nothing_after_warmup(
        params, monkeypatch, no_compile_cache, admits):
    """The program that places a first token takes its slot as an
    operand: compiled once in ``warmup()``, whatever the number of rows a
    pass admits (one, several, every slot), with and without a step in
    flight; it is no model program and not among
    ``compiled_signatures()``."""
    from mxnet_tpu.executor import compile_cache_stats

    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    _xla_compiles()
    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    n = svc.warmup()
    assert n == svc.stats()["compiled_signatures"] == 10
    assert ("first_token", (3,)) in svc._programs._texts
    placed = svc._programs._placed
    warm = (compile_cache_stats()["misses"], _xla_compiles())
    rs = np.random.RandomState(31)
    hs = []
    for wave in range(3):                # cold, then beside rows in flight
        hs += [svc.submit(rs.randint(0, CFG.vocab, 5 + 9 * i + wave),
                          max_new_tokens=3) for i in range(admits)]
        svc._iterate()
        svc._iterate()
    _drive(svc, hs)
    svc._iterate()
    c = svc.stats()
    svc.stop(drain=False, timeout=30)
    assert all(len(h.result(1)) == 3 for h in hs)
    assert (compile_cache_stats()["misses"], _xla_compiles()) == warm
    assert c["compiled_signatures"] == 10
    assert c["counts"]["prefills_ahead"] == 3 * admits
    assert svc._programs._placed - placed == 3 * admits

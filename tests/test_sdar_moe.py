"""SDAR-MoE through the generation engine, against the plain reference
(perfbench/reference/sdar_moe.py) on seeded weights, at a tiny preset on
the CPU: d 64, 4 query heads over 2 KV heads of 16, 8 experts top-2 of
width 32, 2 layers, vocabulary 97, blocks of 4 generated in 4 passes.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerances are those of float32 sums
taken in another order (the program batches, pages and groups; the
reference does none of that): 2e-4 on logits whose spread is about 1.  A
run one precision down (the reference in bfloat16) is 50 times outside
that, which `test_one_precision_down_is_outside_the_tolerance` pins.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import sampling
from mxnet_tpu.ops.paged_attention import (paged_attention,
                                           paged_attention_reference)
from mxnet_tpu.parallel import sdar_moe as sm
from mxnet_tpu.serving.bucketing import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import blocks_for
from perfbench.reference import sdar_moe as ref

C = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
         num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
         num_experts=8, num_experts_per_tok=2, vocab_size=97,
         rms_norm_eps=1e-6, rope_theta=1e6, norm_topk_prob=True)
L, STEPS, MASK, MAX_LEN = 4, 4, 96, 128
CFG = sm.SdarMoeConfig(max_position_embeddings=MAX_LEN, block_length=L,
                       denoising_steps=STEPS, mask_token_id=MASK, **C)
MODEL = sm.SdarMoeLM(CFG, max_len=MAX_LEN, kv_dtype=jnp.float32)
TOL = 2e-4      # float32 sums in another order, logits of spread ~1


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


def _service(params, monkeypatch=None, kernel=None, **kw):
    if kernel is not None:
        monkeypatch.setenv("TPUMX_PALLAS", "1" if kernel == "paged" else "0")
    gc = dict(max_slots=4, block_size=8, num_blocks=64, seq_buckets=[16, 64])
    gc.update(kw)
    return GenerationService(params, MODEL, GenerationConfig(**gc),
                             start=False)


def _ref_logits(params, tokens, at0, dtype="float32"):
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(ref.logits(params, C, toks, len(tokens), at0, L,
                                 block_length=L, dtype=dtype))


def _ref_generate(params, prompt, n):
    return ref.generate(params, C, prompt, n, block_length=L, steps=STEPS,
                        mask_id=MASK, pad_to=MAX_LEN)


def _prefill(svc, toks, blocks):
    """Whole blocks of ``toks`` through the engine's chunk plan."""
    ctx = len(toks) // L * L
    for off, take, tb, wp in (svc._chunk_plan(ctx, force_chunked=True)
                              if ctx else ()):
        table = np.zeros((1, wp), np.int32)
        table[0, :min(wp, len(blocks))] = blocks[:wp]
        svc._programs.run_fill(
            svc._cache,
            pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                             tb)[None, :],
            np.arange(off, off + tb, dtype=np.int32)[None, :],
            np.asarray([take], np.int32), table)
    return ctx


@pytest.mark.parametrize("kernel", ["gather", "paged"])
@pytest.mark.parametrize("plen", [3, 16, 37])
def test_prefill_then_block_passes_match_reference_logits(
        params, monkeypatch, kernel, plen):
    """Prefill through the chunk plan, then block passes through the cache
    (0 to 4 MASKs a block, then its commit pass, then the next block),
    against the reference's full forward at the same block states."""
    svc = _service(params, monkeypatch, kernel)
    assert svc.stats()["decode_kernel"] == kernel
    rng = np.random.default_rng(plen)
    seq = [int(t) for t in rng.integers(0, C["vocab_size"], plen)]
    blocks = svc._alloc_reclaiming(blocks_for(plen + 3 * L, 8))
    ctx = _prefill(svc, seq, blocks)
    S = 4
    for rnd in range(3):
        known = seq[ctx:]
        fill = [int(t) for t in rng.integers(0, 97, L - len(known))]
        n_mask = (rnd + plen) % (L - len(known) + 1)
        masked = [False] * L
        for j in rng.permutation(np.arange(len(known), L))[:n_mask]:
            masked[j] = True
        finished = known + fill
        for state_masked in (masked, [False] * L):     # denoise, commit
            block = [MASK if m else t
                     for t, m in zip(finished, state_masked)]
            tokens = np.zeros((S, L), np.int32)
            positions = np.zeros((S, L), np.int32)
            lengths = np.zeros(S, np.int32)
            flags = np.zeros((S, L), bool)
            tables = np.zeros((S, 8), np.int32)
            tokens[1], positions[1], lengths[1] = block, ctx + np.arange(L), L
            flags[1] = state_masked
            tables[1, :len(blocks)] = blocks
            unmasked, _, lg = svc._programs.run_block(
                svc._cache, tokens, positions, lengths, tables, flags,
                np.asarray([0, 1, 0, 0], np.int32))
            want = _ref_logits(params, seq[:ctx] + block, ctx)
            np.testing.assert_allclose(np.asarray(lg)[1], want, atol=TOL,
                                       rtol=0)
            # the program's choice is the reference's rule on its logits
            if any(state_masked):
                conf = np.where(state_masked, jax.nn.softmax(want).max(-1),
                                -1)
                j = int(np.argmax(conf))
                expect = np.full(L, -1)
                expect[j] = want[j].argmax()
                assert list(unmasked[1]) == list(expect)
            else:
                assert (unmasked[1] == -1).all()
        seq = seq[:ctx] + finished
        ctx += L


def test_one_precision_down_is_outside_the_tolerance(params):
    seq = [int(t) for t in np.random.default_rng(0).integers(0, 97, 40)]
    want = _ref_logits(params, seq, 36)
    low = _ref_logits(params, seq, 36, "bfloat16")
    assert np.abs(low - want).max() > 50 * TOL


@pytest.mark.parametrize("T,lens", [(4, (4, 4, 0)), (16, (16, 8, 12))])
def test_paged_chunk_body_grouped_heads_and_block_mask(T, lens):
    """The gather path against the paged chunk body (interpret mode): 8
    query heads over 2 KV heads whose lanes are never repeated, masked by
    each query's block-end position."""
    rng = np.random.default_rng(T)
    B, H, Hkv, D, bs, W, nb = 3, 8, 2, 16, 8, 6, 24
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((2, nb, bs, Hkv * D)), jnp.float32)
    k_pool, v_pool = pool(), pool()
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * W]
                         .reshape(B, W), jnp.int32)
    start = np.asarray([16, 8, 0])
    pos = start[:, None] + np.arange(T)[None, :]
    block_end = (pos // L + 1) * L - 1
    valid = np.arange(T)[None, :] < np.asarray(lens)[:, None]
    max_pos = np.where(valid, block_end, -1).max(axis=1)
    got = paged_attention(q, k_pool, v_pool, tables, block_end, max_pos,
                          layer=1, call="block")
    ctx = lambda p: p[1][tables].reshape(B, W * bs, Hkv, D)  # noqa: E731
    mask = np.arange(W * bs)[None, None, :] <= block_end[:, :, None]
    want = paged_attention_reference(q, ctx(k_pool), ctx(v_pool),
                                     jnp.asarray(mask), 0.25)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(got)[b, :lens[b]],
                                   np.asarray(want)[b, :lens[b]],
                                   atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_rows_body_fetches_live_pages_only(dtype):
    """The body a block step takes at the cell's layout (pages of whole
    tiles, 128 lanes a KV head pair): grid (B,), its own DMAs over the
    row's live page groups; against the gather path, with an inactive
    row, a row of one page and a row longer than one group."""
    from mxnet_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(1)
    B, T, H, Hkv, D, bs, W, nb = 4, 4, 4, 2, 64, 16, 32, 80
    assert pa._rows_pages(T * (H // Hkv), H // Hkv, W, bs, Hkv * D, dtype,
                          False) == 16
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((2, nb, bs, Hkv * D)), dtype)
    k_pool, v_pool = pool(), pool()
    ctx_len = np.asarray([0, 8, 300, 452])      # row 0 inactive
    n_pages = -(-(ctx_len + T) // bs)
    tables = np.zeros((B, W), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for b in range(1, B):
        tables[b, :n_pages[b]] = [next(free) for _ in range(n_pages[b])]
    pos = ctx_len[:, None] + np.arange(T)[None, :]
    block_end = (pos // L + 1) * L - 1
    max_pos = np.where(np.arange(B) > 0, block_end.max(axis=1), -1)
    got = paged_attention(q, k_pool, v_pool, jnp.asarray(tables), block_end,
                          max_pos, layer=1, call="block")
    ctx = lambda p: p[1][tables].reshape(B, W * bs, Hkv, D)  # noqa: E731
    mask = np.arange(W * bs)[None, None, :] <= block_end[:, :, None]
    want = paged_attention_reference(
        q, ctx(k_pool).astype(jnp.float32), ctx(v_pool).astype(jnp.float32),
        jnp.asarray(mask), 0.125)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2   # bf16 products
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:],
                               atol=tol, rtol=tol)
    assert np.all(np.asarray(got)[0] == 0)


def test_block_call_name_and_unchanged_gpt2_names():
    from mxnet_tpu.ops.paged_attention import _call_name

    assert _call_name(4, 256, "block") == "_paged_call_w256_t4_block"
    assert _call_name(1, 64) == "_paged_call_w64_decode"
    assert _call_name(128, 8) == "_paged_call_w8_t128_prefill"


@pytest.mark.parametrize("M,K,N,sizes", [
    (40, 32, 48, (0, 30, 0, 3, 7, 0)),          # one group takes most
    (300, 64, 32, (100, 0, 1, 150, 29, 20)),    # across row tiles of 128
    (256, 32, 64, (0, 0, 0, 0, 0, 256)),        # only the last group
    (64, 32, 32, (10, 5, 0, 0, 20, 9))])        # 20 rows belong to nobody
def test_grouped_matmul_kernel_matches_ragged_dot(M, K, N, sizes):
    from mxnet_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(M)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((len(sizes), K, N)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(x, w, gs)
    want = jax.lax.ragged_dot(x, w, gs, preferred_element_type=jnp.float32)
    n = sum(sizes)                      # rows behind the groups: undefined
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=1e-4, rtol=1e-5)


def _skewed_router(rng, d, E):
    """One expert takes most tokens, several take none."""
    r = rng.standard_normal((d, E)).astype(np.float32) * 0.05
    r[:, 5:] = 0.0          # experts 5-7: zero logits ...
    bias = np.zeros(E, np.float32)
    bias[2], bias[0] = 6.0, 1.0
    return r, bias


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("n_tokens", [5, 64])
def test_grouped_expert_product_matches_loop_under_skew(n_tokens, pallas):
    rng = np.random.default_rng(n_tokens)
    d, F, E, k = 64, 32, 8, 2
    h = rng.standard_normal((n_tokens, d)).astype(np.float32)
    # skew through the input: a large constant feature the router reads
    router, bias = _skewed_router(rng, d, E)
    h[:, 0] = 4.0
    router[0, :] = bias - 3.0 * (np.arange(E) >= 5)   # 5-7 never chosen
    wg, wu = (rng.standard_normal((E, d, F)).astype(np.float32) / 8
              for _ in range(2))
    wd = rng.standard_normal((E, F, d)).astype(np.float32) / 6
    y, touched = sm.moe_grouped(jnp.asarray(h), jnp.asarray(router),
                                jnp.asarray(wg), jnp.asarray(wu),
                                jnp.asarray(wd), k, pallas=pallas)
    p = jax.nn.softmax(jnp.asarray(h) @ jnp.asarray(router), axis=-1)
    w, e = jax.lax.top_k(p, k)
    w = w / w.sum(-1, keepdims=True)
    counts = np.bincount(np.asarray(e).ravel(), minlength=E)
    assert counts[2] >= n_tokens * 0.9 and (counts[5:] == 0).all()
    assert int(touched) == int((counts > 0).sum())
    with jax.default_matmul_precision("highest"):
        want = ref._experts(jnp.asarray(h), w, e, jnp.asarray(wg),
                            jnp.asarray(wu), jnp.asarray(wd), jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
    # a chip's share of the experts: the shares add up to the whole layer
    parts = [sm.moe_grouped(jnp.asarray(h), jnp.asarray(router),
                            jnp.asarray(wg[lo:hi]), jnp.asarray(wu[lo:hi]),
                            jnp.asarray(wd[lo:hi]), k,
                            experts_held=(lo, hi), pallas=pallas)[0]
             for lo, hi in ((0, 3), (3, 8))]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(y), atol=1e-5, rtol=1e-5)


@pytest.fixture(scope="module")
def served(params):
    svc = _service(params)
    svc.start()
    yield svc
    svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("plen,n_new", [(3, 4), (5, 9), (16, 8), (23, 13),
                                        (64, 6)])
def test_service_generation_matches_reference_procedure(params, served,
                                                        plen, n_new):
    """Whole generations through submit / the scheduler / the paged cache,
    token for token (float32 on both sides; the seeds give no tie), and
    the pass that unmasked each token."""
    prompt = np.random.default_rng(100 + plen).integers(0, 97, plen)
    stream = served.submit(prompt, max_new_tokens=n_new)
    got = stream.result(120)
    want, passes = _ref_generate(params, prompt, n_new)
    assert got == want and len(got) == n_new
    assert stream._req.unmask_pass == passes
    assert stream.stats()["decode_mode"] == "block"


def test_prompt_holding_the_mask_id_is_not_read_as_masked(params, served):
    prompt = [7, MASK, MASK, 9, MASK, 3]       # leftover: MASK, 3
    got = served.generate(prompt, max_new_tokens=6, timeout=120)
    assert got == _ref_generate(params, prompt, 6)[0]


def test_batched_rows_in_different_passes_share_the_step(params, served):
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 97, n) for n in (6, 17, 9, 30, 12, 5)]
    news = [7, 12, 5, 9, 16, 8]
    streams = [served.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    before = served.stats()["block_diffusion"]
    for st, p, n in zip(streams, prompts, news):
        assert st.result(180) == _ref_generate(params, p, n)[0]
    bd = served.stats()["block_diffusion"]
    assert bd["row_passes"] > bd["passes"] >= before["passes"]
    assert 0 < bd["commit_row_passes"] < bd["row_passes"]
    assert bd["experts_touched"] > 0


def test_eos_cuts_the_block(params, served):
    prompt = np.random.default_rng(123).integers(0, 97, 23)
    full = _ref_generate(params, prompt, 13)[0]
    eos = full[5]
    cut = full[:full.index(eos) + 1]
    st = served.submit(prompt, max_new_tokens=13, eos_token=eos)
    assert st.result(120) == cut and st.finish_reason == "eos"


def test_prefix_cache_hit_gives_the_same_logits(params):
    """A second request with the same prompt takes its pages from the
    prefix index (block_size % L == 0: a full page's K/V depend on
    nothing behind the page) and reads the same logits."""
    svc = _service(params)
    seen = {}
    inner = svc._programs.run_block

    def run_block(cache, tokens, *a):
        out = inner(cache, tokens, *a)
        seen.setdefault(len(seen), (np.asarray(tokens).copy(),
                                    np.asarray(out[2]).copy()))
        return out

    svc._programs.run_block = run_block
    svc.start()
    prompt = np.random.default_rng(5).integers(0, 97, 27)
    first = svc.generate(prompt, max_new_tokens=6, timeout=120)
    n_first = len(seen)
    second = svc.generate(prompt, max_new_tokens=6, timeout=120)
    st = svc.stats()
    svc.stop(drain=False, timeout=30)
    assert first == second == _ref_generate(params, prompt, 6)[0]
    assert st["prefix_cache"]["hits"] == 1
    assert st["prefix_cache"]["cached_tokens"] == 24      # 3 pages of 8
    for i in range(n_first):          # the same passes, the same logits
        np.testing.assert_array_equal(seen[i][0], seen[n_first + i][0])
        row = int(np.argmax(seen[n_first + i][0].any(axis=1)))
        row0 = int(np.argmax(seen[i][0].any(axis=1)))
        np.testing.assert_allclose(seen[n_first + i][1][row],
                                   seen[i][1][row0], atol=1e-5, rtol=0)


@pytest.mark.parametrize("after_passes", [2, 7])
def test_preemption_mid_block_resumes_to_the_same_tokens(params,
                                                         after_passes):
    """Preempted with a block in flight: the block is dropped, what was
    committed is kept (and re-prefilled), and generation ends the same."""
    svc = _service(params)
    prompt = np.random.default_rng(11).integers(0, 97, 21)
    stream = svc.submit(prompt, max_new_tokens=11)
    for _ in range(after_passes):
        svc._iterate()
    r = stream._req
    assert r.block is not None and r.block_pass > 0 and any(r.block_masked)
    committed = list(r.generated)
    with svc._lock:
        svc._preempt_slot_locked(svc._slots.index(r))
    assert r.block is None and r.ctx_len % L == 0
    while not stream.finished:
        svc._iterate()
    want, passes = _ref_generate(params, prompt, 11)
    assert stream.result(1) == want and want[:len(committed)] == committed
    assert r.unmask_pass == passes
    assert svc.stats()["counts"]["preempted"] == 1
    svc.stop(drain=False, timeout=30)


def test_pool_pressure_preempts_and_every_request_still_matches(params):
    svc = _service(params, num_blocks=14, watermark_high=0.9,
                   watermark_low=0.6)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n) for n in (20, 18, 22, 17)]
    streams = [svc.submit(p, max_new_tokens=20) for p in prompts]
    svc.start()
    for st, p in zip(streams, prompts):
        assert st.result(300) == _ref_generate(params, p, 20)[0]
    assert svc.stats()["counts"]["preempted"] >= 1
    svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("kw", [dict(temperature=0.7), dict(top_k=5),
                                dict(top_p=0.9)])
def test_submit_refuses_sampling(params, served, kw):
    with pytest.raises(ValueError, match="greedily"):
        served.submit([1, 2, 3], max_new_tokens=4, **kw)


@pytest.mark.parametrize("kw,what", [
    (dict(speculative=True), "speculative"), (dict(multistep_k=4), "multistep"),
    (dict(kv_dtype="int8"), "int8"), (dict(mp_devices=2), "mp"),
    (dict(amp_dtype="bfloat16"), "amp")])
def test_service_refuses_what_the_model_does_not_offer(params, kw, what):
    with pytest.raises(ValueError, match=f"does not offer '{what}'"):
        _service(params, **kw)


@pytest.mark.parametrize("kw", [dict(block_size=6),
                                dict(seq_buckets=[10, 64])])
def test_pages_and_chunks_must_be_whole_blocks(params, kw):
    with pytest.raises(ValueError, match="multiples of"):
        _service(params, **kw)


def test_warmup_covers_every_program_the_traffic_needs(params):
    from mxnet_tpu.executor import compile_cache_stats

    svc = _service(params)
    n = svc.warmup()
    sigs = svc._prefill_signatures()
    assert n == len(sigs) + len(svc._width_buckets) + 1   # + block copy
    assert all(tb % L == 0 for tb, _ in sigs)
    misses = compile_cache_stats()["misses"]
    svc.start()
    rng = np.random.default_rng(2)
    streams = [svc.submit(rng.integers(0, 97, n), max_new_tokens=9)
               for n in (3, 16, 45, 64, 31)]
    for st in streams:
        st.result(180)
    assert compile_cache_stats()["misses"] == misses
    assert svc.compile_stats() and all(
        v["misses"] == 1 for v in svc.compile_stats().values())
    svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("block,steps,want", [(4, 4, (1, 1, 1, 1)),
                                              (4, 2, (2, 2)),
                                              (8, 3, (3, 3, 2)),
                                              (4, 1, (4,))])
def test_unmask_schedule(block, steps, want):
    assert sm.unmask_schedule(block, steps) == want
    assert tuple(ref.unmask_schedule(block, steps)) == want


def test_block_unmask_picks_most_confident_masked_ties_to_lower_position():
    V = 11
    lg = np.full((3, 4, V), -5.0, np.float32)
    lg[0, :, 3] = [2.0, 9.0, 4.0, 4.0]      # position 1 most confident ...
    lg[1, :, 7] = [6.0, 6.0, 6.0, 6.0]      # a four-way tie
    lg[2, :, 1] = [1.0, 2.0, 3.0, 4.0]
    masked = np.asarray([[True, False, True, True],     # ... but not masked
                         [False, True, True, True],
                         [True, True, False, False]])
    out = np.asarray(sampling.block_unmask(jnp.asarray(lg),
                                           jnp.asarray(masked),
                                           jnp.asarray([1, 2, 0])))
    assert out.tolist() == [[-1, -1, 3, -1], [-1, 7, 7, -1], [-1] * 4]
    # more asked for than are masked: only masked positions are unmasked
    out = np.asarray(sampling.block_unmask(jnp.asarray(lg),
                                           jnp.asarray(masked),
                                           jnp.asarray([4, 4, 4])))
    assert (out >= 0).tolist() == masked.tolist()

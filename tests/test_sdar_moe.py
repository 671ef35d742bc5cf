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
from mxnet_tpu.serving.generation.engine import _LandFirst
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
# the published head: 128 lanes, all rotary (the halves meet at lane 64);
# two query heads over one KV head are enough to catch a cut moved by a lane
C128 = dict(C, num_attention_heads=2, num_key_value_heads=1, head_dim=128)
MODEL128 = sm.SdarMoeLM(
    sm.SdarMoeConfig(max_position_embeddings=MAX_LEN, block_length=L,
                     denoising_steps=STEPS, mask_token_id=MASK, **C128),
    max_len=MAX_LEN, kv_dtype=jnp.float32)
TOL = 2e-4      # float32 sums in another order, logits of spread ~1


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


@pytest.fixture(scope="module")
def params128():
    return ref.init_params(3, C128, "float32")


def _service(params, monkeypatch=None, kernel=None, model=MODEL, **kw):
    if kernel is not None:
        monkeypatch.setenv("TPUMX_PALLAS", "1" if kernel == "paged" else "0")
    gc = dict(max_slots=4, block_size=8, num_blocks=64, seq_buckets=[16, 64])
    gc.update(kw)
    return GenerationService(params, model, GenerationConfig(**gc),
                             start=False)


def _ref_logits(params, tokens, at0, dtype="float32", c=C):
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(ref.logits(params, c, toks, len(tokens), at0, L,
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


@pytest.mark.parametrize("widths,kernel,plen", [
    ("tiny", kernel, plen) for plen in (3, 16, 37)
    for kernel in ("gather", "paged")] + [
    ("head128", "gather", 16), ("head128", "gather", 37)])
def test_prefill_then_block_passes_match_reference_logits(
        request, monkeypatch, widths, kernel, plen):
    """Prefill through the chunk plan, then block passes through the cache
    (0 to 4 MASKs a block, then its commit pass, then the next block),
    against the reference's full forward at the same block states.  The
    logits come back ``(S, L, vocab)`` from ``run_block``, which turns
    round what the program hands back position-major.  ``head128``: the
    published head size."""
    p, c, model = (("params", C, MODEL) if widths == "tiny"
                   else ("params128", C128, MODEL128))
    params = request.getfixturevalue(p)
    svc = _service(params, monkeypatch, kernel, model=model)
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
            want = _ref_logits(params, seq[:ctx] + block, ctx, c=c)
            assert lg.shape == (S, L, C["vocab_size"])
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


def _expert_layer(seed, N, k, E, lo, hi, e=None, d=64, F=32):
    """Seeded inputs of ``expert_products`` for a chip that holds experts
    ``lo .. hi - 1`` of ``E``; ``e`` (N, k) forces the router's choice."""
    rng = np.random.default_rng(seed)
    if e is None:
        e = np.stack([rng.choice(E, k, replace=False) for _ in range(N)])
    held = hi - lo
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (f32(rng.standard_normal((N, d))), f32(rng.random((N, k))),
            jnp.asarray(e, jnp.int32),
            f32(rng.standard_normal((held, d, F)) / 8),
            f32(rng.standard_normal((held, d, F)) / 8),
            f32(rng.standard_normal((held, F, d)) / 6))


def _walked_and_whole(args, held, E, pallas):
    """``expert_products`` told the router's width (a share held: the walk
    over the held rows) and not told it (one pass over all ``N k`` rows,
    the held ones masked: the function as it was before the walk)."""
    walked = sm.expert_products(*args, held, pallas, n_experts=E)
    y, sizes, none = sm.expert_products(*args, held, pallas)
    assert none is None
    return walked, (y, sizes)


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("N,k,E,lo,hi", [(64, 8, 256, 0, 16),
                                         (64, 8, 256, 240, 256),
                                         (160, 2, 8, 2, 4)],
                         ids=["16of256", "last16of256", "2of8"])
def test_held_share_walks_its_rows_in_one_trip_bit_for_bit(N, k, E, lo, hi,
                                                            pallas):
    """A balanced router sends a chip half a tile: one trip, and the sums
    are the whole pass's additions in the same order."""
    args = _expert_layer(N, N, k, E, lo, hi)
    (y, sizes, trips), (y0, sizes0) = _walked_and_whole(args, (lo, hi), E,
                                                        pallas)
    e = np.asarray(args[2])
    assert int(sizes0.sum()) == ((e >= lo) & (e < hi)).sum() > 0
    assert int(trips) == 1
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes0))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    assert float(jnp.abs(y).max()) > 0.1


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("tokens_held,want", [(0, 0), (32, 2), (40, 3),
                                              (64, 4)])
def test_router_forced_onto_the_held_experts_takes_more_trips(tokens_held,
                                                               want, pallas):
    """64 tokens x top-8 over 256 experts, 16 held: tiles of 128 rows.
    The first ``tokens_held`` tokens choose held experts only, the others
    none: 0, 256, 320 (half a tile left over) and all 512 rows are held,
    and no assignment is dropped whatever the trips."""
    N, k, E, lo, hi = 64, 8, 256, 16, 32
    rng = np.random.default_rng(tokens_held)
    e = np.stack([rng.choice(np.arange(lo, hi) if i < tokens_held
                             else np.arange(hi, E), k, replace=False)
                  for i in range(N)])
    args = _expert_layer(1, N, k, E, lo, hi, e)
    (y, sizes, trips), (y0, sizes0) = _walked_and_whole(args, (lo, hi), E,
                                                        pallas)
    assert int(sizes.sum()) == tokens_held * k
    assert int(trips) == want == -(-tokens_held * k // 128)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes0))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    assert (np.asarray(y)[tokens_held:] == 0).all()
    assert (np.abs(np.asarray(y)[:tokens_held]).max(axis=1) > 0).all()


@pytest.mark.parametrize("pallas", [False, True],
                         ids=["ragged_dot", "pallas"])
def test_a_group_that_straddles_a_tiles_edge_is_cut_there(pallas):
    """200 rows in tiles of 128 (the last one padded): expert 3 takes rows
    0-99, expert 7 rows 100-199, so 28 of its rows fall to the first trip
    and 72 to the second."""
    N, E, lo, hi = 200, 256, 0, 16
    e = np.where(np.arange(N) < 100, 3, 7)[:, None]
    args = _expert_layer(5, N, 1, E, lo, hi, e)
    (y, sizes, trips), (y0, _) = _walked_and_whole(args, (lo, hi), E, pallas)
    assert int(trips) == 2
    assert [int(n) for n in sizes] == [100 * (g in (3, 7)) for g in range(16)]
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
    h, w, _, wg, wu, wd = args
    with jax.default_matmul_precision("highest"):
        want = ref._experts(h, w, jnp.asarray(e), wg, wu, wd, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


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
    assert n_first >= 2 * (STEPS + 1)
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
    # a pass is in flight: a row leaves its slot at rest, as the
    # scheduler has it (_LandFirst)
    with svc._lock, pytest.raises(_LandFirst):
        svc._preempt_slot_locked(svc._slots.index(r))
    svc._land()
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
    (dict(speculative=True), "speculative"), (dict(kv_dtype="int8"), "int8"),
    (dict(mp_devices=2), "mp"), (dict(amp_dtype="bfloat16"), "amp")])
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


# -- the pass in flight (docs/generation.md "The step in flight") -----------------
def _drive(svc, streams, limit=2000):
    """Run the loop by hand until every stream has ended."""
    for _ in range(limit):
        if all(st.finished for st in streams):
            break
        svc._iterate()
    assert all(st.finished for st in streams)
    svc._iterate()                    # the pass that retires the last slot


def _watch_dispatches(svc, log):
    """Log every block pass the engine dispatches: the first position and
    the request of every fed row."""
    inner = svc._programs.run_block

    def run_block(cache, tokens, positions, lengths, *rest):
        log.append({svc._slots[i].rid: int(positions[i, 0])
                    for i in np.flatnonzero(lengths)})
        return inner(cache, tokens, positions, lengths, *rest)

    svc._programs.run_block = run_block


@pytest.mark.parametrize("plens,news,joins", [
    ((6, 17, 9), (7, 12, 5), ()),             # leftovers 2, 1, 1
    ((16, 8), (8, 16), ((13, 9),)),           # whole blocks; one joins
    ((3, 30, 12, 5), (4, 9, 16, 8), ((21, 11), (64, 6)))])
def test_pass_in_flight_serves_the_reference_procedure(params, plens, news,
                                                       joins):
    """Pass n + 1 is dispatched before pass n is read, for rows in
    different passes of different blocks, a row that joins while others
    continue and prompts with a leftover: the served tokens and the pass
    that unmasked each are the reference procedure's."""
    svc = _service(params)
    svc.warmup()
    assert svc._runs_ahead
    rng = np.random.default_rng(sum(plens))
    prompts = [rng.integers(0, 97, n) for n in plens]
    late = [(rng.integers(0, 97, n), m) for n, m in joins]
    streams, pending = [], list(late)

    def on_token(rid, tok):           # a request joins at a commit
        if pending:
            p, m = pending.pop()
            streams.append(svc.submit(p, max_new_tokens=m))

    for p, n in zip(prompts, news):
        streams.append(svc.submit(p, max_new_tokens=n, on_token=on_token))
    _drive(svc, streams)
    c = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    for st, (p, n) in zip(streams, list(zip(prompts, news)) + late[::-1]):
        want, passes = _ref_generate(params, p, n)
        assert st.result(1) == want
        assert st._req.unmask_pass == passes
    assert len(streams) == len(plens) + len(joins)
    assert c["steps_ahead"] + c["steps_drained"] == c["block_passes"]
    assert c["steps_ahead"] >= 0.9 * c["block_passes"] > 0
    assert c["failed"] == 0 and svc._flight is None
    assert c["tokens"] == sum(news) + sum(m for _, m in joins)


def test_next_pass_is_dispatched_before_the_last_one_is_read(params,
                                                             monkeypatch):
    from mxnet_tpu.serving.generation import engine as engine_mod

    log, passes, kept = [], {}, []
    synced = engine_mod._synced

    def reading(*outs, **kw):
        if id(outs[0]) in passes:
            log.append(("read", passes[id(outs[0])]))
        return synced(*outs, **kw)

    monkeypatch.setattr(engine_mod, "_synced", reading)
    svc = _service(params)
    svc.warmup()
    inner = svc._programs.run_block

    def run_block(*args):
        out = inner(*args)
        kept.append(out[0])             # ids stay unique while these live
        passes[id(out[0])] = len(passes)
        log.append(("dispatch", passes[id(out[0])]))
        return out

    svc._programs.run_block = run_block
    rng = np.random.default_rng(4)
    streams = [svc.submit(rng.integers(0, 97, n), max_new_tokens=8)
               for n in (8, 20, 12)]
    _drive(svc, streams)
    stats = svc.stats()
    svc.stop(drain=False, timeout=30)
    # two blocks of 4 + 1 passes a row, all admitted in the first pass
    n = len(passes)
    assert n == 10 and [e for e in log if e[0] == "read"] == [
        ("read", i) for i in range(n)]
    for i in range(n - 1):
        assert log.index(("dispatch", i + 1)) < log.index(("read", i)), i
    c = stats["counts"]
    assert (c["steps_ahead"], c["steps_drained"]) == (n - 1, 1)
    assert c["block_passes"] == n and c["block_row_passes"] == 3 * n
    assert c["block_commit_row_passes"] == 6


def test_eos_found_a_pass_late_emits_nothing_past_the_cut(params):
    """The commit pass that holds an end-of-sequence id is read after the
    next block's first pass was dispatched: that pass is dropped, nothing
    is emitted past the cut, the prefix index is shown nothing the extra
    pass wrote, and the slot and its pages come back."""
    prompt = np.random.default_rng(123).integers(0, 97, 23)
    full = _ref_generate(params, prompt, 13)[0]
    eos = full[2]                       # in the second block of three
    cut = full[:full.index(eos) + 1]
    svc = _service(params)
    svc.warmup()
    fed, shown, seen = [], [], []
    _watch_dispatches(svc, fed)
    insert = svc._prefix.insert
    svc._prefix.insert = lambda toks, blocks: (
        shown.append(list(toks)), insert(toks, blocks))[1]
    other = svc.submit(np.arange(11) % 97, max_new_tokens=16)
    st = svc.submit(prompt, max_new_tokens=13, eos_token=eos,
                    on_token=lambda rid, t: seen.append(t))
    _drive(svc, [st, other])
    c = svc.stats()["counts"]
    assert st.result(1) == seen == cut and st.finish_reason == "eos"
    assert other.result(1) == _ref_generate(params, np.arange(11) % 97,
                                            16)[0]
    r = st._req
    assert r.ctx_len == len(prompt) + len(cut) and r.unmask_pass == \
        _ref_generate(params, prompt, 13)[1][:len(cut)]
    # the extra pass did run: the row was fed at the block after the one
    # that held the id, and took nothing from it
    ended_at = (len(prompt) + len(cut) - 1) // L * L
    assert any(row.get(r.rid) == ended_at + L for row in fed)
    assert r.decode_steps == 2 + STEPS + 1     # a block of 1, a block of 4
    mine = [t for t in shown if t[:len(prompt)] == list(prompt)]
    assert mine and max(len(t) for t in mine) == len(prompt) + len(cut)
    assert all(s is None for s in svc._slots) and svc._flight is None
    assert c["failed"] == 0 and c["tokens"] == len(cut) + 16
    svc.stop(drain=False, timeout=30)
    assert svc._cache.allocator.num_used == 0


def _landing_first(svc, monkeypatch, calls):
    """Record every slot a request leaves: refused with a pass in flight
    (``land first``), or at rest."""
    preempt = svc._preempt_slot_locked

    def preempting(i, counter="preempted"):
        r = svc._slots[i]
        try:
            preempt(i, counter)
        except _LandFirst:
            calls.append(("land first", r.rid, len(r.unmask_pass)))
            raise
        calls.append((counter, r.rid, len(r.unmask_pass)))

    monkeypatch.setattr(svc, "_preempt_slot_locked", preempting)


def test_preemption_lands_the_pass_in_flight_first(params, monkeypatch):
    svc = _service(params, num_blocks=14, watermark_high=0.9,
                   watermark_low=0.6)
    svc.warmup()
    calls = []
    _landing_first(svc, monkeypatch, calls)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 97, n) for n in (20, 18, 22, 17)]
    streams = [svc.submit(p, max_new_tokens=20) for p in prompts]
    _drive(svc, streams, limit=4000)
    c = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    for st, p in zip(streams, prompts):
        want, passes = _ref_generate(params, p, 20)
        assert st.result(1) == want and st._req.unmask_pass == passes
    assert c["preempted"] >= 1 and c["failed"] == 0 and c["steps_ahead"] > 0
    # the first preemption met a pass in flight: it was refused, the pass
    # landed, and the same row left at rest
    first = calls.index(next(x for x in calls if x[0] == "land first"))
    assert calls[first + 1][:2] == ("preempted", calls[first][1])
    assert calls[first + 1][2] >= calls[first][2]


def test_cancel_lands_the_pass_in_flight_first(params):
    svc = _service(params)
    svc.warmup()
    rng = np.random.default_rng(31)
    p_keep, p_gone = rng.integers(0, 97, 10), rng.integers(0, 97, 14)
    seen, handle = [], {}

    def on_token(rid, tok):
        seen.append(tok)
        if len(seen) == 6:              # its first block of 4 and 2 more
            handle["st"].cancel()

    keep = svc.submit(p_keep, max_new_tokens=16)
    handle["st"] = gone = svc.submit(p_gone, max_new_tokens=16,
                                     on_token=on_token)
    _drive(svc, [keep, gone])
    c = svc.stats()["counts"]
    want = _ref_generate(params, p_gone, 16)[0]
    # cancelled at a commit, with its next block's first pass in flight:
    # that pass lands, gives the row nothing, and the slot is free
    assert gone.finish_reason == "cancelled" and seen == want[:len(seen)]
    assert len(seen) == 6 and gone._req.n_generated == 6
    assert keep.result(1) == _ref_generate(params, p_keep, 16)[0]
    assert c["cancelled"] == 1 and c["failed"] == 0
    assert all(s is None for s in svc._slots) and svc._flight is None
    svc.stop(drain=False, timeout=30)


def test_stop_without_drain_lands_the_pass_in_flight_first(params):
    from mxnet_tpu.serving import ServingClosedError

    svc = _service(params)
    svc.warmup()
    prompt = np.random.default_rng(41).integers(0, 97, 9)
    seen = []
    st = svc.submit(prompt, max_new_tokens=24,
                    on_token=lambda rid, t: seen.append(t))
    for _ in range(9):
        svc._iterate()
    before = list(seen)
    assert svc._flight is not None and len(before) == 3   # leftover 1
    with svc._lock:
        svc._closed, svc._drain = True, False
    assert svc._iterate() is False
    # the pass in flight was the second block's commit: it was read and
    # its tokens emitted before the stream was failed
    want = _ref_generate(params, prompt, 24)[0]
    assert svc._flight is None and seen == want[:7]
    with pytest.raises(ServingClosedError):
        st.result(1)
    assert all(s is None for s in svc._slots)


@pytest.mark.parametrize("fault", ["step", "read", "read_twice"])
def test_failure_with_a_pass_in_flight_costs_no_token(params, monkeypatch,
                                                      fault):
    """An injected step failure (before the 5th dispatch, the 4th pass
    unread) and a read of the pass in flight that raises once or twice:
    the pass before lands, or is given up and its rows fed from the
    host's view again; every stream is the reference's."""
    from mxnet_tpu.fault.inject import injector
    from mxnet_tpu.serving.generation import engine as engine_mod

    if fault == "step":
        monkeypatch.setenv("TPUMX_FAULT_GEN_STEP_FAIL", "5")
    injector().reset()
    svc = _service(params)
    svc.warmup()
    note, failed_at = svc._note_step_failure, []
    monkeypatch.setattr(svc, "_note_step_failure", lambda exc: (
        failed_at.append(svc._flight is not None), note(exc))[1])
    synced, reads, raised = engine_mod._synced, [0], []
    times = {"step": 0, "read": 1, "read_twice": 2}[fault]

    def failing(*outs, **kw):
        f = svc._flight
        if f is not None and outs[0] is f.tokens:
            reads[0] += 1
            if reads[0] >= 4 and len(raised) < times:
                raised.append(reads[0])
                raise RuntimeError("injected read failure")
        return synced(*outs, **kw)

    monkeypatch.setattr(engine_mod, "_synced", failing)
    rng = np.random.default_rng(51)
    prompts = [rng.integers(0, 97, n) for n in (6, 15)]
    seen = [[], []]
    streams = [svc.submit(p, max_new_tokens=10,
                          on_token=lambda rid, t, s=s: s.append(t))
               for p, s in zip(prompts, seen)]
    _drive(svc, streams)
    c = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    monkeypatch.delenv("TPUMX_FAULT_GEN_STEP_FAIL", raising=False)
    injector().reset()
    for st, p, cb in zip(streams, prompts, seen):
        want, passes = _ref_generate(params, p, 10)
        assert st.result(1) == cb == want
        assert st._req.unmask_pass == passes
    assert c["step_failures"] == max(times, 1) and c["failed"] == 0
    assert c["quarantined"] == 0 and c["tokens"] == 20
    assert failed_at[0] is True and len(raised) == times


def test_warmup_covers_the_carry_and_lowers_a_width_once(
        params, monkeypatch, no_compile_cache):
    """The pass in flight adds no model program and compiles nothing
    after warm-up, by this repo's count and by XLA's own: the block
    state carried on the device is fed to the ``gen_block`` programs that
    warm-up lowered from the host's operands."""
    import jax.monitoring as mon
    from mxnet_tpu.executor import compile_cache_stats

    compiles = [0]
    mon.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.__setitem__(
            0, compiles[0] + event.endswith("backend_compile_duration")))
    svc = _service(params)
    n = svc.warmup()
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    assert n == len(svc._prefill_signatures()) + len(svc._width_buckets) + 1
    warm = (compile_cache_stats()["misses"], compiles[0])
    rng = np.random.default_rng(61)
    streams = [svc.submit(rng.integers(0, 97, n), max_new_tokens=m)
               for n, m in ((3, 9), (16, 12), (45, 30), (64, 6), (31, 17))]
    _drive(svc, streams)
    c = svc.stats()["counts"]
    stats = svc.compile_stats()
    svc.stop(drain=False, timeout=30)
    assert (compile_cache_stats()["misses"], compiles[0]) == warm
    assert c["steps_ahead"] >= 0.9 * c["block_passes"] and c["failed"] == 0
    blocks = [v for k, v in stats.items() if k[0] == "gen_block"]
    assert len(blocks) == len(svc._width_buckets)
    assert all(v["misses"] == 1 for v in stats.values())
    assert sum(v["hits"] for v in blocks) == c["block_passes"]

"""The latent-attention model (parallel/latent_moe.py) through the
generation engine, against the plain reference
(perfbench/reference/dots_vlm1.py) on seeded weights, at a tiny preset on
the CPU: d 64, 4 heads (no-position 16, rotary 8, value 16), latent ranks
24 and 32, a dense layer of 96, 16 experts of 32 in 4 groups (top-2 groups,
top-4) and a shared one, 3 layers of which the first is dense, vocabulary
97.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerances are those of float32 sums
taken in another order (the program batches, pages, groups, and — through
the cache — absorbs the heads' key and value projections into the query
and the output; the reference does none of that): 1e-4 on logits whose
spread is about 1.  A run one precision down (the reference in bfloat16)
is a hundred times outside that, which
`test_one_precision_down_is_outside_the_tolerance` pins.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import grouped_matmul as gm
from mxnet_tpu.ops.latent_attention import (latent_attention,
                                            latent_attention_reference)
from mxnet_tpu.parallel import latent_moe as lm
from mxnet_tpu.parallel.sdar_moe import expert_products
from mxnet_tpu.serving.bucketing import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import PagedKVCache, blocks_for
from oracle import greedy
from perfbench.reference import dots_vlm1 as ref

C = dict(num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
         num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
         intermediate_size=96, moe_intermediate_size=32, n_routed_experts=16,
         n_shared_experts=1, num_experts_per_tok=4, n_group=4, topk_group=2,
         norm_topk_prob=True, routed_scaling_factor=2.5, vocab_size=97,
         rms_norm_eps=1e-6, rope_theta=1e4,
         rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                           mscale_all_dim=1,
                           original_max_position_embeddings=64, type="yarn"))
MAX_LEN, V = 128, 97
TOL = 1e-4      # float32 sums in another order, logits of spread ~1


def _config(c):
    rs = c["rope_scaling"]
    return lm.LatentMoeConfig(
        max_position_embeddings=MAX_LEN, rope_factor=rs["factor"],
        rope_original_max_position_embeddings=rs[
            "original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale_all_dim=rs["mscale_all_dim"],
        **{k: v for k, v in c.items() if k != "rope_scaling"})


CFG = _config(C)
MODEL = lm.LatentMoeLM(CFG, max_len=MAX_LEN, kv_dtype=jnp.float32,
                       longest_chunk=64)
# the published head: 128 lanes without position beside 64 rotary ones (192
# a head in ``wqb``: not whole 128-lane tiles, which is what made the chip's
# compiler turn ``wqb`` round; docs/generation.md "A weight reaches its
# product as stored") and values of 128 (256 a head in ``wkvb``); two heads
# are enough to catch a cut moved by a lane
C192 = dict(C, num_attention_heads=2, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128)
MODEL192 = lm.LatentMoeLM(_config(C192), max_len=MAX_LEN,
                          kv_dtype=jnp.float32, longest_chunk=64)
# a chunk no configured rung names, between 64 and the ladder's top: the
# model's own longest chunk is then the top rung of every walk
LONG = 96
MODEL_LONG = lm.LatentMoeLM(CFG, max_len=MAX_LEN, kv_dtype=jnp.float32,
                            longest_chunk=LONG)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


@pytest.fixture(scope="module")
def params192():
    return ref.init_params(3, C192, "float32")


def _service(params, monkeypatch=None, kernel=None, model=MODEL, **kw):
    if kernel is not None:
        monkeypatch.setenv("TPUMX_PALLAS", "1" if kernel == "paged" else "0")
    gc = dict(max_slots=4, block_size=8, num_blocks=64,
              seq_buckets=[16, 64, 120])
    gc.update(kw)
    return GenerationService(params, model, GenerationConfig(**gc),
                             start=False)


def _ref_logits(params, tokens, at0, n_at=1, dtype="float32", c=C):
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(ref.logits(params, c, toks, len(tokens), at0, n_at,
                                 dtype=dtype))


def _ref_greedy(params, prompt, n):
    return greedy(lambda seq: _ref_logits(params, seq, len(seq) - 1)[0],
                  prompt, n)


def _sampler(n, counter):
    z = np.zeros(n, np.int32)
    return (z.astype(np.uint32), np.full(n, counter, np.uint32),
            z.astype(np.float32), z, np.ones(n, np.float32))


def _prefill(svc, toks, blocks):
    """``toks`` through the engine's chunk plan; the last chunk's sampled
    token and last-position logits."""
    for off, take, tb, wp in svc._chunk_plan(len(toks)):
        table = np.zeros((1, wp), np.int32)
        table[0, :min(wp, len(blocks))] = blocks[:wp]
        nxt, last = svc._programs.run(
            "gen_prefill", svc._cache,
            pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                             tb)[None, :],
            np.arange(off, off + tb, dtype=np.int32)[None, :],
            np.asarray([take], np.int32), table, *_sampler(1, len(toks)))
    return int(nxt[0]), np.asarray(last[0])


def _decode(svc, row, tok, pos, blocks, S=4):
    tokens, positions = np.zeros((S, 1), np.int32), np.zeros((S, 1), np.int32)
    lengths = np.zeros(S, np.int32)
    tables = np.zeros((S, svc._width_buckets[-1]), np.int32)
    tokens[row, 0], positions[row, 0], lengths[row] = tok, pos, 1
    tables[row, :len(blocks)] = blocks
    nxt, last = svc._programs.run("gen_decode", svc._cache, tokens, positions,
                                  lengths, tables, *_sampler(S, pos + 1))
    return int(nxt[row]), np.asarray(last[row])


@pytest.mark.parametrize("widths,kernel,plen", [
    ("tiny", kernel, plen) for plen in (3, 16, 37, 70)
    for kernel in ("gather", "paged")] + [
    ("head192", "gather", 16), ("head192", "gather", 37),
    ("long", "gather", 100), ("long", "gather", 115), ("long", "paged", 100)])
def test_chunked_prefill_then_decode_match_reference_logits(
        request, monkeypatch, widths, kernel, plen):
    """Prefill through the chunk plan (every leftover length), then greedy
    decode steps through the latent cache, against the reference's full
    forward over the whole sequence (materialised attention).  ``gather``:
    the absorbed sums over the gathered pages; ``paged``: the absorbed
    kernel (interpreted).  ``head192``: the published head sizes, 128 + 64
    rotary and values of 128 — the flat product's cut into heads, its
    rotary cut and ``wkvb``'s halves, a chunk and a decode step.  ``long``:
    the model's longer chunk (96, a rung of its own above the configured
    64), then a leftover."""
    p, c, model = {"tiny": ("params", C, MODEL),
                   "long": ("params", C, MODEL_LONG),
                   "head192": ("params192", C192, MODEL192)}[widths]
    params = request.getfixturevalue(p)
    svc = _service(params, monkeypatch, kernel, model=model)
    assert svc.stats()["decode_kernel"] == kernel
    assert svc._seq_buckets == ([16, 64, LONG] if widths == "long"
                                else [16, 64])
    seq = [int(t) for t in np.random.default_rng(plen).integers(0, V, plen)]
    blocks = svc._alloc_reclaiming(blocks_for(plen + 5, 8))
    nxt, last = _prefill(svc, seq, blocks)
    np.testing.assert_allclose(
        last, _ref_logits(params, seq, plen - 1, c=c)[0], atol=TOL, rtol=0)
    for _ in range(4):
        seq.append(nxt)
        nxt, last = _decode(svc, 2, seq[-1], len(seq) - 1, blocks)
        np.testing.assert_allclose(
            last, _ref_logits(params, seq, len(seq) - 1, c=c)[0], atol=TOL,
            rtol=0)


def test_one_precision_down_is_outside_the_tolerance(params):
    seq = [int(t) for t in np.random.default_rng(1).integers(0, V, 37)]
    want = _ref_logits(params, seq, 30, 7)
    low = _ref_logits(params, seq, 30, 7, dtype="bfloat16")
    assert np.abs(low - want).max() > 100 * TOL


def _materialised_attention(qn, qr, ctx, wkvb, mask, scale):
    """The plain form, as the reference has it: every context token's
    per-head K and V made from its latent, softmax over the scores."""
    c, dn = wkvb.shape[0], qn.shape[-1]
    kv = jnp.einsum("bnc,chd->bnhd", ctx[..., :c], wkvb)
    s = (jnp.einsum("bthd,bnhd->bhtn", qn, kv[..., :dn])
         + jnp.einsum("bthr,bnr->bhtn", qr, ctx[..., c:])) * scale
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhtn,bnhd->bthd", p, kv[..., dn:])


@pytest.mark.parametrize("T,ctx", [(16, 0), (16, 40)])
def test_absorbed_and_materialised_attention_agree_on_the_same_cache(
        params, T, ctx):
    """One chunk at offset ``ctx`` over a cache that holds the context.
    The attention alone: the absorbed form (the queries through ``Wuk``,
    the sums over the cached latent, the result through ``Wuv`` — what
    both of the program's paths compute) against the materialised form on
    the same cached rows.  Then the whole program: the gather path and the
    kernel write the same latent and give the same logits, the plain
    reference's."""
    rng = np.random.default_rng(T + ctx)
    H, dn, dr, dv, c = 4, 16, 8, 16, 32
    N = ctx + T
    cached = jnp.asarray(rng.normal(size=(1, N, c + dr)), jnp.float32)
    qn = jnp.asarray(rng.normal(size=(1, T, H, dn)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(1, T, H, dr)), jnp.float32)
    wkvb = jnp.asarray(rng.normal(size=(c, H, dn + dv)) * c ** -0.5,
                       jnp.float32)
    mask = (np.arange(N)[None, None, :]
            <= (ctx + np.arange(T))[None, :, None])
    qa = jnp.concatenate([jnp.einsum("bthd,chd->bthc", qn, wkvb[..., :dn]),
                          qr], axis=-1)
    absorbed = jnp.einsum(
        "bthc,chd->bthd",
        latent_attention_reference(qa, cached, mask, c, CFG.softmax_scale),
        wkvb[..., dn:])
    np.testing.assert_allclose(
        absorbed, _materialised_attention(qn, qr, cached, wkvb, mask,
                                          CFG.softmax_scale),
        atol=1e-5, rtol=0)

    seq = rng.integers(0, V, ctx + T).astype(np.int32)
    table = np.arange(1, 9, dtype=np.int32)[None, :]
    lanes = MODEL.cache_spec()["pools"][0][1]
    outs = []
    for kernel in ("gather", "paged"):
        pool = jnp.zeros((3, 16, 8, lanes), jnp.float32)
        if ctx:
            _, pool, _ = lm.latent_moe_decode(
                params, seq[None, :ctx], np.arange(ctx)[None, :],
                np.asarray([ctx]), pool, table, CFG,
                attention_kernel="gather", max_len=MAX_LEN)
        lg, pool, aux = lm.latent_moe_decode(
            params, seq[None, ctx:], np.arange(ctx, ctx + T)[None, :],
            np.asarray([T - 3]), pool, table, CFG, attention_kernel=kernel,
            max_len=MAX_LEN)
        outs.append((np.asarray(lg[0, :T - 3]), np.asarray(pool[:, 1:9])))
        assert int(aux["latent_prefill_pairs"]) == sum(
            range(ctx + 1, ctx + T - 2))
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=TOL, rtol=0)
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=1e-5, rtol=0)
    assert not outs[1][1][..., CFG.latent_width:].any()   # the padding lanes
    np.testing.assert_allclose(
        outs[1][0], _ref_logits(params, seq[:ctx + T - 3], ctx, T - 3),
        atol=TOL, rtol=0)


def _route_by_loops(logits, bias, k, n_group, topk_group, scaling):
    """The published routing in plain Python, one token at a time."""
    ws, es = [], []
    for row in np.asarray(logits, np.float64):
        sc = 1.0 / (1.0 + np.exp(-row))
        c = sc + np.asarray(bias, np.float64)
        per = len(c) // n_group
        scores = [sum(sorted(c[g * per:(g + 1) * per])[-2:])
                  for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-scores[g], g)
                      )[:topk_group]
        cand = [i for g in sorted(kept) for i in range(g * per, (g + 1) * per)]
        e = sorted(cand, key=lambda i: (-c[i], i))[:k]
        w = sc[e] / (sc[e].sum() + 1e-20) * scaling
        es.append(e)
        ws.append(w)
    return np.asarray(ws), np.asarray(es)


@pytest.mark.parametrize("case", ["random", "ties", "all-equal"])
def test_router_matches_a_loop_over_groups(case):
    """Sigmoid scores, the bias in choosing only, the best groups by their
    two best, the best experts inside them — ties to the lower index, in
    the program, the reference and a loop."""
    rng = np.random.default_rng(4)
    logits = rng.normal(0, 2, (33, 32)).astype(np.float32)
    bias = rng.normal(0, 0.1, 32).astype(np.float32)
    if case == "ties":      # few distinct scores: ties at every choice
        logits, bias = np.round(logits), np.zeros(32, np.float32)
    elif case == "all-equal":
        logits[:] = 0.5
        bias[:] = 0
    kw = dict(n_group=8, topk_group=4)
    w, e = lm.route_sigmoid_groups(jnp.asarray(logits), jnp.asarray(bias), 8,
                                   norm_topk=True, scaling=2.5, **kw)
    wr, er = ref.route(jnp.asarray(logits), jnp.asarray(bias), k=8,
                       norm_topk=True, scaling=2.5, **kw)
    wl, el = _route_by_loops(logits, bias, 8, 8, 4, 2.5)
    if case == "random":
        np.testing.assert_array_equal(np.asarray(e), el)
        np.testing.assert_allclose(np.asarray(w), wl, rtol=1e-5)
    else:
        # float32 sigmoids of equal logits are equal, the float64 loop's
        # too: the choices agree as sets, and in order where scores differ
        np.testing.assert_array_equal(np.sort(np.asarray(e), 1),
                                      np.sort(el, 1))
    np.testing.assert_array_equal(np.asarray(e), np.asarray(er))
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), rtol=1e-6)
    assert np.allclose(np.asarray(w).sum(1), 2.5, rtol=1e-5)


@pytest.mark.parametrize("pallas", [False, True], ids=["ragged", "kernel"])
def test_the_shares_of_the_experts_sum_to_the_uncut_layer(params, monkeypatch,
                                                          pallas):
    """Four chips hold four experts each: their parts of the routed result
    plus the shared expert, counted once, are the uncut layer's — in the
    program (grouped products told ``experts_held``) and in the reference
    (given the same share)."""
    monkeypatch.setenv("TPUMX_PALLAS", "1" if pallas else "0")
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(0, 1, (23, 64)), jnp.float32)
    g = lambda n: params[f"l1_{n}"]  # noqa: E731
    w, e = lm.route_sigmoid_groups(h @ g("router"), g("router_bias"), 4, 4,
                                   2, True, 2.5)
    whole, sizes, _ = expert_products(h, w, e, g("wg"), g("wu"), g("wd"),
                                      pallas=pallas)
    assert int(sizes.sum()) == 23 * 4
    parts, ref_parts = 0, 0
    for lo in range(0, 16, 4):
        held = (lo, lo + 4)
        y, sz, trips = expert_products(h, w, e, g("wg")[lo:lo + 4],
                                       g("wu")[lo:lo + 4], g("wd")[lo:lo + 4],
                                       held, pallas=pallas, n_experts=16)
        assert int(trips) == 1
        np.testing.assert_array_equal(np.asarray(sz),
                                      np.asarray(sizes[lo:lo + 4]))
        parts = parts + y
        ref_parts = ref_parts + ref._experts(
            h, w, e, g("wg")[lo:lo + 4], g("wu")[lo:lo + 4],
            g("wd")[lo:lo + 4], lo, jnp.float32)
    shared = lm._gated(h, g("sg"), g("su"), g("sd"))
    uncut = ref._experts(h, w, e, g("wg"), g("wu"), g("wd"), 0, jnp.float32) \
        + ref._gated(h, g("sg"), g("su"), g("sd"), jnp.float32)
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(uncut),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(ref_parts + shared),
                               np.asarray(uncut), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=2e-5, rtol=0)


def test_a_share_of_the_model_is_the_reference_given_the_same_share(
        monkeypatch):
    """The whole model with experts 4-7 held: the program told
    ``experts_held`` against the reference given the same share (an
    expert's weights are its own number's, so a share holds what the
    whole layer would)."""
    monkeypatch.setenv("TPUMX_PALLAS", "0")
    c = dict(C, experts_held=[4, 8])
    p = ref.init_params(3, c, "float32")
    full = ref.init_params(3, C, "float32")
    np.testing.assert_array_equal(np.asarray(p["l1_wg"]),
                                  np.asarray(full["l1_wg"][4:8]))
    model = lm.LatentMoeLM(CFG, max_len=MAX_LEN, experts_held=(4, 8),
                           kv_dtype=jnp.float32, longest_chunk=64)
    svc = _service(p, model=model)
    seq = [int(t) for t in np.random.default_rng(2).integers(0, V, 21)]
    blocks = svc._alloc_reclaiming(4)
    _, last = _prefill(svc, seq, blocks)
    np.testing.assert_allclose(last, _ref_logits(p, seq, 20, c=c)[0],
                               atol=TOL, rtol=0)
    aux = {k: int(v) for k, v in svc._programs.take_aux()[-1].items()}
    assert aux["expert_assignments"] == 2 * 4 * 5     # layers x k x tokens
    assert 0 < aux["expert_assignments_held"] < aux["expert_assignments"]
    assert aux["experts_touched"] <= 2 * 4
    assert aux["expert_tokens_max"] <= 5


@pytest.mark.parametrize("dtype,T,width", [
    (jnp.float32, 1, 40), (jnp.bfloat16, 1, 40), (jnp.float32, 1, 48),
    (jnp.float32, 16, 40), (jnp.bfloat16, 16, 48)],
    ids=["decode-f32", "decode-bf16", "decode-padded", "chunk-f32",
         "chunk-bf16-padded"])
def test_latent_kernel_matches_its_oracle(dtype, T, width):
    """The latent body in interpret mode against the gather form: live
    pages only, an inactive row, a chunk's padded queries, a pool padded
    past the cached vector."""
    rng = np.random.default_rng(T + width)
    B, H, lat, c, bs, W = 3, 4, 40, 32, 8, 6
    pool = rng.normal(0, 1, (2, 32, bs, width)).astype(np.float32)
    pool[..., lat:] = 0
    pool = jnp.asarray(pool, dtype)
    q = jnp.asarray(rng.normal(0, 1, (B, T, H, lat)), jnp.float32)
    tables = np.zeros((B, W), np.int32)
    tables[0, :5], tables[1, :2] = [3, 9, 4, 17, 30], [5, 6]
    if T == 1:
        positions = np.asarray([[37], [9], [0]], np.int32)
        max_pos = np.asarray([37, 9, -1], np.int32)
    else:
        positions = np.stack([20 + np.arange(T), np.arange(T),
                              np.arange(T)]).astype(np.int32)
        max_pos = np.asarray([20 + T - 1, 10, -1], np.int32)  # row 1: padded
    got = latent_attention(q, pool, tables, positions, max_pos, v_width=c,
                           scale=0.3, layer=1)
    # the oracle in float32 over the pool's own (rounded) values
    ctx = pool[1][tables].reshape(B, W * bs, width).astype(jnp.float32)
    mask = np.arange(W * bs)[None, None, :] <= positions[:, :, None]
    want = latent_attention_reference(
        jnp.pad(q, ((0, 0),) * 3 + ((0, width - lat),)), ctx,
        jnp.asarray(mask), c, 0.3)
    valid = positions <= max_pos[:, None]
    tol = 1e-5 if dtype == jnp.float32 else 5e-2   # bfloat16 p and values
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(want)[valid],
                               atol=tol, rtol=0)
    assert not np.asarray(got)[~valid].any()          # nothing read: 0


@pytest.mark.parametrize("M,K,N,dtype,tn", [
    (256, 2048, 768, jnp.bfloat16, 768),       # an SDAR expert: one tile
    (96, 1024, 4096, jnp.float32, 2048),       # 16 MB a matrix: two tiles
    (640, 2304, 1024, jnp.bfloat16, 1024),     # 4.5 MB: whole again
    (64, 512, 8192, jnp.float32, 4096)])
def test_tiled_grouped_matmul_matches_ragged_dot(M, K, N, dtype, tn):
    """The grouped product cut along N where a group's matrix is too large
    to hold twice: every touched group still read once, a column tile a
    pass; against ``jax.lax.ragged_dot``."""
    assert gm._column_tile(K, N, jnp.dtype(dtype).itemsize) == tn
    rng = np.random.default_rng(M)
    G = 5
    sizes = np.asarray([M // 4, 0, M // 2 - 7, 3, 0], np.int32)
    x = jnp.asarray(rng.normal(0, 1, (M, K)), dtype)
    w = jnp.asarray(rng.normal(0, K ** -0.5, (G, K, N)), dtype)
    got = gm.grouped_matmul(x, w, sizes)
    want = jax.lax.ragged_dot(x, w, jnp.asarray(sizes),
                              preferred_element_type=jnp.float32)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=0)


def test_column_tile_keeps_the_cells_shapes():
    """The benchmark's shapes: an SDAR expert's matrices stay whole (their
    step is the parent's), this model's are cut into 4 column tiles."""
    assert gm._column_tile(2048, 768, 2) == 768
    assert gm._column_tile(768, 2048, 2) == 2048
    assert gm._column_tile(7168, 2048, 2) == 512
    assert gm._column_tile(2048, 7168, 2) == 1792


# -- the cache built from a one-pool spec -----------------------------------

def test_cache_is_built_from_the_models_spec():
    spec = MODEL.cache_spec()
    cache = PagedKVCache(num_blocks=16, block_size=8, **spec)
    # a cached vector's 32 + 8 values, padded to a whole 128-lane tile
    assert spec["pools"] == (("latent", 128),)
    assert [tuple(p.shape) for p in cache.pools] == [(3, 16, 8, 128)]
    assert cache.shape == (3, 16, 8, 128) \
        and cache.nbytes() == 3 * 16 * 8 * 128 * 4
    assert PagedKVCache.bytes_per_block(block_size=8, **spec) \
        == 3 * 8 * 128 * 4
    assert PagedKVCache.num_blocks_for_bytes(
        cache.nbytes(), block_size=8, **spec) == 16
    snap = cache.snapshot_blocks([1, 5])
    assert list(snap) == ["latent"] and snap["latent"].shape == (3, 2, 8, 128)
    # the classic pair is what it was
    kv = PagedKVCache(2, 4, 8, 16, 8)
    assert list(kv.snapshot_blocks([1])) == ["k", "v"]
    assert PagedKVCache.bytes_per_block(2, 4, 8, 8) == 2 * 2 * 8 * 32 * 4
    with pytest.raises(ValueError, match="quantizes the K/V pair only"):
        PagedKVCache(num_blocks=16, block_size=8, kv_dtype="int8", **spec)


@pytest.mark.parametrize("kw,what", [
    (dict(speculative=True), "speculative"), (dict(kv_dtype="int8"), "int8"),
    (dict(mp_devices=2), "mp"), (dict(amp_dtype="bfloat16"), "amp")])
def test_service_refuses_what_the_model_does_not_offer(params, kw, what):
    with pytest.raises(ValueError, match=f"does not offer '{what}'"):
        _service(params, **kw)


@pytest.fixture(scope="module")
def served(params):
    svc = _service(params)
    svc.start()
    yield svc
    svc.stop(drain=False, timeout=30)


@pytest.fixture(scope="module")
def served_long(params):
    svc = _service(params, model=MODEL_LONG)
    svc.start()
    yield svc
    svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("chunk,plen,n_new", [
    (64, 3, 4), (64, 16, 8), (64, 23, 13), (64, 70, 6),
    (LONG, 70, 6), (LONG, 100, 8), (LONG, 115, 6)])
def test_service_generation_matches_reference_greedy(request, params, chunk,
                                                     plen, n_new):
    """Whole generations through submit / the scheduler / the step in
    flight / the latent cache, token for token (float32 on both sides; the
    seeds give no tie).  Cut at the model's longer chunk the tokens are
    the same reference's."""
    served = request.getfixturevalue("served" if chunk == 64
                                     else "served_long")
    prompt = np.random.default_rng(100 + plen).integers(0, V, plen)
    assert served.generate(prompt, max_new_tokens=n_new, timeout=120) \
        == _ref_greedy(params, prompt, n_new)


@pytest.mark.parametrize("plen", [100, 115])
def test_a_longer_chunk_gives_the_shorter_chunks_logits(served, served_long,
                                                        plen):
    """The same prompt cut at 96 and at 64: the plans differ, and the last
    position's logits agree within the tolerance both hold against the
    reference."""
    seq = [int(t) for t in np.random.default_rng(plen).integers(0, V, plen)]
    lasts = []
    for svc, first in ((served_long, LONG), (served, 64)):
        assert svc._chunk_plan(plen)[0][:3] == (0, first, first)
        blocks = svc._alloc_reclaiming(blocks_for(plen + 1, 8))
        lasts.append(_prefill(svc, seq, blocks)[1])
        svc._cache.allocator.free(blocks)
    np.testing.assert_allclose(lasts[0], lasts[1], atol=TOL, rtol=0)


def test_the_programs_counts_reach_stats(params):
    """``aux`` of every prefill chunk and decode step, summed once its
    step's tokens were read: cache positions the decode body had to read,
    query-key pairs of the prefill, assignments (all, held, the fullest
    expert) and experts touched."""
    svc = _service(params)
    svc.start()
    svc.generate(np.arange(21), max_new_tokens=6, timeout=120)
    counts = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    assert svc._runs_ahead and counts["steps_ahead"] >= 1
    assert counts["latent_prefill_pairs"] == sum(range(1, 22))
    # decode steps at contexts 21..25 (the sixth token needs no sixth step
    # read; a step dispatched ahead of the end is dropped unread)
    assert counts["latent_ctx_tokens"] == sum(range(22, 27))
    assert counts["expert_assignments"] == 2 * 4 * (21 + 5)
    assert counts["expert_assignments_held"] == counts["expert_assignments"]
    assert 0 < counts["experts_touched"] <= 2 * 16 * (2 + 5)
    assert counts["expert_tokens_max"] >= 5


def test_the_expert_layers_trips_reach_stats_and_a_second_trip_is_exact(
        monkeypatch):
    """Experts 4-7 of 16 held and their router bias raised, so that every
    token (a padded one too) chooses them: a 64-token chunk's 256
    assignments are all this chip's, two tiles of 128 rows an expert layer,
    a decode step's 16 one.  The program counts the tiles it walked, the
    engine sums them, and the tokens are the reference's."""
    monkeypatch.setenv("TPUMX_PALLAS", "0")
    c = dict(C, experts_held=[4, 8])
    p = dict(ref.init_params(3, c, "float32"))
    for i in (1, 2):
        p[f"l{i}_router_bias"] = p[f"l{i}_router_bias"].at[4:8].add(100.0)
    model = lm.LatentMoeLM(CFG, max_len=MAX_LEN, experts_held=(4, 8),
                           kv_dtype=jnp.float32, longest_chunk=64)
    assert model.counters == lm.COUNTERS + ("expert_trips",
                                            "expert_trips_extra")
    svc = _service(p, model=model)
    prompt = [int(t) for t in np.random.default_rng(4).integers(0, V, 70)]
    chunks = [tb for _, _, tb, _ in svc._chunk_plan(len(prompt))]
    assert chunks == [64, 16]
    svc.start()
    got = svc.generate(prompt, max_new_tokens=6, timeout=120)
    counts = svc.stats()["counts"]
    svc.stop(drain=False, timeout=30)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(_ref_logits(p, seq, len(seq) - 1, c=c)[0].argmax()))
    assert list(got) == seq[len(prompt):]
    assert counts["expert_assignments_held"] == counts["expert_assignments"]
    # two expert layers; a chunk of 64 x top-4 = 256 rows in tiles of 128,
    # one of 16 and the 5 decode steps read (4 slots x 4) in one tile each
    assert counts["expert_trips"] == 2 * (2 + 1 + 5)
    assert counts["expert_trips_extra"] == 2 * 1
    # every expert held: nothing is walked, and nothing says it was
    assert MODEL.counters == lm.COUNTERS
    assert "expert_trips" not in _service(p, model=MODEL).stats()["counts"]


def test_prefix_cache_hit_serves_the_same_tokens(params):
    """A second request with the same prompt takes its pages from the
    prefix index — latent pages, shared by block id like any other — and
    copy-on-write gives the writer of a shared tail its own."""
    svc = _service(params)
    svc.start()
    prompt = np.random.default_rng(5).integers(0, V, 24)    # 3 whole pages
    first = svc.generate(prompt, max_new_tokens=6, timeout=120)
    second = svc.generate(prompt, max_new_tokens=6, timeout=120)
    st = svc.stats()
    svc.stop(drain=False, timeout=30)
    assert first == second == _ref_greedy(params, prompt, 6)
    assert st["prefix_cache"]["hits"] == 1
    assert st["prefix_cache"]["cached_tokens"] == 24
    assert st["prefix_cache"]["cow_copies"] >= 1


def test_preemption_resumes_to_the_same_tokens(params):
    svc = _service(params)
    prompt = np.random.default_rng(11).integers(0, V, 21)
    stream = svc.submit(prompt, max_new_tokens=11)
    for _ in range(5):
        svc._iterate()
    r = stream._req
    svc._land()
    with svc._lock:
        svc._preempt_slot_locked(svc._slots.index(r))
    while not stream.finished:
        svc._iterate()
    assert stream.result(1) == _ref_greedy(params, prompt, 11)
    assert svc.stats()["counts"]["preempted"] == 1
    svc.stop(drain=False, timeout=30)


def test_pool_pressure_preempts_and_every_request_still_matches(params):
    svc = _service(params, num_blocks=14, watermark_high=0.9,
                   watermark_low=0.6)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, V, n) for n in (20, 18, 22, 17)]
    streams = [svc.submit(p, max_new_tokens=20) for p in prompts]
    svc.start()
    for st, p in zip(streams, prompts):
        assert st.result(300) == _ref_greedy(params, p, 20)
    assert svc.stats()["counts"]["preempted"] >= 1
    svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("kernel,n_width", [("gather", 5), ("paged", 1)])
def test_warmup_covers_every_program_the_traffic_needs(params, monkeypatch,
                                                       kernel, n_width):
    """With the kernel a table's width is free, so the service keeps one
    width: a decode program and a prefill program a chunk length."""
    from mxnet_tpu.executor import compile_cache_stats

    svc = _service(params, monkeypatch, kernel, prefix_cache=False)
    assert len(svc._width_buckets) == n_width
    n = svc.warmup()
    sigs = svc._prefill_signatures()
    assert n == len(sigs) + n_width
    if kernel == "paged":
        # the 120 rung only says how long a prompt may be
        assert sigs == [(16, 16), (64, 16)] and svc._seq_buckets == [16, 64]
    misses = compile_cache_stats()["misses"]
    svc.start()
    rng = np.random.default_rng(2)
    streams = [svc.submit(rng.integers(0, V, n), max_new_tokens=5)
               for n in (3, 16, 45, 64, 99)]
    for st, n in zip(streams, (3, 16, 45, 64, 99)):
        assert len(st.result(300)) == 5
    assert compile_cache_stats()["misses"] == misses
    svc.stop(drain=False, timeout=30)


def test_yarn_frequencies_are_the_references():
    m = ref._dims(dict(C, rope_scaling=dict(C["rope_scaling"])))
    np.testing.assert_array_equal(lm.yarn_inv_freq(CFG), ref._inv_freq(m))
    full = lm.LatentMoeConfig()
    f = lm.yarn_inv_freq(full)
    plain = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    # fast dimensions keep their frequency, slow ones are divided by 40
    np.testing.assert_allclose(f[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(f[-4:], plain[-4:] / 40, rtol=1e-6)
    assert abs(full.softmax_scale - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2
               ) < 1e-9


def test_call_names():
    from mxnet_tpu.ops.latent_attention import _call_name

    assert _call_name(1, 512) == "_mla_call_w512_decode"
    assert _call_name(512, 512) == "_mla_call_w512_t512_prefill"

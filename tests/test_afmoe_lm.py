"""``Trinity-Mini``'s block (``model_type`` ``afmoe``) through the layer loop
it shares with ``mimo_v2`` (parallel/hybrid_moe.py), the generation engine
and its cache of two kinds, against the plain reference
(perfbench/reference/afmoe.py) on seeded weights, at a tiny preset on the
CPU: d 64, 8 query heads over 2 KV heads of 16, a window of 8, cache blocks
of 4, 8 layers in the published layout — 2 dense (96) then 6 expert layers,
two periods of 3 window + 1 full —, 16 sigmoid-routed experts of 32 top-4
with a shared one, vocabulary 97.

Logits and not tokens wherever the comparison is numeric.  Everything here
is float32 on both sides, so the tolerance is that of float32 sums taken in
another order (the program batches, pages, groups the query heads of a KV
head and walks a ring of window blocks; the reference does none of that):
1e-4 on logits whose spread is about 1.  The four planted faults (the gate
left out, rotary on the full layers, a window an eighth short, the
branches' outputs not normed) miss it by more than a thousand times, the
reference in bfloat16 by more than a hundred.
"""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import hybrid_moe as hm
from mxnet_tpu.parallel.latent_moe import _gated, route_sigmoid_groups
from mxnet_tpu.parallel.sdar_moe import expert_products
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from mxnet_tpu.serving.generation.kv_cache import window_blocks
from oracle import greedy
from perfbench import harness
# the engine's chunk plan, slide and ring tables driven by hand: one program
# call a chunk and a decode step with the other slots idle (blocks of 4 there
# as here)
from test_hybrid_moe import (_Row, _logits_through_the_cache as _through,
                             _prefill)
from perfbench.reference import afmoe as ref

S, F = "sliding_attention", "full_attention"
C = dict(model_type="afmoe", layer_types=[S, S, S, F] * 2,
         num_hidden_layers=8, num_dense_layers=2, hidden_size=64,
         num_attention_heads=8, num_key_value_heads=2, head_dim=16,
         sliding_window=8, intermediate_size=96, moe_intermediate_size=32,
         num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
         score_func="sigmoid", route_norm=True, route_scale=2.826, n_group=1,
         topk_group=1, mup_enabled=True, rope_theta=10000, vocab_size=97,
         rms_norm_eps=1e-5, max_position_embeddings=512)
# one period: what a test that needs a service of its own compiles — both
# dense layers (window), a window and a full expert layer
C4 = dict(C, layer_types=[S, S, S, F], num_hidden_layers=4)
MAX_LEN, V, BS, WIN = 512, 97, 4, 8
TOL = 1e-4      # float32 sums in another order, logits of spread ~1


def _model(c=C, **kw):
    kw.setdefault("longest_chunk", 16)
    return hm.HybridMoeLM(hm.HybridMoeConfig.from_afmoe(c), max_len=MAX_LEN,
                          kv_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(3, C, "float32")


@pytest.fixture(scope="module")
def params4():
    return ref.init_params(3, C4, "float32")


def _service(params, model=None, **kw):
    gc = dict(max_slots=4, block_size=BS, num_blocks=256,
              seq_buckets=[8, 16, 400])
    gc.update(kw)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPUMX_PALLAS", "0")     # read once, when it is made
        return GenerationService(params, model or _model(),
                                 GenerationConfig(**gc), start=False)


@pytest.fixture(scope="module")
def svc8(params):
    """The 8 published-layout layers without the kernel: ONE service for
    every test that does not need its own."""
    svc = _service(params)
    yield svc
    svc.stop(drain=False, timeout=30)


# a chunk no configured rung names, between 16 and the ladder's top: the
# model's own longest chunk is then the top rung of every walk
LONG = 32


@pytest.fixture(scope="module")
def svc8_long(params):
    """``svc8`` with a model whose prefill program takes chunks of 32."""
    svc = _service(params, model=_model(longest_chunk=LONG))
    assert svc._seq_buckets == [8, 16, LONG]
    yield svc
    svc.stop(drain=False, timeout=30)


def _ref_logits(params, tokens, at0, n_at=1, c=C, **kw):
    toks = np.zeros(MAX_LEN, np.int32)
    toks[:len(tokens)] = tokens
    return np.asarray(ref.logits(params, c, toks, len(tokens), at0, n_at,
                                 **kw))


def _ref_greedy(params, prompt, n, c=C):
    return greedy(lambda seq: _ref_logits(params, seq, len(seq) - 1, c=c)[0],
                  prompt, n)


@pytest.mark.parametrize("part", ["prefill", "decode"])
@pytest.mark.parametrize("chunk,plen", [
    (16, 3), (16, 5), (16, 16), (16, 37), (16, 70),
    (LONG, 32), (LONG, 37), (LONG, 70), (LONG, 100)])
def test_chunked_prefill_then_decode_match_reference_logits(request, params,
                                                            chunk, plen,
                                                            part):
    """Prefill through the chunk plan (every leftover length; past the
    window and a chunk, 8 + 16 positions, window blocks have been freed
    and reused), then greedy decode
    steps through both cache kinds with the other slots idle, against the
    reference's full forward over the whole sequence.  The prompts of 3
    and 5 cross the window of 8 WHILE THEY DECODE (positions 3..10 and
    5..12): under the second the first block slides out at a decode
    step.  At the model's longer chunk (32: one chunk, one and a leftover,
    two and a leftover past window + chunk, three) the same comparison
    holds within the same tolerance."""
    svc8 = request.getfixturevalue("svc8" if chunk == 16 else "svc8_long")
    if plen >= chunk:
        assert svc8._chunk_plan(plen)[0][:3] == (0, chunk, chunk)
    freed = svc8.stats()["counts"]["window_blocks_freed"]
    seq = [int(t) for t in np.random.default_rng(plen).integers(0, V, plen)]
    if part == "prefill":
        compared = _through(svc8, seq, 0)
    else:
        compared = _through(svc8, seq, 8)[1:]
    for toks, last in compared:
        np.testing.assert_allclose(
            last, _ref_logits(params, toks, len(toks) - 1)[0], atol=TOL,
            rtol=0)
    if plen > WIN + chunk or (part == "decode" and plen == 5):
        assert svc8.stats()["counts"]["window_blocks_freed"] > freed


@pytest.mark.parametrize("fault", ref.FAULTS + ("control",))
def test_a_planted_fault_and_one_precision_down_fail(svc8, params, fault):
    """The comparison above must FAIL the reference with each fault the
    benchmark's driver plants on it (the gate left out, rotary on the full
    layers, the window an eighth short — 7 of 8 —, the branches' outputs
    not normed), and the reference one precision down."""
    seq = [int(t) for t in np.random.default_rng(37).integers(0, V, 37)]
    kw = dict(dtype="bfloat16") if fault == "control" else dict(fault=fault)
    worst = max(np.abs(last - _ref_logits(params, toks, len(toks) - 1,
                                          **kw)[0]).max()
                for toks, last in _through(svc8, seq, 2))
    assert worst > (100 if fault == "control" else 1000) * TOL


# -- the share of the experts ---------------------------------------------------
def test_the_eight_shares_and_the_shared_expert_once_sum_to_the_uncut_layer(
        params):
    """Eight chips hold two of the 16 experts each: the parts of the routed
    result their grouped products give (told ``experts_held``), with the
    shared expert — which every chip computes alike — counted ONCE, add up
    to the uncut reference's expert layer; and so do the reference's own
    parts given the same shares."""
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(0, 1, (23, 64)), jnp.float32)
    g = lambda n: params[f"l2_{n}"]  # noqa: E731
    w, e = route_sigmoid_groups(h @ g("router"), g("router_bias"), 4, 1, 1,
                                True, 2.826)
    w_ref, e_ref = ref.route(h @ g("router"), g("router_bias"), k=4,
                             norm_topk=True, scaling=2.826)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.826, atol=1e-5)
    shared = _gated(h, g("sg"), g("su"), g("sd"))
    parts, ref_parts = shared, ref._gated(h, g("sg"), g("su"), g("sd"),
                                          jnp.float32)
    for lo in range(0, 16, 2):
        two = slice(lo, lo + 2)
        y, sizes, _ = expert_products(h, w, e, g("wg")[two], g("wu")[two],
                                      g("wd")[two], (lo, lo + 2),
                                      pallas=False, n_experts=16)
        parts = parts + y
        ref_parts = ref_parts + ref._experts(
            h, w, e, g("wg")[two], g("wu")[two], g("wd")[two], lo,
            jnp.float32)
    uncut = ref._experts(h, w, e, g("wg"), g("wu"), g("wd"), 0, jnp.float32) \
        + ref._gated(h, g("sg"), g("su"), g("sd"), jnp.float32)
    for got in (parts, ref_parts):
        np.testing.assert_allclose(np.asarray(got), np.asarray(uncut),
                                   atol=2e-5, rtol=0)


def test_a_share_of_the_model_is_the_reference_given_the_same_share():
    """The whole model with experts 4-7 held: the program told
    ``experts_held`` against the reference given the same share, the
    shared expert whole on both sides."""
    c = dict(C4, experts_held=[4, 8], published={"num_experts": 16},
             num_experts=4)
    p = ref.init_params(3, c, "float32")
    full = ref.init_params(3, C4, "float32")
    np.testing.assert_array_equal(np.asarray(p["l2_wg"]),
                                  np.asarray(full["l2_wg"][4:8]))
    np.testing.assert_array_equal(np.asarray(p["l2_sg"]),
                                  np.asarray(full["l2_sg"]))
    cfg = hm.HybridMoeConfig.from_afmoe(c, n_routed_experts=16)
    svc = _service(p, model=hm.HybridMoeLM(
        cfg, max_len=MAX_LEN, kv_dtype=jnp.float32, longest_chunk=16,
        experts_held=(4, 8)))
    seq = [int(t) for t in np.random.default_rng(2).integers(0, V, 21)]
    _, last = _prefill(svc, seq, svc._alloc_reclaiming(8), _Row())
    np.testing.assert_allclose(last, _ref_logits(p, seq, 20, c=c)[0],
                               atol=TOL, rtol=0)
    aux = {k: int(v) for k, v in svc._programs.take_aux()[-1].items()}
    assert aux["expert_assignments"] == 2 * 4 * 5     # layers x k x tokens
    assert 0 < aux["expert_assignments_held"] < aux["expert_assignments"]
    assert aux["shared_expert_tokens"] == 2 * 5


# -- the published sizes ---------------------------------------------------------
def _published():
    cfg = harness.load_json("configs", "trinity-mini.json")
    return cfg, cfg["published"]


def _count(shapes, pick=lambda n: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


def test_the_published_config_counts_26_billion_and_3_active():
    """ISSUE 47's reading of the ``afmoe`` block, from the shapes the
    program makes of the PUBLISHED config alone: 26.1 B parameters (the
    published "26B"), of which a token multiplies by 3.06 B (every layer's
    attention, router and shared expert, the dense layers, 8 of 128
    experts and the head; of the embedding it reads one row)."""
    _, pub = _published()
    cfg = hm.HybridMoeConfig.from_afmoe(pub)
    shapes = hm.hybrid_moe_param_shapes(cfg)
    total = _count(shapes)
    assert abs(total / 26.1e9 - 1) < 0.01
    routed = _count(shapes, lambda n: n.split("_", 1)[-1] in ("wg", "wu", "wd")
                    and len(shapes[n]) == 3)
    active = total - routed + routed * 8 // 128 - _count(
        shapes, lambda n: n == "tok_emb")
    assert abs(active / 3.06e9 - 1) < 0.01
    near = lambda n, millions: abs(n / 1e6 - millions) < 0.1  # noqa: E731
    layer = lambda i: _count(shapes, lambda n: n.startswith(f"l{i}_"))  # noqa: E731
    attn = _count(shapes, lambda n: n in (
        "l0_wq", "l0_wk", "l0_wv", "l0_wo", "l0_wgate"))
    assert near(attn, 27.26) and near(layer(0), 65.0)
    assert near(layer(2), 839.1) and near(layer(31), 839.1)
    assert near(shapes["tok_emb"][0] * shapes["tok_emb"][1] * 2, 820.0)


def test_the_cut_is_the_arithmetic_of_the_configuration_file():
    """What the cell holds: 16 of 32 layers (both dense, 14 expert layers:
    12 window and 4 full), 16 of 128 experts, the vocabulary whole —
    2,833 M parameters; and the cache's two kinds at the published
    widths."""
    config, pub = _published()
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    for key, value in pub.items():
        assert (config[key] != value) == (key in config["reduced"]), key
    lo, hi = config["experts_held"]
    cfg = hm.HybridMoeConfig.from_afmoe(config,
                                        n_routed_experts=pub["num_experts"])
    assert (len(cfg.layers_of(0)), len(cfg.layers_of(1))) == (4, 12)
    assert sum(cfg.moe_layer_freq) == 14 and hi - lo == 16
    shapes = hm.hybrid_moe_param_shapes(cfg, (lo, hi))
    assert abs(_count(shapes) / 1e6 - 2833) < 3
    assert abs(_count(shapes, lambda n: n.startswith("l2_")) / 1e6
               - 134.5) < 0.1
    full, window = hm.HybridMoeLM(cfg, max_len=config["max_len"]
                                  ).cache_spec()["kinds"]
    assert (full["name"], full["n_layers"], full["pools"]) == \
        ("full", 4, (("k", 512), ("v", 512)))
    assert (window["name"], window["n_layers"], window["window"]) == \
        ("window", 12, 2048)
    assert window["pools"] == full["pools"]
    model = hm.HybridMoeLM(cfg, max_len=config["max_len"],
                           experts_held=(lo, hi))
    assert model.offers == {"sampling"} and model.longest_chunk == 1024
    assert model.one_table_width and model.vocab == 200192
    assert model.counters == hm.COUNTERS + hm.AFMOE_COUNTERS + (
        "expert_trips", "expert_trips_extra")


# -- the cache of two kinds ------------------------------------------------------
def test_cache_is_built_from_the_models_kinds(svc8):
    cache = svc8._cache
    full, window = cache.kinds
    assert (full.name, full.n_layers, full.window) == ("full", 2, 0)
    assert (window.name, window.n_layers, window.window) == ("window", 6, WIN)
    assert window.num_blocks == 1 + 4 * window_blocks(WIN, 1, BS) \
        + window_blocks(WIN, 16, BS)
    assert [p.shape for p in cache.pools[full.span]] == \
        [(2, 256, BS, 32), (2, 256, BS, 32)]
    assert [p.shape for p in cache.pools[window.span]] == \
        [(6, 24, BS, 32), (6, 24, BS, 32)]


@pytest.mark.parametrize("chunk,plen,n_new", [
    (16, 3, 12), (16, 16, 8), (16, 23, 13), (16, 70, 30),
    (LONG, 37, 6), (LONG, 70, 30), (LONG, 100, 12)])
def test_service_generation_matches_reference_greedy(request, params, chunk,
                                                     plen, n_new):
    """Whole generations through submit / the scheduler / the step in
    flight / both cache kinds, token for token (float32 on both sides; the
    seeds give no tie).  The prompt of 3 crosses the window at its sixth
    decode step.  Cut at the model's longer chunk (a wider ring, a window
    pool for the longer chunk) the tokens are the same reference's."""
    svc8 = request.getfixturevalue("svc8" if chunk == 16 else "svc8_long")
    svc8.start()
    prompt = np.random.default_rng(100 + plen).integers(0, V, plen)
    assert svc8.generate(prompt, max_new_tokens=n_new, timeout=300) \
        == _ref_greedy(params, prompt, n_new)


def test_ten_clients_on_four_slots_are_served_the_reference(params, svc8):
    """Slots reused, rows idle beside live ones, admissions beside decode
    steps, rows that cross the window while they decode (prompts under 8)
    beside rows whose prefill slid: every request is served the
    reference's tokens."""
    svc8.start()
    before = svc8.stats()["counts"]
    rng = np.random.default_rng(45)
    prompts = [[int(t) for t in rng.integers(0, V, n)]
               for n in (5, 41, 6, 23, 3, 70, 16, 33, 7, 19)]
    news = (9, 2, 12, 4, 14, 3, 5, 2, 8, 3)
    streams = [svc8.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, news)]
    for st, p, n in zip(streams, prompts, news):
        assert st.result(300) == _ref_greedy(params, p, n)
    after = svc8.stats()["counts"]
    assert after["failed"] == before["failed"]
    assert after["window_rows_past"] > before["window_rows_past"]
    assert svc8._cache.kinds[1].allocator.num_used == 0


def test_preemption_resumes_to_the_same_tokens(params4):
    """A preempted row gives back the blocks of BOTH kinds; its resume
    re-prefills the whole context, the windows rebuilt as the chunks go,
    and serves the tokens an undisturbed run serves."""
    svc = _service(params4, model=_model(C4))
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
    assert stream.result(1) == _ref_greedy(params4, prompt, 30, c=C4)
    assert svc.stats()["counts"]["preempted"] == 1
    assert window.num_used == 0
    svc.stop(drain=False, timeout=30)


def test_the_programs_counts_and_the_kinds_gauges_reach_stats(params4):
    """``aux`` of every prefill chunk and decode step, summed once its
    step's tokens were read — ``hybrid_moe.COUNTERS`` and this model's two
    (decode rows at or past the window, tokens through the shared expert a
    layer) — and the manager's gauges for both kinds."""
    from mxnet_tpu import observability as obs

    svc = _service(params4, model=_model(C4))
    assert svc._programs._model.counters == hm.COUNTERS + hm.AFMOE_COUNTERS
    svc.start()
    svc.generate(np.arange(5), max_new_tokens=10, timeout=120)
    st = svc.stats()
    counts = st["counts"]
    assert counts["full_prefill_pairs"] == sum(range(1, 6))
    # decode steps at positions 5..13 (the tenth token needs no step read)
    assert counts["full_ctx_tokens"] == sum(range(6, 15))
    assert counts["window_ctx_tokens"] == sum(min(p, WIN)
                                              for p in range(6, 15))
    assert counts["window_rows_past"] == 6          # positions 8..13
    assert counts["expert_assignments"] == 2 * 4 * (5 + 9)
    assert counts["shared_expert_tokens"] == 2 * (5 + 9)
    assert counts["window_decode_trips"] == 0       # no kernel, no trip
    assert counts["window_blocks_freed"] >= 1       # slid while decoding
    assert st["cache_kinds"]["window"]["layers"] == 3
    assert st["cache_kinds"]["full"]["layers"] == 1
    text = obs.registry().to_prometheus()
    for kind in ("window", "full"):
        assert f'generation_kv_kind_blocks_used{{kind="{kind}"}}' in text
    svc.stop(drain=False, timeout=30)


def test_the_device_scopes_of_the_afmoe_terms_are_in_the_program(params4):
    """docs/observability.md "Device scopes": the gate, the QK-norm, the
    norm on a branch's output and the shared expert have scopes of their
    own beside the ones every model of this loop has."""
    cfg = hm.HybridMoeConfig.from_afmoe(C4)
    model = hm.HybridMoeLM(cfg, max_len=MAX_LEN, kv_dtype=jnp.float32)
    pools = tuple(
        jnp.zeros((k["n_layers"], 8, BS, w), jnp.float32)
        for k in model.cache_spec()["kinds"] for _, w in k["pools"])
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    text = jax.jit(lambda p, pl: model.step(
        p, i32(2, 1), i32(2, 1), jnp.ones(2, jnp.int32), pl,
        (i32(2, 8), i32(2, 4)), attention_kernel="gather")).lower(
        params4, pools).as_text(debug_info=True)
    for scope in ("layer0/attn.qk_norm", "layer0/attn.gate",
                  "layer0/norm.post", "layer0/ffn", "layer3/moe.shared",
                  "layer3/moe.route", "layer3/moe.combine",
                  "layer3/attn.kernel", "layer3/attn.cache_write"):
        assert scope in text, scope


# -- the other model of the loop -------------------------------------------------
# sha256 of the lowered text of mimo_v2's tiny programs (a decode step of 4
# rows and a 16-token chunk; the sums over gathered pages, and the decode
# step through the interpreted tiles body), recorded on the commit BEFORE
# afmoe's terms went into the loop (PR 47's parent).  A PR that changes
# mimo_v2's program on purpose records them anew and says so.
MIMO_TEXTS = {
    ("gather", 1):
        "51df7ec441988fe6ca72640cae4a1a0a936c1a93789da9858e023c25f613e919",
    ("gather", 16):
        "24cb9047ebdb9013a141ad68da08c11dd17c3e2c807b19f3c9ae08ca0ad5ed0f",
    ("paged", 1):
        "b039174e12f9974035f163e5048b5545893a064690e0984f72b9561c9b70a1f9",
}


@pytest.mark.parametrize("kernel,T", sorted(MIMO_TEXTS))
def test_mimo_v2s_tiny_programs_lower_to_the_text_they_had(kernel, T):
    """The guard of the shared loop: with every ``afmoe`` term off (the
    defaults), ``mimo-v2.5``'s program is the one it was, operation for
    operation — so its cell cannot move with this model's terms."""
    cfg = hm.HybridMoeConfig(
        vocab_size=97, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        hybrid_layer_pattern=(0, 1, 1), moe_layer_freq=(0, 1, 1),
        num_attention_heads=8, num_key_value_heads=1, head_dim=24,
        v_head_dim=16, swa_num_attention_heads=8, swa_num_key_value_heads=2,
        swa_head_dim=24, swa_v_head_dim=16, sliding_window=8,
        n_routed_experts=16, num_experts_per_tok=2,
        max_position_embeddings=512)
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in
              hm.hybrid_moe_param_shapes(cfg, (4, 8)).items()}
    model = hm.HybridMoeLM(cfg, max_len=512, experts_held=(4, 8),
                           kv_dtype=jnp.float32, longest_chunk=16)
    assert model.counters == hm.COUNTERS + ("expert_trips",
                                            "expert_trips_extra")
    pools = tuple(
        jax.ShapeDtypeStruct((k["n_layers"], nb, 4, w), jnp.float32)
        for k, nb in zip(model.cache_spec()["kinds"], (64, 24))
        for _, w in k["pools"])
    B, ring = (4, 4) if T == 1 else (1, 8)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = jax.jit(lambda p, t, pos, ln, pl, tf, tw: model.step(
        p, t, pos, ln, pl, (tf, tw), attention_kernel=kernel)).lower(
        params, i32(B, T), i32(B, T), i32(B), pools, i32(B, 128),
        i32(B, ring)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == MIMO_TEXTS[kernel, T]

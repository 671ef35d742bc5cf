"""The tiles body of ops/paged_attention.py as the window-and-full-attention
model calls it (tests/test_hybrid_moe.py has the preset and the tolerance):
against its oracle over gathered pages, and inside the service's programs at
the 3-layer cut, through the Pallas interpreter.  A file of its own because a
program of the interpreted kernel takes most of a minute to compile."""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.parallel import hybrid_moe as hm
from mxnet_tpu.serving.generation.kv_cache import blocks_for, ring_width
from test_hybrid_moe import (C3, CUT, WIN, _check_logits_through_the_cache,
                             _check_warmup_covers_the_traffic, _config,
                             _model, _service, params3)  # noqa: F401


# -- the tiles body against its oracle ---------------------------------------
def _ring_case(rng, B, T, hkv, G, dk, dv, window, bs, ring, n_blocks, dtype,
               ctx):
    """A paged pool, tables for rows that sit ``ctx[b]`` positions into
    their sequences and feed ``T`` more, the window kind's as a ring that
    holds only what those queries can see."""
    H = hkv * G
    k_pool = jnp.asarray(rng.normal(0, 1, (2, n_blocks, bs, hkv * dk)), dtype)
    v_pool = jnp.asarray(rng.normal(0, 1, (2, n_blocks, bs, hkv * dv)), dtype)
    q = jnp.asarray(rng.normal(0, 1, (B, T, H, dk)), jnp.float32)
    positions = np.asarray(ctx)[:, None] + np.arange(T)[None, :]
    max_pos = positions[:, -1].copy()
    free = list(rng.permutation(np.arange(1, n_blocks)))
    width = ring if window else blocks_for(int(max_pos.max()) + 1, bs)
    tables = np.zeros((B, width), np.int32)
    for b in range(B):
        first = max(0, positions[b, 0] - (window - 1)) // bs if window else 0
        for blk in range(first, max_pos[b] // bs + 1):
            tables[b, blk % width] = free.pop()
    return q, k_pool, v_pool, tables, positions.astype(np.int32), \
        max_pos.astype(np.int32)


def _oracle(q, k_pool, v_pool, tables, positions, hkv, window, sink, layer):
    """``paged_attention_reference`` over the gathered pages, a ring's
    slots at the positions they hold."""
    B, T = positions.shape
    bs, width = k_pool.shape[2], tables.shape[1]
    gather = lambda pool: pool[layer][tables].reshape(  # noqa: E731
        B, width * bs, hkv, -1).astype(jnp.float32)
    if window:
        first = jnp.maximum(positions[:, 0] - (window - 1), 0) // bs
        at = hm._ring_positions(jnp.asarray(first), width, bs)
    else:
        at = jnp.arange(width * bs)[None, :]
    mask = at[:, None, :] <= positions[:, :, None]
    if window:
        mask &= at[:, None, :] > positions[:, :, None] - window
    return pa.paged_attention_reference(
        q, gather(k_pool), gather(v_pool), mask, q.shape[-1] ** -0.5, sink)


@pytest.mark.parametrize("T,window,sink,dtype", [
    (1, 0, False, jnp.float32), (1, 16, True, jnp.float32),
    (1, 16, True, jnp.bfloat16), (24, 0, False, jnp.float32),
    (24, 16, True, jnp.float32), (24, 16, False, jnp.bfloat16),
    (80, 16, True, jnp.float32)],
    ids=["decode-full", "decode-window", "decode-window-bf16", "chunk-full",
         "chunk-window", "chunk-window-nosink-bf16", "chunk-window-tiles"])
def test_tiles_body_matches_its_oracle(T, window, sink, dtype):
    """Grouped heads as rows (4 query heads a KV head), K pages 24 lanes a
    head beside V pages 16, decode and chunks (80 x 4 rows: two tiles),
    rows at different depths, one inactive; the window walk over a ring
    that wrapped, the sink in the denominator."""
    rng = np.random.default_rng(T + window)
    hkv, G, dk, dv, bs = 2, 4, 24, 16, 8
    ring = ring_width(window, T, bs) if window else 0
    ctx = [0, 5, 37, 70]
    q, kp, vp, tables, pos, max_pos = _ring_case(
        rng, 4, T, hkv, G, dk, dv, window, bs, ring, 64, dtype, ctx)
    max_pos[0] = -1                        # an inactive row
    s = jnp.asarray(rng.normal(0, 1, hkv * G), jnp.float32) if sink else None
    got = pa.paged_attention(q, kp, vp, tables, pos, max_pos,
                             scale=dk ** -0.5, layer=1, window=window, sink=s,
                             call="window_prefill")
    want = _oracle(q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), hkv,
                   window, s, 1)
    assert got.shape == (4, T, hkv * G, dv)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want[1:]),
                               atol=tol, rtol=0)
    np.testing.assert_array_equal(np.asarray(got[0]), 0)


# -- a windowed call's trip is sized from what its tile can reach (PR 46) ----
_PHI = dict(window=512, bs=32, ring=32, sink=False)     # phi-4-mini-flash
_MIMO = dict(window=128, bs=16, ring=16, sink=True)     # mimo-v2.5
_AFM = dict(window=2048, bs=32, ring=128, sink=False)   # trinity-mini


@pytest.mark.parametrize("shape,ctx,dead", [
    (_PHI, [2048, 1024, 4096], ()), (_PHI, [2078, 1054, 542], ()),
    (_PHI, [2079, 1055, 543], ()), (_PHI, [1023, 1024, 1185], ()),
    (_PHI, [0, 100, 510], ()), (_PHI, [2050, 2051, 2077], (1,)),
    (_MIMO, [1024, 256, 4096], ()), (_MIMO, [1038, 270, 4110], ()),
    (_MIMO, [1039, 271, 127], ()),
    (_AFM, [0, 100, 513], ()), (_AFM, [1055, 2047, 2048], ()),
    (_AFM, [2079, 4200, 2048], ()), (_AFM, [4200, 2047, 1055], (1,)),
    (_AFM, [511, 1023, 1535], ()), (_AFM, [512, 1024, 1536], ())],
    ids=["w512-p0", "w512-p30", "w512-p31", "w512-ring-wraps",
         "w512-shorter-than-the-window", "w512-inactive-between",
         "w128-sink-p0", "w128-sink-p14", "w128-sink-p15",
         "w2048-under-a-part-and-across-its-edge",
         "w2048-one-short-of-the-window-and-at-it",
         "w2048-64-pages-and-a-ring-that-wrapped",
         "w2048-inactive-between", "w2048-a-part-full",
         "w2048-a-page-into-the-next-part"])
def test_a_windowed_decode_call_reads_its_reach_in_one_trip(shape, ctx, dead):
    """The decode call at the three cells' window, block length and ring,
    at the page boundaries the geometry creates: a query whose window
    straddles ``reach`` pages (17 of 32; 9 of 16) and one whose window
    ends on a page's last position (16; 8), a ring that wrapped, rows
    shorter than the window, an inactive row between live ones; the sink
    in the denominator.  ``trinity-mini``'s trip of 65 pages computes
    the fewest parts of 16 that hold a row's pages (PR 48): a row at 0,
    under one part (100), across a part's edge (513: 17 pages; 1,055: 33),
    one short of the window (2,047: 64), at it (2,048: all 65), 31
    positions later (2,079: 64 pages), a ring that wrapped (4,200), rows
    that fill 1, 2 and 3 parts to the last position (511, 1,023, 1,535) and
    rows one position further.  The results are the oracle's and every
    live row takes one trip."""
    window, bs, ring = shape["window"], shape["bs"], shape["ring"]
    rng = np.random.default_rng(sum(ctx))
    hkv, G, d = 2, 4, 16
    q, kp, vp, tables, pos, max_pos = _ring_case(
        rng, 3, 1, hkv, G, d, d, window, bs, ring, 64 if ring < 128 else 256,
        jnp.float32, ctx)
    for b in dead:
        max_pos[b] = -1
    s = jnp.asarray(rng.normal(0, 1, hkv * G), jnp.float32) \
        if shape["sink"] else None
    got = pa.paged_attention(q, kp, vp, tables, pos, max_pos,
                             scale=d ** -0.5, layer=1, window=window, sink=s,
                             call="window_decode")
    want = _oracle(q, kp, vp, jnp.asarray(tables), jnp.asarray(pos), hkv,
                   window, s, 1)
    live = [b for b in range(3) if b not in dead]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(got)[list(dead)], 0)
    reach = -(-window // bs) + 1
    assert pa._tiles_geometry(G, G, bs, ring, window,
                              pa._page_bytes(kp, vp)) == (8, reach)
    assert int(pa.tiles_decode_trips(pos, max_pos, kp, vp, ring, groups=G,
                                     window=window)) == len(live)


@pytest.mark.parametrize("rows,span,bs,width,window,page_bytes,pages", [
    (8, 1, 32, 32, 512, 2 * 32 * 1280 * 2, 17),   # phi-4-mini-flash, decode
    (8, 1, 16, 16, 128, 16 * (1536 + 1024) * 2, 9),   # mimo-v2.5, decode
    (256, 256, 16, 64, 128, 16 * (1536 + 1024) * 2, 25),  # its 512-token
    (256, 128, 16, 32, 128, 16 * (1536 + 1024) * 2, 17),  # and 128-token chunk
    # a chunk tile of phi-4-mini-flash reaches 25 (21) pages: 8.2 (6.9) MB
    # of K and V beside 256 query rows, which the chip's compiler refused
    (256, 256, 32, 64, 512, 2 * 32 * 1280 * 2, 16),
    (256, 128, 32, 32, 512, 2 * 32 * 1280 * 2, 16),
    # trinity-mini: 65 pages of 64 KB are 8.5 MB, which fit beside a decode
    # tile's 8 rows; its 512-token chunk's 73 beside 256 rows (9.6 MB and
    # 2,336 positions of scores a row) do not
    (8, 1, 32, 128, 2048, 2 * 32 * 512 * 2, 65),
    (256, 256, 32, 128, 2048, 2 * 32 * 512 * 2, 16),
    (8, 1, 32, 1024, 0, 2 * 32 * 1280 * 2, 16),   # no window: 512 positions
    (256, 256, 16, 1024, 0, 16 * (768 + 512) * 2, 32),
    (8, 1, 32, 8, 512, 2 * 32 * 1280 * 2, 8),     # never more than the table
    (8, 1, 16, 4, 0, 16 * 1280 * 2, 4), (8, 1, 4, 4, 8, 4 * 48 * 4, 3)],
    ids=["phi-decode", "mimo-decode", "mimo-chunk", "mimo-chunk128",
         "phi-chunk512-keeps", "phi-chunk128-keeps", "afm-decode",
         "afm-chunk512-keeps", "full-decode", "full-chunk", "narrow-ring",
         "narrow-table", "tiny"])
def test_the_pages_a_trip_of_the_tiles_body(rows, span, bs, width, window,
                                            page_bytes, pages):
    """``_tile_pages``: with a window the pages ``span`` consecutive
    queries can reach, where K and V fit fast memory beside the tile's own
    ``rows``; without one, and where they do not, ``_TILE_POSITIONS``
    positions; never more than the table's width."""
    assert pa._tile_pages(rows, span, bs, width, window, page_bytes) == pages
    assert pa._TILE_ROWS == 256 and pa._TILE_POSITIONS == 512


@pytest.mark.parametrize("pages,bs,part", [
    (65, 32, 16), (33, 32, 16), (32, 32, 16), (31, 32, 31), (17, 32, 17),
    (25, 16, 25), (9, 16, 9), (16, 32, 16), (32, 16, 32), (64, 16, 32)],
    ids=["afm-decode", "two-parts-and-a-page", "two-parts", "under-two",
         "phi-decode", "mimo-chunk", "mimo-decode", "full-32", "full-16",
         "two-parts-of-16"])
def test_a_long_trip_is_computed_to_a_part(pages, bs, part):
    """``_trip_part``: a trip that holds two runs of ``_TILE_POSITIONS``
    positions or more computes the fewest whole runs that hold its tile's
    pages; every shorter one is computed whole, as it always was."""
    assert pa._trip_part(pages, bs) == part


def test_a_tiles_span_is_its_own_rows_or_the_chunks():
    """A tile whole inside a group spans its rows' positions; a decode
    row's tile one; a tile across groups the chunk."""
    geometry = lambda rows, groups: pa._tiles_geometry(  # noqa: E731
        rows, groups, 16, 64, 128, 16 * 2560 * 2)
    assert geometry(8, 8) == (8, 9)                   # one position
    assert geometry(8 * 512, 8) == (256, 25)          # 256 of a group's 512
    assert geometry(8 * 128, 8) == (256, 17)          # two groups' same 128
    assert geometry(4 * 80, 4) == (256, 14)           # across groups: 80


def test_reference_attention_takes_a_sink_and_narrower_values():
    """``paged_attention_reference`` (the ``TPUMX_PALLAS=0`` path) with a
    sink: the softmax over the scores and one more column, dropped."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(0, 1, (2, 3, 4, 6)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 10, 2, 6)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 10, 2, 5)), jnp.float32)
    sink = jnp.asarray(rng.normal(0, 1, 4), jnp.float32)
    mask = jnp.ones((2, 3, 10), bool)
    got = pa.paged_attention_reference(q, k, v, mask, 0.5, sink)
    kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.5
    e = jnp.exp(s)
    p = e / (jnp.exp(sink)[None, :, None, None] + e.sum(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        jnp.einsum("bhqk,bkhd->bqhd", p, vv)), atol=1e-6, rtol=0)


def test_call_names():
    assert pa._call_name(1, 1024, "full_decode") == \
        "_paged_call_w1024_t1_full_decode"
    assert pa._call_name(512, 64, "window_prefill") == \
        "_paged_call_w64_t512_window_prefill"
    assert [hm.name_of(k) for k in (0, 1)] == ["full", "window"]


# -- the tiles body inside the service's programs ------------------------------
@pytest.fixture(scope="module")
def paged3(params3):
    """The 3-layer cut through the tiles body (interpreted)."""
    svc = _service(params3, "paged", model=_model(_config(**CUT)))
    yield svc
    svc.stop(drain=False, timeout=30)


@pytest.mark.parametrize("part", ["prefill", "decode"])
@pytest.mark.parametrize("plen", [16, 70])
def test_chunked_prefill_then_decode_match_reference_logits(params3, paged3,
                                                            plen, part):
    _check_logits_through_the_cache(paged3, params3, C3, "paged", plen, part)


def test_warmup_covers_every_program_the_traffic_needs(paged3):
    """With the kernel a table's width is free, so the service keeps one
    width."""
    _check_warmup_covers_the_traffic(paged3, 1)
    # the 400 rung only says how long a prompt may be
    assert paged3._prefill_signatures() == [(8, 128), (16, 128)] \
        and paged3._seq_buckets == [8, 16]
    rings = {key[0]: key[1][1][1][1][1] for key in paged3.compile_stats()}
    # window_blocks(8, T) rounded up to a power of two
    assert rings == {"gen_prefill": 8, "gen_decode": 4}


def test_the_window_calls_trips_reach_stats_one_a_row_a_layer(paged3):
    """``window_decode_trips``: the tiles body's trips over the window
    layers' decode calls, counted by the program from the call's own
    geometry — one a live row a window layer a step (the cut has two
    window layers; every step's row is deeper than the window)."""
    paged3.start()
    before = dict(paged3.stats()["counts"])
    paged3.generate(np.arange(21), max_new_tokens=6, timeout=600)
    counts = paged3.stats()["counts"]
    rows = (counts["window_ctx_tokens"] - before["window_ctx_tokens"]) \
        // WIN
    assert rows >= 5
    assert counts["window_decode_trips"] - before["window_decode_trips"] \
        == 2 * rows

"""Paged flash-decode attention (ops/paged_attention.py, docs/pallas.md):
the block-table-walking Pallas kernel vs the gathered-dense oracle — direct
kernel parity, the full transformer_lm_decode pipeline across block
boundaries / ragged lengths / inactive slots, chunked prefill, bf16 token
parity, and the zero-recompile + compile-key discipline of the
``TPUMX_PALLAS`` gate.  Runs on the Pallas interpreter (the CPU tier-1
leg); tools/tpu_parity.py re-checks interpreter-vs-native on a real chip.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel import transformer as tr
from mxnet_tpu.serving import pad_tokens_right
from mxnet_tpu.serving.generation import GenerationConfig, GenerationService
from oracle import CFG, greedy_oracle, params  # noqa: F401 (fixture)
from test_generation import _fresh_observability  # noqa: F401 (fixture)

pytestmark = pytest.mark.pallas


@pytest.fixture
def paged(monkeypatch):
    """Force the kernel layer on (CPU default is off; tier-1 exercises the
    interpreter leg through this)."""
    monkeypatch.setenv("TPUMX_PALLAS", "1")
    assert pk.pallas_enabled()


def _dense_reference(q, kp, vp, tables, positions, scale):
    B, T, H, D = q.shape
    W, bs = tables.shape[1], kp.shape[1]
    k_ctx = kp[jnp.asarray(tables)].reshape(B, W * bs, H, D)
    v_ctx = vp[jnp.asarray(tables)].reshape(B, W * bs, H, D)
    ctx_pos = np.arange(W * bs, dtype=np.int32)
    mask = jnp.asarray(ctx_pos[None, None, :] <= positions[:, :, None])
    return pa.paged_attention_reference(q, k_ctx, v_ctx, mask,
                                        jnp.float32(scale))


# -- direct kernel parity -----------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_kernel_matches_gathered_dense(paged, dtype):
    """Ragged per-row lengths, multi-block tables, a null-padded table
    tail, and an inactive row: every VALID row matches the gathered-dense
    attend — rtol 1e-5 in f32, bf16 at bf16 resolution."""
    rs = np.random.RandomState(0)
    B, T, H, D = 4, 4, 2, 16
    nb, bs, W = 12, 4, 4
    dt = jnp.dtype(dtype)
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32)).astype(dt)
    q, kp, vp = mk(B, T, H, D), mk(nb, bs, H * D), mk(nb, bs, H * D)
    tables = np.zeros((B, W), np.int32)
    tables[0, :4] = [2, 5, 7, 9]     # full table
    tables[1, :2] = [1, 3]           # ragged: shorter context
    tables[2, :1] = [4]              # single block
    positions = np.zeros((B, T), np.int32)
    positions[0] = [12, 13, 14, 15]  # prefill chunk crossing block 3
    positions[1] = [5, 0, 0, 0]      # decode-style single query
    positions[2] = [0, 1, 2, 3]      # from position zero
    lengths = np.array([4, 1, 4, 0], np.int32)   # row 3 inactive
    valid = np.arange(T)[None, :] < lengths[:, None]
    max_pos = np.where(valid, positions, -1).max(axis=1).astype(np.int32)
    scale = pa.attention_scale(D)

    got = pa.paged_attention(q, kp[None], vp[None], tables, positions,
                             max_pos, scale)
    want = _dense_reference(q, kp, vp, tables, positions, scale)
    assert got.dtype == dt
    tol = dict(rtol=1e-5, atol=1e-5) if dt == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    for b in range(B):
        for t in range(T):
            if valid[b, t]:
                np.testing.assert_allclose(
                    np.asarray(got[b, t], np.float32),
                    np.asarray(want[b, t], np.float32),
                    err_msg=f"row {b} query {t}", **tol)
    # fully-skipped rows emit exactly zero (never NaN/inf)
    assert float(jnp.abs(got[3].astype(jnp.float32)).max()) == 0.0


def test_kernel_tiles_and_pads_long_chunks(paged):
    """A chunk longer than one query tile (256 rows) runs as several grid
    tiles, with T padded up to a whole number of them in the wrapper (the
    engine's top prefill bucket is max_len - 1): every real row still
    matches the gathered-dense attend."""
    rs = np.random.RandomState(1)
    B, T, H, D = 1, 300, 2, 16
    nb, bs, W = 48, 8, 40
    assert pa._query_tile(T, H * D) == 256
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32))
    q, kp, vp = mk(B, T, H, D), mk(nb, bs, H * D), mk(nb, bs, H * D)
    tables = np.zeros((B, W), np.int32)
    tables[0, :38] = rs.permutation(np.arange(1, nb))[:38]   # 304 slots
    positions = np.arange(T, dtype=np.int32)[None, :]
    max_pos = np.array([T - 1], np.int32)
    scale = pa.attention_scale(D)
    got = pa.paged_attention(q, kp[None], vp[None], tables, positions,
                             max_pos, scale)
    want = _dense_reference(q, kp, vp, tables, positions, scale)
    assert got.shape == (B, T, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- the layered pool, and the decode body's page loop -------------------------------
def _layered_case(lens, W, bs=16, H=2, D=64, layers=3, T=1, dtype=np.float32,
                  null_at=(), seed=0):
    """A pool whose layers differ, rows of the given context lengths (0:
    an inactive row) on a shuffled block table, and T queries a row that
    end at the row's last position.  ``null_at``: (row, logical block)
    table entries nulled INSIDE the live range."""
    rs = np.random.RandomState(seed)
    B = len(lens)
    need = sum(-(-n // bs) for n in lens)
    nb = need + 8
    dt = jnp.dtype(dtype)
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32)).astype(dt)
    q = mk(B, T, H, D)
    kp, vp = mk(layers, nb, bs, H * D), mk(layers, nb, bs, H * D)
    tables = np.zeros((B, W), np.int32)
    perm, o = rs.permutation(np.arange(1, nb)), 0
    for b, n in enumerate(lens):
        k = -(-n // bs)
        tables[b, :k] = perm[o:o + k]
        o += k
    for b, j in null_at:
        tables[b, j] = 0
    positions = np.zeros((B, T), np.int32)
    for b, n in enumerate(lens):
        positions[b] = np.maximum(np.arange(n - T, n), 0)
    max_pos = np.asarray([n - 1 for n in lens], np.int32)
    return q, kp, vp, tables, positions, max_pos


def _layered_reference(q, kp, vp, tables, positions, layer, scale):
    """The gathered-dense attend on ONE layer's slice, null table entries
    masked out (the kernels never read them)."""
    B, T, H, D = q.shape
    W, bs = tables.shape[1], kp.shape[2]
    jt = jnp.asarray(tables)
    k_ctx = kp[layer][jt].reshape(B, W * bs, H, D)
    v_ctx = vp[layer][jt].reshape(B, W * bs, H, D)
    ctx_pos = np.arange(W * bs, dtype=np.int32)
    mask = (ctx_pos[None, None, :] <= positions[:, :, None]) \
        & np.repeat(tables != 0, bs, axis=1)[:, None, :]
    return pa.paged_attention_reference(q, k_ctx, v_ctx, jnp.asarray(mask),
                                        jnp.float32(scale))


def _assert_rows_match(got, want, lens, tol):
    for b, n in enumerate(lens):
        if n:
            np.testing.assert_allclose(
                np.asarray(got[b], np.float32),
                np.asarray(want[b], np.float32), err_msg=f"row {b} (context {n})", **tol)
        else:       # inactive rows emit exactly zero, never NaN
            assert float(jnp.abs(got[b].astype(jnp.float32)).max()) == 0.0


F32_TOL = dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T", [1, 4], ids=["decode", "chunk"])
@pytest.mark.parametrize("layer", [1, 2])
def test_layered_pool_reads_its_own_layer(paged, T, layer):
    """The kernel is handed the whole ``(n_layers, num_blocks, bs, H*D)``
    pool and reads the layer it is told to — both bodies — not layer 0."""
    lens = [37, 5, 0, 64]
    q, kp, vp, tables, positions, max_pos = _layered_case(lens, W=4, T=T)
    scale = pa.attention_scale(q.shape[3])
    got = pa.paged_attention(q, kp, vp, tables, positions, max_pos, scale,
                             layer=layer)
    want = _layered_reference(q, kp, vp, tables, positions, layer, scale)
    for b, n in enumerate(lens):
        valid = min(T, n)
        if n:
            np.testing.assert_allclose(np.asarray(got[b, T - valid:]),
                                       np.asarray(want[b, T - valid:]),
                                       err_msg=f"row {b}", **F32_TOL)
    other = _layered_reference(q, kp, vp, tables, positions, 0, scale)
    assert float(jnp.abs(got[0, -1] - other[0, -1]).max()) > 1e-2


# page = 16 positions, a trip's group = 8 pages = 128 positions at W = 64
_DECODE_EDGES = {
    "one_token": ([1, 2], ()),
    "ends_on_a_page": ([16, 32, 48], ()),
    "one_past_a_page": ([17, 33], ()),
    "ends_on_a_group": ([128, 256], ()),
    "one_past_a_group": ([129, 257], ()),
    "pages_not_a_multiple_of_group": ([176, 80, 300], ()),    # 11, 5, 19
    "inactive_rows": ([0, 200, 0, 0, 9], ()),
    "inactive_first_and_last": ([0, 0, 130, 0], ()),
    "null_inside_the_table": ([200, 150, 40], ((0, 3), (0, 9), (1, 0),
                                               (2, 1))),
    "whole_group_null": ([300], tuple((0, j) for j in range(8, 16))),
    "all_pages_null": ([40, 20], ((0, 0), (0, 1), (0, 2))),
    "mixed_short_and_1000": ([3, 1000, 40, 517, 1, 1000], ()),
}


@pytest.mark.parametrize("case", list(_DECODE_EDGES))
def test_decode_body_page_loop_edges(paged, case):
    """The single-query body walks the row's LIVE pages only, 8 a trip,
    prefetching across the end of a row: every edge the page loop has."""
    lens, null_at = _DECODE_EDGES[case]
    q, kp, vp, tables, positions, max_pos = _layered_case(
        lens, W=64, null_at=null_at, seed=len(case))
    assert pa._decode_pages(1, 64, 16, 128, kp.dtype, False) == 8
    scale = pa.attention_scale(q.shape[3])
    got = pa.paged_attention(q, kp, vp, tables, positions, max_pos, scale,
                             layer=1)
    want = _layered_reference(q, kp, vp, tables, positions, 1, scale)
    if case == "all_pages_null":     # nothing to attend to: 0, like a dead row
        assert float(jnp.abs(got[0]).max()) == 0.0
        lens = [0] + lens[1:]
        got = got.at[0].set(0)
    _assert_rows_match(got, want, lens, F32_TOL)
    assert bool(jnp.all(jnp.isfinite(got)))


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
@pytest.mark.parametrize("W", [1, 4, 64])
def test_decode_body_dtypes_and_widths(paged, dtype, W):
    """f32 and bf16 pools through the single-query body at a table one
    page wide (a trip fetches 1 page), four, and 64 (8 a trip)."""
    top = W * 16
    lens = [top, max(1, top // 3), 0, min(7, top)]
    q, kp, vp, tables, positions, max_pos = _layered_case(
        lens, W=W, dtype=dtype, seed=W)
    assert pa._decode_pages(1, W, 16, 128, kp.dtype, False) == min(W, 8)
    got = pa.paged_attention(q, kp, vp, tables, positions, max_pos, layer=2)
    want = _layered_reference(q, kp, vp, tables, positions, 2,
                              pa.attention_scale(q.shape[3]))
    assert got.dtype == kp.dtype
    tol = F32_TOL if kp.dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    _assert_rows_match(got, want, lens, tol)


@pytest.mark.parametrize("T", [1, 4], ids=["decode", "chunk"])
def test_int8_pool_layered(paged, T):
    """An int8 pool takes the chunk body at every T (its per-(block,
    head) scales ride that body's index maps): the layered pool, one
    layer's scales, against the dequantized gather."""
    lens = [37, 5, 0, 64]
    q, kp, vp, tables, positions, max_pos = _layered_case(lens, W=4, T=T)
    rs = np.random.RandomState(7)
    L, nb, bs, HD = kp.shape
    H = q.shape[2]
    kq = jnp.asarray(rs.randint(-127, 128, kp.shape), jnp.int8)
    vq = jnp.asarray(rs.randint(-127, 128, vp.shape), jnp.int8)
    ks = jnp.asarray(np.abs(rs.randn(L, nb, H)) * 0.02 + 0.01, jnp.float32)
    vs = jnp.asarray(np.abs(rs.randn(L, nb, H)) * 0.02 + 0.01, jnp.float32)
    assert pa._decode_pages(T, 4, bs, HD, kq.dtype, True) == 0
    layer = 2
    got = pa.paged_attention(q, kq, vq, tables, positions, max_pos,
                             k_scale=ks[layer], v_scale=vs[layer],
                             layer=layer)
    deq = lambda p, s: (p.astype(jnp.float32).reshape(L, nb, bs, H, -1)
                        * s[:, :, None, :, None]).reshape(p.shape)
    want = _layered_reference(q, deq(kq, ks), deq(vq, vs), tables,
                              positions, layer,
                              pa.attention_scale(q.shape[3]))
    for b, n in enumerate(lens):
        valid = min(T, n)
        if n:
            np.testing.assert_allclose(np.asarray(got[b, T - valid:]),
                                       np.asarray(want[b, T - valid:]),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape,pages", [
    (dict(t=1, w=64, bs=16, hd=1280, dt="float32", q=False), 8),   # the cell
    (dict(t=1, w=64, bs=16, hd=1280, dt="bfloat16", q=False), 8),
    (dict(t=1, w=2, bs=16, hd=1280, dt="float32", q=False), 2),    # narrow
    (dict(t=1, w=64, bs=32, hd=256, dt="float32", q=False), 4),    # 128 pos
    (dict(t=1, w=64, bs=16, hd=16384, dt="float32", q=False), 1),  # VMEM bound
    (dict(t=4, w=64, bs=16, hd=1280, dt="float32", q=False), 0),   # a chunk
    (dict(t=1, w=64, bs=16, hd=1280, dt="int8", q=True), 0),       # int8 pool
    (dict(t=1, w=64, bs=4, hd=1280, dt="float32", q=False), 0),    # part tile
    (dict(t=1, w=64, bs=8, hd=1280, dt="bfloat16", q=False), 0),   # part tile
    (dict(t=1, w=64, bs=16, hd=192, dt="float32", q=False), 0),    # 1.5 lanes
], ids=lambda v: "-".join(f"{k}{x}" for k, x in v.items())
    if isinstance(v, dict) else str(v))
def test_body_is_chosen_from_the_shapes(shape, pages):
    """Which body a call takes, and how many pages a trip of the decode
    body fetches, follow from the call's shapes alone."""
    assert pa._decode_pages(shape["t"], shape["w"], shape["bs"], shape["hd"],
                            jnp.dtype(shape["dt"]), shape["q"]) == pages


def _one_layer_values(jaxpr, pool_shape, found):
    """Equations OUTSIDE a kernel call whose result is one layer's slice
    of the pool, ``(num_blocks, bs, H*D)`` with or without a leading 1."""
    one = (pool_shape[1:], (1,) + pool_shape[1:])
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if any(getattr(v.aval, "shape", None) in one for v in eqn.outvars):
            found.append(str(eqn.primitive))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _one_layer_values(inner, pool_shape, found)
    return found


@pytest.mark.parametrize("kv", ["float", "int8"])
@pytest.mark.parametrize("T", [1, 16], ids=["decode", "prefill"])
def test_model_step_never_slices_the_pool(params, T, kv):
    """The paged model step hands the kernel the whole pool: no equation
    outside the kernel call makes a (num_blocks, bs, H*D) array of the
    (n_layers, num_blocks, bs, H*D) one — XLA would copy it before the
    opaque call, the whole pool once a step (PERF.md, PR 25) — and the
    int8 scatter requantizes its blocks in the whole pool too.  The
    gather path does slice (XLA fuses that slice into its gather)."""
    B, W, bs = 2, 2, 8
    shape = (CFG.n_layers, 16, bs, CFG.d_model)
    pool = jnp.zeros(shape, jnp.int8 if kv == "int8" else jnp.float32)
    kw = {}
    if kv == "int8":
        sc = jnp.ones((CFG.n_layers, 16, CFG.n_heads))
        kw = dict(k_scale=sc, v_scale=sc)
    args = (np.zeros((B, T), np.int32), np.zeros((B, T), np.int32),
            np.ones(B, np.int32), pool, pool, np.ones((B, W), np.int32))

    def step(kernel):
        return jax.make_jaxpr(lambda p, *a: tr.transformer_lm_decode(
            p, *a, CFG, attention_kernel=kernel, **kw))(params, *args)

    assert _one_layer_values(step("paged").jaxpr, shape, []) == []
    assert _one_layer_values(step("gather").jaxpr, shape, []) != []


# -- full decode pipeline -----------------------------------------------------------
def test_decode_pipeline_matches_dense_across_blocks(params, paged,
                                                     monkeypatch):
    """Prefill + single-token decode steps crossing a block boundary under
    the kernel reproduce the TPUMX_PALLAS=0 gather+dense pipeline at rtol
    1e-5 (f32) — and both reproduce full transformer_lm_apply."""
    rs = np.random.RandomState(0)
    plen, n_steps, bs = 13, 7, 8
    prompt = rs.randint(0, CFG.vocab, plen)
    table = np.array([[1, 2, 3]], np.int32)
    tb = 16

    def run(gate):
        monkeypatch.setenv("TPUMX_PALLAS", gate)
        kp = jnp.zeros((CFG.n_layers, 16, bs, CFG.d_model))
        vp = jnp.zeros_like(kp)
        outs = []
        logits, kp, vp = tr.transformer_lm_decode(
            params, pad_tokens_right(prompt.astype(np.int32), tb)[None, :],
            np.arange(tb, dtype=np.int32)[None, :],
            np.asarray([plen], np.int32), kp, vp, table[:, :2], CFG)
        outs.append(np.asarray(logits[0, :plen]))
        toks = list(prompt)
        last = logits[0, plen - 1]
        for _ in range(n_steps):
            nxt = int(jnp.argmax(last))
            toks.append(nxt)
            pos = len(toks) - 1
            logits, kp, vp = tr.transformer_lm_decode(
                params, np.asarray([[nxt]], np.int32),
                np.asarray([[pos]], np.int32), np.asarray([1], np.int32),
                kp, vp, table, CFG)
            last = logits[0, 0]
            outs.append(np.asarray(last))
        return toks, outs

    toks_paged, outs_paged = run("1")
    toks_dense, outs_dense = run("0")
    assert len(toks_paged) > 16, "must cross a block boundary"
    assert toks_paged == toks_dense
    for a, b in zip(outs_paged, outs_dense):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # and the kernel pipeline agrees with the cacheless full apply
    full = tr.transformer_lm_apply(
        params, jnp.asarray([toks_paged], jnp.int32),
        jnp.arange(len(toks_paged), dtype=jnp.int32), CFG)
    np.testing.assert_allclose(outs_paged[-1], np.asarray(full[0, -1]),
                               rtol=1e-4, atol=1e-4)


def test_bf16_oracle_token_bitwise(params, paged, monkeypatch):
    """bf16 decode through the kernel: greedy tokens are BITWISE identical
    to the gather+dense bf16 pipeline, logits agree at bf16 resolution
    (the one-pass online softmax keeps f32 probabilities where the dense
    path rounds them to bf16 — sub-ulp-of-bf16 differences)."""
    rs = np.random.RandomState(3)
    plen, bs = 11, 8
    prompt = rs.randint(0, CFG.vocab, plen)
    table = np.array([[1, 2, 3]], np.int32)

    def run(gate):
        monkeypatch.setenv("TPUMX_PALLAS", gate)
        kp = jnp.zeros((CFG.n_layers, 16, bs, CFG.d_model),
                       jnp.bfloat16)
        vp = jnp.zeros_like(kp)
        logits, kp, vp = tr.transformer_lm_decode(
            params, pad_tokens_right(prompt.astype(np.int32), 16)[None, :],
            np.arange(16, dtype=np.int32)[None, :],
            np.asarray([plen], np.int32), kp, vp, table[:, :2], CFG,
            compute_dtype=jnp.bfloat16)
        toks = list(prompt)
        last = logits[0, plen - 1]
        all_logits = [np.asarray(last)]
        for _ in range(6):
            nxt = int(jnp.argmax(last))
            toks.append(nxt)
            logits, kp, vp = tr.transformer_lm_decode(
                params, np.asarray([[nxt]], np.int32),
                np.asarray([[len(toks) - 1]], np.int32),
                np.asarray([1], np.int32), kp, vp, table, CFG,
                compute_dtype=jnp.bfloat16)
            last = logits[0, 0]
            all_logits.append(np.asarray(last))
        return toks, all_logits

    toks_paged, lg_paged = run("1")
    toks_dense, lg_dense = run("0")
    assert toks_paged == toks_dense          # the serving-level contract
    for a, b in zip(lg_paged, lg_dense):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


def test_inactive_slots_null_block_isolation(params, paged):
    """Under the kernel gate, inactive (length-0) decode slots still write
    only to the reserved null block 0 and never corrupt live cache."""
    bs = 8
    kp = jnp.zeros((CFG.n_layers, 8, bs, CFG.d_model))
    vp = jnp.zeros_like(kp)
    toks = np.array([[5], [7]], np.int32)
    pos = np.array([[0], [3]], np.int32)
    lengths = np.array([1, 0], np.int32)
    tables = np.array([[1], [2]], np.int32)
    _, kp, vp = tr.transformer_lm_decode(params, toks, pos, lengths,
                                         kp, vp, tables, CFG)
    assert float(jnp.abs(kp[:, 1, 0]).sum()) > 0    # active row wrote
    assert float(jnp.abs(kp[:, 2]).sum()) == 0.0    # inactive row did NOT


# -- engine integration -------------------------------------------------------------
def _gc(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("seq_buckets", [16, 32])
    kw.setdefault("max_new_tokens", 8)
    return GenerationConfig(**kw)


def test_service_greedy_parity_chunked_prefill(params, paged, monkeypatch):
    """End-to-end service under the kernel WITH chunked prefill: streamed
    tokens equal full-sequence greedy decoding (f32)."""
    monkeypatch.setenv("TPUMX_GEN_CHUNKED_PREFILL", "1")
    svc = GenerationService(params, CFG, _gc(chunked_prefill=True),
                            start=False)
    svc.warmup()
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (30, 7, 19)]
    handles = [svc.submit(p, max_new_tokens=6) for p in prompts]
    svc.start()
    results = [h.result(120) for h in handles]
    assert svc.stats()["decode_kernel"] == "paged"
    svc.stop()
    for got, p in zip(results, prompts):
        assert got == greedy_oracle(params, p, 6)


def test_zero_recompiles_under_freeze_paged(params, paged, monkeypatch):
    """Warmup enumerates the same (kind, B, T, W) signature set with the
    kernel on; a staggered mixed stream then runs frozen with exactly one
    miss per signature, and the paged program variants count per-site."""
    from mxnet_tpu.executor import compile_cache_stats

    svc = GenerationService(params, CFG, _gc(max_slots=3), start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(2)
    handles = []
    svc.start()
    for i, n in enumerate([3, 16, 29, 9, 22, 12]):
        handles.append(svc.submit(rs.randint(0, CFG.vocab, n),
                                  max_new_tokens=3 + (i % 4), seed=i))
        if i % 3 == 0:
            time.sleep(0.01)
    for h in handles:
        h.result(120)
    stats = svc.compile_stats()
    svc.stop()
    assert stats, "no programs recorded"
    for key, st in stats.items():
        assert st["misses"] == 1, f"recompile at {key}: {st}"
        assert ("kernel", "paged") in key[1]
    by_site = compile_cache_stats().get("by_site", {})
    assert "gen_decode_paged" in by_site and \
        "gen_prefill_paged" in by_site, \
        f"no paged program sites in {list(by_site)[:8]}"


def test_gate_off_keys_byte_identical(params, monkeypatch):
    """TPUMX_PALLAS=0 must reproduce the pre-kernel compile keys exactly
    (warm caches and freeze sets carry over across the gate)."""
    from mxnet_tpu.serving.generation.programs import GenerationPrograms

    cache = GenerationService(params, CFG, _gc(), start=False)._cache
    tokens = np.zeros((1, 16), np.int32)
    tables = np.zeros((1, 2), np.int32)
    monkeypatch.setenv("TPUMX_PALLAS", "0")
    progs = GenerationPrograms(params, CFG)
    key = progs._key("gen_prefill", cache, tokens, tables)
    assert key == ("gen_prefill",
                   (("tokens", (1, 16), "int32"),
                    ("block_tables", (1, 2), "int32"),
                    ("kv_pool", cache.shape, str(cache.dtype))))
    assert progs.kernel == "gather"
    # the kernel choice is FROZEN at construction: a later env flip can
    # never desync keys from already-traced programs
    monkeypatch.setenv("TPUMX_PALLAS", "1")
    assert progs.kernel == "gather"
    assert progs._key("gen_prefill", cache, tokens, tables) == key
    progs_paged = GenerationPrograms(params, CFG)
    assert progs_paged.kernel == "paged"
    key_paged = progs_paged._key("gen_prefill", cache, tokens, tables)
    assert key_paged[1][-1] == ("kernel", "paged")


# -- model-parallel serving through the paged kernel --------------------------------
def test_sharded_kernel_bitwise_matches_unsharded(paged):
    """paged_attention_sharded: the per-head shard_map over an mp mesh is
    the SAME kernel on each rank's head slice — bitwise equal output."""
    from mxnet_tpu.parallel.mesh import make_mesh

    rs = np.random.RandomState(3)
    B, T, H, D = 3, 1, 4, 8
    nb, bs, W = 8, 4, 3
    mk = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    q, kp, vp = mk(B, T, H, D), mk(nb, bs, H * D), mk(nb, bs, H * D)
    tables = np.array([[1, 2, 0], [3, 0, 0], [4, 5, 1]], np.int32)
    positions = np.array([[6], [2], [9]], np.int32)
    max_pos = np.array([6, 2, 9], np.int32)
    want = pa.paged_attention(q, kp[None], vp[None], tables, positions,
                              max_pos)
    mesh = make_mesh({"mp": 2}, install=False)
    got = pa.paged_attention_sharded(q, kp[None], vp[None], tables,
                                     positions, max_pos, mesh=mesh)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # an indivisible head count is refused with a clear error, not an
    # opaque shard_map failure
    from mxnet_tpu.base import MXNetError

    mesh8 = make_mesh({"mp": 8}, install=False)
    with pytest.raises(MXNetError):
        pa.paged_attention_sharded(q, kp[None], vp[None], tables,
                                   positions, max_pos, mesh=mesh8)


def test_service_mp2_decodes_through_paged_kernel(params, paged):
    """The mp-sharded engine no longer falls back to the dense gather: with
    heads % mp == 0 the decode runs the per-head shard_map'd Pallas kernel
    (engine stats decode_kernel == "paged"), the KV pool lives head-sharded
    (1/mp of the cache per chip), and greedy tokens are bit-identical to
    the mp=1 paged path."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, CFG.vocab, n) for n in (4, 9)]

    def run(mp):
        svc = GenerationService(params, CFG, _gc(mp_devices=mp,
                                                 seq_buckets=[16]),
                                start=False)
        assert svc._programs.kernel == "paged"
        if mp > 1:
            assert len(svc._cache.k.sharding.device_set) == mp
        svc.start()
        outs = [svc.generate(p, max_new_tokens=4, temperature=0.0)
                for p in prompts]
        kern = svc.stats()["decode_kernel"]
        svc.stop()
        return outs, kern

    outs2, kern2 = run(2)
    outs1, kern1 = run(1)
    assert kern1 == kern2 == "paged"
    assert outs1 == outs2
    for got, p in zip(outs2, prompts):
        assert got == greedy_oracle(params, p, 4)


def test_service_mp_indivisible_heads_fall_back_to_gather(params, paged):
    """4 heads over mp=8 cannot head-shard the kernel: the ONLY remaining
    gather fallback, frozen at construction."""
    svc = GenerationService(params, CFG, _gc(mp_devices=8), start=False)
    assert svc._programs.kernel == "gather"


def test_service_mp2_zero_postwarmup_compiles(params, paged, monkeypatch):
    """Warmup + freeze discipline holds unchanged under the mp-sharded
    paged kernel: 1 miss per signature, paged by_site variants, zero
    post-warmup compiles."""
    svc = GenerationService(params, CFG, _gc(mp_devices=2,
                                             seq_buckets=[16]),
                            start=False)
    warmed = svc.warmup()
    assert warmed == len(svc.compile_stats())
    monkeypatch.setenv("TPUMX_FREEZE_COMPILES", "1")
    rs = np.random.RandomState(6)
    svc.start()
    handles = [svc.submit(rs.randint(0, CFG.vocab, n),
                          max_new_tokens=2 + (i % 2), seed=i)
               for i, n in enumerate([3, 14, 9])]
    for h in handles:
        h.result(120)
    stats = svc.compile_stats()
    svc.stop()
    assert stats and all(v["misses"] == 1 for v in stats.values())
    assert all(("kernel", "paged") in k[1] for k in stats)

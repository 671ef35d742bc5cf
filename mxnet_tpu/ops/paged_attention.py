"""Paged flash-decode attention (vLLM's PagedAttention, as a Pallas TPU
kernel).

The generation engine's decode hot path (parallel/transformer.py
``transformer_lm_decode``) historically GATHERED the whole paged KV context
into contiguous ``(B, W*bs, H, D)`` arrays and ran dense attention over the
full table-width bucket every token.  This kernel walks the block table
INSIDE the kernel instead, and it is handed the WHOLE layered pool
``(n_layers, num_blocks, bs, H*D)`` plus the layer's index (a scalar
operand: every layer's call is the same kernel): a ``pallas_call`` is an
opaque custom call, so a ``k_pool[i]`` operand would be materialized — the
whole pool copied once per step (PERF.md, PR 25).  The kernel fetches its
own pages from the donated pool in place.

Two bodies, chosen by what the call sees in its shapes:

* **chunk** (``T > 1``: prefill, speculative verify, a block-diffusion
  step; also an int8 pool and pages that are not whole sublane tiles):
  grid ``(B, T tiles, W / pages)``, the table a scalar-prefetch operand,
  ``(bs, H*D)`` K/V pages through BlockSpecs whose index maps return
  ``(layer, block, 0, 0)`` — one page a grid step, or, for grouped heads,
  8 (the pool is then an operand 8 times over, each with its own index
  map).  Null table slots and pages past the last position the TILE's
  queries may read are redirected to block 0 and skipped.  Up to 256 query
  rows a tile feed the MXU and amortise the grid step.  **Grouped heads**
  (the pool folded ``Hkv * D`` with ``Hkv`` dividing the query heads):
  the ``G = H / Hkv`` query heads of a KV head become ``G * T`` query rows
  against that head's lanes — the wrapper regroups ``q`` to ``(B, G*T,
  Hkv*D)``, as wide as a page, and the body is the same; K and V are never
  repeated in HBM.  The mask is "cache position <= the position given for
  the query", so a model with a block mask passes each query's block-end
  position.
* **decode** (``T == 1``): a single query a row is bound by steps and
  bytes, so the pools stay in HBM (``memory_space=ANY``) and the body
  issues its own DMAs: grid ``(B,)``, a ``fori_loop`` over the row's LIVE
  page groups only — ``ceil((max_pos // bs + 1) / P)`` trips — each trip
  waiting for ``P`` pages of K and V in one half of a double buffer while
  the next group (or the next row's first) lands in the other half, then
  one online-softmax update over ``P * bs`` cache positions with all heads
  at once.  Dead pages are never visited: the table's width bucket costs
  nothing.

* **tiles** (a call with a ``window``, a ``sink`` or V pages narrower
  than its K pages, or one that asks for it — layers of two kinds in one
  model, parallel/hybrid_moe.py): grid ``(B * tiles,)``, up to 256 of the
  grouped query rows a tile, decode (one tile of the ``G`` query heads of
  a KV head) and a prefill chunk alike.  As the decode body it fetches its
  own pages, and only those its tile reads: from the first page its
  earliest query's window reaches (``max(0, pos - window + 1) // bs``; 0
  without a window) to its latest query's own, found in the table as a
  RING — logical page ``p`` in column ``p % W`` — so that a window layer's
  table is as wide as a window and not as a sequence.  A trip's size is
  the call's own: WITHOUT a window 512 positions, the row's pages in
  ``ceil(live / pages)`` trips; WITH one the pages its tile's queries can
  reach, ``ceil((t + window - 1) / bs) + 1`` for ``t`` consecutive
  positions (17 for one token at 512 / 32, 9 at 128 / 16, 65 at 2,048 /
  32), in ONE trip wherever K and V fit fast memory double-buffered beside
  the tile's own query rows (``_tile_pages``: static, from the tile's
  rows, ``window``, the block length, the page's bytes and the table's
  width) — the next trip prefetched is then the next row's.  A trip
  computes every position of its page group, fetched or not; one of two
  runs of 512 positions or more computes the fewest whole runs that hold
  its tile's pages, in one update (``_trip_part``).  The mask is
  ``pos - window < j <= pos``; the sink is one learned logit a query head
  that joins the softmax's denominator and no value: the recurrence starts
  at ``m = sink, l = 1, acc = 0``.  K pages hold ``dk`` lanes a head, V
  pages ``dv``, each sliced at its own width.

All accumulate with the online-softmax m/l recurrence in f32 (the same
scheme as ops/flash_attention.py's forward).  Masking is by cache-position
<= query-position, exactly the dense path's mask, so bucketed table widths
never perturb real rows.

The pool keeps heads FOLDED into its minor dim, so a K/V page is a
lane-dense ``(bs, H*D)`` tile the TPU compiler accepts (docs/pallas.md
"block-layout rule").

Gating: ``mxnet_tpu.ops.pallas_kernels.pallas_enabled()`` — default on for
TPU, ``TPUMX_PALLAS=0`` restores the gather+dense XLA path
(``paged_attention_reference`` below IS that path).  On CPU the same
kernels run through the Pallas interpreter (tier-1's parity leg);
tests/test_chip_compile.py asks the TPU compiler for the real shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
_GROUPED_PAGES = 8      # K/V pages a grid step of the chunk body, grouped heads

__all__ = ["paged_attention", "paged_attention_reference", "attention_scale",
           "paged_attention_sharded", "tiles_decode_trips"]


def attention_scale(d_head: int) -> float:
    """1/sqrt(d) computed in f32 — bit-identical to the traced
    ``1.0 / jnp.sqrt(d).astype(f32)`` the dense decode path uses (host f64
    sqrt can differ in the last ulp)."""
    import numpy as _np

    return float(_np.float32(1.0) / _np.sqrt(_np.float32(d_head)))


def paged_attention_reference(q, k_ctx, v_ctx, attn_mask, scale, sink=None):
    """The gather+dense attend, verbatim from transformer_lm_decode — the
    ``TPUMX_PALLAS=0`` path and the kernel's parity oracle.

    q: (B, T, H, D); k_ctx: (B, W*bs, Hkv, D), v_ctx: (B, W*bs, Hkv, Dv)
    gathered context, ``Hkv`` dividing ``H`` (query head h reads KV head
    ``h // (H // Hkv)``; K and V are never repeated); attn_mask: (B, T,
    W*bs) bool — the caller's, so a block mask or a window is the caller's
    too; scale: f32 scalar; sink: (H,) or, a row of queries each, (T, H)
    f32 — a logit that takes its share of every query's softmax and adds
    no value.  Returns (B, T, H, Dv).
    Same numerics as ring_attention.local_attention: f32 scores and
    accumulation, masked slots at exactly 0 probability.
    """
    B, T, H, D = q.shape
    Hkv, Dv = k_ctx.shape[2], v_ctx.shape[3]
    if Hkv != H:
        # the G query heads of a KV head ride the query axis: (B, G*T,
        # Hkv, D) against the unrepeated context
        G = H // Hkv
        q = q.reshape(B, T, Hkv, G, D).transpose(0, 3, 1, 2, 4) \
            .reshape(B, G * T, Hkv, D)
        if sink is not None:
            sink = _sink_rows(sink, Hkv, G, T)
        o = paged_attention_reference(q, k_ctx, v_ctx,
                                      jnp.tile(attn_mask, (1, G, 1)), scale,
                                      sink)
        return o.reshape(B, G, T, Hkv, Dv).transpose(0, 2, 3, 1, 4) \
            .reshape(B, T, H, Dv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_ctx,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(attn_mask[:, None], s, _NEG)
    if sink is not None:
        # one more column of scores, dropped again behind the softmax
        col = jnp.broadcast_to(
            jnp.atleast_2d(sink.astype(jnp.float32)).T[None, :, :, None],
            s.shape[:3] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, col], axis=-1),
                           axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_ctx.dtype), v_ctx,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o


def _sink_rows(sink, n_kv: int, groups: int, t: int):
    """A sink ``(H,)`` a query head as ``(groups * t, n_kv)``: the grouped
    query rows' (row ``g * t + i`` is query head ``h * groups + g`` of KV
    head ``h``, whatever ``i``)."""
    return jnp.repeat(sink.astype(jnp.float32).reshape(n_kv, groups).T, t,
                      axis=0)


def _paged_kernel(tables_ref, maxpos_ref, tilemax_ref, layer_ref, q_ref,
                  pos_ref, *refs, bs: int, bt: int, n_heads: int, d_head: int,
                  scale: float, quantized: bool, pages: int):
    # grid = (B, T tiles, W / pages); the page groups are the INNERMOST
    # (sequential) dim, so the VMEM scratch (acc/m/l) carries the
    # online-softmax state across the row's cache blocks while only
    # ``pages`` (bs, H*D) K/V tiles are resident: the pool is an operand
    # ``pages`` times over, each with its own index map, so one grid step
    # fetches that many pages of the table wherever they lie (a grid step
    # costs about 0.35 us whatever it does: a page a step is all overhead
    # for a few query rows).
    # Heads are FOLDED into the lane dim (docs/pallas.md "block-layout
    # rule"): every block's last two dims are whole or (8k, 128k), which
    # is what Mosaic accepts; a block that cut one head out of an
    # (..., H, D) array was refused.  The static per-head loop slices
    # lanes [h*D, (h+1)*D) of the resident tiles.
    k_refs, v_refs, refs = refs[:pages], refs[pages:2 * pages], \
        refs[2 * pages:]
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    w = pl.program_id(2)
    nw = pl.num_programs(2)
    n = pages * bs

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # dead groups: null sentinel (table entry 0 — the allocator never
    # hands out physical block 0; a table is filled from its front, so a
    # group whose first page is null is null throughout) or wholly past
    # the last position any query of this tile may read.  The index maps
    # already redirected their DMAs to block 0.  Inside a live group a
    # page past a query's position (a null one among them) is masked.
    live = (tables_ref[b, w * pages] != 0) \
        & (w * n <= jnp.minimum(maxpos_ref[b],
                                tilemax_ref[b, pl.program_id(1)]))

    @pl.when(live)
    def _step():
        # what the products take: a bfloat16 pool's pages as they are
        # (the MXU multiplies float32 operands in one bfloat16 pass
        # anyway, PERF.md PR 25: converting K and V first only costs
        # vector work), everything else in float32
        mxu = k_refs[0].dtype if k_refs[0].dtype == jnp.bfloat16 \
            else jnp.float32
        q = (q_ref[0].astype(jnp.float32) * scale).astype(mxu)  # (bt, H*D)
        join = lambda rs: rs[0][0] if pages == 1 else jnp.concatenate(  # noqa: E731
            [r[0] for r in rs], axis=0)
        k = join(k_refs).astype(mxu)                           # (n, H*D)
        v = join(v_refs).astype(mxu)
        ctx = w * n + jax.lax.broadcasted_iota(jnp.int32, (bt, n), 1)
        mask = ctx <= pos_ref[0]            # cache pos <= query pos (bt, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (bt, n_heads), 1)
        m_all = m_ref[...]                                     # (bt, H)
        l_all = l_ref[...]
        for h in range(n_heads):
            sl = slice(h * d_head, (h + 1) * d_head)
            kh, vh = k[:, sl], v[:, sl]
            if quantized:
                # int8 pool (docs/quantization.md): the per-(block, head)
                # scales ride the same index-mapped VMEM path as the
                # blocks — dequantize is one multiply per tile
                kh = kh * ks_ref[0, :, h:h + 1]
                vh = vh * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(q[:, sl], kh, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, _NEG)
            m_old = m_all[:, h:h + 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_new = alpha * l_all[:, h:h + 1] + jnp.sum(p, axis=1,
                                                        keepdims=True)
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
                p.astype(mxu), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_all = jnp.where(head == h, m_new, m_all)
            l_all = jnp.where(head == h, l_new, l_all)
        m_ref[...] = m_all
        l_ref[...] = l_all

    @pl.when(w == nw - 1)
    def _emit():
        # fully-skipped rows (inactive slots, all-null tables) emit 0 —
        # the dense path's output there is garbage either way
        l_all = jnp.maximum(l_ref[...], 1e-30)
        for h in range(n_heads):
            sl = slice(h * d_head, (h + 1) * d_head)
            o_ref[0, :, sl] = (acc_ref[:, sl] / l_all[:, h:h + 1]
                               ).astype(o_ref.dtype)


def _start_copies(copies):
    """Start the K and V copy of every page of a group that is fetched."""
    for fetched, kc, vc in copies:
        @pl.when(fetched)
        def _():
            kc.start()
            vc.start()


def _wait_copies(copies):
    for fetched, kc, vc in copies:
        @pl.when(fetched)
        def _():
            kc.wait()
            vc.wait()


def _live_page_fetch(tables_ref, maxpos_ref, layer, k_hbm, v_hbm, kbuf, vbuf,
                     sem, bs: int, pages: int):
    """What the bodies that fetch their own pages share: ``(groups,
    page_copies, start, wait)`` over the row's LIVE pages, ``pages`` of
    them a group, into one half of the ``(2, pages, bs, H*D)`` buffers."""
    from jax.experimental.pallas import tpu as pltpu

    W = tables_ref.shape[1]

    def live_pages(row):
        # max_pos == -1 (inactive row): no page at all
        return jnp.minimum(jax.lax.div(maxpos_ref[row] + bs, bs), W)

    def groups(row):
        return jax.lax.div(live_pages(row) + pages - 1, pages)

    def page_copies(row, g, half, enabled=True):
        """(fetched?, K copy, V copy) of each page of group ``g`` of
        ``row``: the same scalars decide the start and the wait.  Null
        table entries and pages past the row's last position are neither
        fetched nor waited for."""
        live = live_pages(row)
        out = []
        for j in range(pages):
            idx = g * pages + j
            blk = tables_ref[row, jnp.minimum(idx, W - 1)]
            out.append(((idx < live) & (blk != 0) & enabled,
                        pltpu.make_async_copy(k_hbm.at[layer, blk],
                                              kbuf.at[half, j],
                                              sem.at[0, half]),
                        pltpu.make_async_copy(v_hbm.at[layer, blk],
                                              vbuf.at[half, j],
                                              sem.at[1, half])))
        return out

    return groups, page_copies, _start_copies, _wait_copies


def _decode_kernel(tables_ref, maxpos_ref, layer_ref, q_ref, sel_ref,
                   selt_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref,
                   den_ref, acc_ref, trip_ref, *, bs: int, pages: int,
                   scale: float):
    # grid = (B,), one single-query row a step.  The pools are whole HBM
    # arrays; this body fetches the row's live pages itself, ``pages`` of
    # them a trip, into one half of the (2, pages, bs, H*D) buffers while
    # the other half is being computed on.  ``trip_ref`` counts trips over
    # the whole call: a trip's half is its parity, so the prefetch can run
    # on across the end of a row into the next row's first group.
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n = pages * bs
    hp = sel_ref.shape[1]
    layer = layer_ref[0]

    groups, page_copies, start, wait = _live_page_fetch(
        tables_ref, maxpos_ref, layer, k_hbm, v_hbm, kbuf, vbuf, sem, bs,
        pages)

    @pl.when(b == 0)
    def _first_row():
        trip_ref[0] = 0
        # a page that is not fetched leaves its slot as it was: keep what
        # a zero probability multiplies finite (K needs none of this: a
        # masked score is replaced, not multiplied)
        vbuf[...] = jnp.zeros_like(vbuf)

    # the row before prefetched this row's first group at its last trip;
    # a row that ran no trip (inactive) prefetched nothing
    @pl.when((b == 0) | (groups(jnp.maximum(b - 1, 0)) == 0))
    def _own_first_group():
        start(page_copies(b, 0, jax.lax.rem(trip_ref[0], 2)))

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    den_ref[...] = jnp.zeros_like(den_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32) * scale                   # (1, H*D)
    max_pos = maxpos_ref[b]
    n_groups = groups(b)
    row_in_page = jax.lax.broadcasted_iota(jnp.int32, (bs, hp), 0)
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    def trip(g, _):
        t = trip_ref[0]
        half = jax.lax.rem(t, 2)
        more = g + 1 < n_groups
        start(page_copies(jnp.where(more, b, jnp.minimum(b + 1, n_rows - 1)),
                          jnp.where(more, g + 1, 0), 1 - half,
                          more | (b + 1 < n_rows)))
        mine = page_copies(b, g, half)
        wait(mine)
        k = kbuf[half].astype(jnp.float32).reshape(n, -1)      # (n, H*D)
        v = vbuf[half].astype(jnp.float32).reshape(n, -1)
        # every head's scores at once: the 0/1 selector sums head h's
        # lanes of k * q into column h
        s = dot(k * q, sel_ref[...])                           # (n, hp)
        # cache pos <= query pos, page by page; a page that was not
        # fetched holds another page's values: masked whole
        ok = jnp.concatenate(
            [row_in_page <= jnp.where(fetched, max_pos, -1)
             - (g * pages + j) * bs
             for j, (fetched, _, _) in enumerate(mine)], axis=0)
        s = jnp.where(ok, s, _NEG)
        m_old = m_ref[...]                                     # (1, hp)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_old - m_new)
        m_ref[...] = m_new
        # probabilities (and alpha, in 8 rows more of the same product)
        # back from one column a head to the head's lanes
        wide = dot(jnp.concatenate(
            [p, jnp.broadcast_to(alpha, (8, hp))], axis=0), selt_ref[...])
        p_wide, alpha_wide = wide[:n], wide[n:]

        def rows_summed(x):     # (n, H*D) -> (8, H*D): whole vregs added,
            out = x[0:8]        # the last 8 -> 1 waits for the row's end
            for r in range(8, n, 8):
                out = out + x[r:r + 8]
            return out

        # numerator and normalizer from the SAME expanded values: what
        # the product rounded of p and alpha cancels in their ratio
        acc_ref[...] = acc_ref[...] * alpha_wide + rows_summed(p_wide * v)
        den_ref[...] = den_ref[...] * alpha_wide + rows_summed(p_wide)
        trip_ref[0] = t + 1

    jax.lax.fori_loop(0, n_groups, trip, None)
    # rows that ran no trip (inactive slots) and rows whose pages were all
    # null emit 0
    den = jnp.sum(den_ref[...], axis=0, keepdims=True)
    o_ref[0] = (jnp.sum(acc_ref[...], axis=0, keepdims=True)
                / jnp.maximum(den, 1e-30)).astype(o_ref.dtype)


def _rows_kernel(tables_ref, maxpos_ref, layer_ref, q_ref, pos_ref, k_hbm,
                 v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
                 trip_ref, *, bs: int, pages: int, n_heads: int, d_head: int,
                 scale: float):
    # grid = (B,), one row's few query rows a step (a block-diffusion
    # step: the block's positions x the query heads of a KV head).  As the
    # decode body: the pools stay in HBM and the row's LIVE page groups
    # are fetched by this body, double-buffered, the next row's first
    # group behind this row's last — a dead page costs nothing, where the
    # chunk body pays a grid step for it (0.84 us with 16 page operands,
    # PERF.md PR 26).  The arithmetic is the chunk body's: per KV head an
    # online-softmax update of its (rows, D) slice over the group's
    # ``pages * bs`` cache positions, masked by the position each query
    # row was given.
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n = pages * bs
    rows = q_ref.shape[1]
    groups, page_copies, start, wait = _live_page_fetch(
        tables_ref, maxpos_ref, layer_ref[0], k_hbm, v_hbm, kbuf, vbuf, sem,
        bs, pages)

    @pl.when(b == 0)
    def _first_row():
        trip_ref[0] = 0
        # a page that is not fetched leaves its slot as it was: keep what
        # a zero probability multiplies finite
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when((b == 0) | (groups(jnp.maximum(b - 1, 0)) == 0))
    def _own_first_group():
        start(page_copies(b, 0, jax.lax.rem(trip_ref[0], 2)))

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    mxu = kbuf.dtype if kbuf.dtype == jnp.bfloat16 else jnp.float32
    q = (q_ref[0].astype(jnp.float32) * scale).astype(mxu)     # (rows, H*D)
    pos = pos_ref[0]                                           # (rows, 1)
    n_groups = groups(b)
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, n_heads), 1)

    def trip(g, _):
        t = trip_ref[0]
        half = jax.lax.rem(t, 2)
        more = g + 1 < n_groups
        start(page_copies(jnp.where(more, b, jnp.minimum(b + 1, n_rows - 1)),
                          jnp.where(more, g + 1, 0), 1 - half,
                          more | (b + 1 < n_rows)))
        wait(page_copies(b, g, half))
        k = kbuf[half].reshape(n, -1).astype(mxu)              # (n, H*D)
        v = vbuf[half].reshape(n, -1).astype(mxu)
        # a page that was not fetched lies past the row's last position,
        # so past every query's: the position mask covers it
        mask = g * n + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1) \
            <= pos
        m_all, l_all = m_ref[...], l_ref[...]                  # (rows, H)
        for h in range(n_heads):
            sl = slice(h * d_head, (h + 1) * d_head)
            s = jax.lax.dot_general(q[:, sl], k[:, sl],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, _NEG)
            m_old = m_all[:, h:h + 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_new = alpha * l_all[:, h:h + 1] + jnp.sum(p, axis=1,
                                                        keepdims=True)
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
                p.astype(mxu), v[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_all = jnp.where(head == h, m_new, m_all)
            l_all = jnp.where(head == h, l_new, l_all)
        m_ref[...] = m_all
        l_ref[...] = l_all
        trip_ref[0] = t + 1

    jax.lax.fori_loop(0, n_groups, trip, None)
    # rows that ran no trip (inactive slots) emit 0
    l_all = jnp.maximum(l_ref[...], 1e-30)
    for h in range(n_heads):
        sl = slice(h * d_head, (h + 1) * d_head)
        o_ref[0, :, sl] = (acc_ref[:, sl] / l_all[:, h:h + 1]
                           ).astype(o_ref.dtype)


def _tiles_kernel(tables_ref, first_ref, end_ref, layer_ref, q_ref, pos_ref,
                  *refs, bs: int, pages: int, part: int, n_heads: int,
                  d_key: int, d_value: int, scale: float, window: int,
                  sink: bool):
    # grid = (B * tiles,): step s is tile s % tiles of row s // tiles, up
    # to 256 grouped query rows.  As the rows body, the pools stay in HBM
    # and this body fetches its pages itself, double-buffered, the next
    # step's first group behind this step's last — but only the logical
    # pages first_ref[row, tile] .. end_ref[row, tile] - 1, which is where
    # a window pays: pages before the tile's earliest window are never
    # fetched.  The table is a ring: logical page p sits in column p % W
    # (a table as wide as the sequence is one whose ring never wraps).
    # A trip of ``pages`` page slots is computed whole (``part == pages``)
    # or, a long one, over the fewest whole runs of ``part`` slots that
    # hold its tile's pages: a row shorter than the trip pays for what it
    # has, to a part.
    from jax.experimental.pallas import tpu as pltpu

    if sink:
        sink_ref, *refs = refs
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref, trip_ref = \
        refs
    step = pl.program_id(0)
    n_steps = pl.num_programs(0)
    n_tiles = first_ref.shape[1]
    W = tables_ref.shape[1]
    n = pages * bs
    rows = q_ref.shape[1]
    layer = layer_ref[0]

    def span(s):
        """(row, first logical page, pages) of step ``s``."""
        b, t = jax.lax.div(s, n_tiles), jax.lax.rem(s, n_tiles)
        return b, first_ref[b, t], end_ref[b, t] - first_ref[b, t]

    def groups(s):
        return jax.lax.div(span(s)[2] + pages - 1, pages)

    def page_copies(s, g, half, enabled=True, slots=range(pages)):
        b, first, count = span(s)
        out = []
        for j in slots:
            idx = g * pages + j
            blk = tables_ref[b, jax.lax.rem(first + idx, W)]
            out.append(((idx < count) & (blk != 0) & enabled,
                        pltpu.make_async_copy(k_hbm.at[layer, blk],
                                              kbuf.at[half, j],
                                              sem.at[0, half]),
                        pltpu.make_async_copy(v_hbm.at[layer, blk],
                                              vbuf.at[half, j],
                                              sem.at[1, half])))
        return out

    @pl.when(step == 0)
    def _first_step():
        trip_ref[0] = 0
        # a page that is not fetched leaves its slot as it was: keep what
        # a zero probability multiplies finite
        vbuf[...] = jnp.zeros_like(vbuf)

    def held(s, g):
        """Page slots of trip ``g`` of step ``s`` that hold a page."""
        return span(s)[2] - g * pages

    def by_parts(act, s, g, half, enabled=True):
        """``act`` on the trip's copies: all of them or, a trip of
        several parts, those of the parts that hold a page — a loop over
        the whole parts (a part's slots unrolled, as a short trip's are:
        65 slots unrolled at three places made the program slow to load)
        and the slots left over."""
        if part == pages:
            return act(page_copies(s, g, half, enabled))
        live = jnp.where(enabled, held(s, g), 0)
        whole = pages // part

        def one(p, _):
            act(page_copies(s, g, half,
                            slots=[p * part + j for j in range(part)]))

        jax.lax.fori_loop(
            0, jnp.clip(jax.lax.div(live + part - 1, part), 0, whole), one,
            None)
        if pages % part:
            @pl.when(live > whole * part)
            def _():
                act(page_copies(s, g, half, slots=range(whole * part, pages)))

    @pl.when((step == 0) | (groups(jnp.maximum(step - 1, 0)) == 0))
    def _own_first_group():
        by_parts(_start_copies, step, 0, jax.lax.rem(trip_ref[0], 2))

    # the sink joins the denominator and no value: the recurrence starts
    # at m = sink, l = 1, acc = 0
    m_ref[...] = sink_ref[...] if sink else jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.ones_like(l_ref) if sink else jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    mxu = kbuf.dtype if kbuf.dtype == jnp.bfloat16 else jnp.float32
    q = (q_ref[0].astype(jnp.float32) * scale).astype(mxu)   # (rows, Hkv*dk)
    pos = pos_ref[0]                                           # (rows, 1)
    _, first, _ = span(step)
    n_groups = groups(step)
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, n_heads), 1)

    def update(k, v, page):
        """The online-softmax update over the cache positions of ``k`` and
        ``v``, which start at logical page ``page``."""
        n = k.shape[0]
        # a page that was not fetched lies past the row's last position or
        # behind every window of the tile: the position mask covers it
        ctx = page * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        mask = ctx <= pos
        if window:
            mask &= ctx > pos - window
        m_all, l_all = m_ref[...], l_ref[...]                  # (rows, Hkv)
        for h in range(n_heads):
            sk = slice(h * d_key, (h + 1) * d_key)
            sv = slice(h * d_value, (h + 1) * d_value)
            s = jax.lax.dot_general(q[:, sk], k[:, sk],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, _NEG)
            m_old = m_all[:, h:h + 1]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_new = alpha * l_all[:, h:h + 1] + jnp.sum(p, axis=1,
                                                        keepdims=True)
            acc_ref[:, sv] = acc_ref[:, sv] * alpha + jax.lax.dot_general(
                p.astype(mxu), v[:, sv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_all = jnp.where(head == h, m_new, m_all)
            l_all = jnp.where(head == h, l_new, l_all)
        m_ref[...] = m_all
        l_ref[...] = l_all

    def trip(g, _):
        t = trip_ref[0]
        half = jax.lax.rem(t, 2)
        more = g + 1 < n_groups
        by_parts(_start_copies,
                 jnp.where(more, step, jnp.minimum(step + 1, n_steps - 1)),
                 jnp.where(more, g + 1, 0), 1 - half,
                 more | (step + 1 < n_steps))
        by_parts(_wait_copies, step, g, half)
        if part == pages:
            update(kbuf[half].reshape(n, -1).astype(mxu),      # (n, Hkv*dk)
                   vbuf[half].reshape(n, -1).astype(mxu),      # (n, Hkv*dv)
                   first + g * pages)
        else:
            # ONE update over the fewest whole parts that hold the tile's
            # pages (the whole trip once less than a part would be left
            # over): an update costs ~1 us whatever it holds, so several
            # short ones cost more than they save (PERF.md PR 48)
            live, lo = held(step, g), 0
            for size in (*range(part, pages - part + 1, part), pages):
                @pl.when((live > lo) if size == pages
                         else (live > lo) & (live <= size))
                def _(size=size):
                    update(kbuf[half, :size].reshape(size * bs, -1)
                           .astype(mxu),
                           vbuf[half, :size].reshape(size * bs, -1)
                           .astype(mxu), first + g * pages)
                lo = size
        trip_ref[0] = t + 1

    jax.lax.fori_loop(0, n_groups, trip, None)
    # tiles that ran no trip (inactive slots, padded rows) emit 0
    l_all = jnp.maximum(l_ref[...], 1e-30)
    for h in range(n_heads):
        sv = slice(h * d_value, (h + 1) * d_value)
        o_ref[0, :, sv] = (acc_ref[:, sv] / l_all[:, h:h + 1]
                           ).astype(o_ref.dtype)


_TILE_ROWS = 256        # grouped query rows a step of the tiles body
# cache positions a trip of it WITHOUT a window: a trip costs ~1.5 us beside
# its bytes, and the full kind's decode call read 5.00 ms at 512 against
# 6.08 at 256, a 512-token chunk's 2.04 against 3.18 (PERF.md PR 32).  A
# call WITH a window sizes its trip from the pages its tile can reach
# (_tile_pages), and a trip of two such runs or more is computed over the
# fewest of them that hold its tile's pages (_trip_part)
_TILE_POSITIONS = 512
# fast memory a tiles call may fill: the 16 MB the chip's compiler gives a
# kernel, less 1 MB for what it keeps there itself.  Reckoned as
# _tile_pages reckons, a 256-row chunk tile of 2,560 bfloat16 lanes a
# position (K and V) was taken with 5.2 MB of double-buffered K and V
# beside it (14.7 MB in all) and refused with 6.9 (16.8: "ran out of
# memory in memory space vmem", PERF.md PR 46); an 8-row decode tile was
# taken with 8.5 MB (8.8 in all, PERF.md PR 48)
_TILE_VMEM_BYTES = 15 << 20


def _tile_pages(rows: int, span: int, bs: int, w: int, window: int,
                page_bytes: int) -> int:
    """Pages a trip of the tiles body fetches.  ``span`` consecutive query
    positions with a ``window`` read at most ``reach = ceil((span + window
    - 1) / bs) + 1`` pages (the window's positions, and the page both ends
    may straddle), so such a call takes them in ONE trip wherever the
    trip fits fast memory beside the tile that runs it: K and V pages of
    ``page_bytes`` a pair, ``reach`` pairs double-buffered, and the tile's
    own ``rows`` query rows — a row's queries and output (float32,
    double-buffered), its accumulator and running maximum and sum, at most
    six cached positions' K and V together, and a trip's scores three
    times over (the scores, their mask, the probabilities) — stay under
    ``_TILE_VMEM_BYTES``.  A call without a window, or whose reach does
    not fit, takes ``_TILE_POSITIONS`` positions a trip.  Never more pages
    than the table is wide."""
    pages = _TILE_POSITIONS // bs
    if window:
        reach = -(-(span + window - 1) // bs) + 1
        tile = rows * (6 * page_bytes // bs + 3 * 4 * reach * bs)
        if 2 * reach * page_bytes + tile <= _TILE_VMEM_BYTES:
            pages = reach
    return max(1, min(pages, w))


def _trip_part(pages: int, bs: int) -> int:
    """Pages a part of a trip: the trip itself (computed whole), or
    ``_TILE_POSITIONS`` positions of a trip that holds two such runs or
    more, whose ONE update reads the fewest whole parts that hold its
    tile's pages."""
    part = _TILE_POSITIONS // bs
    return part if pages >= 2 * part else pages


def _tiles_geometry(rows: int, groups: int, bs: int, w: int, window: int,
                    page_bytes: int):
    """``(query rows a tile, pages a trip)`` of a tiles call over ``rows``
    grouped query rows, ``rows // groups`` consecutive positions a group.
    A tile that is whole inside a group spans its own rows' positions; one
    that crosses groups may span the chunk's."""
    bt = min(_TILE_ROWS, -(-rows // 8) * 8)
    t = rows // groups
    return bt, _tile_pages(bt, bt if t % bt == 0 else t, bs, w, window,
                           page_bytes)


def _tile_spans(positions, max_pos, bt: int, bs: int, window: int):
    """``(first, end)`` logical pages, ``(B, tiles)`` each, that every tile
    of ``bt`` of the grouped query rows at ``positions (B, R)`` reads (``R``
    whole tiles; a padded row sits at -1): up to its latest query's own
    (and the row's last valid position), from the first its earliest
    query's window reaches."""
    B, R = positions.shape
    tiled = positions.reshape(B, R // bt, bt)
    hi = jnp.minimum(jnp.max(tiled, axis=2), max_pos[:, None])
    end = jnp.where(hi >= 0, hi // bs + 1, 0)
    lo = jnp.min(jnp.where(tiled >= 0, tiled, jnp.iinfo(jnp.int32).max),
                 axis=2)
    first = jnp.maximum(lo - (window - 1), 0) // bs if window \
        else jnp.zeros_like(end)
    return jnp.minimum(first, end), end


def _page_bytes(k_pool, v_pool) -> int:
    """A K page and a V page together."""
    return (k_pool.shape[3] + v_pool.shape[3]) * k_pool.shape[2] \
        * jnp.dtype(k_pool.dtype).itemsize


def tiles_decode_trips(positions, max_pos, k_pool, v_pool, table_width: int,
                       *, groups: int, window: int):
    """Trips of the tiles body over one DECODE call with these operands
    (``positions (B, 1)``, ``groups`` query heads a KV head): the sum over
    its rows of ``ceil(pages read / pages a trip)``, int32 — what a model
    counts as ``window_decode_trips`` (docs/observability.md), from the
    geometry :func:`_tiles_call` itself takes."""
    bs = k_pool.shape[2]
    _, pages = _tiles_geometry(groups, groups, bs, table_width, window,
                               _page_bytes(k_pool, v_pool))
    # a row's one tile holds its one position, once a query head
    first, end = _tile_spans(jnp.asarray(positions, jnp.int32),
                             jnp.asarray(max_pos, jnp.int32), 1, bs, window)
    return jnp.sum((end - first + pages - 1) // pages).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "scale", "interpret", "groups", "call", "window"))
def _tiles_call(tables, max_pos, layer, q, positions, k_pool, v_pool,
                sink=None, *, n_heads, scale, interpret, groups, call,
                window):
    """The tiles body's call.  q: (B, G*T, Hkv*dk) group-major; positions:
    (B, G*T); sink: None or (G*T, Hkv) f32.  Returns (B, G*T, Hkv*dv)."""
    from jax.experimental.pallas import tpu as pltpu

    B, R, HDk = q.shape
    bs, HDv = k_pool.shape[2], v_pool.shape[3]
    W = tables.shape[1]
    bt, pages = _tiles_geometry(R, groups, bs, W, window,
                                _page_bytes(k_pool, v_pool))
    r_pad = -(-R // bt) * bt
    if r_pad != R:
        # padded query rows sit at position -1: they read nothing
        q = jnp.pad(q, ((0, 0), (0, r_pad - R), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, r_pad - R)),
                            constant_values=-1)
        if sink is not None:
            sink = jnp.pad(sink, ((0, r_pad - R), (0, 0)))
    n_tiles = r_pad // bt
    first, end = _tile_spans(positions, max_pos, bt, bs, window)
    tile = lambda w: pl.BlockSpec(  # noqa: E731
        (1, bt, w), lambda s, *_: (s // n_tiles, s % n_tiles, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [tile(HDk), tile(1)]
    args = [tables, first, end, layer, q, positions[:, :, None]]
    if sink is not None:
        in_specs.append(pl.BlockSpec((bt, n_heads),
                                     lambda s, *_: (s % n_tiles, 0)))
        args.append(sink)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B * n_tiles,),
        in_specs=in_specs + [hbm, hbm],
        out_specs=tile(HDv),
        scratch_shapes=[pltpu.VMEM((2, pages, bs, HDk), k_pool.dtype),
                        pltpu.VMEM((2, pages, bs, HDv), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((bt, n_heads), jnp.float32),     # m
                        pltpu.VMEM((bt, n_heads), jnp.float32),     # l
                        pltpu.VMEM((bt, HDv), jnp.float32),         # acc
                        pltpu.SMEM((1,), jnp.int32)],               # trips
    )
    kernel = functools.partial(
        _tiles_kernel, bs=bs, pages=pages, part=_trip_part(pages, bs),
        n_heads=n_heads, d_key=HDk // n_heads, d_value=HDv // n_heads,
        scale=scale, window=window, sink=sink is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, r_pad, HDv), q.dtype),
        # steps run in order: the double buffer's parity and the prefetch
        # of the next step's first group carry from one step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=_call_name(R // groups, W, call),
    )(*args, k_pool, v_pool)
    return out[:, :R]


def _query_tile(t: int, hd: int) -> int:
    """Query rows per grid step: the whole chunk while its f32 tile stays
    under ~1 MB of VMEM (q, out and the accumulator each hold one, q/out
    double-buffered), else the largest power-of-two tile that does —
    always a multiple of 8, so a tiled block is legal for Mosaic."""
    from .pallas_kernels import _row_cap

    cap = min(256, _row_cap(hd))
    return t if t <= cap else cap


def _decode_pages(t: int, w: int, bs: int, hd: int, pool_dtype,
                  quantized: bool) -> int:
    """Pages a trip of the decode body fetches, or 0 where the call takes
    the chunk body: more than one query a row, an int8 pool (its scales
    ride the chunk body's index maps), or a page that is not whole tiles
    of the pool's dtype — ``bs`` a multiple of the sublane tile, ``H*D``
    of 128 lanes (the body reads ``pages`` of them as one ``(pages * bs,
    H*D)`` tile; a head slice of 3 x 64 lanes under an mp mesh is
    refused).  Otherwise about 128 cache positions a trip — one MXU pass
    of rows — while K and V, double-buffered, stay under 4 MB of VMEM,
    and never more pages than the table is wide."""
    item = jnp.dtype(pool_dtype).itemsize
    if t != 1 or quantized or bs % (32 // item) or hd % 128:
        return 0
    fit = (4 << 20) // (4 * bs * hd * item)
    return max(1, min(128 // bs, fit, w))


def _rows_pages(t: int, groups: int, w: int, bs: int, hd: int, pool_dtype,
                quantized: bool) -> int:
    """Pages a trip of the rows body fetches, or 0 where the call takes
    the chunk body.  The rows body is for grouped heads with a few query
    rows a batch row (a block-diffusion step): up to 256 of them, whole
    sublane tiles, over a float pool whose pages are whole tiles (as the
    decode body asks).  About 256 cache positions a trip: a trip costs
    about 3 us whatever it holds, 0.8 ms a call at 16 pages against 1.0 at
    8 (PERF.md PR 26)."""
    item = jnp.dtype(pool_dtype).itemsize
    if groups == 1 or t > 256 or t % 8 or quantized \
            or bs % (32 // item) or hd % 128:
        return 0
    return max(1, min(256 // bs, w))


def _call_name(t: int, w: int, call=None) -> str:
    """The kernel's name in a device trace: decode and prefill apart, one
    name per block-table width (and per prefill chunk length); a model
    may name its calls itself (``call="block"``: the block-diffusion
    step's).  It starts with the wrapper's name, which trace readers
    search for, and ends in a letter: a reader that groups operations
    strips a trailing number."""
    if call is None:
        call = "decode" if t == 1 else "prefill"
    return f"_paged_call_w{w}_decode" if call == "decode" \
        else f"_paged_call_w{w}_t{t}_{call}"


def _head_selector(n_heads: int, d_head: int):
    """``(H*D, hp)`` 0/1: column h selects head h's lanes; ``hp`` is H
    rounded up to whole 128-lane tiles."""
    import numpy as _np

    hp = -(-n_heads // 128) * 128
    lanes = _np.arange(n_heads * d_head)
    sel = _np.zeros((lanes.size, hp), _np.float32)
    sel[lanes, lanes // d_head] = 1
    return sel


def _decode_call(tables, max_pos, layer, q, k_pool, v_pool, *, n_heads,
                 scale, pages, interpret):
    from jax.experimental.pallas import tpu as pltpu

    B, _, HD = q.shape
    bs = k_pool.shape[2]
    W = tables.shape[1]
    sel = _head_selector(n_heads, HD // n_heads)
    hp = sel.shape[1]
    row = pl.BlockSpec((1, 1, HD), lambda b, *_: (b, 0, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda b, *_: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[row, whole(sel), whole(sel.T), hbm, hbm],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((2, pages, bs, HD), k_pool.dtype),
                        pltpu.VMEM((2, pages, bs, HD), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((1, hp), jnp.float32),     # m
                        pltpu.VMEM((8, HD), jnp.float32),     # normalizer
                        pltpu.VMEM((8, HD), jnp.float32),     # numerator
                        pltpu.SMEM((1,), jnp.int32)],         # trips
    )
    kernel = functools.partial(_decode_kernel, bs=bs, pages=pages,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, HD), q.dtype),
        # rows run in order: the double buffer's parity and the prefetch
        # of the next row's first group carry from one row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=_call_name(1, W),
    )(tables, max_pos, layer, q, jnp.asarray(sel), jnp.asarray(sel.T),
      k_pool, v_pool)


def _rows_call(tables, max_pos, layer, q, positions, k_pool, v_pool, *,
               n_heads, scale, pages, interpret, name):
    from jax.experimental.pallas import tpu as pltpu

    B, R, HD = q.shape
    bs = k_pool.shape[2]
    row = pl.BlockSpec((1, R, HD), lambda b, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[row, pl.BlockSpec((1, R, 1), lambda b, *_: (b, 0, 0)),
                  hbm, hbm],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((2, pages, bs, HD), k_pool.dtype),
                        pltpu.VMEM((2, pages, bs, HD), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((R, n_heads), jnp.float32),     # m
                        pltpu.VMEM((R, n_heads), jnp.float32),     # l
                        pltpu.VMEM((R, HD), jnp.float32),          # acc
                        pltpu.SMEM((1,), jnp.int32)],              # trips
    )
    kernel = functools.partial(_rows_kernel, bs=bs, pages=pages,
                               n_heads=n_heads, d_head=HD // n_heads,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, HD), q.dtype),
        # rows run in order: the double buffer's parity and the prefetch
        # of the next row's first group carry from one row to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(tables, max_pos, layer, q, positions[:, :, None], k_pool, v_pool)


@functools.partial(jax.jit,
                   static_argnames=("n_heads", "scale", "interpret", "groups",
                                    "call"))
def _paged_call(tables, max_pos, layer, q, positions, k_pool, v_pool,
                k_scale=None, v_scale=None, *, n_heads, scale, interpret,
                groups=1, call=None):
    """q: (B, T, H*D); positions: (B, T); pools: the WHOLE layered pool
    (n_layers, num_blocks, bs, H*D), read at ``layer`` — a (1,) int32
    OPERAND (the last scalar-prefetch one), not a static: the model's 36
    calls then share one trace and one lowered kernel (as statics they
    were 36 kernels to lower for each program — 7 minutes of every
    warm-up at GPT-2-large, PERF.md PR 25); scales (int8 pool only): ONE
    layer's (num_blocks, H).  ``groups`` > 1: the query rows are
    ``groups`` query heads' rows of one KV head each, group-major
    (:func:`paged_attention` regroups them), and ``n_heads`` counts KV
    heads; only the call's name needs to know.  Returns (B, T, H*D)."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, HD = q.shape
    bs = k_pool.shape[2]
    W = tables.shape[1]
    quantized = k_scale is not None
    pages = _decode_pages(T, W, bs, HD, k_pool.dtype, quantized)
    if pages:
        return _decode_call(tables, max_pos, layer, q, k_pool, v_pool,
                            n_heads=n_heads, scale=scale, pages=pages,
                            interpret=interpret)
    pages = _rows_pages(T, groups, W, bs, HD, k_pool.dtype, quantized)
    if pages:
        return _rows_call(tables, max_pos, layer, q, positions, k_pool,
                          v_pool, n_heads=n_heads, scale=scale, pages=pages,
                          interpret=interpret,
                          name=_call_name(T // groups, W, call))
    bt = _query_tile(T, HD)
    t_pad = -(-T // bt) * bt
    if t_pad != T:
        # padded queries sit at position 0: they attend one cache slot of
        # a live block and are sliced off below
        q = jnp.pad(q, ((0, 0), (0, t_pad - T), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, t_pad - T)))

    # pages a grid step: one for the queries of one head each (a prefill
    # tile of 256 rows fills the step); for grouped heads, whose block
    # step has a few rows a KV head, 8 pages — 128 cache positions, one
    # MXU pass of columns — as separate operands of the same pool
    pages = 1 if quantized or groups == 1 else \
        next(p for p in (_GROUPED_PAGES, 4, 2, 1) if W % p == 0)
    # the last position any query of a tile may read: a tile of a long
    # chunk stops at its own pages, not at the chunk's last
    tile_max = jnp.max(positions.reshape(B, t_pad // bt, bt), axis=2)

    def block_index(j, b, t, w, tables_ref, maxpos_ref, tilemax_ref,
                    layer_ref):
        # dead blocks redirect to the null block: consecutive identical
        # indices skip the re-fetch, so dead grid steps cost no HBM traffic
        page = w * pages + j
        dead = page * bs > jnp.minimum(maxpos_ref[b], tilemax_ref[b, t])
        return jnp.where(dead, 0, tables_ref[b, page])

    def page_index(j):
        return lambda *a: (a[-1][0], block_index(j, *a), 0, 0)

    q_spec = pl.BlockSpec((1, bt, HD), lambda b, t, w, *_: (b, t, 0))
    # pages of the layered pool, fetched in place: the layer's slice is
    # never an operand, so XLA has nothing to copy
    kv_specs = [pl.BlockSpec((None, 1, bs, HD), page_index(j))
                for j in range(pages)]
    in_specs = [q_spec,
                # positions ride as a (bt, 1) COLUMN: a (1, T) row block of
                # a (B, T) array is not a legal TPU block for B > 1
                pl.BlockSpec((1, bt, 1), lambda b, t, w, *_: (b, t, 0)),
                *kv_specs, *kv_specs]
    args = [tables, max_pos, tile_max, layer, q, positions[:, :, None],
            *[k_pool] * pages, *[v_pool] * pages]
    if quantized:
        # (num_blocks, H) -> (num_blocks, 1, H): a (1, 1, H) block is
        # whole in its last two dims
        in_specs += [pl.BlockSpec((1, 1, n_heads),
                                  lambda *a: (block_index(0, *a), 0, 0))] * 2
        args += [k_scale[:, None, :], v_scale[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, t_pad // bt, W // pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bt, HD), jnp.float32),
                        pltpu.VMEM((bt, n_heads), jnp.float32),
                        pltpu.VMEM((bt, n_heads), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, bs=bs, bt=bt, n_heads=n_heads,
                               d_head=HD // n_heads, scale=scale,
                               quantized=quantized, pages=pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, t_pad, HD), q.dtype),
        interpret=interpret,
        name=_call_name(T // groups, W, call),
    )(*args)
    return out[:, :T]


def paged_attention(q, k_pool, v_pool, block_tables, positions, max_pos,
                    scale=None, k_scale=None, v_scale=None, *, layer: int = 0,
                    call=None, window: int = 0, sink=None,
                    tiles: bool = False):
    """Attention of ``q`` against a paged KV pool, walking the block table
    in-kernel.

    Parameters
    ----------
    q : (B, T, H, D) — this chunk's queries (T=1 decode, T=bucket prefill).
    k_pool, v_pool : (n_layers, num_blocks, block_size, Hkv*D) — the WHOLE
        layered pool (already holding this chunk's scattered K/V), heads
        folded into the minor dim (KV head h owns lanes ``[h*D, (h+1)*D)``).
        The kernel fetches its pages from it in place; a per-layer slice
        would be copied by XLA before an opaque kernel call.  ``Hkv`` may
        divide ``H`` (grouped-query attention): query head h reads KV head
        ``h // (H // Hkv)``'s lanes, and K and V are never repeated — the
        ``H // Hkv`` query heads of a KV head become ``T * H // Hkv`` query
        rows against that head's page (the chunk body).
    block_tables : (B, W) int32 — physical block of each logical block;
        0 is the null sentinel.
    positions : (B, T) int32 — global position of each query (in-range):
        the LAST cache position it may read.  A model whose mask is not
        "cache position <= query position" passes that position here (a
        block mask: the end of the query's block).
    max_pos : (B,) int32 — last VALID query position per row (−1 for
        inactive rows: every block is skipped and the output is 0).
    scale : float, optional — softmax scale; default
        :func:`attention_scale` of D.
    k_scale, v_scale : (num_blocks, H) f32, optional — ONE layer's
        per-(block, head) dequantization scales for an INT8 pool
        (docs/quantization.md): the kernel dequantizes each K/V tile in
        VMEM, with the scales index-mapped through the same
        scalar-prefetched block table as the blocks themselves.
    layer : int — which layer of the pool to read (the model's layer loop
        is unrolled, so a Python constant; it reaches the kernel as an
        operand, so every layer's call is the same kernel).
    call : str, optional — names the call in a device trace
        (``_paged_call_w<W>_t<T>_<call>``); default ``decode`` / ``prefill``
        by ``T``.
    window : int — 0, or how many cache positions back a query reads, its
        own among them (``pos - window < j <= pos``).  The table is then
        a RING: logical page ``p`` in column ``p % W``, ``W`` at least the
        pages a row's chunk and window span.
    sink : (H,) f32, optional — one learned logit a query head that joins
        the softmax's denominator and adds no value.

    tiles : bool — take the tiles body whatever the call has: a model
        whose window layers take it asks it for its full layers too
        (parallel/hybrid_moe.py), so that a table's width costs them
        nothing either.

    A call with a window, a sink or V pages narrower than K pages takes
    the tiles body (float pools only), and so does one that asks for it.

    Returns (B, T, H, dv) in q's dtype (``dv`` = D unless V pages are
    narrower), matching :func:`paged_attention_reference` at rtol 1e-5
    (f32) on valid rows.
    """
    from .pallas_kernels import _use_interpret

    B, T, H, D = q.shape
    if scale is None:
        scale = attention_scale(D)
    if k_scale is not None:
        k_scale = jnp.asarray(k_scale, jnp.float32)
        v_scale = jnp.asarray(v_scale, jnp.float32)
    positions = jnp.asarray(positions, jnp.int32)
    Hkv = k_pool.shape[3] // D
    G = H // Hkv
    if tiles or window or sink is not None \
            or k_pool.shape[3] != v_pool.shape[3]:
        assert k_scale is None, "the tiles body reads float pools"
        dv = v_pool.shape[3] // Hkv
        q = q.reshape(B, T, Hkv, G, D).transpose(0, 3, 1, 2, 4)
        out = _tiles_call(
            jnp.asarray(block_tables, jnp.int32),
            jnp.asarray(max_pos, jnp.int32), jnp.full((1,), layer, jnp.int32),
            q.reshape(B, G * T, Hkv * D), jnp.tile(positions, (1, G)),
            k_pool, v_pool,
            None if sink is None else _sink_rows(sink, Hkv, G, T),
            n_heads=Hkv, scale=float(scale), interpret=_use_interpret(),
            groups=G, call=call, window=int(window))
        return out.reshape(B, G, T, Hkv, dv).transpose(0, 2, 3, 1, 4) \
            .reshape(B, T, H, dv)
    if G > 1:
        # (B, T, Hkv, G, D) -> (B, G*T, Hkv*D): group-major query rows, as
        # wide as a K/V page; positions repeat per group
        q = q.reshape(B, T, Hkv, G, D).transpose(0, 3, 1, 2, 4)
        positions = jnp.tile(positions, (1, G))
    out = _paged_call(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(max_pos, jnp.int32), jnp.full((1,), layer, jnp.int32),
        q.reshape(B, G * T, Hkv * D), positions, k_pool,
        v_pool, k_scale, v_scale, n_heads=Hkv, scale=float(scale),
        interpret=_use_interpret(), groups=G, call=call)
    if G > 1:
        return out.reshape(B, G, T, Hkv, D).transpose(0, 2, 3, 1, 4) \
            .reshape(B, T, H, D)
    return out.reshape(B, T, H, D)


def paged_attention_sharded(q, k_pool, v_pool, block_tables, positions,
                            max_pos, mesh, axis: str = "mp", scale=None,
                            k_scale=None, v_scale=None, *, layer: int = 0):
    """:func:`paged_attention` partitioned PER HEAD over a model-parallel
    mesh axis (docs/sharding.md, docs/generation.md).

    An opaque ``pallas_call`` cannot be partitioned by GSPMD.  But every
    head is independent — so a ``shard_map`` over the head dimension runs
    the SAME kernel on each mp rank's head slice (Q and the output on
    their head dim, the folded layered K/V pools on their ``H*D`` minor
    dim, which splits on head boundaries; block tables / positions
    replicated — they are head-invariant).  Per-head numerics are
    bit-identical to the unsharded kernel.

    Requires ``H % mesh.shape[axis] == 0`` (the caller gates kernel choice
    on this at service construction).  Works inside an outer GSPMD ``jit``:
    the surrounding column-parallel QKV projection already produces
    head-sharded activations, so no resharding is inserted at the boundary.
    """
    from jax.sharding import PartitionSpec as P

    from ..base import MXNetError

    H = q.shape[2]
    n = int(mesh.shape[axis])
    if H % n:
        raise MXNetError(
            f"paged_attention_sharded: {H} heads not divisible by mesh "
            f"axis {axis!r} of size {n}")
    if scale is None:
        scale = attention_scale(q.shape[3])
    qspec = P(None, None, axis, None)   # heads at dim 2 of q and the output
    pspec = P(None, None, None, axis)   # folded heads: the pools' minor dim
    args = [q, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(max_pos, jnp.int32)]
    in_specs = [qspec, pspec, pspec, P(), P(), P()]
    if k_scale is not None:
        # int8 pool: the per-(block, head) scales shard on their head dim
        # alongside the pools — each rank dequantizes its own head slice
        args += [jnp.asarray(k_scale, jnp.float32),
                 jnp.asarray(v_scale, jnp.float32)]
        in_specs += [P(None, axis), P(None, axis)]

    def local(q, k, v, t, p, m, ks=None, vs=None):
        return paged_attention(q, k, v, t, p, m, scale=scale, k_scale=ks,
                               v_scale=vs, layer=layer)

    # pallas_call cannot declare varying-mesh-axes metadata
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=qspec, check_vma=False)(*args)

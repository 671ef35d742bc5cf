"""Latent attention (MLA) over a paged LATENT pool, as a Pallas TPU kernel.

A latent-attention layer caches, for every token, ONE vector shared by all
its heads: the normed latent ``c_kv`` (``kv_lora_rank`` wide) and the
rotated shared key ``k_rope`` side by side — a pool ``(n_layers,
num_blocks, block_size, c + r)``.  In the ABSORBED form the heads' key and
value projections are folded into the query and the output, so every head
attends to the SAME cached vector::

    score[h, j] = (ql[h] . c_kv[j] + qr[h] . k_rope[j]) * scale
    ol[h]       = sum_j softmax_j(score[h, :]) c_kv[j]

with ``ql[h] = q_nope[h] Wuk[h]^T`` and the layer's output ``ol[h] Wuv[h]``
(``parallel/latent_moe.py``).  The cached vector is key AND value: a page
is read once and used twice — the whole width against ``[ql | qr]``, its
first ``c`` lanes against the probabilities.

One body serves decode and prefill.  Its unit is a TILE of query rows —
``tt`` tokens x all ``H`` heads, up to 1,024 rows: a decode row's one token
(128 heads: one MXU pass of rows), or ``tt`` consecutive tokens of a
prefill chunk — against the pages of ONE batch row's table.  As
``ops/paged_attention.py``'s rows body: the pool stays in HBM
(``memory_space=ANY``) and is handed over WHOLE with the layer's index an
operand (a ``pool[i]`` operand would be copied by XLA; as a static it
would be a kernel a layer); the body fetches the tile's LIVE page groups
itself, double-buffered, the next tile's first group behind this tile's
last, so a dead page costs nothing and the table's width bucket costs
nothing; the online-softmax m/l recurrence runs in float32.  The mask is
"cache position <= the position given for the query"; queries past the
row's ``max_pos`` (a chunk's padding, an inactive slot) read nothing and
give 0.

At 128 heads the decode tile does ``128 x (576 + 512) x 2`` operations
over a cached token's 1,152 bytes, 242 a byte, which is the v5e's ridge
(197 T / 819 G = 240): bound by the pool's bytes and by the MXU at once.

The path without Pallas (and the kernel's oracle) is
:func:`latent_attention_reference`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
# Query rows a tile (tokens x heads) and cache positions a trip fetches, by
# the chip (PERF.md PR 30; the five calls of a 512-token prefill chunk at
# 1,024-1,536 of context and of a 256-row decode step at 1.5 k): 256 rows
# and 256 positions 13.5 and 11.8 ms; 512 rows 9.8; 512 positions 9.4 and
# 10.7; 1,024 rows 8.2; 1,024 positions no better (10.6 and 10.8).  A page
# group is the MXU's stationary operand, so the more query rows stream past
# it the less its loading weighs; a trip's last positions are half masked.
_TILE_ROWS = 1024
_TRIP_POSITIONS = 512

__all__ = ["latent_attention", "latent_attention_reference"]


def latent_attention_reference(q, ctx, attn_mask, v_width: int, scale):
    """The gather+dense form: ``q`` (B, T, H, Dq) absorbed queries, ``ctx``
    (B, N, Dq) the rows' gathered latent context, ``attn_mask`` (B, T, N)
    bool.  Float32 scores and accumulation, masked slots at exactly 0
    probability.  Returns (B, T, H, v_width) float32."""
    s = jnp.einsum("bthd,bnd->bhtn", q.astype(ctx.dtype), ctx,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(attn_mask[:, None], s, _NEG)
    p = jnp.where(attn_mask[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("bhtn,bnd->bthd", p.astype(ctx.dtype),
                      ctx[..., :v_width], preferred_element_type=jnp.float32)


def _latent_kernel(tables_ref, row_ref, tilemax_ref, layer_ref, q_ref,
                   pos_ref, pool_hbm, o_ref, kbuf, sem, m_ref, l_ref,
                   acc_ref, trip_ref, *, bs: int, pages: int, v_width: int):
    # grid = (tiles,), a tile of query rows a step, in order: ``trip_ref``
    # counts trips over the whole call, a trip's half of the (2, pages,
    # bs, width) buffer is its parity, and the prefetch runs on across the
    # end of a tile into the next tile's first group.
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    n_tiles = pl.num_programs(0)
    n = pages * bs
    rows = q_ref.shape[1]
    W = tables_ref.shape[1]
    layer = layer_ref[0]

    def live_pages(t):
        # tile max -1 (no valid query): no page at all
        return jnp.minimum(jax.lax.div(tilemax_ref[t] + bs, bs), W)

    def groups(t):
        return jax.lax.div(live_pages(t) + pages - 1, pages)

    def page_copies(t, g, half, enabled=True):
        """(fetched?, copy) of each page of group ``g`` of tile ``t``: the
        same scalars decide the start and the wait.  Null table entries
        and pages past the tile's last position are neither fetched nor
        waited for."""
        live, row = live_pages(t), row_ref[t]
        out = []
        for j in range(pages):
            idx = g * pages + j
            blk = tables_ref[row, jnp.minimum(idx, W - 1)]
            out.append(((idx < live) & (blk != 0) & enabled,
                        pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                              kbuf.at[half, j],
                                              sem.at[half])))
        return out

    def start(copies):
        for fetched, c in copies:
            pl.when(fetched)(c.start)

    def wait(copies):
        for fetched, c in copies:
            pl.when(fetched)(c.wait)

    @pl.when(i == 0)
    def _first_tile():
        trip_ref[0] = 0
        # a page that is not fetched leaves its slot as it was: keep what
        # a zero probability multiplies finite
        kbuf[...] = jnp.zeros_like(kbuf)

    # the tile before prefetched this tile's first group at its last
    # trip; a tile that ran no trip prefetched nothing
    @pl.when((i == 0) | (groups(jnp.maximum(i - 1, 0)) == 0))
    def _own_first_group():
        start(page_copies(i, 0, jax.lax.rem(trip_ref[0], 2)))

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    mxu = kbuf.dtype if kbuf.dtype == jnp.bfloat16 else jnp.float32
    q = q_ref[0]                # (rows, width), scaled, in the MXU's dtype
    pos = pos_ref[0]                                           # (rows, 1)
    n_groups = groups(i)

    def trip(g, _):
        t = trip_ref[0]
        half = jax.lax.rem(t, 2)
        more = g + 1 < n_groups
        start(page_copies(jnp.where(more, i, jnp.minimum(i + 1, n_tiles - 1)),
                          jnp.where(more, g + 1, 0), 1 - half,
                          more | (i + 1 < n_tiles)))
        wait(page_copies(i, g, half))
        k = kbuf[half].reshape(n, -1).astype(mxu)              # (n, width)
        # a page that was not fetched lies past the tile's last position,
        # so past every query's: the position mask covers it
        mask = g * n + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1) \
            <= pos
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s, _NEG)
        m_old = m_ref[...]                                     # (rows, 1)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        # the cached vector's first lanes are the value
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(mxu), k[:, :v_width], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        trip_ref[0] = t + 1

    jax.lax.fori_loop(0, n_groups, trip, None)
    # tiles that ran no trip and queries that may read nothing emit 0
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def _tile_tokens(t: int, n_heads: int) -> int:
    """Tokens a tile: the most that keep ``tokens x heads`` within
    ``_TILE_ROWS`` and divide the chunk."""
    tt = max(1, min(t, _TILE_ROWS // n_heads))
    while t % tt:
        tt -= 1
    return tt


def _call_name(t: int, w: int, call=None) -> str:
    """The kernel's name in a device trace, as the paged kernel's: decode
    and prefill apart, one name per block-table width (and per chunk
    length); it ends in a letter (a reader that groups operations strips
    a trailing number)."""
    if t == 1 and call is None:
        return f"_mla_call_w{w}_decode"
    return f"_mla_call_w{w}_t{t}_{call or 'prefill'}"


@functools.partial(jax.jit, static_argnames=("v_width", "scale", "interpret",
                                             "call"))
def _mla_call(tables, max_pos, layer, q, positions, pool, *, v_width, scale,
              interpret, call=None):
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, dq = q.shape
    bs, width = pool.shape[2], pool.shape[3]
    W = tables.shape[1]
    # scaled in float32, then what the products take: a bfloat16 pool's
    # pages as they are, anything else in float32 — done here, once, so a
    # tile's queries cross into fast memory at half the bytes
    q = (q.astype(jnp.float32) * scale).astype(
        pool.dtype if pool.dtype == jnp.bfloat16 else jnp.float32)
    if width != dq:
        # a pool padded to whole lane tiles: the padding multiplies zeros
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - dq),))
    tt = _tile_tokens(T, H)
    nt, rows = T // tt, tt * H
    # a query past the row's last valid position (chunk padding, an
    # inactive slot) may read nothing; a tile stops at its own last page
    positions = jnp.where(positions <= max_pos[:, None], positions, -1)
    tile_max = jnp.max(positions.reshape(B * nt, tt), axis=1)
    tile_row = jnp.arange(B * nt, dtype=jnp.int32) // nt
    pos_rows = jnp.broadcast_to(positions[:, :, None], (B, T, H)
                                ).reshape(B * nt, rows, 1)
    pages = max(1, min(_TRIP_POSITIONS // bs, W))
    n, item = pages * bs, jnp.dtype(pool.dtype).itemsize
    # double-buffered pages, queries, positions (a lane tile a row) and
    # output; the accumulator, m and l; a trip's scores and probabilities
    need = 2 * (n * width * item + rows * width * q.dtype.itemsize
                + rows * 512 + rows * v_width * 4) \
        + rows * (v_width + 256) * 4 + 3 * rows * n * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B * nt,),
        in_specs=[pl.BlockSpec((1, rows, width), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((1, rows, 1), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, v_width), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, pages, bs, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((rows, 1), jnp.float32),        # m
                        pltpu.VMEM((rows, 1), jnp.float32),        # l
                        pltpu.VMEM((rows, v_width), jnp.float32),  # acc
                        pltpu.SMEM((1,), jnp.int32)],              # trips
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, pages=pages,
                          v_width=v_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * nt, rows, v_width), jnp.float32),
        # tiles run in order: the double buffer's parity and the prefetch
        # of the next tile's first group carry from one tile to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(100 << 20, max(need + (8 << 20),
                                                    32 << 20)))),
        interpret=interpret,
        name=_call_name(T, W, call),
    )(tables, tile_row, tile_max, layer, q.reshape(B * nt, rows, width),
      pos_rows, pool)
    return out.reshape(B, T, H, v_width)


def latent_attention(q, pool, block_tables, positions, max_pos, *,
                     v_width: int, scale: float, layer: int = 0, call=None):
    """Absorbed latent attention of ``q`` against a paged latent pool.

    Parameters
    ----------
    q : (B, T, H, c + r) — the chunk's absorbed queries ``[ql | qr]``.
    pool : (n_layers, num_blocks, block_size, width >= c + r) — the WHOLE
        layered pool, already holding this chunk's ``[c_kv | k_rope]``;
        lanes past ``c + r`` (a pool padded to whole lane tiles) are zero.
    block_tables : (B, W) int32 — physical block of each logical block;
        0 is the null sentinel.
    positions : (B, T) int32 — the LAST cache position each query may read.
    max_pos : (B,) int32 — last VALID query position per row (-1 for
        inactive rows: nothing is read and the output is 0).
    v_width : the first ``c`` lanes of a cached vector are its value.
    scale : the softmax scale (the model's: it carries the rotary
        scaling's ``mscale`` squared).
    layer : which layer of the pool to read (a Python constant that
        reaches the kernel as an operand).
    call : names the call in a device trace (``_mla_call_w<W>_decode`` for
        ``T == 1``, else ``_mla_call_w<W>_t<T>_<call>``).

    Returns (B, T, H, v_width) float32, matching
    :func:`latent_attention_reference` on valid queries.
    """
    from .pallas_kernels import _use_interpret

    return _mla_call(jnp.asarray(block_tables, jnp.int32),
                     jnp.asarray(max_pos, jnp.int32),
                     jnp.full((1,), layer, jnp.int32), q,
                     jnp.asarray(positions, jnp.int32), pool,
                     v_width=int(v_width), scale=float(scale),
                     interpret=_use_interpret(), call=call)

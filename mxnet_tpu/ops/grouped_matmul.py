"""Grouped matrix product for sparse experts, as a Pallas TPU kernel.

``grouped_matmul(x, w, sizes)``: rows of ``x`` (M, K) sorted by group,
``w`` (G, K, N) one matrix a group, ``sizes`` (G,) rows a group; row ``r``
of group ``g`` gives ``x[r] @ w[g]``.  The same contract as
``jax.lax.ragged_dot`` (which is the path without Pallas, and the
oracle), for the shape the expert layer of a serving step has: FEW rows a
group (64 rows x 4 positions x top-8 over 128 experts is 16 an expert), so
the product is bound by reading every touched expert's weights once.

The chip's own ragged dot walks small tiles of ``w`` (at the block step's
shapes it read 24% of the HBM roofline, PERF.md PR 26: thousands of grid
steps of a fraction of a microsecond each).  This kernel's unit of work is
a (row tile, group) pair that intersect — at most ``M / tm + G - 1`` of
them, the grid's inner dimension — and a step takes the group's WHOLE
``(K, N)`` matrix (3 MB of bfloat16 at 2048 x 768) against the tile's
``tm`` rows, keeps the rows that belong to the group, and adds them into
the tile's output, which stays resident while consecutive steps share the
tile.  Steps are as long as their matrix takes to arrive.  A matrix too
large to hold twice in fast memory (7168 x 2048 is 29 MB) is cut along
``N`` into the fewest equal column tiles of whole 128-lane groups that
stay under ``_W_TILE_BYTES`` — the grid's OUTER dimension, so a touched
expert is still read once, a column tile a pass over the items, and only
the few row tiles that hold assignments are read again each pass.

Work items come from ``sizes`` alone (``_work_items``, plain ``jnp``): for
item ``i`` its group, its row tile, and how many items are real; the rest
of the static grid repeats the last item's tile and does nothing.  Rows
behind the last group belong to no item: their output is whatever the
tile's other items left (zero, or not written at all) — the caller masks
them, as it has to with ``ragged_dot``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["grouped_matmul"]

_W_TILE_BYTES = 8 << 20     # the most a step's (K, tn) slice of a matrix holds


def _column_tile(K: int, N: int, itemsize: int) -> int:
    """Columns a grid step takes of a group's ``(K, N)`` matrix: all of
    them where the matrix is under ``_W_TILE_BYTES``, else the widest
    divisor of ``N`` in whole 128-lane groups that is."""
    if K * N * itemsize <= _W_TILE_BYTES or N % 128:
        return N
    lanes = N // 128
    fit = max(1, _W_TILE_BYTES // (K * 128 * itemsize))
    return 128 * max(d for d in range(1, lanes + 1)
                     if lanes % d == 0 and d <= fit)


def _work_items(sizes, m: int, tm: int):
    """``(group, tile, start, end, n_items)`` of the (row tile, group)
    pairs that intersect, in row order; arrays of the static length ``m /
    tm + G - 1``, padded with copies of the last real item."""
    G = sizes.shape[0]
    n_max = m // tm + G - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    n_items = item_end[-1]
    item = jnp.minimum(jnp.arange(n_max, dtype=jnp.int32),
                       jnp.maximum(n_items - 1, 0))
    group = jnp.searchsorted(item_end, item, side="right").astype(jnp.int32)
    group = jnp.minimum(group, G - 1)
    tile = first[group] + item - (item_end[group] - tiles[group])
    return (group, jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32),
            starts.astype(jnp.int32), ends.astype(jnp.int32),
            n_items.astype(jnp.int32)[None])


def _gmm_kernel(group_ref, tile_ref, start_ref, end_ref, n_ref, x_ref, w_ref,
                o_ref, *, tm: int):
    i = pl.program_id(1)
    tile = tile_ref[i]

    @pl.when((i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile))
    def _first_item_of_the_tile():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _item():
        g = group_ref[i]
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        prod = jax.lax.dot_general(x_ref[...], w_ref[0],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        o_ref[...] += jnp.where(mine, prod, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gmm_call(x, w, sizes, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    G, _, N = w.shape
    # few rows a group: small tiles waste little on rows of other groups;
    # a prefill chunk's thousands of rows: fewer, fuller items
    tm = 128 if M <= 4096 else 512
    m_pad = -(-M // tm) * tm
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    group, tile, starts, ends, n_items = _work_items(sizes, m_pad, tm)
    item = jnp.dtype(w.dtype).itemsize
    tn = _column_tile(K, N, item)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, m_pad // tm + G - 1),
        in_specs=[pl.BlockSpec((tm, K), lambda j, i, g, t, *_: (t[i], 0)),
                  pl.BlockSpec((1, K, tn),
                               lambda j, i, g, *_: (g[i], 0, j))],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, g, t, *_: (t[i], j)),
    )
    # double-buffered: a group's column tile, the row tile, the f32 output
    need = 2 * (K * tn * item + tm * K * jnp.dtype(x.dtype).itemsize
                + tm * tn * 4) + tm * tn * 4
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(100 << 20, need + (8 << 20)))),
        interpret=interpret,
        name="_gmm_call",
    )(group, tile, starts, ends, n_items, x, w)
    return out[:M]


def grouped_matmul(x, w, sizes):
    """``x`` (M, K) rows sorted by group, ``w`` (G, K, N), ``sizes`` (G,)
    int32 -> (M, N) float32; operands multiply in their own dtype and
    accumulate in float32.  Rows behind ``sum(sizes)`` are undefined."""
    from .pallas_kernels import _use_interpret

    return _gmm_call(x, w, jnp.asarray(sizes, jnp.int32),
                     interpret=_use_interpret())

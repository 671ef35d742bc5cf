"""The scan of a Mamba-2 layer (SSD, arXiv:2405.21060) over a slot's
recurrent state, as Pallas TPU kernels beside their plain ``jax.numpy``
bodies (docs/generation.md "Cache kinds").

A Mamba-2 layer keeps, a head ``m`` of its ``H``, a MATRIX state ``S[m]`` of
``P x N`` entries (``P`` the head's size, ``N`` the state's) that decays by
ONE scalar a head a position, with ``B_t`` and ``C_t`` (``N`` wide) shared
by every head::

    S_t[m] = exp(D_t[m] A[m]) S_{t-1}[m] + D_t[m] x_t[m] B_t^T
    y_t[m] = S_t[m] C_t

``D_t[m] > 0`` the head's step (a softplus), ``A[m] < 0``, ``x_t[m]`` the
head's ``P`` inputs behind the causal convolution.  The skip term, the
gate and its norm, the projections and the convolution are the model's
(``parallel/granite_hybrid.py``).  Over a chunk the recurrence is matrix
products (the "duality"): with ``G_t[m]`` the running sum of ``D A``,

    y_t[m] = sum_{j <= t} (C_t . B_j) e^{G_t - G_j} D_j x_j[m]  +  e^{G_t} S_0[m] C_t

— ONE ``C B^T`` for all heads, a head's decay between two positions laid
over it, against ``D x`` — and the state at the chunk's end is ``e^{G_T}
S_0 + sum_j e^{G_T - G_j} D_j x_j B_j^T``.  The decay between two
positions is the exponential of a DIFFERENCE that is never positive
(``ops/retention.py`` folds its decay into the operands, which bounds a
chunk's summed log-decay; a head here may forget in one position — ``D A``
of -40 — so nothing is folded).

**The state's layout.**  A slot's state a layer is ONE float32 array ``(N +
8, H P)``: a head's ``P`` inputs side by side on the lanes (lane ``m P +
p``), the state's ``N`` entries down the first ``N`` sublanes — ``S[m][p,
n]`` at ``[n, m P + p]``, so that a decode step's read against ``C`` is a
sum DOWN the sublanes, plain vector adds, and never across lanes — and
under them one more sublane tile for the convolution's last ``K - 1``
inputs, which are ``H P + 2 N`` wide (``x``, ``B`` and ``C`` are convolved
together): rows ``0 .. K - 2`` hold their ``x`` part, rows ``K - 1 .. 2 K -
3`` their ``B | C`` part in the first ``2 N`` lanes.  2,228,224 B a layer
at 64 heads of 64 and ``N`` 128 for the 2,149,376 of the mathematics.
:func:`conv_state` reads that tile before the layer's products and the
scan's call writes the new one beside the state it updates: with the
kernels on, XLA never indexes the pool (``ops/selective_scan.py`` says what
happened when it did).  The pool is ``(n_layers, slots + 1, N + 8, H P)``,
indexed by the slot the row's table names (0: the scratch idle rows point
at).

**The calls.**  ``_ssd_call_conv_<decode|prefill>`` (grid: rows) copies the
rows' tile of convolution inputs out.  ``_ssd_call_decode`` (one token a
row; grid: rows): the row's state streams through VMEM once — scaled by its
head's scalar, ``D x B^T`` added, read against ``C`` — and is written back
in place: one read and one write of the state a step.
``_ssd_call_t<T>_prefill`` (a chunk of T positions a row; grid: rows x lane
tiles of 512 x sub-chunks of 128 positions): a lane tile's state stays in
VMEM while the chunk's sub-chunks pass; a sub-chunk is the carried state
read against ``C`` (one product a tile), a head's masked ``(C B^T) o L``
against its ``D x``, and the sub-chunk's ``B^T (w o D x)`` added to the
state; the carried state is read once and written once a chunk.  ``C B^T``
is the same for every head and lane tile and is made once, outside.

A row whose chunk starts at position 0 (``fresh``) starts from the zero
state inside the call; a padded position and an idle row are identities
(``D = 0``: ``S 1 + 0``).  Everything here is float32, the kernels'
products at the MXU's highest precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# (layer, slots, fresh) ahead as scalars, the pool the last operand and the
# first result, updated in place: the selective scan's call, as it is
from .selective_scan import _call as _pool_call

__all__ = ["ssd", "ssd_decode_reference", "ssd_prefill_reference",
           "state_shapes", "conv_state"]

_LANES = 128
_CONV_ROWS = 8      # the sublane tile under the state's: the convolution's
_TILE = 512         # lanes a grid step of the prefill kernel
_SUB = 128          # positions a sub-chunk of the prefill kernel


def state_shapes(n_heads: int, d_head: int, d_state: int, d_conv: int):
    """``((pool name, a slot's shape), ...)`` of a layer's state: one
    pool, the state's ``N`` sublanes over the tile that holds the
    convolution's last ``K - 1`` inputs (their ``x`` part, then their ``B |
    C`` part)."""
    assert 2 * (d_conv - 1) <= _CONV_ROWS, d_conv
    assert d_state % _CONV_ROWS == 0 and 2 * d_state <= n_heads * d_head
    return (("ssd", (d_state + _CONV_ROWS, n_heads * d_head)),)


def _pack_conv(conv, di):
    """The kept inputs ``(B, K - 1, d_i + 2 N)`` as the state's last tile
    ``(B, 8, d_i)``."""
    B, k, w = conv.shape
    bc = jnp.pad(conv[:, :, di:], ((0, 0), (0, 0), (0, 2 * di - w)))
    tile = jnp.concatenate([conv[:, :, :di], bc], axis=1)
    return jnp.pad(tile, ((0, 0), (0, _CONV_ROWS - 2 * k), (0, 0)))


def _unpack_conv(tile, d_conv, d_bc):
    k = d_conv - 1
    return jnp.concatenate([tile[:, :k], tile[:, k:2 * k, :d_bc]], axis=-1)


def conv_state(pool, layer: int, slots, d_conv: int, d_bc: int, *,
               kernel: bool, call: str = "decode"):
    """The convolution's last ``K - 1`` inputs of the rows' slots, oldest
    first: ``(B, K - 1, H P + d_bc)``, ``d_bc`` the width of ``B | C``.
    ``call`` names the kernel's call in a device trace
    (``_ssd_call_conv_<call>``)."""
    n = pool.shape[2] - _CONV_ROWS
    if not kernel:
        return _unpack_conv(pool[layer, slots, n:], d_conv, d_bc)
    from .pallas_kernels import _use_interpret

    tile = _ssd_call_conv(
        jnp.full((1,), layer, jnp.int32), jnp.asarray(slots, jnp.int32),
        pool, call=call, interpret=_use_interpret())
    return _unpack_conv(tile, d_conv, d_bc)


def _over_lanes(a, P):
    """A value a head ``(..., H)`` over its head's lanes ``(..., H P)``."""
    return jnp.repeat(a, P, axis=-1)


def _with_conv(pool, layer, slots, s, conv):
    """The pool with the slots' states ``s`` and kept inputs ``conv``."""
    n, di = s.shape[1:]
    return pool.at[layer, slots, :n].set(s) \
        .at[layer, slots, n:].set(_pack_conv(conv, di))


def ssd_decode_reference(step, x, Bm, Cm, A, fresh, pool, slots, layer,
                         conv):
    """One token a row, plainly: ``step`` (B, H); ``x`` (B, H P); ``Bm``,
    ``Cm`` (B, N); ``A`` (H,); ``fresh`` (B,) bool; ``conv`` (B, K - 1, H P
    + 2 N) the convolution's inputs to keep.  Returns ``(y (B, H P),
    pool)``."""
    N, P = Bm.shape[1], x.shape[1] // step.shape[1]
    s = jnp.where(fresh[:, None, None], 0.0, pool[layer, slots, :N])
    s = _over_lanes(jnp.exp(step * A), P)[:, None, :] * s \
        + Bm[:, :, None] * (_over_lanes(step, P) * x)[:, None, :]
    y = jnp.sum(s * Cm[:, :, None], axis=1)
    return y, _with_conv(pool, layer, slots, s, conv)


def ssd_prefill_reference(step, x, Bm, Cm, A, fresh, pool, slots, layer,
                          conv):
    """A chunk a row in the matrix form, plainly (one chunk, the decay a
    mask): ``step`` (B, T, H); ``x`` (B, T, H P); ``Bm``, ``Cm`` (B, T, N);
    a padded position has ``step = 0``.  Returns ``(y (B, T, H P),
    pool)``."""
    B, T, H = step.shape
    N, P = Bm.shape[2], x.shape[2] // H
    hi = jax.lax.Precision.HIGHEST
    s0 = jnp.where(fresh[:, None, None], 0.0,
                   pool[layer, slots, :N]).reshape(B, N, H, P)
    G = jnp.cumsum(step * A, axis=1)                       # (B, T, H)
    dx = step[..., None] * x.reshape(B, T, H, P)
    causal = jnp.tril(jnp.ones((T, T), bool))
    Gh = G.transpose(0, 2, 1)                              # (B, H, T)
    decay = jnp.exp(jnp.where(causal, Gh[..., :, None] - Gh[..., None, :],
                              -jnp.inf))                   # (B, H, T, T)
    cb = jnp.einsum("btn,bjn->btj", Cm, Bm, precision=hi)
    y = jnp.einsum("btj,bhtj,bjhp->bthp", cb, decay, dx, precision=hi) \
        + jnp.exp(G)[..., None] * jnp.einsum("btn,bnhp->bthp", Cm, s0,
                                             precision=hi)
    wj = jnp.exp(G[:, -1:] - G)                            # (B, T, H)
    s = jnp.exp(G[:, -1])[:, None, :, None] * s0 + jnp.einsum(
        "bjn,bjhp->bnhp", Bm, wj[..., None] * dx, precision=hi)
    return y.reshape(B, T, H * P), _with_conv(
        pool, layer, slots, s.reshape(B, N, H * P), conv)


# -- the kernels ------------------------------------------------------------

def _conv_kernel(layer_ref, slot_ref, s_ref, o_ref):
    del layer_ref, slot_ref         # (the index map reads them)
    o_ref[0] = s_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("call", "interpret"))
def _ssd_call_conv(layer, slots, pool, *, call, interpret):
    from jax.experimental.pallas import tpu as pltpu

    B, di = slots.shape[0], pool.shape[3]
    tile = (pool.shape[2] - _CONV_ROWS) // _CONV_ROWS   # the tile under N
    return pl.pallas_call(
        _conv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec(
                (1, 1, _CONV_ROWS, di),
                lambda b, layer, slot: (layer[0], slot[b], tile, 0))],
            out_specs=pl.BlockSpec((1, _CONV_ROWS, di),
                                   lambda b, *_: (b, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, _CONV_ROWS, di), pool.dtype),
        interpret=interpret, name=f"_ssd_call_conv_{call}")(layer, slots,
                                                            pool)


def _decode_kernel(layer_ref, slot_ref, fresh_ref, a_ref, dx_ref, b_ref,
                   c_ref, cv_ref, s_ref, so_ref, y_ref, *, d_inner):
    # grid = (rows,); the blocks are the row's whole state and vectors
    del layer_ref, slot_ref         # (the index maps read them)
    fresh = fresh_ref[pl.program_id(0)] > 0
    b, c = b_ref[0], c_ref[0]                           # (N, 128)
    N = b.shape[0]
    for j in range(d_inner // _LANES):
        sl = slice(j * _LANES, (j + 1) * _LANES)
        s = jnp.where(fresh, 0.0, s_ref[0, 0, :N, sl])  # (N, 128)
        s = a_ref[0, :, sl] * s + dx_ref[0, :, sl] * b
        so_ref[0, 0, :N, sl] = s
        y_ref[0, :, sl] = jnp.sum(s * c, axis=0, keepdims=True)
    so_ref[0, 0, N:, :] = cv_ref[0]


def _dot(a, b):
    """A float32 product at the MXU's highest precision."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _prefill_kernel(layer_ref, slot_ref, fresh_ref, dx_ref, gx_ref, g_ref,
                    gt_ref, cb_ref, c_ref, bt_ref, cv_ref, s_ref, so_ref,
                    y_ref, *, P):
    # grid = (rows, lane tiles, sub-chunks); the tile's state stays in the
    # result's block while the sub-chunks pass
    del layer_ref, slot_ref
    N = c_ref.shape[2]
    Q, tile = dx_ref.shape[1:]
    fresh = fresh_ref[pl.program_id(0)] > 0

    @pl.when(pl.program_id(2) == 0)
    def _first():
        so_ref[0, 0, :N] = jnp.where(fresh, 0.0, s_ref[0, 0, :N])
        so_ref[0, 0, N:] = cv_ref[0]

    s = so_ref[0, 0, :N]                                # (N, tile)
    gx, dx = gx_ref[0], dx_ref[0]                       # (Q, tile)
    last = gx[Q - 1:Q]
    # the carried state, decayed to each position
    y_ref[0] = jnp.exp(gx) * _dot(c_ref[0], s)
    so_ref[0, 0, :N] = jnp.exp(last) * s \
        + _dot(bt_ref[0], jnp.exp(last - gx) * dx)
    # inside the sub-chunk, a head at a time: (C B^T) o L against D x
    cb = cb_ref[0]                                      # (Q, Q), causal
    g, gt = g_ref[0, 0], gt_ref[0, 0]                   # (Q, h), (h, Q)
    width = max(P, _LANES)
    per = width // P                                    # heads a lane group
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1)
    for k in range(tile // width):
        sl = slice(k * width, (k + 1) * width)
        dxg = dx[:, sl]
        acc = jnp.zeros((Q, width), jnp.float32)
        for i in range(per):
            h = k * per + i
            m = cb * jnp.exp(jnp.minimum(g[:, h:h + 1] - gt[h:h + 1, :], 0.0))
            acc += _dot(m, dxg if per == 1
                        else jnp.where(lane // P == i, dxg, 0.0))
        y_ref[0, :, sl] += acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_call_decode(layer, slots, fresh, a, dx, bl, cl, cv, pool, *,
                     interpret):
    B, _, di = a.shape
    N, rows = bl.shape[1], pool.shape[2]
    row = pl.BlockSpec((1, 1, di), lambda b, *_: (b, 0, 0))
    col = pl.BlockSpec((1, N, _LANES), lambda b, *_: (b, 0, 0))
    return _pool_call(
        functools.partial(_decode_kernel, d_inner=di), "_ssd_call_decode",
        (B,), [row, row, col, col,
               pl.BlockSpec((1, _CONV_ROWS, di), lambda b, *_: (b, 0, 0))],
        pl.BlockSpec((1, 1, rows, di),
                     lambda b, layer, slot, fresh: (layer[0], slot[b], 0, 0)),
        row, (B, 1, di), (a, dx, bl, cl, cv), pool, (layer, slots, fresh),
        8 * rows * di * 4 + (16 << 20), interpret)


@functools.partial(jax.jit, static_argnames=("P", "interpret"))
def _ssd_call_prefill(layer, slots, fresh, dx, gx, g, gt, cb, c, bt, cv,
                      pool, *, P, interpret):
    B, T, di = dx.shape
    N, rows = c.shape[2], pool.shape[2]
    Q, heads = cb.shape[2], g.shape[3]
    tile = heads * P
    lanes = pl.BlockSpec((1, Q, tile), lambda b, j, q, *_: (b, q, j))
    return _pool_call(
        functools.partial(_prefill_kernel, P=P), f"_ssd_call_t{T}_prefill",
        (B, di // tile, T // Q),
        [lanes, lanes,
         pl.BlockSpec((1, 1, Q, heads), lambda b, j, q, *_: (b, j, q, 0)),
         pl.BlockSpec((1, 1, heads, Q), lambda b, j, q, *_: (b, j, 0, q)),
         pl.BlockSpec((1, Q, Q), lambda b, j, q, *_: (b, q, 0)),
         pl.BlockSpec((1, Q, N), lambda b, j, q, *_: (b, q, 0)),
         pl.BlockSpec((1, N, Q), lambda b, j, q, *_: (b, 0, q)),
         pl.BlockSpec((1, _CONV_ROWS, tile), lambda b, j, q, *_: (b, 0, j))],
        pl.BlockSpec((1, 1, rows, tile),
                     lambda b, j, q, layer, slot, fresh:
                     (layer[0], slot[b], 0, j)),
        lanes, (B, T, di), (dx, gx, g, gt, cb, c, bt, cv), pool,
        (layer, slots, fresh),
        12 * Q * tile * 4 + 6 * rows * tile * 4 + 8 * Q * Q * 4 + (16 << 20),
        interpret)


def _tile(di: int, P: int) -> int:
    """Lanes a grid step of the prefill kernel takes: whole heads."""
    for t in (_TILE, _LANES):
        if di % t == 0 and t % P == 0:
            return t
    return di


def ssd(step, x, Bm, Cm, A, fresh, pool, slots, conv, *, layer: int,
        kernel: bool):
    """A Mamba-2 layer's scan over the slots' states.

    Parameters
    ----------
    step : (B, T, H) float32 — the heads' steps ``D_t > 0``; 0 at a padded
        position and in an idle row (an identity on the state).
    x : (B, T, H P) float32 — the heads' inputs behind the convolution.
    Bm, Cm : (B, T, N) float32 — the positions' input and output weights,
        one pair for all heads.
    A : (H,) float32, negative.
    fresh : (B,) bool — the row's chunk starts at position 0: it starts
        from the zero state whatever its slot held.
    pool : the WHOLE pool ``(n_layers, slots + 1, N + 8, H P)``, its first
        ``N`` sublanes updated in place.
    slots : (B,) int32 — the state each row's table names (0: the scratch).
    conv : (B, K - 1, H P + 2 N) float32 — the convolution's inputs the
        slot keeps for the next step, written beside the state.
    layer : which layer of the pool (a Python constant that reaches the
        kernel as an operand).
    kernel : the Pallas calls (``_ssd_call_decode`` for ``T == 1``, else
        ``_ssd_call_t<T>_prefill``), or the plain bodies above.

    Returns ``(y (B, T, H P) float32, pool)``: ``y_t = S_t C_t``, the skip
    term not in it.
    """
    B, T, H = step.shape
    di, N = x.shape[2], Bm.shape[2]
    P = di // H
    slots = jnp.asarray(slots, jnp.int32)
    if not kernel:
        if T == 1:
            y, pool = ssd_decode_reference(
                step[:, 0], x[:, 0], Bm[:, 0], Cm[:, 0], A, fresh, pool,
                slots, layer, conv)
            return y[:, None], pool
        return ssd_prefill_reference(step, x, Bm, Cm, A, fresh, pool, slots,
                                     layer, conv)
    from .pallas_kernels import _use_interpret

    assert di % _LANES == 0 and (P % _LANES == 0 or _LANES % P == 0), (di, P)
    interpret = _use_interpret()
    prefetch = (jnp.full((1,), layer, jnp.int32), slots,
                fresh.astype(jnp.int32))
    cv = _pack_conv(conv, di)
    if T == 1:
        lanes = lambda t: jnp.broadcast_to(  # noqa: E731
            t[..., None], t.shape + (_LANES,))
        pool, y = _ssd_call_decode(
            *prefetch, _over_lanes(jnp.exp(step * A), P),
            _over_lanes(step, P) * x, lanes(Bm[:, 0]), lanes(Cm[:, 0]), cv,
            pool, interpret=interpret)
        return y, pool
    # whole sub-chunks: the positions behind the chunk's end are identities
    Q = _SUB if T > _SUB else -(-T // 8) * 8
    Tp = -(-T // Q) * Q
    pad = lambda t: jnp.pad(  # noqa: E731
        t, ((0, 0), (0, Tp - T)) + ((0, 0),) * (t.ndim - 2))
    step, x, Bm, Cm = pad(step), pad(x), pad(Bm), pad(Cm)
    nq, tile = Tp // Q, _tile(di, P)
    heads = tile // P
    # the running sum of the log-decay inside each sub-chunk
    G = jnp.cumsum((step * A).reshape(B, nq, Q, H), axis=2).reshape(B, Tp, H)
    g = G.reshape(B, Tp, H // heads, heads).transpose(0, 2, 1, 3)
    cb = jnp.einsum("bqtn,bqjn->bqtj", Cm.reshape(B, nq, Q, N),
                    Bm.reshape(B, nq, Q, N),
                    precision=jax.lax.Precision.HIGHEST)
    cb = jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), cb, 0.0)
    pool, y = _ssd_call_prefill(
        *prefetch, _over_lanes(step, P) * x, _over_lanes(G, P), g,
        g.transpose(0, 1, 3, 2), cb.reshape(B, Tp, Q), Cm,
        Bm.transpose(0, 2, 1), cv, pool, P=P, interpret=interpret)
    return y[:, :T], pool

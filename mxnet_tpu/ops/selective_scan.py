"""The selective scan of a Mamba layer over a slot's recurrent state, as two
Pallas TPU kernels beside their plain ``jax.numpy`` bodies
(docs/generation.md "Cache kinds").

A state-space layer keeps, a channel ``c`` of its ``d_i``, a DIAGONAL state
of ``N`` entries with an input-dependent step (Mamba-1, arXiv:2312.00752)::

    s_t[n, c] = exp(D_t[c] A[n, c]) s_{t-1}[n, c] + D_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n s_t[n, c] C_t[n]

``D_t > 0`` the step (a softplus), ``A < 0``, ``u_t`` the channel's input
behind its causal convolution, ``B_t`` and ``C_t`` the position's ``N``
input and output weights.  The skip term, the gate, the projections and
the convolution are the model's (``parallel/sambay_lm.py``).

**The state's layout.**  A slot's state a layer is ONE float32 array ``(N +
8, d_i)``: channels on the lanes, the scan's ``N`` entries down the first
``N`` sublanes (16 = two tiles), and under them one more tile whose first
``K - 1`` sublanes are the convolution's last inputs (3 at ``K`` 4):
:func:`conv_state` reads them before the layer's products, and the scan's
call writes the new ones beside the state it updates.  480 KB a layer at
``d_i`` 5120 for the 380 of the mathematics.  (A pool of its own for the
convolution's inputs, 24 MB a layer, the chip's compiler re-lays-out,
copies into fast memory and copies back around every step; a slice of
sublanes 16-18 gathered or scattered by XLA makes it turn the whole pool
round, 570 MB a layer: with the kernels on, XLA never indexes the pool.)
The pool is ``(n_layers, slots + 1, N + 8, d_i)``, indexed by the
slot the row's table names (0: the scratch idle rows point at).

**The calls.**  ``_ssm_call_conv_<decode|prefill>`` (grid: rows) copies the
rows' tile of convolution inputs out.  ``_ssm_call_decode`` (one token a
row; grid: rows): the row's state streams through VMEM once — decayed, the position added, read
against ``C`` — and is written back in place: one read and one write of the
state a step.  ``_ssm_call_t<T>_prefill`` (a chunk of T positions a row;
grid: rows x channel tiles): the tile's state stays in registers while the
chunk's positions pass in order, eight to a loop trip; the carried state is
read once and written once a chunk.  ``B_t`` and ``C_t`` arrive spread over a
lane tile (``(N, 128)`` a position), so that the kernel never turns a row
into a column.

A row whose chunk starts at position 0 (``fresh``) starts from the zero
state inside the call; a padded position and an idle row are identities
(``D = 0``: ``s 1 + 0``).  Everything here is float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["selective_scan", "scan_decode_reference",
           "scan_prefill_reference", "state_shapes", "conv_state"]

_LANES = 128
_TRIP = 8           # positions a trip of the prefill kernel: a sublane tile
_TILE = 512         # channels a grid step of the prefill kernel
_CONV_ROWS = 8      # the sublane tile under the scan's: the convolution's


def state_shapes(d_inner: int, d_state: int, d_conv: int):
    """``((pool name, a slot's shape), ...)`` of a layer's state: one
    pool, the scan's ``N`` sublanes over the tile that holds the
    convolution's last ``K - 1`` inputs."""
    assert d_conv - 1 <= _CONV_ROWS, d_conv
    return (("ssm", (d_state + _CONV_ROWS, d_inner)),)


def conv_state(pool, layer: int, slots, d_conv: int, *, kernel: bool,
               call: str = "decode"):
    """The convolution's last ``K - 1`` inputs of the rows' slots, oldest
    first: ``(B, K - 1, d_i)``.  ``call`` names the kernel's call in a
    device trace (``_ssm_call_conv_<call>``)."""
    n = pool.shape[2] - _CONV_ROWS
    if not kernel:
        return pool[layer, slots, n:n + d_conv - 1]
    from .pallas_kernels import _use_interpret

    return _ssm_call_conv(
        jnp.full((1,), layer, jnp.int32), jnp.asarray(slots, jnp.int32),
        pool, call=call, interpret=_use_interpret())[:, :d_conv - 1]


def _with_conv(pool, layer, slots, s, conv):
    """The pool with the slots' scan states ``s`` and last inputs ``conv``."""
    n = s.shape[1]
    return pool.at[layer, slots, :n].set(s) \
        .at[layer, slots, n:n + conv.shape[1]].set(conv)


def scan_decode_reference(step, u, Bm, Cm, A, fresh, pool, slots, layer,
                          conv):
    """One token a row, plainly: ``step``, ``u`` (B, d_i); ``Bm``, ``Cm``
    (B, N); ``A`` (N, d_i); ``fresh`` (B,) bool; ``conv`` (B, K - 1, d_i)
    the convolution's inputs to keep.  Returns ``(y (B, d_i), pool)``."""
    N = A.shape[0]
    s = jnp.where(fresh[:, None, None], 0.0, pool[layer, slots, :N])
    s = jnp.exp(step[:, None, :] * A) * s \
        + (step * u)[:, None, :] * Bm[:, :, None]
    y = jnp.sum(s * Cm[:, :, None], axis=1)
    return y, _with_conv(pool, layer, slots, s, conv)


def scan_prefill_reference(step, u, Bm, Cm, A, fresh, pool, slots, layer,
                           conv):
    """A chunk a row, position by position: ``step``, ``u`` (B, T, d_i);
    ``Bm``, ``Cm`` (B, T, N); a padded position has ``step = 0``.  Returns
    ``(y (B, T, d_i), pool)``."""
    N = A.shape[0]
    s0 = jnp.where(fresh[:, None, None], 0.0, pool[layer, slots, :N])

    def one(s, xs):
        d_t, u_t, b_t, c_t = xs
        s = jnp.exp(d_t[:, None, :] * A) * s \
            + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s, y = jax.lax.scan(one, s0, tuple(
        a.swapaxes(0, 1) for a in (step, u, Bm, Cm)))
    return y.swapaxes(0, 1), _with_conv(pool, layer, slots, s, conv)


# -- the kernels ------------------------------------------------------------

def _conv_kernel(layer_ref, slot_ref, s_ref, o_ref):
    del layer_ref, slot_ref         # (the index map reads them)
    o_ref[0] = s_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("call", "interpret"))
def _ssm_call_conv(layer, slots, pool, *, call, interpret):
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
        interpret=interpret, name=f"_ssm_call_conv_{call}")(layer, slots,
                                                            pool)


def _decode_kernel(layer_ref, slot_ref, fresh_ref, step_ref, u_ref, b_ref,
                   c_ref, a_ref, cv_ref, s_ref, so_ref, y_ref, *, d_inner):
    # grid = (rows,); the blocks are the row's whole state and vectors
    del layer_ref, slot_ref         # (the index maps read them)
    fresh = fresh_ref[pl.program_id(0)] > 0
    b, c = b_ref[0], c_ref[0]                           # (N, 128)
    N = b.shape[0]
    for j in range(d_inner // _LANES):
        sl = slice(j * _LANES, (j + 1) * _LANES)
        d = step_ref[0, :, sl]                          # (1, 128)
        s = jnp.where(fresh, 0.0, s_ref[0, 0, :N, sl])  # (N, 128)
        s = jnp.exp(d * a_ref[:, sl]) * s + (d * u_ref[0, :, sl]) * b
        so_ref[0, 0, :N, sl] = s
        y_ref[0, :, sl] = jnp.sum(s * c, axis=0, keepdims=True)
    so_ref[0, 0, N:, :] = cv_ref[0]


def _prefill_kernel(layer_ref, slot_ref, fresh_ref, step_ref, u_ref, b_ref,
                    c_ref, a_ref, cv_ref, s_ref, so_ref, y_ref, *, T, tile):
    # grid = (rows, channel tiles); the tile's state rides the loop
    del layer_ref, slot_ref
    fresh = fresh_ref[pl.program_id(0)] > 0
    A = a_ref[...]                                      # (N, tile)
    N = A.shape[0]
    wide = lambda t: jnp.concatenate(  # noqa: E731 — (N, 128) over the tile
        [t] * (tile // _LANES), axis=1) if tile > _LANES else t

    def trip(i, s):
        t0 = pl.multiple_of(i * _TRIP, _TRIP)
        d8 = step_ref[0, pl.ds(t0, _TRIP), :]           # (8, tile)
        x8 = d8 * u_ref[0, pl.ds(t0, _TRIP), :]
        rows = []
        for r in range(_TRIP):
            d = d8[r:r + 1]
            s = jnp.exp(d * A) * s + x8[r:r + 1] * wide(b_ref[0, t0 + r])
            rows.append(jnp.sum(s * wide(c_ref[0, t0 + r]), axis=0,
                                keepdims=True))
        y_ref[0, pl.ds(t0, _TRIP), :] = jnp.concatenate(rows, axis=0)
        return s

    so_ref[0, 0, :N] = jax.lax.fori_loop(
        0, T // _TRIP, trip, jnp.where(fresh, 0.0, s_ref[0, 0, :N]))
    so_ref[0, 0, N:] = cv_ref[0]


def _call(kernel, name, grid, specs, state_spec, y_spec, y_shape, operands,
          pool, prefetch, vmem, interpret):
    """One of the two calls: ``prefetch`` (layer, slots, fresh) go ahead as
    scalars, the pool is the last operand and the first result, updated in
    place."""
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=grid, in_specs=specs + [state_spec],
        out_specs=[state_spec, y_spec])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(y_shape, jnp.float32)],
        input_output_aliases={3 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret, name=name,
    )(*prefetch, *operands, pool)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_call_decode(layer, slots, fresh, step, u, bl, cl, A, cv, pool, *,
                     interpret):
    B, _, di = step.shape
    N, rows = A.shape[0], pool.shape[2]
    row = pl.BlockSpec((1, 1, di), lambda b, *_: (b, 0, 0))
    col = pl.BlockSpec((1, N, _LANES), lambda b, *_: (b, 0, 0))
    return _call(
        functools.partial(_decode_kernel, d_inner=di), "_ssm_call_decode",
        (B,), [row, row, col, col,
               pl.BlockSpec((N, di), lambda b, *_: (0, 0)),
               pl.BlockSpec((1, _CONV_ROWS, di), lambda b, *_: (b, 0, 0))],
        pl.BlockSpec((1, 1, rows, di),
                     lambda b, layer, slot, fresh: (layer[0], slot[b], 0, 0)),
        row, (B, 1, di), (step, u, bl, cl, A, cv), pool,
        (layer, slots, fresh), 8 * rows * di * 4 + (16 << 20), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_call_prefill(layer, slots, fresh, step, u, bl, cl, A, cv, pool, *,
                      interpret):
    B, T, di = step.shape
    N, n_rows = A.shape[0], pool.shape[2]
    tile = _TILE if di % _TILE == 0 else _LANES
    rows = pl.BlockSpec((1, T, tile), lambda b, j, *_: (b, 0, j))
    cols = pl.BlockSpec((1, T, N, _LANES), lambda b, j, *_: (b, 0, 0, 0))
    return _call(
        functools.partial(_prefill_kernel, T=T, tile=tile),
        f"_ssm_call_t{T}_prefill", (B, di // tile),
        [rows, rows, cols, cols,
         pl.BlockSpec((N, tile), lambda b, j, *_: (0, j)),
         pl.BlockSpec((1, _CONV_ROWS, tile), lambda b, j, *_: (b, 0, j))],
        pl.BlockSpec((1, 1, n_rows, tile),
                     lambda b, j, layer, slot, fresh:
                     (layer[0], slot[b], 0, j)),
        rows, (B, T, di), (step, u, bl, cl, A, cv), pool,
        (layer, slots, fresh),
        6 * T * tile * 4 + 4 * T * N * _LANES * 4 + (16 << 20), interpret)


def selective_scan(step, u, Bm, Cm, A, fresh, pool, slots, conv, *,
                   layer: int, kernel: bool):
    """A state-space layer's scan over the slots' states.

    Parameters
    ----------
    step : (B, T, d_i) float32 — the positions' steps ``D_t > 0``; 0 at a
        padded position and in an idle row (an identity on the state).
    u : (B, T, d_i) float32 — the channels' inputs behind the convolution.
    Bm, Cm : (B, T, N) float32 — the positions' input and output weights.
    A : (N, d_i) float32, negative.
    fresh : (B,) bool — the row's chunk starts at position 0: it starts
        from the zero state whatever its slot held.
    pool : the WHOLE pool ``(n_layers, slots + 1, N + 8, d_i)``, its first
        ``N`` sublanes updated in place.
    slots : (B,) int32 — the state each row's table names (0: the scratch).
    conv : (B, K - 1, d_i) float32 — the convolution's inputs the slot keeps
        for the next step, written beside the state.
    layer : which layer of the pool (a Python constant that reaches the
        kernel as an operand).
    kernel : the Pallas calls (``_ssm_call_decode`` for ``T == 1``, else
        ``_ssm_call_t<T>_prefill``), or the plain bodies above.

    Returns ``(y (B, T, d_i) float32, pool)``: ``y_t = s_t . C_t``, the
    skip term not in it.
    """
    B, T, di = step.shape
    slots = jnp.asarray(slots, jnp.int32)
    if not kernel:
        if T == 1:
            y, pool = scan_decode_reference(
                step[:, 0], u[:, 0], Bm[:, 0], Cm[:, 0], A, fresh, pool,
                slots, layer, conv)
            return y[:, None], pool
        return scan_prefill_reference(step, u, Bm, Cm, A, fresh, pool, slots,
                                      layer, conv)
    from .pallas_kernels import _use_interpret

    assert di % _LANES == 0 and T % _TRIP in (0, 1), (di, T)
    prefetch = (jnp.full((1,), layer, jnp.int32), slots,
                fresh.astype(jnp.int32))
    lanes = lambda t: jnp.broadcast_to(  # noqa: E731
        t[..., None], t.shape + (_LANES,))
    call = _ssm_call_decode if T == 1 else _ssm_call_prefill
    if T == 1:
        Bm, Cm = Bm[:, 0], Cm[:, 0]
    cv = jnp.pad(conv, ((0, 0), (0, _CONV_ROWS - conv.shape[1]), (0, 0)))
    pool, y = call(*prefetch, step, u, lanes(Bm), lanes(Cm), A, cv, pool,
                   interpret=_use_interpret())
    return y, pool

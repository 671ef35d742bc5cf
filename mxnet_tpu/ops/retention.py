"""Power retention over a slot's recurrent state, as two Pallas TPU kernels
beside their plain ``jax.numpy`` bodies (docs/generation.md "Cache kinds").

A power-retention layer (degree 2) is gated linear attention whose feature
map is the symmetric square of the key: with ``g_t <= 0`` the log of a KV
head's decay and ``phi(a) . phi(b) = (a . b)^2``::

    S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T      z_t = e^{g_t} z_{t-1} + phi(k_t)
    o_t,m = phi(q_t,m)^T S_t / (phi(q_t,m) . z_t + eps)        (m: a query head of the KV head's group)

which is the attention form ``w_t,j = exp(G_t - G_j) (q_t . k_j)^2`` (``G``
the running sum of ``g``), ``o_t = sum_j w_t,j v_j / (sum_j w_t,j + eps)``,
summed in another order.

**The state's layout.**  The symmetric square of ``a`` (d wide) has ``d (d
+ 1) / 2`` distinct entries (8,256 at d = 128).  They are kept as ``R = d /
2 + 1`` ROWS of d lanes, row ``r`` holding ``c_r a_i a_{(i + r) mod d}``:
the pairs at cyclic distance ``r``.  Every unordered pair ``{i, j}`` has one
distance in ``1 .. d/2 - 1`` (``c = sqrt 2``), the squares are row 0 (``c =
1``), and the pairs at distance ``d / 2`` appear twice in the last row (``c
= 1``: twice 1 is the pair's weight 2).  ``R d`` = 8,320 lanes at d = 128,
65 whole lane tiles, 0.8% over the packed 8,256 — and a row is the vector
times itself ROTATED, which the chip does on its lanes with no gather.  A
head's state is ONE float32 array ``(R, dv + 8, d)``: ``S[r, e, i]`` in its
first ``dv`` sublanes (``e``: the value's lane) and the normaliser ``z[r,
i]`` in sublane ``dv`` — ``z`` is ``S`` for a value that is always 1 — with
7 sublanes of padding to a whole tile (a pool of its own for ``z``, 40 MB,
is small enough for the chip's compiler to copy into fast memory and back
around every call).  The pool is ``(n_layers, slots + 1, KV heads, R, dv +
8, d)``, indexed by the slot the row's table names (0: the scratch idle
rows point at): 36.2 MB a layer a slot at d = 128 for the 34.1 of the
mathematics.

**The calls.**  ``_ret_call_decode`` (one token a row; grid rows x KV
heads): a head's state streams through VMEM a row of ``phi`` at a time —
decayed, ``phi(k) v^T`` added, read by the group's query heads (one MXU
product a row of ``phi``), written back in place — one read and one write
of the state a step.  ``_ret_call_t<T>_prefill`` (a chunk of T positions a
row): inside the chunk the causal ``(q k^T)^2`` with the decay folded into
the operands (``q' = q e^{G/2}``, ``k' = k e^{-G/2}``: ``(q' . k')^2`` is
the decayed square), across chunks ``phi(q')`` against the carried state,
and the chunk's ``v^T phi(k')`` added to it; the whole scaled by ``e^{G_T}``
on the way out.  The fold needs ``|G|`` over one chunk well inside float32's
exponent (a decay of 0.99 a step over 512 positions is ``e^-5.1``); the
plain body below masks instead and has no such bound.

A row whose chunk starts at position 0 (``fresh``) starts from the zero
state inside the call; a padded position and an idle row are identities
(``g = 0``, ``k = 0``: ``S 1 + 0``).

The kernels' products take float32 operands at the MXU's highest precision
and accumulate in float32 (bfloat16 operands were tried: on a v5e the cast
of every state tile costs more than the passes it saves, PERF.md PR 40);
gates, their sums, the state and the normaliser are float32 throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["phi", "phi_rows", "phi_width", "packed_width", "retention",
           "retention_decode_reference", "retention_prefill_reference",
           "state_shapes"]

_SQRT2 = float(np.sqrt(2.0))
_ROWS = 8       # a sublane tile: the group's query heads are padded to it


def phi_rows(d: int) -> int:
    """Rows of the state's layout: cyclic distances 0 .. d / 2."""
    assert d % 2 == 0, d
    return d // 2 + 1


def phi_width(d: int) -> int:
    """Lanes a head's expansion takes as stored: ``(d / 2 + 1) d``."""
    return phi_rows(d) * d


def packed_width(d: int) -> int:
    """Entries of the symmetric square by the mathematics: ``d (d + 1) / 2``."""
    return d * (d + 1) // 2


def state_shapes(n_kv_heads: int, d: int, dv: int):
    """``((pool name, a slot's shape), ...)`` of a layer's state: one
    pool, ``S`` over ``z`` and the padding of its tile."""
    return (("state", (n_kv_heads, phi_rows(d), dv + _ROWS, d)),)


def _split(state):
    """``(S (..., R, dv, d), z (..., R, d))`` of a state ``(..., R, dv + 8,
    d)``."""
    dv = state.shape[-2] - _ROWS
    return state[..., :dv, :], state[..., dv, :]


def _join(S, z):
    pad = jnp.zeros(z.shape[:-1] + (_ROWS - 1, z.shape[-1]), S.dtype)
    return jnp.concatenate([S, z[..., None, :], pad], axis=-2)


def _coef(d: int):
    c = np.full((phi_rows(d),), _SQRT2, np.float32)
    c[0] = c[-1] = 1.0
    return c


def phi(a):
    """The symmetric square of ``a (..., d)`` as stored, ``(..., R, d)``:
    ``phi(a) . phi(b)`` summed over both axes is ``(a . b)^2``."""
    d = a.shape[-1]
    rolled = jnp.stack([jnp.roll(a, -r, axis=-1)
                        for r in range(phi_rows(d))], axis=-2)
    return a[..., None, :] * rolled * jnp.asarray(_coef(d))[:, None]


def retention_decode_reference(q, k, v, g, fresh, pool, slots, layer, eps):
    """One token a row, plainly: ``q`` (B, Hq, d), ``k`` (B, Hkv, d), ``v``
    (B, Hkv, dv), ``g`` (B, Hkv) the log decay, ``fresh`` (B,) bool.
    Returns ``(o (B, Hq, dv), pool)``."""
    B, Hq, d = q.shape
    Hkv = k.shape[1]
    S, z = _split(jnp.where(fresh[:, None, None, None, None], 0.0,
                            pool[layer, slots]))
    a = jnp.exp(g)
    pk = phi(k)                                           # (B, Hkv, R, d)
    S = a[..., None, None, None] * S \
        + pk[:, :, :, None, :] * v[:, :, None, :, None]
    z = a[..., None, None] * z + pk
    pq = phi(q.reshape(B, Hkv, Hq // Hkv, d))             # (B, Hkv, G, R, d)
    num = jnp.einsum("bngri,bnrei->bnge", pq, S)
    den = jnp.einsum("bngri,bnri->bng", pq, z)
    o = num / (den[..., None] + eps)
    return o.reshape(B, Hq, -1), pool.at[layer, slots].set(_join(S, z))


def retention_prefill_reference(q, k, v, g, fresh, pool, slots, layer, eps):
    """A chunk a row in the chunked form, plainly (the decay a mask, not a
    fold): ``q`` (B, T, Hq, d), ``k`` (B, T, Hkv, d), ``v`` (B, T, Hkv, dv),
    ``g`` (B, T, Hkv); a padded position has ``g = 0`` and ``k = 0``.
    Returns ``(o (B, T, Hq, dv), pool)``."""
    B, T, Hq, d = q.shape
    Hkv = k.shape[2]
    S, z = _split(jnp.where(fresh[:, None, None, None, None], 0.0,
                            pool[layer, slots]))
    G = jnp.cumsum(g, axis=1)                             # (B, T, Hkv)
    q5 = q.reshape(B, T, Hkv, Hq // Hkv, d)
    # inside the chunk
    s = jnp.einsum("btngd,bjnd->bngtj", q5, k)
    Gn = G.transpose(0, 2, 1)                             # (B, Hkv, T)
    causal = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.exp(jnp.where(causal, Gn[..., :, None] - Gn[..., None, :],
                              -jnp.inf))                  # (B, Hkv, T, T)
    w = s * s * decay[:, :, None]
    num = jnp.einsum("bngtj,bjne->btnge", w, v)
    den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)       # (B, T, Hkv, G)
    # across chunks: the carried state, decayed to each position
    pq = phi(q5)                                          # (B, T, Hkv, G, R, d)
    eG = jnp.exp(G)
    num = num + eG[..., None, None] * jnp.einsum("btngri,bnrei->btnge", pq, S)
    den = den + eG[..., None] * jnp.einsum("btngri,bnri->btng", pq, z)
    o = num / (den[..., None] + eps)
    # the state at the chunk's end
    pk = phi(k)                                           # (B, T, Hkv, R, d)
    wj = jnp.exp(G[:, -1:, :] - G)                        # (B, T, Hkv)
    eT = jnp.exp(G[:, -1])                                # (B, Hkv)
    S = eT[..., None, None, None] * S + jnp.einsum(
        "bjne,bjnri->bnrei", v * wj[..., None], pk)
    z = eT[..., None, None] * z + jnp.einsum("bjn,bjnri->bnri", wj, pk)
    return o.reshape(B, T, Hq, -1), pool.at[layer, slots].set(_join(S, z))


# -- the kernels ------------------------------------------------------------

def _roll1(x, interpret):
    """``x`` rotated one lane down: ``out[..., i] = x[..., (i + 1) % d]``."""
    if interpret:
        return jnp.roll(x, -1, axis=-1)
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, x.shape[-1] - 1, x.ndim - 1)


def _dot(a, b, dims):
    """A float32 product at the MXU's highest precision."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


_NT = ((1,), (1,))      # contract both operands' lanes
_NN = ((1,), (0,))


def _ones_row(d):
    """The normaliser's tile of the value down the sublanes: 1 in its first
    sublane (``z`` is ``S`` for a value that is always 1), 0 in the padding."""
    return (jax.lax.broadcasted_iota(jnp.int32, (_ROWS, d), 0) == 0
            ).astype(jnp.float32)


def _decode_kernel(layer_ref, slot_ref, fresh_ref, q_ref, kva_ref, s_ref,
                   so_ref, num_ref, den_ref, *, R, d, interpret):
    # grid = (rows, KV heads); the blocks are one head's whole state
    del layer_ref, slot_ref         # (the index maps read them)
    fresh = fresh_ref[pl.program_id(0)] > 0
    q = q_ref[0, 0]                                     # (8, d): the group
    kva = kva_ref[0, 0]
    k = jnp.broadcast_to(kva[0:1], (_ROWS, d))
    a = kva[2:3]                                        # (1, d): e^g
    # the value down the sublanes, the same in every lane, over the
    # normaliser's 1: (dv + 8, d)
    vT = jnp.concatenate([jnp.broadcast_to(kva[1:2], (d, d)).T,
                          _ones_row(d)], axis=0)
    qr, kr = q, k
    num = jnp.zeros((_ROWS, d), jnp.float32)
    den = jnp.zeros((_ROWS, d), jnp.float32)
    for r in range(R):
        c = 1.0 if r in (0, R - 1) else _SQRT2
        pk = (k * kr * c)[0:1]                          # (1, d)
        pq = q * qr * c                                 # (8, d)
        new = jnp.where(fresh, 0.0, s_ref[0, 0, 0, r]) * a + vT * pk
        so_ref[0, 0, 0, r] = new                        # (dv + 8, d)
        num = num + _dot(pq, new[:d], _NT)              # (8, dv)
        den = den + pq * new[d:d + 1]
        if r + 1 < R:
            qr, kr = _roll1(qr, interpret), _roll1(kr, interpret)
    num_ref[0, 0] = num
    den_ref[0, 0] = den


def _prefill_kernel(layer_ref, slot_ref, fresh_ref, q_ref, k_ref, v_ref,
                    vt_ref, aux_ref, s_ref, so_ref, num_ref, den_ref, qroll,
                    kroll, *, R, d, G, T, interpret):
    # grid = (rows, KV heads); q_ref is the group's G heads, head-major
    del layer_ref, slot_ref
    fresh = fresh_ref[pl.program_id(0)] > 0
    egt = aux_ref[0, 0, 0:1, :]                         # (1, d): e^{G_T}
    k, v = k_ref[0, 0], v_ref[0, 0]                     # (T, d)
    causal = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1) \
        <= jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    for gi in range(G):         # inside the chunk, a query head at a time
        rows = slice(gi * T, (gi + 1) * T)
        s = _dot(q_ref[0, 0, rows, :], k, _NT)                  # (T, T)
        w = jnp.where(causal, s * s, 0.0)
        num_ref[0, 0, rows, :] = _dot(w, v, _NN)
        # (the normaliser is summed over its lanes outside)
        den_ref[0, 0, rows, :] = jnp.broadcast_to(
            jnp.sum(w, axis=1, keepdims=True) * (1.0 / d), (T, d))
    qroll[...] = q_ref[0, 0]
    kroll[...] = k

    def row(r, _):              # a row of phi: the carried state's part
        c = jnp.where((r == 0) | (r == R - 1), 1.0, _SQRT2)
        qr, kr = qroll[...], kroll[...]
        pq = q_ref[0, 0] * qr * c                               # (G T, d)
        pk = k_ref[0, 0] * kr * c                               # (T, d)
        old = jnp.where(fresh, 0.0, s_ref[0, 0, 0, r])      # (dv + 8, d)
        num_ref[0, 0] += _dot(pq, old[:d], _NT)
        den_ref[0, 0] += pq * old[d:d + 1]
        so_ref[0, 0, 0, r, :d, :] = (
            old[:d] + _dot(vt_ref[0, 0], pk, _NN)) * egt
        so_ref[0, 0, 0, r, d:, :] = (
            old[d:] + _ones_row(d) * jnp.sum(pk, axis=0, keepdims=True)) * egt
        qroll[...] = _roll1(qr, interpret)
        kroll[...] = _roll1(kr, interpret)

    jax.lax.fori_loop(0, R, row, None)


def _state_spec(R, rows, d):
    """The block of one head's state of the slot a row's table names, for
    the pool as operand and as result."""
    return pl.BlockSpec((1, 1, 1, R, rows, d),
                        lambda b, n, layer, slot, fresh:
                        (layer[0], slot[b], n, 0, 0, 0))


def _rows_spec(rows, lanes):
    return pl.BlockSpec((1, 1, rows, lanes), lambda b, n, *_: (b, n, 0, 0))


def _call(kernel, name, operands, small_specs, outs, scratch, pool, prefetch,
          vmem, interpret):
    """One of the two calls: ``prefetch`` (layer, slots, fresh) go ahead as
    scalars, the pool is the last operand and the first result, updated in
    place."""
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv = operands[0].shape[:2]
    R, rows, d = pool.shape[3:]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, Hkv),
        in_specs=small_specs + [_state_spec(R, rows, d)],
        out_specs=[_state_spec(R, rows, d)] + [_rows_spec(*o) for o in outs],
        scratch_shapes=scratch)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype)]
        + [jax.ShapeDtypeStruct((B, Hkv) + o, jnp.float32) for o in outs],
        input_output_aliases={3 + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret, name=name,
    )(*prefetch, *operands, pool)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ret_call_decode(layer, slots, fresh, q8, kva, pool, *, interpret):
    R, rows, d = pool.shape[3:]
    block = R * rows * d * 4
    return _call(
        functools.partial(_decode_kernel, R=R, d=d, interpret=interpret),
        "_ret_call_decode", (q8, kva),
        [_rows_spec(_ROWS, d), _rows_spec(_ROWS, d)],
        [(_ROWS, d), (_ROWS, d)], [], pool, (layer, slots, fresh),
        4 * block + (16 << 20), interpret)


@functools.partial(jax.jit, static_argnames=("G", "interpret"))
def _ret_call_prefill(layer, slots, fresh, qs, ks, v, vt, aux, pool, *, G,
                      interpret):
    from jax.experimental.pallas import tpu as pltpu

    R, rows, d = pool.shape[3:]
    T = ks.shape[2]
    block, group = R * rows * d * 4, G * T * d * 4
    return _call(
        functools.partial(_prefill_kernel, R=R, d=d, G=G, T=T,
                          interpret=interpret),
        f"_ret_call_t{T}_prefill", (qs, ks, v, vt, aux),
        [_rows_spec(G * T, d), _rows_spec(T, d), _rows_spec(T, d),
         _rows_spec(d, T), _rows_spec(_ROWS, d)],
        [(G * T, d), (G * T, d)],
        [pltpu.VMEM((G * T, d), jnp.float32),
         pltpu.VMEM((T, d), jnp.float32)], pool, (layer, slots, fresh),
        4 * block + 10 * group + 6 * T * T * 4 + (16 << 20), interpret)


def retention(q, k, v, g, fresh, pool, slots, *, layer: int, eps: float,
              kernel: bool):
    """A retention layer's step over the slots' states.

    Parameters
    ----------
    q : (B, T, Hq, d) float32 — normed, rotated queries; query head ``h``
        reads KV head ``h // (Hq / Hkv)``.
    k : (B, T, Hkv, d), v : (B, T, Hkv, dv) float32; a padded position and
        an idle row carry ``k = 0``.
    g : (B, T, Hkv) float32 — the log of the decay, ``<= 0``; 0 at a
        padded position and in an idle row.
    fresh : (B,) bool — the row's chunk starts at position 0: it starts
        from the zero state whatever its slot held.
    pool : the WHOLE pool (``state_shapes``), updated in place.
    slots : (B,) int32 — the state each row's table names (0: the scratch).
    layer : which layer of the pool (a Python constant that reaches the
        kernel as an operand).
    kernel : the Pallas calls (``_ret_call_decode`` for ``T == 1``, else
        ``_ret_call_t<T>_prefill``), or the plain bodies above.

    Returns ``(o (B, T, Hq, dv) float32, pool)``.
    """
    B, T, Hq, d = q.shape
    Hkv, dv = k.shape[2], v.shape[3]
    G = Hq // Hkv
    slots = jnp.asarray(slots, jnp.int32)
    if not kernel:
        if T == 1:
            o, pool = retention_decode_reference(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], fresh, pool, slots,
                layer, eps)
            return o[:, None], pool
        return retention_prefill_reference(q, k, v, g, fresh, pool, slots,
                                           layer, eps)
    from .pallas_kernels import _use_interpret

    assert dv == d and G <= _ROWS and T % _ROWS in (0, 1), (dv, d, G, T)
    interpret = _use_interpret()
    prefetch = (jnp.full((1,), layer, jnp.int32), slots,
                fresh.astype(jnp.int32))
    lanes = lambda t: jnp.broadcast_to(t[..., None, None],  # noqa: E731
                                       t.shape + (1, d))
    if T == 1:
        q8 = jnp.pad(q[:, 0].reshape(B, Hkv, G, d),
                     ((0, 0), (0, 0), (0, _ROWS - G), (0, 0)))
        kva = jnp.concatenate(
            [k[:, 0, :, None], v[:, 0, :, None], lanes(jnp.exp(g[:, 0])),
             jnp.zeros((B, Hkv, _ROWS - 3, d), jnp.float32)], axis=2)
        pool, num, den = _ret_call_decode(
            *prefetch, q8, kva, pool, interpret=interpret)
        o = num[:, :, :G] / (jnp.sum(den[:, :, :G], axis=-1, keepdims=True)
                             + eps)
        return o.reshape(B, 1, Hq, dv), pool
    Gc = jnp.cumsum(g, axis=1)                            # (B, T, Hkv)
    qs = q.reshape(B, T, Hkv, G, d) * jnp.exp(0.5 * Gc)[..., None, None]
    ks = k * jnp.exp(-0.5 * Gc)[..., None]
    qs = qs.transpose(0, 2, 3, 1, 4).reshape(B, Hkv, G * T, d)
    aux = jnp.broadcast_to(lanes(jnp.exp(Gc[:, -1])), (B, Hkv, _ROWS, d))
    pool, num, den = _ret_call_prefill(
        *prefetch, qs, ks.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 3, 1), aux, pool, G=G, interpret=interpret)
    o = num / (jnp.sum(den, axis=-1, keepdims=True) + eps)
    o = o.reshape(B, Hkv, G, T, dv).transpose(0, 3, 1, 2, 4)
    return o.reshape(B, T, Hq, dv), pool

"""Pallas flash attention (single-chip; the ring carries it across chips).

Forward is one Pallas kernel: for each (batch*head, q-block) program, k/v
blocks stream through VMEM with the online-softmax m/l recurrence, so HBM
traffic is O(T*D) and nothing T×T ever materializes — the standard
flash-attention scheme mapped to the TPU memory hierarchy (VMEM blocks,
MXU matmuls; /opt/skills/guides/pallas_guide.md patterns).  The reference
has no analogue (2018 softmax(QK^T)V materializes the scores); SURVEY §5.7
makes long-context first-class, and this is the single-device leg the
sequence-parallel ring composes with (`parallel/ring_attention.py` holds
the cross-chip m/l merge).

Backward (docs/pallas.md): under the ``TPUMX_PALLAS`` gate the dq and
dk/dv passes are true Pallas kernels — the forward additionally emits the
per-row logsumexp, and both backward kernels replay the score tile from
VMEM-resident q/k blocks (``p = exp(s - lse)``) with causal block
skipping, so the whole recompute stays tiled in fast memory end-to-end
(FlashAttention, Dao et al.).  ``TPUMX_PALLAS=0`` restores the previous
memory-efficient lax.scan recompute (`_bwd_scan`) byte-for-byte.

Block sizes are selected from dtype and head dim to fit the ~16MB VMEM
budget (``select_flash_blocks``; ``TPUMX_FLASH_BLOCK_Q``/``_K`` override).

On CPU (tests, virtual meshes) the SAME kernels run through the Pallas
interpreter (`TPUMX_PALLAS_INTERPRET` / non-TPU backend, like the other
kernels in pallas_kernels.py).  Oracle: tests/test_flash_attention.py
checks outputs AND gradients against `parallel.ring_attention.local_attention`.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def _use_interpret():
    # lazy: pallas_kernels re-exports flash_attention from here, so a
    # top-level back-import would be circular when this module loads first
    from .pallas_kernels import _use_interpret as impl

    return impl()


def _use_pallas_bwd():
    from .pallas_kernels import pallas_enabled

    return pallas_enabled()


def select_flash_blocks(d_head: int, dtype):
    """(block_q, block_k) sized to VMEM from dtype and head dim.

    Per grid step the kernel holds the q tile plus double-buffered k/v
    tiles (lane dim padded to 128 by Mosaic for d_head < 128), the f32
    accumulator scratch, and up to three (bq, bk) f32 score tiles in the
    backward (p, dp, ds).  Blocks grow together in powers of two from 128
    while that footprint fits a ~4.5MB slice of the 16MB VMEM — larger
    tiles amortize the online-softmax rescale and the MXU ramp.
    ``TPUMX_FLASH_BLOCK_Q``/``TPUMX_FLASH_BLOCK_K`` pin either explicitly.
    """
    env_q = os.environ.get("TPUMX_FLASH_BLOCK_Q")
    env_k = os.environ.get("TPUMX_FLASH_BLOCK_K")
    if env_q or env_k:
        bq = int(env_q) if env_q else 128
        return bq, int(env_k) if env_k else bq
    item = jnp.dtype(dtype).itemsize
    lane_d = max(int(d_head), 128)  # Mosaic pads the minor dim to a lane

    def cost(bq, bk):
        tiles = (bq + 2 * bk) * lane_d * item * 2      # double-buffered
        scratch = bq * lane_d * 4 + 2 * bq * 4          # f32 acc + m/l
        scores = 3 * bq * bk * 4                        # p/dp/ds (bwd)
        return tiles + scratch + scores

    bq = bk = 128
    while bq < 512 and cost(bq * 2, bk * 2) <= 4.5 * 1024 * 1024:
        bq *= 2
        bk *= 2
    return bq, bk


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, bq: int, bk: int, causal: bool,
                scale: float, t_real: int, with_lse: bool):
    if with_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        (o_ref, acc_ref, m_ref, l_ref), lse_ref = refs, None
    # grid = (bh, q blocks, k blocks); kj is the INNERMOST (sequential)
    # dim, so the VMEM scratch (acc/m/l) carries the online-softmax state
    # across k blocks while only ONE (bk, d) k/v tile is resident — true
    # streaming, VMEM use independent of T
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: blocks strictly above the diagonal contribute nothing
    live = (kj * bk <= (qi + 1) * bq - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < t_real                          # padding tail
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, _NEG)
        m_old = m_ref[...]                            # (bq, 1) columns
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp of the masked scaled scores — the backward
            # kernels' recompute anchor (p = exp(s - lse)).  Padded rows
            # stay finite: their q is zero, so s == 0 on surviving columns.
            # Carried as a (bq, 1) COLUMN of a (BH, T, 1) array: a (1, bq)
            # row block of (BH, T) is not a legal TPU block
            # (docs/pallas.md "block-layout rule").
            lse_ref[0] = m_ref[...] + jnp.log(
                jnp.maximum(l_ref[...], 1e-30))


def _sds(shape, dtype, like):
    # inside shard_map (Ulysses impl="flash") outputs must carry the
    # inputs' varying-mesh-axes annotation or check_vma rejects them
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


@functools.partial(jax.jit, static_argnames=("t_real", "causal", "bq", "bk",
                                             "scale", "interpret",
                                             "with_lse"))
def _fwd_call(q3, k3, v3, t_real, causal, bq, bk, scale, interpret,
              with_lse=False):
    from jax.experimental.pallas import tpu as pltpu

    bh, t_pad, d = q3.shape
    grid = (bh, t_pad // bq, t_pad // bk)
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, causal=causal,
                               scale=scale, t_real=t_real, with_lse=with_lse)
    o_shape = _sds((bh, t_pad, d), q3.dtype, q3)
    o_spec = pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0))
    if with_lse:
        out_shape = (o_shape, _sds((bh, t_pad, 1), jnp.float32, q3))
        out_specs = (o_spec,
                     pl.BlockSpec((1, bq, 1), lambda i, j, kk: (i, j, 0)))
    else:
        out_shape, out_specs = o_shape, o_spec
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0)),
                  pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, 0)),
                  pl.BlockSpec((1, bk, d), lambda i, j, kk: (i, kk, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
        name="_fwd_call_flash",
    )(q3, k3, v3)


# ---------------------------------------------------------------------------
# Pallas backward: dq kernel (grid over q blocks, k innermost) and a fused
# dk/dv kernel (grid over k blocks, q innermost).  Both replay the (bq, bk)
# score tile in VMEM from the forward's lse — no T×T residency, causal
# blocks above the diagonal skipped exactly like the forward.
# ---------------------------------------------------------------------------

def _bwd_mask(qi, kj, bq, bk, t_real, causal):
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < t_real
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        mask = mask & (kpos <= qpos)
    return mask


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, bq: int, bk: int, causal: bool, scale: float,
               t_real: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = (kj * bk <= (qi + 1) * bq - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, d), scaled
        k = k_ref[0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(_bwd_mask(qi, kj, bq, bk, t_real, causal), s, _NEG)
        p = jnp.exp(s - lse_ref[0])                    # masked cols → 0
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _emit():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, bq: int, bk: int, causal: bool,
                scale: float, t_real: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # causal: q blocks strictly above the k block's diagonal see none of it
    live = ((qi + 1) * bq - 1 >= kj * bk) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, d), scaled
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(_bwd_mask(qi, kj, bq, bk, t_real, causal), s, _NEG)
        p = jnp.exp(s - lse_ref[0])                    # (bq, bk)
        dv_acc[:] += jax.lax.dot_general(               # pᵀ @ g
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += jax.lax.dot_general(               # dsᵀ @ q_scaled
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("t_real", "causal", "bq", "bk",
                                             "scale", "interpret"))
def _bwd_call(q3, k3, v3, g3, lse, delta, t_real, causal, bq, bk, scale,
              interpret):
    from jax.experimental.pallas import tpu as pltpu

    bh, t_pad, d = q3.shape
    q_spec = pl.BlockSpec((1, bq, d), lambda i, a, b: (i, a, 0))
    q_spec_inner = pl.BlockSpec((1, bq, d), lambda i, a, b: (i, b, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda i, a, b: (i, b, 0))
    k_spec_outer = pl.BlockSpec((1, bk, d), lambda i, a, b: (i, a, 0))
    # lse / delta are (BH, T, 1): (bq, 1) column blocks
    row_spec = pl.BlockSpec((1, bq, 1), lambda i, a, b: (i, a, 0))
    row_spec_inner = pl.BlockSpec((1, bq, 1), lambda i, a, b: (i, b, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, causal=causal,
                          scale=scale, t_real=t_real),
        grid=(bh, t_pad // bq, t_pad // bk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_sds((bh, t_pad, d), q3.dtype, q3),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="_bwd_call_flash_dq",
    )(q3, k3, v3, g3, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, causal=causal,
                          scale=scale, t_real=t_real),
        grid=(bh, t_pad // bk, t_pad // bq),
        in_specs=[q_spec_inner, k_spec_outer, k_spec_outer, q_spec_inner,
                  row_spec_inner, row_spec_inner],
        out_specs=(k_spec_outer, k_spec_outer),
        out_shape=(_sds((bh, t_pad, d), k3.dtype, k3),
                   _sds((bh, t_pad, d), v3.dtype, v3)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="_bwd_call_flash_dkv",
    )(q3, k3, v3, g3, lse, delta)
    return dq, dk, dv


def _bwd_scan(q3, k3, v3, o3, g3, t_real, causal, scale, bk):
    """Memory-efficient backward: scan over k/v blocks, one (T, bk) tile
    live; standard flash-attention recompute with delta = sum(g*o)."""
    bh, t, d = q3.shape
    q = q3.astype(jnp.float32) * scale
    g = g3.astype(jnp.float32)
    o = o3.astype(jnp.float32)
    delta = jnp.sum(g * o, axis=-1)                    # (bh, t)

    # logsumexp per row, recomputed blockwise (cheap: one pass)
    def lse_body(carry, j):
        m, l = carry
        k = jax.lax.dynamic_slice(k3, (0, j * bk, 0), (bh, bk, d)) \
            .astype(jnp.float32)
        s = jnp.einsum("btd,bkd->btk", q, k)
        s = s + _mask(j, bk, t, t_real, causal)
        m_new = jnp.maximum(m, jnp.max(s, axis=2))
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new[..., None]),
                                             axis=2)
        return (m_new, l), None

    nk = t // bk
    # carries derive from q so they inherit its varying-mesh-axes (vma)
    # annotation — plain jnp.zeros carries would fail lax.scan's type check
    # inside shard_map (the Ulysses impl="flash" path)
    row0 = jnp.zeros_like(q[:, :, 0])
    (m, l), _ = jax.lax.scan(lse_body, (row0 + _NEG, row0),
                             jnp.arange(nk))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))

    def grad_body(dq, j):
        k = jax.lax.dynamic_slice(k3, (0, j * bk, 0), (bh, bk, d)) \
            .astype(jnp.float32)
        v = jax.lax.dynamic_slice(v3, (0, j * bk, 0), (bh, bk, d)) \
            .astype(jnp.float32)
        s = jnp.einsum("btd,bkd->btk", q, k) + _mask(j, bk, t, t_real,
                                                     causal)
        p = jnp.exp(s - lse[..., None])                # (bh, t, bk)
        dv = jnp.einsum("btk,btd->bkd", p, g)
        dp = jnp.einsum("btd,bkd->btk", g, v)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("btk,bkd->btd", ds, k)
        dk = jnp.einsum("btk,btd->bkd", ds, q)
        return dq, (dk, dv)

    dq, (dks, dvs) = jax.lax.scan(grad_body, jnp.zeros_like(q),
                                  jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 1).reshape(bh, t, d)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(bh, t, d)
    return (dq * scale).astype(q3.dtype), dk.astype(k3.dtype), \
        dv.astype(v3.dtype)


def _mask(j, bk, t, t_real, causal):
    kpos = j * bk + jnp.arange(bk)[None, :]            # (1, bk)
    qpos = jnp.arange(t)[:, None]                      # (t, 1)
    ok = kpos < t_real
    if causal:
        ok = ok & (kpos <= qpos)
    return jnp.where(ok, 0.0, _NEG)[None]              # (1, t, bk)


def _pad_to(x, t_pad):
    pad = t_pad - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _pad_grid(t_real, bq, bk):
    t_pad = ((t_real + bq - 1) // bq) * bq
    return ((t_pad + bk - 1) // bk) * bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, t_real, causal, blocks, scale):
    bq, bk = blocks
    t_pad = _pad_grid(t_real, bq, bk)
    out = _fwd_call(_pad_to(q3, t_pad), _pad_to(k3, t_pad),
                    _pad_to(v3, t_pad), t_real, causal, bq, bk, scale,
                    _use_interpret())
    return out[:, :t_real]


def _flash_fwd(q3, k3, v3, t_real, causal, blocks, scale):
    bq, bk = blocks
    if _use_pallas_bwd():
        # forward once more WITH the lse output — the anchor the Pallas
        # backward kernels recompute p from (a with_lse=False program would
        # throw the softmax stats away)
        t_pad = _pad_grid(t_real, bq, bk)
        out_p, lse = _fwd_call(_pad_to(q3, t_pad), _pad_to(k3, t_pad),
                               _pad_to(v3, t_pad), t_real, causal, bq, bk,
                               scale, _use_interpret(), with_lse=True)
        return out_p[:, :t_real], (q3, k3, v3, out_p[:, :t_real], lse)
    out = _flash(q3, k3, v3, t_real, causal, blocks, scale)
    return out, (q3, k3, v3, out, None)


def _flash_bwd(t_real, causal, blocks, scale, res, g):
    q3, k3, v3, out, lse = res
    bq, bk = blocks
    if lse is not None:
        t_pad = _pad_grid(t_real, bq, bk)
        g_pad = _pad_to(g, t_pad)
        o_pad = _pad_to(out, t_pad)
        # delta = rowsum(dO * O): one cheap elementwise pass; zero-padded g
        # zeroes every padded row's contribution inside the kernels
        delta = jnp.sum(g_pad.astype(jnp.float32)
                        * o_pad.astype(jnp.float32), axis=-1, keepdims=True)
        dq, dk, dv = _bwd_call(_pad_to(q3, t_pad), _pad_to(k3, t_pad),
                               _pad_to(v3, t_pad), g_pad, lse, delta,
                               t_real, causal, bq, bk, scale,
                               _use_interpret())
    else:
        t_pad = ((t_real + bk - 1) // bk) * bk
        dq, dk, dv = _bwd_scan(_pad_to(q3, t_pad), _pad_to(k3, t_pad),
                               _pad_to(v3, t_pad), _pad_to(out, t_pad),
                               _pad_to(g, t_pad), t_real, causal, scale, bk)
    return dq[:, :t_real], dk[:, :t_real], dv[:, :t_real]


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, scale=None,
                    block_q: int = None, block_k: int = None):
    """(B, T, H, D) attention with O(T) memory.  Drop-in for
    `parallel.ring_attention.local_attention` (same signature/semantics,
    incl. the optional softmax scale), usable as the `attention=` callable
    of the transformer LM and behind the `_contrib_flash_attention` op.
    Block sizes default to :func:`select_flash_blocks` (dtype/head-dim
    VMEM fit); pass ``block_q``/``block_k`` to pin them."""
    B, T, H, D = q.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    sel_q, sel_k = select_flash_blocks(D, q.dtype)
    block_q = int(block_q) if block_q else sel_q
    block_k = int(block_k) if block_k else sel_k
    if T >= block_q:
        bq = block_q
    else:
        bq = max(16, 1 << (T - 1).bit_length())  # next pow2, >= 16
    bk = min(block_k, bq)
    to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    out = _flash(to3(q), to3(k), to3(v), T, causal, (bq, bk), scale)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3).astype(q.dtype)

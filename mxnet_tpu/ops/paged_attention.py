"""Paged flash-decode attention (vLLM's PagedAttention, as a Pallas TPU
kernel).

The generation engine's decode hot path (parallel/transformer.py
``transformer_lm_decode``) historically GATHERED the whole paged KV context
into contiguous ``(B, W*bs, H, D)`` arrays and ran dense attention over the
full table-width bucket every token — per-token HBM traffic scaling with
the bucket width, and a full materialized copy of the cache slice besides.
This kernel walks the block table INSIDE the kernel instead: the table is a
scalar-prefetch operand (``PrefetchScalarGridSpec``), so the index map
streams exactly the K/V blocks the row owns from the donated pool straight
through VMEM, accumulating with the online-softmax m/l recurrence (the same
scheme as ops/flash_attention.py's forward).  Null table slots (the block-0
sentinel) and blocks past the row's last written position are redirected to
block 0 and skipped — consecutive identical block indices mean Mosaic never
re-issues the DMA, so dead grid steps cost neither bandwidth nor compute.

One kernel serves BOTH generation phases: decode (``T=1`` single queries
per slot) and (chunked) prefill (``T=seq-bucket`` chunk attending to
everything already cached, including its own freshly scattered K/V).
Masking is by cache-position <= query-position, exactly the dense path's
mask, so bucketed table widths never perturb real rows.

The pool keeps heads FOLDED into its minor dim — ``(num_blocks, bs, H*D)``
— so a K/V block is a lane-dense ``(bs, H*D)`` tile the TPU compiler
accepts (docs/pallas.md "block-layout rule"); the kernel's per-head loop
slices lanes.

Gating: ``mxnet_tpu.ops.pallas_kernels.pallas_enabled()`` — default on for
TPU, ``TPUMX_PALLAS=0`` restores the gather+dense XLA path
(``paged_attention_reference`` below IS that path).  On CPU the same
kernel runs through the Pallas interpreter (tier-1's parity leg);
tests/test_chip_compile.py asks the TPU compiler for the real shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30

__all__ = ["paged_attention", "paged_attention_reference", "attention_scale",
           "paged_attention_sharded"]


def attention_scale(d_head: int) -> float:
    """1/sqrt(d) computed in f32 — bit-identical to the traced
    ``1.0 / jnp.sqrt(d).astype(f32)`` the dense decode path uses (host f64
    sqrt can differ in the last ulp)."""
    import numpy as _np

    return float(_np.float32(1.0) / _np.sqrt(_np.float32(d_head)))


def paged_attention_reference(q, k_ctx, v_ctx, attn_mask, scale):
    """The gather+dense attend, verbatim from transformer_lm_decode — the
    ``TPUMX_PALLAS=0`` path and the kernel's parity oracle.

    q: (B, T, H, D); k_ctx/v_ctx: (B, W*bs, H, D) gathered context;
    attn_mask: (B, T, W*bs) bool; scale: f32 scalar.  Same numerics as
    ring_attention.local_attention: f32 scores and accumulation, masked
    slots at exactly 0 probability.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_ctx,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(attn_mask[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_ctx.dtype), v_ctx,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o


def _paged_kernel(tables_ref, maxpos_ref, q_ref, pos_ref, k_ref, v_ref,
                  *refs, bs: int, bt: int, n_heads: int, d_head: int,
                  scale: float, quantized: bool):
    # grid = (B, T tiles, W); W is the INNERMOST (sequential) dim, so the
    # VMEM scratch (acc/m/l) carries the online-softmax state across the
    # row's cache blocks while only ONE (bs, H*D) K/V tile is resident.
    # Heads are FOLDED into the lane dim (docs/pallas.md "block-layout
    # rule"): every block's last two dims are whole or (8k, 128k), which
    # is what Mosaic accepts; a block that cut one head out of an
    # (..., H, D) array was refused.  The static per-head loop slices
    # lanes [h*D, (h+1)*D) of the resident tiles.
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    w = pl.program_id(2)
    nw = pl.num_programs(2)

    @pl.when(w == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # dead blocks: null sentinel (table entry 0 — the allocator never hands
    # out physical block 0) or wholly past the row's last valid query
    # position.  The index map already redirected their DMA to block 0.
    live = (tables_ref[b, w] != 0) & (w * bs <= maxpos_ref[b])

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale               # (bt, H*D)
        k = k_ref[0].astype(jnp.float32)                       # (bs, H*D)
        v = v_ref[0].astype(jnp.float32)
        ctx = w * bs + jax.lax.broadcasted_iota(jnp.int32, (bt, bs), 1)
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
                p, vh, (((1,), (0,)), ((), ())),
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


def _query_tile(t: int, hd: int) -> int:
    """Query rows per grid step: the whole chunk while its f32 tile stays
    under ~1 MB of VMEM (q, out and the accumulator each hold one, q/out
    double-buffered), else the largest power-of-two tile that does —
    always a multiple of 8, so a tiled block is legal for Mosaic."""
    from .pallas_kernels import _row_cap

    cap = min(256, _row_cap(hd))
    return t if t <= cap else cap


def _call_name(t: int, w: int) -> str:
    """The kernel's name in a device trace: decode and prefill apart, one
    name per block-table width (and per prefill chunk length).  It starts
    with the wrapper's name, which trace readers search for, and ends in
    a letter: a reader that groups operations strips a trailing number."""
    return f"_paged_call_w{w}_decode" if t == 1 \
        else f"_paged_call_w{w}_t{t}_prefill"


@functools.partial(jax.jit,
                   static_argnames=("n_heads", "scale", "interpret"))
def _paged_call(tables, max_pos, q, positions, k_pool, v_pool, k_scale=None,
                v_scale=None, *, n_heads, scale, interpret):
    """q: (B, T, H*D); positions: (B, T); pools: (num_blocks, bs, H*D);
    scales (int8 pool only): (num_blocks, H).  Returns (B, T, H*D)."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, HD = q.shape
    bs = k_pool.shape[1]
    W = tables.shape[1]
    quantized = k_scale is not None
    bt = _query_tile(T, HD)
    t_pad = -(-T // bt) * bt
    if t_pad != T:
        # padded queries sit at position 0: they attend one cache slot of
        # a live block and are sliced off below
        q = jnp.pad(q, ((0, 0), (0, t_pad - T), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, t_pad - T)))

    def kv_index(b, t, w, tables_ref, maxpos_ref):
        # dead blocks redirect to the null block: consecutive identical
        # indices skip the re-fetch, so dead grid steps cost no HBM traffic
        blk = tables_ref[b, w]
        return (jnp.where(w * bs > maxpos_ref[b], 0, blk), 0, 0)

    q_spec = pl.BlockSpec((1, bt, HD), lambda b, t, w, *_: (b, t, 0))
    in_specs = [q_spec,
                # positions ride as a (bt, 1) COLUMN: a (1, T) row block of
                # a (B, T) array is not a legal TPU block for B > 1
                pl.BlockSpec((1, bt, 1), lambda b, t, w, *_: (b, t, 0)),
                pl.BlockSpec((1, bs, HD), kv_index),
                pl.BlockSpec((1, bs, HD), kv_index)]
    args = [tables, max_pos, q, positions[:, :, None], k_pool, v_pool]
    if quantized:
        # (num_blocks, H) -> (num_blocks, 1, H): a (1, 1, H) block is
        # whole in its last two dims
        in_specs += [pl.BlockSpec((1, 1, n_heads), kv_index)] * 2
        args += [k_scale[:, None, :], v_scale[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, t_pad // bt, W),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bt, HD), jnp.float32),
                        pltpu.VMEM((bt, n_heads), jnp.float32),
                        pltpu.VMEM((bt, n_heads), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, bs=bs, bt=bt, n_heads=n_heads,
                               d_head=HD // n_heads, scale=scale,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, t_pad, HD), q.dtype),
        interpret=interpret,
        name=_call_name(T, W),
    )(*args)
    return out[:, :T]


def paged_attention(q, k_pool, v_pool, block_tables, positions, max_pos,
                    scale=None, k_scale=None, v_scale=None):
    """Attention of ``q`` against a paged KV pool, walking the block table
    in-kernel.

    Parameters
    ----------
    q : (B, T, H, D) — this chunk's queries (T=1 decode, T=bucket prefill).
    k_pool, v_pool : (num_blocks, block_size, H*D) — ONE layer's pool
        (already holding this chunk's scattered K/V), heads folded into
        the minor dim (head h owns lanes ``[h*D, (h+1)*D)``).
    block_tables : (B, W) int32 — physical block of each logical block;
        0 is the null sentinel.
    positions : (B, T) int32 — global position of each query (in-range).
    max_pos : (B,) int32 — last VALID query position per row (−1 for
        inactive rows: every block is skipped and the output is 0).
    scale : float, optional — softmax scale; default
        :func:`attention_scale` of D.
    k_scale, v_scale : (num_blocks, H) f32, optional — per-(block, head)
        dequantization scales for an INT8 pool (docs/quantization.md):
        the kernel dequantizes each K/V tile in VMEM, with the scales
        index-mapped through the same scalar-prefetched block table as
        the blocks themselves.

    Returns (B, T, H, D) in q's dtype, matching
    :func:`paged_attention_reference` at rtol 1e-5 (f32) on valid rows.
    """
    from .pallas_kernels import _use_interpret

    B, T, H, D = q.shape
    if scale is None:
        scale = attention_scale(D)
    if k_scale is not None:
        k_scale = jnp.asarray(k_scale, jnp.float32)
        v_scale = jnp.asarray(v_scale, jnp.float32)
    out = _paged_call(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(max_pos, jnp.int32), q.reshape(B, T, H * D),
        jnp.asarray(positions, jnp.int32), k_pool, v_pool, k_scale, v_scale,
        n_heads=H, scale=float(scale), interpret=_use_interpret())
    return out.reshape(B, T, H, D)


def paged_attention_sharded(q, k_pool, v_pool, block_tables, positions,
                            max_pos, mesh, axis: str = "mp", scale=None,
                            k_scale=None, v_scale=None):
    """:func:`paged_attention` partitioned PER HEAD over a model-parallel
    mesh axis (docs/sharding.md, docs/generation.md).

    An opaque ``pallas_call`` cannot be partitioned by GSPMD.  But every
    head is independent — so a ``shard_map`` over the head dimension runs
    the SAME kernel on each mp rank's head slice (Q and the output on
    their head dim, the folded K/V pools on their ``H*D`` minor dim, which
    splits on head boundaries; block tables / positions replicated — they
    are head-invariant).  Per-head numerics are bit-identical to the
    unsharded kernel.

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
    pspec = P(None, None, axis)         # folded heads: the pools' minor dim
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
                               v_scale=vs)

    # pallas_call cannot declare varying-mesh-axes metadata
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=qspec, check_vma=False)(*args)

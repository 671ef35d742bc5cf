"""Ring attention: exact blockwise attention over a sequence-sharded mesh axis.

A first-class capability the 2018 reference lacks (SURVEY.md §5.7).  Q/K/V are
sharded on the sequence axis across the `sp` mesh axis; K/V blocks rotate
around the ring via ppermute while each device accumulates its Q-block's
attention with a numerically-stable running softmax (flash-attention style
m/l accumulators).  Compute overlaps the ICI transfer of the next block.

Shapes (per device, inside shard_map): q,k,v: (B, Tlocal, H, D).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from .mesh import get_mesh

__all__ = ["ring_attention", "ring_attention_sharded", "local_attention"]


def _block_attn(q, k, v, bias=None, scale=None):
    """One q-block × kv-block partial attention.

    Returns (unnormalized out, running max m, running denom l)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    # (B, T, H, D) → scores (B, H, Tq, Tk)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # (B,H,Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two partial softmax accumulations (log-sum-exp algebra)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * _bh_to_bqh(a1) + o2 * _bh_to_bqh(a2)
    return o, m, l


def _bh_to_bqh(x):
    # (B,H,Tq) -> (B,Tq,H,1) to scale (B,Tq,H,D)
    return jnp.transpose(x, (0, 2, 1))[..., None]


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Call INSIDE shard_map with q,k,v sequence-sharded on `axis_name`.

    Exact (not approximate) attention over the full sequence; K/V ring-rotate
    `n` steps; per-step compute is a local flash-attention block.
    """

    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]

    def bias_for(step):
        if not causal:
            return None
        # global positions: q-block at rank, kv-block from rank-step (mod n)
        kv_rank = (rank - step) % n
        q_pos = rank * Tq + jnp.arange(Tq)
        k_pos = kv_rank * Tk + jnp.arange(Tk)
        mask = q_pos[:, None] >= k_pos[None, :]
        return jnp.where(mask, 0.0, -1e30)[None, None]

    o, m, l = _block_attn(q, k, v, bias_for(0), scale)

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        # rotate kv one hop around the ring (overlaps with next block compute)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        o2, m2, l2 = _block_attn(q, k_nxt, v_nxt, bias_for(i), scale)
        o, m, l = _merge(o, m, l, o2, m2, l2)
        return (o, m, l, k_nxt, v_nxt)

    if n > 1:
        o, m, l, _, _ = lax.fori_loop(1, n, body, (o, m, l, k, v))
    out = o / _bh_to_bqh(l)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: Optional[Mesh] = None,
                           axis_name: str = "sp", causal: bool = False):
    """Host-level entry: shard q,k,v over the sequence axis and run the ring."""
    mesh = mesh or get_mesh()
    spec = PartitionSpec(None, axis_name, None, None)


    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def local_attention(q, k, v, causal: bool = False, scale=None):
    """Single-device reference attention (oracle for ring tests)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)

"""Differential attention (arXiv:2410.05258) over paged pools, by way of
``ops/paged_attention.py``'s tiles body as it is (docs/generation.md "Cache
kinds").

With ``H`` query heads and ``Hkv`` KV heads of ``d`` lanes, query pair ``i``
``(q[2i], q[2i+1])`` reads KV pair ``p = i // 2``::

    a_i = softmax(q1 k1^T s) V - lam softmax(q2 k2^T s) V
    (k1, k2) = (k[2p], k[2p+1]);  V = v[2p] | v[2p+1]  (2 d wide)
    o_i = (1 - lam0) RMSNorm_2d(a_i; gain)

**One read of K and V.**  A KV pair is ONE head of ``2 d`` lanes of the
pools as they are stored — ``k[2p] | k[2p+1]`` and ``v[2p] | v[2p+1]`` lie
side by side in the folded minor dimension — and its four queries are
padded to it: ``q1 | 0`` scores against ``k1`` alone, ``0 | q2`` against
``k2`` alone, and each softmax weights the whole ``2 d``-wide ``V``.  That is
grouped-query attention of ``H`` heads over ``Hkv / 2`` KV heads of ``2 d``
lanes (128 at ``d`` 64), which the tiles body runs with a window or without
one; the subtraction, the norm and the scale come behind it
(:func:`diff_combine`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pad_queries", "diff_combine", "diff_attention_paged",
           "diff_attention_gathered"]


def pad_queries(q):
    """``q (B, T, H, d)`` as the queries of KV pairs ``2 d`` wide: an even
    head ``q | 0``, an odd head ``0 | q``."""
    B, T, H, d = q.shape
    q = q.reshape(B, T, H // 2, 2, d)
    z = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], z], axis=-1),
                      jnp.concatenate([z, q[..., 1, :]], axis=-1)],
                     axis=-2).reshape(B, T, H, 2 * d)


def diff_combine(a, lam, lam0: float, gain, eps: float):
    """The differential behind the softmaxes: ``a (B, T, H, 2 d)`` float32,
    head ``2i`` the first softmax's sum and ``2i + 1`` the second's; returns
    ``(B, T, H d)``: ``(1 - lam0) rms(a1 - lam a2) gain`` a pair."""
    B, T, H, w = a.shape
    a = a.astype(jnp.float32).reshape(B, T, H // 2, 2, w)
    a = a[..., 0, :] - lam * a[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)
    return (a * (1.0 - lam0)).reshape(B, T, H // 2 * w)


def diff_attention_paged(q, k_pool, v_pool, block_tables, positions, max_pos,
                         scale: float, *, layer: int, call: str,
                         window: int = 0):
    """The two softmaxes' sums of every query pair, through the tiles body:
    ``q (B, T, H, d)``; the pools ``(n_layers, num_blocks, block_size, Hkv
    d)`` as every paged model keeps them; ``block_tables``, ``positions``,
    ``max_pos``, ``layer``, ``call`` and ``window`` as
    :func:`~mxnet_tpu.ops.paged_attention.paged_attention` takes them (a
    window kind's table is a ring).  Returns ``(B, T, H, 2 d)`` float32
    for :func:`diff_combine`."""
    from . import paged_attention as _pa
    from .pallas_kernels import _use_interpret

    B, T, H, d = q.shape
    pairs = k_pool.shape[3] // (2 * d)
    G = H // pairs
    q = pad_queries(q).reshape(B, T, pairs, G, 2 * d).transpose(0, 3, 1, 2, 4)
    out = _pa._tiles_call(
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(max_pos, jnp.int32),
        jnp.full((1,), layer, jnp.int32), q.reshape(B, G * T, pairs * 2 * d),
        jnp.tile(jnp.asarray(positions, jnp.int32), (1, G)), k_pool, v_pool,
        None, n_heads=pairs, scale=float(scale), interpret=_use_interpret(),
        groups=G, call=call, window=int(window))
    return out.reshape(B, G, T, pairs, 2 * d).transpose(0, 2, 3, 1, 4) \
        .reshape(B, T, H, 2 * d)


def diff_attention_gathered(q, k_ctx, v_ctx, mask, scale: float):
    """The same sums over gathered pages (the ``TPUMX_PALLAS=0`` path and
    the kernel's oracle): ``k_ctx``, ``v_ctx`` ``(B, W bs, Hkv d)``, ``mask``
    ``(B, T, W bs)`` the caller's."""
    from .paged_attention import paged_attention_reference

    B, T, H, d = q.shape
    n = k_ctx.shape[1]
    heads = lambda t: t.reshape(B, n, -1, 2 * d)  # noqa: E731
    return paged_attention_reference(
        pad_queries(q), heads(k_ctx), heads(v_ctx), mask, scale)

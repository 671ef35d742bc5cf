"""Operations and bytes of what the ``dots-vlm1`` configuration adds: the
latent attention kernel (decode and prefill) and the grouped products of
the experts a chip holds.  From shapes and from what the program counted
of its own work (``stats()["counts"]``: cache positions read, query-key
pairs, assignments, experts touched), never from a clock (``counts.py``
and ``counts_moe.py`` hold the rest of the yardstick and are not edited).

A multiply-add counts as 2 operations.  Only useful work is counted: the
cached vector's 576 values and not the lanes that pad it, valid queries
and not a chunk's padding — a share of a roofline then cannot pass 100%.
"""
from __future__ import annotations


def latent_read_bytes(ctx_tokens, pool_width, n_layers, bytes_per=2):
    """Bytes of the latent pool a decode step's attention had to read:
    ``ctx_tokens`` cache positions (each fed row's context and its own
    token, summed over the rows of every step), every layer, each
    position's cached vector ONCE for all the heads (it is key and value),
    as wide as the pool stores it."""
    return int(ctx_tokens) * pool_width * bytes_per * n_layers


def latent_flops(pairs, n_heads, latent_width, v_width, n_layers):
    """Operations of absorbed latent attention over ``pairs`` (query
    token, cache position) pairs: every head's score over the cached
    vector's ``latent_width`` values and its weighted sum of the first
    ``v_width``, every layer.  For a decode step a pair is a cache
    position read."""
    return int(pairs) * n_heads * (latent_width + v_width) * 2 * n_layers


def latent_prefill_read_bytes(pairs, tile_tokens, pool_width, n_layers,
                              bytes_per=2):
    """Bytes of the latent pool a prefill chunk's attention had to read:
    a tile of ``tile_tokens`` queries reads its context once, so a
    (query, position) pair costs a ``tile_tokens``-th of a cached vector."""
    return latent_read_bytes(pairs, pool_width, n_layers, bytes_per) \
        // max(1, int(tile_tokens))


def held_expert_bytes(experts_touched, d_model, d_expert, bytes_per=2):
    """Bytes of expert weights the grouped products had to read: the three
    projections of every HELD expert that got at least one assignment,
    counted per layer and per call (``experts_touched`` sums both), each
    read once."""
    return int(experts_touched) * 3 * d_model * d_expert * bytes_per


def held_expert_flops(assignments_held, d_model, d_expert):
    """Operations of the grouped products for the (token, expert)
    assignments that fell to held experts (summed over the layers): 3
    products of ``d_model x d_expert``."""
    return int(assignments_held) * 6 * d_model * d_expert


def decode_ridge(n_heads, latent_width, v_width, pool_width, bytes_per=2):
    """Operations a byte of the decode body: against the chip's
    ``bf16_flops_per_s / hbm_bytes_per_s`` it says which bound binds."""
    return n_heads * (latent_width + v_width) * 2 / (pool_width * bytes_per)

"""Operations and bytes of what the ``sdar-30b-a3b`` configuration adds:
the grouped expert products and the block step's attention.  From shapes
and from what the program counted of its routing, never from a clock
(``counts.py`` holds the rest of the yardstick and is not edited).

A multiply-add counts as 2 operations.
"""
from __future__ import annotations


def expert_bytes(experts_touched, d_model, d_expert, bytes_per=2):
    """Bytes of expert weights the grouped products of a span had to read:
    the three projections (gate, up, down) of every expert that was routed
    at least one token, counted per layer and per call
    (``experts_touched`` sums both), each read once."""
    return int(experts_touched) * 3 * d_model * d_expert * bytes_per


def expert_flops(assignments, d_model, d_expert):
    """Operations of the grouped products for ``assignments`` (token,
    expert) pairs of one layer: 3 products of ``d_model x d_expert``,
    2 operations a multiply-add."""
    return int(assignments) * 6 * d_model * d_expert


def block_kv_bytes(ctx_tokens, n_kv_heads, d_head, n_layers, bytes_per=2):
    """Bytes of K and V the block steps' attention had to read:
    ``ctx_tokens`` cache positions (each fed row's context and its own
    block, summed over the rows of every step), K and V, every layer,
    each KV head's lanes once (never repeated for its query heads)."""
    return int(ctx_tokens) * 2 * n_kv_heads * d_head * bytes_per * n_layers

"""Operations and bytes of what the ``mimo-v2.5`` configuration adds: the
paged kernel's tiles body over the two cache kinds (decode and prefill
calls, ``full`` and ``window``), and the grouped products of the experts a
chip holds (``counts_mla.py``'s, at this model's widths).  From shapes and
from what the program counted of its own work (``stats()["counts"]``: cache
positions the decode calls were asked to read and query-key pairs inside
the chunk calls' masks, a kind), never from a clock.

A multiply-add counts as 2 operations.  Only useful work is counted: a
key head's 192 values and a value head's 128 as the model has them and not
lanes that pad them, positions inside the mask and not the rest of the
pages that hold them, valid queries and not a chunk's padding — a share of
a roofline then cannot pass 100%.
"""
from __future__ import annotations

from perfbench.counts_mla import held_expert_bytes, held_expert_flops  # noqa: F401


def kind_read_bytes(ctx_tokens, kv_heads, d_key, d_value, n_layers,
                    bytes_per=2):
    """Bytes of a kind's K and V pools a decode step's attention had to
    read: ``ctx_tokens`` cache positions (what each fed row's query sees —
    its whole context in the ``full`` kind, the last ``window`` positions
    in the ``window`` kind — summed over the rows of every step), every
    layer of the kind, each position's K and V ONCE for the query heads
    that share a KV head."""
    return int(ctx_tokens) * kv_heads * (d_key + d_value) * bytes_per \
        * n_layers


def kind_flops(pairs, n_heads, d_key, d_value, n_layers):
    """Operations of attention over ``pairs`` (query token, cache
    position) pairs inside the mask: every query head's score over
    ``d_key`` values and its weighted sum of ``d_value``, every layer of
    the kind.  For a decode step a pair is a cache position read."""
    return int(pairs) * n_heads * (d_key + d_value) * 2 * n_layers


def prefill_read_bytes(pairs, tile_tokens, kv_heads, d_key, d_value,
                       n_layers, bytes_per=2):
    """Bytes of a kind's pools a prefill chunk's attention had to read: a
    tile of ``tile_tokens`` queries (the tiles body: 256 grouped rows, all
    of one query head of each KV head) reads its context once, and every
    query head of a KV head reads it again: a pair costs ``groups /
    tile_tokens`` of a position — given as ``tile_tokens`` already divided
    by the groups."""
    return kind_read_bytes(pairs, kv_heads, d_key, d_value, n_layers,
                           bytes_per) // max(1, int(tile_tokens))


def cache_bytes_per_token(blocks_by_kind, bytes_per_block_by_kind,
                          live_tokens):
    """Device bytes of the blocks running rows own, both kinds, over those
    rows' live tokens: 5,120 B a token of the ``full`` kind and a fixed
    ~10 blocks a row of the ``window`` kind at ``mimo-v2.5``'s widths;
    30,720 B if every layer kept every token."""
    if not live_tokens:
        return None
    return sum(n * b for n, b in zip(blocks_by_kind,
                                     bytes_per_block_by_kind)) / live_tokens

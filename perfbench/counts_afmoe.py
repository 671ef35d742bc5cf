"""Operations and bytes of what the ``trinity-mini`` configuration runs: the
paged kernel's tiles body over the two cache kinds (decode and prefill
calls, ``full`` and ``window``; ``counts_hybrid.py``'s functions at this
model's widths — 4 KV heads of 128 lanes for keys and values alike, 32 query
heads, 4 full and 12 window layers) and the grouped products of the experts a
chip holds (``counts_mla.py``'s).  From shapes and from what the program
counted of its own work (``stats()["counts"]``), never from a clock.  The
shared expert, the gate and the norms are plain XLA products and fusions: no
kernel of this configuration's own, so no roofline here.

A multiply-add counts as 2 operations.  Only useful work is counted:
positions inside the mask and not the rest of the pages that hold them (a
window layer's trip computes every position of its 16 pages, of which a row
under the window fills few), valid queries and not a chunk's padding — a
share of a roofline then cannot pass 100%.
"""
from __future__ import annotations

from perfbench.counts_hybrid import (cache_bytes_per_token,  # noqa: F401
                                     kind_flops, kind_read_bytes,
                                     prefill_read_bytes)
from perfbench.counts_mla import held_expert_bytes, held_expert_flops  # noqa: F401


def pool_used_pct(blocks_owned, blocks_held):
    """Blocks of a cache kind that rows own over the blocks the kind holds
    (the null block left out), in percent: what a pool sized for every
    slot's worst case uses under the traffic's lengths."""
    if not blocks_held:
        return None
    return 100.0 * blocks_owned / blocks_held

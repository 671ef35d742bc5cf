"""Operations and bytes of what the ``granite-4.0-h-micro`` configuration
adds: Mamba-2's one-token step and chunked scan over a slot's matrix state,
and grouped-query attention over the paged K and V of the few attention
layers.  By the MODEL's mathematics — a head keeps ``P x N`` state entries,
a layer the convolution's last 3 inputs over ``x | B | C``; a query head
scores 64 values against a key and weights 64 — whatever layout or kernel
the program has, so that a later kernel is read against the same work; and
from what the program counted of its own work (``stats()["counts"]``: rows
fed, tokens and row-chunks of the chunk calls, cache positions the decode
calls were asked to read, query-key pairs inside the chunk calls' masks),
never from a clock (``counts.py`` holds the rest of the yardstick and is
not edited).

A multiply-add counts as 2 operations.  Only useful work is counted: the 3
kept inputs of a layer and not the sublane tile a layout pads them to,
positions inside the mask and not the rest of the pages that hold them,
valid positions and not a chunk's padding — a share of a roofline then
cannot pass 100%.  The scan's operations are the RECURRENCE's, whatever
chunking a kernel computes it in: a chunked kernel's products (the state
read against ``C``, ``B^T`` times the inputs, the masked ``C B^T`` inside
the chunk) are another order of the same sums.
"""
from __future__ import annotations

# K and V read a reading layer (8,192 B a position at 8 heads of 64 over 4
# layers), a prefill tile's share of them, and what running rows own of the
# cache's kinds a live token: the same arithmetic as the other model of a
# state kind beside paged kinds
from perfbench.counts_sambay import (cache_bytes_per_token,  # noqa: F401
                                     kv_read_bytes, prefill_read_bytes)

SSD_OPS = 5     # an entry a position: a . S, (D x) B, +, . C, +


def state_bytes_per_slot(n_heads, d_head, d_state, d_conv, n_layers,
                         bytes_per=4):
    """Bytes of one request's recurrent state: a head's ``d_head x
    d_state`` entries and the convolution's last ``d_conv - 1`` inputs
    (``x | B | C`` wide), every Mamba-2 layer: 77,377,536 at 64 heads of 64
    over 128 entries and 36 layers."""
    di = n_heads * d_head
    return n_layers * (di * d_state + (d_conv - 1) * (di + 2 * d_state)) \
        * bytes_per


def ssd_decode_bytes(rows, n_heads, d_head, d_state, d_conv, n_layers,
                     bytes_per=4):
    """Bytes a decode step's Mamba-2 layers had to move: every fed row's
    state read once and written once, every layer (``rows`` summed over the
    steps)."""
    return 2 * int(rows) * state_bytes_per_slot(
        n_heads, d_head, d_state, d_conv, n_layers, bytes_per)


def ssd_flops(tokens, n_heads, d_head, d_state, n_layers):
    """Operations of the scan over ``tokens`` positions (a decode step's
    rows, or a chunk's valid positions): ``SSD_OPS`` an entry of the
    state."""
    return int(tokens) * n_layers * n_heads * d_head * d_state * SSD_OPS


def scan_prefill_bytes(chunks, tokens, n_heads, d_head, d_state, d_conv,
                       n_layers, bytes_per=4):
    """Bytes the chunked scan had to move: a row's state read once and
    written once a chunk (``chunks``: rows summed over the calls), and a
    position's inputs and outputs a head's lane, its ``B`` and ``C`` and
    its steps."""
    per_token = (2 * n_heads * d_head + 2 * d_state + n_heads) * bytes_per \
        * n_layers
    return ssd_decode_bytes(chunks, n_heads, d_head, d_state, d_conv,
                            n_layers, bytes_per) + int(tokens) * per_token


def attn_flops(pairs, n_heads, d_head, n_layers):
    """Operations of attention over ``pairs`` (query token, cache
    position) pairs inside the mask, an attention layer: every query
    head's score over ``d_head`` values and its weighted sum of as many.
    For a decode step a pair is a cache position read."""
    return int(pairs) * n_heads * 2 * 2 * d_head * n_layers


def decode_step_bytes(rows, ctx_tokens, weight_bytes, kv_heads, d_head,
                      attn_layers, n_heads, d_state_head, d_state, d_conv,
                      ssd_layers):
    """The bytes one decode step has to move, by part: the weights once,
    the K and V the rows' queries see, the states in and out.
    ``ctx_tokens`` is summed over the step's rows.  ``(total, {part:
    bytes})``."""
    parts = {
        "weights": int(weight_bytes),
        "full": kv_read_bytes(ctx_tokens, kv_heads, d_head, attn_layers),
        "state": ssd_decode_bytes(rows, n_heads, d_state_head, d_state,
                                  d_conv, ssd_layers)}
    return sum(parts.values()), parts

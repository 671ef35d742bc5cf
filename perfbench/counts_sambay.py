"""Operations and bytes of what the ``phi-4-mini-flash`` configuration adds:
the state-space layers' one-token step and chunked scan over a slot's state,
and differential attention over the window kind and over the ONE full pool
that eight layers read.  By the MODEL's mathematics — a channel keeps 16
state entries and the convolution's last 3 inputs; a query head scores 64
values against a key and weights a KV pair's 128 — whatever layout or kernel
the program has, so that a later kernel is read against the same work; and
from what the program counted of its own work (``stats()["counts"]``: rows
fed, tokens and row-chunks of the chunk calls, cache positions the decode
calls were asked to read a reader, query-key pairs inside the chunk calls'
masks), never from a clock (``counts.py`` holds the rest of the yardstick
and is not edited).

A multiply-add counts as 2 operations.  Only useful work is counted: the 19
sublanes of a state and not the 24 a layout pads them to, positions inside
the mask and not the rest of the pages that hold them, valid positions and
not a chunk's padding, queries that ran and not the zeros they are padded
with — a share of a roofline then cannot pass 100%.
"""
from __future__ import annotations

SCAN_OPS = 7    # an entry a position: D A, exp, . s, (D u) B, +, . C, +


def state_bytes_per_slot(d_inner, d_state, d_conv, n_layers, bytes_per=4):
    """Bytes of one request's recurrent state: a channel's ``d_state``
    entries and its convolution's last ``d_conv - 1`` inputs, every
    state-space layer: 3,502,080 at 5120 channels over 9 layers."""
    return n_layers * (d_state + d_conv - 1) * d_inner * bytes_per


def ssm_decode_bytes(rows, d_inner, d_state, d_conv, n_layers, bytes_per=4):
    """Bytes a decode step's state-space layers had to move: every fed
    row's state read once and written once, every layer (``rows`` summed
    over the steps)."""
    return 2 * int(rows) * state_bytes_per_slot(d_inner, d_state, d_conv,
                                                n_layers, bytes_per)


def ssm_flops(tokens, d_inner, d_state, n_layers):
    """Operations of the scan over ``tokens`` positions (a decode step's
    rows, or a chunk's valid positions): ``SCAN_OPS`` an entry of the
    state."""
    return int(tokens) * n_layers * d_inner * d_state * SCAN_OPS


def scan_prefill_bytes(chunks, tokens, d_inner, d_state, d_conv, n_layers,
                       bytes_per=4):
    """Bytes the chunked scan had to move: a row's state read once and
    written once a chunk (``chunks``: rows summed over the calls), and a
    position's step, input and output a channel and its ``B`` and ``C``."""
    per_token = (3 * d_inner + 2 * d_state) * bytes_per * n_layers
    return ssm_decode_bytes(chunks, d_inner, d_state, d_conv, n_layers,
                            bytes_per) + int(tokens) * per_token


def kv_read_bytes(ctx_tokens, kv_heads, d_head, readers, bytes_per=2):
    """Bytes of a kind's K and V pools that decode steps' attention had to
    read: ``ctx_tokens`` cache positions a reading layer (what each fed
    row's query sees, summed over the rows of every step), ``readers``
    layers — each reads every position's K and V once for the queries that
    share them: 5,120 B a position a reader at 20 heads of 64."""
    return int(ctx_tokens) * kv_heads * 2 * d_head * bytes_per * readers


def diff_attn_flops(pairs, n_heads, d_head, readers):
    """Operations of differential attention over ``pairs`` (query token,
    cache position) pairs inside the mask, a reading layer: every query
    head's score over ``d_head`` values and its weighted sum of a KV pair's
    ``2 d_head``.  For a decode step a pair is a cache position read."""
    return int(pairs) * n_heads * 2 * (d_head + 2 * d_head) * readers


def prefill_read_bytes(pairs, tile_tokens, kv_heads, d_head, readers,
                       bytes_per=2):
    """Bytes of a kind's pools a prefill chunk's attention had to read: a
    tile of ``tile_tokens`` queries reads its context once (``tile_tokens``
    already divided by the query heads that share a KV pair and ride the
    tile's rows)."""
    return kv_read_bytes(pairs, kv_heads, d_head, readers, bytes_per) \
        // max(1, int(tile_tokens))


def cache_bytes_per_token(units_by_kind, bytes_per_unit_by_kind, live_tokens):
    """Device bytes of what running rows own of every cache kind (blocks
    of the paged kinds, slots of the state kind) over those rows' live
    tokens."""
    if not live_tokens:
        return None
    return sum(n * b for n, b in zip(units_by_kind,
                                     bytes_per_unit_by_kind)) / live_tokens


def decode_step_bytes(rows, ctx_tokens, window_tokens, weight_bytes, kv_heads,
                      d_head, full_readers, window_layers, d_inner, d_state,
                      d_conv, ssm_layers):
    """The bytes one decode step has to move, by kind: the weights once,
    the shared full pool a reader, the windows, the states in and out.
    ``ctx_tokens`` and ``window_tokens`` are summed over the step's rows.
    ``(total, {part: bytes})``."""
    parts = {
        "weights": int(weight_bytes),
        "full": kv_read_bytes(ctx_tokens, kv_heads, d_head, full_readers),
        "window": kv_read_bytes(window_tokens, kv_heads, d_head,
                                window_layers),
        "state": ssm_decode_bytes(rows, d_inner, d_state, d_conv, ssm_layers)}
    return sum(parts.values()), parts

"""Operations and bytes of what the ``brumby-14b`` configuration adds: the
power-retention layer's one-token step over a slot's recurrent state and
its chunked scan.  By the MODEL's mathematics — the symmetric square of a
128-wide head has 8,256 entries; 8 KV heads keep a state, 40 query heads
read it — whatever layout or kernel the program has, so that a later kernel
is read against the same work; and from what the program counted of its
own work (``stats()["counts"]``: rows fed, tokens, row-chunks and causal
pairs of the chunk calls), never from a clock (``counts.py`` holds the rest
of the yardstick and is not edited).

A multiply-add counts as 2 operations.  Only useful work is counted: the
8,256 entries and not the lanes a layout pads them to, valid positions and
not a chunk's padding, float32 state bytes once in and once out — a share
of a roofline then cannot pass 100%.
"""
from __future__ import annotations


def phi_entries(d_head):
    """Distinct entries of the symmetric square of a ``d_head``-wide
    vector: 8,256 at 128."""
    return d_head * (d_head + 1) // 2


def state_bytes_per_slot(n_kv_heads, d_head, d_value, n_layers, bytes_per=4):
    """Bytes of one request's recurrent state: a KV head's ``S`` (entries x
    ``d_value``) and its normaliser ``z`` (entries), every layer: 204.5 MB
    at 8 heads of 128 over 6 layers in float32."""
    return n_layers * n_kv_heads * phi_entries(d_head) * (d_value + 1) \
        * bytes_per


def decode_state_bytes(rows, n_kv_heads, d_head, d_value, n_layers,
                       bytes_per=4):
    """Bytes a decode step's retention had to move: every fed row's state
    read once and written once, every layer (``rows`` summed over the
    steps)."""
    return 2 * int(rows) * state_bytes_per_slot(n_kv_heads, d_head, d_value,
                                                n_layers, bytes_per)


def decode_flops(rows, n_heads, n_kv_heads, d_head, d_value, n_layers):
    """Operations of the one-token step: a KV head decays its state and
    adds ``phi(k) v^T`` (a multiply and a multiply-add an entry of ``S``
    and of ``z``), and each of its query heads reads it (a multiply-add an
    entry of ``S`` and of ``z``)."""
    per = phi_entries(d_head) * (d_value + 1)
    return int(rows) * n_layers * (3 * n_kv_heads + 2 * n_heads) * per


def scan_flops(tokens, pairs, n_heads, n_kv_heads, d_head, d_value,
               n_layers):
    """Operations of the chunked scan over ``tokens`` valid positions with
    ``pairs`` causal (query, key) pairs inside their chunks: every query
    head reads the carried state (a multiply-add an entry), every KV head
    adds the position to it (a multiply-add an entry), and inside the
    chunk a pair costs a score over ``d_head``, its square and a weighted
    sum of ``d_value`` and of 1."""
    per = phi_entries(d_head) * (d_value + 1)
    carried = int(tokens) * (n_heads + n_kv_heads) * 2 * per
    inside = int(pairs) * n_heads * (2 * d_head + 1 + 2 * (d_value + 1))
    return n_layers * (carried + inside)


def scan_state_bytes(chunks, n_kv_heads, d_head, d_value, n_layers,
                     bytes_per=4):
    """Bytes the scan had to move: a row's state read once and written
    once a chunk, every layer (``chunks``: rows summed over the calls)."""
    return decode_state_bytes(chunks, n_kv_heads, d_head, d_value, n_layers,
                              bytes_per)


def decode_ridge(n_heads, n_kv_heads, d_value, bytes_per=4):
    """Operations a byte of the one-token step (its entries cancel):
    against the chip's ``bf16_flops_per_s / hbm_bytes_per_s`` it says which
    bound binds — 1.6 at 40 heads over 8, far under a v5e's 240."""
    return (3 * n_kv_heads + 2 * n_heads) / (2 * n_kv_heads * bytes_per)

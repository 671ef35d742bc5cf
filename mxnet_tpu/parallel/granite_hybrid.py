"""Granite 4.0-H: Mamba-2 layers beside a few no-position grouped-query
attention layers, a gated feed-forward layer in every layer, and the four
muP multipliers (``model_type`` ``granitemoehybrid`` with no routed expert,
as ``granite-4.0-h-micro``'s ``config.json`` carries its keys;
arXiv:2405.21060 for the mixer; docs/generation.md "Cache kinds").

The layers, with ``x`` the residual stream and ``rms`` the RMS norm with a
gain::

    x_0 = embedding_multiplier Emb[token]
    x <- x + residual_multiplier Mixer_l(rms(x; g_in))
    x <- x + residual_multiplier W_down (silu(a) * b),  [a | b] = W_up rms(x; g_post)
    logits = (rms(x; g_f) Emb^T) / logits_scaling          (tied head)

    Mixer_l = attention (``layer_types[l] == "attention"``: 5, 15, 25, 35):
        grouped queries over K and V of every position, NO position term,
        scores times attention_multiplier (not 1/sqrt(head size)), no bias
    Mixer_l = Mamba-2 (every other layer):
        [z | xBC | dt] = h W_in;  xBC' = silu(conv_K(xBC) + b_c)   (x, B and C convolved TOGETHER)
        [x | B | C] = xBC';  D_t[m] = softplus(dt_t[m] + dt_bias[m])
        the scan of ``ops/ssd.py`` (a head's state a P x N matrix, a scalar decay a head)
        y_t[m] = S_t[m] C_t + Dskip[m] x_t[m]
        out = rms(y_t * silu(z_t); g_n) W_out       (the gate first, ONE norm over all lanes)

The cache has TWO kinds (``cache_spec()["kinds"]``): ``full`` — the
attention layers' K and V, every position, paged — and ``state`` — the
Mamba-2 layers' matrix states over their convolutions' last inputs, one
pool, a slot a row (``serving/generation/kv_cache.py::CacheKind``).  The
state kind is the LARGER: 80 MB a slot at the published widths beside 8 KB
a token of K and V, so slots, not blocks, are what admission runs out of.

Every layer writes a cache or a state at every position, so a chunk runs
every layer; the HEAD runs on a prompt's last position alone
(``fills_without_head``: a chunk that is not a prompt's last goes through
the fill program, ``want_logits=False``), since a chunk's logits at
100,352 ids are 0.4 MB a position.

Parameters are a flat dict in ONE dtype and are never cast in the program:
products take operands in that dtype and accumulate in float32; the
residual stream, norms, softmax, softplus, the decay, the gate and its
norm, the state, the convolution's kept inputs and the scan's sums are
float32; the paged pools have their own dtype (bfloat16 on the chip).  The
multipliers are in the program, not folded into the weights.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .sdar_moe import _mm, _mm_as_stored, _rms
from .transformer import paged_write_coords

Params = Dict[str, jnp.ndarray]

__all__ = ["GraniteHybridConfig", "GraniteHybridLM", "granite_hybrid_decode",
           "granite_hybrid_param_shapes"]

COUNTERS = ("ssd_decode_rows", "ssd_prefill_tokens", "ssd_prefill_chunks",
            "ssd_rows_started", "full_ctx_tokens", "full_prefill_pairs")


@dataclass(frozen=True)
class GraniteHybridConfig:
    """The published ``config.json`` keys that shape the model (defaults:
    ``granite-4.0-h-micro``)."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    layer_types: Tuple[str, ...] = ()       # (): attention at 5 of every 10

    def __post_init__(self):
        assert self.hidden_size % self.num_attention_heads == 0
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.d_inner == self.mamba_expand * self.hidden_size
        # ONE B, C pair for all heads is what the scan's kernels take
        assert self.mamba_n_groups == 1
        assert set(self.kinds) <= {"mamba", "attention"}, self.kinds
        assert len(self.kinds) == self.num_hidden_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def d_conv_in(self) -> int:
        """What the convolution runs over: ``x | B | C``."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Which mixer each layer has."""
        if self.layer_types:
            return tuple(self.layer_types)
        return tuple("attention" if i % 10 == 5 else "mamba"
                     for i in range(self.num_hidden_layers))

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers with this mixer, in order: a layer's place among
        them is its layer in its cache kind's pools."""
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)


def granite_hybrid_param_shapes(cfg: GraniteHybridConfig
                                ) -> Dict[str, Tuple[int, ...]]:
    d, H, hkv, dh, F = (cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim,
                        cfg.shared_intermediate_size)
    di, Hm, K, w = (cfg.d_inner, cfg.mamba_n_heads, cfg.mamba_d_conv,
                    cfg.d_conv_in)
    s = {"tok_emb": (cfg.vocab_size, d), "g_f": (d,)}
    mixers = {
        "mamba": {"w_in": (d, di + w + Hm), "conv_w": (K, w), "conv_b": (w,),
                  "dt_bias": (Hm,), "A_log": (Hm,), "D_skip": (Hm,),
                  "g_n": (di,), "w_out": (di, d)},
        "attention": {"wq": (d, H * dh), "wk": (d, hkv * dh),
                      "wv": (d, hkv * dh), "wo": (H * dh, d)}}
    for i, kind in enumerate(cfg.kinds):
        layer = {"g_in": (d,), "g_post": (d,), "w_up": (d, 2 * F),
                 "w_down": (F, d), **mixers[kind]}
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def _f32(a):
    return a.astype(jnp.float32)


def granite_hybrid_decode(params: Params, tokens, positions, lengths, pools,
                          block_tables, cfg: GraniteHybridConfig, *,
                          kernel: bool, max_len: int,
                          want_logits: bool = True):
    """Cache-aware forward over the two kinds: ``pools`` is ``(k_full,
    v_full, ssd)`` and ``block_tables`` ``(full (B, W), state (B, 1) — the
    slot, 0 for an idle row)``.  Arguments otherwise as
    ``transformer_lm_decode``.

    ``want_logits=False`` is a chunk that is not a prompt's last: every
    layer, no head.  A chunk of more than one position runs the head at
    each row's LAST valid position only.  Returns ``(logits (B, 1, vocab)
    float32 or None, pools, aux)``; ``aux`` is the dict of this call's
    counts (``COUNTERS``; docs/observability.md)."""
    from ..ops import paged_attention as _pa
    from ..ops.ssd import conv_state, ssd

    B, T = tokens.shape
    H, hkv, dh, eps = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim, cfg.rms_norm_eps)
    di, N, K, Hm, P = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                       cfg.mamba_n_heads, cfg.mamba_d_head)
    F, res = cfg.shared_intermediate_size, cfg.residual_multiplier
    k_full, v_full, ssd_pool = pools
    t_full, t_state = (jnp.asarray(t, jnp.int32) for t in block_tables)
    bs = k_full.shape[2]
    positions, valid, phys, offs = paged_write_coords(
        positions, lengths, t_full, bs, max_len)
    lengths = jnp.asarray(lengths, jnp.int32)
    live = lengths > 0
    slots = jnp.where(live, t_state[:, 0], 0)
    # a chunk that starts at position 0 starts from the zero state
    fresh = live & (positions[:, 0] == 0)
    max_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)
    phase = "decode" if T == 1 else "prefill"
    if not kernel:
        pos_f = jnp.arange(t_full.shape[1] * bs, dtype=jnp.int32)[None, None]
        mask = pos_f <= positions[:, :, None]
    # the program's own counts (docs/observability.md): valid queries only
    n_valid = jnp.sum(lengths)
    seen = jnp.sum(jnp.where(valid, positions + 1, 0))
    aux = dict.fromkeys(COUNTERS, jnp.zeros((), jnp.int32))
    aux["ssd_rows_started"] = jnp.sum(fresh)
    if T == 1:
        aux.update(ssd_decode_rows=n_valid, full_ctx_tokens=seen)
    else:
        aux.update(ssd_prefill_tokens=n_valid, full_prefill_pairs=seen,
                   ssd_prefill_chunks=jnp.sum(live))
    aux = {k: jnp.asarray(v, jnp.int32) for k, v in aux.items()}

    scope = jax.named_scope     # docs/observability.md "Device scopes"
    at_kind = {"mamba": 0, "attention": 0}  # the next layer of a kind's pools
    with scope("embed"):
        x = _f32(params["tok_emb"][tokens]) * cfg.embedding_multiplier
    for i, kind in enumerate(cfg.kinds):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        li = at_kind[kind]
        at_kind[kind] += 1
        with scope(f"layer{i}"):
            with scope("norm"):
                h = _rms(x, g("g_in"), eps)
            if kind == "mamba":
                with scope("mixer.mamba2"):
                    with scope("in_proj"):
                        zxd = _mm(h, g("w_in"))
                        z, xbc = zxd[..., :di], zxd[..., di:2 * di + 2 * N]
                        step = jax.nn.softplus(zxd[..., 2 * di + 2 * N:]
                                               + _f32(g("dt_bias")))
                    with scope("conv"):
                        old = jnp.where(
                            fresh[:, None, None], 0.0, conv_state(
                                ssd_pool, li, slots, K, 2 * N, kernel=kernel,
                                call=phase))
                        padded = jnp.concatenate([old, xbc], axis=1)
                        w = _f32(g("conv_w"))
                        xbc = jax.nn.silu(sum(
                            w[k] * padded[:, k:k + T] for k in range(K))
                            + _f32(g("conv_b")))
                        # the K - 1 inputs behind the last valid one (an
                        # idle row keeps what its slot, the scratch, held)
                        keep = lengths[:, None] + jnp.arange(K - 1)
                        kept = jnp.take_along_axis(padded, keep[:, :, None],
                                                   axis=1)
                    # a padded position and an idle row are identities,
                    # whatever their inputs hold
                    step = jnp.where(valid[:, :, None], step, 0.0)
                    xs = jnp.where(valid[:, :, None], xbc[..., :di], 0.0)
                    with scope("scan"):
                        y, ssd_pool = ssd(
                            step, xs, xbc[..., di:di + N], xbc[..., di + N:],
                            -jnp.exp(_f32(g("A_log"))), fresh, ssd_pool,
                            slots, kept, layer=li, kernel=kernel)
                    with scope("gate_norm"):
                        y = y + jnp.repeat(_f32(g("D_skip")), P) * xs
                        y = _rms(y * jax.nn.silu(z), g("g_n"), eps)
                    with scope("out_proj"):
                        out = _mm(y, g("w_out"))
            else:
                with scope("mixer.attn"):
                    with scope("proj"):
                        q = _mm_as_stored(h, g("wq")).reshape(B, T, H, dh)
                        k = _mm_as_stored(h, g("wk"))
                        v = _mm_as_stored(h, g("wv"))
                    with scope("cache_write"):
                        k_full = k_full.at[li, phys, offs].set(
                            k.astype(k_full.dtype))
                        v_full = v_full.at[li, phys, offs].set(
                            v.astype(v_full.dtype))
                    with scope("kernel"):
                        if kernel:
                            a = _pa.paged_attention(
                                q, k_full, v_full, t_full, positions, max_pos,
                                scale=cfg.attention_multiplier, layer=li,
                                call=f"full_{phase}", tiles=True)
                            # a query past its row's last valid position
                            # read nothing: nothing of the null block's pages
                            a = jnp.where(valid[:, :, None, None], a, 0.0)
                        else:
                            pages = [pool[li][t_full].reshape(B, -1, hkv, dh)
                                     for pool in (k_full, v_full)]
                            a = _pa.paged_attention_reference(
                                q, *pages, mask, cfg.attention_multiplier)
                    with scope("proj"):
                        out = _mm(a.reshape(B, T, H * dh), g("wo"))
            x = x + res * out
            with scope("norm"):
                h = _rms(x, g("g_post"), eps)
            with scope("ffn"):
                ab = _mm(h, g("w_up"))
                x = x + res * _mm(jax.nn.silu(ab[..., :F]) * ab[..., F:],
                                  g("w_down"))
    pools = (k_full, v_full, ssd_pool)
    if not want_logits:
        return None, pools, aux
    with scope("head"):
        if T > 1:   # a prompt's last position alone
            last = jnp.clip(lengths - 1, 0, T - 1)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        xn = _rms(x, params["g_f"], eps)
        emb = params["tok_emb"]
        logits = jax.lax.dot_general(
            xn.astype(emb.dtype), emb, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / cfg.logits_scaling
    return logits, pools, aux


@dataclass(frozen=True)
class GraniteHybridLM:
    """The model as the generation engine takes one (the seam of
    ``serving/generation/programs.py``): one token a row a step
    (``block_len`` 0, so it rides the step in flight), a cache of two kinds
    (``cache_spec``), no head on a chunk that is not a prompt's last
    (``fills_without_head``), and the counts its program hands back
    (``counters``).  ``max_len`` is the service's longest position."""
    cfg: GraniteHybridConfig
    max_len: int
    kv_dtype: object = jnp.bfloat16
    # the longest chunk a prefill program takes: its temporaries (the
    # in-projection's 8,512 lanes, the scan's operands, the feed-forward's
    # 16,384) grow with the chunk: ~0.5 GB at 1,024 positions
    longest_chunk: int = 1024
    block_len = 0
    offers = frozenset({"sampling"})
    counters = COUNTERS
    fills_without_head = True
    # the tiles body fetches the pages a tile reads and no others: a
    # table's width costs nothing, so the service keeps one
    one_table_width = True

    @property
    def vocab(self) -> int:
        return self.cfg.vocab_size

    @property
    def heads(self) -> int:
        return self.cfg.num_attention_heads

    def cache_spec(self) -> dict:
        """Two kinds: ``full`` keeps every position of the attention
        layers' K and V and is sized by tokens; ``state`` is a slot's
        Mamba-2 states and convolution inputs, float32, sized by rows —
        and the larger of the two."""
        from ..ops.ssd import state_shapes

        c = self.cfg
        kv = (("k", c.num_key_value_heads * c.head_dim),
              ("v", c.num_key_value_heads * c.head_dim))
        attn, mamba = c.layers_of("attention"), c.layers_of("mamba")
        return dict(dtype=self.kv_dtype, kinds=(
            dict(name="full", n_layers=len(attn), pools=kv, writers=attn,
                 readers=attn),
            dict(name="state", n_layers=len(mamba), dtype=jnp.float32,
                 state=state_shapes(c.mamba_n_heads, c.mamba_d_head,
                                    c.mamba_d_state, c.mamba_d_conv),
                 writers=mamba, readers=mamba)))

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        the two kinds' pools, one kind after the other, ``block_tables`` a
        table a kind; returns ``(logits, pools, aux)``.  No mesh is
        offered, so ``mp_mesh`` is always None."""
        return granite_hybrid_decode(
            params, tokens, positions, lengths, pools, block_tables,
            self.cfg, kernel=attention_kernel == "paged",
            max_len=self.max_len, want_logits=want_logits)

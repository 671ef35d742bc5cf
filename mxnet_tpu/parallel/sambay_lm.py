"""SambaY with differential attention: a decoder-hybrid-decoder of Mamba,
window attention, ONE full attention layer and a cross-decoder of gated
memory units and cross attention over that layer's K and V (``model_type``
``phi4flash``, as ``Phi-4-mini-flash-reasoning``'s ``config.json`` carries
its keys; arXiv:2507.06607; docs/generation.md "Cache kinds").

The layers, with ``x`` the residual stream (``2h`` layers: the defaults'
``h`` is 16)::

    x <- x + Mixer_l(LN(x));  x <- x + W_down(silu(g) * u), [g | u] = W_gu LN'(x)
    logits = LN_f(x) E^T          (LayerNorm with gain and bias; E the tied embedding)

    Mixer_l = Mamba (l even, l <= h; l = h hands on its memory M)
              DiffAttn over a window (l odd, l < h; each writes its own K, V)
              DiffAttn, full (l = h + 1: writes THE full K, V)
              Cross (l odd, l > h + 1: W_q and W_o only; K, V are layer h + 1's)
              GMU (l even, l > h): out = W_o (M_t * silu(W_i h_t))

    Mamba: [u | z] = W_in h;  u' = silu(conv_K(u) + b_c);  [r | B | C] = W_x u'
           D_t = softplus(W_dt r + b_dt);  the scan of ``ops/selective_scan.py``
           y_t = s_t . C_t + Dskip u'_t;  out = W_out (y_t * silu(z_t));  M_t = y_t
    DiffAttn: ``ops/diff_attention.py``; lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)

No rotary and no other positional term: the state-space layers carry
position.

The cache has THREE kinds (``cache_spec()["kinds"]``): ``full`` — ONE
layer's K and V, every position, written by layer ``h + 1`` and read by it
and by every cross layer (one pool row, many readers) —, ``window`` — the
window layers', a ring a row —, and ``state`` — the Mamba layers' scan
state over their convolutions' last inputs, one pool, a slot a row
(``serving/generation/kv_cache.py::CacheKind``).

**The prefill skip.**  The cross-decoder writes no cache and no state, so
of a prompt it need run at the LAST position alone: a chunk that is not a
prompt's last runs the self-decoder and writes layer ``h + 1``'s K and V
(``want_logits=False``: no attention there, no head); a prompt's last chunk
runs the self-decoder over its positions, writes K and V, and from layer
``h + 1``'s queries on runs ONE position a row, and the head on it.  Exact,
not an approximation.  ``prefill_skip=False`` runs every layer at every
position (the reference's way; tests compare the two).

Parameters are a flat dict in ONE dtype and are never cast in the program:
products take operands in that dtype and accumulate in float32; the
residual stream, LayerNorms, softmax, the subtraction and its norm,
softplus, the decay, the state and the scan's sums are float32; the paged
pools have their own dtype (bfloat16 on the chip).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .sdar_moe import _mm, _mm_as_stored
from .transformer import paged_write_coords

Params = Dict[str, jnp.ndarray]

__all__ = ["SambaYConfig", "SambaYLM", "sambay_lm_decode",
           "sambay_param_shapes"]

COUNTERS = ("ssm_decode_rows", "ssm_prefill_tokens", "ssm_prefill_chunks",
            "ssm_rows_started", "window_ctx_tokens", "full_ctx_tokens",
            "window_prefill_pairs", "full_prefill_pairs",
            "cross_positions_run", "cross_positions_skipped",
            "window_decode_trips")


@dataclass(frozen=True)
class SambaYConfig:
    """The published ``config.json`` keys that shape the model (defaults:
    ``Phi-4-mini-flash-reasoning``), and what the config does not carry:
    Mamba-1's sizes and, for a model that is not the published layout
    scaled, which mixer each layer has."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                        # 0: ceil(hidden_size / 16)
    layer_kinds: Tuple[str, ...] = ()       # (): the SambaY layout

    def __post_init__(self):
        assert self.hidden_size % self.num_attention_heads == 0
        # a KV pair is one head of the pools, its four queries one group
        assert self.num_key_value_heads % 2 == 0
        assert self.num_attention_heads == 2 * self.num_key_value_heads
        kinds = self.kinds
        assert kinds.count("full") == 1 and "ssm" in kinds
        f = kinds.index("full")
        assert all(k in ("ssm", "swa") for k in kinds[:f]) \
            and all(k in ("gmu", "cross") for k in kinds[f + 1:]), kinds
        assert kinds[f - 1] == "ssm"        # the memory a GMU reads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def rank(self) -> int:
        return self.dt_rank or -(-self.hidden_size // 16)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Which mixer each layer has."""
        if self.layer_kinds:
            return tuple(self.layer_kinds)
        n = self.num_hidden_layers
        h = n // 2
        assert n % 2 == 0 and h % 2 == 0, n
        return tuple(
            ("ssm" if i % 2 == 0 else "swa") if i < h else
            "ssm" if i == h else "full" if i == h + 1 else
            "gmu" if i % 2 == 0 else "cross" for i in range(n))

    def layers_of(self, *kinds: str) -> Tuple[int, ...]:
        """The layers with one of these mixers, in order: a layer's place
        among its cache kind's WRITERS is its layer in that kind's pools."""
        return tuple(i for i, k in enumerate(self.kinds) if k in kinds)

    def lam0(self, layer: int) -> float:
        """The differential's constant part, by the layer's depth."""
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


def sambay_param_shapes(cfg: SambaYConfig) -> Dict[str, Tuple[int, ...]]:
    d, H, hkv, dh, F = (cfg.hidden_size, cfg.num_attention_heads,
                        cfg.num_key_value_heads, cfg.head_dim,
                        cfg.intermediate_size)
    di, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.rank
    s = {"tok_emb": (cfg.vocab_size, d), "lnf_g": (d,), "lnf_b": (d,)}
    attn_q = {"wq": (d, H * dh), "bq": (H * dh,), "wo": (H * dh, d),
              "bo": (d,), "lam": (4, dh), "sub_g": (2 * dh,)}
    attn_kv = {"wk": (d, hkv * dh), "bk": (hkv * dh,), "wv": (d, hkv * dh),
               "bv": (hkv * dh,)}
    mixers = {
        "ssm": {"w_in": (d, 2 * di), "conv_w": (K, di), "conv_b": (di,),
                "w_x": (di, R + 2 * N), "w_dt": (R, di), "b_dt": (di,),
                "A_log": (N, di), "D_skip": (di,), "w_out": (di, d)},
        "swa": {**attn_q, **attn_kv}, "full": {**attn_q, **attn_kv},
        "cross": attn_q, "gmu": {"w_i": (d, di), "w_o": (di, d)}}
    for i, kind in enumerate(cfg.kinds):
        layer = {"ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,),
                 "wgu": (d, 2 * F), "wd": (F, d), **mixers[kind]}
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _f32(a):
    return a.astype(jnp.float32)


def _at_last(a, last):
    """``a (B, T, ...)`` at each row's position ``last (B,)``: ``(B, 1,
    ...)``."""
    idx = last.reshape((-1, 1) + (1,) * (a.ndim - 2))
    return jnp.take_along_axis(a, idx, axis=1)


def sambay_lm_decode(params: Params, tokens, positions, lengths, pools,
                     block_tables, cfg: SambaYConfig, *, kernel: bool,
                     max_len: int, want_logits: bool = True,
                     prefill_skip: bool = True):
    """Cache-aware forward over the three kinds: ``pools`` is ``(k_full,
    v_full, k_window, v_window, ssm)`` and ``block_tables`` ``(full
    (B, W), window (B, Wr) — a ring —, state (B, 1) — the slot, 0 for an
    idle row)``.  Arguments otherwise as ``transformer_lm_decode``.

    ``want_logits=False`` is a chunk that is not a prompt's last: the
    self-decoder and the full layer's K and V, nothing behind them.  A
    chunk of more than one position with ``prefill_skip`` runs the layers
    from the full layer's queries on at each row's LAST valid position
    only.  Returns ``(logits (B, T or 1, vocab) float32 or None, pools,
    aux)``; ``aux`` is the dict of this call's counts (``COUNTERS``;
    docs/observability.md)."""
    from ..ops import diff_attention as _da
    from ..ops.paged_attention import tiles_decode_trips
    from ..ops.selective_scan import conv_state, selective_scan

    B, T = tokens.shape
    H, hkv, dh, eps = (cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim, cfg.layer_norm_eps)
    di, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.rank
    win = cfg.sliding_window
    k_full, v_full, k_win, v_win, ssm_pool = pools
    t_full, t_win, t_state = (jnp.asarray(t, jnp.int32) for t in block_tables)
    bs = k_full.shape[2]
    positions, valid, phys_f, offs = paged_write_coords(
        positions, lengths, t_full, bs, max_len)
    lengths = jnp.asarray(lengths, jnp.int32)
    ring = t_win.shape[1]
    phys_w = jnp.where(valid, jnp.take_along_axis(
        t_win, (positions // bs) % ring, axis=1), 0)
    live = lengths > 0
    slots = jnp.where(live, t_state[:, 0], 0)
    # a chunk that starts at position 0 starts from the zero state
    fresh = live & (positions[:, 0] == 0)
    last = jnp.clip(lengths - 1, 0, T - 1)
    skip = prefill_skip and T > 1
    scale = float(dh) ** -0.5
    phase = "decode" if T == 1 else "prefill"
    if not kernel:
        from .hybrid_moe import _ring_positions

        pos_f = jnp.arange(t_full.shape[1] * bs, dtype=jnp.int32)[None, None]
        pos_w = _ring_positions(
            jnp.maximum(positions[:, 0] - (win - 1), 0) // bs, ring, bs
        )[:, None, :]
    # the program's own counts (docs/observability.md): valid queries only
    n_valid = jnp.sum(lengths)
    n_live = jnp.sum(live).astype(jnp.int32)
    seen = jnp.where(valid, positions + 1, 0)
    run = (n_live if skip else n_valid) if want_logits else 0
    aux = dict.fromkeys(COUNTERS, jnp.zeros((), jnp.int32))
    aux["ssm_rows_started"] = jnp.sum(fresh).astype(jnp.int32)
    max_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)
    if T == 1:
        aux.update(ssm_decode_rows=n_valid, full_ctx_tokens=jnp.sum(seen),
                   window_ctx_tokens=jnp.sum(jnp.minimum(seen, win)))
        if kernel:
            # the tiles body's trips over the window layers' calls (a KV
            # pair is one head to it): one a live row a layer is a trip as
            # long as a window's reach
            aux["window_decode_trips"] = cfg.kinds.count("swa") \
                * tiles_decode_trips(positions, max_pos, k_win, v_win, ring,
                                     groups=2 * H // hkv, window=win)
    else:
        aux.update(
            ssm_prefill_tokens=n_valid, ssm_prefill_chunks=n_live,
            window_prefill_pairs=jnp.sum(jnp.minimum(seen, win)),
            full_prefill_pairs=(
                0 if not want_logits else jnp.sum(seen) if not skip
                else jnp.sum(jnp.where(live, _at_last(seen, last)[:, 0], 0))),
            cross_positions_run=run, cross_positions_skipped=n_valid - run)
    aux = {k: jnp.asarray(v, jnp.int32) for k, v in aux.items()}

    def attend(q, k_pool, v_pool, table, at, upto, *, li, window, call):
        """Both softmaxes' sums of the queries ``q (B, t, H, dh)`` at
        positions ``at`` over a kind's pools."""
        if kernel:
            a = _da.diff_attention_paged(
                q, k_pool, v_pool, table, at, upto, scale, layer=li,
                call=f"{call}_{phase}", window=window)
            # a query past its row's last valid position read nothing, and
            # nothing over nothing must not reach the null block's pages
            return jnp.where(asked[:, :, None, None], a, 0.0)
        pos = pos_w if window else pos_f
        mask = pos <= at[:, :, None]
        if window:
            mask &= pos > at[:, :, None] - window
        pages = [pool[li][table].reshape(B, -1, hkv * dh)
                 for pool in (k_pool, v_pool)]
        return _da.diff_attention_gathered(q, *pages, mask, scale)

    scope = jax.named_scope     # docs/observability.md "Device scopes"
    at_kind = {"ssm": 0, "swa": 0}      # the next layer of a kind's pools
    memory, asked = None, valid         # asked: the queries that count
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)      # (B, T, d)
    for i, kind in enumerate(cfg.kinds):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        with scope(f"layer{i}"):
            with scope("norm"):
                h = _ln(x, g("ln1_g"), g("ln1_b"), eps)
            with scope(f"mixer.{kind}"):
                if kind == "ssm":
                    li = at_kind["ssm"]
                    at_kind["ssm"] += 1
                    uz = _mm(h, g("w_in"))
                    u, z = uz[..., :di], uz[..., di:]
                    with scope("conv"):
                        old = jnp.where(fresh[:, None, None], 0.0,
                                        conv_state(ssm_pool, li, slots, K,
                                                   kernel=kernel, call=phase))
                        padded = jnp.concatenate([old, u], axis=1)
                        w = _f32(g("conv_w"))
                        u1 = jax.nn.silu(sum(
                            w[k] * padded[:, k:k + T] for k in range(K))
                            + _f32(g("conv_b")))
                        # the K - 1 inputs behind the last valid one (an
                        # idle row keeps what its slot, the scratch, held)
                        keep = lengths[:, None] + jnp.arange(K - 1)
                        kept = jnp.take_along_axis(padded, keep[:, :, None],
                                                   axis=1)
                    rbc = _mm(u1, g("w_x"))
                    step = jax.nn.softplus(_mm(rbc[..., :R], g("w_dt"))
                                           + _f32(g("b_dt")))
                    # a padded position and an idle row are identities,
                    # whatever their inputs hold
                    step = jnp.where(valid[:, :, None], step, 0.0)
                    u1 = jnp.where(valid[:, :, None], u1, 0.0)
                    with scope("scan"):
                        y, ssm_pool = selective_scan(
                            step, u1, rbc[..., R:R + N], rbc[..., R + N:],
                            -jnp.exp(_f32(g("A_log"))), fresh, ssm_pool,
                            slots, kept, layer=li, kernel=kernel)
                    memory = y + _f32(g("D_skip")) * u1
                    out = _mm(memory * jax.nn.silu(z), g("w_out"))
                elif kind == "gmu":
                    out = _mm(memory * jax.nn.silu(_mm(h, g("w_i"))),
                              g("w_o"))
                else:
                    if kind != "cross":
                        k = _mm_as_stored(h, g("wk")) + _f32(g("bk"))
                        v = _mm_as_stored(h, g("wv")) + _f32(g("bv"))
                        with scope("cache_write"):
                            if kind == "swa":
                                li = at_kind["swa"]
                                at_kind["swa"] += 1
                                k_win = k_win.at[li, phys_w, offs].set(
                                    k.astype(k_win.dtype))
                                v_win = v_win.at[li, phys_w, offs].set(
                                    v.astype(v_win.dtype))
                            else:
                                k_full = k_full.at[0, phys_f, offs].set(
                                    k.astype(k_full.dtype))
                                v_full = v_full.at[0, phys_f, offs].set(
                                    v.astype(v_full.dtype))
                    if kind == "full":
                        if not want_logits:
                            break       # the chunk has filled its caches
                        if skip:
                            # from here on, one position a row: its last
                            x, h, memory = (_at_last(a, last)
                                            for a in (x, h, memory))
                            positions = _at_last(positions, last)
                            asked = live[:, None]
                    q = (_mm_as_stored(h, g("wq")) + _f32(g("bq"))).reshape(
                        B, -1, H, dh)
                    with scope("kernel"):
                        if kind == "swa":
                            a = attend(q, k_win, v_win, t_win, positions,
                                       max_pos, li=li, window=win, call="swa")
                        else:
                            a = attend(q, k_full, v_full, t_full, positions,
                                       max_pos, li=0, window=0, call=kind)
                    with scope("diff"):
                        lv = _f32(g("lam"))
                        lam = jnp.exp(jnp.sum(lv[0] * lv[1])) \
                            - jnp.exp(jnp.sum(lv[2] * lv[3])) + cfg.lam0(i)
                        a = _da.diff_combine(a, lam, cfg.lam0(i), g("sub_g"),
                                             eps)
                    out = _mm(a, g("wo")) + _f32(g("bo"))
                x = x + out
            with scope("norm"):
                h = _ln(x, g("ln2_g"), g("ln2_b"), eps)
            with scope("ffn"):
                gu = _mm(h, g("wgu"))
                F = cfg.intermediate_size
                x = x + _mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], g("wd"))
    pools = (k_full, v_full, k_win, v_win, ssm_pool)
    if not want_logits:
        return None, pools, aux
    with scope("head"):
        xn = _ln(x, params["lnf_g"], params["lnf_b"], eps)
        emb = params["tok_emb"]
        logits = jax.lax.dot_general(
            xn.astype(emb.dtype), emb, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return logits, pools, aux


@dataclass(frozen=True)
class SambaYLM:
    """The model as the generation engine takes one (the seam of
    ``serving/generation/programs.py``): one token a row a step
    (``block_len`` 0, so it rides the step in flight), a cache of three
    kinds (``cache_spec``), the prefill skip (``fills_without_head``: the
    engine sends every chunk but a prompt's last through the fill program),
    and the counts its program hands back (``counters``).  ``max_len`` is
    the service's longest position."""
    cfg: SambaYConfig
    max_len: int
    kv_dtype: object = jnp.bfloat16
    # the longest chunk a prefill program takes: its temporaries (the
    # scan's inputs spread over lanes, the queries) grow with the chunk,
    # and so does what a row of the window kind owns while it is prefilled
    longest_chunk: int = 512
    prefill_skip: bool = True
    block_len = 0
    offers = frozenset({"sampling"})
    counters = COUNTERS
    # the tiles body fetches the pages a tile reads and no others: a
    # table's width costs nothing, so the service keeps one
    one_table_width = True

    @property
    def fills_without_head(self) -> bool:
        return self.prefill_skip

    @property
    def vocab(self) -> int:
        return self.cfg.vocab_size

    @property
    def heads(self) -> int:
        return self.cfg.num_attention_heads

    def cache_spec(self) -> dict:
        """Three kinds: ``full`` keeps every position of ONE layer's K and
        V and is sized by tokens — its ``writers`` is that layer, its
        ``readers`` that layer and every cross layer, all at pool row 0 —;
        ``window`` keeps what ``sliding_window`` positions can still see
        and is sized by rows; ``state`` is a slot's scan and convolution
        state, float32, sized by rows."""
        from ..ops.selective_scan import state_shapes

        c = self.cfg
        kv = (("k", c.num_key_value_heads * c.head_dim),
              ("v", c.num_key_value_heads * c.head_dim))
        full, swa, ssm = (c.layers_of("full"), c.layers_of("swa"),
                          c.layers_of("ssm"))
        return dict(dtype=self.kv_dtype, kinds=(
            dict(name="full", n_layers=len(full), pools=kv, writers=full,
                 readers=c.layers_of("full", "cross")),
            dict(name="window", n_layers=len(swa), pools=kv,
                 window=c.sliding_window, writers=swa, readers=swa),
            dict(name="state", n_layers=len(ssm), dtype=jnp.float32,
                 state=state_shapes(c.d_inner, c.d_state, c.d_conv),
                 writers=ssm, readers=ssm)))

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        the three kinds' pools, one kind after the other, ``block_tables``
        a table a kind; returns ``(logits, pools, aux)``.  No mesh is
        offered, so ``mp_mesh`` is always None."""
        return sambay_lm_decode(
            params, tokens, positions, lengths, pools, block_tables,
            self.cfg, kernel=attention_kernel == "paged",
            max_len=self.max_len, want_logits=want_logits,
            prefill_skip=self.prefill_skip)

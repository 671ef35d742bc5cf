"""SDAR-MoE: a grouped-KV rotary block with sparse experts, generated by
diffusion over blocks (``model_type`` ``sdar_moe``; docs/generation.md
"Block-diffusion generation").

The block, with ``x`` the residual stream and ``L`` the block length::

    h = rms(x, g1);  q, k, v = h Wq, h Wk, h Wv            (no bias)
    q = rope(rms(q, gq), pos);  k = rope(rms(k, gk), pos)   (per head)
    a[t, h] = softmax_j(q[t, h] . k[j, h // G] / sqrt(D)) @ v[:, h // G]
              over j <= (pos[t] // L + 1) * L - 1           (G = Hq / Hkv)
    x = x + a Wo
    h = rms(x, g2);  p = softmax(h Wr)                      (all E experts)
    (w, e) = top_k(p, k);  w = w / sum(w)
    x = x + sum_i w_i (silu(h Wg[e_i]) * (h Wu[e_i])) Wd[e_i]
    logits = rms(x, gf) Wh                                  (untied head)

Attention is causal over blocks and bidirectional inside one; there is no
shift: the logits at a position predict that position's own token.

Parameters are a flat dict in ONE dtype (bfloat16 on the chip, float32 in
the CPU tests) and are never cast inside the program: matrix products take
operands in the parameters' dtype and accumulate in float32; the residual
stream, the norms, the router's softmax and attention's softmax are
float32.  :func:`sdar_moe_decode` is the cache-aware step the generation
engine traces (the signature of ``transformer_lm_decode``, whose write
coordinates, null block and table logic it shares); the plain reference
is ``perfbench/reference/sdar_moe.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .transformer import paged_write_coords

Params = Dict[str, jnp.ndarray]

__all__ = ["SdarMoeConfig", "SdarMoeLM", "sdar_moe_init", "sdar_moe_decode",
           "moe_grouped", "expert_products", "count_trips", "trip_counters",
           "TRIP_COUNTERS", "unmask_schedule"]

#: what a model that holds a share of its experts counts of
#: :func:`expert_products`' walks (docs/observability.md)
TRIP_COUNTERS = ("expert_trips", "expert_trips_extra")


@dataclass(frozen=True)
class SdarMoeConfig:
    """The published ``config.json`` keys that shape the model (defaults:
    SDAR-30B-A3B-Chat), and the generation procedure's settings, which the
    config does not carry (the SDAR family's published defaults)."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    # generation by diffusion over blocks
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669

    def __post_init__(self):
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.num_experts_per_tok <= self.num_experts
        assert 1 <= self.denoising_steps <= self.block_length
        assert self.max_position_embeddings % self.block_length == 0
        assert 0 <= self.mask_token_id < self.vocab_size

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings


def unmask_schedule(block_length: int, steps: int) -> Tuple[int, ...]:
    """Masked positions a denoise pass unmasks, by pass number: the even
    split of the block over the steps, the remainder to the first passes
    (``low_confidence_static``)."""
    base, rem = divmod(block_length, steps)
    return tuple(base + (s < rem) for s in range(steps))


def sdar_moe_init(cfg: SdarMoeConfig, key, dtype=jnp.float32) -> Params:
    """Seeded scaled-normal weights in ``dtype`` (norm gains near one, so
    that a gain left out shows)."""
    d, D, F, E = (cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size,
                  cfg.num_experts)
    hq, hkv = cfg.num_attention_heads * D, cfg.num_key_value_heads * D

    def normal(i, shape, scale):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale).astype(dtype)

    def gain(i, n):
        return (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                              (n,), jnp.float32)
                ).astype(dtype)

    s = d ** -0.5
    res = s / (2.0 * cfg.num_hidden_layers) ** 0.5
    p = {"tok_emb": normal(0, (cfg.vocab_size, d), 0.1),
         "head": normal(1, (d, cfg.vocab_size), s),
         "norm_f": gain(2, d)}
    for i in range(cfg.num_hidden_layers):
        b = 16 * (i + 1)
        p[f"l{i}_norm1"] = gain(b, d)
        p[f"l{i}_wq"] = normal(b + 1, (d, hq), s)
        p[f"l{i}_wk"] = normal(b + 2, (d, hkv), s)
        p[f"l{i}_wv"] = normal(b + 3, (d, hkv), s)
        p[f"l{i}_wo"] = normal(b + 4, (hq, d), hq ** -0.5 * res / s)
        p[f"l{i}_q_norm"] = gain(b + 5, D)
        p[f"l{i}_k_norm"] = gain(b + 6, D)
        p[f"l{i}_norm2"] = gain(b + 7, d)
        # a router whose logits spread like a trained one's: top-8 of 128
        # then carries most of the mass, and ties are rare
        p[f"l{i}_router"] = normal(b + 8, (d, E), 2.0 * s)
        p[f"l{i}_wg"] = normal(b + 9, (E, d, F), s)
        p[f"l{i}_wu"] = normal(b + 10, (E, d, F), s)
        p[f"l{i}_wd"] = normal(b + 11, (E, F, d), F ** -0.5 * res / s)
    return p


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * g.astype(jnp.float32)


def _mm(a, w):
    """``a @ w``: operands in the parameters' dtype, float32 out."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _mm_as_stored(a, w):
    """:func:`_mm` for a weight whose columns are heads of a width that is
    not whole 128-lane tiles (192 = 128 + 64 rotary lanes): the result
    stays FLAT, ``(..., w.shape[1])``, until the compiler has laid the
    program out.  Cutting such a result into heads moves data whoever pays;
    written straight behind the product, the chip's compiler folds the cut
    into the product and turns the WEIGHT round to suit it — a copy of the
    whole matrix, 100 MB of ``wq`` a layer at ``mimo-v2.5``'s widths, in
    every call — where behind the barrier it re-lays-out the activation
    (docs/generation.md "A weight reaches its product as stored")."""
    return jax.lax.optimization_barrier(_mm(a, w))


def _rope(x, positions, theta):
    """Rotate-half rotary embedding of ``x`` (B, T, H, D) in float32."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[..., None] * inv      # (B, T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def expert_products(h, w, e, wg, wu, wd,
                    experts_held: Optional[Tuple[int, int]] = None,
                    pallas: bool = False, n_experts: Optional[int] = None):
    """The expert layer behind its router, dropless: given every token's
    chosen experts ``e`` (N, k) — ids over ALL the layer's experts — and
    their weights ``w`` (N, k), sort the ``N * k`` assignments by expert
    and compute one grouped matrix product per projection — ``pallas``:
    the kernel of ``ops/grouped_matmul.py`` (an expert's matrix a grid
    step, or a column tile of it: the few rows an expert gets in a serving
    step are bound by reading its weights once); else
    ``jax.lax.ragged_dot``.  No ``(tokens, E, F)`` intermediate, no
    capacity, no dropped token.  Whoever routes (softmax and top-k here,
    sigmoid scores over groups in ``latent_moe.py``) is the caller.

    ``experts_held = (lo, hi)`` says which experts ``wg`` / ``wu`` / ``wd``
    hold (default: as many as they hold, from 0), ``n_experts`` how many
    the router chooses among (default: the held ones): assignments to
    experts held elsewhere add nothing here — a chip that holds a share of
    the experts computes its share of the result, and nothing stands in
    for the other chips.  Nor does it pay for their rows: the sort puts the
    held assignments first, and with a share held the function walks THOSE
    in tiles of ``cap`` rows — gather, the three products, weigh,
    scatter-add, ``cap`` rows each — for ``ceil(held assignments / cap)``
    trips of one ``lax.while_loop``.  ``cap`` is twice the share a
    balanced router sends here, ``2 N k held / n_experts`` in whole row
    tiles of the kernel (128) and at most ``N k``: one trip is the rule; a
    second one reads the touched experts' weights again, is exact like the
    first (a group that straddles a tile's edge is cut there) and is
    counted.  With every expert held every row is real and there is one
    pass over all of them, no loop.

    Returns ``(y (N, d) float32, sizes (held,) int32, trips)``: ``sizes``
    counts the assignments each held expert got, ``trips`` (int32 scalar)
    the tiles walked — None where every expert is held and nothing is
    walked."""
    N, top_k = e.shape
    lo, hi = experts_held if experts_held is not None else (0, wg.shape[0])
    held = hi - lo
    assert wg.shape[0] == held
    # device scopes (docs/observability.md "Device scopes"): the sort of
    # the assignments belongs to the router, the rows' way to their experts
    # and back onto their tokens is ``moe.combine``
    scope = jax.named_scope
    with scope("moe.route"):
        local = e.reshape(-1) - lo
        mine = (local >= 0) & (local < held)
        # assignments to experts held elsewhere sort behind every group
        order = jnp.argsort(jnp.where(mine, local, held), stable=True)
        token = order // top_k
        sizes = jnp.bincount(jnp.where(mine, local, held), length=held + 1
                             )[:held].astype(jnp.int32)
    if pallas:
        from ..ops.grouped_matmul import grouped_matmul as rd
    else:
        rd = functools.partial(jax.lax.ragged_dot,
                               preferred_element_type=jnp.float32)
    hx = h.astype(wg.dtype)

    def products(tok, groups):
        """The tokens ``tok`` of rows sorted by expert, ``groups`` rows an
        expert, through their experts: ``(len(tok), d)`` float32."""
        with scope("moe.combine"):
            xs = hx[tok]
        with scope("moe.experts"):
            act = jax.nn.silu(rd(xs, wg, groups)) * rd(xs, wu, groups)
            return rd(act.astype(wd.dtype), wd, groups)

    if held >= (n_experts or held):
        ys = products(token, sizes)                            # (N*k, d)
        with scope("moe.combine"):
            # rows behind the last group hold whatever the kernel left there
            ys = jnp.where(mine[order][:, None],
                           ys * w.reshape(-1)[order][:, None], 0.0)
            y = jnp.zeros((N, h.shape[1]), jnp.float32).at[token].add(ys)
        return y, sizes, None

    M = N * top_k
    cap = min(M, -(-2 * M * held // (n_experts * 128)) * 128)
    with scope("moe.route"):
        # whole tiles: rows behind the held ones are masked, wherever from
        order = jnp.pad(order, (0, -M % cap))
        ends = jnp.cumsum(sizes)
        starts, n_mine = ends - sizes, ends[-1]
        wf = w.reshape(-1)

    def trip(carry):
        t, y = carry
        with scope("moe.route"):
            at = t * cap
            rows = jax.lax.dynamic_slice(order, (at,), (cap,))
            # each group's [start, end) cut to the tile's [at, at + cap)
            share = (jnp.clip(ends - at, 0, cap)
                     - jnp.clip(starts - at, 0, cap))
            tok = rows // top_k
        ys = products(tok, share)                              # (cap, d)
        with scope("moe.combine"):
            live = at + jnp.arange(cap, dtype=jnp.int32) < n_mine
            ys = jnp.where(live[:, None], ys * wf[rows][:, None], 0.0)
            return t + 1, y.at[tok].add(ys)

    def more(carry):
        with scope("moe.route"):
            return carry[0] * cap < n_mine

    trips, y = jax.lax.while_loop(
        more, trip, (jnp.zeros((), jnp.int32),
                     jnp.zeros((N, h.shape[1]), jnp.float32)))
    return y, sizes, trips


def trip_counters(experts_held: Optional[Tuple[int, int]],
                  n_experts: int) -> Tuple[str, ...]:
    """The names :func:`count_trips` adds to the counts of a model whose
    chip holds ``experts_held`` of ``n_experts``: none with every expert
    held."""
    whole = experts_held is None or \
        experts_held[1] - experts_held[0] >= n_experts
    return () if whole else TRIP_COUNTERS


def count_trips(aux: dict, trips) -> None:
    """Add one expert layer call's ``trips`` (:func:`expert_products`) to a
    program's counts: the tiles walked, and those beyond the first.  None
    (every expert held: nothing walked) adds nothing, not even the names:
    such a program hands back what it did before."""
    if trips is None:
        return
    zero = jnp.zeros((), jnp.int32)
    for name, n in zip(TRIP_COUNTERS, (trips, jnp.maximum(trips - 1, 0))):
        aux[name] = aux.get(name, zero) + n


def moe_grouped(h, router, wg, wu, wd, top_k: int, norm_topk: bool = True,
                experts_held: Optional[Tuple[int, int]] = None,
                pallas: bool = False):
    """This model's sparse layer: route every token of ``h`` (N, d) over
    ALL ``router.shape[1]`` experts — softmax, top-k, renormalise — then
    :func:`expert_products`.

    Returns ``(y (N, d) float32, touched)``: ``touched`` counts the held
    experts at least one token was routed to."""
    with jax.named_scope("moe.route"):
        p = jax.nn.softmax(_mm(h, router), axis=-1)            # (N, E) f32
        w, e = jax.lax.top_k(p, top_k)                         # (N, k)
        if norm_topk:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
    y, sizes, _ = expert_products(h, w, e, wg, wu, wd, experts_held, pallas,
                                  n_experts=router.shape[1])
    with jax.named_scope("moe.route"):
        return y, jnp.sum(sizes > 0)


def sdar_moe_decode(params: Params, tokens, positions, lengths, k_pool,
                    v_pool, block_tables, cfg: SdarMoeConfig, *,
                    attention_kernel: Optional[str] = None,
                    call: str = "prefill", want_logits: bool = True,
                    experts_held: Optional[Tuple[int, int]] = None,
                    max_len: Optional[int] = None):
    """Cache-aware forward over a paged KV pool folded ``n_kv_heads *
    head_dim`` wide (K and V are never repeated for their query heads).

    Arguments as ``transformer_lm_decode``; every query attends to cache
    positions up to the END of its own block of ``cfg.block_length``, this
    chunk's writes included, so chunk offsets and valid lengths must be
    whole blocks.  ``call`` names the paged kernel's calls in a device
    trace (``"block"`` for the block step).  ``want_logits=False`` (the
    prefill of whole prompt blocks, which only fills the cache) skips the
    head.

    Returns ``(logits (B, T, vocab) float32 or None, k_pool, v_pool,
    touched)``; ``touched`` sums, over the layers, the experts that were
    routed at least one of the chunk's tokens (padded ones too)."""
    from ..ops import paged_attention as _pa
    from ..ops import pallas_kernels as _pk

    B, T = tokens.shape
    L = cfg.block_length
    Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    block_size = k_pool.shape[2]
    W = block_tables.shape[1]
    positions, valid, phys, offs = paged_write_coords(
        positions, lengths, block_tables, block_size,
        max_len or cfg.max_len)
    block_end = (positions // L + 1) * L - 1
    use_paged = (_pk.pallas_enabled() if attention_kernel is None
                 else attention_kernel == "paged")
    if use_paged:
        max_pos = jnp.max(jnp.where(valid, block_end, -1), axis=1)
        scale = _pa.attention_scale(D)
    else:
        ctx_pos = jnp.arange(W * block_size, dtype=jnp.int32)
        attn_mask = ctx_pos[None, None, :] <= block_end[:, :, None]
        scale = 1.0 / jnp.sqrt(D).astype(jnp.float32)
    eps = cfg.rms_norm_eps
    scope = jax.named_scope     # docs/observability.md "Device scopes"
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)      # (B, T, d)
    touched = jnp.zeros((), jnp.int32)
    for i in range(cfg.num_hidden_layers):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        with scope(f"layer{i}"):
            with scope("norm"):
                h = _rms(x, g("norm1"), eps)
            with scope("attn.proj"):
                q = _rope(_rms(_mm(h, g("wq")).reshape(B, T, Hq, D),
                               g("q_norm"), eps), positions, cfg.rope_theta)
                k = _rope(_rms(_mm(h, g("wk")).reshape(B, T, Hkv, D),
                               g("k_norm"), eps), positions, cfg.rope_theta)
                v = _mm(h, g("wv"))
            with scope("attn.cache_write"):
                fold = lambda t: t.reshape(B, T, Hkv * D).astype(  # noqa: E731
                    k_pool.dtype)
                k_pool = k_pool.at[i, phys, offs].set(fold(k))
                v_pool = v_pool.at[i, phys, offs].set(fold(v))
            with scope("attn.kernel"):
                if use_paged:
                    # the kernel masks by the positions it is given: each
                    # query's block-end position (the rotary positions are
                    # applied already)
                    o = _pa.paged_attention(q, k_pool, v_pool, block_tables,
                                            block_end, max_pos, scale=scale,
                                            layer=i, call=call)
                else:
                    gather = lambda pool: pool[i][block_tables].reshape(  # noqa: E731
                        B, W * block_size, Hkv, D)
                    o = _pa.paged_attention_reference(
                        q, gather(k_pool), gather(v_pool), attn_mask, scale)
            with scope("attn.proj"):
                x = x + _mm(o.reshape(B, T, Hq * D), g("wo"))
            with scope("norm"):
                h = _rms(x, g("norm2"), eps)
            y, n = moe_grouped(h.reshape(B * T, -1), g("router"), g("wg"),
                               g("wu"), g("wd"), cfg.num_experts_per_tok,
                               cfg.norm_topk_prob, experts_held,
                               pallas=use_paged)
            with scope("moe.combine"):
                x = x + y.reshape(B, T, -1)
            touched = touched + n.astype(jnp.int32)
    logits = None
    if want_logits:
        with scope("head"):
            logits = _mm(_rms(x, params["norm_f"], eps), params["head"])
    return logits, k_pool, v_pool, touched


@dataclass(frozen=True)
class SdarMoeLM:
    """The model as the generation engine takes one (the seam of
    ``serving/generation/programs.py``): its step, vocabulary, longest
    position and cache spec.  ``max_len`` is the service's longest
    position where that is shorter than the published one."""
    cfg: SdarMoeConfig
    max_len: int
    kv_dtype: object = jnp.bfloat16
    offers = frozenset({"block"})

    @property
    def vocab(self) -> int:
        return self.cfg.vocab_size

    @property
    def block_len(self) -> int:
        return self.cfg.block_length

    @property
    def heads(self) -> int:
        return self.cfg.num_attention_heads

    @property
    def mask_id(self) -> int:
        return self.cfg.mask_token_id

    @property
    def unmask_schedule(self) -> Tuple[int, ...]:
        return unmask_schedule(self.cfg.block_length,
                               self.cfg.denoising_steps)

    def cache_spec(self) -> dict:
        return dict(n_layers=self.cfg.num_hidden_layers,
                    n_heads=self.cfg.num_key_value_heads,
                    d_head=self.cfg.head_dim, dtype=self.kv_dtype)

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        ``(k, v)``; returns ``(logits, pools, aux)``, ``aux`` the experts
        touched.  No mesh is offered, so ``mp_mesh`` is always None."""
        logits, k_pool, v_pool, touched = sdar_moe_decode(
            params, tokens, positions, lengths, *pools, block_tables,
            self.cfg, attention_kernel=attention_kernel,
            call=call or "prefill", want_logits=want_logits,
            max_len=self.max_len)
        return logits, (k_pool, v_pool), touched

"""A latent-attention block with sigmoid-routed experts and a shared one
(the DeepSeek-V3 language model's keys, as ``dots.vlm1.inst``'s
``config.json`` carries them; docs/generation.md "Latent attention").

The layers, with ``x`` the residual stream, ``H`` heads, ``dn`` / ``dr`` /
``dv`` the no-position, rotary and value head sizes, ``rq`` / ``c`` the
query's and the key-value's latent ranks::

    h = rms(x, g1)
    cq = rms(h Wqa, gq)                                         (rq)
    [qn | qr] = (cq Wqb).reshape(H, dn + dr);  qr = rope(qr, pos)
    [ckv | kr] = h Wkva;  ckv = rms(ckv, gkv);  kr = rope(kr, pos)
                      cached, a token: [ckv | kr]  (c + dr), ONE kr for all heads
    materialised:  [kn | v] = (ckv Wkvb).reshape(H, dn + dv)
        a[t, h] = softmax_j<=t((qn[t,h].kn[j,h] + qr[t,h].kr[j]) s) @ v[:, h]
    absorbed:  Wkvb[h] = [Wuk[h] | Wuv[h]];  ql[t,h] = qn[t,h] Wuk[h]^T  (c)
        score = (ql[t,h].ckv[j] + qr[t,h].kr[j]) s;  ol[t,h] = sum_j p ckv[j]
        a[t, h] = ol[t,h] Wuv[h]
    x = x + a.reshape(H dv) Wo;        s = (dn + dr)^-0.5 m^2,  m = 0.1 ln(factor) + 1
    h = rms(x, g2)
    a leading dense layer:  x = x + (silu(h Wg) * (h Wu)) Wd
    an expert layer:  sc = sigmoid(h Wr)  (E, float32);  c = sc + b   (choosing only)
        G_g = the sum of the 2 largest c of group g;  keep the topk_group best groups
        e = top_k(c over the kept groups);  w = sc[e] / (sum sc[e] + 1e-20) * factor
        x = x + sum_i w_i E_{e_i}(h) + E_shared(h);   E(h) = (silu(h Wg) * (h Wu)) Wd
    logits = rms(x, gf) Wh

The two forms of the attention are one function of the same cache.  The
program runs the ABSORBED form everywhere: the Pallas kernel
(``ops/latent_attention.py``) for decode and for a prefill chunk's tiles
of tokens, and without the kernel the same sums over the gathered pages
(``latent_attention_reference``).  The MATERIALISED form is the plain
reference's (``perfbench/reference/dots_vlm1.py``) and the tests'; PERF.md
PR 30 has the chip's numbers for both forms of the prefill.

The rotary embedding is rotate-half over ``dr`` with YaRN's frequencies
(``cos`` and ``sin`` unscaled: ``mscale / mscale_all_dim`` is 1).  The
source stores the rotary columns interleaved and permutes them before it
rotates: a fixed permutation of ``Wqb``'s and ``Wkva``'s columns, which
seeded weights absorb.

``experts_held = (lo, hi)`` is the chip's share of the routed experts:
the router scores all ``n_routed_experts``, the expert products
(``sdar_moe.expert_products``, shared with that model) add this chip's
experts' part, the shared expert is computed whole, and nothing stands in
for the other chips or their exchange.  The products are told the
router's width and so work on this chip's rows alone: with 16 of 256
held, a sixteenth of the ``N x 8`` assignments, walked in tiles of twice
that (512 rows of a 512-token chunk's 4,096).  One trip a layer call is
the rule; the program counts the trips (``expert_trips``) and those beyond
a call's first (``expert_trips_extra``: the router sent this chip more
than twice its share), which cost another read of the touched experts'
weights and change no result.

Parameters are a flat dict in ONE dtype and are never cast in the
program: products take operands in that dtype and accumulate in float32;
the residual stream, norms, sigmoid scores, group sums and softmax are
float32; the latent pool has its own dtype (bfloat16 on the chip).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sdar_moe import (_mm, _mm_as_stored, _rms, count_trips,
                       expert_products, trip_counters)
from .transformer import paged_write_coords

Params = Dict[str, jnp.ndarray]

__all__ = ["LatentMoeConfig", "LatentMoeLM", "latent_moe_decode",
           "latent_moe_param_shapes", "latent_moe_init", "route_sigmoid_groups",
           "yarn_inv_freq"]

COUNTERS = ("latent_ctx_tokens", "latent_prefill_pairs", "expert_assignments",
            "expert_assignments_held", "experts_touched", "expert_tokens_max")


@dataclass(frozen=True)
class LatentMoeConfig:
    """The published ``config.json`` keys that shape the model (defaults:
    ``dots.vlm1.inst``'s language model)."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 163840

    def __post_init__(self):
        assert self.n_routed_experts % self.n_group == 0
        assert self.topk_group <= self.n_group
        assert self.num_experts_per_tok <= \
            self.topk_group * (self.n_routed_experts // self.n_group)
        assert self.qk_rope_head_dim % 2 == 0

    @property
    def latent_width(self) -> int:
        """A token's cached vector: its latent and the shared rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0 \
            if self.rope_factor > 1 else 1.0
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
                     * m * m)


def yarn_inv_freq(cfg: LatentMoeConfig) -> np.ndarray:
    """YaRN's rotary frequencies (``dr / 2``, float32): the plain ones
    where a dimension turns more than ``beta_fast`` times over the
    original context, those divided by ``factor`` where it turns less
    than ``beta_slow`` times, a linear ramp over the dimensions between."""
    dr, base = cfg.qk_rope_head_dim, cfg.rope_theta
    plain = 1.0 / base ** (np.arange(0, dr, 2, dtype=np.float64) / dr)

    def dim_of(turns):
        return dr * math.log(cfg.rope_original_max_position_embeddings
                             / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.rope_beta_slow)), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / cfg.rope_factor * ramp + plain * (1 - ramp)
            ).astype(np.float32)


def _rope(x, positions, inv_freq):
    """Rotate-half rotary embedding of ``x`` (B, T, H, dr) in float32."""
    D = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # (B, T, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def route_sigmoid_groups(logits, bias, top_k: int, n_group: int,
                         topk_group: int, norm_topk: bool = True,
                         scaling: float = 1.0):
    """The router behind its product: ``logits`` (N, E) float32 ->
    ``(w (N, k) float32, e (N, k) int32)``.  Sigmoid scores; a correction
    ``bias`` that takes part in CHOOSING only; the ``topk_group`` groups
    whose two best corrected scores sum highest; the ``top_k`` best
    corrected scores inside them; weights from the uncorrected scores,
    renormalised and times ``scaling``.  Ties go to the lower index."""
    N, E = logits.shape
    sc = jax.nn.sigmoid(logits)
    c = sc + bias.astype(jnp.float32)
    per_group = c.reshape(N, n_group, E // n_group)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # (N, G)
    kept = jax.lax.top_k(group_score, topk_group)[1]                # (N, g)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)                                          # (N, G)
    masked = jnp.where(keep[:, :, None], per_group, -jnp.inf).reshape(N, E)
    e = jax.lax.top_k(masked, top_k)[1]
    w = jnp.take_along_axis(sc, e, axis=1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, e


def latent_moe_param_shapes(cfg: LatentMoeConfig,
                            experts_held: Optional[Tuple[int, int]] = None
                            ) -> Dict[str, Tuple[int, ...]]:
    """Every parameter's shape, the routed experts' as this chip holds
    them (``experts_held``; default all)."""
    d, H = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq, c, f = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.moe_intermediate_size
    lo, hi = experts_held or (0, cfg.n_routed_experts)
    held, fs = hi - lo, cfg.n_shared_experts * f
    s = {"tok_emb": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
         "norm_f": (d,)}
    for i in range(cfg.num_hidden_layers):
        layer = {"norm1": (d,), "wqa": (d, rq), "q_norm": (rq,),
                 "wqb": (rq, H * (dn + dr)), "wkva": (d, c + dr),
                 "kv_norm": (c,), "wkvb": (c, H * (dn + dv)),
                 "wo": (H * dv, d), "norm2": (d,)}
        if i < cfg.first_k_dense_replace:
            F = cfg.intermediate_size
            layer.update(wg=(d, F), wu=(d, F), wd=(F, d))
        else:
            layer.update(router=(d, cfg.n_routed_experts),
                         router_bias=(cfg.n_routed_experts,),
                         wg=(held, d, f), wu=(held, d, f), wd=(held, f, d),
                         sg=(d, fs), su=(d, fs), sd=(fs, d))
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def latent_moe_init(cfg: LatentMoeConfig, key, dtype=jnp.float32,
                    experts_held: Optional[Tuple[int, int]] = None) -> Params:
    """Seeded weights in ``dtype``: products normal over the square root
    of their fan-in (residual outputs divided by ``sqrt(2 x layers)``),
    norm gains near one and a small correction bias that is not zero, so
    that a term left out shows (a sigmoid router's best scores lie close
    together: a bias of their spread's size would decide every choice).
    (The benchmark makes its own, ``perfbench/reference/dots_vlm1.py``.)"""
    res = (2.0 * cfg.num_hidden_layers) ** -0.5
    p = {}
    for i, (name, shape) in enumerate(sorted(
            latent_moe_param_shapes(cfg, experts_held).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        kind = name.split("_", 1)[-1]
        if "norm" in name:
            z = 1.0 + 0.1 * z
        elif kind == "router_bias":
            z = 0.01 * z
        elif name == "tok_emb":
            z = 0.1 * z
        else:
            z = z * shape[-2] ** -0.5 * (res if kind in ("wo", "wd", "sd")
                                         else 1.0)
        p[name] = z.astype(dtype)
    return p


def _gated(h, wg, wu, wd):
    """``(silu(h Wg) * (h Wu)) Wd``: the dense layer and the shared expert."""
    return _mm(jax.nn.silu(_mm(h, wg)) * _mm(h, wu), wd)


def latent_moe_decode(params: Params, tokens, positions, lengths, pool,
                      block_tables, cfg: LatentMoeConfig, *,
                      attention_kernel: Optional[str] = None,
                      experts_held: Optional[Tuple[int, int]] = None,
                      max_len: Optional[int] = None, call=None):
    """Cache-aware forward over a paged latent pool ``(n_layers,
    num_blocks, block_size, width >= kv_lora_rank + qk_rope_head_dim)``.

    Arguments as ``transformer_lm_decode``: a chunk ``tokens`` (B, T) at
    ``positions``, ``lengths`` valid a row; every valid position's
    ``[ckv | kr]`` is written at its table's coordinates (the others into
    the null block) and every query attends to cache positions up to its
    own.

    Returns ``(logits (B, T, vocab) float32, pool, aux)``; ``aux`` is the
    dict of this call's counts (``COUNTERS``, and with a share of the
    experts held ``sdar_moe.TRIP_COUNTERS``; docs/observability.md), made
    on the device from what the program itself saw: valid queries only,
    except ``experts_touched``, which counts the experts whose weights the
    products read."""
    from ..ops import latent_attention as _la
    from ..ops import pallas_kernels as _pk

    B, T = tokens.shape
    H = cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    c, lat = cfg.kv_lora_rank, cfg.latent_width
    block_size, width = pool.shape[2], pool.shape[3]
    W = block_tables.shape[1]
    positions, valid, phys, offs = paged_write_coords(
        positions, lengths, block_tables, block_size,
        max_len or cfg.max_position_embeddings)
    use_kernel = (_pk.pallas_enabled() if attention_kernel is None
                  else attention_kernel == "paged")
    max_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)
    if not use_kernel:
        ctx_pos = jnp.arange(W * block_size, dtype=jnp.int32)
        attn_mask = ctx_pos[None, None, :] <= positions[:, :, None]
    scale, eps = cfg.softmax_scale, cfg.rms_norm_eps
    inv_freq = jnp.asarray(yarn_inv_freq(cfg))
    lo, hi = experts_held or (0, cfg.n_routed_experts)
    valid_flat = valid.reshape(-1)
    reads = jnp.sum(jnp.where(valid, positions + 1, 0))
    zero = jnp.zeros((), jnp.int32)
    aux = {"latent_ctx_tokens": reads if T == 1 else zero,
           "latent_prefill_pairs": zero if T == 1 else reads,
           "expert_assignments": zero, "expert_assignments_held": zero,
           "experts_touched": zero, "expert_tokens_max": zero}
    scope = jax.named_scope     # docs/observability.md "Device scopes"
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)      # (B, T, d)
    for i in range(cfg.num_hidden_layers):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        with scope(f"layer{i}"):
            with scope("norm"):
                h = _rms(x, g("norm1"), eps)
            with scope("attn.proj"):
                q = _mm_as_stored(_rms(_mm(h, g("wqa")), g("q_norm"), eps),
                                  g("wqb")).reshape(B, T, H, dn + dr)
                qn, qr = q[..., :dn], _rope(q[..., dn:], positions, inv_freq)
                kva = _mm(h, g("wkva"))                        # (B, T, c+dr)
                latent = jnp.concatenate(
                    [_rms(kva[..., :c], g("kv_norm"), eps),
                     _rope(kva[:, :, None, c:], positions, inv_freq)[:, :, 0]],
                    axis=-1).astype(pool.dtype)
            with scope("attn.cache_write"):
                if width != lat:
                    latent = jnp.pad(latent,
                                     ((0, 0), (0, 0), (0, width - lat)))
                pool = pool.at[i, phys, offs].set(latent)
            with scope("attn.proj"):    # the absorbed product, query side
                wkvb = g("wkvb").reshape(c, H, dn + dv)
                ql = jnp.einsum("bthd,chd->bthc", qn.astype(wkvb.dtype),
                                wkvb[..., :dn],
                                preferred_element_type=jnp.float32)
                qa = jnp.concatenate([ql, qr], axis=-1)        # (B, T, H, lat)
            with scope("attn.kernel"):
                if use_kernel:
                    ol = _la.latent_attention(qa, pool, block_tables,
                                              positions, max_pos, v_width=c,
                                              scale=scale, layer=i, call=call)
                else:
                    ctx = pool[i][block_tables].reshape(
                        B, W * block_size, width)[..., :lat]
                    ol = _la.latent_attention_reference(qa, ctx, attn_mask,
                                                        c, scale)
            with scope("attn.proj"):    # ... and the value side, then wo
                a = jnp.einsum("bthc,chd->bthd", ol.astype(wkvb.dtype),
                               wkvb[..., dn:],
                               preferred_element_type=jnp.float32)
                x = x + _mm(a.reshape(B, T, H * dv), g("wo"))
            with scope("norm"):
                h = _rms(x, g("norm2"), eps)
            if i < cfg.first_k_dense_replace:
                with scope("ffn"):
                    x = x + _gated(h, g("wg"), g("wu"), g("wd"))
                continue
            hf = h.reshape(B * T, -1)
            with scope("moe.route"):
                w, e = route_sigmoid_groups(
                    _mm(hf, g("router")), g("router_bias"),
                    cfg.num_experts_per_tok, cfg.n_group, cfg.topk_group,
                    cfg.norm_topk_prob, cfg.routed_scaling_factor)
            y, sizes, trips = expert_products(
                hf, w, e, g("wg"), g("wu"), g("wd"), (lo, hi),
                pallas=use_kernel, n_experts=cfg.n_routed_experts)
            with scope("ffn"):          # the shared expert
                shared = _gated(hf, g("sg"), g("su"), g("sd"))
            with scope("moe.combine"):
                x = x + (y + shared).reshape(B, T, -1)
            with scope("moe.route"):    # the program's own counts
                mine = (e >= lo) & (e < hi) & valid_flat[:, None]
                load = jnp.bincount(
                    jnp.where(mine, e - lo, hi - lo).reshape(-1),
                    length=hi - lo + 1)[:hi - lo]
                aux["expert_assignments"] += (
                    jnp.sum(valid_flat) * e.shape[1]).astype(jnp.int32)
                aux["expert_assignments_held"] += jnp.sum(load).astype(
                    jnp.int32)
                aux["experts_touched"] += jnp.sum(sizes > 0).astype(jnp.int32)
                aux["expert_tokens_max"] = jnp.maximum(
                    aux["expert_tokens_max"], jnp.max(load).astype(jnp.int32))
                count_trips(aux, trips)
    with scope("head"):
        logits = _mm(_rms(x, params["norm_f"], eps), params["head"])
    return logits, pool, aux


@dataclass(frozen=True)
class LatentMoeLM:
    """The model as the generation engine takes one (the seam of
    ``serving/generation/programs.py``): one token a row a step
    (``block_len`` 0, so it rides the step in flight), ONE latent pool a
    layer (``cache_spec``), the chip's share of the routed experts
    (``experts_held``), and the counts its program hands back
    (``counters``).  ``max_len`` is the service's longest position.  A
    cached vector is zero-padded to whole 128-lane tiles (576 -> 640: the
    chip stores a 576-wide minor dimension as 640 anyway, and its compiler
    refuses a page copy of 576 lanes)."""
    cfg: LatentMoeConfig
    max_len: int
    experts_held: Optional[Tuple[int, int]] = None
    kv_dtype: object = jnp.bfloat16
    # the longest chunk a prefill program takes on the chip: the held
    # experts' products cost a chunk the same at 512 tokens as at 1,024
    # (bound by their weights' bytes), so a long prompt is cheapest in
    # long chunks; its temporaries (the absorbed queries, the assignments'
    # gathered rows) grow with the chunk, `dots-vlm1`'s peak 13.73 ->
    # 14.03 GB at 1,024.  2,048 would hold FEWER tokens a call on prompts
    # of a median 1,024 (563 for 593: half of them never fill one) at
    # twice the temporaries again (PERF.md, PR 50).  A seq bucket above it
    # only says how long a prompt may be
    longest_chunk: int = 1024
    block_len = 0
    offers = frozenset({"sampling"})
    # the latent kernel fetches live pages only, for a chunk as for one
    # token: a table's width costs nothing, so the service keeps one
    one_table_width = True

    @property
    def counters(self) -> Tuple[str, ...]:
        """The names of the program's counts: with a share of the experts
        held, the expert layers' trips too."""
        return COUNTERS + trip_counters(self.experts_held,
                                        self.cfg.n_routed_experts)

    @property
    def vocab(self) -> int:
        return self.cfg.vocab_size

    @property
    def heads(self) -> int:
        return self.cfg.num_attention_heads

    def cache_spec(self) -> dict:
        lanes = -(-self.cfg.latent_width // 128) * 128
        return dict(n_layers=self.cfg.num_hidden_layers, dtype=self.kv_dtype,
                    pools=(("latent", lanes),))

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        ``(latent,)``; returns ``(logits, pools, aux)``.  No mesh is
        offered, so ``mp_mesh`` is always None."""
        logits, pool, aux = latent_moe_decode(
            params, tokens, positions, lengths, pools[0], block_tables,
            self.cfg, attention_kernel=attention_kernel,
            experts_held=self.experts_held, max_len=self.max_len, call=call)
        return logits, (pool,), aux

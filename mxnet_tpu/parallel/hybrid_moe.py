"""A block whose layers attend in two ways — over a window, or over
everything — above sigmoid-routed experts: ONE layer loop for TWO published
models (docs/generation.md "Cache kinds").  ``HybridMoeConfig``'s defaults
are the first's; every term of the second is a field that is off there.

* ``mimo_v2`` (``MiMo-V2.5``): head counts a kind, keys wider than values,
  a sink in the window layers, partial rotary on EVERY layer with a base a
  kind, scaled values, one norm before each half, no shared expert.
* ``afmoe`` (``Trinity-Mini``; ``HybridMoeConfig.from_afmoe``): one pair of
  head counts, keys as wide as values, no sink; a sigmoid GATE on the
  heads' output, an RMS norm over each head's q and k, rotary over all the
  lanes of the WINDOW layers and no positional term at all in the full
  ones, a norm on each branch's OUTPUT besides the one on its input
  (sandwich), a shared expert beside the routed ones, the embedding times
  ``sqrt(hidden_size)``, router weights times ``route_scale``.

The layers, with ``x`` the residual stream, ``H`` query heads, ``dq`` /
``dr`` / ``dv`` the key, rotary and value head sizes; a layer's kind is
``hybrid_layer_pattern``'s entry: ``F`` (0) has ``Hkv`` KV heads, rotary
base ``rope_theta``, no window; ``W`` (1) has ``swa_num_key_value_heads``,
base ``swa_rope_theta``, a window of ``sliding_window`` positions (the
query's own among them) and — ``mimo_v2`` — a sink ``s_h`` a query head.
Terms in [brackets] are ``afmoe``'s::

    x = Emb[token] [* sqrt(d)]
    h = rms(x, g1)
    q = (h Wq).reshape(H, dq);  k = (h Wk).reshape(Hkv, dq)
    v = (h Wv).reshape(Hkv, dv) * attention_value_scale
    [q = rms(q, gq);  k = rms(k, gk)                     over a head's lanes]
    q = [rope(q[:, :dr], pos) | q[:, dr:]];  k likewise        (rotate-half, the FIRST dr lanes;
                                                 [dr = dq in W layers, NO rotation in F layers])
    cached: k, v   (F: every position; W: the last ``sliding_window``)
    z[t,h,j] = q[t,h] . k[j, h // (H/Hkv)] * dq^-0.5     F: j <= t;  W: t - window < j <= t
    p = softmax_j(z);  with a sink  p[t,h,j] = exp(z[t,h,j]) / (exp(s_h) + sum_i exp(z[t,h,i]))
    a[t,h] = sum_j p[t,h,j] v[j, h // (H/Hkv)]
    y = (a.reshape(H dv) [* sigmoid(h Wgate)]) Wo;       x = x + [rms(] y [, g1')]
    h = rms(x, g2)
    a dense layer (moe_layer_freq 0):  y = (silu(h Wg) * (h Wu)) Wd
    an expert layer:  sc = sigmoid(h Wr)  (E, float32);  c = sc + b   (choosing only)
        e = top_k(c);  w = sc[e] / (sum sc[e] + 1e-20) * routed_scaling_factor
        y = sum_i w_i E_{e_i}(h) [+ E_shared(h)];        E(h) = (silu(h Wg) * (h Wu)) Wd
    x = x + [rms(] y [, g2')]
    logits = rms(x, gf) Wh

The cache has TWO kinds (``cache_spec()["kinds"]``): ``full`` — the ``F``
layers' K and V, every position, under the table every model has — and
``window`` — the ``W`` layers', of which a row keeps the blocks its next
query can still see: its table is a ring as wide as a window and a chunk
(``serving/generation/kv_cache.py::CacheKind``).  Attention is
``ops/paged_attention.py``'s tiles body for both kinds of both models
(and, without the kernel, the same sums over the gathered pages); the
router is ``latent_moe``'s with one group, the expert products
``sdar_moe``'s, the shared expert ``latent_moe``'s.

``experts_held = (lo, hi)`` is the chip's share of the routed experts, as
in ``latent_moe.py``: the router scores all of them, the products add this
chip's experts' part — over this chip's rows alone, in tiles of twice a
balanced router's share, the trips counted (``expert_trips``,
``expert_trips_extra``) — the shared expert is computed whole, and nothing
stands in for the other chips.

Parameters are a flat dict in ONE dtype and are never cast in the program:
products take operands in that dtype and accumulate in float32; the
residual stream, every norm, the gate's and the router's sigmoid and the
softmax with its sink are float32; the pools have their own dtype
(bfloat16 on the chip).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .latent_moe import _gated, route_sigmoid_groups
from .sdar_moe import (_mm, _mm_as_stored, _rms, _rope, count_trips,
                       expert_products, trip_counters)
from .transformer import paged_write_coords

Params = Dict[str, jnp.ndarray]

__all__ = ["HybridMoeConfig", "HybridMoeLM", "hybrid_moe_decode",
           "hybrid_moe_param_shapes", "hybrid_moe_init"]

COUNTERS = ("full_ctx_tokens", "window_ctx_tokens", "full_prefill_pairs",
            "window_prefill_pairs", "expert_assignments",
            "expert_assignments_held", "experts_touched", "expert_tokens_max",
            "window_decode_trips")
# what a model with a shared expert counts besides (``afmoe``): the decode
# rows at or past the window, where the window kind reads less than the
# full kind does, and the tokens the shared expert ran, a layer.  (Counted
# for that model alone: ``mimo_v2``'s programs keep the outputs they had.)
AFMOE_COUNTERS = ("window_rows_past", "shared_expert_tokens")


@dataclass(frozen=True)
class HybridMoeConfig:
    """The published ``config.json`` keys that shape the model (defaults:
    ``MiMo-V2.5``'s language model)."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    hybrid_layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1) + (0, 1, 1, 1, 1, 1) * 7 + (0,)
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    attention_value_scale: float = 0.707
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0      # published null
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 1048576
    # ``afmoe``'s terms (the module's docstring), off for ``mimo_v2``
    attention_gate: bool = False    # sigmoid(h Wgate) times the heads' output
    qk_norm: bool = False           # an RMS norm over each head's q and k
    rope_on_full: bool = True       # False: the full layers have no rotary
    sandwich_norm: bool = False     # a norm on each branch's output as well
    n_shared_experts: int = 0
    embedding_multiplier: float = 1.0

    @classmethod
    def from_afmoe(cls, c: dict, **over) -> "HybridMoeConfig":
        """The config of an ``afmoe`` ``config.json``'s keys (``c``;
        ``Trinity-Mini``), its first ``num_hidden_layers`` layers:
        ``layer_types`` is the pattern, ``num_dense_layers`` the leading
        dense layers, ``route_norm`` / ``route_scale`` the router's
        weights, ``mup_enabled`` the embedding's multiplier; one pair of
        head counts and sizes for both kinds, rotary over all the lanes.
        ``over`` replaces fields (the router's published width where the
        file's ``num_experts`` counts the experts held)."""
        n = c["num_hidden_layers"]
        heads = dict(num_attention_heads=c["num_attention_heads"],
                     num_key_value_heads=c["num_key_value_heads"],
                     head_dim=c["head_dim"], v_head_dim=c["head_dim"])
        return replace(cls(
            **heads, **{"swa_" + k: v for k, v in heads.items()},
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            moe_intermediate_size=c["moe_intermediate_size"],
            num_hidden_layers=n,
            hybrid_layer_pattern=tuple(int(t == "sliding_attention")
                                       for t in c["layer_types"][:n]),
            moe_layer_freq=tuple(int(i >= c["num_dense_layers"])
                                 for i in range(n)),
            sliding_window=c["sliding_window"],
            add_swa_attention_sink_bias=False, partial_rotary_factor=1.0,
            rope_theta=float(c["rope_theta"]),
            swa_rope_theta=float(c["rope_theta"]), attention_value_scale=1.0,
            n_routed_experts=c["num_experts"],
            num_experts_per_tok=c["num_experts_per_tok"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            norm_topk_prob=bool(c["route_norm"]),
            routed_scaling_factor=float(c["route_scale"]),
            layernorm_epsilon=float(c["rms_norm_eps"]),
            max_position_embeddings=c["max_position_embeddings"],
            attention_gate=True, qk_norm=True, rope_on_full=False,
            sandwich_norm=True, n_shared_experts=c["num_shared_experts"],
            embedding_multiplier=math.sqrt(c["hidden_size"])
            if c["mup_enabled"] else 1.0), **over)

    def __post_init__(self):
        n = self.num_hidden_layers
        assert len(self.hybrid_layer_pattern) == len(self.moe_layer_freq) == n
        # one query layout and one pair of head sizes for both kinds: what
        # differs between them is the KV heads, the base, the window, the sink
        assert self.swa_num_attention_heads == self.num_attention_heads
        assert (self.swa_head_dim, self.swa_v_head_dim) == \
            (self.head_dim, self.v_head_dim)
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert self.num_attention_heads % self.swa_num_key_value_heads == 0
        assert self.rotary_dim % 2 == 0
        assert 0 in self.hybrid_layer_pattern and 1 in self.hybrid_layer_pattern

    @property
    def rotary_dim(self) -> int:
        return int(self.partial_rotary_factor * self.head_dim)

    def layers_of(self, kind: int) -> Tuple[int, ...]:
        """The layers of one attention kind (0 full, 1 window), in order:
        a layer's place here is its layer in that kind's pools."""
        return tuple(i for i, k in enumerate(self.hybrid_layer_pattern)
                     if k == kind)

    def kv_heads(self, kind: int) -> int:
        return self.swa_num_key_value_heads if kind \
            else self.num_key_value_heads

    def has_sink(self, kind: int) -> bool:
        return bool(self.add_swa_attention_sink_bias if kind
                    else self.add_full_attention_sink_bias)

    def has_rope(self, kind: int) -> bool:
        return bool(kind or self.rope_on_full)

    @property
    def counters(self) -> Tuple[str, ...]:
        """The names of the counts the program makes for this model."""
        return COUNTERS + (AFMOE_COUNTERS if self.n_shared_experts else ())


def hybrid_moe_param_shapes(cfg: HybridMoeConfig,
                            experts_held: Optional[Tuple[int, int]] = None
                            ) -> Dict[str, Tuple[int, ...]]:
    """Every parameter's shape, the routed experts' as this chip holds
    them (``experts_held``; default all)."""
    d, H, dq, dv = (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
                    cfg.v_head_dim)
    f, F, E = (cfg.moe_intermediate_size, cfg.intermediate_size,
               cfg.n_routed_experts)
    lo, hi = experts_held or (0, E)
    held, fs = hi - lo, cfg.n_shared_experts * f
    s = {"tok_emb": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
         "norm_f": (d,)}
    for i, kind in enumerate(cfg.hybrid_layer_pattern):
        hkv = cfg.kv_heads(kind)
        layer = {"norm1": (d,), "wq": (d, H * dq), "wk": (d, hkv * dq),
                 "wv": (d, hkv * dv), "wo": (H * dv, d), "norm2": (d,)}
        if cfg.has_sink(kind):
            layer["sink"] = (H,)
        if cfg.attention_gate:
            layer["wgate"] = (d, H * dv)
        if cfg.qk_norm:
            layer.update(q_norm=(dq,), k_norm=(dq,))
        if cfg.sandwich_norm:
            layer.update(norm1_post=(d,), norm2_post=(d,))
        if cfg.moe_layer_freq[i]:
            layer.update(router=(d, E), router_bias=(E,), wg=(held, d, f),
                         wu=(held, d, f), wd=(held, f, d))
            if fs:
                layer.update(sg=(d, fs), su=(d, fs), sd=(fs, d))
        else:
            layer.update(wg=(d, F), wu=(d, F), wd=(F, d))
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def hybrid_moe_init(cfg: HybridMoeConfig, key, dtype=jnp.float32,
                    experts_held: Optional[Tuple[int, int]] = None) -> Params:
    """Seeded weights in ``dtype``: products normal over the square root
    of their fan-in (residual outputs divided by ``sqrt(2 x layers)``),
    norm gains near one, a small correction bias and sinks n(0, 1), so
    that a term left out shows.  (The benchmark makes its own,
    ``perfbench/reference/mimo_v2.py``.)"""
    res = (2.0 * cfg.num_hidden_layers) ** -0.5
    p = {}
    for i, (name, shape) in enumerate(sorted(
            hybrid_moe_param_shapes(cfg, experts_held).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        kind = name.split("_", 1)[-1]
        if "norm" in name:
            z = 1.0 + 0.1 * z
        elif kind == "router_bias":
            z = 0.01 * z
        elif name == "tok_emb":
            z = 0.1 * z
        elif kind != "sink":
            z = z * shape[-2] ** -0.5 * (res if kind in ("wo", "wd", "sd")
                                         else 1.0)
        p[name] = z.astype(dtype)
    return p


def name_of(kind: int) -> str:
    """The cache kind of an attention kind, and its calls' name in a
    device trace (``_paged_call_w<W>_t<T>_<full|window>_<decode|prefill>``)."""
    return "window" if kind else "full"


def _ring_positions(first_block, width: int, block_size: int):
    """The cache position of every slot of a ring table's gathered pages,
    ``(B, width * block_size)``: column ``s`` holds the one logical block
    of ``first_block .. first_block + width - 1`` that is ``s`` modulo
    ``width``."""
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    block = first_block[:, None] + (col - first_block[:, None]) % width
    return (block[:, :, None] * block_size
            + jnp.arange(block_size, dtype=jnp.int32)).reshape(
        first_block.shape[0], width * block_size)


def hybrid_moe_decode(params: Params, tokens, positions, lengths, pools,
                      block_tables, cfg: HybridMoeConfig, *,
                      attention_kernel: Optional[str] = None,
                      experts_held: Optional[Tuple[int, int]] = None,
                      max_len: Optional[int] = None):
    """Cache-aware forward over the two kinds' paged pools: ``pools`` is
    ``(k_full, v_full, k_window, v_window)``, each ``(its kind's layers,
    its kind's blocks, block_size, KV heads x lanes)``, and
    ``block_tables`` is ``(full (B, W), window (B, Wr))`` — the window
    kind's a RING: the logical block ``b`` of a row sits in column ``b %
    Wr``, and the row holds no block that its first query here cannot
    see (but for one of slack).

    Arguments otherwise as ``transformer_lm_decode``.  Returns ``(logits
    (B, T, vocab) float32, pools, aux)``; ``aux`` is the dict of this
    call's counts (``cfg.counters``, and with a share of the experts held
    ``sdar_moe.TRIP_COUNTERS``; docs/observability.md), made on the
    device from what the program itself saw: valid queries only, except
    ``experts_touched``, which counts the experts whose weights the
    products read."""
    from ..ops import paged_attention as _pa
    from ..ops import pallas_kernels as _pk

    B, T = tokens.shape
    H, dq, dv, dr = (cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim,
                     cfg.rotary_dim)
    win = cfg.sliding_window
    pools = list(pools)
    tables = [jnp.asarray(t, jnp.int32) for t in block_tables]
    bs = pools[0].shape[2]
    positions, valid, phys_f, offs = paged_write_coords(
        positions, lengths, tables[0], bs, max_len
        or cfg.max_position_embeddings)
    ring = tables[1].shape[1]
    phys = (phys_f, jnp.where(valid, jnp.take_along_axis(
        tables[1], (positions // bs) % ring, axis=1), 0))
    use_kernel = (_pk.pallas_enabled() if attention_kernel is None
                  else attention_kernel == "paged")
    max_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)
    scale = float(dq) ** -0.5
    if not use_kernel:
        pos_f = jnp.arange(tables[0].shape[1] * bs, dtype=jnp.int32)[None]
        pos_w = _ring_positions(
            jnp.maximum(positions[:, 0] - (win - 1), 0) // bs, ring, bs)
        at = positions[:, :, None]
        masks = (pos_f[:, None, :] <= at,
                 (pos_w[:, None, :] <= at) & (pos_w[:, None, :] > at - win))
    lo, hi = experts_held or (0, cfg.n_routed_experts)
    valid_flat = valid.reshape(-1)
    reads = (jnp.sum(jnp.where(valid, positions + 1, 0)),
             jnp.sum(jnp.where(valid, jnp.minimum(positions + 1, win), 0)))
    zero = jnp.zeros((), jnp.int32)
    phase = "decode" if T == 1 else "prefill"
    aux = dict.fromkeys(cfg.counters, zero)
    for kind in (0, 1):
        aux[name_of(kind) + ("_ctx_tokens" if T == 1 else "_prefill_pairs")] \
            = reads[kind]
    if use_kernel and T == 1:
        # the tiles body's trips over the window layers' calls: one a live
        # row a layer is a trip as long as a window's reach
        aux["window_decode_trips"] = sum(cfg.hybrid_layer_pattern) \
            * _pa.tiles_decode_trips(
                positions, max_pos, pools[2], pools[3], ring,
                groups=H // cfg.kv_heads(1), window=win)
    if T == 1 and "window_rows_past" in aux:
        aux["window_rows_past"] = jnp.sum(
            valid & (positions >= win)).astype(jnp.int32)
    eps = cfg.layernorm_epsilon
    at_kind = [0, 0]            # the next layer of each kind's pools
    scope = jax.named_scope     # docs/observability.md "Device scopes"
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)      # (B, T, d)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
    for i, kind in enumerate(cfg.hybrid_layer_pattern):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        hkv, li = cfg.kv_heads(kind), at_kind[kind]
        at_kind[kind] += 1
        theta = cfg.swa_rope_theta if kind else cfg.rope_theta

        def heads_of(name, n):
            """A projection cut into ``n`` heads, normed over each head's
            lanes where the model norms it, rotate-half over the first
            ``dr`` lanes where this layer's kind has rotary."""
            with scope("attn.proj"):
                t = _mm_as_stored(h, g(name)).reshape(B, T, n, dq)  # noqa: B023
            if cfg.qk_norm:
                with scope("attn.qk_norm"):
                    t = _rms(t, g(name[1] + "_norm"), eps)
            if not cfg.has_rope(kind):  # noqa: B023
                return t
            with scope("attn.proj"):
                if dr == dq:
                    return _rope(t, positions, theta)  # noqa: B023
                return jnp.concatenate(
                    [_rope(t[..., :dr], positions, theta), t[..., dr:]],  # noqa: B023
                    axis=-1)

        def added(y, norm, into):
            """``x`` and a branch's output (the sum in the scope ``into``),
            normed first where the model norms it (sandwich)."""
            if cfg.sandwich_norm:
                with scope("norm.post"):
                    y = _rms(y, g(norm), eps)
            with scope(into):
                return x + y  # noqa: B023

        with scope(f"layer{i}"):
            with scope("norm"):
                h = _rms(x, g("norm1"), eps)
            q, k = heads_of("wq", H), heads_of("wk", hkv)
            with scope("attn.proj"):
                v = _mm(h, g("wv")) * cfg.attention_value_scale
            if cfg.attention_gate:
                with scope("attn.gate"):
                    gate = jax.nn.sigmoid(_mm(h, g("wgate")))
            with scope("attn.cache_write"):
                k_pool, v_pool = pools[2 * kind], pools[2 * kind + 1]
                k_pool = k_pool.at[li, phys[kind], offs].set(
                    k.reshape(B, T, hkv * dq).astype(k_pool.dtype))
                v_pool = v_pool.at[li, phys[kind], offs].set(
                    v.astype(v_pool.dtype))
                pools[2 * kind], pools[2 * kind + 1] = k_pool, v_pool
            sink = g("sink") if cfg.has_sink(kind) else None
            with scope("attn.kernel"):
                if use_kernel:
                    a = _pa.paged_attention(
                        q, k_pool, v_pool, tables[kind], positions, max_pos,
                        scale=scale, layer=li,
                        call=f"{name_of(kind)}_{phase}",
                        window=win if kind else 0, sink=sink, tiles=True)
                else:
                    gather = lambda pool, w: pool[li][tables[kind]].reshape(  # noqa: E731,B023
                        B, -1, hkv, w)
                    a = _pa.paged_attention_reference(
                        q, gather(k_pool, dq), gather(v_pool, dv),
                        masks[kind], scale, sink)
            a = a.reshape(B, T, H * dv)
            if cfg.attention_gate:
                with scope("attn.gate"):
                    a = a * gate
            with scope("attn.proj"):
                y = _mm(a, g("wo"))
            x = added(y, "norm1_post", "attn.proj")
            with scope("norm"):
                h = _rms(x, g("norm2"), eps)
            if not cfg.moe_layer_freq[i]:
                with scope("ffn"):
                    y = _gated(h, g("wg"), g("wu"), g("wd"))
                x = added(y, "norm2_post", "ffn")
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
            if cfg.n_shared_experts:
                with scope("moe.shared"):
                    y = y + _gated(hf, g("sg"), g("su"), g("sd"))
                    aux["shared_expert_tokens"] += jnp.sum(
                        valid_flat).astype(jnp.int32)
            x = added(y.reshape(B, T, -1), "norm2_post", "moe.combine")
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
    return logits, tuple(pools), aux


@dataclass(frozen=True)
class HybridMoeLM:
    """The model as the generation engine takes one (the seam of
    ``serving/generation/programs.py``): one token a row a step
    (``block_len`` 0, so it rides the step in flight), a cache of two
    kinds (``cache_spec``), the chip's share of the routed experts
    (``experts_held``), and the counts its program hands back
    (``counters``).  ``max_len`` is the service's longest position."""
    cfg: HybridMoeConfig
    max_len: int
    experts_held: Optional[Tuple[int, int]] = None
    kv_dtype: object = jnp.bfloat16
    # the longest chunk a prefill program takes on the chip.  A prompt is
    # cheapest in the longest chunks: the held experts' products are bound
    # by their weights' bytes (a `_gmm_call` launch reads 0.383 ms at 512
    # rows and 0.407 at 1,024 on `mimo-v2.5`).  What grows with the chunk
    # is the program's temporaries (the queries, the assignments' gathered
    # rows, the logits of every position: 0.83 GB at 1,024 over
    # `trinity-mini`'s vocabulary), the window kind's pool and its chunk
    # call's ring.  512 -> 1,024 read +10.2% tokens on `mimo-v2.5`; 2,048
    # read +1.4% / +1.2% more on the two cells and left `trinity-mini`
    # 0.56 GB of the chip's memory (15.19 GB): not worth a cell that can
    # fail (PERF.md, PR 50)
    longest_chunk: int = 1024
    block_len = 0
    offers = frozenset({"sampling"})
    # the tiles body fetches the pages a tile reads and no others, for a
    # chunk as for one token: a table's width costs nothing, so the
    # service keeps one
    one_table_width = True

    @property
    def counters(self) -> Tuple[str, ...]:
        """The names of the program's counts: with a share of the experts
        held, the expert layers' trips too."""
        return self.cfg.counters + trip_counters(self.experts_held,
                                                 self.cfg.n_routed_experts)

    @property
    def vocab(self) -> int:
        return self.cfg.vocab_size

    @property
    def heads(self) -> int:
        return self.cfg.num_attention_heads

    def cache_spec(self) -> dict:
        """Two kinds: ``full`` keeps every position and is sized by
        tokens; ``window`` keeps what ``sliding_window`` positions can
        still see and is sized by rows.  K pages hold a head's lanes as
        they are: ``mimo_v2``'s 192 beside V pages of 128 (PERF.md PR 32
        has the chip's reading against 256 padded), ``afmoe``'s 128 and
        128."""
        c = self.cfg

        def kind(k, **more):
            return dict(name=name_of(k), n_layers=len(c.layers_of(k)),
                        pools=(("k", c.kv_heads(k) * c.head_dim),
                               ("v", c.kv_heads(k) * c.v_head_dim)), **more)

        return dict(dtype=self.kv_dtype,
                    kinds=(kind(0), kind(1, window=c.sliding_window)))

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        the two kinds' pools, one after the other, ``block_tables`` a
        table a kind; returns ``(logits, pools, aux)``.  No mesh is
        offered, so ``mp_mesh`` is always None."""
        return hybrid_moe_decode(
            params, tokens, positions, lengths, pools, block_tables,
            self.cfg, attention_kernel=attention_kernel,
            experts_held=self.experts_held, max_len=self.max_len)

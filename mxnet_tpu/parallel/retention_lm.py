"""A Qwen3 block whose attention is POWER RETENTION: a gated linear layer
over a recurrent state (``model_type`` ``brumby``, as ``Brumby-14B-Base``'s
``config.json`` carries the block's keys; docs/generation.md "Cache kinds").

The layer, with ``x`` the residual stream, ``t`` a position, ``n`` a KV
head, ``m`` a query head of its group, ``d`` the head size, degree 2::

    h = rms(x, g1)
    q = rope(rms(h Wq, gq));  k = rope(rms(h Wk, gk));  v = h Wv      (per head)
    g[t,n] = log sigmoid((h Wgate + bgate)[n])            float32, <= 0
    S[t,n] = e^g[t,n] S[t-1,n] + phi(k[t,n]) v[t,n]^T     z[t,n] = e^g[t,n] z[t-1,n] + phi(k[t,n])
    o[t,m] = phi(q[t,m])^T S[t,n] / (phi(q[t,m]) . z[t,n] + eps)
    x = x + o Wo;   x = x + (silu(y Wg) * (y Wu)) Wd,  y = rms(x, g2)
    logits = rms(x, gf) Wh                                (untied head)

``phi`` is the symmetric square (``phi(a) . phi(b) = (a . b)^2``), kept in
the layout of ``ops/retention.py``.  The projections, norms, rotary
embedding and head are ``sdar_moe``'s (the same Qwen3 lineage), the gated
feed-forward ``latent_moe``'s dense layer.

The cache is ONE kind and it is no pages: ``state`` — a layer's ``S`` over
its ``z`` a slot, float32, indexed by the slot a row's one-column table names
(``serving/generation/kv_cache.py::CacheKind``).  A row whose chunk starts
at position 0 starts from the zero state inside the program; a padded
position and an idle row leave the state as it was (``g = 0``, ``k = 0``).
Nothing of a position is kept, so there is no prefix to share and no draft
to roll back: the model offers ``sampling`` alone.

Parameters are a flat dict in ONE dtype and are never cast in the program:
products take operands in that dtype and accumulate in float32; the
residual stream, norms, gates, their sums, the state and the normaliser
are float32, and so are the operands of the kernels' products over the
expansions (at the MXU's highest precision).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .latent_moe import _gated
from .sdar_moe import _mm, _mm_as_stored, _rms, _rope

Params = Dict[str, jnp.ndarray]

__all__ = ["RetentionConfig", "RetentionLM", "retention_lm_decode",
           "retention_param_shapes"]

COUNTERS = ("retention_decode_rows", "retention_prefill_tokens",
            "retention_prefill_chunks", "retention_prefill_pairs",
            "retention_rows_started")


@dataclass(frozen=True)
class RetentionConfig:
    """The published ``config.json`` keys that shape the model (defaults:
    ``Brumby-14B-Base``), and what the config does not carry: the power's
    degree and the normaliser's epsilon."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    power: int = 2
    retention_eps: float = 1e-6

    def __post_init__(self):
        assert self.num_attention_heads % self.num_key_value_heads == 0
        # the state's layout is the symmetric SQUARE's
        assert self.power == 2 and self.head_dim % 2 == 0


def retention_param_shapes(cfg: RetentionConfig) -> Dict[str, Tuple[int, ...]]:
    d, D, F = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    hq, hkv = cfg.num_attention_heads * D, cfg.num_key_value_heads * D
    s = {"tok_emb": (cfg.vocab_size, d), "head": (d, cfg.vocab_size),
         "norm_f": (d,)}
    for i in range(cfg.num_hidden_layers):
        layer = {"norm1": (d,), "wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv),
                 "wo": (hq, d), "q_norm": (D,), "k_norm": (D,),
                 "wgate": (d, cfg.num_key_value_heads),
                 "bgate": (cfg.num_key_value_heads,), "norm2": (d,),
                 "wg": (d, F), "wu": (d, F), "wd": (F, d)}
        s.update({f"l{i}_{n}": shape for n, shape in layer.items()})
    return s


def retention_lm_decode(params: Params, tokens, positions, lengths, pools,
                        block_tables, cfg: RetentionConfig, *,
                        kernel: bool, max_len: int):
    """State-aware forward: ``pools`` is ``(state,)`` (``ops/retention.py``'s
    ``state_shapes``, a layer and a slot ahead), ``block_tables`` ``(B, 1)``
    — the slot each row's state lives in, 0 (the scratch) for an idle row.
    Arguments otherwise as ``transformer_lm_decode``.  Returns ``(logits
    (B, T, vocab) float32, pools, aux)``; ``aux`` is the dict of this
    call's counts (``COUNTERS``; docs/observability.md)."""
    from ..ops.retention import retention

    B, T = tokens.shape
    Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    (pool,) = pools
    positions = jnp.clip(jnp.asarray(positions, jnp.int32), 0, max_len - 1)
    lengths = jnp.asarray(lengths, jnp.int32)
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lengths[:, None]
    live = lengths > 0
    slots = jnp.where(live, jnp.asarray(block_tables, jnp.int32)[:, 0], 0)
    # a chunk that starts at position 0 starts from the zero state
    fresh = live & (positions[:, 0] == 0)
    # the program's own counts (docs/observability.md): rows a decode step
    # fed; tokens, row-chunks and causal (query, key) pairs of a chunk call;
    # chunks that began at position 0
    n_valid = jnp.sum(lengths)
    zero = jnp.zeros((), jnp.int32)
    chunk = dict(retention_prefill_tokens=n_valid,
                 retention_prefill_chunks=jnp.sum(live).astype(jnp.int32),
                 retention_prefill_pairs=jnp.sum(lengths * (lengths + 1)
                                                 // 2))
    aux = dict(dict.fromkeys(COUNTERS, zero),
               retention_rows_started=jnp.sum(fresh).astype(jnp.int32),
               **(dict(retention_decode_rows=n_valid) if T == 1 else chunk))
    eps = cfg.rms_norm_eps
    scope = jax.named_scope     # docs/observability.md "Device scopes"
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)      # (B, T, d)
    for i in range(cfg.num_hidden_layers):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        with scope(f"layer{i}"):
            with scope("norm"):
                h = _rms(x, g("norm1"), eps)
            with scope("attn.proj"):
                # flat products, cut into heads behind the barrier: cut
                # straight behind them, the chip's compiler turns the
                # WEIGHTS round to suit the cut (52 MB of ``wq`` a layer a
                # call; docs/generation.md "A weight reaches its product
                # as stored")
                q = _rope(_rms(_mm_as_stored(h, g("wq")).reshape(B, T, Hq, D),
                               g("q_norm"), eps), positions, cfg.rope_theta)
                k = _rope(_rms(_mm_as_stored(h, g("wk")).reshape(B, T, Hkv,
                                                                 D),
                               g("k_norm"), eps), positions, cfg.rope_theta)
                v = _mm_as_stored(h, g("wv")).reshape(B, T, Hkv, D)
                gate = jax.nn.log_sigmoid(
                    _mm(h, g("wgate")) + g("bgate").astype(jnp.float32))
            with scope("attn.retention"):
                with scope("expand"):
                    # a padded position and an idle row are identities
                    k = jnp.where(valid[:, :, None, None], k, 0.0)
                    gate = jnp.where(valid[:, :, None], gate, 0.0)
                with scope("kernel"):
                    o, pool = retention(
                        q, k, v, gate, fresh, pool, slots, layer=i,
                        eps=cfg.retention_eps, kernel=kernel)
            with scope("attn.proj"):
                x = x + _mm(o.reshape(B, T, Hq * D), g("wo"))
            with scope("norm"):
                h = _rms(x, g("norm2"), eps)
            with scope("ffn"):
                x = x + _gated(h, g("wg"), g("wu"), g("wd"))
    with scope("head"):
        logits = _mm(_rms(x, params["norm_f"], eps), params["head"])
    return logits, (pool,), aux


@dataclass(frozen=True)
class RetentionLM:
    """The model as the generation engine takes one (the seam of
    ``serving/generation/programs.py``): one token a row a step
    (``block_len`` 0, so it rides the step in flight), a cache of ONE kind
    that is a slot's state (``cache_spec``), and the counts its program
    hands back (``counters``).  ``max_len`` is the service's longest
    position."""
    cfg: RetentionConfig
    max_len: int
    # the longest chunk a prefill program takes: the scan's temporaries
    # (the group's queries, a chunk's square) grow with it
    longest_chunk: int = 512
    block_len = 0
    offers = frozenset({"sampling"})
    counters = COUNTERS

    @property
    def vocab(self) -> int:
        return self.cfg.vocab_size

    @property
    def heads(self) -> int:
        return self.cfg.num_attention_heads

    def cache_spec(self) -> dict:
        """One kind, ``state``: no pages, a fixed array a layer a slot —
        sized by the slots alone (docs/generation.md "Cache kinds")."""
        from ..ops.retention import state_shapes

        c = self.cfg
        return dict(dtype=jnp.float32, kinds=(dict(
            name="state", n_layers=c.num_hidden_layers,
            state=state_shapes(c.num_key_value_heads, c.head_dim,
                               c.head_dim)),))

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        the state's one pool, ``block_tables`` the rows' slots; returns
        ``(logits, pools, aux)``.  No mesh is offered, so ``mp_mesh`` is
        always None."""
        return retention_lm_decode(
            params, tokens, positions, lengths, pools, block_tables,
            self.cfg, kernel=attention_kernel == "paged",
            max_len=self.max_len)

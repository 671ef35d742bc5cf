"""Decoder-only Transformer LM, mesh-first (SURVEY §5.7 long-context).

The reference era treats sequence length as a single-device axis; this
module is the capability the survey calls out as first-class here: a
language model whose TRAINING STEP is laid out over a ``Mesh`` with the
batch on ``dp`` and the sequence on ``sp``, attention running as a ring
(`ring_attention`, flash-style m/l accumulators, causal across shard
boundaries) so each device holds T/sp of every activation — the memory
that bounds context length.  Everything else in the block (embeddings,
LayerNorm, MLP) is pointwise over the sequence, so sp-sharding them is
free; gradients are psum'd over the mesh and the replicated params stay
bit-identical on every shard.

Design notes (tpu-first):
- params are a flat dict of jnp arrays; the apply fn is pure and takes the
  attention callable as a parameter — `local_attention` single-device,
  `ring_attention` inside shard_map.  One model definition, no divergence.
- tied input/output embeddings (d_model-major matmuls for the MXU).
- the sharded step is ONE compiled program: shard_map(jit) over the whole
  forward/backward/update, collectives only where math requires them
  (ring ppermute inside attention, one grad psum).

Oracles: tests/test_transformer_lm.py checks the sp-sharded forward and
train step against the single-device model to 1e-3.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import local_attention, ring_attention

Params = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_len: int = 512

    @property
    def d_head(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


@dataclass(frozen=True)
class TransformerLM:
    """GPT-2's block as the generation engine takes a model (the seam of
    ``serving/generation/programs.py``): the cache-aware step
    (:func:`transformer_lm_decode`), vocabulary, longest position, the
    heads a model-parallel mesh must divide, and the cache spec
    :class:`~mxnet_tpu.serving.generation.kv_cache.PagedKVCache` is built
    from.  One token a row a step (``block_len`` 0); every program family
    of the engine is offered."""
    cfg: TransformerConfig
    compute_dtype: object = None
    block_len = 0
    offers = frozenset({"sampling", "speculative", "int8", "mp", "amp"})

    @property
    def vocab(self) -> int:
        return self.cfg.vocab

    @property
    def max_len(self) -> int:
        return self.cfg.max_len

    @property
    def heads(self) -> int:
        return self.cfg.n_heads

    def cache_spec(self) -> dict:
        return dict(n_layers=self.cfg.n_layers, n_heads=self.cfg.n_heads,
                    d_head=self.cfg.d_head,
                    dtype=self.compute_dtype or jnp.float32)

    def step(self, params, tokens, positions, lengths, pools, block_tables,
             *, attention_kernel, mp_mesh=None, call=None, want_logits=True):
        """The serving seam's one contract (``programs.py``): ``pools`` is
        ``(k, v)`` or, for the int8 pool, ``(k, v, k_scale, v_scale)``;
        returns ``(logits, pools, aux)`` with ``pools`` as it came in.
        GPT-2's block names its kernel calls by their shape and always
        computes logits (``call`` / ``want_logits`` are the block-diffusion
        model's), and has nothing else to hand back (``aux`` None)."""
        k_pool, v_pool, *scales = pools
        k_scale, v_scale = scales or (None, None)
        logits, *pools = transformer_lm_decode(
            params, tokens, positions, lengths, k_pool, v_pool,
            block_tables, self.cfg, compute_dtype=self.compute_dtype,
            attention_kernel=attention_kernel, mp_mesh=mp_mesh,
            k_scale=k_scale, v_scale=v_scale)
        return logits, tuple(pools), None


def transformer_lm_init(cfg: TransformerConfig, key) -> Params:
    """Scaled-normal init; residual-out projections down-scaled by
    1/sqrt(2*n_layers) (standard GPT-2 style stabilization)."""
    def normal(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(jnp.float32)

    keys = iter(jax.random.split(key, 4 + 6 * cfg.n_layers))
    s = 1.0 / math.sqrt(cfg.d_model)
    res = s / math.sqrt(2.0 * cfg.n_layers)
    p: Params = {
        "tok_emb": normal(next(keys), (cfg.vocab, cfg.d_model), 0.02),
        "pos_emb": normal(next(keys), (cfg.max_len, cfg.d_model), 0.02),
        "lnf_g": jnp.ones((cfg.d_model,)),
        "lnf_b": jnp.zeros((cfg.d_model,)),
    }
    for i in range(cfg.n_layers):
        p[f"l{i}_ln1_g"] = jnp.ones((cfg.d_model,))
        p[f"l{i}_ln1_b"] = jnp.zeros((cfg.d_model,))
        p[f"l{i}_wqkv"] = normal(next(keys), (cfg.d_model, 3 * cfg.d_model), s)
        p[f"l{i}_wo"] = normal(next(keys), (cfg.d_model, cfg.d_model), res)
        p[f"l{i}_ln2_g"] = jnp.ones((cfg.d_model,))
        p[f"l{i}_ln2_b"] = jnp.zeros((cfg.d_model,))
        p[f"l{i}_w1"] = normal(next(keys), (cfg.d_model, cfg.d_ff), s)
        p[f"l{i}_b1"] = jnp.zeros((cfg.d_ff,))
        p[f"l{i}_w2"] = normal(next(keys), (cfg.d_ff, cfg.d_model), res)
        p[f"l{i}_b2"] = jnp.zeros((cfg.d_model,))
    return p


def _ln(x, g, b, eps=1e-5, mesh=None):
    from ..ops import pallas_kernels as _pk
    if _pk.pallas_enabled():
        # fused stats+normalize kernel (docs/pallas.md): one read one
        # write; custom-vjp backward keeps training grads exact
        ln = lambda x, g, b: _pk.layer_norm_fused(  # noqa: E731
            x, g, b, eps=eps).astype(x.dtype)
        if mesh is not None:
            # inside a GSPMD-partitioned program (mp serving) the compiler
            # cannot partition an opaque Mosaic call: run it per device in
            # a shard_map over the replicated activations
            ln = jax.shard_map(ln, mesh=mesh, in_specs=(P(), P(), P()),
                               out_specs=P(), check_vma=False)
        return ln(x, g, b)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def transformer_lm_apply(params: Params, tokens, positions,
                         cfg: TransformerConfig, attention=None):
    """Logits for next-token prediction.

    tokens: (B, T) int32 — T may be the LOCAL sequence block under sp.
    positions: (T,) int32 GLOBAL positions of those columns.
    attention: (q, k, v) -> out with shapes (B, T, H, Dh); defaults to the
    single-device `local_attention(causal=True)`.
    """
    if attention is None:
        attention = functools.partial(local_attention, causal=True)
    B, T = tokens.shape
    if T == 1:
        # single-position decode path: a one-row dynamic slice instead of a
        # gather against the full (max_len, d_model) table
        pe = jax.lax.dynamic_slice_in_dim(params["pos_emb"],
                                          positions[0], 1, axis=0)
    else:
        pe = params["pos_emb"][positions]
    x = params["tok_emb"][tokens] + pe[None, :, :]
    for i in range(cfg.n_layers):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        h = _ln(x, g("ln1_g"), g("ln1_b"))
        qkv = h @ g("wqkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        to_heads = lambda t: t.reshape(B, T, cfg.n_heads, cfg.d_head)
        o = attention(to_heads(q), to_heads(k), to_heads(v))
        x = x + o.reshape(B, T, cfg.d_model) @ g("wo")
        h = _ln(x, g("ln2_g"), g("ln2_b"))
        x = x + jax.nn.gelu(h @ g("w1") + g("b1")) @ g("w2") + g("b2")
    x = _ln(x, params["lnf_g"], params["lnf_b"])
    return x @ params["tok_emb"].T  # tied embeddings


def _scatter_kv_quantized(pool, scale, layer: int, vals, tables, positions,
                          valid, max_pos, nt: int):
    """Quantizing scatter into layer ``layer`` of the int8 paged pool
    (docs/quantization.md), in place: the touched blocks are gathered
    from and scattered back into the WHOLE layered pool, so no one-layer
    slice of it is ever made (a slice of a donated pool is a copy).

    Only the ``nt`` logical blocks this chunk's contiguous positions can
    touch are gathered (decode: exactly one block per row), dequantized
    with their current per-(block, head) scales, updated with the chunk's
    float K/V, re-scaled from the masked absmax over the WRITTEN prefix,
    requantized, and scattered back.  Untouched blocks keep their bits;
    a touched block whose scale is unchanged requantizes to identical
    int8 (the absmax entry stores as exactly ±127, so ``round(q*s/s)``
    is the identity) — per-row bits are a pure function of the row's own
    write history, which is what makes greedy tokens batch-composition-
    independent under int8.

    pool: (n_layers, num_blocks, bs, H*D) int8; scale: (n_layers,
    num_blocks, H) f32; vals: (B, T, H, D) float; tables: (B, W) int32;
    positions/valid: (B, T); max_pos: (B,) last valid position AFTER this
    write (-1 for inactive rows).  Returns (pool, scale).
    """
    B, T, H, D = vals.shape
    bs = pool.shape[2]
    W = tables.shape[1]
    # positions are contiguous per row, so the row's first entry names the
    # first touched logical block (all-invalid rows write to block 0)
    l0 = positions[:, 0] // bs                                     # (B,)
    tl = l0[:, None] + jnp.arange(nt, dtype=jnp.int32)[None, :]    # (B, nt)
    row_live = jnp.any(valid, axis=1)
    j_ok = (tl < W) & row_live[:, None]
    tphys = jnp.where(
        j_ok, jnp.take_along_axis(tables, jnp.minimum(tl, W - 1), axis=1),
        0)
    blk = pool[layer, tphys].reshape(B, nt, bs, H, D).astype(jnp.float32) \
        * scale[layer, tphys][:, :, None, :, None]   # (B, nt, bs, H, D)
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    j = jnp.clip(positions // bs - l0[:, None], 0, nt - 1)
    o = positions % bs
    cur = blk[bidx, j, o]
    blk = blk.at[bidx, j, o].set(
        jnp.where(valid[..., None, None], vals.astype(jnp.float32), cur))
    # per-(block, head) scale from the masked absmax over the written
    # prefix only — unwritten tail garbage (and freshly re-allocated
    # blocks' stale bits) never pollutes the scale
    pos_of = tl[:, :, None] * bs \
        + jnp.arange(bs, dtype=jnp.int32)[None, None, :]   # (B, nt, bs)
    live = (pos_of <= max_pos[:, None, None]) & j_ok[:, :, None]
    amax = jnp.max(jnp.abs(blk) * live[..., None, None].astype(jnp.float32),
                   axis=(2, 4))                            # (B, nt, H)
    new_s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(blk / new_s[:, :, None, :, None]),
                 -127, 127).astype(jnp.int8).reshape(B, nt, bs, H * D)
    # duplicate targets only ever alias the reserved null block 0
    return pool.at[layer, tphys].set(q), scale.at[layer, tphys].set(new_s)


def _touched_blocks(T: int, block_size: int) -> int:
    """Static count of logical blocks ``T`` contiguous positions can span
    at any alignment (decode T=1 -> 1)."""
    return (T + block_size - 2) // block_size + 1


def paged_write_coords(positions, lengths, block_tables, block_size: int,
                       max_len: int):
    """Where a chunk's K/V go in the paged pool, shared by every layer and
    by every model that decodes through it: ``(positions clipped to the
    model's range, valid (B, T), physical block, offset in it)``.  Logical
    block -> physical block via the table; invalid (padded / inactive-slot)
    queries write into the reserved null block 0 instead of clobbering
    real cache."""
    T = positions.shape[1]
    W = block_tables.shape[1]
    positions = jnp.clip(jnp.asarray(positions, jnp.int32), 0, max_len - 1)
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < \
        jnp.asarray(lengths, jnp.int32)[:, None]            # (B, T)
    logical = jnp.clip(positions // block_size, 0, W - 1)
    phys = jnp.where(valid,
                     jnp.take_along_axis(block_tables, logical, axis=1), 0)
    return positions, valid, phys, positions % block_size


def transformer_lm_decode(params: Params, tokens, positions, lengths,
                          k_pool, v_pool, block_tables,
                          cfg: TransformerConfig, compute_dtype=None,
                          attention_kernel: Optional[str] = None,
                          mp_mesh=None, k_scale=None, v_scale=None):
    """Cache-aware forward: read/write a paged per-layer KV cache.

    The generation engine's one model step, serving BOTH phases
    (docs/generation.md): *prefill* feeds a whole (padded) prompt chunk and
    fills cache positions ``[0, lengths)``; *decode* feeds T=1 single
    queries per slot against their already-filled caches.  Every shape is
    static per (batch, T, table-width) signature, so sequences growing
    inside their block tables never recompile.

    The SAME path is the speculative-decoding *verify* step
    (docs/generation.md "Speculative decoding"): a (B, s+1) chunk of
    ``[pending, d_1..d_s]`` mid-sequence tokens per slot, with per-row
    ``positions`` starting at each slot's context length — because
    queries see same-chunk writes and the causal mask bounds reads at
    ``positions``, per-position logits come out exactly as s+1 sequential
    T=1 decode steps would produce them, in ONE dispatch.  Rejected
    positions need no device rollback: their entries sit at positions
    >= the post-verify context length, are never attended (causal mask)
    before being overwritten by the next chunk fed at those positions,
    and the engine's copy-on-write keeps them out of shared blocks.

    Parameters
    ----------
    tokens : (B, T) int32 — the chunk fed this call (right-padded).
    positions : (B, T) int32 — GLOBAL positions of those tokens (query i of
        row b sits at ``positions[b, i]``); padded entries may hold any
        in-range value.
    lengths : (B,) int32 — valid query count per row; rows with 0 are
        inactive decode slots (their writes are routed to the reserved null
        block 0 and their outputs are garbage).
    k_pool, v_pool : (n_layers, num_blocks, block_size, n_heads * d_head) —
        the paged cache pool, heads folded into the minor dim (the
        lane-dense block layout the paged kernel needs, docs/pallas.md);
        block 0 is the null/scratch block.
    block_tables : (B, W) int32 — logical block j of row b lives in
        physical block ``block_tables[b, j]``; the gathered context covers
        global positions ``[0, W * block_size)``.

    Returns ``(logits (B, T, vocab) float32, k_pool, v_pool)`` — pools are
    functionally updated (pass with donation to update in place).  A query
    at position p attends to cache entries at positions <= p, INCLUDING the
    k/v written from this very chunk — so a bucketed prefill followed by
    T=1 decode steps reproduces `transformer_lm_apply` logits exactly
    (tests/test_generation.py asserts rtol 1e-5, f32 and bf16).

    ``k_scale``/``v_scale`` (``(n_layers, num_blocks, n_heads)`` f32,
    docs/quantization.md) switch the pool to INT8 storage: the scatter
    quantizes the chunk's K/V in-program per (layer, block, head) and
    both attention paths dequantize at read — the gathered-dense
    reference path explicitly, the Pallas kernel inside the kernel with
    the scales riding VMEM next to the block tables.  The return grows to
    ``(logits, k_pool, v_pool, k_scale, v_scale)``; with scales omitted
    this function (and its compiled programs) is byte-identical to the
    pre-quantization layout.
    """
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(compute_dtype), params)
    B, T = tokens.shape
    n_layers, num_blocks, block_size, _ = k_pool.shape
    W = block_tables.shape[1]
    positions, valid, phys, offs = paged_write_coords(
        positions, lengths, block_tables, block_size, cfg.max_len)
    # gathered context is in LOGICAL order: flat index j holds position j
    ctx_pos = jnp.arange(W * block_size, dtype=jnp.int32)
    attn_mask = ctx_pos[None, None, :] <= positions[:, :, None]  # (B,T,W*bs)
    # bit-identical scale to local_attention's (f32 sqrt, not host f64)
    scale = 1.0 / jnp.sqrt(cfg.d_head).astype(jnp.float32)
    # TPUMX_PALLAS (docs/pallas.md): walk the block table INSIDE a Pallas
    # kernel — K/V blocks stream through VMEM, dead blocks are skipped —
    # instead of gathering the whole (B, W*bs) bucket per token.  Read at
    # trace time; =0 keeps the gather+dense path (and its programs) intact.
    # ``attention_kernel`` ("paged"/"gather") pins the choice explicitly —
    # GenerationPrograms freezes it per service.  Under an mp mesh GSPMD
    # cannot partition the opaque kernel call itself, but ``mp_mesh`` routes
    # it through a per-head shard_map (ops/paged_attention
    # .paged_attention_sharded) whenever heads divide the axis — mp-sharded
    # models decode through the fast path (docs/generation.md).
    from ..ops import pallas_kernels as _pk
    from ..ops import paged_attention as _pa
    from ..ops.paged_attention import paged_attention_reference as \
        _pa_reference
    if attention_kernel is None:
        use_paged = _pk.pallas_enabled()
    else:
        use_paged = attention_kernel == "paged"
    quantized = k_scale is not None
    if use_paged or quantized:
        # last valid query position per row; -1 (inactive slots) skips
        # every block and the row's output is garbage, same as the oracle
        max_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)
    if use_paged:
        kernel_scale = _pa.attention_scale(cfg.d_head)
    if quantized:
        nt = _touched_blocks(T, block_size)

    # device scopes (docs/observability.md "Device scopes"): every
    # operation's ``op_name`` says which layer part it belongs to; read
    # while tracing only, the lowered program is the same
    scope = jax.named_scope
    with scope("embed"):
        x = params["tok_emb"][tokens] + jnp.take(params["pos_emb"],
                                                 positions, axis=0)
    for i in range(cfg.n_layers):
        g = lambda n: params[f"l{i}_{n}"]  # noqa: B023 — read immediately
        with scope(f"layer{i}"):
            with scope("norm"):
                h = _ln(x, g("ln1_g"), g("ln1_b"), mesh=mp_mesh)
            with scope("attn.proj"):
                qkv = h @ g("wqkv")
                q, k, v = jnp.split(qkv, 3, axis=-1)
                to_heads = lambda t: t.reshape(B, T, cfg.n_heads, cfg.d_head)
                q, k, v = to_heads(q), to_heads(k), to_heads(v)
            with scope("attn.cache_write"):
                if quantized:
                    k_pool, k_scale = _scatter_kv_quantized(
                        k_pool, k_scale, i, k, block_tables, positions,
                        valid, max_pos, nt)
                    v_pool, v_scale = _scatter_kv_quantized(
                        v_pool, v_scale, i, v, block_tables, positions,
                        valid, max_pos, nt)
                else:
                    fold = lambda t: t.reshape(B, T, cfg.d_model).astype(
                        k_pool.dtype)
                    k_pool = k_pool.at[i, phys, offs].set(fold(k))
                    v_pool = v_pool.at[i, phys, offs].set(fold(v))
            with scope("attn.kernel"):
                if use_paged:
                    # the kernel is handed the WHOLE pool and the layer's
                    # index and fetches its own pages: a ``k_pool[i]``
                    # operand of an opaque kernel call would be copied, the
                    # whole pool once a step
                    kw = dict(scale=kernel_scale, layer=i,
                              k_scale=k_scale[i] if quantized else None,
                              v_scale=v_scale[i] if quantized else None)
                    if mp_mesh is not None:
                        o = _pa.paged_attention_sharded(
                            q, k_pool, v_pool, block_tables, positions,
                            max_pos, mesh=mp_mesh, axis="mp", **kw)
                    else:
                        o = _pa.paged_attention(q, k_pool, v_pool,
                                                block_tables, positions,
                                                max_pos, **kw)
                else:
                    if quantized:
                        # dequantize at read: per-(block, head) scales
                        # broadcast over the gathered context
                        # (docs/quantization.md)
                        deq = lambda pool, sc: (
                            pool[block_tables].reshape(
                                B, W, block_size, cfg.n_heads, cfg.d_head
                            ).astype(jnp.float32)
                            * sc[block_tables][:, :, None, :, None]
                        ).reshape(B, W * block_size, cfg.n_heads, cfg.d_head)
                        k_ctx = deq(k_pool[i], k_scale[i])
                        v_ctx = deq(v_pool[i], v_scale[i])
                    else:
                        k_ctx = k_pool[i][block_tables].reshape(
                            B, W * block_size, cfg.n_heads, cfg.d_head)
                        v_ctx = v_pool[i][block_tables].reshape(
                            B, W * block_size, cfg.n_heads, cfg.d_head)
                    # same numerics as ring_attention.local_attention (f32
                    # scores and accumulation), with the causal mask
                    # generalized to cache-position <= query-position —
                    # padded/unwritten slots land at exactly 0 probability
                    # (exp(-1e30 - m) underflows), so bucketed table widths
                    # never perturb real rows
                    o = _pa_reference(q, k_ctx, v_ctx, attn_mask, scale)
            with scope("attn.proj"):
                x = x + o.reshape(B, T, cfg.d_model) @ g("wo")
            with scope("norm"):
                h = _ln(x, g("ln2_g"), g("ln2_b"), mesh=mp_mesh)
            with scope("ffn"):
                x = x + jax.nn.gelu(h @ g("w1") + g("b1")) @ g("w2") \
                    + g("b2")
    with scope("head"):
        x = _ln(x, params["lnf_g"], params["lnf_b"],
                mesh=mp_mesh)
        logits = x @ params["tok_emb"].T
    if quantized:
        return logits.astype(jnp.float32), k_pool, v_pool, k_scale, v_scale
    return logits.astype(jnp.float32), k_pool, v_pool


def lm_loss(params: Params, tokens, labels, positions,
            cfg: TransformerConfig, attention=None, mask=None,
            compute_dtype=None):
    """Mean next-token cross-entropy; `mask` (B, T) optionally excludes
    positions (e.g. padding) from the mean.  ``compute_dtype=jnp.bfloat16``
    casts params for the forward (f32 master weights stay outside — the
    MXU recipe of docs/amp.md)."""
    if compute_dtype is not None:
        params = jax.tree_util.tree_map(
            lambda p: p.astype(compute_dtype), params)
    logits = transformer_lm_apply(params, tokens, positions, cfg, attention)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_step(params, momenta, tokens, labels, positions, cfg,
               lr=0.1, momentum=0.9, attention=None, compute_dtype=None):
    """Single-device SGD-momentum step (the oracle for the sharded one)."""
    loss, grads = jax.value_and_grad(lm_loss)(params, tokens, labels,
                                              positions, cfg,
                                              attention=attention,
                                              compute_dtype=compute_dtype)
    momenta = jax.tree_util.tree_map(lambda m, g: momentum * m + g,
                                     momenta, grads)
    params = jax.tree_util.tree_map(lambda p, m: p - lr * m, params, momenta)
    return loss, params, momenta


def make_sharded_train_step(mesh: Mesh, cfg: TransformerConfig,
                            lr=0.1, momentum=0.9, sp_impl: str = "ring",
                            compute_dtype=None):
    """One compiled dp×sp training step.

    Layout: tokens/labels (B, T) sharded P('dp', 'sp'); positions (T,)
    sharded P('sp'); params/momenta replicated.  Attention over 'sp' is
    the causal ring (``sp_impl="ring"``: ppermute k/v blocks, activation
    memory stays T/sp everywhere) or Ulysses (``sp_impl="ulysses"``:
    all_to_all to head-sharding, full-sequence local attention — fewer
    collective hops, but requires n_heads % sp == 0 and holds full-T
    activations inside attention).  The per-shard mean loss is weighted
    into the global mean and grads are psum'd over both axes, so the
    replicated update is identical everywhere.  Returns
    step(params, momenta, tokens, labels, positions)
    -> (loss, params, momenta), jitted with donated carries.
    """
    axes = ("dp", "sp")
    repl, data = P(), P("dp", "sp")
    if sp_impl in ("ulysses", "ulysses_flash"):
        from .sequence_parallel import ulysses_attention
        if cfg.n_heads % mesh.shape["sp"]:
            raise ValueError(
                f"ulysses needs n_heads ({cfg.n_heads}) divisible by "
                f"sp ({mesh.shape['sp']})")
        attn_fn = functools.partial(
            ulysses_attention,
            impl="flash" if sp_impl == "ulysses_flash" else "dense")
    elif sp_impl == "ring":
        attn_fn = ring_attention
    else:
        raise ValueError(f"unknown sp_impl {sp_impl!r}")

    def shard_step(params, momenta, tokens, labels, positions):
        attention = functools.partial(attn_fn, axis_name="sp", causal=True)

        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]

        def local_loss(p):
            # scaled so that the AUTO-PSUM shard_map applies to the
            # cotangent of replicated params (each shard contributes
            # d(local_i)/dp; the sum over shards must equal the gradient
            # of the GLOBAL mean = (1/n) sum_i local_i, every shard
            # holding B/dp x T/sp tokens)
            return lm_loss(p, tokens, labels, positions, cfg,
                           attention=attention,
                           compute_dtype=compute_dtype) / n_shards

        loss, grads = jax.value_and_grad(local_loss)(params)
        # EXPLICIT allreduce of the param cotangents: with replication
        # checking off (check_vma=False: the graph may hold pallas_call,
        # which cannot declare varying mesh axes) no auto-psum is inserted on
        # the backward, so each shard holds only its local contribution here
        grads = jax.tree_util.tree_map(lambda g: jax.lax.psum(g, axes), grads)
        loss = jax.lax.psum(loss, axes)  # back to the global mean for report
        momenta = jax.tree_util.tree_map(lambda m, g: momentum * m + g,
                                         momenta, grads)
        params = jax.tree_util.tree_map(lambda p, m: p - lr * m,
                                        params, momenta)
        return loss, params, momenta

    fn = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(repl, repl, data, data, P("sp")),
        out_specs=(repl, repl, repl), check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))


def shard_batch(mesh: Mesh, tokens, labels, positions):
    """Place host arrays with the layout make_sharded_train_step expects."""
    data = NamedSharding(mesh, P("dp", "sp"))
    pos = NamedSharding(mesh, P("sp"))
    return (jax.device_put(tokens, data), jax.device_put(labels, data),
            jax.device_put(positions, pos))


# -- partition rules (docs/sharding.md) ---------------------------------------------
def transformer_partition_rules(mp_axis: str = "mp"):
    """The transformer LM's hand-rolled sharding, as a RULE SET — the form
    `Module.fit(shard_rules=...)` and `Executor.fused_step` consume
    (parallel/partition_rules.py), retiring this module's bespoke layout
    code as the thing other models must copy.

    Megatron-style tensor-parallel placement over the model axis: the QKV
    and MLP-in projections shard their OUTPUT features, the attention-out /
    MLP-out projections shard their INPUT features, embeddings shard the
    vocab/feature dim, LayerNorm gains/biases replicate (first match wins;
    the trailing catch-all keeps everything else replicated)."""
    return (
        (r"wqkv$|w1$", (None, mp_axis)),     # column-parallel (out features)
        (r"wo$|w2$", (mp_axis, None)),       # row-parallel (in features)
        (r"tok_emb$|pos_emb$", (None, mp_axis)),
        (r"ln\w*_[gb]$|_b1$|_b2$", ()),      # norms + biases replicate
    )


def make_partitioned_train_step(mesh: Mesh, cfg: TransformerConfig,
                                rules=None, lr=0.1, momentum=0.9,
                                compute_dtype=None,
                                mp_compute: Optional[bool] = None):
    """The rule-set successor of :func:`make_sharded_train_step`: ONE
    compiled dp×mp training step whose params and momenta are STORED
    sharded per partition rules (docs/sharding.md) instead of replicated —
    the island's hand-rolled layout folded into the same
    gather/compute/slice FSDP discipline ``Module.fit`` uses, so training a
    transformer bigger than one chip's HBM needs a rules tuple, not a
    bespoke shard_map.

    Layout: tokens/labels (B, T) sharded ``P('dp')``; positions replicated;
    params/momenta sharded per ``rules`` (default
    :func:`transformer_partition_rules`).  Gradients psum over ``dp`` only
    — the mp axis carries shards, never replicas.  Returns ``(step,
    shard_fn, gather_fn)``: ``step(params, momenta, tokens, labels,
    positions) -> (loss, params, momenta)`` jitted with donated sharded
    carries; ``shard_fn``/``gather_fn`` place/unplace a param dict
    (checkpoint boundary).

    ``mp_compute`` (default: the ``TPUMX_MP_COMPUTE`` gate, on whenever the
    rule set is compute-partitionable) turns ``mp`` from a storage axis into
    a COMPUTE axis: instead of the shard_map gather-compute-slice, the step
    is a GSPMD global-view ``jit`` whose matmuls XLA partitions along the
    Megatron column/row specs — column-parallel QKV/FFN-in, row-parallel
    attention-out/FFN-out, one reduce per block, and NO all_gather of any
    rule-sharded weight in the traced program (tests assert the jaxpr).
    Step time now improves with mp, which is the ROADMAP item-2 claim.
    """
    from .partition_rules import (make_param_specs,
                                  make_shard_and_gather_fns,
                                  mp_compute_enabled,
                                  rules_compute_partitionable)

    if rules is None:
        rules = transformer_partition_rules()
    if mp_compute is None:
        mp_compute = (mp_compute_enabled()
                      and rules_compute_partitionable(rules))
    key0 = jax.random.PRNGKey(0)
    shapes = {k: tuple(v.shape)
              for k, v in transformer_lm_init(cfg, key0).items()}
    specs = make_param_specs(rules, shapes, mesh, mp_axis="mp")
    if mp_compute:
        return _make_compute_partitioned_train_step(
            mesh, cfg, specs, shapes, lr=lr, momentum=momentum,
            compute_dtype=compute_dtype)
    mesh_sizes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
    dp = mesh_sizes.get("dp", 1)

    def _axes_of(entry):
        return entry if isinstance(entry, tuple) else (entry,)

    def _gather(x, spec):
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            for ax in reversed(_axes_of(entry)):
                x = jax.lax.all_gather(x, ax, axis=dim, tiled=True)
        return x

    def _slice(x, spec):
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            idx, nshard = 0, 1
            for ax in _axes_of(entry):
                idx = idx * mesh_sizes[ax] + jax.lax.axis_index(ax)
                nshard *= mesh_sizes[ax]
            size = x.shape[dim] // nshard
            x = jax.lax.dynamic_slice_in_dim(x, idx * size, size, axis=dim)
        return x

    spec_of = {k: specs.get(k, ()) for k in shapes}
    pspec_tree = {k: P(*spec_of[k]) for k in shapes}

    def shard_step(params, momenta, tokens, labels, positions):
        full = {k: _gather(v, spec_of[k]) for k, v in params.items()}

        def local_loss(p):
            return lm_loss(p, tokens, labels, positions, cfg,
                           compute_dtype=compute_dtype) / dp

        loss, grads = jax.value_and_grad(local_loss)(full)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, "dp"), grads)
        loss = jax.lax.psum(loss, "dp")
        grads = {k: _slice(g, spec_of[k]) for k, g in grads.items()}
        momenta = jax.tree_util.tree_map(lambda m, g: momentum * m + g,
                                         momenta, grads)
        params = jax.tree_util.tree_map(lambda p, m: p - lr * m,
                                        params, momenta)
        return loss, params, momenta

    fn = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(pspec_tree, pspec_tree, P("dp"), P("dp"), P()),
        out_specs=(P(), pspec_tree, pspec_tree), check_vma=False)
    step = jax.jit(fn, donate_argnums=(0, 1))
    shard_fn, gather_fn = make_shard_and_gather_fns(specs, mesh)
    return step, shard_fn, gather_fn


def _make_compute_partitioned_train_step(mesh: Mesh, cfg: TransformerConfig,
                                         specs, shapes, lr=0.1, momentum=0.9,
                                         compute_dtype=None):
    """The tensor-parallel-COMPUTE variant of
    :func:`make_partitioned_train_step`: a GSPMD global-view ``jit`` traced
    at global batch shapes — the exact math of the single-device
    :func:`train_step` — with every rule-sharded param pinned to its spec by
    ``with_sharding_constraint``.  XLA's SPMD partitioner then splits the
    einsums themselves: the column-parallel QKV/FFN-in matmuls compute only
    their local output features, the row-parallel projections contract their
    local input slice and combine with one reduce per block, and no
    all_gather of a rule-sharded weight exists anywhere in the program
    (tests/test_mp_compute.py asserts the jaxpr and optimized HLO)."""
    from jax.sharding import NamedSharding

    from .partition_rules import make_shard_and_gather_fns

    spec_of = {k: specs.get(k, ()) for k in shapes}
    has_dp = "dp" in mesh.axis_names

    def _pin(x, spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    def step(params, momenta, tokens, labels, positions):
        params = {k: _pin(v, spec_of[k]) for k, v in params.items()}
        momenta = {k: _pin(v, spec_of[k]) for k, v in momenta.items()}
        if has_dp:
            tokens = _pin(tokens, ("dp",))
            labels = _pin(labels, ("dp",))

        def loss_fn(p):
            return lm_loss(p, tokens, labels, positions, cfg,
                           compute_dtype=compute_dtype)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        momenta = {k: _pin(momentum * momenta[k] + grads[k], spec_of[k])
                   for k in momenta}
        params = {k: _pin(params[k] - lr * momenta[k], spec_of[k])
                  for k in params}
        return loss, params, momenta

    step = jax.jit(step, donate_argnums=(0, 1))
    shard_fn, gather_fn = make_shard_and_gather_fns(specs, mesh)
    return step, shard_fn, gather_fn

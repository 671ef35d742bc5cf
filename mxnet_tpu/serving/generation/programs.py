"""The generation engine's compiled model programs.

ONE traced step function serves both phases — prefill (B=1, T=seq-bucket)
and decode (B=max_slots, T=1) — built from the MODEL's cache-aware step
plus the per-row sampling kernel from :mod:`mxnet_tpu.ops.sampling`.  A
model (:func:`as_model`) is an object with ``step(params, tokens,
positions, lengths, k_pool, v_pool, block_tables, *, attention_kernel,
...) -> (logits, k_pool, v_pool, ...)``, its ``vocab``, ``max_len``,
``heads``, ``cache_spec()`` (what :class:`PagedKVCache` is built from),
``block_len`` (0: one token a row a step) and ``offers`` (the program
families it can run).  :class:`~mxnet_tpu.parallel.transformer
.TransformerLM` (GPT-2's block, ``transformer_lm_decode``) is the first,
:class:`~mxnet_tpu.parallel.sdar_moe.SdarMoeLM` (grouped-KV rotary block,
sparse experts, generation by diffusion over blocks: ``gen_block``) the
second.  Each distinct
``(kind, batch, chunk, table-width)`` signature compiles exactly once;
every lookup is fed through ``executor._note_cache`` so these programs
appear in :func:`mxnet_tpu.executor.compile_cache_stats` (sites
``gen_prefill`` / ``gen_decode``), are explained by
``TPUMX_EXPLAIN_RECOMPILES=1``, and are *refused* post-warmup under
``TPUMX_FREEZE_COMPILES=1`` — the same zero-recompile discipline as the
fused train step and the bucketed serving cache.

KV pools are donated: the decode loop updates the cache in place on device
instead of copying ``O(num_blocks)`` memory every token.

Preemption (docs/generation.md "incremental allocation + victim
preemption") adds NO program shapes to this family: a preempted request's
context re-prefills through the same ``gen_prefill`` (T, W) rung
signatures the chunk planner already emits — the engine's warmup simply
enumerates the re-prefill plans too, so the post-warmup zero-recompile
guarantee (``TPUMX_FREEZE_COMPILES=1``) holds with preemption active, and
``TPUMX_GEN_PREEMPTION=0`` restores the reserve-ahead program-key set
byte-for-byte.
"""
from __future__ import annotations

import functools
import threading
from typing import Dict, Optional

import numpy as _np

from ...observability import tracing as _tracing

__all__ = ["GenerationPrograms", "block_copy_pools", "as_model"]


def as_model(model, compute_dtype=None):
    """The model object of a service: a ``TransformerConfig`` becomes
    the GPT-2 block's :class:`TransformerLM`; anything that already has a
    ``step`` is taken as it is."""
    if hasattr(model, "step"):
        return model
    from ...parallel.transformer import TransformerConfig, TransformerLM

    if isinstance(model, TransformerConfig):
        return TransformerLM(model, compute_dtype)
    raise TypeError(f"not a generation model: {model!r} (a TransformerConfig "
                    f"or an object with step / cache_spec / vocab / max_len)")


def _step_args(tokens, positions, lengths, block_tables, seeds, counters,
               temperature, top_k, top_p):
    """A step program's host arguments in their dtypes."""
    return (_np.asarray(tokens, _np.int32),
            _np.asarray(positions, _np.int32),
            _np.asarray(lengths, _np.int32),
            _np.asarray(block_tables, _np.int32),
            _np.asarray(seeds, _np.uint32),
            _np.asarray(counters, _np.uint32),
            _np.asarray(temperature, _np.float32),
            _np.asarray(top_k, _np.int32),
            _np.asarray(top_p, _np.float32))


def _synced(*outs):
    """The step's sampled tokens as NumPy arrays: the read that waits for
    the device, under its own span so that the wait is not mistaken for
    host work (``serving.step.dispatch`` ends where the call returned)."""
    with _tracing.span("serving.step.sync", cat="serving"):
        arrays = tuple(_np.asarray(o) for o in outs)
    return arrays[0] if len(arrays) == 1 else arrays


def block_copy_pools(k_pool, v_pool, src, dst, k_scale=None, v_scale=None):
    """Copy physical block ``src`` onto ``dst`` across every layer of the
    paged pool — the copy-on-write primitive of prefix caching
    (docs/generation.md): a writer whose tail block is shared gets a
    private copy BEFORE its first scatter, so shared prompt history is
    never mutated.  ``src``/``dst``: shape-(1,) int32.  For the int8 pool
    the per-(layer, block, head) scales ride along — a block's bits are
    only meaningful with its scales, so they copy as one unit.  Returns
    ``(k_pool, v_pool)`` or ``(k_pool, v_pool, k_scale, v_scale)``;
    called with donation the copy happens in place on device."""
    import jax
    import jax.numpy as jnp

    s = jnp.asarray(src, jnp.int32)[0]
    d = jnp.asarray(dst, jnp.int32)[0]

    def cp(pool):
        blk = jax.lax.dynamic_slice_in_dim(pool, s, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(pool, blk, d, axis=1)

    k_pool, v_pool = cp(k_pool), cp(v_pool)
    if k_scale is not None:
        return k_pool, v_pool, cp(k_scale), cp(v_scale)
    return k_pool, v_pool


def _model_step(params, k_pool, v_pool, tokens, positions, lengths,
                block_tables, seeds, counters, temperature, top_k, top_p,
                *, model, attention_kernel="gather", mp_mesh=None):
    import jax.numpy as jnp

    from ...ops.sampling import sample_logits

    logits, k_pool, v_pool = model.step(
        params, tokens, positions, lengths, k_pool, v_pool, block_tables,
        attention_kernel=attention_kernel, mp_mesh=mp_mesh)
    # logits at the LAST VALID position of each row feed the sampler
    # (prefill: position len-1 predicts token len; decode: T=1 row 0)
    last_idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0,
                        tokens.shape[1] - 1)
    last = jnp.take_along_axis(logits, last_idx[:, None, None],
                               axis=1)[:, 0, :]
    next_tokens = sample_logits(last, seeds, counters, temperature,
                                top_k, top_p)
    return next_tokens, last, k_pool, v_pool


def _model_step_q(params, k_pool, v_pool, k_scale, v_scale, tokens,
                  positions, lengths, block_tables, seeds, counters,
                  temperature, top_k, top_p, *, model,
                  attention_kernel="gather", mp_mesh=None):
    """The int8-KV variant of :func:`_model_step` (docs/quantization.md):
    the per-(layer, block, head) scale arrays ride as two extra DONATED
    pool operands — a separate traced function so the unquantized
    program layout stays byte-identical when ``kv_dtype`` is off."""
    import jax.numpy as jnp

    from ...ops.sampling import sample_logits

    logits, k_pool, v_pool, k_scale, v_scale = model.step(
        params, tokens, positions, lengths, k_pool, v_pool, block_tables,
        attention_kernel=attention_kernel, mp_mesh=mp_mesh,
        k_scale=k_scale, v_scale=v_scale)
    last_idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0,
                        tokens.shape[1] - 1)
    last = jnp.take_along_axis(logits, last_idx[:, None, None],
                               axis=1)[:, 0, :]
    next_tokens = sample_logits(last, seeds, counters, temperature,
                                top_k, top_p)
    return next_tokens, last, k_pool, v_pool, k_scale, v_scale


def _verify_step(params, k_pool, v_pool, tokens, positions, lengths,
                 block_tables, seeds, counters, temperature, top_k, top_p,
                 *, model, attention_kernel="gather", mp_mesh=None):
    """Speculative verify (docs/generation.md "Speculative decoding"):
    ONE cache-aware multi-query step over ``[pending, d_1..d_s]`` per row
    — the same chunked-prefill path as :func:`_model_step`, but ALL valid
    positions feed the sampler (via ``speculative_verify``) instead of
    just the last one.  Returns per-position target tokens plus the
    leading accepted-draft count per row."""
    from ...ops.sampling import speculative_verify

    logits, k_pool, v_pool = model.step(
        params, tokens, positions, lengths, k_pool, v_pool, block_tables,
        attention_kernel=attention_kernel, mp_mesh=mp_mesh)
    target, accepted = speculative_verify(
        logits, tokens, seeds, counters, temperature, top_k, top_p,
        lengths)
    return target, accepted, k_pool, v_pool


def _verify_step_q(params, k_pool, v_pool, k_scale, v_scale, tokens,
                   positions, lengths, block_tables, seeds, counters,
                   temperature, top_k, top_p, *, model,
                   attention_kernel="gather", mp_mesh=None):
    """int8-KV variant of :func:`_verify_step` (scales donated along)."""
    from ...ops.sampling import speculative_verify

    logits, k_pool, v_pool, k_scale, v_scale = model.step(
        params, tokens, positions, lengths, k_pool, v_pool, block_tables,
        attention_kernel=attention_kernel, mp_mesh=mp_mesh,
        k_scale=k_scale, v_scale=v_scale)
    target, accepted = speculative_verify(
        logits, tokens, seeds, counters, temperature, top_k, top_p,
        lengths)
    return target, accepted, k_pool, v_pool, k_scale, v_scale


def _multistep(params, k_pool, v_pool, tokens, positions, lengths,
               block_tables, seeds, counters, temperature, top_k, top_p,
               *, k, model, attention_kernel="gather", mp_mesh=None):
    """``k`` decode iterations inside ONE donated program via
    ``lax.scan`` (docs/generation.md "multi-step decoding") — each scan
    iteration is exactly the single-step decode math (same (S, 1) model
    call, same ``(seed, position)`` sampler keying, same one-position
    scatter), so tokens match the step-at-a-time path and the int8 pool's
    write pattern is bit-identical; only the host↔device round-trips in
    between are amortized away.  ``tokens``/``positions``/``counters``
    are the FIRST iteration's (S,) values; rows with ``lengths == 0`` are
    inactive throughout (null-block writes).  Returns (S, k) tokens."""
    import jax
    import jax.numpy as jnp

    from ...ops.sampling import sample_logits

    def body(carry, _):
        k_pool, v_pool, tok, pos, ctr = carry
        logits, k_pool, v_pool = model.step(
            params, tok[:, None], pos[:, None], lengths, k_pool, v_pool,
            block_tables, attention_kernel=attention_kernel,
            mp_mesh=mp_mesh)
        nxt = sample_logits(logits[:, 0, :], seeds, ctr, temperature,
                            top_k, top_p)
        return (k_pool, v_pool, nxt, pos + 1, ctr + 1), nxt

    init = (k_pool, v_pool,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(counters, jnp.uint32))
    (k_pool, v_pool, _, _, _), toks = jax.lax.scan(
        body, init, None, length=k)
    return jnp.transpose(toks), k_pool, v_pool  # (S, k)


def _multistep_q(params, k_pool, v_pool, k_scale, v_scale, tokens,
                 positions, lengths, block_tables, seeds, counters,
                 temperature, top_k, top_p, *, k, model,
                 attention_kernel="gather", mp_mesh=None):
    """int8-KV variant of :func:`_multistep`: the scale arrays join the
    scan carry, and because each iteration scatters exactly one position
    per row (the single-step pattern), the masked-absmax requantization
    touches blocks in the same order single-step decode would."""
    import jax
    import jax.numpy as jnp

    from ...ops.sampling import sample_logits

    def body(carry, _):
        k_pool, v_pool, k_scale, v_scale, tok, pos, ctr = carry
        logits, k_pool, v_pool, k_scale, v_scale = model.step(
            params, tok[:, None], pos[:, None], lengths, k_pool, v_pool,
            block_tables, attention_kernel=attention_kernel,
            mp_mesh=mp_mesh,
            k_scale=k_scale, v_scale=v_scale)
        nxt = sample_logits(logits[:, 0, :], seeds, ctr, temperature,
                            top_k, top_p)
        return (k_pool, v_pool, k_scale, v_scale, nxt, pos + 1,
                ctr + 1), nxt

    init = (k_pool, v_pool, k_scale, v_scale,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(counters, jnp.uint32))
    (k_pool, v_pool, k_scale, v_scale, _, _, _), toks = jax.lax.scan(
        body, init, None, length=k)
    return jnp.transpose(toks), k_pool, v_pool, k_scale, v_scale


def _block_fill(params, k_pool, v_pool, tokens, positions, lengths,
                block_tables, *, model, attention_kernel="gather"):
    """Prefill of a block-diffusion model: whole blocks of context written
    into the cache, no logits — the first block step reads the first
    generated positions' own logits."""
    _, k_pool, v_pool, _ = model.step(
        params, tokens, positions, lengths, k_pool, v_pool, block_tables,
        attention_kernel=attention_kernel, call="prefill",
        want_logits=False)
    return k_pool, v_pool


def _block_step(params, k_pool, v_pool, tokens, positions, lengths,
                block_tables, masked, n_unmask, *, model,
                attention_kernel="gather"):
    """One pass of generation by diffusion over blocks (docs/generation.md
    "Block-diffusion generation"): every row feeds its block of
    ``model.block_len`` token ids, MASK where ``masked``, at positions
    ``ctx .. ctx + L - 1``; K/V are written at the block's positions
    (overwriting an earlier pass's), and in the same program the rows'
    ``n_unmask`` most confident masked positions are chosen.  A row with
    no MASK is on its commit pass: the same program, its tokens ignored.
    Returns ``(unmasked (S, L): the new token id, -1 where nothing was
    unmasked; experts touched, summed over layers; logits (S, L, vocab);
    pools)``."""
    from ...ops.sampling import block_unmask

    logits, k_pool, v_pool, touched = model.step(
        params, tokens, positions, lengths, k_pool, v_pool, block_tables,
        attention_kernel=attention_kernel, call="block")
    return (block_unmask(logits, masked, n_unmask), touched, logits,
            k_pool, v_pool)


class GenerationPrograms:
    """Owns the jitted step + per-signature compile accounting."""

    def __init__(self, params, model, compute_dtype=None,
                 mp_devices: int = 1, shard_rules=None, kv_dtype=None):
        import jax

        model = self._model = as_model(model, compute_dtype)
        # int8 paged KV cache (docs/quantization.md): the jitted step
        # gains the two donated scale operands and every program key a
        # ("kv_dtype", "int8") component; None keeps the classic layout
        # byte-identical
        self._kv_dtype = kv_dtype
        # model-parallel serving (docs/sharding.md): with mp_devices > 1 the
        # params live sharded per partition rules over a 1-axis ``mp`` mesh
        # — the SAME rule sets training uses — and the jitted global-view
        # programs let GSPMD insert the collectives, so a model bigger than
        # one chip's HBM decodes through unchanged engine plumbing
        self._mp_mesh = None
        self._mp_specs = None
        if mp_devices and int(mp_devices) > 1:
            from ...parallel.mesh import make_mesh
            from ...parallel.partition_rules import make_param_specs
            from ...parallel.transformer import transformer_partition_rules

            self._mp_mesh = make_mesh({"mp": int(mp_devices)}, install=False)
            rules = shard_rules or transformer_partition_rules()
            self._mp_specs = make_param_specs(
                rules, {k: tuple(v.shape) for k, v in params.items()},
                self._mp_mesh, mp_axis="mp")
        # the attention kernel (docs/pallas.md) is frozen at service
        # construction: TPUMX_PALLAS read ONCE here.  GSPMD cannot
        # partition an opaque Pallas call, but under an mp mesh the kernel
        # runs as a per-head shard_map (paged_attention_sharded) whenever
        # the heads divide the axis — mp-sharded models decode through the
        # fast path; an indivisible head count is the only gather fallback.
        # A mid-run env flip can never desync keys from traced programs.
        from ...ops.pallas_kernels import pallas_enabled

        mp_ok = (self._mp_mesh is None
                 or model.heads % int(self._mp_mesh.shape["mp"]) == 0)
        self._kernel = "paged" if pallas_enabled() and mp_ok else "gather"
        self._params = self._place_params(params)
        # multi-token decoding (docs/generation.md "Speculative
        # decoding"): the verify step shares the model step's operand
        # layout but returns per-position targets + accept counts; the
        # multistep scan needs one jitted partial per static k (built
        # lazily — creating a jit wrapper traces nothing)
        self._step_kw = dict(model=model, attention_kernel=self._kernel,
                             mp_mesh=self._mp_mesh)
        self._jit = self._jit_verify = self._jit_fill = self._jit_block \
            = None
        if model.block_len:
            # generation by diffusion over blocks (docs/generation.md):
            # a prefill that only fills the cache, and the block step
            kw = dict(model=model, attention_kernel=self._kernel)
            self._jit_fill = jax.jit(functools.partial(_block_fill, **kw),
                                     donate_argnums=(1, 2))
            self._jit_block = jax.jit(functools.partial(_block_step, **kw),
                                      donate_argnums=(1, 2))
        elif kv_dtype == "int8":
            self._jit = jax.jit(
                functools.partial(_model_step_q, **self._step_kw),
                donate_argnums=(1, 2, 3, 4))
            self._jit_verify = jax.jit(
                functools.partial(_verify_step_q, **self._step_kw),
                donate_argnums=(1, 2, 3, 4))
        else:
            self._jit = jax.jit(
                functools.partial(_model_step, **self._step_kw),
                donate_argnums=(1, 2))
            self._jit_verify = jax.jit(
                functools.partial(_verify_step, **self._step_kw),
                donate_argnums=(1, 2))
        self._jit_ms: Dict[int, object] = {}
        # the prefix-cache CoW block copy (docs/generation.md "prefix
        # caching"): ONE signature per pool family, donated like the
        # model step so the copy is an in-place device-side move
        if kv_dtype == "int8":
            self._jit_copy = jax.jit(block_copy_pools,
                                     donate_argnums=(0, 1, 4, 5))
        else:
            self._jit_copy = jax.jit(
                lambda k, v, s, d: block_copy_pools(k, v, s, d),
                donate_argnums=(0, 1))
        self._lock = threading.Lock()
        self._stats: Dict[tuple, Dict[str, int]] = {}

    def _place_params(self, params):
        import jax.numpy as jnp

        out = {k: jnp.asarray(v) for k, v in params.items()}
        if self._mp_mesh is not None:
            from ...parallel.partition_rules import shard_params

            out = shard_params(out, self._mp_specs, self._mp_mesh)
        return out

    def place_cache(self, cache) -> None:
        """Lay the paged KV pool out for this service's mesh: under mp with
        the per-head paged kernel the pool lives HEAD-SHARDED on the mp
        axis — each chip stores 1/mp of the cache (the same memory win the
        params already get), and the donated decode programs keep that
        layout steady-state.  No-op without an mp mesh."""
        if self._mp_mesh is None or self._kernel != "paged":
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        # (n_layers, num_blocks, block_size, n_heads*d_head): the folded
        # minor dim splits on head boundaries
        sh = NamedSharding(self._mp_mesh, P(None, None, None, "mp"))
        if cache.quantized:
            # per-(layer, block, head) scales shard on their head dim 2
            ssh = NamedSharding(self._mp_mesh, P(None, None, "mp"))
            cache.swap(jax.device_put(cache.k, sh),
                       jax.device_put(cache.v, sh),
                       jax.device_put(cache.k_scale, ssh),
                       jax.device_put(cache.v_scale, ssh))
            return
        cache.swap(jax.device_put(cache.k, sh), jax.device_put(cache.v, sh))

    def refresh_params(self, params) -> None:
        """Swap in updated model weights (programs are shape-keyed, so no
        recompile — the next call simply runs with the new arrays, resharded
        onto the mp mesh when one is configured)."""
        self._params = self._place_params(params)

    @property
    def kernel(self) -> str:
        """Active decode-attention implementation: ``"paged"`` (the Pallas
        block-table-walking kernel, docs/pallas.md) or ``"gather"`` (the
        gather+dense XLA path).  Frozen at construction from the
        ``TPUMX_PALLAS`` gate (gather under an mp mesh) — the bench
        trajectory attributes wins via this field."""
        return self._kernel

    def _key(self, kind: str, cache, tokens, block_tables) -> tuple:
        sig = (("tokens", tuple(tokens.shape), "int32"),
               ("block_tables", tuple(block_tables.shape), "int32"),
               ("kv_pool", cache.shape, str(cache.k.dtype)))
        # the paged kernel variant keys its programs separately, while
        # gather (TPUMX_PALLAS=0) keys stay byte-identical to the
        # pre-kernel layout — warm caches and freeze sets carry over
        if self.kernel == "paged":
            sig = sig + (("kernel", "paged"),)
        # int8 KV pool (docs/quantization.md): its own program family —
        # kv_dtype off leaves every pre-existing key byte-identical
        if self._kv_dtype == "int8":
            sig = sig + (("kv_dtype", "int8"),)
        return (kind, sig)

    def run(self, kind: str, cache, tokens, positions, lengths,
            block_tables, seeds, counters, temperature, top_k, top_p):
        """Execute one step; returns ``(next_tokens np(B,), last_logits)``.

        ``cache`` is updated in place (donated pools swapped back).  The
        compile-cache note happens BEFORE dispatch, so a frozen service
        raises :class:`FreezeCompilesError` without burning an XLA compile.
        """
        from ... import executor as _executor

        kernel = self.kernel
        key = self._key(kind, cache, tokens, block_tables)
        with self._lock:
            per = self._stats.get(key)
            hit = per is not None
            if per is None:
                per = self._stats[key] = {"hits": 0, "misses": 0}
        # program variants count per-site in compile_cache_stats()["by_site"]
        # — "gen_decode_paged" next to the classic "gen_decode", with the
        # int8-pool family as its own "_int8"-suffixed site
        site_kind = kind if kernel == "gather" else f"{kind}_{kernel}"
        if self._kv_dtype == "int8":
            site_kind = f"{site_kind}_int8"
        _executor._note_cache(hit=hit, site=(site_kind, ("lm",)), key=key)
        with self._lock:
            per["hits" if hit else "misses"] += 1
        next_tokens, last = self._dispatch(self._jit, cache, _step_args(
            tokens, positions, lengths, block_tables, seeds, counters,
            temperature, top_k, top_p))
        return _synced(next_tokens), last

    def _dispatch(self, fn, cache, args):
        """Call a step program on the cache's pools (the scales too for
        the int8 pool) and swap the donated pools it returns, last among
        its outputs, back into the cache; returns the other outputs."""
        pools = (cache.k, cache.v)
        if self._kv_dtype == "int8":
            pools += (cache.k_scale, cache.v_scale)
        with _tracing.span("serving.step.dispatch", cat="serving"):
            out = fn(self._params, *pools, *args)
            cache.swap(*out[-len(pools):])
        return out[:-len(pools)]

    def _note(self, kind: str, key: tuple) -> None:
        """Compile-cache bookkeeping shared by every program family:
        per-signature hit/miss counts plus the ``_note_cache`` call that
        feeds freeze/explain — BEFORE dispatch, like :meth:`run`."""
        from ... import executor as _executor

        with self._lock:
            per = self._stats.get(key)
            hit = per is not None
            if per is None:
                per = self._stats[key] = {"hits": 0, "misses": 0}
        site_kind = kind if self.kernel == "gather" \
            else f"{kind}_{self.kernel}"
        if self._kv_dtype == "int8":
            site_kind = f"{site_kind}_int8"
        _executor._note_cache(hit=hit, site=(site_kind, ("lm",)), key=key)
        with self._lock:
            per["hits" if hit else "misses"] += 1

    def run_verify(self, cache, tokens, positions, lengths, block_tables,
                   seeds, counters, temperature, top_k, top_p):
        """One speculative verify step: ``tokens`` (S, Tk) holds
        ``[pending, d_1..d_s]`` per row (right-padded; ``lengths`` counts
        the valid columns).  Returns ``(target np(S, Tk), accepted
        np(S,))`` — see :func:`~mxnet_tpu.ops.sampling.speculative_verify`
        for the emit contract.  Site ``gen_verify``; keys share the
        :meth:`run` namespace so warmup enumerates the (Tk, W) ladder."""
        key = self._key("gen_verify", cache, tokens, block_tables)
        self._note("gen_verify", key)
        return _synced(*self._dispatch(self._jit_verify, cache, _step_args(
            tokens, positions, lengths, block_tables, seeds, counters,
            temperature, top_k, top_p)))

    def run_fill(self, cache, tokens, positions, lengths, block_tables):
        """A block-diffusion model's prefill chunk (site ``gen_prefill``):
        fills the cache, returns nothing to read."""
        tokens = _np.asarray(tokens, _np.int32)
        block_tables = _np.asarray(block_tables, _np.int32)
        self._note("gen_prefill", self._key("gen_prefill", cache, tokens,
                                            block_tables))
        self._dispatch(self._jit_fill, cache, (
            tokens, _np.asarray(positions, _np.int32),
            _np.asarray(lengths, _np.int32), block_tables))

    def run_block(self, cache, tokens, positions, lengths, block_tables,
                  masked, n_unmask):
        """One block step (site ``gen_block``): returns ``(unmasked np(S,
        L), experts touched np(), logits (S, L, vocab) on the device)``
        — see :func:`_block_step`."""
        tokens = _np.asarray(tokens, _np.int32)
        block_tables = _np.asarray(block_tables, _np.int32)
        self._note("gen_block", self._key("gen_block", cache, tokens,
                                          block_tables))
        unmasked, touched, logits = self._dispatch(self._jit_block, cache, (
            tokens, _np.asarray(positions, _np.int32),
            _np.asarray(lengths, _np.int32), block_tables,
            _np.asarray(masked, _np.bool_), _np.asarray(n_unmask, _np.int32)))
        return _synced(unmasked, touched) + (logits,)

    def _ms_jit(self, k: int):
        import jax

        with self._lock:
            fn = self._jit_ms.get(k)
            if fn is None:
                if self._kv_dtype == "int8":
                    fn = jax.jit(
                        functools.partial(_multistep_q, k=k,
                                          **self._step_kw),
                        donate_argnums=(1, 2, 3, 4))
                else:
                    fn = jax.jit(
                        functools.partial(_multistep, k=k,
                                          **self._step_kw),
                        donate_argnums=(1, 2))
                self._jit_ms[k] = fn
        return fn

    def run_multistep(self, k: int, cache, tokens, positions, lengths,
                      block_tables, seeds, counters, temperature, top_k,
                      top_p):
        """``k`` decode iterations in one donated program (``lax.scan``).

        ``tokens``/``positions``/``counters`` are the first iteration's
        (S,) values; returns np (S, k) tokens per row.  Each k is its own
        program signature (``("k", k)`` key component, site
        ``gen_multistep``) — the engine's pow2 k-ladder keeps the family
        finite for warmup."""
        tokens = _np.asarray(tokens, _np.int32)
        key = self._key("gen_multistep", cache, tokens, block_tables)
        key = (key[0], key[1] + (("k", int(k)),))
        self._note("gen_multistep", key)
        return _synced(*self._dispatch(self._ms_jit(int(k)), cache, _step_args(
            tokens, positions, lengths, block_tables, seeds, counters,
            temperature, top_k, top_p)))

    def copy_block(self, cache, src: int, dst: int) -> None:
        """Copy pool block ``src`` onto ``dst`` (scales included for the
        int8 pool) — the copy-on-write append of prefix caching.  One
        program signature per pool family, accounted at site
        ``gen_block_copy`` with the same freeze/explain discipline as the
        model steps; warmed by ``GenerationService.warmup`` whenever the
        prefix cache is enabled."""
        from ... import executor as _executor

        sig = (("kv_pool", cache.shape, str(cache.k.dtype)),)
        # same key namespacing as _key(): the paged-kernel service and the
        # int8 pool each keep their whole program family distinct
        if self.kernel == "paged":
            sig = sig + (("kernel", "paged"),)
        if self._kv_dtype == "int8":
            sig = sig + (("kv_dtype", "int8"),)
        key = ("gen_block_copy", sig)
        with self._lock:
            per = self._stats.get(key)
            hit = per is not None
            if per is None:
                per = self._stats[key] = {"hits": 0, "misses": 0}
        site = "gen_block_copy_int8" if self._kv_dtype == "int8" \
            else "gen_block_copy"
        _executor._note_cache(hit=hit, site=(site, ("lm",)), key=key)
        with self._lock:
            per["hits" if hit else "misses"] += 1
        s = _np.asarray([src], _np.int32)
        d = _np.asarray([dst], _np.int32)
        if self._kv_dtype == "int8":
            k, v, ks, vs = self._jit_copy(cache.k, cache.v, s, d,
                                          cache.k_scale, cache.v_scale)
            cache.swap(k, v, ks, vs)
            return
        k, v = self._jit_copy(cache.k, cache.v, s, d)
        cache.swap(k, v)

    def compile_stats(self) -> Dict[tuple, Dict[str, int]]:
        """Per-signature ``{"hits", "misses"}`` — every signature compiled
        by a warmed service must show exactly 1 miss."""
        with self._lock:
            return {k: dict(v) for k, v in self._stats.items()}

    def compiled_signatures(self) -> int:
        with self._lock:
            return len(self._stats)

"""The generation engine's compiled model programs.

A program kind is ONE traced function, whatever the pool: the cache's
device arrays travel as one operand, ``pools`` — ``(k, v)``, ``(k, v,
k_scale, v_scale)`` for the int8 pool (docs/quantization.md), or whatever
the model's ``cache_spec()`` named (latent attention: one pool) — that every
kind takes after the parameters, donates, and returns last, so the decode
loop updates the cache in place on device instead of copying
``O(num_blocks)`` memory every token.  The kinds: ``gen_prefill`` (B=1,
T=seq-bucket) and ``gen_decode`` (B=max_slots, T=1) are the same
:func:`_model_step`, the MODEL's cache-aware step plus the per-row
sampling kernel from :mod:`mxnet_tpu.ops.sampling`; ``gen_verify``; a
block-diffusion model's ``gen_prefill`` (:func:`_block_fill`) and
``gen_block``; ``gen_fill`` (:func:`_fill_step`: a one-token model's chunk
that is not a prompt's last, where the model skips what such a chunk need
not run); and ``gen_block_copy``.

A model (:func:`as_model`) is an object with ``step(params, tokens,
positions, lengths, pools, block_tables, *, attention_kernel,
mp_mesh=None, call=None, want_logits=True) -> (logits, pools, aux)`` —
``pools`` the structure that came in (a model whose ``cache_spec()`` names
several cache kinds gets their pools one kind after the other, and
``block_tables`` as a tuple, a table a kind: docs/generation.md "Cache
kinds"), ``aux`` whatever else the program
must hand back (a one-token model: None, or a dict of scalar counts the
engine sums into ``stats()["counts"]`` under the names in the model's
``counters``) — its ``vocab``, ``max_len``, ``heads``, ``cache_spec()``
(what :class:`PagedKVCache` is built from), ``block_len`` (0: one token a
row a step) and ``offers`` (the program families it can run).
:class:`~mxnet_tpu.parallel.transformer.TransformerLM` (GPT-2's block,
``transformer_lm_decode``) is the first,
:class:`~mxnet_tpu.parallel.sdar_moe.SdarMoeLM` (grouped-KV rotary block,
sparse experts, generation by diffusion over blocks) the second,
:class:`~mxnet_tpu.parallel.latent_moe.LatentMoeLM` (latent attention over
one latent pool, a gated dense layer, sigmoid-routed experts with a shared
one, of which the chip holds a share) the third,
:class:`~mxnet_tpu.parallel.hybrid_moe.HybridMoeLM` (window and full
attention layers over a cache of two kinds: ``mimo_v2``'s block and, the
same loop with its own terms on, ``afmoe``'s) the fourth,
:class:`~mxnet_tpu.parallel.retention_lm.RetentionLM` (power retention over
a cache kind that is a slot's state) the fifth,
:class:`~mxnet_tpu.parallel.sambay_lm.SambaYLM` (state-space, window and
full attention layers over three kinds side by side, a table a kind and a
slot's index among them; ``fills_without_head``: it runs its cross-decoder
at a prompt's last position alone) the sixth.

No step waits for the device: :meth:`GenerationPrograms.run` hands back
what the jitted call returned, and its caller reads the sampled tokens
(:func:`_synced`) when it needs their values — the engine after it has
dispatched the NEXT decode step, whose ``tokens`` operand
:meth:`GenerationPrograms.carry_tokens` merges on the device from the
tokens still there and the host's (docs/generation.md "The step in
flight"; one slot-sized program, no model in it, not a signature below).
A prefill's first token joins that step the same way:
:meth:`GenerationPrograms.place_first` puts it, unread, at its row of the
tokens the carry merges (a second slot-sized program, the slot an operand).
A block pass hands its block state on the same way
(:meth:`GenerationPrograms.run_block` without ``read``,
:meth:`GenerationPrograms.carry_block`).

Each distinct ``(kind, batch, chunk, table-width)`` signature compiles
exactly once.  Every kind runs through :meth:`GenerationPrograms._run`,
whose lookup is fed through ``executor._note_cache`` so these programs
appear in :func:`mxnet_tpu.executor.compile_cache_stats` (sites
``gen_prefill`` / ``gen_decode`` / ...), are explained by
``TPUMX_EXPLAIN_RECOMPILES=1``, and are *refused* post-warmup under
``TPUMX_FREEZE_COMPILES=1`` — the same zero-recompile discipline as the
fused train step and the bucketed serving cache.

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
from typing import Dict

import numpy as _np

from ...observability import device_scopes as _device_scopes
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


_SAMPLER_DTYPES = (_np.uint32, _np.uint32, _np.float32, _np.int32,
                   _np.float32)


def _step_args(tokens, positions, lengths, block_tables, *sampler):
    """A step program's arguments in their dtypes; ``sampler`` is
    ``(seeds, counters, temperature, top_k, top_p)`` or nothing.
    ``tokens`` already on the device (the last step's, carried onward:
    :meth:`GenerationPrograms.carry_tokens`) go in as they are — a NumPy
    conversion would wait for the step that produces them."""
    import jax

    if not isinstance(tokens, jax.Array):
        tokens = _np.asarray(tokens, _np.int32)
    if isinstance(block_tables, tuple):
        # a table a cache kind (docs/generation.md "Cache kinds")
        block_tables = tuple(_np.asarray(t, _np.int32) for t in block_tables)
    else:
        block_tables = _np.asarray(block_tables, _np.int32)
    return (tokens,) + tuple(_np.asarray(a, _np.int32) for a in
                             (positions, lengths)) + (block_tables,) + tuple(
        _np.asarray(a, dt) for a, dt in zip(sampler, _SAMPLER_DTYPES))


def _synced(*outs, of=None, waited=None):
    """The step's sampled tokens as NumPy arrays: the read that waits for
    the device, under its own span so that the wait is not mistaken for
    host work (``serving.step.dispatch`` ends where the call returned).
    :meth:`GenerationPrograms.run` leaves this read to its caller, which
    may make it after it has dispatched the next step.  ``of`` says in
    the span's arguments what is read where that is not a decode step's
    tokens (``"prefill"``: first tokens, read after the decode step they
    fed was dispatched).  ``waited``, the caller's microseconds by phase,
    is handed the span's own duration under ``sync_wait`` (the engine's
    ``stats()["phase_ms"]``), whether or not the read raised."""
    sync = _tracing.span("serving.step.sync", cat="serving",
                         args={"of": of} if of else None)
    try:
        with sync:
            arrays = tuple(_np.asarray(o) for o in outs)
    finally:
        if waited is not None:
            waited["sync_wait"] += sync.duration_us
    return arrays[0] if len(arrays) == 1 else arrays


def block_copy_pools(pools, src, dst):
    """Copy physical block ``src`` onto ``dst`` across every layer of the
    paged pool — the copy-on-write primitive of prefix caching
    (docs/generation.md): a writer whose tail block is shared gets a
    private copy BEFORE its first scatter, so shared prompt history is
    never mutated.  ``src``/``dst``: shape-(1,) int32.  For the int8 pool
    the per-(layer, block, head) scales ride along in ``pools`` — a
    block's bits are only meaningful with its scales, so they copy as one
    unit.  Returns ``pools``; called with donation the copy happens in
    place on device."""
    import jax
    import jax.numpy as jnp

    def cp(pool):
        blk = jax.lax.dynamic_slice_in_dim(pool, s, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(pool, blk, d, axis=1)

    with jax.named_scope("block_copy"):
        s = jnp.asarray(src, jnp.int32)[0]
        d = jnp.asarray(dst, jnp.int32)[0]
        return tuple(cp(pool) for pool in pools)


def _carry(prev, tokens, keep):
    """The next step's ``tokens (S, 1)``: the last step's sampled token
    ``prev (S,)`` in the rows that ``keep``, the host's everywhere else.
    No model in it: one slot-sized program a service."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("carry"):
        return jnp.where(keep[:, None], prev[:, None].astype(jnp.int32),
                         tokens)


def _place_first(prev, first, slot):
    """``prev (S,)``, tokens on the device that the next step's rows are
    fed from (:func:`_carry`), with a prefill's ``first (1,)`` token at
    row ``slot (1,)``: the row joins the step from its prefill without
    the host reading the token.  The slot is an operand: one slot-sized
    program a service however many rows a pass admits, no model in it."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("first_token"):
        return jax.lax.dynamic_update_slice(
            prev.astype(jnp.int32), first.astype(jnp.int32), (slot[0],))


def _carry_block(unmasked, prev_tokens, prev_masked, tokens, masked, keep):
    """The next block pass's ``tokens (S, L)`` and ``masked (S, L)``: in
    the rows that ``keep`` (they continue their block) what the last pass
    was fed, ``prev_tokens`` / ``prev_masked``, with the positions it
    unmasked filled in from its ``unmasked (S, L)`` (the new id, -1 where
    nothing was unmasked); the host's everywhere else.  No model in it:
    one slot-sized program a service."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("carry"):
        took = unmasked >= 0
        keep = keep[:, None]
        return (jnp.where(keep, jnp.where(took, unmasked, prev_tokens),
                          tokens),
                jnp.where(keep, prev_masked & ~took, masked))


def _model_step(params, pools, tokens, positions, lengths, block_tables,
                seeds, counters, temperature, top_k, top_p, *, model,
                attention_kernel="gather", mp_mesh=None):
    import jax
    import jax.numpy as jnp

    from ...ops.sampling import sample_logits

    # the program's kind as the engine counts it, outermost in every
    # operation's ``op_name`` (docs/observability.md "Device scopes")
    with jax.named_scope("decode" if tokens.shape[1] == 1 else "prefill"):
        logits, pools, aux = model.step(
            params, tokens, positions, lengths, pools, block_tables,
            attention_kernel=attention_kernel, mp_mesh=mp_mesh)
        # logits at the LAST VALID position of each row feed the sampler
        # (prefill: position len-1 predicts token len; decode: T=1 row 0)
        with jax.named_scope("head"):
            # (a model that skips all but a prompt's last position hands
            # back that one row's)
            last_idx = jnp.clip(jnp.asarray(lengths, jnp.int32) - 1, 0,
                                logits.shape[1] - 1)
            last = jnp.take_along_axis(logits, last_idx[:, None, None],
                                       axis=1)[:, 0, :]
        with jax.named_scope("sample"):
            next_tokens = sample_logits(last, seeds, counters, temperature,
                                        top_k, top_p)
    return next_tokens, last, aux, pools


def _verify_step(params, pools, tokens, positions, lengths, block_tables,
                 seeds, counters, temperature, top_k, top_p, *, model,
                 attention_kernel="gather", mp_mesh=None):
    """Speculative verify (docs/generation.md "Speculative decoding"):
    ONE cache-aware multi-query step over ``[pending, d_1..d_s]`` per row
    — the same chunked-prefill path as :func:`_model_step`, but ALL valid
    positions feed the sampler (via ``speculative_verify``) instead of
    just the last one.  Returns per-position target tokens plus the
    leading accepted-draft count per row."""
    import jax

    from ...ops.sampling import speculative_verify

    with jax.named_scope("verify"):
        logits, pools, _ = model.step(
            params, tokens, positions, lengths, pools, block_tables,
            attention_kernel=attention_kernel, mp_mesh=mp_mesh)
        with jax.named_scope("sample"):
            target, accepted = speculative_verify(
                logits, tokens, seeds, counters, temperature, top_k, top_p,
                lengths)
    return target, accepted, pools


def _block_fill(params, pools, tokens, positions, lengths, block_tables,
                *, model, attention_kernel="gather", mp_mesh=None):
    """Prefill of a block-diffusion model: whole blocks of context written
    into the cache, no logits — the first block step reads the first
    generated positions' own logits."""
    import jax

    with jax.named_scope("fill"):
        _, pools, _ = model.step(
            params, tokens, positions, lengths, pools, block_tables,
            attention_kernel=attention_kernel, mp_mesh=mp_mesh,
            call="prefill", want_logits=False)
    return (pools,)


def _fill_step(params, pools, tokens, positions, lengths, block_tables,
               *, model, attention_kernel="gather", mp_mesh=None):
    """A chunk that is not a prompt's last, of a one-token model that
    ``fills_without_head`` (docs/generation.md "The prefill skip"): the
    caches and states the chunk's positions leave behind, no logits, no
    sample — the model leaves out every layer that writes none.  Returns
    the program's counts beside the pools."""
    import jax

    with jax.named_scope("fill"):
        _, pools, aux = model.step(
            params, tokens, positions, lengths, pools, block_tables,
            attention_kernel=attention_kernel, mp_mesh=mp_mesh,
            call="prefill", want_logits=False)
    return aux, pools


def _block_step(params, pools, tokens, positions, lengths, block_tables,
                masked, n_unmask, *, model, attention_kernel="gather",
                mp_mesh=None):
    """One pass of generation by diffusion over blocks (docs/generation.md
    "Block-diffusion generation"): every row feeds its block of
    ``model.block_len`` token ids, MASK where ``masked``, at positions
    ``ctx .. ctx + L - 1``; K/V are written at the block's positions
    (overwriting an earlier pass's), and in the same program the rows'
    ``n_unmask`` most confident masked positions are chosen.  A row with
    no MASK is on its commit pass: the same program, its tokens ignored.
    Returns ``(unmasked (S, L): the new token id, -1 where nothing was
    unmasked; experts touched, summed over layers; logits (L, S, vocab);
    pools)``.

    The logits go back POSITION-major, as the head's product wrote them:
    its rows are ``S x L`` and a row tile holds 8, so the chip's compiler
    puts ``L`` (4) outside and tiles ``S``, and the sampler reads them so.
    Handed back ``(S, L, vocab)`` they cost one re-layout of the whole
    array a pass (155 MB at ``sdar-30b-a3b``'s widths) for a result the
    engine never reads; :meth:`GenerationPrograms.run_block` turns them
    round for whoever does (docs/generation.md "A weight reaches its
    product as stored")."""
    import jax

    from ...ops.sampling import block_unmask

    with jax.named_scope("block"):
        logits, pools, touched = model.step(
            params, tokens, positions, lengths, pools, block_tables,
            attention_kernel=attention_kernel, mp_mesh=mp_mesh, call="block")
        with jax.named_scope("sample"):
            unmasked = block_unmask(logits, masked, n_unmask)
        logits = logits.swapaxes(0, 1)
    return unmasked, touched, logits, pools


class GenerationPrograms:
    """Owns the jitted programs + per-signature compile accounting."""

    def __init__(self, params, model, compute_dtype=None,
                 mp_devices: int = 1, shard_rules=None, kv_dtype=None):
        model = self._model = as_model(model, compute_dtype)
        # int8 paged KV cache (docs/quantization.md): the pools operand
        # holds the two scale arrays too, and every program key gains a
        # ("kv_dtype", "int8") component; None keeps the classic keys
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
        self._step_kw = dict(model=model, attention_kernel=self._kernel,
                             mp_mesh=self._mp_mesh)
        # program kind -> (the function it traces, its site in
        # compile_cache_stats()["by_site"]).  Program variants count per
        # site — "gen_decode_paged" next to the classic "gen_decode", the
        # int8-pool family "_int8"-suffixed; the block copy runs no
        # attention, so its site is not named for the kernel
        variant = "" if self._kernel == "gather" else f"_{self._kernel}"
        int8 = "_int8" if kv_dtype == "int8" else ""
        self._kinds = {
            kind: (fn, kind + (variant if fn is not block_copy_pools
                               else "") + int8)
            for kind, fn in (
                ("gen_prefill",
                 _block_fill if model.block_len else _model_step),
                ("gen_decode", _model_step),
                ("gen_verify", _verify_step),
                ("gen_block", _block_step),
                ("gen_fill", _fill_step),
                ("gen_block_copy", block_copy_pools))}
        self._jits: Dict[object, object] = {}
        import jax

        # (creating one traces nothing)
        self._carry_jit = jax.jit(_carry_block if model.block_len else _carry)
        self._place_jit = jax.jit(_place_first)
        self._placed = 0                    # first tokens placed so far
        self._aux: list = []                # run()s' aux nobody took yet
        self._lock = threading.Lock()
        self._stats: Dict[tuple, Dict[str, int]] = {}
        # key -> thunk for the optimised HLO text, noted where a key is
        # first seen; the carry's by the shape of the tokens it merges
        # (observability.device_scopes)
        self._texts: Dict[tuple, object] = {}
        _device_scopes.register(self)

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
        # minor dim splits on head boundaries; the int8 pool's
        # per-(layer, block, head) scales shard on their head dim 2
        specs = (P(None, None, None, "mp"),) * 2 + (P(None, None, "mp"),) * 2
        cache.swap(jax.device_put(pool, NamedSharding(self._mp_mesh, spec))
                   for pool, spec in zip(cache.pools, specs))

    def refresh_params(self, params) -> None:
        """Swap in updated model weights (programs are shape-keyed, so no
        recompile — the next call simply runs with the new arrays, resharded
        onto the mp mesh when one is configured)."""
        self._params = self._place_params(params)

    @property
    def kernel(self) -> str:
        """Active decode-attention implementation: ``"paged"`` (the Pallas
        block-table-walking kernel, docs/pallas.md; per head under an mp
        mesh whose axis the heads divide) or ``"gather"`` (the
        gather+dense XLA path).  Frozen at construction from the
        ``TPUMX_PALLAS`` gate."""
        return self._kernel

    def _key(self, kind: str, cache, tokens=None,
             block_tables=None) -> tuple:
        sig = (("kv_pool", cache.shape, str(cache.pools[0].dtype)),)
        if tokens is not None:
            widths = tuple(block_tables.shape) \
                if not isinstance(block_tables, tuple) \
                else tuple(tuple(t.shape) for t in block_tables)
            sig = (("tokens", tuple(tokens.shape), "int32"),
                   ("block_tables", widths, "int32")) + sig
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

    def _run(self, kind: str, cache, args) -> tuple:
        """The one way a program runs: note the compile-cache lookup, call
        the kind's jitted function on the cache's ``pools`` (donated),
        swap what it returns, last among its outputs, back into the cache;
        returns the other outputs.  The note — per-signature hit/miss
        counts plus the ``_note_cache`` call that feeds freeze/explain —
        happens BEFORE dispatch, so a frozen service raises
        :class:`FreezeCompilesError` without burning an XLA compile."""
        from ... import executor as _executor

        fn, site = self._kinds[kind]
        # the block copy has no model in it: no parameters, no tokens or
        # tables in its key, and its caller's serving.cow_copy span times it
        step = fn is not block_copy_pools
        key = self._key(kind, cache, args[0], args[3]) if step \
            else self._key(kind, cache)
        with self._lock:
            per = self._stats.get(key)
            hit = per is not None
            if per is None:
                per = self._stats[key] = {"hits": 0, "misses": 0}
            jitted = self._jits.get(fn)
            if jitted is None:
                # one jit wrapper per function: creating one traces
                # nothing
                import jax

                if step:
                    jitted = jax.jit(functools.partial(fn, **self._step_kw),
                                     donate_argnums=(1,))
                else:
                    jitted = jax.jit(fn, donate_argnums=(0,))
                self._jits[fn] = jitted
            if not hit:
                self._texts[key] = _device_scopes.text_thunk(
                    jitted, ((self._params,) if step else ())
                    + (cache.pools,) + tuple(args))
                if fn is _block_step or (fn is _model_step
                                         and args[0].shape[1] == 1):
                    self._note_carry(args[0].shape)
        _executor._note_cache(hit=hit, site=(site, ("lm",)), key=key)
        with self._lock:
            per["hits" if hit else "misses"] += 1
        if not step:
            cache.swap(jitted(cache.pools, *args))
            return ()
        with _tracing.span("serving.step.dispatch", cat="serving"):
            *out, pools = jitted(self._params, cache.pools, *args)
            cache.swap(pools)
        return tuple(out)

    def _note_carry(self, rows):
        """The carry that feeds steps whose ``tokens`` are ``rows`` (S, 1)
        or (S, L): its shapes follow from theirs."""
        import jax

        if ("carry", rows) in self._texts:
            return
        i32 = jax.ShapeDtypeStruct(rows, _np.int32)
        flag = jax.ShapeDtypeStruct(rows, _np.bool_)
        keep = jax.ShapeDtypeStruct(rows[:1], _np.bool_)
        self._texts["carry", rows] = _device_scopes.text_thunk(
            self._carry_jit, (i32, i32, flag, i32, flag, keep)
            if self._model.block_len else
            (jax.ShapeDtypeStruct(rows[:1], _np.int32), i32, keep))

    def device_programs(self):
        """What ``observability.device_scopes`` reads: ``(None: the kind
        is the text's outermost scope, key, launches, thunk for the
        optimised HLO text)`` of every signature this object has run, and
        of the carry (launched, for all anyone counts, as often as the
        steps it feeds)."""
        with self._lock:
            launches = {key: sum(per.values())
                        for key, per in self._stats.items()}
            texts = list(self._texts.items())
        out = []
        for key, thunk in texts:
            n = launches.get(key)
            if n is None and key[0] == "first_token":
                n = self._placed
            elif n is None:     # the carry, keyed ("carry", tokens' shape)
                n = sum(m for fed, m in launches.items()
                        if ("tokens", key[1], "int32") in fed[1])
            out.append((None, key, n, thunk))
        return out

    def run(self, kind: str, cache, tokens, positions, lengths,
            block_tables, seeds, counters, temperature, top_k, top_p):
        """Dispatch one step; returns ``(next_tokens (B,), last_logits)``
        as the jitted call returned them, on the device and not waited
        for: the caller reads the tokens (:func:`_synced`) when it needs
        their values, which for a decode step is after it has dispatched
        the next one (docs/generation.md "the step in flight").  What
        else the model's step handed back (its ``aux``: a dict of counts
        the program made, or None) waits, on the device too, for
        :meth:`take_aux`: ``run`` itself returns the pair its callers and
        their wrappers unpack (the benchmark's drivers and tests among
        them).

        ``cache`` is updated in place (donated pools swapped back)."""
        next_tokens, last, aux = self._run(kind, cache, _step_args(
            tokens, positions, lengths, block_tables, seeds, counters,
            temperature, top_k, top_p))
        if aux is not None:
            self._aux.append(aux)
        return next_tokens, last

    def take_aux(self) -> tuple:
        """The ``aux`` of every :meth:`run` since the last call, oldest
        first, as the programs returned them (not waited for)."""
        aux, self._aux = tuple(self._aux), []
        return aux

    def carry_tokens(self, prev, tokens, keep):
        """The ``tokens`` operand of a decode step dispatched while the
        last one's ``prev (S,)`` tokens are still on the device: ``prev``
        in the rows that ``keep (S,) bool``, the host's ``tokens (S, 1)``
        in the others, merged on the device without a read.  ``prev`` is
        the object :meth:`run` returned, whatever it is: one that is
        already a NumPy array is merged here on the host."""
        tokens, keep = _np.asarray(tokens, _np.int32), _np.asarray(keep, bool)
        if isinstance(prev, _np.ndarray):
            return _np.where(keep[:, None], prev[:, None], tokens)
        return self._carry_jit(prev, tokens, keep)

    def place_first(self, prev, first, slot: int):
        """``prev (S,)`` — the last step's tokens, or any ``(S,)`` int32
        array where no step is in flight — with a prefill's ``first
        (1,)`` token, as :meth:`run` returned it, at row ``slot``
        (:func:`_place_first`): what :meth:`carry_tokens` then feeds the
        row from.  Nothing is read; ``prev`` itself stays as it was."""
        key = ("first_token", tuple(prev.shape))
        if key not in self._texts:
            import jax

            i32 = jax.ShapeDtypeStruct(key[1], _np.int32)
            one = jax.ShapeDtypeStruct((1,), _np.int32)
            self._texts[key] = _device_scopes.text_thunk(
                self._place_jit, (i32, one, one))
        self._placed += 1
        return self._place_jit(prev, first, _np.asarray([slot], _np.int32))

    def run_verify(self, cache, tokens, positions, lengths, block_tables,
                   seeds, counters, temperature, top_k, top_p, waited=None):
        """One speculative verify step: ``tokens`` (S, Tk) holds
        ``[pending, d_1..d_s]`` per row (right-padded; ``lengths`` counts
        the valid columns).  Returns ``(target np(S, Tk), accepted
        np(S,))`` — see :func:`~mxnet_tpu.ops.sampling.speculative_verify`
        for the emit contract.  Site ``gen_verify``; keys share the
        :meth:`run` namespace so warmup enumerates the (Tk, W) ladder.
        ``waited`` as :func:`_synced` takes it: the step is read here."""
        return _synced(*self._run("gen_verify", cache, _step_args(
            tokens, positions, lengths, block_tables, seeds, counters,
            temperature, top_k, top_p)), waited=waited)

    def run_fill(self, cache, tokens, positions, lengths, block_tables):
        """A chunk that fills the cache and returns nothing to read: a
        block-diffusion model's prefill chunk (site ``gen_prefill``), or a
        one-token model's chunk that is not its prompt's last (site
        ``gen_fill``; its counts wait for :meth:`take_aux`)."""
        args = _step_args(tokens, positions, lengths, block_tables)
        if self._model.block_len:
            self._run("gen_prefill", cache, args)
            return
        (aux,) = self._run("gen_fill", cache, args)
        if aux is not None:
            self._aux.append(aux)

    def run_block(self, cache, tokens, positions, lengths, block_tables,
                  masked, n_unmask, read=True):
        """One block step (site ``gen_block``): returns ``(unmasked np(S,
        L), experts touched np(), logits (S, L, vocab) on the device)``
        — see :func:`_block_step`.  Without ``read`` all three come back
        as the jitted call returned them, on the device and not waited
        for, the logits position-major ``(L, S, vocab)`` (the engine's
        pass in flight: it reads the first two a pass late and the third
        never, docs/generation.md "The step in flight"), and ``tokens``
        and ``masked`` already on the device (:meth:`carry_block`) go in
        as they are."""
        import jax

        if not isinstance(masked, jax.Array):
            masked = _np.asarray(masked, _np.bool_)
        unmasked, touched, logits = self._run("gen_block", cache, _step_args(
            tokens, positions, lengths, block_tables) + (
            masked, _np.asarray(n_unmask, _np.int32)))
        if not read:
            return unmasked, touched, logits
        return _synced(unmasked, touched) + (logits.swapaxes(0, 1),)

    def carry_block(self, unmasked, prev_tokens, prev_masked, tokens, masked,
                    keep):
        """The ``tokens`` and ``masked`` operands of a block pass
        dispatched while the last one's ``unmasked (S, L)`` is still on
        the device (:func:`_carry_block`): merged there without a read,
        whatever of the rest is on the host."""
        return self._carry_jit(
            unmasked, prev_tokens, prev_masked,
            _np.asarray(tokens, _np.int32), _np.asarray(masked, _np.bool_),
            _np.asarray(keep, _np.bool_))

    def copy_block(self, cache, src: int, dst: int) -> None:
        """Copy pool block ``src`` onto ``dst`` (scales included for the
        int8 pool) — the copy-on-write append of prefix caching.  One
        program signature per pool family, accounted at site
        ``gen_block_copy`` with the same freeze/explain discipline as the
        model steps; warmed by ``GenerationService.warmup`` whenever the
        prefix cache is enabled."""
        self._run("gen_block_copy", cache, (_np.asarray([src], _np.int32),
                                            _np.asarray([dst], _np.int32)))

    def compile_stats(self) -> Dict[tuple, Dict[str, int]]:
        """Per-signature ``{"hits", "misses"}`` — every signature compiled
        by a warmed service must show exactly 1 miss."""
        with self._lock:
            return {k: dict(v) for k, v in self._stats.items()}

    def compiled_signatures(self) -> int:
        with self._lock:
            return len(self._stats)

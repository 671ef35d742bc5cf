"""Driver of the ``chat-decode-sat`` serving cell: ``GenerationService`` over
``granite-4.0-h-micro`` (36 Mamba-2 layers whose per-head matrix state, 80
MB a slot, is the largest thing in the cache, beside 4 no-position
grouped-query attention layers on paged K and V; a gated feed-forward in
every layer; the four muP multipliers), through the program's normal path
(``warmup()``, ``start()``, ``submit(on_token=...)``), under the load the
traffic file's generator offers.  Set-up, ramp, window and ``on_token``
stamping are ``drivers/generation.py``'s (``offer``), the one schedule for
every seed ``drivers/latent_decode.py``'s (``_OneSchedule``), the recording
service ``drivers/hybrid_decode.py``'s (``_Tokens``); ``serve_tok_s`` counts
the tokens stamped in the window.  The model is one token a row a step and
rides the engine's step in flight; every chunk of a prompt but its last runs
through the fill program, which has no head.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/granite_hybrid.py`` (float32, ``highest``,
the whole sequence at once, the scan ONE recurrence position by position,
no cache, no state carried, no chunk):

(i)  finished requests of the window — the longest among them and a seeded
     few — teacher-forced through the reference: how far each served
     token's reference logit lies under the reference's best;
(ii) the service's own programs on its own pools: seeded prompts (one that
     ends in each rung of the ladder, several past the longest chunk so
     that a state is carried across chunks, one past 4,096) through the
     engine's chunk plan — fill programs and a last chunk, as the engine
     runs them; the first into a slot that a throw-away prompt filled
     before it (the zero start) — then greedy decode steps across a block
     boundary in one batch whose other rows are idle (the identity), the
     last-position logits against the reference's full forward.

The pools are freed between the two (the probe's programs need them, the
reference needs their room).  Hooks a test or a calibration may set in
``ctx.hooks``: ``control`` (the reference one precision down stands in the
program's place), ``fault`` (one of ``reference/granite_hybrid.py::FAULTS``,
planted on the reference's side of the comparison, which must then fail),
``ref_pads`` (the lengths the reference compiles for), ``wrap_service``
(called with the service before its warm-up), ``peaks`` and
``device_prefix`` (a trace that is not a TPU's), ``readings`` (a dict that is
filled with what the comparison read: a calibration reads the control and
the faults from it in the same run).
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_ssd, harness
from perfbench.drivers import generation as gen
from perfbench.drivers.hybrid_decode import _Row, _Tokens
from perfbench.drivers.latent_decode import _OneSchedule
from perfbench.drivers.reason_decode import _prefill
from perfbench.reference import granite_hybrid as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2 has the table, the seeds and the calls): the largest that sound
# runs of the program gave, what the control gave (the reference with every
# product's result, the norms, softmax, softplus, the decay and the carried
# state in bfloat16, at the same prompts and tokens), and what the four
# PLANTED faults read in the same run.  The logits' standard deviation is
# 0.057 (the tied embedding is 0.01 n: reference/granite_hybrid.py), and
# the two gaps are absolute: read them against that.
#   logit_row_med_rel  the median over the probe's 72 rows of a row's rms
#                  difference over its logits' std: the arithmetic alone
#                  (80 branches of bfloat16 operands, each as large in the
#                  stream as the next: four times a shallower model's).
#                  Sound 0.0216-0.0238 (ten seeds), control 0.098; no state
#                  carried 1.00, no residual multiplier 1.13, 1/sqrt(64)
#                  0.69, the norm before the gate 1.12.  THE CONTROL AND ALL
#                  FOUR FAULTS FAIL HERE; the limit at the geometric middle of
#                  sound and control has 2x room each way.
#   logit_row_max_rel  the worst row.  A dense model has no row routed
#                  otherwise: sound 0.0264-0.0293, control 0.136, the faults
#                  0.76-1.23; at the geometric middle, 2x room each way:
#                  the control and the faults fail here too.
#   tie_gap_max    the widest gap of the served tokens under the
#                  reference's best.  3-4% of the served tokens are not the
#                  reference's first (the best of 100,352 logits lie
#                  close): sound 0.0036-0.0074, control 0.0198 over the two
#                  shortest requests, the faults 0.32-0.38; a token altered
#                  where it is produced reads the gap to a random logit,
#                  ~4.4 deviations: 0.25.  A sound gap is bounded by the two
#                  logits' errors (~0.0013 each).  Held 2.7x over the
#                  largest sound reading, 12x under an altered token.
#   tie_gap_mean   the mean gap.  Sound 3.1e-5-4.7e-5, control 6.6e-4, the
#                  faults 0.068-0.133: 4.2x over the largest sound reading,
#                  3.3x under the control's.
LIMITS = {"tie_gap_max": 0.02, "tie_gap_mean": 2e-4,
          "logit_row_med_rel": 0.047, "logit_row_max_rel": 0.06}
N_PROBE, PROBE_DECODE = 8, 8   # sequences and decode steps of the logits probe
N_SAMPLE = 8                   # served requests checked
REF_PADS = (2048, 4096, 8192, 18432)    # the reference compiles once a length


def _model(ctx):
    import dataclasses

    import jax.numpy as jnp
    from mxnet_tpu.parallel import granite_hybrid as gh

    c = ctx.config
    # (a program without this model, the cell's parent commit, fails here)
    names = {f.name for f in dataclasses.fields(gh.GraniteHybridConfig)}
    cfg = gh.GraniteHybridConfig(**{
        **{k: v for k, v in c.items() if k in names},
        "layer_types": ref.layer_types(c)})
    return gh.GraniteHybridLM(
        cfg, max_len=c["max_len"],
        kv_dtype=jnp.dtype(c.get("param_dtype", "bfloat16")))


def _ref_hidden(ctx, params, tokens, dtype="float32", fault=None):
    """The reference's stream behind its last norm at every position of one
    sequence, padded to one of a few lengths."""
    n = len(tokens)
    pad = next((p for p in ctx.hooks.get("ref_pads", REF_PADS) if p >= n),
               -(-n // ref.F_BLOCK) * ref.F_BLOCK)
    toks = np.zeros(pad, np.int32)
    toks[:n] = tokens
    return ref.hidden(params, ctx.config, toks, dtype=dtype, fault=fault)


def probe_lengths(ctx, svc):
    """The probe's prompt lengths: one that ends in each rung of the
    service's ladder (a single short chunk; exactly the middle rung; whole
    longest chunks and then the middle rung; whole longest chunks alone),
    leftovers behind one, two and four longest chunks — a state carried
    across chunks, one past 4,096 at the cell's size —, and a handful of
    tokens; the first and the fifth so that their decode steps cross a
    block boundary."""
    c, t = ctx.config, ctx.traffic
    bs = svc._config.block_size
    rungs = svc._seq_buckets
    r0, r1, r2 = rungs[0], rungs[len(rungs) // 2], rungs[-1]
    hi = min(t["prompt"]["max"], c["max_len"] - PROBE_DECODE - 1)
    lens = [r0 - r0 // 4, r1, r2 + r1, 2 * r2, r2 + r1 // 2 + 44,
            4 * r2 + r1 // 2 + 77, 2 * r2 + r1 + r0 + 5, 37]
    for i in (0, 4):
        lens[i] = lens[i] // bs * bs + bs - PROBE_DECODE // 2
    lens = np.clip(lens, 1, hi)
    return lens[:min(N_PROBE, svc._config.max_slots)]


def probe_programs(ctx, svc):
    """(ii), the program's side: seeded prompts prefilled through the
    engine's chunk plan and decoded ``PROBE_DECODE`` greedy steps in one
    batch, on the service's own pools of both kinds.  The first sequence's
    slot held a throw-away prompt's state before it; the decode batch is
    the service's, so every row but the probe's is idle.  Returns
    ``[(tokens, first row's position, logits rows)]``."""
    from mxnet_tpu.serving.generation.kv_cache import blocks_for

    c, gcfg = ctx.config, svc._config
    rng = ctx.rng(5)
    bs, S = gcfg.block_size, gcfg.max_slots
    lens = probe_lengths(ctx, svc)
    n = len(lens)
    seqs = [[int(t) for t in rng.integers(0, c["vocab"], k)] for k in lens]
    take = lambda k: svc._alloc_reclaiming(  # noqa: E731
        blocks_for(k + PROBE_DECODE + 1, bs))
    # the throw-away prompt: what it leaves in its slot must not be seen
    row, blocks = _Row(), take(lens[1])
    _prefill(svc, [int(t) for t in rng.integers(0, c["vocab"], lens[1])],
             blocks, row)
    svc._drop_windows(row)
    svc._cache.allocator.free(blocks)
    tables, rows, got = [], [], []
    for toks in seqs:
        tables.append(take(len(toks)))
        rows.append(_Row())
        nxt, last = _prefill(svc, toks, tables[-1], rows[-1])
        got.append([last])
        toks.append(nxt)
    zs = np.zeros(S, np.int32)
    w = svc._width_buckets[-1]
    # the probe's rows spread over the batch, idle rows between them
    at = np.linspace(0, S - 1, n).astype(int)
    for _ in range(PROBE_DECODE):
        tokens = np.zeros((S, 1), np.int32)
        positions = np.zeros((S, 1), np.int32)
        lengths, counters = zs.copy(), zs.astype(np.uint32)
        table = np.zeros((S, w), np.int32)
        for i, toks, blocks, row in zip(at, seqs, tables, rows):
            ctx_len = len(toks) - 1
            tokens[i, 0], positions[i, 0], lengths[i] = toks[-1], ctx_len, 1
            counters[i] = ctx_len + 1
            table[i, :min(w, len(blocks))] = blocks[:w]
            svc._slide(row, ctx_len, ctx_len + 1)
        nxt, last = svc._programs.run(
            "gen_decode", svc._cache, tokens, positions, lengths,
            (table, *svc._ring_tables(list(zip(at, rows)), S, 1)),
            zs.astype(np.uint32), counters, zs.astype(np.float32), zs,
            np.ones(S, np.float32))
        nxt, last = np.asarray(nxt), np.asarray(last)
        for i, toks, r in zip(at, seqs, got):
            r.append(last[i])
            toks.append(int(nxt[i]))
    svc._programs.take_aux()        # the probe's counts are nobody's
    return [(toks[:-1], int(k) - 1, np.stack(r))
            for toks, k, r in zip(seqs, lens, got)]


def probe_logits(ctx, params, fed, control=False, fault=None):
    """(ii), the comparison: the fed rows' logits against the reference's
    at the same positions — the root-mean-square difference over the
    standard deviation of the reference's logits, and the median row's."""
    c = ctx.config
    sq = var = 0.0
    rows = []
    for toks, at0, mine in fed:
        at = slice(at0, at0 + len(mine))
        want = np.asarray(ref.head(
            params, c, _ref_hidden(ctx, params, toks, fault=fault)[at]),
            np.float64)
        if control:
            mine = np.asarray(ref.head(
                params, c, _ref_hidden(ctx, params, toks, "bfloat16")[at],
                dtype="bfloat16"))
        diff2 = (np.asarray(mine, np.float64) - want) ** 2
        sq += float(np.mean(diff2))
        var += float(np.var(want))
        rows += list(np.sqrt(diff2.mean(axis=1)) / want.std(axis=1))
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "logit_row_med_rel": float(np.median(rows)),
            "logit_row_max_rel": float(np.max(rows)), "rows": len(rows),
            "logit_std": float(np.sqrt(var / len(fed)))}


def served_gaps(ctx, params, sample, control=False, fault=None):
    """(i): every served token of the sampled requests, teacher-forced:
    how far its reference logit lies below the reference's best — the
    widest gap, the mean gap, the count of tokens that are not the
    reference's first.  The head is taken ``ref.HEAD_ROWS`` rows a call and
    a call's gaps come back, not its logits (a row is 0.4 MB).
    ``control``: the token the bfloat16 reference puts first stands in for
    the served one."""
    import jax.numpy as jnp

    c = ctx.config
    gaps = []
    for rec in sample:
        toks = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
        lo, n_out = len(rec.prompt) - 1, len(rec.tokens)
        x = _ref_hidden(ctx, params, toks, fault=fault)
        low = _ref_hidden(ctx, params, toks, "bfloat16") if control else None
        for i in range(0, n_out, ref.HEAD_ROWS):
            at = slice(lo + i, lo + min(i + ref.HEAD_ROWS, n_out))
            lg = ref.head(params, c, x[at])
            if control:
                chosen = jnp.argmax(ref.head(params, c, low[at],
                                             dtype="bfloat16"), axis=-1)
            else:
                chosen = jnp.asarray(rec.tokens[i:i + ref.HEAD_ROWS],
                                     jnp.int32)
            picked = jnp.take_along_axis(lg, chosen[:, None], axis=1)[:, 0]
            gaps.append(np.asarray(lg.max(axis=-1) - picked, np.float64))
    gaps = np.concatenate(gaps)
    return {"tie_gap_max": float(gaps.max()),
            "tie_gap_mean": float(gaps.mean()),
            "tokens": int(gaps.size), "not_first": int((gaps > 0).sum())}


def pick_sample(ctx, finished):
    """The served requests (i) checks: the longest and a seeded few."""
    pool = sorted(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    if not pool:
        return []
    sample = [pool.pop()]
    rng = ctx.rng(6)
    return sample + [pool[i] for i in rng.permutation(len(pool))[
        :N_SAMPLE - len(sample)]]


def build(ctx):
    """Set-up up to a started service: the benchmark's weights on the
    device from the seed, the service, its warm-up."""
    from mxnet_tpu.executor import compile_cache_stats
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    c, t = ctx.config, ctx.traffic
    # first the model: a program that has no such model (this cell's
    # parent commit) fails here, before anything is made on the device
    model = _model(ctx)
    params = ref.init_params(ctx.seed, c, c.get("param_dtype", "bfloat16"))
    ctx.mark("weights")
    service = dict(c["service"], **t.get("service", {}))
    service["seq_buckets"] = tuple(service["seq_buckets"])
    svc = GenerationService(params, model, GenerationConfig(**service),
                            start=False)
    if ctx.hooks.get("wrap_service"):
        ctx.hooks["wrap_service"](svc)
    ctx.mark("service")
    n_programs = svc.warmup()
    ctx.mark("warmup")
    warm = (compile_cache_stats(), harness.CompileClock.snapshot())
    svc.start()
    return svc, params, n_programs, warm


def _shapes(ctx):
    c = ctx.config
    kinds = ref.layer_types(c)
    return dict(H=c["num_attention_heads"], hkv=c["num_key_value_heads"],
                dh=c["hidden_size"] // c["num_attention_heads"],
                attn=kinds.count("attention"),
                state=(c["mamba_n_heads"], c["mamba_d_head"],
                       c["mamba_d_state"], c["mamba_d_conv"],
                       kinds.count("mamba")))


def _work_counters(ctx, d, prefix=""):
    """Operations and bytes of a span from the program's own counts
    (``d``: deltas of ``stats()["counts"]``), by ``counts_ssd.py``: the
    model's mathematics, whatever the layout."""
    from mxnet_tpu.ops.paged_attention import _TILE_ROWS

    m = _shapes(ctx)
    H, hkv, dh, attn, state = m["H"], m["hkv"], m["dh"], m["attn"], m["state"]
    Hm, P, N, _, n_ssd = state
    return {
        prefix + "full_decode_bytes": counts_ssd.kv_read_bytes(
            d["full_ctx_tokens"], hkv, dh, attn),
        prefix + "full_decode_flops": counts_ssd.attn_flops(
            d["full_ctx_tokens"], H, dh, attn),
        prefix + "ssd_decode_bytes": counts_ssd.ssd_decode_bytes(
            d["ssd_decode_rows"], *state),
        prefix + "ssd_decode_flops": counts_ssd.ssd_flops(
            d["ssd_decode_rows"], Hm, P, N, n_ssd),
        prefix + "scan_prefill_bytes": counts_ssd.scan_prefill_bytes(
            d["ssd_prefill_chunks"], d["ssd_prefill_tokens"], *state),
        prefix + "scan_prefill_flops": counts_ssd.ssd_flops(
            d["ssd_prefill_tokens"], Hm, P, N, n_ssd),
        prefix + "prefill_attn_flops": counts_ssd.attn_flops(
            d["full_prefill_pairs"], H, dh, attn),
        # a tile is _TILE_ROWS queries of ONE query head of each KV head
        prefix + "prefill_attn_bytes": counts_ssd.prefill_read_bytes(
            d["full_prefill_pairs"], _TILE_ROWS / (H // hkv), hkv, dh, attn)}


def _cache_counters(svc, snap):
    """What the manager holds at a ``stats()`` read: the bytes of both
    kinds' units a live token."""
    kinds = svc._cache.kinds
    used = [snap["cache_kinds"][k.name]["used"] for k in kinds]
    per_unit = [sum(int(p.nbytes) for p in svc._cache.pools[k.span])
                // k.num_blocks for k in kinds]
    got = counts_ssd.cache_bytes_per_token(used, per_unit,
                                           snap["live_tokens"])
    return {} if got is None else {"cache_bytes_per_token": got}


def run(ctx):
    import jax
    from mxnet_tpu.executor import compile_cache_stats
    from mxnet_tpu.ops import pallas_kernels as pk

    c, t = ctx.config, ctx.traffic
    devs = jax.devices()
    svc, params, n_programs, warm = build(ctx)
    rec = _Tokens(svc)
    w = gen.offer(_OneSchedule(ctx), rec)
    setup_s = w["t0"] - ctx.t_proc0
    records, mine, finished, failed = \
        w["records"], w["mine"], w["finished"], w["failed"]
    window_s, sl = w["window_s"], w["slice"]
    svc.stop(drain=False, timeout=60)
    after = (compile_cache_stats(), harness.CompileClock.snapshot())
    compiles = (after[0]["misses"] - warm[0]["misses"]) \
        + (after[1]["compiles"] - warm[1]["compiles"])
    kernel = svc.stats()["decode_kernel"]
    peak = harness.memory_peak_bytes(devs)
    e2e = {"setup_s": setup_s, "serve_tok_s": w["serve_tok_s"]}
    clock = harness.CompileClock.snapshot()
    print(f"setup: setup_s={setup_s:.3f} compile_s={clock['compile_s']:.3f}"
          f" cache_hits={clock['hits']} cache_misses={clock['misses']} "
          f"programs={n_programs} kernel={kernel} marks={ctx.marks}",
          flush=True)
    at_open, at_close = w["at_open"], w["at_close"]
    iters = at_close["iterations"] - at_open["iterations"]
    d = {k: at_close["counts"][k] - at_open["counts"][k]
         for k in at_close["counts"]}
    # (two of the counts are gauges: what the state kind holds)
    slot_bytes = at_close["counts"]["state_bytes_per_slot"]
    steps = d["steps_ahead"] + d["steps_drained"]
    cache = _cache_counters(svc, rec.snaps[-1])
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} decode_steps={steps} ahead={d['steps_ahead']} "
          f"prefill_tokens={d['prefill_tokens']} prefill_chunks="
          f"{d['prefill_chunks']} ssd_decode_rows={d['ssd_decode_rows']} "
          f"ssd_prefill_tokens={d['ssd_prefill_tokens']} chunks="
          f"{d['ssd_prefill_chunks']} rows_started={d['ssd_rows_started']} "
          f"full_ctx_tokens={d['full_ctx_tokens']} full_prefill_pairs="
          f"{d['full_prefill_pairs']} requests_sent={len(records)} "
          f"of_window={len(mine)} finished={len(finished)} "
          f"failed={len(failed)} preempted={d['preempted']} "
          f"waiting_at_close={at_close['waiting']} running_at_close="
          f"{at_close['running']} live_tokens_at_close="
          f"{rec.snaps[-1]['live_tokens']} slots_live_at_close="
          f"{at_close['counts']['state_slots_live']} state_bytes_per_slot="
          f"{slot_bytes} cache_kinds_at_close={rec.snaps[-1]['cache_kinds']} "
          f"cache={cache} compiles_after_warmup={compiles}", flush=True)

    # the window's counter deltas whole, and what the reducers divide by
    src = harness.Sources(
        counters=dict(
            d, window_ms=window_s * 1e3, iterations=iters, tokens=w["tokens"],
            slot_iterations=iters * svc._config.max_slots,
            kv_peak_occupancy=at_close["peak_occupancy"],
            preemptions=d["preempted"], compiles_after_warmup=compiles,
            decode_steps=steps, state_bytes_per_slot=slot_bytes, **cache,
            **{"slice.iterations": w["slice_iters"]}),
        events=[r.stream.stats() for r in mine if r.stream is not None],
        config=c, traffic=t)
    if ctx.trace:
        src.peaks = ctx.hooks.get("peaks") or counts.peaks(
            devs[0].device_kind)
        src.trace = sl.load(ctx.hooks.get("device_prefix", "/device:TPU:"))
        # the slice's own counter deltas: ``offer`` read stats() at the
        # window's open, the slice's two ends and the window's close.  A
        # step in flight at either end is counted a step late at both.
        a, b = rec.snaps[1]["counts"], rec.snaps[2]["counts"]
        ds = {k: b[k] - a[k] for k in b}
        src.counters.update({"slice." + k: v for k, v in ds.items()})
        src.counters.update(_work_counters(ctx, ds, "slice."))

    # -- correct: what the window served, against the plain reference ------
    control, fault = bool(ctx.hooks.get("control")), ctx.hooks.get("fault")
    sample = pick_sample(ctx, finished)
    t_ref = time.perf_counter()
    checks = [("finished_requests", len(finished), ">=1",
               len(finished) >= 1)]
    if sample:
        fed = probe_programs(ctx, svc)
        # the probe's programs needed the pools; the reference needs their room
        for arr in svc._cache.pools:
            arr.delete()
        readings = ctx.hooks.get("readings")
        if readings is not None:
            readings.update(fed=fed, sample=sample, params=params)
        probe = probe_logits(ctx, params, fed, control, fault)
        served = served_gaps(ctx, params, sample, control, fault)
        print(f"reference: {time.perf_counter() - t_ref:.1f} s over "
              f"{served['tokens']} served tokens of {len(sample)} requests "
              f"(prompts {[len(r.prompt) for r in sample]}, outputs "
              f"{[len(r.tokens) for r in sample]}; not the reference's "
              f"first: {served['not_first']}) and {probe['rows']} probe rows "
              f"(prompts {[len(f[0]) - PROBE_DECODE for f in fed]}; the "
              f"logits' std {probe['logit_std']:.4f})", flush=True)
        checks += [(name, got[name], LIMITS[name], got[name] <= LIMITS[name])
                   for name, got in (("tie_gap_max", served),
                                     ("tie_gap_mean", served),
                                     ("logit_row_med_rel", probe),
                                     ("logit_row_max_rel", probe))]
        print(f"probe rows: logit_rms_rel {probe['logit_rms_rel']:.4f} "
              f"(compared with nothing)", flush=True)
    native = pk.pallas_enabled() and not pk._use_interpret()
    checks += [("compiles_after_warmup", compiles, 0, compiles == 0),
               ("failed_requests", len(failed), 0, len(failed) == 0),
               ("decode_kernel", kernel, c["decode_kernel"],
                kernel == c["decode_kernel"]
                and (native or not ctx.require_tpu))]
    outcome = {"e2e": e2e, "sources": src, "checks": checks,
               "attempted": len(mine), "failed": len(failed),
               "memory_peak_bytes": peak}
    # free the chip for whoever drives the next seed in this process
    if ctx.hooks.get("readings") is None:
        for arr in (*svc._cache.pools, *params.values()):
            if not arr.is_deleted():
                arr.delete()
    return outcome

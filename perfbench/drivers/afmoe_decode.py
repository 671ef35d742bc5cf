"""Driver of the ``afmoe`` serving cell: ``GenerationService`` over
``Trinity-Mini`` (window layers with rotary beside full layers without,
gated attention with a QK-norm, sandwich norms, 128 sigmoid-routed experts
with a shared one of which the chip holds 16; a cache of two kinds whose
WINDOW kind, 2,048 positions a row whatever the row's length, is the largest
thing in it), through the program's normal path (``warmup()``, ``start()``,
``submit(on_token=...)``), under the load the traffic file's generator
offers.  Set-up, ramp, window and ``on_token`` stamping are
``drivers/generation.py``'s (``offer``), the one schedule for every seed
``drivers/latent_decode.py``'s (``_OneSchedule``), the recording service
``drivers/hybrid_decode.py``'s (``_Tokens``); ``serve_tok_s`` counts the
tokens stamped in the window.  The model is one token a row a step and rides
the engine's step in flight.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/afmoe.py`` (float32, ``highest``, attention
materialised over the whole sequence with the window a mask, given the same
share of the experts):

(i)  finished requests — the longest among them, the longest whose prompt
     was under the window and whose end was past it (it crossed the window
     WHILE IT DECODED), and a seeded few — teacher-forced through the
     reference: how far each served token's reference logit lies under the
     reference's best;
(ii) the service's own prefill and decode programs on its own caches:
     seeded prompts (leftovers of every length behind the last whole chunk,
     one a few positions short of the window, one of several thousand
     tokens so that window blocks have been freed and reused) through the
     engine's chunk plan, then greedy decode steps in one full batch —
     across a block boundary and ACROSS the window's edge —, the
     last-position logits against the reference's full forward.

The pools are freed between the two (the probe's programs need them, the
reference needs their room).  Hooks a test or a calibration may set in
``ctx.hooks``: ``control`` (the reference one precision down stands in the
program's place), ``fault`` (one of ``reference/afmoe.py::FAULTS``, planted
on the reference's side of the comparison, which must then fail),
``ref_pads`` (the lengths the reference compiles for), ``wrap_service``
(called with the service before its warm-up), ``peaks`` and
``device_prefix`` (a trace that is not a TPU's), ``readings`` (a dict that is
filled with what the comparison read: a calibration reads the control and
the faults from it in the same run).
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_afmoe, harness
from perfbench.drivers import generation as gen
from perfbench.drivers.hybrid_decode import _Row, _Tokens
from perfbench.drivers.latent_decode import _OneSchedule
from perfbench.reference import afmoe as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2 has the table, the seeds and the calls): the largest that sound
# runs of the program gave, the smallest that the control gave (the
# reference with router scores, norms, sigmoids, softmax and every product's
# result in bfloat16, at the same prompts and tokens), and what the four
# PLANTED faults read in the same run.  The names are ``hybrid_decode``'s:
#   logit_row_med_rel  the median over the probe's 72 rows of a row's rms
#                  difference over its logits' std: the arithmetic alone (a
#                  row routed otherwise on the two sides is left out by the
#                  median).  Sound 0.0071-0.0073 (six seeds), control 0.0170; the gate
#                  left out 0.606, rotary on the full layers 0.150, a
#                  window of 1,792 0.409, no norm on the branches' outputs
#                  1.31.  THE CONTROL AND EVERY FAULT FAIL HERE; the limit
#                  at the geometric middle has 1.5x room each way.
#   logit_row_max_rel  the worst row.  Sound 0.115-0.200 (control 0.201: a
#                  token whose eighth and ninth expert lie closer than the
#                  rounding is routed otherwise on either side); a row that
#                  is wholly wrong (another position's logits) reads 1.4,
#                  whatever the seed.  Held between the two, 3x over the
#                  largest sound reading.
#   tie_gap_mean   the mean gap of the served tokens under the reference's
#                  best.  Sound 0.0032-0.0061 (one token in twenty is not the
#                  reference's first: the best of 200,192 seeded logits lie
#                  close together), control 0.0085: this one cannot tell
#                  them apart (the probe's median row does).  Held at
#                  3.3x the largest sound reading (an MoE's gaps have the
#                  tail routing gives them: PR 26 lost a sound run at 1.3x)
#                  against one served token in 225 altered where it is
#                  produced (+0.020).
#   tie_gap_max    the widest such gap.  Sound 0.50-0.64 (control 0.56); a
#                  token altered where it is produced reads ~4.5 (the best
#                  of 200,192 logits over a random one).  Held 3.1x over the
#                  largest sound reading, 2.2x under that.
LIMITS = {"tie_gap_max": 2.0, "tie_gap_mean": 0.02,
          "logit_row_med_rel": 0.011, "logit_row_max_rel": 0.6}
N_PROBE, PROBE_DECODE = 8, 8   # sequences and decode steps of the logits probe
PROBE_LONGEST = 6600           # the probe's longest prompt
N_SAMPLE = 4                   # served requests checked
REF_PADS = (4096, 8192, 16384, 32768)   # the reference compiles once a length


def _model(ctx):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import hybrid_moe as hm

    c = ctx.config
    # (a program without this model — the cell's parent commit — fails here)
    cfg = hm.HybridMoeConfig.from_afmoe(
        c, n_routed_experts=c["published"]["num_experts"])
    return hm.HybridMoeLM(cfg, max_len=c["max_len"],
                          experts_held=tuple(c["experts_held"]),
                          kv_dtype=jnp.dtype(c.get("param_dtype", "bfloat16")))


def _ref_hidden(ctx, params, tokens, dtype="float32", fault=None):
    """The reference's stream behind its last norm at every position of one
    sequence, padded to one of a few lengths."""
    n = len(tokens)
    pad = next((p for p in ctx.hooks.get("ref_pads", REF_PADS) if p >= n),
               -(-n // ref.Q_BLOCK) * ref.Q_BLOCK)
    toks = np.zeros(pad, np.int32)
    toks[:n] = tokens
    return ref.hidden(params, ctx.config, toks, n, dtype=dtype, fault=fault)


def probe_lengths(ctx, gcfg):
    """The probe's prompt lengths: leftovers of every length behind the
    last whole chunk up to ``PROBE_LONGEST``; the first so that its decode
    steps cross the window's edge, the second a block boundary."""
    c, t = ctx.config, ctx.traffic
    bs, win = gcfg.block_size, c["sliding_window"]
    n = min(N_PROBE, gcfg.max_slots)
    hi = min(PROBE_LONGEST, t["prompt"]["max"],
             c["max_len"] - PROBE_DECODE - 1)
    lens = np.minimum(np.linspace(t["prompt"]["min"], hi, n).astype(int)
                      + 37 * np.arange(n), hi)
    lens[0] = max(1, win - PROBE_DECODE // 2)
    lens[1] = lens[1] // bs * bs + bs - PROBE_DECODE // 2
    return lens


def probe_programs(ctx, svc):
    """(ii), the program's side: seeded prompts prefilled through the
    engine's chunk plan and decoded ``PROBE_DECODE`` greedy steps in one
    batch, on the service's own caches of both kinds.  Returns ``[(tokens,
    first row's position, logits rows)]``."""
    from mxnet_tpu.serving.bucketing import pad_tokens_right
    from mxnet_tpu.serving.generation.kv_cache import blocks_for

    c, gcfg = ctx.config, svc._config
    rng = ctx.rng(5)
    bs, S = gcfg.block_size, gcfg.max_slots
    lens = probe_lengths(ctx, gcfg)
    n = len(lens)
    seqs = [[int(t) for t in rng.integers(0, c["vocab"], k)] for k in lens]
    tables, rows, got = [], [], []
    z1 = np.zeros(1, np.int32)
    for toks in seqs:
        blocks = svc._alloc_reclaiming(blocks_for(len(toks) + PROBE_DECODE
                                                  + 1, bs))
        row = _Row()
        tables.append(blocks)
        rows.append(row)
        for off, take, tb, wp in svc._chunk_plan(len(toks)):
            table = np.zeros((1, wp), np.int32)
            table[0, :min(wp, len(blocks))] = blocks[:wp]
            svc._slide(row, off, off + take)
            nxt, last = svc._programs.run(
                "gen_prefill", svc._cache,
                pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                                 tb)[None, :],
                np.arange(off, off + tb, dtype=np.int32)[None, :],
                np.asarray([take], np.int32),
                (table, *svc._ring_tables([(0, row)], 1, tb)),
                z1.astype(np.uint32), np.asarray([len(toks)], np.uint32),
                z1.astype(np.float32), z1, np.ones(1, np.float32))
            svc._slide(row, off + take, off + take)
        got.append([np.asarray(last[0])])
        toks.append(int(nxt[0]))
    zs = np.zeros(S, np.int32)
    w = svc._width_buckets[-1]
    for _ in range(PROBE_DECODE):
        tokens = np.zeros((S, 1), np.int32)
        positions = np.zeros((S, 1), np.int32)
        lengths, counters = zs.copy(), zs.astype(np.uint32)
        table = np.zeros((S, w), np.int32)
        for i, toks in enumerate(seqs):
            ctx_len = len(toks) - 1
            tokens[i, 0], positions[i, 0], lengths[i] = toks[-1], ctx_len, 1
            counters[i] = ctx_len + 1
            table[i, :min(w, len(tables[i]))] = tables[i][:w]
            svc._slide(rows[i], ctx_len, ctx_len + 1)
        nxt, last = svc._programs.run(
            "gen_decode", svc._cache, tokens, positions, lengths,
            (table, *svc._ring_tables(list(enumerate(rows)), S, 1)),
            zs.astype(np.uint32), counters, zs.astype(np.float32), zs,
            np.ones(S, np.float32))
        last = np.asarray(last[:n])
        for i, toks in enumerate(seqs):
            got[i].append(last[i])
            toks.append(int(nxt[i]))
    return [(toks[:-1], int(k) - 1, np.stack(r))
            for toks, k, r in zip(seqs, lens, got)]


def probe_logits(ctx, params, fed, control=False, fault=None):
    """(ii), the comparison: the fed rows' logits against the reference's
    at the same positions — the root-mean-square difference over the
    standard deviation of the reference's logits, and the median row's."""
    sq = var = 0.0
    rows = []
    for toks, at0, mine in fed:
        at = slice(at0, at0 + len(mine))
        want = np.asarray(ref.head(
            params, _ref_hidden(ctx, params, toks, fault=fault)[at]),
            np.float64)
        if control:
            mine = np.asarray(ref.head(
                params, _ref_hidden(ctx, params, toks, "bfloat16")[at],
                dtype="bfloat16"))
        diff2 = (np.asarray(mine, np.float64) - want) ** 2
        sq += float(np.mean(diff2))
        var += float(np.var(want))
        rows += list(np.sqrt(diff2.mean(axis=1)) / want.std(axis=1))
    # a token whose eighth and ninth experts lie closer than the rounding
    # is routed otherwise on the two sides, and its row reads many times
    # the others': the root-mean-square takes those in, the median row
    # leaves them out and reads the arithmetic
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "logit_row_med_rel": float(np.median(rows)),
            "logit_row_max_rel": float(np.max(rows)), "rows": len(rows)}


def served_gaps(ctx, params, sample, control=False, fault=None):
    """(i): every served token of the sampled requests, teacher-forced:
    how far its reference logit lies below the reference's best — the
    widest gap, the mean gap, the count of tokens that are not the
    reference's first.  The head is taken ``ref.HEAD_ROWS`` rows a call and
    a call's gaps come back, not its logits (a row is 0.8 MB).
    ``control``: the token the bfloat16 reference puts first stands in for
    the served one."""
    import jax.numpy as jnp

    gaps = []
    for rec in sample:
        toks = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
        lo, n_out = len(rec.prompt) - 1, len(rec.tokens)
        x = _ref_hidden(ctx, params, toks, fault=fault)
        low = _ref_hidden(ctx, params, toks, "bfloat16") if control else None
        for i in range(0, n_out, ref.HEAD_ROWS):
            at = slice(lo + i, lo + min(i + ref.HEAD_ROWS, n_out))
            lg = ref.head(params, x[at])
            if control:
                chosen = jnp.argmax(ref.head(params, low[at],
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
    """The served requests (i) checks: the longest, the longest whose
    prompt was under the window and whose end was past it if that is
    another, and a seeded few."""
    win = ctx.config["sliding_window"]
    pool = sorted(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    if not pool:
        return []
    sample = [pool.pop()]
    crossed = [r for r in pool if len(r.prompt) < win
               and len(r.prompt) + len(r.tokens) > win]
    if crossed:
        sample.append(crossed[-1])
        pool.remove(crossed[-1])
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
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return dict(H=c["num_attention_heads"], hkv=c["num_key_value_heads"],
                dh=c["head_dim"],
                layers=(kinds.count("full_attention"),
                        kinds.count("sliding_attention")),
                expert_layers=c["num_hidden_layers"] - c["num_dense_layers"])


def _work_counters(ctx, d, prefix=""):
    """Operations and bytes of a span from the program's own counts
    (``d``: deltas of ``stats()["counts"]``), by ``counts_afmoe.py``."""
    from mxnet_tpu.ops.paged_attention import _TILE_ROWS

    c, m = ctx.config, _shapes(ctx)
    H, hkv, dh = m["H"], m["hkv"], m["dh"]
    dm, df = c["hidden_size"], c["moe_intermediate_size"]
    out = {}
    pre_bytes = pre_flops = 0
    for kind, name in enumerate(("full", "window")):
        nl = m["layers"][kind]
        out[f"{prefix}{name}_decode_bytes"] = counts_afmoe.kind_read_bytes(
            d[f"{name}_ctx_tokens"], hkv, dh, dh, nl)
        out[f"{prefix}{name}_decode_flops"] = counts_afmoe.kind_flops(
            d[f"{name}_ctx_tokens"], H, dh, dh, nl)
        pre_flops += counts_afmoe.kind_flops(
            d[f"{name}_prefill_pairs"], H, dh, dh, nl)
        # a tile is _TILE_ROWS queries of ONE query head of each KV head
        pre_bytes += counts_afmoe.prefill_read_bytes(
            d[f"{name}_prefill_pairs"], _TILE_ROWS / (H // hkv), hkv, dh, dh,
            nl)
    out.update({prefix + "prefill_attn_flops": pre_flops,
                prefix + "prefill_attn_bytes": pre_bytes,
                prefix + "moe_bytes": counts_afmoe.held_expert_bytes(
                    d["experts_touched"], dm, df),
                prefix + "moe_flops": counts_afmoe.held_expert_flops(
                    d["expert_assignments_held"], dm, df)})
    return out


def _cache_counters(svc, snap):
    """What the manager holds at a ``stats()`` read: the window kind's
    blocks a running row and their share of the blocks the kind holds, and
    the bytes of both kinds' blocks a live token."""
    kinds = svc._cache.kinds
    used = [snap["cache_kinds"][k.name]["used"] for k in kinds]
    per_block = [sum(int(p.nbytes) for p in svc._cache.pools[k.span])
                 // k.num_blocks for k in kinds]
    out = {"cache_bytes_per_token": counts_afmoe.cache_bytes_per_token(
               used, per_block, snap["live_tokens"]),
           "window_pool_used_pct": counts_afmoe.pool_used_pct(
               used[1], snap["cache_kinds"][kinds[1].name]["total"])}
    if snap["running"]:
        out["window_blocks_per_row"] = used[1] / snap["running"]
    return {k: v for k, v in out.items() if v is not None}


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
    steps = d["steps_ahead"] + d["steps_drained"]
    # every token but a request's first is a decode row's
    decode_rows = d["tokens"] - d["prefills_ahead"] - d["prefills_read"]
    cache = _cache_counters(svc, rec.snaps[-1])
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} decode_steps={steps} ahead={d['steps_ahead']} "
          f"decode_rows={decode_rows} prefill_tokens={d['prefill_tokens']} "
          f"full_ctx_tokens={d['full_ctx_tokens']} window_ctx_tokens="
          f"{d['window_ctx_tokens']} window_rows_past="
          f"{d['window_rows_past']} window_decode_trips="
          f"{d['window_decode_trips']} window_blocks_freed="
          f"{d['window_blocks_freed']} assignments={d['expert_assignments']} "
          f"held={d['expert_assignments_held']} touched="
          f"{d['experts_touched']} shared_expert_tokens="
          f"{d['shared_expert_tokens']} requests_sent={len(records)} "
          f"of_window={len(mine)} finished={len(finished)} failed="
          f"{len(failed)} preempted={d['preempted']} waiting_at_close="
          f"{at_close['waiting']} running_at_close={at_close['running']} "
          f"live_tokens_at_close={rec.snaps[-1]['live_tokens']} "
          f"cache_kinds_at_close={rec.snaps[-1]['cache_kinds']} "
          f"cache={cache} compiles_after_warmup={compiles}", flush=True)

    m = _shapes(ctx)
    held = c["experts_held"][1] - c["experts_held"][0]
    # the window's counter deltas whole, and what the reducers divide by
    src = harness.Sources(
        counters=dict(
            d, window_ms=window_s * 1e3, iterations=iters, tokens=w["tokens"],
            slot_iterations=iters * svc._config.max_slots,
            kv_peak_occupancy=at_close["peak_occupancy"],
            preemptions=d["preempted"], compiles_after_warmup=compiles,
            decode_steps=steps, decode_rows=decode_rows,
            window_layer_rows=decode_rows * m["layers"][1],
            expert_mean_load=d["expert_assignments_held"]
            / (held * m["expert_layers"]), **cache,
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
              f"(prompts {[len(f[0]) - PROBE_DECODE for f in fed]})",
              flush=True)
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

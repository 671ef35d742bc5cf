"""Driver of the serving cells: ``GenerationService`` over the
configuration's transformer, through the program's normal path
(``warmup()``, ``start()``, ``submit(on_token=...)``), under the load the
traffic file's generator offers.  The benchmark stamps every token itself
in ``on_token`` and times requests from when they were due.

Set-up: weights on the device from the seed, the service, its warm-up (the
programs this configuration's ladder holds and no others), the ramp.  The
window: ``--seconds`` of the same traffic.  After it: no further arrivals;
an open loop's requests are drained, a closed loop's unfinished ones are
cut (they are the loop's standing backlog, not failures).  Then the
comparison with the plain reference, on what the window served.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from perfbench import counts, harness
from perfbench.reference import gpt2 as ref

# The limits, each set from readings on the chip at the cell's own size
# (PERF.md section 2): the largest that sound runs of the program gave over
# 12 seeds, and the smallest that the control (the reference in bfloat16,
# at the same prompts and tokens) gave over 4.
#   logit_rms_rel  sound 0.0070-0.0074; control 0.0154-0.0159.  Only 2.1x
#                  apart, because the stated precision (f32 at the TPU's
#                  default) already multiplies in one bf16 pass and differs
#                  from bf16 only in what is stored between products; but
#                  both sides repeat within 3% from seed to seed, so the
#                  limit at their geometric middle has 1.4x room each way.
#                  THE CONTROL FAILS HERE.
#   tie_gap_max    sound <= 0.0250; control 0.0253-0.0391: a widest gap,
#                  which swings by its nature and cannot tell them apart.
#                  Held at 3x against a token altered where it is produced
#                  (that reads about 1 and more).
#   tie_gap_mean   sound <= 9.6e-5; control 2.2e-4-3.9e-4.  Held at 3x.
LIMITS = {"tie_gap_max": 0.075, "tie_gap_mean": 3e-4, "logit_rms_rel": 0.0105}
N_SAMPLE = 8            # served requests checked, the longest among them
N_PROBE, PROBE_DECODE = 8, 8   # sequences and decode steps of the logits probe


def _model(ctx):
    from mxnet_tpu.parallel import transformer as tr

    c = ctx.config
    return tr.TransformerConfig(vocab=c["vocab"], d_model=c["d_model"],
                                n_heads=c["n_heads"], n_layers=c["n_layers"],
                                d_ff=c["d_ff"], max_len=c["max_len"])


def _ref_logits(ctx, params, tokens, dtype="float32"):
    """Reference logits of one sequence, padded to ``max_len``."""
    c = ctx.config
    padded = np.zeros(c["max_len"], np.int32)
    padded[:len(tokens)] = tokens
    return ref.logits(params, padded, n_layers=c["n_layers"],
                      n_heads=c["n_heads"], dtype=dtype)


def served_gaps(ctx, params, sample, control=False):
    """Over the sampled requests' served tokens: how far the served
    token's reference logit lies below the reference's best — the widest
    gap, the mean gap, and the share of tokens that are not the
    reference's first.  ``control``: the token the bfloat16 reference puts
    first stands in for the served one."""
    import jax.numpy as jnp

    gaps = []
    for rec in sample:
        toks = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
        lg = _ref_logits(ctx, params, toks)
        lo, hi = len(rec.prompt) - 1, len(toks) - 1
        at = lg[lo:hi]
        if control:
            chosen = jnp.argmax(_ref_logits(ctx, params, toks,
                                            "bfloat16")[lo:hi], axis=-1)
        else:
            chosen = jnp.asarray(rec.tokens, jnp.int32)
        picked = jnp.take_along_axis(at, chosen[:, None], axis=1)[:, 0]
        gaps.append(np.asarray(at.max(axis=-1) - picked, np.float64))
    gaps = np.concatenate(gaps)
    return {"tie_gap_max": float(gaps.max()),
            "tie_gap_mean": float(gaps.mean()),
            "tokens": int(gaps.size), "not_first": int((gaps > 0).sum())}


def probe_logits(ctx, svc, params, control=False):
    """The service's own step programs, called the way ``warmup()`` calls
    them on the service's own cache: ``N_PROBE`` seeded prompts prefilled
    (the engine's chunk plan) and decoded ``PROBE_DECODE`` greedy steps in
    one batch; their last-position logits against the reference's at the
    same positions, as the root-mean-square difference over the standard
    deviation of the reference's logits."""
    from mxnet_tpu.serving.bucketing import bucket_batch, pad_tokens_right
    from mxnet_tpu.serving.generation.kv_cache import blocks_for

    c, gcfg = ctx.config, svc._config
    rng = ctx.rng(5)
    bs, S = gcfg.block_size, gcfg.max_slots
    hi = min(ctx.traffic["prompt"]["max"], c["max_len"] - PROBE_DECODE - 1)
    lens = np.linspace(ctx.traffic["prompt"]["min"], hi,
                       min(N_PROBE, S)).astype(int)
    seqs = [list(rng.integers(0, c["vocab"], n)) for n in lens]
    tables, got = [], []           # block ids per row; logits per row
    z1 = np.zeros(1, np.int32)
    for toks in seqs:
        blocks = svc._alloc_reclaiming(blocks_for(len(toks) + PROBE_DECODE
                                                  + 1, bs))
        tables.append(blocks)
        n = len(toks)
        for off, take, tb, wp in svc._chunk_plan(n):
            table = np.zeros((1, wp), np.int32)
            k = min(wp, len(blocks))
            table[0, :k] = blocks[:k]
            nxt, last = svc._programs.run(
                "gen_prefill", svc._cache,
                pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                                 tb)[None, :],
                np.arange(off, off + tb, dtype=np.int32)[None, :],
                np.asarray([take], np.int32), table, z1.astype(np.uint32),
                np.asarray([n], np.uint32), z1.astype(np.float32), z1,
                np.ones(1, np.float32))
        got.append([np.asarray(last[0])])
        toks.append(int(nxt[0]))
    zs = np.zeros(S, np.int32)
    for _ in range(PROBE_DECODE):
        tokens, positions = np.zeros((S, 1), np.int32), \
            np.zeros((S, 1), np.int32)
        lengths, counters = zs.copy(), zs.astype(np.uint32)
        w = bucket_batch(max(blocks_for(len(t), bs) for t in seqs),
                         svc._width_buckets)
        table = np.zeros((S, w), np.int32)
        for i, toks in enumerate(seqs):
            ctx_len = len(toks) - 1
            tokens[i, 0], positions[i, 0], lengths[i] = toks[-1], ctx_len, 1
            counters[i] = ctx_len + 1
            k = min(w, len(tables[i]))
            table[i, :k] = tables[i][:k]
        nxt, last = svc._programs.run(
            "gen_decode", svc._cache, tokens, positions, lengths, table,
            zs.astype(np.uint32), counters, zs.astype(np.float32), zs,
            np.ones(S, np.float32))
        last = np.asarray(last)
        for i, toks in enumerate(seqs):
            got[i].append(last[i])
            toks.append(int(nxt[i]))
    sq = var = 0.0
    for i, toks in enumerate(seqs):
        lg = _ref_logits(ctx, params, toks[:-1])
        rows = np.asarray(lg[lens[i] - 1:len(toks) - 1], np.float64)
        if control:
            mine = np.asarray(_ref_logits(ctx, params, toks[:-1], "bfloat16")
                              [lens[i] - 1:len(toks) - 1], np.float64)
        else:
            mine = np.stack(got[i]).astype(np.float64)
        sq += float(np.mean((mine - rows) ** 2))
        var += float(np.var(rows))
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "rows": len(seqs) * (PROBE_DECODE + 1)}


def compare(served, probe, limits=LIMITS):
    out = []
    for name, got in (("tie_gap_max", served), ("tie_gap_mean", served),
                      ("logit_rms_rel", probe)):
        out.append((name, got[name], limits[name],
                    got[name] <= limits[name]))
    return out


def _snapshot(svc):
    s = svc.stats()
    return {"iterations": s["iterations"], "counts": s["counts"],
            "peak_occupancy": s["kv_blocks"]["peak_occupancy"],
            "waiting": s["waiting"], "running": s["running"]}


def build(ctx):
    """Set-up up to a started service: the benchmark's weights on the
    device from the seed, the service, its warm-up."""
    from mxnet_tpu.executor import compile_cache_stats
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    c, t = ctx.config, ctx.traffic
    params = ref.init_params(ctx.seed, vocab=c["vocab"],
                             d_model=c["d_model"], n_layers=c["n_layers"],
                             d_ff=c["d_ff"], max_len=c["max_len"])
    ctx.mark("weights")
    service = dict(c["service"], **t.get("service", {}))
    service["seq_buckets"] = tuple(service["seq_buckets"])
    svc = GenerationService(params, _model(ctx), GenerationConfig(**service),
                            start=False)
    if ctx.hooks.get("wrap_service"):
        ctx.hooks["wrap_service"](svc)
    ctx.mark("service")
    n_programs = svc.warmup()
    ctx.mark("warmup")
    warm = (compile_cache_stats(), harness.CompileClock.snapshot())
    svc.start()
    return svc, params, n_programs, warm


def offer(ctx, svc, rate=None):
    """The traffic: ramp, the window of ``ctx.seconds`` (with the traced
    slice inside it), no further arrivals, the drain.  Returns what the
    benchmark saw, by its own stamps."""
    t = ctx.traffic
    gen = importlib.import_module("perfbench.generators." + t["generator"])
    open_loop = t["generator"] == "open_loop"
    submit = lambda pr, n, cb: svc.submit(  # noqa: E731
        pr, max_new_tokens=n, on_token=cb)
    load = gen.Load(ctx, submit, rate) if open_loop else gen.Load(ctx, submit)
    load.start()
    time.sleep(t["ramp_seconds"])
    at_open = _snapshot(svc)
    t0 = time.perf_counter()
    sl = harness.TraceSlice(ctx) if ctx.trace else None
    slice_iters = None
    if sl is not None:
        time.sleep(ctx.seconds / 3)
        sl.start()       # starting and stopping the profiler take seconds:
        it0 = svc.stats()["iterations"]     # count between them only
        time.sleep(t["trace_seconds"])
        slice_iters = svc.stats()["iterations"] - it0
        sl.stop()
    time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
    at_close = _snapshot(svc)
    t1 = time.perf_counter()
    load.stop()

    # which requests are the window's: an open loop's are those its
    # schedule put into it (drained after it); a closed loop's are those
    # that ended in it
    records = list(load.records)
    if open_loop:
        mine = [r for r in records if r.idx in load.window]
        deadline = time.perf_counter() + t["drain_seconds"]
        while time.perf_counter() < deadline and not all(
                r.done or r.error for r in mine):
            time.sleep(0.05)
    else:
        mine = [r for r in records if r.error is not None
                or (r.done and t0 <= r.stamps[-1] < t1)]
    t_drained = time.perf_counter()
    stamps = np.sort(np.concatenate(
        [np.asarray(r.stamps) for r in records if r.stamps] or [np.zeros(0)]))
    tokens = int(np.searchsorted(stamps, t1) - np.searchsorted(stamps, t0))
    out = {"t0": t0, "t1": t1, "window_s": t1 - t0, "records": records,
           "mine": mine, "finished": [r for r in mine if r.done],
           "failed": [r for r in mine if not r.done], "tokens": tokens,
           "at_open": at_open, "at_close": at_close, "slice": sl,
           "slice_iters": slice_iters, "serve_tok_s": tokens / (t1 - t0)}
    if open_loop:
        missing = (t_drained - t0) * 1e3   # a request that never answered
        ttft = [(r.stamps[0] - r.due) * 1e3 if r.stamps else missing
                for r in mine]
        gaps = list(np.concatenate([np.diff(r.stamps) for r in mine
                                    if len(r.stamps) > 1]
                                   or [np.zeros(1)]) * 1e3)
        out.update(ttft=ttft, gaps=gaps,
                   ttft_p95_ms=harness.percentile(ttft, 95),
                   itl_p95_ms=harness.percentile(gaps, 95))
        print(f"samples: ttft n={len(ttft)} p50="
              f"{harness.percentile(ttft, 50):.3f} p95="
              f"{out['ttft_p95_ms']:.3f} ms; itl n={len(gaps)} p50="
              f"{harness.percentile(gaps, 50):.3f} p95="
              f"{out['itl_p95_ms']:.3f} ms", flush=True)
    return out


def run(ctx):
    import jax
    from mxnet_tpu.executor import compile_cache_stats
    from mxnet_tpu.ops import pallas_kernels as pk

    c, t = ctx.config, ctx.traffic
    devs = jax.devices()
    svc, params, n_programs, warm = build(ctx)
    w = offer(ctx, svc)
    setup_s = w["t0"] - ctx.t_proc0
    records, mine, finished, failed = \
        w["records"], w["mine"], w["finished"], w["failed"]
    window_s, sl = w["window_s"], w["slice"]
    events = [r.stream.stats() for r in mine if r.stream is not None]
    svc.stop(drain=False, timeout=60)
    after = (compile_cache_stats(), harness.CompileClock.snapshot())
    compiles = (after[0]["misses"] - warm[0]["misses"]) \
        + (after[1]["compiles"] - warm[1]["compiles"])
    kernel = svc.stats()["decode_kernel"]
    peak = harness.memory_peak_bytes(devs)
    e2e = {"setup_s": setup_s, "serve_tok_s": w["serve_tok_s"]}
    for k in ("ttft_p95_ms", "itl_p95_ms"):
        if k in w:
            e2e[k] = w[k]
    clock = harness.CompileClock.snapshot()
    print(f"setup: setup_s={setup_s:.3f} compile_s={clock['compile_s']:.3f}"
          f" cache_hits={clock['hits']} cache_misses={clock['misses']} "
          f"programs={n_programs} kernel={kernel} marks={ctx.marks}",
          flush=True)
    at_open, at_close = w["at_open"], w["at_close"]
    iters = at_close["iterations"] - at_open["iterations"]
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} requests_sent={len(records)} of_window="
          f"{len(mine)} finished={len(finished)} failed={len(failed)} "
          f"waiting_at_close={at_close['waiting']} running_at_close="
          f"{at_close['running']} compiles_after_warmup={compiles}",
          flush=True)

    dcount = {k: at_close["counts"][k] - at_open["counts"][k]
              for k in at_close["counts"]}
    src = harness.Sources(
        counters={"window_ms": window_s * 1e3, "iterations": iters,
                  "tokens": w["tokens"],
                  "slot_iterations": iters * svc._config.max_slots,
                  "kv_peak_occupancy": at_close["peak_occupancy"],
                  "preemptions": dcount["preempted"],
                  "compiles_after_warmup": compiles,
                  "slice.iterations": w["slice_iters"]},
        timers={"generator_late": [r.submitted - r.due for r in mine
                                   if r.submitted is not None]},
        events=events, config=c, traffic=t)
    if ctx.trace:
        src.peaks = ctx.hooks.get("peaks") or counts.peaks(
            devs[0].device_kind)
        src.trace = sl.load(ctx.hooks.get("device_prefix", "/device:TPU:"))
        # K and V the tokens decoded in the slice had to read
        kv = 0
        for r in records:
            for j, s in enumerate(r.stamps[1:], 1):
                if sl.t0 <= s < sl.t1:
                    kv += counts.lm_kv_bytes_per_decoded_token(
                        c["d_model"], c["n_layers"], len(r.prompt) + j)
        src.counters["slice.kv_bytes"] = kv

    # -- correct: what the window served, against the plain reference ------
    control = bool(ctx.hooks.get("control"))
    rng = ctx.rng(6)
    pool = sorted(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    sample = pool[-1:] + [pool[i] for i in rng.permutation(
        max(0, len(pool) - 1))[:N_SAMPLE - 1]]
    t_ref = time.perf_counter()
    checks = [("finished_requests", len(finished), ">=1",
               len(finished) >= 1)]
    if sample:
        probe = probe_logits(ctx, svc, params, control)
        served = served_gaps(ctx, params, sample, control)
        print(f"reference: {time.perf_counter() - t_ref:.1f} s over "
              f"{served['tokens']} served tokens of {len(sample)} requests "
              f"(not the reference's first: {served['not_first']}) and "
              f"{probe['rows']} probe rows", flush=True)
        checks += compare(served, probe)
    native = pk.pallas_enabled() and not pk._use_interpret()
    checks += [("compiles_after_warmup", compiles, 0, compiles == 0),
               ("decode_kernel", kernel, c["decode_kernel"],
                kernel == c["decode_kernel"]
                and (native or not ctx.require_tpu))]
    outcome = {"e2e": e2e, "sources": src, "checks": checks,
               "attempted": len(mine), "failed": len(failed),
               "memory_peak_bytes": peak}
    # free the chip for whoever drives the next seed in this process
    for arr in (svc._cache.k, svc._cache.v, *params.values()):
        arr.delete()
    return outcome

"""Driver of the power-retention serving cells: ``GenerationService`` over
the configuration's retention model (``brumby-14b``: the Qwen3-14B block
with gated power retention in softmax's place — ONE cache kind, and it is a
slot's recurrent state, no pages), through the program's normal path
(``warmup()``, ``start()``, ``submit(on_token=...)``), under the load the
traffic file's generator offers.  Set-up, ramp, window and ``on_token``
stamping are ``drivers/generation.py``'s (``offer``), the one schedule for
every seed ``drivers/latent_decode.py``'s (``_OneSchedule``);
``serve_tok_s`` counts the tokens stamped in the window.  The model is one
token a row a step and rides the engine's step in flight.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/brumby.py`` (float32, ``highest``, the
ATTENTION form: every weight ``exp(G_t - G_j) (q . k)^2`` materialised over
the whole sequence, no state):

(i)  finished requests — the longest among them, the longest over 8,192
     tokens of prompt if another, and a seeded sample — teacher-forced
     through the reference: how far each served token's reference logit
     lies under the reference's best;
(ii) the service's own prefill and decode programs on its own state: seeded
     prompts (leftovers of every length behind the last whole chunk, some of
     several thousand tokens) through the engine's chunk plan — the first of
     them into a slot that a throw-away prompt filled before it (the zero
     start) — then greedy decode steps in one batch whose other rows are
     idle (the identity), the last-position logits against the reference's
     full forward.

The pools are freed between the two (the probe's programs need them, the
reference needs their room).  Hooks a test or a calibration may set in
``ctx.hooks``: ``control`` (the reference one precision down stands in the
program's place), ``fault`` (``"no_gate"`` / ``"no_norm"``: planted on the
reference's side of the comparison, which must then fail), ``ref_pads``
(the lengths the reference compiles for), ``wrap_service`` (called with the
service before its warm-up), ``peaks`` and ``device_prefix`` (a trace that
is not a TPU's), ``readings`` (a dict that is filled with every reading of
the comparison).
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_retention, harness
from perfbench.drivers import generation as gen
from perfbench.drivers.block_diffusion import _Recording
# (i)'s choice of served requests: the longest, the longest over 8,192
# tokens of prompt if that is another, and a seeded few
from perfbench.drivers.hybrid_decode import pick_sample
from perfbench.drivers.latent_decode import _OneSchedule
from perfbench.reference import brumby as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2 has the table, the seeds and the calls): the largest that sound
# runs of the program gave, the smallest that the control gave (the
# reference with norms, gates and their running sums, the powers, the
# weighted sums and every product's result in bfloat16, at the same prompts
# and tokens), and what the two PLANTED faults read in the same runs (the
# gate left out of the reference: g = 0; its normaliser left out).  The
# names are ``latent_decode``'s:
#   logit_row_med_rel  the median over the probe's 72 rows of a row's rms
#                  difference over its logits' std: the arithmetic alone.
#                  Sound 0.00667-0.00680 (the bfloat16 operands of the
#                  products over the parameters: ``gpt2-large`` reads
#                  0.0070), control 0.0161-0.0209, no gate 0.46-0.54, no
#                  normaliser 1.38-1.39.  THE CONTROL AND BOTH FAULTS FAIL
#                  HERE; the limit at the geometric middle has 1.5x room
#                  each way.
#   logit_row_max_rel  the worst row.  A dense model has no row routed
#                  otherwise: sound 0.0071-0.0074, control 0.0228-0.0387,
#                  no gate 0.52-0.60, and a row that is wholly wrong
#                  (another position's logits) reads 1.4.  At the
#                  geometric middle of sound and control, 1.75x room each
#                  way: the control and both faults fail here too.
#   tie_gap_mean   the mean gap of the served tokens under the reference's
#                  best.  Sound 8.7e-5-1.24e-4, control 5.0e-4, no gate
#                  0.51, no normaliser 4.0.  Held 2.4x over the largest
#                  sound reading, 1.7x under the control's.
#   tie_gap_max    the widest such gap, which swings by its nature.  Sound
#                  0.013-0.033 over 11 seeds (mean 0.022), control 0.061
#                  (one reading), no gate 2.4, no normaliser 7.3.  Held
#                  1.7x over the largest sound reading, 1.1x under the
#                  control's: the control need not fail here.
LIMITS = {"tie_gap_max": 0.055, "tie_gap_mean": 3e-4,
          "logit_row_med_rel": 0.0105, "logit_row_max_rel": 0.013}
N_PROBE, PROBE_DECODE = 8, 8   # sequences and decode steps of the logits probe
PROBE_LONGEST = 6600           # the probe's longest prompt
# the reference compiles once per length (whole feed-forward blocks)
REF_PADS = (4096, 8192, 16384, 28672)
REF_ROWS = 2048                # logits rows a reference call returns


def _model(ctx):
    from mxnet_tpu.parallel import retention_lm as rl

    c = ctx.config
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "max_position_embeddings")
    a = c.get("assumed_values", {})
    cfg = rl.RetentionConfig(
        rope_theta=float(c["rope_theta"]), power=int(a.get("power", 2)),
        retention_eps=float(a.get("retention_eps", 1e-6)),
        **{k: c[k] for k in keys})
    return rl.RetentionLM(cfg, max_len=c["max_len"], longest_chunk=min(
        512, c["service"]["seq_buckets"][-1]))


def _ref_logits(ctx, params, tokens, at0, n_rows, dtype="float32",
                fault=None):
    """Reference logits of the ``n_rows`` positions from ``at0`` of one
    sequence, padded to one of a few lengths and taken ``REF_ROWS`` rows a
    call (row ``i`` predicts the token at ``at0 + i + 1``)."""
    n = len(tokens)
    pads = ctx.hooks.get("ref_pads", REF_PADS)
    rows = min(REF_ROWS, pads[0])
    out = []
    for lo in range(0, n_rows, rows):
        need = max(n, at0 + lo + rows)     # the rows' slice must lie inside
        pad = next((p for p in pads if p >= need),
                   -(-need // ref.F_BLOCK) * ref.F_BLOCK)
        toks = np.zeros(pad, np.int32)
        toks[:n] = tokens
        out.append(np.asarray(ref.logits(
            params, ctx.config, toks, n, at0 + lo, rows, dtype=dtype,
            fault=fault))[:n_rows - lo].astype(np.float64))
    return np.concatenate(out)


def _prefill(svc, toks, slot):
    """``toks`` through the engine's chunk plan into the state ``slot``
    names (a state kind's table is one column: the slot); the last chunk's
    sampled token and last-position logits."""
    from mxnet_tpu.serving.bucketing import pad_tokens_right

    z1 = np.zeros(1, np.int32)
    for off, take, tb, _ in svc._chunk_plan(len(toks)):
        nxt, last = svc._programs.run(
            "gen_prefill", svc._cache,
            pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                             tb)[None, :],
            np.arange(off, off + tb, dtype=np.int32)[None, :],
            np.asarray([take], np.int32), np.asarray([[slot]], np.int32),
            z1.astype(np.uint32), np.asarray([len(toks)], np.uint32),
            z1.astype(np.float32), z1, np.ones(1, np.float32))
    return int(nxt[0]), np.asarray(last[0])


def probe_programs(ctx, svc):
    """(ii), the program's side: seeded prompts prefilled through the
    engine's chunk plan and decoded ``PROBE_DECODE`` greedy steps in one
    batch, on the service's own state.  The first sequence's slot held a
    throw-away prompt's state before it (a row that enters a slot starts
    from zero inside the program); the decode batch is the service's, so
    every row but the probe's is idle (an identity on the scratch).
    Returns ``[(tokens, first row's position, logits rows)]``."""
    c, gcfg = ctx.config, svc._config
    rng = ctx.rng(5)
    S = gcfg.max_slots
    n = min(N_PROBE, S)
    hi = min(PROBE_LONGEST, ctx.traffic["prompt"]["max"],
             c["max_len"] - PROBE_DECODE - 1)
    # leftovers of every length behind the last whole chunk
    lens = np.minimum(np.linspace(ctx.traffic["prompt"]["min"], hi, n
                                  ).astype(int) + 37 * np.arange(n), hi)
    seqs = [[int(t) for t in rng.integers(0, c["vocab"], k)] for k in lens]
    slots = svc._alloc_reclaiming(n)
    _prefill(svc, [int(t) for t in rng.integers(0, c["vocab"], lens[1])],
             slots[0])
    got = []
    for toks, slot in zip(seqs, slots):
        nxt, last = _prefill(svc, toks, slot)
        got.append([last])
        toks.append(nxt)
    zs = np.zeros(S, np.int32)
    # the probe's rows spread over the batch, idle rows between them
    at = np.linspace(0, S - 1, n).astype(int)
    for _ in range(PROBE_DECODE):
        tokens = np.zeros((S, 1), np.int32)
        positions = np.zeros((S, 1), np.int32)
        lengths, counters = zs.copy(), zs.astype(np.uint32)
        table = np.zeros((S, 1), np.int32)
        for i, toks in zip(at, seqs):
            ctx_len = len(toks) - 1
            tokens[i, 0], positions[i, 0], lengths[i] = toks[-1], ctx_len, 1
            counters[i] = ctx_len + 1
        table[at, 0] = slots
        nxt, last = svc._programs.run(
            "gen_decode", svc._cache, tokens, positions, lengths, table,
            zs.astype(np.uint32), counters, zs.astype(np.float32), zs,
            np.ones(S, np.float32))
        nxt, last = np.asarray(nxt), np.asarray(last)
        for i, toks, rows in zip(at, seqs, got):
            rows.append(last[i])
            toks.append(int(nxt[i]))
    svc._cache.allocator.free(slots)
    return [(toks[:-1], int(k) - 1, np.stack(r))
            for toks, k, r in zip(seqs, lens, got)]


def probe_logits(ctx, params, fed, control=False, fault=None):
    """(ii), the comparison: the fed rows' logits against the reference's
    at the same positions — the root-mean-square difference over the
    standard deviation of the reference's logits, and the median row's."""
    sq = var = 0.0
    rows = []
    for toks, at0, mine in fed:
        want = _ref_logits(ctx, params, toks, at0, len(mine), fault=fault)
        if control:
            mine = _ref_logits(ctx, params, toks, at0, len(mine), "bfloat16")
        diff2 = (np.asarray(mine, np.float64) - want) ** 2
        sq += float(np.mean(diff2))
        var += float(np.var(want))
        rows += list(np.sqrt(diff2.mean(axis=1)) / want.std(axis=1))
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "logit_row_med_rel": float(np.median(rows)),
            "logit_row_max_rel": float(np.max(rows)), "rows": len(rows)}


def served_gaps(ctx, params, sample, control=False, fault=None):
    """(i): every served token of the sampled requests, teacher-forced:
    how far its reference logit lies below the reference's best — the
    widest gap, the mean gap, the count of tokens that are not the
    reference's first.  ``control``: the token the bfloat16 reference puts
    first stands in for the served one."""
    gaps = []
    for rec in sample:
        toks = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
        lo, n_out = len(rec.prompt) - 1, len(rec.tokens)
        at = _ref_logits(ctx, params, toks, lo, n_out, fault=fault)
        if control:
            chosen = _ref_logits(ctx, params, toks, lo, n_out,
                                 "bfloat16").argmax(axis=-1)
        else:
            chosen = np.asarray(rec.tokens, np.int64)
        gaps.append(at.max(axis=-1) - at[np.arange(n_out), chosen])
    gaps = np.concatenate(gaps)
    return {"tie_gap_max": float(gaps.max()),
            "tie_gap_mean": float(gaps.mean()),
            "tokens": int(gaps.size), "not_first": int((gaps > 0).sum())}


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


def _work_counters(ctx, d, prefix=""):
    """Operations and bytes of a span from the program's own counts
    (``d``: deltas of ``stats()["counts"]``), by ``counts_retention.py``:
    the model's mathematics, whatever the layout."""
    c = ctx.config
    shape = (c["num_attention_heads"], c["num_key_value_heads"],
             c["head_dim"], c["head_dim"], c["num_hidden_layers"])
    return {
        prefix + "state_decode_bytes": counts_retention.decode_state_bytes(
            d["retention_decode_rows"], *shape[1:]),
        prefix + "state_decode_flops": counts_retention.decode_flops(
            d["retention_decode_rows"], *shape),
        prefix + "scan_prefill_flops": counts_retention.scan_flops(
            d["retention_prefill_tokens"], d["retention_prefill_pairs"],
            *shape),
        prefix + "scan_prefill_bytes": counts_retention.scan_state_bytes(
            d["retention_prefill_chunks"], *shape[1:])}


def run(ctx):
    import jax
    from mxnet_tpu.executor import compile_cache_stats
    from mxnet_tpu.ops import pallas_kernels as pk

    c, t = ctx.config, ctx.traffic
    devs = jax.devices()
    svc, params, n_programs, warm = build(ctx)
    rec = _Recording(svc)
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
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} decode_steps={steps} ahead={d['steps_ahead']} "
          f"prefill_tokens={d['prefill_tokens']} decode_rows="
          f"{d['retention_decode_rows']} scan_tokens="
          f"{d['retention_prefill_tokens']} scan_chunks="
          f"{d['retention_prefill_chunks']} rows_started="
          f"{d['retention_rows_started']} requests_sent={len(records)} "
          f"of_window={len(mine)} finished={len(finished)} failed="
          f"{len(failed)} preempted={d['preempted']} waiting_at_close="
          f"{at_close['waiting']} running_at_close={at_close['running']} "
          f"slots_live_at_close={at_close['counts']['state_slots_live']} "
          f"state_bytes_per_slot={slot_bytes} cache_kinds_at_close="
          f"{rec.snaps[-1]['cache_kinds']} compiles_after_warmup={compiles}",
          flush=True)

    # the window's counter deltas whole, and what the reducers divide by
    src = harness.Sources(
        counters=dict(
            d, window_ms=window_s * 1e3, iterations=iters, tokens=w["tokens"],
            slot_iterations=iters * svc._config.max_slots,
            kv_peak_occupancy=at_close["peak_occupancy"],
            preemptions=d["preempted"], compiles_after_warmup=compiles,
            decode_steps=steps, state_bytes_per_slot=slot_bytes,
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
              f"(prompts {[len(r.prompt) for r in sample]}; not the "
              f"reference's first: {served['not_first']}) and "
              f"{probe['rows']} probe rows", flush=True)
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

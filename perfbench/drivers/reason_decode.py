"""Driver of the reasoning-decode serving cells: ``GenerationService`` over
the configuration's SambaY model (``phi-4-mini-flash``: Mamba, window and
ONE full attention layer that a cross-decoder of gated memory units and
cross attention reads, every attention differential — THREE cache kinds in
one manager: a slot's state, a window ring and one shared full pool),
through the program's normal path (``warmup()``, ``start()``,
``submit(on_token=...)``), under the load the traffic file's generator
offers.  Set-up, ramp, window and ``on_token`` stamping are
``drivers/generation.py``'s (``offer``), the one schedule for every seed
``drivers/latent_decode.py``'s (``_OneSchedule``); ``serve_tok_s`` counts the
tokens stamped in the window.  The model is one token a row a step and rides
the engine's step in flight; every chunk of a prompt but its last runs
through the fill program, which has no cross-decoder and no head.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/phi4_flash.py`` (float32, ``highest``, the
whole sequence at once, the scan a plain ``lax.scan`` over positions, no
cache, no state carried, NO SKIP: every layer at every position):

(i)  finished requests of the window — the longest, the one with the most
     generated tokens if another, and a seeded few — teacher-forced through
     the reference: how far each served token's reference logit lies under
     the reference's best;
(ii) the service's own programs on its own pools: seeded prompts
     (leftovers of every length behind the last whole chunk, one past
     4,096) through the engine's chunk plan — fill programs and a last
     chunk, as the engine runs them; the first into a slot that a
     throw-away prompt filled before it (the zero start) — then greedy
     decode steps across a block boundary in one batch whose other rows are
     idle (the identity), the last-position logits against the reference's
     full forward.

The pools are freed between the two (the probe's programs need them, the
reference needs their room).  Hooks a test or a calibration may set in
``ctx.hooks``: ``control`` (the reference one precision down stands in the
program's place), ``fault`` (``"no_lambda"`` / ``"no_shared_kv"`` /
``"no_memory"``: planted on the reference's side of the comparison, which
must then fail), ``ref_pad`` (the one length the reference compiles for),
``wrap_service`` (called with the service before its warm-up), ``peaks``
and ``device_prefix`` (a trace that is not a TPU's), ``readings`` (a dict
that is filled with every reading of the comparison).
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_sambay, harness
from perfbench.drivers import generation as gen
from perfbench.drivers.hybrid_decode import _Row, _Tokens
from perfbench.drivers.latent_decode import _OneSchedule
from perfbench.reference import phi4_flash as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2 has the table, the seeds and the calls): the largest that sound
# runs of the program gave, what the control gave (the reference with norms,
# softmax, the subtraction, softplus, the decay, the state and every
# product's result in bfloat16, at the same prompts and tokens), and what
# the three PLANTED faults read in the same run (lam = 0; the cross layers
# reading zeros for layer 17's K and V; M = 1).  The names are
# ``latent_decode``'s:
#   logit_row_med_rel  the median over the probe's 54 rows of a row's rms
#                  difference over its logits' std: the arithmetic alone.
#                  Sound 0.0050-0.0052 (seven seeds), control 0.0187, no
#                  lambda 0.131, no shared K/V 0.118, no memory 0.359.  THE
#                  CONTROL AND ALL THREE FAULTS FAIL HERE; the limit at the
#                  geometric middle of sound and control has 1.9x room each
#                  way.
#   logit_row_max_rel  the worst row.  A dense model has no row routed
#                  otherwise: sound 0.0055-0.0058, control 0.0239, the faults
#                  0.121-0.388, and a row that is wholly wrong (another
#                  position's logits) reads 1.4.  At the geometric middle
#                  of sound and control, 2x room each way: the control and
#                  the faults fail here too.
#   tie_gap_max, tie_gap_mean  the widest and the mean gap of the served
#                  tokens under the reference's best.  The head is the tied
#                  embedding: the logits' spread is ~5 and the best token
#                  leads by more than a rounding moves, so EVERY served
#                  token was the reference's first on every sound seed, and
#                  under the control and the faults too (0.0 over ~3,000
#                  tokens a run: these two cannot tell a precision apart
#                  here).  They are held against a token altered where it
#                  is produced, which reads the gap to another logit
#                  (several spreads: over 5; one such token in 3,000 reads
#                  0.0017 in the mean), with room for a tie at bfloat16's
#                  resolution (brumby's widest sound gap, 0.033 at a spread
#                  of 1, is 0.17 at this one).
LIMITS = {"tie_gap_max": 0.5, "tie_gap_mean": 0.001,
          "logit_row_med_rel": 0.0098, "logit_row_max_rel": 0.0115}
N_PROBE, PROBE_DECODE = 6, 8   # sequences and decode steps of the logits probe
PROBE_LONGEST = 4300           # the probe's longest prompt: one past 4,096
N_SAMPLE = 3                   # served requests checked
REF_ROWS = 1024                # logits rows a reference call returns


def _model(ctx):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import sambay_lm as sl

    c, a = ctx.config, ctx.config.get("assumed_values", {})
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "sliding_window", "layer_norm_eps", "max_position_embeddings")
    cfg = sl.SambaYConfig(
        d_state=int(a.get("d_state", 16)), d_conv=int(a.get("d_conv", 4)),
        expand=int(a.get("expand", 2)), dt_rank=int(a.get("dt_rank", 0)),
        layer_kinds=tuple(c.get("layer_kinds", ())),
        **{k: c[k] for k in keys})
    return sl.SambaYLM(
        cfg, max_len=c["max_len"],
        kv_dtype=jnp.dtype(c.get("param_dtype", "bfloat16")),
        longest_chunk=min(512, c["service"]["seq_buckets"][-1]))


def _ref_pad(ctx, longest):
    """The ONE length every reference call of a run is padded to (it
    compiles once): whole feed-forward blocks over the longest sequence
    checked and a call's rows."""
    if ctx.hooks.get("ref_pad"):
        return int(ctx.hooks["ref_pad"])
    need = longest + 1
    return max(2 * ref.F_BLOCK, -(-need // (2 * ref.F_BLOCK))
               * 2 * ref.F_BLOCK)


def _ref_logits(ctx, params, pad, tokens, at0, n_rows, dtype="float32",
                fault=None):
    """Reference logits of the ``n_rows`` positions from ``at0`` of one
    sequence, padded to ``pad`` and taken ``REF_ROWS`` rows a call (row
    ``i`` predicts the token at ``at0 + i + 1``)."""
    n = len(tokens)
    rows = min(REF_ROWS, pad)
    toks = np.zeros(pad, np.int32)
    toks[:n] = tokens
    out = []
    for lo in range(0, n_rows, rows):
        at = min(at0 + lo, pad - rows)      # the rows' slice lies inside
        got = np.asarray(ref.logits(params, ctx.config, toks, n, at, rows,
                                    dtype=dtype, fault=fault))
        out.append(got[at0 + lo - at:][:n_rows - lo].astype(np.float64))
    return np.concatenate(out)


def _prefill(svc, toks, blocks, row):
    """``toks`` through the engine's chunk plan as the engine runs it —
    every chunk but the last through the fill program where the model
    fills without a head — into the full kind's ``blocks`` and what ``row``
    owns of the kinds behind it; the last chunk's sampled token and
    last-position logits."""
    from mxnet_tpu.serving.bucketing import pad_tokens_right

    z1 = np.zeros(1, np.int32)
    n = len(toks)
    for off, take, tb, wp in svc._chunk_plan(n):
        table = np.zeros((1, wp), np.int32)
        table[0, :min(wp, len(blocks))] = blocks[:wp]
        svc._slide(row, off, off + take)
        args = (pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                                 tb)[None, :],
                np.arange(off, off + tb, dtype=np.int32)[None, :],
                np.asarray([take], np.int32),
                (table, *svc._ring_tables([(0, row)], 1, tb)))
        if svc._fills and off + take < n:
            svc._programs.run_fill(svc._cache, *args)
        else:
            nxt, last = svc._programs.run(
                "gen_prefill", svc._cache, *args, z1.astype(np.uint32),
                np.asarray([n], np.uint32), z1.astype(np.float32), z1,
                np.ones(1, np.float32))
        svc._slide(row, off + take, off + take)
    return int(nxt[0]), np.asarray(last[0])


def probe_programs(ctx, svc):
    """(ii), the program's side: seeded prompts prefilled through the
    engine's chunk plan and decoded ``PROBE_DECODE`` greedy steps in one
    batch, on the service's own pools of all three kinds.  The first
    sequence's slot held a throw-away prompt's state before it; the decode
    batch is the service's, so every row but the probe's is idle.  Returns
    ``[(tokens, first row's position, logits rows)]``."""
    from mxnet_tpu.serving.generation.kv_cache import blocks_for

    c, gcfg = ctx.config, svc._config
    rng = ctx.rng(5)
    bs, S = gcfg.block_size, gcfg.max_slots
    n = min(N_PROBE, S)
    hi = min(PROBE_LONGEST, ctx.traffic["prompt"]["max"],
             c["max_len"] - PROBE_DECODE - 1)
    # leftovers of every length behind the last whole chunk; the first two
    # so that their decode steps cross a block boundary
    lens = np.minimum(np.linspace(ctx.traffic["prompt"]["min"], hi, n
                                  ).astype(int) + 37 * np.arange(n), hi)
    lens[:2] = lens[:2] // bs * bs + bs - PROBE_DECODE // 2
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


def probe_logits(ctx, params, pad, fed, control=False, fault=None):
    """(ii), the comparison: the fed rows' logits against the reference's
    at the same positions — the root-mean-square difference over the
    standard deviation of the reference's logits, and the median row's."""
    sq = var = 0.0
    rows = []
    for toks, at0, mine in fed:
        want = _ref_logits(ctx, params, pad, toks, at0, len(mine),
                           fault=fault)
        if control:
            mine = _ref_logits(ctx, params, pad, toks, at0, len(mine),
                               "bfloat16")
        diff2 = (np.asarray(mine, np.float64) - want) ** 2
        sq += float(np.mean(diff2))
        var += float(np.var(want))
        rows += list(np.sqrt(diff2.mean(axis=1)) / want.std(axis=1))
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "logit_row_med_rel": float(np.median(rows)),
            "logit_row_max_rel": float(np.max(rows)), "rows": len(rows)}


def served_gaps(ctx, params, pad, sample, control=False, fault=None):
    """(i): every served token of the sampled requests, teacher-forced:
    how far its reference logit lies below the reference's best — the
    widest gap, the mean gap, the count of tokens that are not the
    reference's first.  ``control``: the token the bfloat16 reference puts
    first stands in for the served one."""
    gaps = []
    for rec in sample:
        toks = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
        lo, n_out = len(rec.prompt) - 1, len(rec.tokens)
        at = _ref_logits(ctx, params, pad, toks, lo, n_out, fault=fault)
        if control:
            chosen = _ref_logits(ctx, params, pad, toks, lo, n_out,
                                 "bfloat16").argmax(axis=-1)
        else:
            chosen = np.asarray(rec.tokens, np.int64)
        gaps.append(at.max(axis=-1) - at[np.arange(n_out), chosen])
    gaps = np.concatenate(gaps)
    return {"tie_gap_max": float(gaps.max()),
            "tie_gap_mean": float(gaps.mean()),
            "tokens": int(gaps.size), "not_first": int((gaps > 0).sum())}


def pick_sample(ctx, finished):
    """The served requests (i) checks: the longest, the one that generated
    the most (the most steps through the window's ring and the state) if
    that is another, and a seeded few."""
    pool = sorted(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    if not pool:
        return []
    sample = [pool.pop()]
    if pool:
        most = max(pool, key=lambda r: len(r.tokens))
        if len(most.tokens) > len(sample[0].tokens):
            sample.append(most)
            pool.remove(most)
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
    c, a = ctx.config, ctx.config.get("assumed_values", {})
    kinds = ref.layer_kinds(c)
    return dict(
        H=c["num_attention_heads"], hkv=c["num_key_value_heads"],
        dh=c["hidden_size"] // c["num_attention_heads"],
        di=int(a.get("expand", 2)) * c["hidden_size"],
        N=int(a.get("d_state", 16)), K=int(a.get("d_conv", 4)),
        ssm=kinds.count("ssm"), swa=kinds.count("swa"),
        readers=kinds.count("full") + kinds.count("cross"))


def _work_counters(ctx, d, prefix=""):
    """Operations and bytes of a span from the program's own counts
    (``d``: deltas of ``stats()["counts"]``), by ``counts_sambay.py``: the
    model's mathematics, whatever the layout."""
    from mxnet_tpu.ops.paged_attention import _TILE_ROWS

    m = _shapes(ctx)
    H, hkv, dh = m["H"], m["hkv"], m["dh"]
    state = (m["di"], m["N"], m["K"], m["ssm"])
    # a tile is _TILE_ROWS rows: the 4 queries of a KV pair ride them
    tile = _TILE_ROWS / (H // (hkv // 2))
    pre = ((d["window_prefill_pairs"], m["swa"]),
           (d["full_prefill_pairs"], m["readers"]))
    return {
        prefix + "cross_decode_bytes": counts_sambay.kv_read_bytes(
            d["full_ctx_tokens"], hkv, dh, m["readers"]),
        prefix + "cross_decode_flops": counts_sambay.diff_attn_flops(
            d["full_ctx_tokens"], H, dh, m["readers"]),
        prefix + "window_decode_bytes": counts_sambay.kv_read_bytes(
            d["window_ctx_tokens"], hkv, dh, m["swa"]),
        prefix + "window_decode_flops": counts_sambay.diff_attn_flops(
            d["window_ctx_tokens"], H, dh, m["swa"]),
        prefix + "ssm_decode_bytes": counts_sambay.ssm_decode_bytes(
            d["ssm_decode_rows"], *state),
        prefix + "ssm_decode_flops": counts_sambay.ssm_flops(
            d["ssm_decode_rows"], m["di"], m["N"], m["ssm"]),
        prefix + "scan_prefill_bytes": counts_sambay.scan_prefill_bytes(
            d["ssm_prefill_chunks"], d["ssm_prefill_tokens"], *state),
        prefix + "scan_prefill_flops": counts_sambay.ssm_flops(
            d["ssm_prefill_tokens"], m["di"], m["N"], m["ssm"]),
        prefix + "prefill_attn_flops": sum(
            counts_sambay.diff_attn_flops(p, H, dh, n) for p, n in pre),
        prefix + "prefill_attn_bytes": sum(
            counts_sambay.prefill_read_bytes(p, tile, hkv, dh, n)
            for p, n in pre)}


def _cache_counters(svc, snap):
    """What the manager holds at a ``stats()`` read: the window kind's
    blocks a running row, and the bytes of all kinds' units a live
    token."""
    kinds = svc._cache.kinds
    used = [snap["cache_kinds"][k.name]["used"] for k in kinds]
    per_unit = [sum(int(p.nbytes) for p in svc._cache.pools[k.span])
                // k.num_blocks for k in kinds]
    out = {"cache_bytes_per_token": counts_sambay.cache_bytes_per_token(
        used, per_unit, snap["live_tokens"])}
    if snap["running"]:
        window = [k.name for k in kinds if k.window][0]
        out["window_blocks_per_row"] = \
            snap["cache_kinds"][window]["used"] / snap["running"]
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
    # (two of the counts are gauges: what the state kind holds)
    slot_bytes = at_close["counts"]["state_bytes_per_slot"]
    steps = d["steps_ahead"] + d["steps_drained"]
    cache = _cache_counters(svc, rec.snaps[-1])
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} decode_steps={steps} ahead={d['steps_ahead']} "
          f"prefill_tokens={d['prefill_tokens']} ssm_decode_rows="
          f"{d['ssm_decode_rows']} ssm_prefill_tokens="
          f"{d['ssm_prefill_tokens']} chunks={d['ssm_prefill_chunks']} "
          f"rows_started={d['ssm_rows_started']} full_ctx_tokens="
          f"{d['full_ctx_tokens']} window_ctx_tokens="
          f"{d['window_ctx_tokens']} cross_run={d['cross_positions_run']} "
          f"cross_skipped={d['cross_positions_skipped']} "
          f"window_blocks_freed={d['window_blocks_freed']} requests_sent="
          f"{len(records)} of_window={len(mine)} finished={len(finished)} "
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
            decode_steps=steps, state_bytes_per_slot=slot_bytes,
            cross_prompt_positions=d["cross_positions_run"]
            + d["cross_positions_skipped"], **cache,
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
        pad = _ref_pad(ctx, max(
            [len(r.prompt) + len(r.tokens) for r in sample]
            + [len(toks) for toks, _, _ in fed]))
        readings = ctx.hooks.get("readings")
        if readings is not None:
            readings.update(fed=fed, sample=sample, params=params, pad=pad)
        probe = probe_logits(ctx, params, pad, fed, control, fault)
        served = served_gaps(ctx, params, pad, sample, control, fault)
        print(f"reference: {time.perf_counter() - t_ref:.1f} s at pad {pad} "
              f"over {served['tokens']} served tokens of {len(sample)} "
              f"requests (prompts {[len(r.prompt) for r in sample]}, outputs "
              f"{[len(r.tokens) for r in sample]}; not the reference's "
              f"first: {served['not_first']}) and {probe['rows']} probe rows",
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

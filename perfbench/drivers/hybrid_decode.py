"""Driver of the hybrid-attention serving cells: ``GenerationService`` over
the configuration's model of window and full attention layers (``mimo-v2.5``:
two cache kinds — the window layers' rows free the blocks behind their 128
positions —, a sink, keys wider than values, sigmoid-routed experts of which
the chip holds a share), through the program's normal path (``warmup()``,
``start()``, ``submit(on_token=...)``), under the load the traffic file's
generator offers.  Set-up, ramp, window and ``on_token`` stamping are
``drivers/generation.py``'s (``offer``), the one schedule for every seed
``drivers/latent_decode.py``'s (``_OneSchedule``); ``serve_tok_s`` counts the
tokens stamped in the window.  The model is one token a row a step and rides
the engine's step in flight.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/mimo_v2.py`` (float32, ``highest``,
attention materialised over the whole sequence with the window a mask and
the sink a column, given the same share of the experts and the same slice
of the vocabulary):

(i)  finished requests — the longest among them, the longest over 8,192
     tokens if another, and a seeded sample — teacher-forced through the
     reference: how far each served token's reference logit lies under the
     reference's best;
(ii) the service's own prefill and decode programs on its own caches:
     seeded prompts (leftovers of every length behind the last whole chunk,
     some longer than a window and a chunk and some of several thousand
     tokens, so that window blocks have been freed and reused) through the
     engine's chunk plan, then greedy decode steps in one full batch across
     a block boundary, the last-position logits against the reference's
     full forward.

The pools are freed between the two (the probe's programs need them, the
reference needs their room).  Hooks a test or a calibration may set in
``ctx.hooks``: ``control`` (the reference one precision down stands in the
program's place), ``fault`` (``"no_sink"`` / ``"short_window"``: planted on
the reference's side of the comparison, which must then fail),
``ref_pads`` (the lengths the reference compiles for), ``wrap_service``
(called with the service before its warm-up), ``peaks`` and
``device_prefix`` (a trace that is not a TPU's), ``readings`` (a dict that
is filled with every reading of the comparison).
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_hybrid, harness
from perfbench.drivers import generation as gen
from perfbench.drivers.block_diffusion import _Recording
from perfbench.drivers.latent_decode import _OneSchedule
from perfbench.reference import mimo_v2 as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2 has the table, the seeds and the calls): the largest that sound
# runs of the program gave, the smallest that the control gave (the
# reference with router scores, norms, softmax and every product's result
# in bfloat16, at the same prompts and tokens), and what the two PLANTED
# faults read in the same runs (the sink left out of the reference; its
# window 112 positions).  The names are ``latent_decode``'s:
#   logit_row_med_rel  the median over the probe's 72 rows of a row's rms
#                  difference over its logits' std: the arithmetic alone (a
#                  row routed otherwise on the two sides is left out by the
#                  median).  Sound 0.0048-0.0056, control 0.0116-0.0123, no
#                  sink 0.0129-0.0132, a window of 112 0.148-0.155.  THE
#                  CONTROL AND BOTH FAULTS FAIL HERE; the limit at the
#                  geometric middle has 1.4x room each way.
#   logit_row_max_rel  the worst row.  Sound 0.091-0.130 (control 0.093-
#                  0.157: a token whose eighth and ninth expert lie closer
#                  than the rounding is routed otherwise on either side);
#                  a row that is wholly wrong (another position's logits)
#                  reads 1.4, whatever the seed.  Held between the two,
#                  4.6x over the largest sound reading.
#   tie_gap_mean   the mean gap of the served tokens under the reference's
#                  best.  Sound <= 0.00088, control 0.0022-0.0034, a
#                  window of 112 0.054-0.057.  Held at 4.5x the largest
#                  sound reading (an MoE's gaps have the tail routing
#                  gives them: PR 26 lost a sound run at 1.3x).
#   tie_gap_max    the widest such gap.  Sound <= 0.34 (control <= 0.37, a
#                  window of 112 0.71-0.77); a token altered where it is
#                  produced reads ~4.9 (the best of 19,072 logits over a
#                  random one).  Held 4.4x over the largest sound reading.
LIMITS = {"tie_gap_max": 1.5, "tie_gap_mean": 0.004,
          "logit_row_med_rel": 0.008, "logit_row_max_rel": 0.6}
N_PROBE, PROBE_DECODE = 8, 8   # sequences and decode steps of the logits probe
PROBE_LONGEST = 6600           # the probe's longest prompt
N_SAMPLE = 4                   # served requests checked
LONG = 8192                    # one of them is longer than this, if any is
REF_PADS = (4096, 8192, 16384)  # the reference compiles once per length
REF_ROWS = 2048                # logits rows a reference call returns


def _model(ctx):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import hybrid_moe as hm

    c = ctx.config
    n = c["num_hidden_layers"]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads",
            "swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
            "sliding_window", "add_swa_attention_sink_bias",
            "add_full_attention_sink_bias", "partial_rotary_factor",
            "attention_value_scale", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "layernorm_epsilon",
            "max_position_embeddings")
    scaling = c.get("routed_scaling_factor")
    cfg = hm.HybridMoeConfig(
        n_routed_experts=c["published"]["n_routed_experts"],
        hybrid_layer_pattern=tuple(c["hybrid_layer_pattern"][:n]),
        moe_layer_freq=tuple(c["moe_layer_freq"][:n]),
        rope_theta=float(c["rope_theta"]),
        swa_rope_theta=float(c["swa_rope_theta"]),
        routed_scaling_factor=1.0 if scaling is None else float(scaling),
        **{k: c[k] for k in keys})
    return hm.HybridMoeLM(cfg, max_len=c["max_len"],
                          experts_held=tuple(c["experts_held"]),
                          kv_dtype=jnp.dtype(c.get("param_dtype", "bfloat16")))


class _Tokens(_Recording):
    """``_Recording`` that also keeps, beside every ``stats()``, the live
    tokens of the running rows (what the blocks they own hold)."""

    def stats(self):
        s = super().stats()
        s["live_tokens"] = sum(r.ctx_len for r in list(self._svc._slots)
                               if r is not None)
        return s


def _ref_logits(ctx, params, tokens, at0, n_rows, dtype="float32",
                fault=None):
    """Reference logits of the ``n_rows`` positions from ``at0`` of one
    sequence, padded to one of a few lengths (row ``i`` predicts the token
    at ``at0 + i + 1``)."""
    n = len(tokens)
    pads = ctx.hooks.get("ref_pads", REF_PADS)
    rows = min(REF_ROWS, pads[0])
    need = max(n, at0 + rows)          # the rows' slice must lie inside
    pad = next((p for p in pads if p >= need),
               -(-need // ref.Q_BLOCK) * ref.Q_BLOCK)
    toks = np.zeros(pad, np.int32)
    toks[:n] = tokens
    return np.asarray(ref.logits(params, ctx.config, toks, n, at0, rows,
                                 dtype=dtype, fault=fault)
                      )[:n_rows].astype(np.float64)


class _Row:
    """What the engine's window code reads of a request."""
    rid, wins = -1, None


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
    n = min(N_PROBE, S)
    hi = min(PROBE_LONGEST, ctx.traffic["prompt"]["max"],
             c["max_len"] - PROBE_DECODE - 1)
    # leftovers of every length behind the last whole chunk; the first two
    # so that their decode steps cross a block boundary
    lens = np.minimum(np.linspace(ctx.traffic["prompt"]["min"], hi, n
                                  ).astype(int) + 37 * np.arange(n), hi)
    lens[:2] = lens[:2] // bs * bs + bs - PROBE_DECODE // 2
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
        want = _ref_logits(ctx, params, toks, at0, len(mine), fault=fault)
        if control:
            mine = _ref_logits(ctx, params, toks, at0, len(mine), "bfloat16")
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


def pick_sample(ctx, finished):
    """The served requests (i) checks: the longest, the longest over
    ``LONG`` tokens of prompt if that is another, and a seeded few."""
    pool = sorted(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    if not pool:
        return []
    sample = [pool.pop()]
    long = [r for r in pool if len(r.prompt) > LONG]
    if len(sample[0].prompt) <= LONG and long:
        sample.append(long[-1])
        pool.remove(long[-1])
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
    n = c["num_hidden_layers"]
    pattern = c["hybrid_layer_pattern"][:n]
    return dict(H=c["num_attention_heads"], dk=c["head_dim"],
                dv=c["v_head_dim"],
                hkv=(c["num_key_value_heads"], c["swa_num_key_value_heads"]),
                layers=(pattern.count(0), pattern.count(1)),
                expert_layers=sum(c["moe_layer_freq"][:n]))


def _work_counters(ctx, d, prefix=""):
    """Operations and bytes of a span from the program's own counts
    (``d``: deltas of ``stats()["counts"]``), by ``counts_hybrid.py``."""
    from mxnet_tpu.ops.paged_attention import _TILE_ROWS

    c, m = ctx.config, _shapes(ctx)
    H, dk, dv = m["H"], m["dk"], m["dv"]
    dm, df = c["hidden_size"], c["moe_intermediate_size"]
    out = {}
    pre_bytes = pre_flops = 0
    for kind, name in enumerate(("full", "window")):
        hkv, nl = m["hkv"][kind], m["layers"][kind]
        out[f"{prefix}{name}_decode_bytes"] = counts_hybrid.kind_read_bytes(
            d[f"{name}_ctx_tokens"], hkv, dk, dv, nl)
        out[f"{prefix}{name}_decode_flops"] = counts_hybrid.kind_flops(
            d[f"{name}_ctx_tokens"], H, dk, dv, nl)
        pre_flops += counts_hybrid.kind_flops(
            d[f"{name}_prefill_pairs"], H, dk, dv, nl)
        # a tile is _TILE_ROWS queries of ONE query head of each KV head
        pre_bytes += counts_hybrid.prefill_read_bytes(
            d[f"{name}_prefill_pairs"], _TILE_ROWS / (H // hkv), hkv, dk, dv,
            nl)
    out.update({prefix + "prefill_attn_flops": pre_flops,
                prefix + "prefill_attn_bytes": pre_bytes,
                prefix + "moe_bytes": counts_hybrid.held_expert_bytes(
                    d["experts_touched"], dm, df),
                prefix + "moe_flops": counts_hybrid.held_expert_flops(
                    d["expert_assignments_held"], dm, df)})
    return out


def _cache_counters(svc, snap):
    """What the manager holds at a ``stats()`` read: the window kind's
    blocks a running row, and the bytes of both kinds' blocks a live
    token."""
    kinds = svc._cache.kinds
    used = [snap["cache_kinds"][k.name]["used"] for k in kinds]
    per_block = [sum(int(p.nbytes) for p in svc._cache.pools[k.span])
                 // k.num_blocks for k in kinds]
    out = {"cache_bytes_per_token": counts_hybrid.cache_bytes_per_token(
        used, per_block, snap["live_tokens"])}
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
    cache = _cache_counters(svc, rec.snaps[-1])
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} decode_steps={steps} ahead={d['steps_ahead']} "
          f"prefill_tokens={d['prefill_tokens']} full_ctx_tokens="
          f"{d['full_ctx_tokens']} window_ctx_tokens="
          f"{d['window_ctx_tokens']} window_blocks_freed="
          f"{d['window_blocks_freed']} assignments={d['expert_assignments']} "
          f"held={d['expert_assignments_held']} touched="
          f"{d['experts_touched']} requests_sent={len(records)} of_window="
          f"{len(mine)} finished={len(finished)} failed={len(failed)} "
          f"preempted={d['preempted']} waiting_at_close="
          f"{at_close['waiting']} running_at_close={at_close['running']} "
          f"live_tokens_at_close={rec.snaps[-1]['live_tokens']} "
          f"cache_kinds_at_close={rec.snaps[-1]['cache_kinds']} "
          f"cache={cache} compiles_after_warmup={compiles}", flush=True)

    held = c["experts_held"][1] - c["experts_held"][0]
    slots = held * _shapes(ctx)["expert_layers"]
    # the window's counter deltas whole, and what the reducers divide by
    src = harness.Sources(
        counters=dict(
            d, window_ms=window_s * 1e3, iterations=iters, tokens=w["tokens"],
            slot_iterations=iters * svc._config.max_slots,
            kv_peak_occupancy=at_close["peak_occupancy"],
            preemptions=d["preempted"], compiles_after_warmup=compiles,
            decode_steps=steps,
            expert_mean_load=d["expert_assignments_held"] / slots, **cache,
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

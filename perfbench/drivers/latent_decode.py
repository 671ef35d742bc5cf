"""Driver of the latent-attention serving cells: ``GenerationService`` over
the configuration's latent-attention model (``dots-vlm1``: MLA over a
latent paged cache, a gated dense layer, sigmoid-routed experts with a
shared one, of which the chip holds a share), through the program's normal
path (``warmup()``, ``start()``, ``submit(on_token=...)``), under the load
the traffic file's generator offers.  Set-up, ramp, window and ``on_token``
stamping are ``drivers/generation.py``'s (``offer``); ``serve_tok_s``
counts the tokens stamped in the window.  The model is one token a row a
step and rides the engine's step in flight.

Every seed is given the same SCHEDULE of work (``_OneSchedule``): the
generator hands every seed the same lengths in another order, which
evens the work out only where a window consumes rounds of them, and this
cell's window ends inside the first round (a request lives two minutes):
which 256 of the 512 prompts fill the slots first was the seed's, and
moved ``serve_tok_s`` by 3% of its mean.  The order is the cell's here;
token ids and weights stay the seed's.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/dots_vlm1.py`` (float32, ``highest``,
materialised attention over the whole sequence, given the same share of
the experts and the same slice of the vocabulary):

(i)  a seeded sample of the finished requests, the longest among them,
     teacher-forced through the reference: how far each served token's
     reference logit lies under the reference's best;
(ii) the service's own prefill and decode programs on its own cache:
     seeded prompts through the engine's chunk plan (every leftover
     length), then greedy decode steps in one batch, the last-position
     logits against the reference's full forward — where the absorbed
     attention over the latent cache meets the materialised reference.

The pool is freed between the two (the probe's programs need it, the
reference needs its room).  Hooks a test or ``calibrate.py`` may set in
``ctx.hooks``: ``control`` (the reference one precision down stands in
the program's place), ``ref_pads`` (the lengths the reference compiles
for), ``wrap_service`` (called with the service before its warm-up),
``peaks`` and ``device_prefix`` (a trace that is not a TPU's).
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_mla, harness
from perfbench.drivers import generation as gen
from perfbench.drivers.block_diffusion import _Recording
from perfbench.reference import dots_vlm1 as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2 has the table, the seeds and the calls): the largest that
# sound runs of the program gave, the smallest that the control gave (the
# reference with router scores, norms, softmax and every product's result
# in bfloat16, at the same prompts and tokens), and what a PLANTED fault
# read in the same runs (the probe's or the served data altered on the
# host, after the window).
#   logit_row_med_rel  the median over the probe's 72 rows of a row's rms
#                  difference over its logits' std: the arithmetic alone (a
#                  row routed otherwise on the two sides is left out by
#                  the median); repeats within 13% from seed to seed on
#                  both sides.  THE CONTROL FAILS HERE; the limit at the
#                  geometric middle has 1.4x room each way.
#   logit_row_max_rel  the worst row.  A token whose eighth and ninth
#                  expert (or fourth and fifth group) lie closer than the
#                  rounding is routed otherwise on the two sides and its
#                  row reads 0.1-0.4, on the program's side and the
#                  control's alike; a row that is wholly wrong (another
#                  position's logits) reads 1.4, whatever the seed.  Held
#                  between the two.  (The rows' root-mean-square,
#                  ``logit_rms_rel``, is printed and decides nothing: it
#                  swings threefold with the seed, cannot tell the control
#                  apart, and sat 1.4x under one wrong row.)
#   tie_gap_mean   a mean over a tail of tokens routed otherwise; the
#                  control reads 1.5-2.4x the program.  Held at 3x the
#                  largest sound reading, against one served token in 200
#                  altered where it is produced.
#   tie_gap_max    a widest gap swings by its nature (a token routed
#                  otherwise is served a few tenths of the logits' std,
#                  1.0, under the reference's best) and reads alike on
#                  both sides.  Held at 2x, against one altered token
#                  (the best of 16,160 logits over a random one).
LIMITS = {"tie_gap_max": 2.5, "tie_gap_mean": 0.021, "logit_row_med_rel": 0.022,
          "logit_row_max_rel": 1.0}
N_PROBE, PROBE_DECODE = 8, 8   # sequences and decode steps of the logits probe
N_SAMPLE = 6                   # served requests checked, the longest among them
REF_PADS = (3072, 6400)        # the reference compiles once per length
REF_ROWS = 2048                # logits rows a reference call returns


class _SeedsDraws:
    """A generator for the load: orders from ``order`` (the cell's, the same
    for every seed), everything drawn from ``draws`` (the seed's)."""

    def __init__(self, order, draws):
        self.permutation = order.permutation
        self._draws = draws

    def __getattr__(self, name):
        return getattr(self._draws, name)


class _OneSchedule:
    """The context as the load generator sees it: ``rng(stream)`` permutes
    by the stream alone and draws by the seed, so that every seed offers
    the same lengths in the same order with token ids of its own."""

    def __init__(self, ctx):
        self._ctx = ctx

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def rng(self, stream=0):
        return _SeedsDraws(np.random.default_rng([0, int(stream)]),
                           self._ctx.rng(stream))


def _model(ctx):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import latent_moe as lm

    c, rs = ctx.config, ctx.config["rope_scaling"]
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_shared_experts", "num_experts_per_tok",
            "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor", "rms_norm_eps",
            "max_position_embeddings")
    cfg = lm.LatentMoeConfig(
        n_routed_experts=c["published"]["n_routed_experts"],
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max_position_embeddings=rs[
            "original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        **{k: c[k] for k in keys})
    return lm.LatentMoeLM(cfg, max_len=c["max_len"],
                          experts_held=tuple(c["experts_held"]),
                          kv_dtype=jnp.dtype(c.get("param_dtype", "bfloat16")))


def _ref_logits(ctx, params, tokens, at0, n_rows, dtype="float32"):
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
                                 dtype=dtype))[:n_rows].astype(np.float64)


def probe_programs(ctx, svc):
    """(ii), the program's side: seeded prompts prefilled through the
    engine's chunk plan and decoded ``PROBE_DECODE`` greedy steps in one
    batch, on the service's own cache.  Returns ``[(tokens, first row's
    position, logits rows)]``."""
    from mxnet_tpu.serving.bucketing import bucket_batch, pad_tokens_right
    from mxnet_tpu.serving.generation.kv_cache import blocks_for

    c, gcfg = ctx.config, svc._config
    rng = ctx.rng(5)
    bs, S = gcfg.block_size, gcfg.max_slots
    n = min(N_PROBE, S)
    hi = min(ctx.traffic["prompt"]["max"], c["max_len"] - PROBE_DECODE - 1)
    # leftovers of every length behind the last whole chunk
    lens = np.minimum(np.linspace(ctx.traffic["prompt"]["min"], hi, n
                                  ).astype(int) + 37 * np.arange(n), hi)
    seqs = [[int(t) for t in rng.integers(0, c["vocab"], k)] for k in lens]
    tables, got = [], []
    z1 = np.zeros(1, np.int32)
    for toks in seqs:
        blocks = svc._alloc_reclaiming(blocks_for(len(toks) + PROBE_DECODE
                                                  + 1, bs))
        tables.append(blocks)
        for off, take, tb, wp in svc._chunk_plan(len(toks)):
            table = np.zeros((1, wp), np.int32)
            table[0, :min(wp, len(blocks))] = blocks[:wp]
            nxt, last = svc._programs.run(
                "gen_prefill", svc._cache,
                pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                                 tb)[None, :],
                np.arange(off, off + tb, dtype=np.int32)[None, :],
                np.asarray([take], np.int32), table, z1.astype(np.uint32),
                np.asarray([len(toks)], np.uint32), z1.astype(np.float32),
                z1, np.ones(1, np.float32))
        got.append([np.asarray(last[0])])
        toks.append(int(nxt[0]))
    zs = np.zeros(S, np.int32)
    for _ in range(PROBE_DECODE):
        tokens = np.zeros((S, 1), np.int32)
        positions = np.zeros((S, 1), np.int32)
        lengths, counters = zs.copy(), zs.astype(np.uint32)
        w = bucket_batch(max(blocks_for(len(t), bs) for t in seqs),
                         svc._width_buckets)
        table = np.zeros((S, w), np.int32)
        for i, toks in enumerate(seqs):
            ctx_len = len(toks) - 1
            tokens[i, 0], positions[i, 0], lengths[i] = toks[-1], ctx_len, 1
            counters[i] = ctx_len + 1
            table[i, :min(w, len(tables[i]))] = tables[i][:w]
        nxt, last = svc._programs.run(
            "gen_decode", svc._cache, tokens, positions, lengths, table,
            zs.astype(np.uint32), counters, zs.astype(np.float32), zs,
            np.ones(S, np.float32))
        last = np.asarray(last[:n])
        for i, toks in enumerate(seqs):
            got[i].append(last[i])
            toks.append(int(nxt[i]))
    return [(toks[:-1], int(k) - 1, np.stack(rows))
            for toks, k, rows in zip(seqs, lens, got)]


def probe_logits(ctx, params, fed, control=False):
    """(ii), the comparison: the fed rows' logits against the reference's
    at the same positions — the root-mean-square difference over the
    standard deviation of the reference's logits, and the median row's."""
    sq = var = 0.0
    rows = []
    for toks, at0, mine in fed:
        want = _ref_logits(ctx, params, toks, at0, len(mine))
        if control:
            mine = _ref_logits(ctx, params, toks, at0, len(mine), "bfloat16")
        diff2 = (np.asarray(mine, np.float64) - want) ** 2
        sq += float(np.mean(diff2))
        var += float(np.var(want))
        rows += list(np.sqrt(diff2.mean(axis=1)) / want.std(axis=1))
    # a token whose eighth and ninth experts (or fourth and fifth groups)
    # lie closer than the rounding is routed otherwise on the two sides,
    # and its row reads many times the others': the root-mean-square takes
    # those in, the median row leaves them out and reads the arithmetic
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "logit_row_med_rel": float(np.median(rows)),
            "logit_row_max_rel": float(np.max(rows)), "rows": len(rows)}


def served_gaps(ctx, params, sample, control=False):
    """(i): every served token of the sampled requests, teacher-forced:
    how far its reference logit lies below the reference's best — the
    widest gap, the mean gap, the count of tokens that are not the
    reference's first.  ``control``: the token the bfloat16 reference puts
    first stands in for the served one."""
    gaps = []
    for rec in sample:
        toks = np.concatenate([rec.prompt, np.asarray(rec.tokens, np.int32)])
        lo, n_out = len(rec.prompt) - 1, len(rec.tokens)
        at = _ref_logits(ctx, params, toks, lo, n_out)
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


def _work_counters(ctx, svc, d, prefix=""):
    """Operations and bytes of a span from the program's own counts
    (``d``: deltas of ``stats()["counts"]``), by ``counts_mla.py``."""
    from mxnet_tpu.ops.latent_attention import _tile_tokens

    c = ctx.config
    nl, H = c["num_hidden_layers"], c["num_attention_heads"]
    lat = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    width = svc._cache.shape[-1]       # the cached vector as the pool stores it
    tile = _tile_tokens(svc._seq_buckets[-1], H)
    dm, df = c["hidden_size"], c["moe_intermediate_size"]
    return {
        prefix + "mla_decode_bytes": counts_mla.latent_read_bytes(
            d["latent_ctx_tokens"], width, nl),
        prefix + "mla_decode_flops": counts_mla.latent_flops(
            d["latent_ctx_tokens"], H, lat, c["kv_lora_rank"], nl),
        prefix + "mla_prefill_bytes": counts_mla.latent_prefill_read_bytes(
            d["latent_prefill_pairs"], tile, width, nl),
        prefix + "mla_prefill_flops": counts_mla.latent_flops(
            d["latent_prefill_pairs"], H, lat, c["kv_lora_rank"], nl),
        prefix + "moe_bytes": counts_mla.held_expert_bytes(
            d["experts_touched"], dm, df),
        prefix + "moe_flops": counts_mla.held_expert_flops(
            d["expert_assignments_held"], dm, df)}


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
    steps = d["steps_ahead"] + d["steps_drained"]
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} decode_steps={steps} ahead={d['steps_ahead']} "
          f"prefill_tokens={d['prefill_tokens']} ctx_tokens="
          f"{d['latent_ctx_tokens']} assignments={d['expert_assignments']} "
          f"held={d['expert_assignments_held']} touched="
          f"{d['experts_touched']} requests_sent={len(records)} of_window="
          f"{len(mine)} finished={len(finished)} failed={len(failed)} "
          f"preempted={d['preempted']} waiting_at_close="
          f"{at_close['waiting']} running_at_close={at_close['running']} "
          f"compiles_after_warmup={compiles}", flush=True)

    held = c["experts_held"][1] - c["experts_held"][0]
    slots = held * (c["num_hidden_layers"] - c["first_k_dense_replace"])
    # the window's counter deltas whole, and what the reducers divide by
    src = harness.Sources(
        counters=dict(
            d, window_ms=window_s * 1e3, iterations=iters, tokens=w["tokens"],
            slot_iterations=iters * svc._config.max_slots,
            kv_peak_occupancy=at_close["peak_occupancy"],
            preemptions=d["preempted"], compiles_after_warmup=compiles,
            decode_steps=steps,
            expert_mean_load=d["expert_assignments_held"] / slots,
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
        src.counters.update(_work_counters(ctx, svc, ds, "slice."))

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
        fed = probe_programs(ctx, svc)
        # the probe's programs needed the pool; the reference needs its room
        for arr in svc._cache.pools:
            arr.delete()
        probe = probe_logits(ctx, params, fed, control)
        served = served_gaps(ctx, params, sample, control)
        print(f"reference: {time.perf_counter() - t_ref:.1f} s over "
              f"{served['tokens']} served tokens of {len(sample)} requests "
              f"(not the reference's first: {served['not_first']}) and "
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
    for arr in (*svc._cache.pools, *params.values()):
        if not arr.is_deleted():
            arr.delete()
    return outcome

"""Driver of the block-diffusion serving cells: ``GenerationService`` over
the configuration's SDAR-MoE model (grouped-KV rotary block, sparse
experts, generation by diffusion over blocks), through the program's
normal path (``warmup()``, ``start()``, ``submit(on_token=...)``), under
the load the traffic file's generator offers.  Set-up, ramp, window and
``on_token`` stamping are ``drivers/generation.py``'s (``offer``);
``serve_tok_s`` counts the tokens stamped in the window.

After the window, ``correct``, on what the timed service produced at the
timed sizes, against ``reference/sdar_moe.py`` (float32, ``highest``, the
whole sequence at once):

(i)  the service's own prefill and ``gen_block`` programs on its own
     cache: seeded prompts through the engine's chunk plan, then block
     states with 0 to 4 MASKs and their commit passes, the logits at all
     ``L`` positions of every fed block against the reference's;
(ii) for a seeded sample of committed blocks of finished requests, the
     block states rebuilt from the pass at which each token was unmasked
     (the engine records it per token), and how far each served token's
     reference logit lies under the reference's best at the pass that
     unmasked it.
"""
from __future__ import annotations

import time

import numpy as np

from perfbench import counts, counts_moe, harness
from perfbench.drivers import generation as gen
from perfbench.reference import sdar_moe as ref

# The limits, from readings on the chip at the cell's own size (PERF.md
# section 2): the largest that sound runs of the program gave over 15
# seeds, and what the control (the reference with router, norms, softmaxes
# and every product's result in bfloat16, at the same block states) gave
# over 5.
#   logit_row_med_rel  sound 0.0081-0.0121; control 0.0183-0.0230.  The
#                  median over the probe's rows of a row's rms difference
#                  over its logits' std: the arithmetic alone.  Only 1.5x
#                  apart (the program multiplies bfloat16 operands too; the
#                  control differs in what it keeps in bfloat16 between the
#                  products); the limit between them has 1.24x room above
#                  the program and 1.22x below the control.
#                  THE CONTROL FAILS HERE.
#   logit_rms_rel  sound 0.024-0.040; control 0.039-0.047: the same rows'
#                  root-mean-square, which takes in the rows whose token
#                  was routed otherwise on the two sides (eighth and ninth
#                  experts closer than the rounding: such a row reads
#                  0.1-0.26) and so swings with the seed on both sides.
#                  Held at 2x against a fault in a few rows, which the
#                  median does not see (one row of 128 wholly wrong: 0.13).
#   tie_gap_max    sound <= 0.384 (one run of 15; the others <= 0.076);
#                  control 0.004-0.337.  A block whose token was routed
#                  otherwise serves tokens the reference ranks a few tenths
#                  of the logits' std (1.0) under its best.  Held at 4x,
#                  against a token altered where it is produced (about 4.5,
#                  the best of 151,936 logits over a random one).
#   tie_gap_mean   sound <= 0.0124 (the same run; the others <= 0.0032);
#                  control 0.0001-0.0105.  Held at 3x (one altered token
#                  of 64 reads 0.07).
LIMITS = {"tie_gap_max": 1.5, "tie_gap_mean": 0.04, "logit_rms_rel": 0.075,
          "logit_row_med_rel": 0.015}
N_PROBE, PROBE_ROUNDS = 8, 2   # sequences; blocks fed each (denoise+commit)
N_SAMPLE, SAMPLE_BLOCKS = 8, 2  # served requests; blocks checked of each
REF_PADS = (1024, 2560)        # the reference compiles once per length


def _model(ctx):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import sdar_moe as sm

    c = ctx.config
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "max_position_embeddings")
    cfg = sm.SdarMoeConfig(rope_theta=float(c["rope_theta"]),
                           block_length=c["block_length"],
                           denoising_steps=c["denoising_steps"],
                           mask_token_id=c["mask_token_id"],
                           **{k: c[k] for k in keys})
    return sm.SdarMoeLM(cfg, max_len=c["max_len"],
                        kv_dtype=jnp.dtype(c.get("param_dtype", "bfloat16")))


def _ref_logits(ctx, params, tokens, at0, dtype="float32"):
    """Reference logits at the ``L`` positions from ``at0`` of a sequence
    that ends with them, padded to one of a few lengths."""
    c, L = ctx.config, ctx.config["block_length"]
    n = len(tokens)
    pad = next((p for p in ctx.hooks.get("ref_pads", REF_PADS) if p >= n),
               -(-n // 512) * 512)
    toks = np.zeros(pad, np.int32)
    toks[:n] = tokens
    return np.asarray(ref.logits(params, c, toks, n, at0, L, block_length=L,
                                 dtype=dtype), np.float64)


def probe_logits(ctx, svc, params, control=False):
    """(i): the root-mean-square difference of the fed blocks' logits from
    the reference's, over the standard deviation of the reference's."""
    from mxnet_tpu.serving.bucketing import bucket_batch, pad_tokens_right
    from mxnet_tpu.serving.generation.kv_cache import blocks_for

    c, gcfg = ctx.config, svc._config
    L, mask_id, vocab = c["block_length"], c["mask_token_id"], c["vocab"]
    rng = ctx.rng(5)
    bs, S = gcfg.block_size, gcfg.max_slots
    n = min(N_PROBE, S)
    room = (PROBE_ROUNDS + 1) * L
    hi = min(ctx.traffic["prompt"]["max"], c["max_len"] - room)
    lens = np.linspace(ctx.traffic["prompt"]["min"], hi, n).astype(int) \
        + np.arange(n) % L                    # leftovers of every length
    seqs = [[int(t) for t in rng.integers(0, vocab, k)]
            for k in np.minimum(lens, hi)]
    tables, ctxs = [], []
    for toks in seqs:
        blocks = svc._alloc_reclaiming(blocks_for(len(toks) + room, bs))
        tables.append(blocks)
        whole = len(toks) // L * L
        ctxs.append(whole)
        for off, take, tb, wp in (svc._chunk_plan(whole, force_chunked=True)
                                  if whole else ()):
            table = np.zeros((1, wp), np.int32)
            table[0, :min(wp, len(blocks))] = blocks[:wp]
            svc._programs.run_fill(
                svc._cache,
                pad_tokens_right(np.asarray(toks[off:off + take], np.int32),
                                 tb)[None, :],
                np.arange(off, off + tb, dtype=np.int32)[None, :],
                np.asarray([take], np.int32), table)
    fed = []                 # (sequence up to and with the block, at, logits)
    for rnd in range(PROBE_ROUNDS):
        finished, states = [], []
        for i, toks in enumerate(seqs):
            known = toks[ctxs[i]:]
            new = [int(t) for t in rng.integers(0, vocab, L - len(known))]
            n_mask = (rnd + i) % (len(new) + 1)
            masked = np.zeros(L, bool)
            masked[rng.permutation(np.arange(len(known), L))[:n_mask]] = True
            finished.append(known + new)
            states.append(masked)
        for commit in (False, True):
            tokens = np.zeros((S, L), np.int32)
            positions = np.zeros((S, L), np.int32)
            lengths = np.zeros(S, np.int32)
            flags = np.zeros((S, L), bool)
            w = bucket_batch(max(blocks_for(x + L, bs) for x in ctxs),
                             svc._width_buckets)
            table = np.zeros((S, w), np.int32)
            for i in range(n):
                flags[i] = False if commit else states[i]
                tokens[i] = np.where(flags[i], mask_id, finished[i])
                positions[i] = ctxs[i] + np.arange(L)
                lengths[i] = L
                table[i, :min(w, len(tables[i]))] = tables[i][:w]
            _, _, lg = svc._programs.run_block(
                svc._cache, tokens, positions, lengths, table, flags,
                np.ones(S, np.int32))
            lg = np.asarray(lg[:n])
            for i in range(n):
                fed.append((seqs[i][:ctxs[i]] + [int(t) for t in tokens[i]],
                            ctxs[i], lg[i]))
        for i in range(n):
            seqs[i] = seqs[i][:ctxs[i]] + finished[i]
            ctxs[i] += L
    sq = var = 0.0
    rows = []
    for toks, at0, mine in fed:
        want = _ref_logits(ctx, params, toks, at0)
        if control:
            mine = _ref_logits(ctx, params, toks, at0, "bfloat16")
        diff2 = (np.asarray(mine, np.float64) - want) ** 2
        sq += float(np.mean(diff2))
        var += float(np.var(want))
        rows += list(np.sqrt(diff2.mean(axis=1)) / want.std(axis=1))
    # a token whose eighth and ninth experts lie closer than the rounding
    # is routed otherwise on the two sides, and its row reads ten times
    # the others': the root-mean-square takes those in, the median row
    # leaves them out and reads the arithmetic alone
    return {"logit_rms_rel": float(np.sqrt(sq / var)),
            "logit_row_med_rel": float(np.median(rows)),
            "logit_row_max_rel": float(np.max(rows)), "rows": len(rows)}


def served_gaps(ctx, params, sample, control=False):
    """(ii): committed blocks of each sampled request, at each pass
    that unmasked a token of it: how far the served token's reference
    logit lies below the reference's best there — the widest gap, the
    mean gap, the share of tokens that are not the reference's first.
    ``control``: the token the bfloat16 reference puts first stands in
    for the served one."""
    c = ctx.config
    L, mask_id = c["block_length"], c["mask_token_id"]
    rng = ctx.rng(7)
    gaps = []
    for rec in sample:
        prompt = [int(t) for t in rec.prompt]
        seq = prompt + [int(t) for t in rec.tokens]
        passes = [-1] * len(prompt) + list(rec.stream._req.unmask_pass)
        first = len(prompt) // L          # blocks that hold generated tokens
        last = len(seq) // L              # ... and are whole
        for b in rng.permutation(np.arange(first, last))[:SAMPLE_BLOCKS]:
            at0 = int(b) * L
            blk, at = seq[at0:at0 + L], passes[at0:at0 + L]
            for s in sorted({p for p in at if p >= 0}):
                state = [t if p < s else mask_id for t, p in zip(blk, at)]
                lg = _ref_logits(ctx, params, seq[:at0] + state, at0)
                pick = lg
                if control:
                    pick = _ref_logits(ctx, params, seq[:at0] + state, at0,
                                       "bfloat16")
                for j in range(L):
                    if at[j] == s:
                        tok = int(pick[j].argmax()) if control else blk[j]
                        gaps.append(float(lg[j].max() - lg[j][tok]))
    gaps = np.asarray(gaps, np.float64)
    return {"tie_gap_max": float(gaps.max()),
            "tie_gap_mean": float(gaps.mean()),
            "tokens": int(gaps.size), "not_first": int((gaps > 0).sum())}


class _Recording:
    """The service, with every ``stats()`` the load loop takes kept:
    ``offer`` reads them at the window's and the traced slice's ends, and
    the slice's counter deltas come from the same reads."""

    def __init__(self, svc):
        self._svc, self.snaps = svc, []

    def stats(self):
        s = self._svc.stats()
        self.snaps.append(s)
        return s

    def __getattr__(self, name):
        return getattr(self._svc, name)


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


def run(ctx):
    import jax
    from mxnet_tpu.executor import compile_cache_stats
    from mxnet_tpu.ops import pallas_kernels as pk

    c, t = ctx.config, ctx.traffic
    devs = jax.devices()
    svc, params, n_programs, warm = build(ctx)
    rec = _Recording(svc)
    w = gen.offer(ctx, rec)
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
    print(f"samples: window_s={window_s:.4f} tokens={w['tokens']} "
          f"iterations={iters} passes={d['block_passes']} row_passes="
          f"{d['block_row_passes']} commits={d['block_commit_row_passes']} "
          f"prefill_chunks={d['block_prefill_chunks']} requests_sent="
          f"{len(records)} of_window={len(mine)} finished={len(finished)} "
          f"failed={len(failed)} waiting_at_close={at_close['waiting']} "
          f"running_at_close={at_close['running']} "
          f"compiles_after_warmup={compiles}", flush=True)

    S, E, nl = svc._config.max_slots, c["num_experts"], c["num_hidden_layers"]
    src = harness.Sources(
        counters={"window_ms": window_s * 1e3, "iterations": iters,
                  "tokens": w["tokens"],
                  "kv_peak_occupancy": at_close["peak_occupancy"],
                  "preemptions": d["preempted"],
                  "compiles_after_warmup": compiles,
                  "block_row_passes": d["block_row_passes"],
                  "block_commit_row_passes": d["block_commit_row_passes"],
                  "block_tokens_committed": d["block_tokens_committed"],
                  "block_slot_passes": d["block_passes"] * S,
                  "block_experts_touched": d["block_experts_touched"],
                  "block_expert_slots": d["block_passes"] * nl * E,
                  "slice.iterations": w["slice_iters"]},
        events=[r.stream.stats() for r in mine if r.stream is not None],
        config=c, traffic=t)
    if ctx.trace:
        src.peaks = ctx.hooks.get("peaks") or counts.peaks(
            devs[0].device_kind)
        src.trace = sl.load(ctx.hooks.get("device_prefix", "/device:TPU:"))
        # the slice's own counter deltas: ``offer`` read stats() at the
        # window's open, the slice's two ends and the window's close
        a, b = rec.snaps[1]["counts"], rec.snaps[2]["counts"]
        ds = {k: b[k] - a[k] for k in b}
        dm, df = c["hidden_size"], c["moe_intermediate_size"]
        k = c["num_experts_per_tok"]
        # a prefill chunk of hundreds of tokens reaches every expert
        src.counters.update({
            "slice.block_passes": ds["block_passes"],
            "slice.moe_bytes": counts_moe.expert_bytes(
                ds["block_experts_touched"]
                + ds["block_prefill_chunks"] * nl * E, dm, df),
            "slice.moe_flops": nl * counts_moe.expert_flops(
                (ds["block_row_passes"] * c["block_length"]
                 + ds["prefill_tokens"]) * k, dm, df),
            "slice.block_kv_bytes": counts_moe.block_kv_bytes(
                ds["block_ctx_tokens"], c["num_key_value_heads"],
                c["head_dim"], nl)})

    # -- correct: what the window served, against the plain reference ------
    control = bool(ctx.hooks.get("control"))
    rng = ctx.rng(6)
    L = c["block_length"]
    pool = sorted((r for r in finished
                   if (len(r.prompt) + len(r.tokens)) // L
                   > len(r.prompt) // L),
                  key=lambda r: len(r.prompt) + len(r.tokens))
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
        checks += gen.compare(served, probe, LIMITS)
        med = probe["logit_row_med_rel"]
        checks.append(("logit_row_med_rel", med, LIMITS["logit_row_med_rel"],
                       med <= LIMITS["logit_row_med_rel"]))
        print(f"probe rows: worst {probe['logit_row_max_rel']:.4f}",
              flush=True)
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
    for arr in (svc._cache.k, svc._cache.v, *params.values()):
        arr.delete()
    return outcome

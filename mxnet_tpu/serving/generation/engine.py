"""GenerationService — continuous-batching autoregressive decoding.

The scheduling model is Orca's iteration-level scheduling fused with
vLLM's paged KV cache, recast in tpu-mx's zero-recompile idiom
(docs/generation.md):

- the engine owns ``max_slots`` *decode slots*; every loop iteration it
  (1) evicts finished/cancelled/expired requests (freeing their cache
  blocks), (2) under the default incremental-allocation policy preempts
  victims when the pool crosses its high watermark and grows each running
  request's block table one block at allocation-boundary crossings,
  (3) admits waiting requests into free slots — priority classes first,
  FIFO within a class; blocks for the current context only (or the
  worst case up front under ``TPUMX_GEN_PREEMPTION=0`` reserve-ahead) —
  running one bucketed *prefill* program per admission (a re-admitted
  preempted request re-prefills its context through the chunked-prefill
  rungs, emitting nothing), then (4) runs ONE *decode* program over all
  occupied slots, advancing every running request by one token.  A short
  request finishing never waits for a long neighbour, and a queued
  request starts the moment a slot and blocks free up — admission and
  eviction happen every token, not every batch;
- a failed decode step is retried once, then bisected so only the suspect
  request is quarantined with a typed :class:`GenerationStepError` while
  healthy slots keep decoding; requests a failing iteration never touched
  are requeued, not failed (docs/fault_tolerance.md);
- prefill is bucketed on the :func:`~mxnet_tpu.serving.bucketing.seq_buckets`
  ladder (B=1, T=bucket); decode runs at fixed batch ``max_slots`` with the
  block-table width bucketed on its own pow2 ladder — so the entire
  steady-state program set is finite, enumerated by :meth:`warmup`, and
  guarded by ``TPUMX_FREEZE_COMPILES=1`` after ``mark_warm()``;
- tokens stream back per request through :class:`GenerationStream`
  (iterator and/or ``on_token`` callback), with the queue-bound
  backpressure policies and deadline semantics of
  :class:`~mxnet_tpu.serving.InferenceService`;
- observability: ``serving.prefill``/``serving.decode`` spans, gauges for
  tokens/sec, KV-block occupancy and running/waiting requests, TTFT and
  inter-token latency histograms — all in the process registry.
"""
from __future__ import annotations

import logging
import os
import queue
import threading
import time
from collections import deque
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as _np

from ... import observability as _obs
from ...base import getenv
from ...fault.inject import injector as _fault_injector
from ...observability import flight_recorder as _flight
from ...observability import tracing as _trace
from ...ops.sampling import SAMPLER_BODIES, sampler_body
from ..batcher import (BACKPRESSURE_POLICIES, DeadlineExceededError,
                       QueueFullError, RequestShedError, ServingClosedError,
                       ServingError)
from ..bucketing import (batch_buckets, bucket_batch, bucket_seq_len,
                         pad_tokens_right, seq_buckets)
from .kv_cache import (PagedKVCache, blocks_for, ring_width,
                       window_blocks)
from .programs import GenerationPrograms, _synced, as_model

__all__ = ["GenerationConfig", "GenerationService", "GenerationStream",
           "GenerationStepError"]


class GenerationStepError(ServingError):
    """A decode step failed for this specific request even after the
    retry, and bisection isolated it (the quarantine outcome) — or the
    request exhausted its error-requeue budget.  Other requests in the
    same batch keep decoding (docs/generation.md "failure isolation")."""

_WAITING, _RUNNING, _FINISHED, _CANCELLED, _FAILED = (
    "waiting", "running", "finished", "cancelled", "failed")


class GenerationConfig:
    """Knobs for :class:`GenerationService`; every default reads its
    ``TPUMX_GEN_*`` environment variable first (docs/env_vars.md)."""

    def __init__(self, max_slots: Optional[int] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 max_new_tokens: Optional[int] = None,
                 queue_bound: Optional[int] = None,
                 backpressure: Optional[str] = None,
                 default_deadline_ms: Optional[float] = None,
                 amp_dtype: Optional[str] = None,
                 eos_token: Optional[int] = None,
                 chunked_prefill: Optional[bool] = None,
                 mp_devices: Optional[int] = None,
                 shard_rules=None,
                 preemption: Optional[bool] = None,
                 watermark_high: Optional[float] = None,
                 watermark_low: Optional[float] = None,
                 admission_budget: Optional[float] = None,
                 kv_dtype: Optional[str] = "__env__",
                 prefix_cache: Optional[bool] = None,
                 prefix_cache_blocks: Optional[int] = None,
                 speculative: Optional[bool] = None,
                 draft_mode: Optional[str] = None,
                 draft_k: Optional[int] = None,
                 draft_ngram: Optional[int] = None,
                 draft_window: Optional[int] = None):
        self.max_slots = int(max_slots if max_slots is not None
                             else getenv("TPUMX_GEN_SLOTS", 4))
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.block_size = int(block_size if block_size is not None
                              else getenv("TPUMX_GEN_BLOCK_SIZE", 16))
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = int(num_blocks if num_blocks is not None
                              else getenv("TPUMX_GEN_NUM_BLOCKS", 128))
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        self.max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None
            else getenv("TPUMX_GEN_MAX_NEW_TOKENS", 64))
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.queue_bound = int(queue_bound if queue_bound is not None
                               else getenv("TPUMX_GEN_QUEUE_BOUND", 256))
        if self.queue_bound < 1:
            raise ValueError("queue_bound must be >= 1")
        self.backpressure = (backpressure if backpressure is not None
                             else getenv("TPUMX_GEN_BACKPRESSURE", "block"))
        if self.backpressure not in BACKPRESSURE_POLICIES:
            raise ValueError(
                f"backpressure must be one of {BACKPRESSURE_POLICIES}, "
                f"got {self.backpressure!r}")
        env_deadline = os.environ.get("TPUMX_GEN_DEADLINE_MS")
        if default_deadline_ms is not None:
            self.default_deadline_ms: Optional[float] = float(default_deadline_ms)
        elif env_deadline:
            self.default_deadline_ms = float(env_deadline)
        else:
            self.default_deadline_ms = None
        # low-precision decode: params cast in-program, the KV pool stored
        # in the compute dtype (docs/amp.md's serving leg for generation)
        env_amp = (os.environ.get("TPUMX_GEN_AMP_DTYPE")
                   or os.environ.get("TPUMX_SERVING_AMP_DTYPE"))
        self.amp_dtype: Optional[str] = (
            str(amp_dtype) if amp_dtype is not None else (env_amp or None))
        self.seq_buckets = (sorted(int(b) for b in seq_buckets)
                            if seq_buckets else None)
        self.eos_token = None if eos_token is None else int(eos_token)
        # chunked prefill (docs/generation.md): long prompts split into
        # seq-bucket-sized chunks through the same cache-aware prefill
        # program instead of padding to the full ladder rung
        self.chunked_prefill = bool(
            chunked_prefill if chunked_prefill is not None
            else getenv("TPUMX_GEN_CHUNKED_PREFILL", 1))
        # model-parallel serving (docs/sharding.md): params sharded per
        # partition rules over an mp mesh axis so a model bigger than one
        # chip's HBM serves through the same engine
        self.mp_devices = int(mp_devices if mp_devices is not None
                              else getenv("TPUMX_GEN_MP_DEVICES", 1))
        if self.mp_devices < 1:
            raise ValueError("mp_devices must be >= 1")
        self.shard_rules = shard_rules
        # incremental KV allocation + victim preemption (docs/generation.md):
        # admission takes only the blocks the context needs, decode grows
        # the table one block at boundary crossings, and pool pressure
        # preempts the newest-admitted lowest-priority request back to the
        # queue.  =0 restores reserve-ahead admission byte-for-byte,
        # warmup enumeration and program keys included.
        self.preemption = bool(preemption if preemption is not None
                               else getenv("TPUMX_GEN_PREEMPTION", True))
        self.watermark_high = float(
            watermark_high if watermark_high is not None
            else getenv("TPUMX_GEN_WATERMARK_HIGH", 0.95))
        self.watermark_low = float(
            watermark_low if watermark_low is not None
            else getenv("TPUMX_GEN_WATERMARK_LOW", 0.80))
        if not (0.0 < self.watermark_low <= self.watermark_high <= 1.0):
            raise ValueError(
                f"watermarks must satisfy 0 < low <= high <= 1, got "
                f"low={self.watermark_low}, high={self.watermark_high}")
        # int8 paged KV cache (docs/quantization.md): the pool stores int8
        # with per-(layer, block, head) scales — ~2x the block budget at
        # the same bytes — quantized at scatter and dequantized at read in
        # both attention paths.  None/unset keeps the compute-dtype pool
        # and every program key byte-identical.
        if kv_dtype == "__env__":
            raw = os.environ.get("TPUMX_GEN_KV_DTYPE", "").strip().lower()
            kv_dtype = None if raw in ("", "0", "none", "off") else raw
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        # overload control: submissions whose projected worst-case blocks
        # (queued + running) would exceed this multiple of the pool hit the
        # backpressure policy BEFORE the pool thrashes
        self.admission_budget = float(
            admission_budget if admission_budget is not None
            else getenv("TPUMX_GEN_ADMISSION_BUDGET", 4.0))
        if self.admission_budget <= 0:
            raise ValueError("admission_budget must be > 0")
        # prefix caching (docs/generation.md "prefix caching"): hash
        # prompt tokens per block, share read-only resident KV blocks
        # across requests with refcounts + copy-on-write, and prefill
        # only the uncached suffix through the existing chunk rungs.
        # =0 restores today's behavior byte-for-byte (program keys and
        # tokens bitwise).
        self.prefix_cache = bool(
            prefix_cache if prefix_cache is not None
            else getenv("TPUMX_GEN_PREFIX_CACHE", True))
        # reserve cap on blocks the index may keep resident (0 = bounded
        # only by the pool + watermark eviction)
        self.prefix_cache_blocks = int(
            prefix_cache_blocks if prefix_cache_blocks is not None
            else getenv("TPUMX_GEN_PREFIX_CACHE_BLOCKS", 0))
        if self.prefix_cache_blocks < 0:
            raise ValueError("prefix_cache_blocks must be >= 0")
        # speculative decoding (docs/generation.md "Speculative
        # decoding"): a drafter proposes up to draft_k tokens per slot
        # and ONE multi-query verify step accepts/rejects them — greedy
        # output stays bitwise target-only, sampled output draws the
        # literally identical tokens ((seed, position) keying).  =0 (the
        # default) keeps every code path, program key and token
        # byte-identical to single-token decode.
        self.speculative = bool(
            speculative if speculative is not None
            else getenv("TPUMX_GEN_SPECULATIVE", 0))
        # "ngram" = self-speculative prompt lookup against the request's
        # own history (no second model); "model" = a small draft
        # transformer passed to GenerationService(draft_params=...)
        self.draft_mode = str(
            draft_mode if draft_mode is not None
            else getenv("TPUMX_GEN_DRAFT_MODE", "ngram")).strip().lower()
        if self.draft_mode not in ("ngram", "model"):
            raise ValueError(
                f"draft_mode must be 'ngram' or 'model', "
                f"got {self.draft_mode!r}")
        self.draft_k = int(draft_k if draft_k is not None
                           else getenv("TPUMX_GEN_DRAFT_K", 4))
        if self.draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        self.draft_ngram = int(draft_ngram if draft_ngram is not None
                               else getenv("TPUMX_GEN_DRAFT_NGRAM", 3))
        if self.draft_ngram < 1:
            raise ValueError("draft_ngram must be >= 1")
        self.draft_window = int(draft_window if draft_window is not None
                                else getenv("TPUMX_GEN_DRAFT_WINDOW", 32))
        if self.draft_window < 1:
            raise ValueError("draft_window must be >= 1")

    def __repr__(self):
        return (f"GenerationConfig(max_slots={self.max_slots}, "
                f"block_size={self.block_size}, "
                f"num_blocks={self.num_blocks}, "
                f"seq_buckets={self.seq_buckets}, "
                f"max_new_tokens={self.max_new_tokens}, "
                f"backpressure={self.backpressure!r}, "
                f"amp_dtype={self.amp_dtype!r}, "
                f"kv_dtype={self.kv_dtype!r}, "
                f"preemption={self.preemption}, "
                f"prefix_cache={self.prefix_cache}, "
                f"speculative={self.speculative})")


class _GenRequest:
    """Engine-internal per-request state."""

    __slots__ = ("rid", "prompt_len", "seq_tokens", "bucket", "max_new",
                 "temperature", "top_k", "top_p", "seed", "eos_token",
                 "deadline", "on_token", "state", "blocks", "ctx_len",
                 "n_generated", "out_queue", "done_event", "error",
                 "finish_reason", "t_submit", "t_first", "t_last",
                 "cancel_requested", "priority", "admit_seq",
                 "n_preempted", "n_requeues", "trace", "seg_state",
                 "seg_t0", "breakdown", "breakdown_first", "rung_s",
                 "decode_steps", "n_retries", "token_log", "wide_event",
                 "lock", "cached_len", "cached_total", "cow_copies",
                 "charged_blocks", "draft_proposed", "draft_accepted",
                 "mode_tokens", "index_safe_len", "block", "wins",
                 "block_masked", "block_at", "block_pass", "unmask_pass")

    def __init__(self, rid, prompt, bucket, max_new, temperature, top_k,
                 top_p, seed, eos_token, deadline, on_token, priority=0):
        self.rid = rid
        self.prompt_len = len(prompt)
        self.seq_tokens: List[int] = [int(t) for t in prompt]
        self.bucket = bucket
        self.max_new = max_new
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed) & 0xFFFFFFFF
        self.eos_token = eos_token
        self.deadline = deadline
        self.on_token = on_token
        self.state = _WAITING
        self.blocks: Optional[List[int]] = None
        # a window kind each (docs/generation.md "Cache kinds"): [the
        # first logical block the row still owns, its blocks from there]
        self.wins: Optional[List[list]] = None
        self.ctx_len = 0
        self.n_generated = 0
        self.out_queue: "queue.Queue" = queue.Queue()
        self.done_event = threading.Event()
        self.error: Optional[BaseException] = None
        self.finish_reason: Optional[str] = None
        self.t_submit = time.perf_counter()
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.cancel_requested = False
        self.priority = int(priority)
        self.admit_seq = -1        # admission recency, keys victim order
        self.n_preempted = 0       # watermark/growth preemptions survived
        self.n_requeues = 0        # error-path requeues consumed
        # prefix caching (docs/generation.md): tokens served from shared
        # blocks at the LAST admission / over the request's lifetime, CoW
        # copies taken, and the overload estimator's projected charge
        self.cached_len = 0
        self.cached_total = 0
        self.cow_copies = 0
        self.charged_blocks = 0
        # speculative decoding (docs/generation.md): drafts proposed for /
        # accepted by this request, tokens emitted per decode mode, and —
        # int8 pool only — the longest prefix whose quantized bits are
        # safe to index into the prefix cache (a partial-rejection verify
        # can requantize a boundary block under a transiently larger
        # scale; None = the whole context is safe)
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.mode_tokens: Dict[str, int] = {}
        self.index_safe_len: Optional[int] = None
        # generation by diffusion over blocks (docs/generation.md): the
        # open block at positions ctx_len.., as of the last pass that
        # was READ — its token ids, which of them still hold MASK (a flag
        # per position, never read off the id: a prompt may hold the mask
        # id), the pass each was unmasked at, the pass number — and, per
        # generated token, the pass that unmasked it (what a reference
        # needs to rebuild the block states)
        self.block: Optional[List[int]] = None
        self.block_masked: List[bool] = []
        self.block_at: List[int] = []
        self.block_pass = 0
        self.unmask_pass: List[int] = []
        # latency attribution (docs/observability.md): the request's
        # lifetime is partitioned into contiguous segments — queue,
        # admission, prefill, decode, preempted — whose transition points
        # are the scheduling events below, so the components sum exactly
        # to measured wall time (and, snapshotted at first token, to TTFT)
        self.trace = None               # TraceContext handed across threads
        self.seg_state = "queue"
        self.seg_t0 = self.t_submit
        self.breakdown: Dict[str, float] = {}
        self.breakdown_first: Optional[Dict[str, float]] = None
        self.rung_s: Dict[int, float] = {}
        self.decode_steps = 0
        self.n_retries = 0
        self.token_log: List[float] = []
        self.wide_event: Optional[dict] = None
        # serializes seg() against GenerationStream.stats()'s live
        # snapshot: the engine mutates the segment partition OUTSIDE the
        # service lock (prefill/decode run unlocked), so without this a
        # caller could read a torn (seg_state, seg_t0) pair or catch
        # the breakdown dict mid-resize
        self.lock = threading.Lock()

    def seg(self, state: str, now: float) -> None:
        """Close the open lifetime segment at ``now`` and open ``state``."""
        with self.lock:
            self.breakdown[self.seg_state] = \
                self.breakdown.get(self.seg_state, 0.0) \
                + (now - self.seg_t0)
            self.seg_state = state
            self.seg_t0 = now

    def expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (now if now is not None else time.perf_counter()) \
            >= self.deadline

    @property
    def generated(self) -> List[int]:
        return self.seq_tokens[self.prompt_len:]


class _Phase:
    """A span of the engine loop whose duration is also added to one key
    of ``stats()["phase_ms"]``: the counter and the span share one pair of
    clock reads, so the two agree whether or not a profiler runs
    (docs/observability.md)."""

    __slots__ = ("_acc", "_key", "_span")

    def __init__(self, acc: Dict[str, float], key: str, span):
        self._acc, self._key, self._span = acc, key, span

    def __enter__(self):
        self._span.__enter__()
        return self._span

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._acc[self._key] += self._span.duration_us
        return False


class _StepInputs(NamedTuple):
    """The host side of one model step over the decode slots
    (:meth:`GenerationService._build_step`): the ``(slot, request)`` rows
    it feeds, the bucketed table width, and the program's operands —
    ``sampler`` is ``(seeds, counters, temperature, top_k, top_p)``, or
    empty for a step that samples nothing."""
    rows: list
    width: int
    tokens: "_np.ndarray"
    positions: "_np.ndarray"
    lengths: "_np.ndarray"
    tables: "_np.ndarray"
    sampler: tuple

    @property
    def operands(self) -> tuple:
        return (self.tokens, self.positions, self.lengths, self.tables,
                *self.sampler)


class _BlockPass(NamedTuple):
    """What a block pass in flight holds beside a one-token step's: the
    ``tokens`` / ``masked`` operands it was fed (on the device where they
    were carried there), the experts it touched as the program returned
    them, where each row stands after it by counts alone (``after``: id
    -> the number of its next pass and the MASKs its block then holds),
    and the prefill ``(chunks, tokens)`` dispatched since the pass
    before, which are counted where the pass is."""
    tokens: object
    masked: object
    touched: object
    after: dict
    prefill: tuple


class _Flight(NamedTuple):
    """A decode step that has been dispatched and not read
    (docs/generation.md "the step in flight"): the rows it fed; what it
    returned for the next step to be fed from and the host to read, as
    the program handed it back — a one-token step's sampled ``tokens
    (S,)``, a block pass's ``unmasked (S, L)``; the clock around its
    dispatch; the iteration its participation events name; its rows'
    ``lead`` (id -> the positions the row's context moves on when the
    step lands: 1 for a token, ``L`` for a block's commit pass, 0 for a
    denoise pass); the counts (``aux``) of the programs dispatched up to
    and with it that nobody has read yet; a block pass's own state."""
    step: _StepInputs
    tokens: object
    t0: float
    t1: float
    iteration: int
    lead: dict
    aux: tuple = ()
    block: Optional[_BlockPass] = None


class _First(NamedTuple):
    """A prefill's first token, dispatched and not read (docs/generation.md
    "the step in flight"): the request, the ``(1,)`` token as the last
    chunk's program handed it back, the counts (``aux``) of the programs
    dispatched up to that chunk that nobody has read yet, and the tokens
    the chunks fed and how many chunks they were, which are counted where
    the programs' counts are."""
    req: _GenRequest
    token: object
    aux: tuple = ()
    prefill_tokens: int = 0
    prefill_chunks: int = 0


class _LandFirst(Exception):
    """Raised under the schedule when a row of the step in flight has to
    leave its slot — a preemption, a cancel, a deadline, a shutdown that
    does not drain: what the step gave it is still on the device, so the
    engine reads and emits that step first and schedules again.  A first
    token that a prefill left on the device lands with it."""


class GenerationStream:
    """Per-request handle: iterate generated tokens as they stream, or
    block on :meth:`result` for the full list."""

    def __init__(self, req: _GenRequest,
                 service: Optional["GenerationService"] = None):
        self._req = req
        self._service = service

    @property
    def request_id(self) -> int:
        return self._req.rid

    def __iter__(self):
        while True:
            kind, payload = self._req.out_queue.get()
            if kind == "tok":
                yield payload
            elif kind == "done":
                return
            else:  # "error"
                raise payload

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the request finishes; the generated token ids."""
        if not self._req.done_event.wait(timeout):
            raise TimeoutError(
                f"generation request {self._req.rid} still running "
                f"after {timeout}s")
        if self._req.error is not None:
            raise self._req.error
        return list(self._req.generated)

    def cancel(self) -> None:
        """Ask the engine to evict this request at its next iteration."""
        self._req.cancel_requested = True

    @property
    def finished(self) -> bool:
        return self._req.done_event.is_set()

    @property
    def finish_reason(self) -> Optional[str]:
        return self._req.finish_reason

    @property
    def ttft_ms(self) -> Optional[float]:
        if self._req.t_first is None:
            return None
        return (self._req.t_first - self._req.t_submit) * 1e3

    @property
    def started(self) -> bool:
        """Whether the engine has emitted at least one token for this
        request (the router's resubmit-safety criterion: an unstarted
        request can move replicas without duplicate delivery)."""
        return self._req.t_first is not None

    @property
    def trace_id(self) -> Optional[str]:
        """The request's trace id (stable across threads and replica
        hops; None with ``TPUMX_TRACING=0``)."""
        return None if self._req.trace is None else self._req.trace.trace_id

    def stats(self) -> dict:
        """Per-request observability: the wide-event record once the
        request finished, or a live snapshot of the same shape while it
        runs — TTFT, per-token timestamps, the latency breakdown, and
        preemption/requeue/retry counts (docs/observability.md).  The
        live path snapshots under the request's segment lock so the
        breakdown is never torn against a concurrent seg() transition."""
        r = self._req
        ev = r.wide_event
        if ev is not None:
            return dict(ev)
        svc = self._service
        with r.lock:
            ev = r.wide_event  # may have finished while we acquired
            if ev is not None:
                return dict(ev)
            now = time.perf_counter()
            bd = dict(r.breakdown)
            bd[r.seg_state] = bd.get(r.seg_state, 0.0) + (now - r.seg_t0)
            first = r.breakdown_first
            rung = dict(r.rung_s)
            token_log = list(r.token_log)
            outcome, finish_reason = r.state, r.finish_reason
            error, t_first = r.error, r.t_first
            n_generated, decode_steps = r.n_generated, r.decode_steps
            preemptions, requeues = r.n_preempted, r.n_requeues
            retries = r.n_retries
            cached_total, cow_copies = r.cached_total, r.cow_copies
            draft_proposed = r.draft_proposed
            draft_accepted = r.draft_accepted
            mode_tokens = dict(r.mode_tokens)
        return {
            "type": "generation_request",
            "request_id": r.rid,
            "trace_id": self.trace_id,
            "replica": None if svc is None else svc._replica_id,
            "priority": r.priority,
            "prompt_tokens": r.prompt_len,
            "output_tokens": n_generated,
            "outcome": outcome,
            "finish_reason": finish_reason,
            "error": None if error is None else repr(error),
            "total_ms": round((now - r.t_submit) * 1e3, 3),
            "ttft_ms": (None if t_first is None
                        else round((t_first - r.t_submit) * 1e3, 3)),
            "ttft_breakdown_ms": (
                None if first is None
                else {k: round(v * 1e3, 3) for k, v in first.items()}),
            "breakdown_ms": {k: round(v * 1e3, 3) for k, v in bd.items()},
            "prefill_rungs_ms": {str(k): round(v * 1e3, 3)
                                 for k, v in rung.items()},
            "decode_steps": decode_steps,
            "preemptions": preemptions,
            "requeues": requeues,
            "retries": retries,
            "prefix_cached_tokens": cached_total,
            "cow_copies": cow_copies,
            "decode_mode": _dominant_mode(mode_tokens),
            "accepted_ratio": (None if draft_proposed == 0 else
                               round(draft_accepted / draft_proposed, 4)),
            "draft_proposed_tokens": draft_proposed,
            "draft_accepted_tokens": draft_accepted,
            "token_offsets_ms": [round((t - r.t_submit) * 1e3, 3)
                                 for t in token_log],
        }


def _dominant_mode(mode_tokens: Dict[str, int]) -> str:
    """The decode mode that emitted most of a request's tokens —
    the wide-event ``decode_mode`` field (``single`` when nothing has
    been emitted yet)."""
    if not mode_tokens:
        return "single"
    return max(mode_tokens.items(), key=lambda kv: (kv[1], kv[0]))[0]


class GenerationService:
    """Continuous-batching LM generation over a paged KV cache.

    Parameters
    ----------
    params : dict of jnp arrays
        The model's parameters (``transformer_lm_init`` layout for a
        ``TransformerConfig``).
    model_cfg : :class:`~mxnet_tpu.parallel.transformer.TransformerConfig`
        or a model object (:func:`~.programs.as_model`: its step, cache
        spec, vocabulary and longest position), e.g.
        :class:`~mxnet_tpu.parallel.sdar_moe.SdarMoeLM`.  A model with a
        ``block_len`` generates by diffusion over blocks
        (docs/generation.md); what a model does not offer (sampling
        knobs, speculation, int8 KV, an mp mesh) is refused.
    config : :class:`GenerationConfig`, optional
    start : bool
        When False the engine loop is not launched until :meth:`start` —
        useful to enqueue a deterministic initial backlog (tests) or to
        :meth:`warmup` before taking traffic.
    """

    _TPS_WINDOW = 5.0  # seconds of token timestamps behind the tokens/sec gauge

    def __init__(self, params, model_cfg, config: Optional[GenerationConfig]
                 = None, start: bool = True, draft_params=None,
                 draft_cfg=None):
        import jax.numpy as jnp

        self._model_cfg = model_cfg
        self._config = config or GenerationConfig()
        self._replica_id = 0  # the router overwrites with the fleet index
        cfg = self._config
        compute_dtype = None
        if cfg.amp_dtype:
            compute_dtype = jnp.dtype(cfg.amp_dtype)
        model = self._model = as_model(model_cfg, compute_dtype)
        for what, asked in (("amp", cfg.amp_dtype),
                            ("speculative", cfg.speculative),
                            ("int8", cfg.kv_dtype == "int8"),
                            ("mp", cfg.mp_devices > 1)):
            if asked and what not in model.offers:
                raise ValueError(
                    f"{type(model).__name__} does not offer {what!r} "
                    f"(it offers {sorted(model.offers)})")
        self._block_len = L = int(model.block_len)
        if L and (cfg.block_size % L or model.max_len % L):
            # a full page's K/V then depend only on tokens up to the
            # page's end, which keeps the prefix index's chained hash valid
            raise ValueError(
                f"block_size {cfg.block_size} and max_len {model.max_len} "
                f"must be multiples of the model's block length {L}")
        # prefill ladder: bounded by the model's position table — a prompt
        # must also leave room for at least one generated token
        max_prompt = model_cfg.max_len - 1
        self._seq_buckets = (cfg.seq_buckets if cfg.seq_buckets
                             else seq_buckets(max_prompt))
        if L:
            # prefill chunks are whole blocks (the ladder's own cap,
            # max_len - 1, is not)
            if cfg.seq_buckets and any(b % L for b in cfg.seq_buckets):
                raise ValueError(
                    f"seq_buckets {cfg.seq_buckets} must be multiples of "
                    f"the model's block length {L}")
            self._seq_buckets = [b for b in self._seq_buckets if b % L == 0]
        if self._seq_buckets[-1] > max_prompt:
            raise ValueError(
                f"largest seq bucket {self._seq_buckets[-1]} exceeds the "
                f"model's max prompt length {max_prompt}")
        # the ladder's top is the longest prompt the service takes; the
        # rungs a prompt is cut into stop at the longest chunk the model's
        # prefill program takes, where it names one (its temporaries grow
        # with the chunk) and prompts are chunked at all.  That chunk is
        # itself the top rung where a prompt can be as long: a long prompt
        # then walks in the model's chunks, whatever rungs were configured
        self._prompt_buckets = self._seq_buckets
        cap = getattr(model, "longest_chunk", None)
        if cap and cfg.chunked_prefill:
            self._seq_buckets = sorted(
                {b for b in self._seq_buckets if b < cap}
                | {min(cap, self._seq_buckets[-1])})
        # the cache is what the model's spec says: the classic K/V pair
        # of folded heads, or the pools it names (latent attention: one)
        spec = model.cache_spec()
        if "kinds" in spec:
            # the kinds behind the first are sized by rows: a window kind
            # by what every slot owns at rest and the one row being
            # prefilled owns besides, a state kind by a unit a slot
            spec["window_rows"] = (cfg.max_slots, self._seq_buckets[-1])
        self._cache = PagedKVCache(
            num_blocks=cfg.num_blocks, block_size=cfg.block_size,
            kv_dtype=cfg.kv_dtype, **spec)
        # admission is by a free slot AND by the first kind's units
        # (docs/generation.md "Cache kinds").  Where the first kind is
        # paged, those are blocks under its watermark; where the cache's
        # ONE kind is a slot's state, a row owns its one state from
        # admission to release and nothing grows, so there is no headroom
        # to keep and nothing for a watermark to preempt.  Every kind
        # behind the first is sized by the slots: a free slot has its units
        self._state_kind = self._cache.kinds[0].state
        if self._state_kind:
            self._cache.allocator.set_watermarks(1.0, 1.0)
        else:
            self._cache.allocator.set_watermarks(cfg.watermark_high,
                                                 cfg.watermark_low)
        # the kinds behind the first (docs/generation.md "Cache kinds"),
        # window kinds and a slot's state beside them: empty for every
        # model whose layers are of one kind, and nothing below that names
        # them runs
        self._windows = self._cache.kinds[1:]
        # a model that runs part of itself at a prompt's last position
        # alone (docs/generation.md "The prefill skip"): every chunk but
        # the last goes through the fill program, which has no head
        self._fills = bool(getattr(model, "fills_without_head", False))
        # prefix caching (docs/generation.md "prefix caching"): the chain-
        # hash index over resident full blocks.  None with the gate off —
        # every code path below then stays byte-identical to pre-cache
        # behavior (program keys, admission accounting, tokens).
        from .prefix_cache import PrefixCacheIndex
        self._prefix = (PrefixCacheIndex(
            self._cache.allocator, cfg.block_size,
            capacity_blocks=cfg.prefix_cache_blocks)
            if cfg.prefix_cache and not self._windows
            and not self._state_kind else None)
        windows = [k.name for k in self._windows if not k.state]
        states = [k.name for k in self._cache.kinds if k.state]
        if cfg.prefix_cache and windows:
            # a hit at position p would also need the window layers' last
            # positions before p, which their rows freed as they went: the
            # index would have to keep a window of blocks with every
            # prefix it names.  Such a model declines prefix reuse, and
            # the service says so here and in stats()["prefix_cache"]
            logging.getLogger(__name__).info(
                "%s keeps window cache kinds %s: no prefix reuse (a row "
                "frees the blocks behind its window, so no cached prefix "
                "could serve them)", type(model).__name__,
                windows)
        if cfg.prefix_cache and states:
            # a hit at position p would need the state as it stood AT p,
            # and a row keeps only the state at its last position: the
            # index would have to keep a snapshot with every prefix
            logging.getLogger(__name__).info(
                "%s keeps the state cache kind %s: no prefix reuse (a row "
                "keeps its state at its last position alone, so no cached "
                "prefix could serve one)", type(model).__name__, states)
        self._pc_evictions_seen = 0
        self._programs = GenerationPrograms(params, model,
                                            mp_devices=cfg.mp_devices,
                                            shard_rules=cfg.shard_rules,
                                            kv_dtype=cfg.kv_dtype)
        # mp + paged kernel: the pool lives head-sharded on the mp mesh
        # (1/mp of the cache per chip, docs/generation.md)
        self._programs.place_cache(self._cache)
        # decode block-table widths: pow2 ladder up to the blocks needed to
        # address max_len positions (the cap itself kept, like batch_buckets)
        self._width_buckets = batch_buckets(
            self._cache.blocks_for(model_cfg.max_len))
        # a model whose kernels fetch live pages only, in prefill as in
        # decode, pays nothing for a table's width: one width, the widest,
        # and a program a chunk length instead of one a (length, width)
        self._one_width = (getattr(model, "one_table_width", False)
                           and self._programs.kernel == "paged")
        if self._one_width:
            self._width_buckets = self._width_buckets[-1:]
        # multi-token decoding (docs/generation.md "Speculative
        # decoding"): the verify chunk length Tk = s + 1 (pending token +
        # s drafts) is pow2-bucketed so warmup enumerates the full
        # (Tk, W) verify set.  EMPTY with the gate off — the warmup set,
        # program keys and growth arithmetic then stay byte-identical.
        self._verify_buckets = ([b for b in batch_buckets(cfg.draft_k + 1)
                                 if b >= 2] if cfg.speculative else [])
        # worst-case positions ONE iteration may write past ctx — block
        # growth reserves this span ahead (1 = classic single-token)
        self._iter_span = max(
            1, (cfg.draft_k + 1) if cfg.speculative else 1, L)
        self._draft = None
        if cfg.speculative and cfg.draft_mode == "model":
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "draft_mode='model' requires draft_params and "
                    "draft_cfg (a small transformer_lm_init model)")
            if int(draft_cfg.vocab) != int(model_cfg.vocab):
                raise ValueError(
                    f"draft model vocab {draft_cfg.vocab} != target "
                    f"vocab {model_cfg.vocab}")
            from .speculative import DraftModel
            self._draft = DraftModel(
                draft_params, draft_cfg, cfg.draft_k,
                min(cfg.draft_window, int(draft_cfg.max_len)),
                compute_dtype=compute_dtype)

        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._waiting: "deque[_GenRequest]" = deque()
        self._slots: List[Optional[_GenRequest]] = [None] * cfg.max_slots
        self._closed = False
        self._drain = True
        self._killed = False          # chaos hook: crashed-replica simulation
        self._next_rid = 0
        self._admit_seq = 0           # admission recency for victim order
        self._consec_step_failures = 0
        self._max_error_requeues = 3  # error-path requeue budget per request
        self._iteration = 0
        # the decode step dispatched and not read yet — a one-token step
        # or a block pass: either is built from counts alone, and what
        # the last one returned stays on the device for it.  Only a
        # service whose every decode step is that step leaves one in
        # flight: a verify chunk's proposer needs the last values on
        # the host before it can build.  Nor under an mp
        # mesh: a step's tokens come back committed to the mesh, and fed
        # onward they would key a second lowering of every decode width
        self._flight: Optional[_Flight] = None
        self._runs_ahead = not (cfg.speculative or cfg.mp_devices > 1)
        # the first tokens that this pass's prefills left on the device,
        # by request id: the decode step of the pass is fed from them
        # (``place_first``) and dispatched before they are read.  Where
        # nothing may stay in flight each is read as its prefill ends
        self._firsts: Dict[int, _First] = {}
        # what a first token is placed into where no step is in flight
        # (warm-up leaves one on the device)
        self._no_tokens = _np.zeros(cfg.max_slots, _np.int32)
        # a block-diffusion model's prefill [chunks, tokens] dispatched
        # since the last block pass was: counted with the next one
        self._prefill_uncounted = [0, 0]
        self._membership: "deque[Tuple[int, Tuple[int, ...]]]" = \
            deque(maxlen=4096)
        self._worker: Optional[threading.Thread] = None
        self._worker_lock = threading.Lock()
        self._autostart = bool(start)

        self._counts = {"submitted": 0, "finished": 0, "cancelled": 0,
                        "failed": 0, "rejected": 0, "expired": 0,
                        "shed": 0, "tokens": 0, "preempted": 0,
                        "requeued": 0, "quarantined": 0, "step_failures": 0,
                        "prefix_hits": 0, "prefix_misses": 0,
                        "prefix_evictions": 0, "cached_tokens": 0,
                        # positions the prefill and fill calls fed, and
                        # those calls
                        "prefill_tokens": 0, "prefill_chunks": 0,
                        "cow_copies": 0,
                        "draft_proposed": 0, "draft_accepted": 0,
                        "spec_steps": 0,
                        # decode steps dispatched before the last one's
                        # tokens were read, and those dispatched with
                        # nothing in flight
                        "steps_ahead": 0, "steps_drained": 0,
                        # first tokens of prefills read after the decode
                        # step they fed was dispatched, and those read
                        # at once (nothing may stay in flight, or the
                        # pass had nothing to decode)
                        "prefills_ahead": 0, "prefills_read": 0,
                        # calls of a sampling program (a decode or verify
                        # step, a prefill chunk), by the body
                        # their rows' knobs make its sampler take
                        # (ops/sampling.sampler_body)
                        **{f"sampler_steps_{b}": 0
                           for b in SAMPLER_BODIES},
                        # generation by diffusion over blocks: program
                        # calls, rows fed over them, rows on their commit
                        # pass, tokens emitted at commits, and (from the
                        # program) experts routed at least one token,
                        # summed over the layers of every call
                        "block_passes": 0, "block_row_passes": 0,
                        "block_commit_row_passes": 0,
                        "block_tokens_committed": 0,
                        "block_experts_touched": 0,
                        # cache positions the fed rows' queries could
                        # read (context + block, summed over rows), and
                        # the prefill chunks fed beside the block steps
                        "block_ctx_tokens": 0, "block_prefill_chunks": 0}
        # counts a one-token model's PROGRAM makes and hands back with
        # every step (``aux``; docs/observability.md), summed here when
        # the step's tokens are read
        self._counts.update(dict.fromkeys(getattr(model, "counters", ()), 0))
        if self._windows:
            # blocks of a window kind that slid out of their row's sight
            # and went back to the kind's allocator
            self._counts["window_blocks_freed"] = 0
        self._peak_occupancy = 0.0
        # host microseconds of the loop by phase, from the phase spans' own
        # clock reads (written by the engine thread only).  ``sync_wait``,
        # the waits for the device, lies inside ``step``; ``lock_wait`` is
        # the loop's wait for its own lock at the top of a pass
        self._phase_us = {"schedule": 0.0, "build": 0.0, "step": 0.0,
                          "emit": 0.0, "idle_wait": 0.0, "sync_wait": 0.0,
                          "lock_wait": 0.0}
        # wall microseconds of the passes, from ``serving.iteration``'s own
        # clock reads, by what a pass was: one that dispatched a decode
        # step while neither it nor the pass before it dispatched a prefill
        # or fill chunk decoded alone (with a step in flight the step a
        # pass waits for was queued behind the chunks of the pass that
        # dispatched it: their device time lands a pass late); every other
        # one is ``admitting``
        self._iter_us = {"decode_only": 0.0, "admitting": 0.0}
        self._iters_decode_only = 0
        # prefill or fill chunks this pass dispatched, and whether the
        # pass before dispatched any
        self._pass_chunks = 0
        self._chunks_before = False
        # the collector's pauses while the loop runs (``serving.gc``)
        self._gc = _obs.GcWatch("serving.gc")
        self._ttft: "deque[float]" = deque(maxlen=4096)
        self._itl: "deque[float]" = deque(maxlen=4096)
        self._token_times: "deque[float]" = deque(maxlen=8192)

        reg = _obs.registry()
        self._g_running = reg.gauge("generation_running_requests")
        self._g_waiting = reg.gauge("generation_waiting_requests")
        self._g_blocks_used = reg.gauge("generation_kv_blocks_used")
        self._g_blocks_free = reg.gauge("generation_kv_blocks_free")
        self._g_occupancy = reg.gauge("generation_kv_block_occupancy")
        self._g_live_occupancy = reg.gauge(
            "generation_kv_block_live_occupancy",
            help="fraction of the pool holding WRITTEN context — the "
                 "number reserve-ahead reservation wastes and incremental "
                 "allocation recovers (docs/generation.md)")
        # a cache kind each of a cache of several (none for most models):
        # the units its rows own now
        self._g_kind_blocks = [
            reg.gauge("generation_kv_kind_blocks_used",
                      labels={"kind": k.name},
                      help="blocks of a cache kind that rows own (a window "
                           "kind: at most window_blocks() a row, whatever "
                           "its length; a state kind: slots; "
                           "docs/generation.md \"Cache kinds\")")
            for k in (self._cache.kinds if self._windows else ())]
        self._g_tps = reg.gauge("generation_tokens_per_sec")
        self._c_tokens = reg.counter("generation_tokens_total")
        self._c_requests = reg.counter("generation_requests_total")
        self._c_preempt = reg.counter(
            "generation_preemptions_total",
            help="running requests preempted back to the waiting queue "
                 "by KV-pool pressure (watermark or failed growth)")
        self._c_requeue = reg.counter(
            "generation_requeues_total",
            help="requests requeued (not failed) after an iteration error "
                 "that never touched them")
        self._c_quarantine = reg.counter(
            "generation_quarantines_total",
            help="requests isolated by decode-step bisection and failed "
                 "with GenerationStepError")
        self._c_step_fail = reg.counter(
            "generation_step_failures_total",
            help="decode-step program invocations that raised")
        self._h_ttft = reg.histogram("generation_ttft_seconds")
        self._h_itl = reg.histogram("generation_inter_token_seconds")
        self._c_pc_hits = reg.counter(
            "generation_prefix_cache_hits_total",
            help="admissions whose prompt matched >= 1 cached full block "
                 "(prefill runs only the uncached suffix)")
        self._c_pc_misses = reg.counter(
            "generation_prefix_cache_misses_total",
            help="admissions that matched nothing in the prefix index")
        self._c_pc_evict = reg.counter(
            "generation_prefix_cache_evictions_total",
            help="cache-only blocks dropped from the prefix index "
                 "(LRU, ahead of victim preemption)")
        self._c_pc_tokens = reg.counter(
            "generation_prefix_cached_tokens_total",
            help="prompt tokens served from shared blocks instead of "
                 "being re-prefilled")
        self._g_blocks_shared = reg.gauge(
            "generation_kv_blocks_shared",
            help="pool blocks held by more than one owner "
                 "(BlockAllocator.num_shared) — the shared/exclusive "
                 "split of the occupancy gauges")
        self._g_pc_blocks = reg.gauge(
            "generation_prefix_cache_blocks",
            help="blocks currently resident in the prefix index")
        self._c_draft_proposed = reg.counter(
            "generation_draft_proposed_tokens_total",
            help="draft tokens proposed to the speculative verify step "
                 "(ngram prompt-lookup or the draft model)")
        self._c_draft_accepted = reg.counter(
            "generation_draft_accepted_tokens_total",
            help="proposed draft tokens the target model accepted "
                 "(emitted bitwise as its own tokens)")
        self._c_sampler_steps = [
            reg.counter(
                "serving_sampler_steps_total", labels={"body": b},
                help="calls of a sampling program (decode and verify "
                     "steps, prefill chunks), by the body of its "
                     "sampler their rows select: greedy (argmax alone), "
                     "draw (temperature and noise, no sort), filter (one "
                     "sort for top-k / top-p)")
            for b in SAMPLER_BODIES]
        self._c_prefills = {
            key: reg.counter(
                "serving_prefill_first_tokens_total", labels={"read": how},
                help="first tokens of prefills by when the host read "
                     "them: ahead (left on the device, fed to the pass's "
                     "decode step from there and read after its "
                     "dispatch) or at_once (speculation, an mp mesh, a "
                     "pass with nothing to decode)")
            for key, how in (("prefills_ahead", "ahead"),
                             ("prefills_read", "at_once"))}

    # -- submission ---------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               seed: int = 0, eos_token: Optional[int] = "__config__",
               deadline_ms: Optional[float] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               timeout: Optional[float] = None,
               priority: int = 0,
               trace_ctx: Optional[_trace.TraceContext] = None
               ) -> GenerationStream:
        """Enqueue one generation request; returns a stream handle.

        ``prompt``: 1-D int token ids.  ``temperature <= 0`` is greedy;
        ``top_k``/``top_p`` follow :mod:`mxnet_tpu.ops.sampling` semantics.
        ``seed`` keys the request's private sampling randomness (its tokens
        are independent of which requests share its decode batch).
        ``deadline_ms`` bounds total queue+generate time.  ``on_token(rid,
        token)`` is called from the engine thread per token.  ``timeout``
        bounds a *blocking* submit under the ``block`` policy.
        ``priority`` is the request's class: higher classes are admitted
        first and preempted last (ties FIFO / newest-admitted-first).
        ``trace_ctx`` is the explicit trace handoff (docs/observability.md):
        the router passes its dispatch context so the request keeps ONE
        trace id across the replica hop; without it the submitting
        thread's context (or a fresh trace) is used.
        """
        cfg = self._config
        if self._closed:
            raise ServingClosedError("generation service is shut down")
        prompt = _np.asarray(prompt, dtype=_np.int64).ravel()
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if _np.any(prompt < 0) or _np.any(prompt >= self._model_cfg.vocab):
            raise ValueError(
                f"prompt token ids must be in [0, {self._model_cfg.vocab})")
        # over-long prompts are rejected HERE (bucket_seq_len raises), the
        # enqueue-time contract the fixed-shape serving layer shares
        bucket = bucket_seq_len(prompt.size, self._prompt_buckets)
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else cfg.max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if "sampling" not in self._model.offers and (
                temperature > 0 or top_k > 0 or top_p < 1.0):
            raise ValueError(
                f"{type(self._model).__name__} generates greedily "
                f"(temperature {temperature}, top_k {top_k}, top_p {top_p} "
                f"asked for): sampling is not offered by this model")
        total = int(prompt.size) + max_new
        if total > self._model_cfg.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) = "
                f"{total} exceeds the model's max_len "
                f"{self._model_cfg.max_len}")
        need = self._cache.blocks_for(total)
        if need > self._cache.num_blocks - 1:
            raise ValueError(
                f"request needs {need} cache blocks but the pool only has "
                f"{self._cache.num_blocks - 1} allocatable")
        # overload accounting with the prefix cache on: blocks the index
        # would serve are not new demand — charge only the projected
        # uncached suffix plus one block of copy-on-write slack
        charge = need
        if self._prefix is not None:
            cached_blocks = self._prefix.peek(prompt) // cfg.block_size
            if cached_blocks:
                charge = max(1, need - cached_blocks + 1)
        eos = cfg.eos_token if eos_token == "__config__" else (
            None if eos_token is None else int(eos_token))
        ms = deadline_ms if deadline_ms is not None \
            else cfg.default_deadline_ms
        deadline = None if ms is None else time.perf_counter() + ms / 1e3

        budget = cfg.admission_budget * (self._cache.num_blocks - 1)
        with self._lock:
            if self._closed:
                raise ServingClosedError("generation service is shut down")

            def _overloaded():
                # the token-budget estimator (docs/generation.md "overload
                # control"): worst-case projected blocks of everything
                # queued+running, plus this request — fires the policy
                # BEFORE the pool thrashes, not when the queue fills
                if len(self._waiting) >= cfg.queue_bound:
                    return f"generation queue bound {cfg.queue_bound} reached"
                projected = self._projected_blocks_locked() + charge
                if projected > budget:
                    return (f"projected KV demand {projected} blocks exceeds "
                            f"admission budget {budget:.0f} "
                            f"({cfg.admission_budget}x pool)")
                return None

            reason = _overloaded()
            if reason is not None:
                if cfg.backpressure == "reject":
                    self._counts["rejected"] += 1
                    raise QueueFullError(reason)
                if cfg.backpressure == "shed_oldest":
                    while self._waiting and _overloaded() is not None:
                        shed = self._waiting.popleft()
                        self._counts["shed"] += 1
                        self._finish_locked(shed, error=RequestShedError(
                            "request shed under overload (shed_oldest): "
                            + reason))
                else:  # block
                    t_end = (None if timeout is None
                             else time.perf_counter() + timeout)
                    while _overloaded() is not None and not self._closed:
                        remaining = (None if t_end is None
                                     else t_end - time.perf_counter())
                        if remaining is not None and remaining <= 0:
                            raise QueueFullError(
                                f"blocking submit timed out after {timeout}s")
                        self._not_full.wait(remaining)
                    if self._closed:
                        raise ServingClosedError(
                            "generation service is shut down")
            req = _GenRequest(self._next_rid, prompt.astype(_np.int32),
                              bucket, max_new, temperature, top_k, top_p,
                              seed, eos, deadline, on_token,
                              priority=priority)
            req.charged_blocks = charge
            if _trace.enabled():
                req.trace = (trace_ctx or _trace.current_trace()
                             or _trace.new_trace())
            self._next_rid += 1
            self._waiting.append(req)
            self._counts["submitted"] += 1
            self._c_requests.inc()
            self._g_waiting.set(len(self._waiting))
            self._not_empty.notify_all()
        if self._autostart:
            self._ensure_worker()
        return GenerationStream(req, self)

    def generate(self, prompt, **kwargs) -> List[int]:
        """Blocking convenience wrapper: ``submit(...).result()``."""
        timeout = kwargs.pop("timeout", None)
        return self.submit(prompt, **kwargs).result(timeout)

    # -- lifecycle ----------------------------------------------------------------
    def start(self) -> None:
        """Launch the engine loop (idempotent)."""
        self._autostart = True
        self._ensure_worker()

    def _ensure_worker(self) -> None:
        if self._killed:
            return  # a crashed replica never restarts itself
        if self._worker is not None and self._worker.is_alive():
            return
        with self._worker_lock:
            if self._worker is None or not self._worker.is_alive():
                t = threading.Thread(target=self._loop,
                                     name="tpumx-generation-engine",
                                     daemon=True)
                self._worker = t
                t.start()

    def warmup(self) -> int:
        """Pre-compile the entire steady-state program set: one prefill per
        seq bucket, one decode per block-table-width bucket.  Calls
        ``observability.mark_warm()`` — with ``TPUMX_FREEZE_COMPILES=1``
        any later compile-cache miss raises instead of stalling the loop.
        Returns the number of programs compiled by this call."""
        before = self._programs.compiled_signatures()
        # every program's zero operands: an empty batch through the step
        # builder — all rows length 0, so warmup writes only to the null
        # block
        zeros = lambda T, w, **kw: self._build_step(  # noqa: E731
            (), T, width=w, **kw)
        with _obs.span("serving.warmup", cat="serving"):
            sigs, widths = self._prefill_signatures(), self._width_buckets
            if self._block_len:
                self._warmup_block(sigs, widths)
                sigs = widths = ()
            # every (T, W) pair the chunk planner can emit — the plain
            # per-rung ladder when chunked prefill is off
            for tb, wp in sigs:
                first, _ = self._programs.run(
                    "gen_prefill", self._cache,
                    *zeros(tb, wp, slots=1).operands)
                if self._fills:
                    self._programs.run_fill(self._cache, *zeros(
                        tb, wp, slots=1, sampler=False).operands)
            for w in widths:
                z = zeros(1, w)
                toks, _ = self._programs.run("gen_decode", self._cache,
                                             *z.operands)
            if self._runs_ahead and widths:
                # the step in flight hands its tokens on through one
                # slot-sized program with no model in it, and a prefill
                # its first token through another: placed into the
                # tokens of a step, and where none is in flight into the
                # array this leaves on the device
                self._no_tokens = self._programs.place_first(
                    self._programs.place_first(toks, first, 0), first, 0)
                _synced(self._programs.carry_tokens(
                    self._no_tokens, z.tokens, z.lengths > 0))
            # speculative verify (docs/generation.md "Speculative
            # decoding"): every (Tk, W) pair on the ladders
            for tk in self._verify_buckets:
                for w in self._width_buckets:
                    self._programs.run_verify(self._cache,
                                              *zeros(tk, w).operands)
            if self._draft is not None:
                # the draft proposer is ONE (S, window, k) program
                S = self._config.max_slots
                self._draft.propose(
                    _np.zeros((S, self._draft.window), _np.int32),
                    _np.zeros((S, self._draft.window), _np.int32),
                    _np.zeros(S, _np.int32))
            if self._prefix is not None:
                # the CoW block copy is part of the steady-state set;
                # copying the reserved null block onto itself warms it
                # without touching real cache state
                self._programs.copy_block(self._cache, 0, 0)
        _obs.mark_warm()
        return self._programs.compiled_signatures() - before

    def _warmup_block(self, sigs, widths) -> None:
        """A block-diffusion model's program set: the cache-filling
        prefill per (T, W), the block step per table width, and the
        slot-sized program with no model in it through which the pass in
        flight hands its block state on."""
        S, L = self._config.max_slots, self._block_len
        for tb, wp in sigs:
            self._programs.run_fill(self._cache, *self._build_step(
                (), tb, sampler=False, slots=1, width=wp).operands)
        masked = _np.zeros((S, L), bool)
        for w in widths:
            z = self._build_step((), L, sampler=False, width=w)
            # (as a pass in flight calls it, ``read`` False and positional
            # for a wrapper's sake: the logits stay as the program wrote
            # them, and nothing logits-sized is made beside them)
            unmasked, touched, _ = self._programs.run_block(
                self._cache, *z.operands, masked, _np.zeros(S, _np.int32),
                False)
            _synced(unmasked, touched)
        if self._runs_ahead:
            _synced(*self._programs.carry_block(
                unmasked, z.tokens, masked, z.tokens, masked,
                _np.zeros(S, bool)))

    def stop(self, drain: bool = True, timeout: Optional[float] = None,
             reject_queued: bool = False) -> None:
        """Shut down.  ``drain=True`` finishes running AND queued requests
        first; ``drain=False`` fails them with ServingClosedError.
        ``reject_queued=True`` (with ``drain=True``) is the graceful
        PREEMPTION mode: requests already decoding in slots run to
        completion, WAITING ones are rejected with a clear shutdown error
        — bounded work without abandoning accepted streams."""
        started = self._worker is not None and self._worker.is_alive()
        with self._lock:
            self._closed = True
            self._drain = drain
            if reject_queued or not started:
                # rejected-at-queue (preemption) or no loop to hand them to
                while self._waiting:
                    self._finish_locked(self._waiting.popleft(),
                                        error=ServingClosedError(
                                            "generation service shutting "
                                            "down; queued request rejected"))
            self._not_empty.notify_all()
            self._not_full.notify_all()
        if started:
            self._worker.join(timeout)
        if self._prefix is not None:
            # release the cache's own block references (blocks still held
            # by live requests merely lose their shared status)
            self._prefix.drop_all()
        self.uninstall_signal_handlers()

    drain_and_stop = stop

    def kill(self) -> None:
        """Chaos/test hook (docs/fault_tolerance.md): simulate a crashed
        replica.  The engine loop exits at its next iteration WITHOUT
        draining, failing, or notifying outstanding requests — their
        streams hang exactly as they would if the process died.  The
        router's health probe is the layer that must notice and recover
        (``TPUMX_FAULT_GEN_KILL_REPLICA`` drives this deterministically)."""
        self._killed = True
        with self._lock:
            self._not_empty.notify_all()

    def health(self) -> dict:
        """Liveness/health snapshot for the router's probe loop."""
        worker_ok = self._worker is None or self._worker.is_alive()
        with self._lock:
            waiting = len(self._waiting)
            running = sum(1 for r in self._slots if r is not None)
        return {
            "alive": (not self._killed) and (not self._closed) and worker_ok,
            "replica": self._replica_id,
            "killed": self._killed,
            "closed": self._closed,
            "consecutive_step_failures": self._consec_step_failures,
            "waiting": waiting,
            "running": running,
            "occupancy": self._cache.allocator.occupancy(),
        }

    def load(self) -> float:
        """Dispatch-ranking load score: queue depth + running slots +
        KV occupancy — the same signals the observability gauges export
        (the router's least-loaded policy sorts on this)."""
        with self._lock:
            waiting = len(self._waiting)
            running = sum(1 for r in self._slots if r is not None)
        return waiting + running + self._cache.allocator.occupancy()

    def shutdown(self, timeout: Optional[float] = None) -> None:
        """Graceful preemption shutdown (docs/fault_tolerance.md): slots
        finish their generations, queued requests are rejected."""
        _obs.registry().counter(
            "serving_graceful_shutdowns_total",
            help="graceful (signal-driven) service shutdowns").inc()
        self.stop(drain=True, timeout=timeout, reject_queued=True)

    def install_signal_handlers(self, signals=None) -> bool:
        """Drain-on-SIGTERM/SIGINT, same hook as InferenceService
        (mxnet_tpu.fault.preemption).  Returns False off the main thread."""
        from ...fault.preemption import (DEFAULT_SIGNALS,
                                         install_shutdown_hook)

        if getattr(self, "_signal_unregister", None) is not None:
            return True
        _flight.install()  # a preempted replica leaves its black box
        self._signal_unregister = install_shutdown_hook(
            lambda signum: self.shutdown(),
            signals or DEFAULT_SIGNALS)
        return self._signal_unregister is not None

    def uninstall_signal_handlers(self) -> None:
        unreg = getattr(self, "_signal_unregister", None)
        if unreg is not None:
            self._signal_unregister = None
            unreg()
            # symmetric lifecycle: the hub restores default dispositions
            # once its last callback unregisters (a mid-delivery dump
            # still fires — the hub iterates a snapshot)
            _flight.uninstall()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop(drain=True)

    # -- the engine loop ----------------------------------------------------------
    def _phase(self, phase: str, name: str, args: Optional[dict] = None,
               ctx=None) -> "_Phase":
        return _Phase(self._phase_us, phase,
                      _obs.span(name, cat="serving", args=args, ctx=ctx))

    def _loop(self) -> None:
        with self._gc:
            while self._pass():
                pass

    def _pass(self) -> bool:
        """One turn of the loop: the idle wait, or an iteration under its
        span, whose wall time is counted by what the pass was
        (``stats()["counts"]["iter_us_decode_only"]`` / ``_admitting``).
        False ends the loop."""
        if not self._lock.acquire(False):
            # a client's submit or a reader of stats() holds it: the wait
            # is the loop's, and would lie in no span
            with self._phase("lock_wait", "serving.lock_wait"):
                self._lock.acquire()
        try:
            if self._killed:
                return False  # crashed-replica simulation: vanish, no cleanup
            if not self._closed and not self._waiting \
                    and all(r is None for r in self._slots):
                # nothing queued, nothing running: not an iteration
                self._update_gauges_locked()
                with self._phase("idle_wait", "serving.idle_wait"):
                    self._not_empty.wait(0.05)
                return True
        finally:
            self._lock.release()
        steps = self._counts["steps_ahead"] + self._counts["steps_drained"]
        with _obs.span("serving.iteration", cat="serving",
                       args={"iteration": self._iteration}) as it:
            alive = self._iterate()
        chunked, self._pass_chunks = self._pass_chunks > 0, 0
        alone = not (chunked or self._chunks_before) and steps < \
            self._counts["steps_ahead"] + self._counts["steps_drained"]
        self._chunks_before = chunked
        self._iter_us["decode_only" if alone else "admitting"] += \
            it.duration_us
        self._iters_decode_only += alone
        return alive

    def _iterate(self) -> bool:
        """One pass of the loop with requests queued or running: schedule
        under the lock, dispatch the prefill chunks of what was admitted,
        dispatch one decode step over what runs — a row that joins from
        its prefill fed its first token from the device — and only then
        read and emit the step before it and those first tokens
        (docs/generation.md "the step in flight").  False ends the
        loop."""
        try:
            plan = self._schedule()
        except _LandFirst:
            self._land()
            plan = self._schedule()
        if isinstance(plan, bool):
            return plan
        admitted, progress = plan
        try:
            for req in admitted:
                try:
                    self._prefill(req)
                except Exception as exc:  # noqa: BLE001 — isolate
                    self._requeue_or_fail(req, exc)
            # a row whose token (or block) in flight is its last is not
            # fed again
            running = [r for r in self._slots
                       if r is not None and r.state == _RUNNING
                       and not self._ends_in_flight(r)]
            self._membership.append(
                (self._iteration,
                 tuple(sorted(r.rid for r in running))))
            if running:
                self._decode_isolated(running)
            else:
                # nothing left to feed: the pass reads the last step, and
                # the first tokens of what it admitted
                self._land()
        except Exception as exc:  # noqa: BLE001 — the loop must survive
            # any per-iteration surprise with minimum blast radius:
            # requeue what the failing iteration never touched (with
            # nothing in flight: a row leaves its slot at rest)
            self._land()
            self._absorb_iteration_error(exc, progress)
        self._iteration += 1
        with self._phase("schedule", "serving.schedule"), self._lock:
            self._update_gauges_locked()
        return True

    def _schedule(self):
        """The locked part of an iteration: purge, evict, preempt, grow,
        admit.  Returns ``(admitted, progress)``, or what :meth:`_iterate`
        is to return at once: True when the pass has nothing to run, False
        when the loop ends.  Raises
        :class:`_LandFirst` when a row of the step in flight must leave
        its slot; everything done before that is safe to do again."""
        with self._phase("schedule", "serving.schedule"), self._lock:
            if self._killed:
                return False
            self._purge_waiting_locked()
            with _obs.span("serving.evict", cat="serving"):
                self._evict_locked()
            if self._closed and not self._drain:
                if self._flight is not None:
                    raise _LandFirst
                err = ServingClosedError("generation service shut down")
                for r in list(self._waiting):
                    self._finish_locked(r, error=err)
                self._waiting.clear()
                for i, r in enumerate(self._slots):
                    if r is not None:
                        self._release_slot_locked(i, error=err)
                self._update_gauges_locked()
                return False
            if self._config.preemption:
                self._watermark_preempt_locked()
                self._grow_blocks_locked()
            with _obs.span("serving.admit", cat="serving"):
                admitted = self._admit_locked()
            active = [r for r in self._slots if r is not None]
            if not active and not admitted:
                # the pass emptied the queue (purged, or closed and
                # drained), or its head cannot be admitted yet; a step
                # still in flight fed only rows that have ended since
                # (an end-of-sequence id found a step late): nothing of
                # it is wanted
                self._flight = None
                if self._closed and not self._waiting:
                    return False
                self._update_gauges_locked()
                if self._waiting:
                    self._not_empty.wait(0.05)
                return True
            # per-iteration progress snapshot: the blast-radius guard
            # distinguishes requests the failing step advanced from
            # untouched ones (the latter are requeued, never failed)
            progress = {r.rid: r.n_generated
                        for r in self._slots if r is not None}
        return admitted, progress

    def _flies(self, r: _GenRequest) -> bool:
        """Whether ``r`` is a row of the step in flight: what that step
        gave it — a token, a block's unmasked positions, its commit — is
        on the device, and the host's view of the row one step behind.
        Or its prefill's first token is, unread."""
        f = self._flight
        return (f is not None and r.rid in f.lead) or r.rid in self._firsts

    def _lead(self, r: _GenRequest) -> int:
        """The positions ``r``'s context moves on when the step in flight
        lands, known from counts: 1 for a token, a block for a block's
        commit pass, 0 for a denoise pass (and for a row not in flight).
        The host's ``ctx_len`` is that far behind what the device has
        written."""
        f = self._flight
        return 0 if f is None else f.lead.get(r.rid, 0)

    def _ends_in_flight(self, r: _GenRequest) -> bool:
        """Whether what is on the device unread brings ``r`` to its
        ``max_new_tokens``: the token the step in flight sampled (its
        prefill's first token counts like it), or the block it commits,
        whose tokens the sequence does not hold yet."""
        lead = self._lead(r)
        new = (r.ctx_len + lead - len(r.seq_tokens)) if self._block_len \
            else lead + (r.rid in self._firsts)
        return new > 0 and r.n_generated + new >= r.max_new

    # -- scheduling (all _locked helpers hold self._lock) -------------------------
    def _purge_waiting_locked(self) -> None:
        now = time.perf_counter()
        keep: "deque[_GenRequest]" = deque()
        for r in self._waiting:
            if r.cancel_requested:
                self._counts["cancelled"] += 1
                self._finish_locked(r, reason=_CANCELLED)
            elif r.expired(now):
                self._counts["expired"] += 1
                self._finish_locked(r, error=DeadlineExceededError(
                    f"deadline exceeded after "
                    f"{(now - r.t_submit) * 1e3:.1f}ms in queue"))
            else:
                keep.append(r)
        if len(keep) != len(self._waiting):
            self._waiting = keep
            self._not_full.notify_all()

    def _evict_locked(self) -> None:
        now = time.perf_counter()
        for i, r in enumerate(self._slots):
            if r is None:
                continue
            if r.cancel_requested and r.state == _RUNNING:
                if self._flies(r):
                    raise _LandFirst
                self._counts["cancelled"] += 1
                self._release_slot_locked(i, reason=_CANCELLED)
            elif r.state in (_FINISHED, _FAILED, _CANCELLED):
                self._release_slot_locked(i)
            elif r.expired(now):
                if self._flies(r):
                    raise _LandFirst
                self._counts["expired"] += 1
                self._release_slot_locked(i, error=DeadlineExceededError(
                    f"deadline exceeded after {r.n_generated} tokens"))

    def _admit_need(self, r: _GenRequest) -> int:
        """Blocks an admission must secure for ``r``: under incremental
        allocation the current context plus the first iteration's write
        span — the request decodes in the very iteration that admits it,
        before :meth:`_grow_blocks_locked` next runs, so a verify chunk
        that crosses a block boundary there must already own the block
        it writes (span 1 == the classic next position);
        under reserve-ahead the full worst case."""
        cfg = self._config
        if cfg.preemption:
            ctx = r.ctx_len if r.ctx_len > 0 else r.prompt_len
            return self._cache.blocks_for(
                min(ctx + self._iter_span, r.prompt_len + r.max_new))
        return self._cache.blocks_for(r.prompt_len + r.max_new)

    def _admit_locked(self) -> List[_GenRequest]:
        """Priority-class-then-FIFO admission: fill free slots while the
        best waiting request's block need fits (head-of-line blocking
        within the chosen class is the deliberate fairness policy,
        docs/generation.md).  Under incremental allocation, admission
        additionally leaves the high-watermark headroom intact unless
        nothing is running at all (the progress guarantee)."""
        cfg = self._config
        alloc = self._cache.allocator
        total = self._cache.num_blocks - 1
        admitted = []
        free = [i for i, s in enumerate(self._slots) if s is None]
        while free and self._waiting:
            best_i, head = 0, self._waiting[0]
            for j, r in enumerate(self._waiting):
                if r.priority > head.priority:
                    best_i, head = j, r
            need = self._admit_need(head)
            # prefix cache (docs/generation.md): take shared references on
            # the longest cached full-block prefix; only the uncached
            # remainder is new allocation
            shared: List[int] = []
            cached = 0
            if self._prefix is not None:
                ctx = head.ctx_len if head.ctx_len > 0 else head.prompt_len
                shared, cached = self._prefix.acquire(head.seq_tokens[:ctx])
            grow = need - len(shared)
            if cfg.preemption and any(s is not None for s in self._slots) \
                    and alloc.num_used + grow > alloc.watermark_high * total:
                # cache-only blocks are reclaimable headroom: evict before
                # concluding the pool is too full to admit
                over = alloc.num_used + grow - alloc.watermark_high * total
                if self._prefix is not None and over > 0:
                    self._prefix.evict_blocks(int(over) + 1)
                if alloc.num_used + grow > alloc.watermark_high * total:
                    if shared:
                        alloc.decref(shared)
                    break  # keep the growth headroom; readmit later
            if not all(k.allocator.can_allocate(
                    1 if k.state else window_blocks(
                        k.window, self._seq_buckets[-1], cfg.block_size))
                    for k in self._windows):
                break   # (sized by slots: a free slot has its units)
            blocks = self._alloc_reclaiming(grow)
            if blocks is None:
                if shared:
                    alloc.decref(shared)
                break
            del self._waiting[best_i]
            head.blocks = shared + blocks
            head.cached_len = cached
            head.cached_total += cached
            if self._prefix is not None:
                if cached:
                    self._counts["prefix_hits"] += 1
                    self._counts["cached_tokens"] += cached
                    self._c_pc_hits.inc()
                    self._c_pc_tokens.inc(cached)
                else:
                    self._counts["prefix_misses"] += 1
                    self._c_pc_misses.inc()
            head.state = _RUNNING
            head.admit_seq = self._admit_seq
            self._admit_seq += 1
            self._slots[free.pop(0)] = head
            admitted.append(head)
            # latency attribution: close the wait segment (queue on first
            # admission, preempted on re-admission) and record it as a
            # span of the request's trace — the engine thread picks up
            # the context the submitter parked on the request
            now = time.perf_counter()
            waited, t_wait0 = head.seg_state, head.seg_t0
            head.seg("admission", now)
            if head.trace is not None:
                _trace.record_event(
                    "gen.queue", "serving", t_wait0, now, ctx=head.trace,
                    args={"rid": head.rid, "kind": waited,
                          "replica": self._replica_id})
            self._not_full.notify_all()
        return admitted

    def _alloc_reclaiming(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks, reclaiming cache-only prefix blocks
        (LRU) when the free list alone cannot cover it — the cache yields
        to live demand BEFORE any running request is preempted.  Safe
        with or without the service lock: allocator and index carry their
        own locks."""
        alloc = self._cache.allocator
        got = alloc.allocate(n)
        if got is None and self._prefix is not None:
            self._prefix.evict_blocks(int(n) - alloc.num_free)
            got = alloc.allocate(n)
        return got

    def _slide(self, r, seen: int, upto: int) -> None:
        """The side of a row's advance that the kinds behind the first
        take (docs/generation.md "Cache kinds").  Of a state kind ``r``
        owns one unit, its slot's state, taken at its first step and kept
        to its release.  Of a window kind ``r`` keeps the blocks from the
        one that holds position ``seen - window + 1`` — the first a query at
        ``seen`` reads — to the one that holds ``upto - 1``.  Blocks that
        slid out of every later query's sight go back to the kind's
        allocator (the next allocation may hand them to another row while
        a step that read them is still queued: programs run in order on
        the device, so that step has read them before anything writes
        them), new ones are taken for what the step will write.  ``seen``
        is the HOST's context length, one behind the device's under a
        step in flight: were that step's read to fail, the row is fed
        from ``seen`` again and still owns what it reads.  A kind is
        sized for every slot's share, so an allocation cannot fail."""
        bs = self._config.block_size
        if r.wins is None:
            r.wins = [[0, []] for _ in self._windows]
        for kind, win in zip(self._windows, r.wins):
            if kind.state:
                win[1] = win[1] or kind.allocator.allocate(1)
                if not win[1]:
                    raise ServingError(
                        f"cache kind {kind.name!r} has no free slot for "
                        f"request {r.rid}")
                continue
            first = max(0, seen - (kind.window - 1)) // bs
            if first > win[0]:
                gone = win[1][:first - win[0]]
                if gone:
                    with _obs.span("serving.window.slide", cat="serving"):
                        kind.allocator.free(gone)
                        del win[1][:len(gone)]
                    self._counts["window_blocks_freed"] += len(gone)
                win[0] = first
            need = blocks_for(upto, bs) - win[0] - len(win[1])
            if need > 0:
                got = kind.allocator.allocate(need)
                if got is None:
                    raise ServingError(
                        f"cache kind {kind.name!r} exhausted allocating "
                        f"{need} blocks for request {r.rid}")
                win[1].extend(got)

    def _drop_windows(self, r: _GenRequest) -> None:
        """Return what ``r`` owns of every kind behind the first (release,
        preemption: a resumed row's re-prefill takes window blocks anew as
        it goes, and a state from zero: a state has no snapshot, so every
        token is prefilled again)."""
        for kind, win in zip(self._windows, r.wins or ()):
            kind.allocator.free(win[1])
        r.wins = None

    def _ring_tables(self, rows, S: int, T: int) -> tuple:
        """The tables of the kinds behind the first, of a step that feeds
        ``T`` positions a row.  A window kind's is ``(S, ring_width)``, a
        RING — the logical block ``b`` of a row sits in column ``b %
        width``; a state kind's is one column, the row's slot."""
        out = []
        for j, kind in enumerate(self._windows):
            width = 1 if kind.state else ring_width(
                kind.window, T, self._config.block_size)
            table = _np.zeros((S, width), _np.int32)
            for i, r in rows:
                first, blocks = r.wins[j]
                table[i, (first + _np.arange(len(blocks))) % width] = blocks
            out.append(table)
        return tuple(out)

    def _cow_for_write(self, r: _GenRequest, off: int, take: int) -> None:
        """Copy-on-write (docs/generation.md "prefix caching"): before a
        scatter into positions ``[off, off + take)``, any target block
        with ``refcount > 1`` (shared prompt history) is replaced by a
        private in-program copy — writers never touch shared blocks, and
        sharers' logits are bit-identical before and after the append.
        Runs on the engine thread with no service lock held."""
        if self._prefix is None or take <= 0:
            return
        bs = self._config.block_size
        alloc = self._cache.allocator
        for li in range(off // bs, (off + take - 1) // bs + 1):
            if li >= len(r.blocks):
                break
            b = r.blocks[li]
            if alloc.refcount(b) <= 1:
                continue
            fresh = self._alloc_reclaiming(1)
            if fresh is None:
                raise ServingError(
                    f"KV pool exhausted allocating a copy-on-write block "
                    f"for request {r.rid} (shared block {b})")
            with _obs.span("serving.cow_copy", cat="serving",
                           args={"rid": r.rid, "src": int(b),
                                 "dst": int(fresh[0])}, ctx=r.trace):
                self._programs.copy_block(self._cache, b, fresh[0])
            r.blocks[li] = fresh[0]
            alloc.decref([b])
            r.cow_copies += 1
            self._counts["cow_copies"] += 1

    def _index_safe_ctx(self, r: _GenRequest) -> int:
        """Longest context prefix whose cache bits are safe to share via
        the prefix index.  f32/bf16 pools: the whole context (rejected
        speculative writes only ever land at positions >= ctx_len, never
        inside an indexed full block).  int8 pools: capped at
        ``index_safe_len`` once a partial-rejection verify requantized a
        mixed accepted/rejected boundary block under a transiently larger
        scale (docs/generation.md "Speculative decoding")."""
        if r.index_safe_len is None:
            return r.ctx_len
        return min(r.ctx_len, r.index_safe_len)

    def _pick_victim_locked(self) -> Optional[int]:
        """Victim slot for preemption: lowest priority class first, then
        newest admitted (vLLM's evict-the-latecomer policy — the oldest
        request monotonically progresses, guaranteeing liveness)."""
        best_i = None
        best_key = None
        for i, r in enumerate(self._slots):
            if r is None or r.state != _RUNNING:
                continue
            key = (r.priority, -r.admit_seq)
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        return best_i

    def _preempt_slot_locked(self, i: int, counter: str = "preempted") -> None:
        """Move a running request back to the waiting queue: blocks
        returned to the pool, context retained — re-admission re-prefills
        it through the chunked-prefill rungs (tokens stay bit-identical:
        sampling is keyed on (seed, position) only)."""
        r = self._slots[i]
        if self._flies(r):
            raise _LandFirst
        r.seg("preempted", time.perf_counter())
        with _obs.span("serving.preempt", cat="serving",
                       args={"rid": r.rid, "ctx": r.ctx_len,
                             "blocks": len(r.blocks or ()),
                             "kind": counter}, ctx=r.trace):
            self._slots[i] = None
            r.block = None   # a block in flight is dropped; commits stay
            if r.blocks:
                # a preempted request's written context is valid history:
                # index its full blocks so the decref below leaves them
                # RESIDENT (cache-held) and the re-prefill on re-admission
                # re-hits them — resumed TTFT collapses too.  The error-
                # requeue path ("requeued") skips this: a failing step may
                # have left the blocks suspect.
                if self._prefix is not None and counter == "preempted" \
                        and self._index_safe_ctx(r) > 0:
                    self._prefix.insert(
                        r.seq_tokens[:self._index_safe_ctx(r)], r.blocks)
                self._cache.allocator.free(r.blocks)
                r.blocks = None
            self._drop_windows(r)
            r.state = _WAITING
            self._waiting.appendleft(r)
            if counter == "preempted":
                r.n_preempted += 1
                self._c_preempt.inc()
            else:
                r.n_requeues += 1
                self._c_requeue.inc()
            self._counts[counter] += 1

    def _watermark_preempt_locked(self) -> None:
        """Crossing the high watermark preempts victims down to the low
        watermark, so near-term block growth never hits a hard exhaust
        mid-step.  The last running request is never preempted (it alone
        cannot thrash the pool — its worst case was validated at submit)."""
        alloc = self._cache.allocator
        if not alloc.above_high():
            return
        # cache-only blocks go first (docs/generation.md "prefix
        # caching"): LRU eviction of index-held blocks ahead of victim
        # preemption — dropping reusable history is strictly cheaper than
        # re-prefilling a live request
        if self._prefix is not None:
            # the whole crossing in one call: one walk of the index
            asked = alloc.blocks_above_low()
            with _obs.span("serving.watermark", cat="serving",
                           args={"asked": asked}) as sp:
                sp.args["freed"] = self._prefix.evict_blocks(asked)
            if not alloc.above_high():
                return
        while alloc.above_low():
            if sum(1 for r in self._slots
                   if r is not None and r.state == _RUNNING) <= 1:
                break
            v = self._pick_victim_locked()
            if v is None:
                break
            self._preempt_slot_locked(v)

    def _grow_blocks_locked(self) -> None:
        """Incremental allocation: before the decode step, every running
        request whose next written position crosses a block boundary gets
        one more block — oldest admitted first.  Exhaustion preempts the
        victim policy's pick; when the grower IS the pick, it preempts
        itself (it is the newest/lowest — latecomers yield)."""
        order = sorted(
            (i for i, r in enumerate(self._slots)
             if r is not None and r.state == _RUNNING),
            key=lambda i: self._slots[i].admit_seq)
        for i in order:
            r = self._slots[i]
            if r is None or r.state != _RUNNING:
                continue  # preempted by an earlier grower this pass
            # reserve the whole iteration's worst-case write span (the
            # verify chunk may append up to _iter_span positions); span 1
            # == the classic next-position arithmetic, and the cap at
            # prompt+max_new means single-token services are
            # byte-identical
            need = self._cache.blocks_for(
                min(r.ctx_len + self._lead(r) + self._iter_span,
                    r.prompt_len + r.max_new))
            while len(r.blocks) < need:
                got = self._alloc_reclaiming(need - len(r.blocks))
                if got is not None:
                    r.blocks.extend(got)
                    break
                v = self._pick_victim_locked()
                if v is None or self._slots[v] is r:
                    self._preempt_slot_locked(i)
                    break
                self._preempt_slot_locked(v)

    def _projected_blocks_locked(self) -> int:
        """Worst-case KV demand of everything queued + running — the
        overload estimator's input (docs/generation.md).  With the prefix
        cache on, each request carries its submit-time charge: worst case
        minus the blocks the index projected to serve, plus CoW slack —
        a shared-prompt burst no longer rejects on demand the pool never
        actually sees.  Cache off: charge == the full worst case."""
        worst = self._cache.blocks_for
        total = 0
        for r in self._waiting:
            total += r.charged_blocks or worst(r.prompt_len + r.max_new)
        for r in self._slots:
            if r is not None:
                total += r.charged_blocks or worst(r.prompt_len + r.max_new)
        return total

    def _release_slot_locked(self, i: int, reason: str = _FINISHED,
                             error: Optional[BaseException] = None) -> None:
        r = self._slots[i]
        self._slots[i] = None
        if r.blocks:
            # keep a finished request's full blocks resident for the next
            # shared-prompt arrival (only clean completions: an errored
            # request's cache state is suspect)
            if self._prefix is not None and reason == _FINISHED \
                    and error is None and self._index_safe_ctx(r) > 0:
                self._prefix.insert(
                    r.seq_tokens[:self._index_safe_ctx(r)], r.blocks)
            self._cache.allocator.free(r.blocks)
            r.blocks = None
        self._drop_windows(r)
        self._finish_locked(r, reason=reason, error=error)
        self._not_full.notify_all()  # blocks freed: budget waiters re-check

    def _finish_locked(self, r: _GenRequest, reason: str = _FINISHED,
                       error: Optional[BaseException] = None) -> None:
        if r.done_event.is_set():
            return
        now = time.perf_counter()
        r.seg("end", now)  # close the final lifetime segment
        if error is not None:
            r.state = _FAILED
            r.finish_reason = r.finish_reason or "error"
            r.error = error
            self._counts["failed"] += 1
            r.out_queue.put(("error", error))
        else:
            r.state = reason
            r.finish_reason = r.finish_reason or reason
            r.out_queue.put(("done", r.finish_reason))
        # every request terminates in ONE wide-event record
        # (docs/observability.md): ring + TPUMX_TRACE_LOG sink + stream
        # stats, and the trace gains its terminal reply span
        r.wide_event = self._build_wide_event(r, now)
        _trace.record_wide_event(r.wide_event)
        if r.trace is not None:
            _trace.record_event("gen.reply", "serving", now,
                                time.perf_counter(), ctx=r.trace,
                                args={"rid": r.rid, "outcome": r.state,
                                      "replica": self._replica_id})
        r.done_event.set()

    def _build_wide_event(self, r: _GenRequest, now: float) -> dict:
        bd = dict(r.breakdown)
        bd.pop("end", None)
        first = r.breakdown_first
        return {
            "type": "generation_request",
            "request_id": r.rid,
            "trace_id": None if r.trace is None else r.trace.trace_id,
            "replica": self._replica_id,
            "priority": r.priority,
            "prompt_tokens": r.prompt_len,
            "output_tokens": r.n_generated,
            "outcome": r.state,
            "finish_reason": r.finish_reason,
            "error": None if r.error is None else repr(r.error),
            "total_ms": round((now - r.t_submit) * 1e3, 3),
            "ttft_ms": (None if r.t_first is None
                        else round((r.t_first - r.t_submit) * 1e3, 3)),
            "ttft_breakdown_ms": (
                None if first is None
                else {k: round(v * 1e3, 3) for k, v in first.items()}),
            "breakdown_ms": {k: round(v * 1e3, 3) for k, v in bd.items()},
            "prefill_rungs_ms": {str(k): round(v * 1e3, 3)
                                 for k, v in r.rung_s.items()},
            "decode_steps": r.decode_steps,
            "preemptions": r.n_preempted,
            "requeues": r.n_requeues,
            "retries": r.n_retries,
            "prefix_cached_tokens": r.cached_total,
            "cow_copies": r.cow_copies,
            "decode_mode": _dominant_mode(r.mode_tokens),
            "accepted_ratio": (None if r.draft_proposed == 0 else
                               round(r.draft_accepted / r.draft_proposed,
                                     4)),
            "draft_proposed_tokens": r.draft_proposed,
            "draft_accepted_tokens": r.draft_accepted,
            "token_offsets_ms": [round((t - r.t_submit) * 1e3, 3)
                                 for t in r.token_log],
        }

    # -- model steps (engine thread, no lock held) --------------------------------
    def _chunk_plan(self, prompt_len: int, force_chunked: bool = False,
                    start: int = 0):
        """Prefill chunking (docs/generation.md): ``[(off, take, T, W)]``.

        A single entry is the legacy path — whole prompt padded to its
        ladder rung, table width ``blocks_for(rung)``.  With chunked
        prefill on and a prompt past the smallest rung, the prompt is
        split greedily into rung-sized chunks fed through the SAME
        cache-aware prefill program (each chunk writes its positions and
        attends to everything already cached), so a 130-token prompt
        costs 64+64+64 padded positions instead of 256.  Chunk table
        widths are pow2-bucketed on the decode width ladder, keeping the
        whole (T, W) signature set finite and warmup-enumerable.

        ``force_chunked`` is the re-prefill spelling (a preempted
        request's context can exceed the prompt ladder, and must chunk
        even when ``chunked_prefill`` is off): the rung walk is used for
        any length past the smallest rung.

        ``start`` is the prefix-cache spelling (docs/generation.md
        "prefix caching"): positions ``[0, start)`` are already resident
        in shared blocks, so the walk covers only the uncached suffix —
        re-bucketed onto the SAME (T, W) ladder, which is why a cache hit
        mints no new program shapes.
        """
        rungs = self._seq_buckets
        if start > 0:
            return self._walk(start, prompt_len)
        chunked = self._config.chunked_prefill or force_chunked
        chunks = self._walk(0, prompt_len) \
            if chunked and prompt_len > rungs[0] else ()
        if len(chunks) <= 1:  # no walk, or exactly one rung: the legacy plan
            tb = bucket_seq_len(prompt_len, rungs)
            return [(0, prompt_len, tb, self._rung_width(tb))]
        return chunks

    def _walk(self, off: int, end: int):
        """The greedy walk of positions ``[off, end)`` down the rungs: the
        longest rung that fits what is left, the shortest for a last
        leftover under every rung."""
        rungs, chunks = self._seq_buckets, []
        while off < end:
            rem = end - off
            fitting = [b for b in rungs if b <= rem]
            tb = fitting[-1] if fitting else rungs[0]
            take = min(rem, tb)
            w = bucket_batch(self._cache.blocks_for(off + tb),
                             self._width_buckets)
            chunks.append((off, take, tb, w))
            off += take
        return chunks

    def _rung_width(self, tb: int) -> int:
        """Table width of a prompt padded whole to its rung: the blocks
        the rung spans, or the service's one width."""
        return self._width_buckets[0] if self._one_width \
            else self._cache.blocks_for(tb)

    def _prefill_signatures(self):
        """Every (T, W) prefill signature the chunk planner can emit —
        the warmup enumeration set (finite: one pass over the possible
        prompt lengths, pure host arithmetic).  With preemption enabled
        the set also covers every RE-prefill plan — a preempted request's
        context can be any length up to ``max_len - 1`` and must replay
        through already-warmed rungs (the zero-recompile guarantee holds
        under ``TPUMX_FREEZE_COMPILES=1`` with preemption active)."""
        cfg = self._config
        out = {(tb, self._rung_width(tb)) for tb in self._seq_buckets}
        # a block-diffusion model prefills whole blocks only, every
        # context through the chunk walk, and never needs a cached
        # prompt's last logits
        step = self._block_len or 1
        if cfg.chunked_prefill:
            for L in range(step, self._prompt_buckets[-1] + 1, step):
                for (_, _, tb, w) in self._chunk_plan(L):
                    out.add((tb, w))
        if cfg.preemption or self._block_len:
            for L in range(step, self._model_cfg.max_len, step):
                for (_, _, tb, w) in self._chunk_plan(L, force_chunked=True):
                    out.add((tb, w))
        if self._prefix is not None:
            # cache-hit suffixes (docs/generation.md "prefix caching"):
            # the rung walk from every block-aligned cached length to
            # every context length — memoized on (off, remaining) so the
            # whole enumeration is one pass over reachable walk states
            bs = cfg.block_size
            max_ctx = self._model_cfg.max_len - 1
            seen = set()
            for start in range(bs, max_ctx, bs):
                for ctx in range(start + step, max_ctx + 1, step):
                    off, rem = start, ctx - start
                    while rem > 0 and (off, rem) not in seen:
                        seen.add((off, rem))
                        fitting = [b for b in self._seq_buckets if b <= rem]
                        tb = fitting[-1] if fitting else self._seq_buckets[0]
                        take = min(rem, tb)
                        out.add((tb, bucket_batch(
                            self._cache.blocks_for(off + tb),
                            self._width_buckets)))
                        off += take
                        rem -= take
            # fully-cached prompts: the single-token logit recompute at
            # position p-1 (only block-aligned prompt lengths can be
            # fully cached, and fresh prompts are bounded by the ladder)
            tb0 = self._seq_buckets[0]
            for p in (() if self._block_len else
                      range(bs, self._prompt_buckets[-1] + 1, bs)):
                out.add((tb0, bucket_batch(
                    self._cache.blocks_for(p - 1 + tb0),
                    self._width_buckets)))
        return sorted(out)

    def _chunk_inputs(self, r: _GenRequest, off: int, take: int, tb: int,
                      wp: int):
        """The host part of one prefill chunk (span
        ``serving.prefill.build``): copy-on-write over its span, then
        ``(tokens (1, tb), positions (1, tb), table (1, wp))``."""
        with self._phase("build", "serving.prefill.build"):
            self._cow_for_write(r, off, take)
            table = _np.zeros((1, wp), _np.int32)
            n = min(wp, len(r.blocks))
            table[0, :n] = r.blocks[:n]
            if self._windows:
                self._slide(r, off, off + take)
                table = (table, *self._ring_tables([(0, r)], 1, tb))
            tokens = pad_tokens_right(
                _np.asarray(r.seq_tokens[off:off + take], _np.int32),
                tb)[None, :]
            positions = _np.arange(off, off + tb, dtype=_np.int32)[None, :]
        return tokens, positions, table

    def _prefill(self, r: _GenRequest) -> None:
        if self._block_len:
            return self._block_prefill(r)
        next_tok = None
        # re-admission after preemption: replay the WHOLE cached context
        # (prompt + already-generated tokens) through the chunked-prefill
        # rungs, emit nothing — the pending token at index ctx_len is
        # already in seq_tokens and the next decode picks it up.  The
        # final chunk's sample (seed, counter=ctx) is bit-identical to the
        # token already emitted, so it is simply discarded.
        resumed = r.ctx_len > 0
        ctx = r.ctx_len if resumed else r.prompt_len
        cached = min(r.cached_len, ctx)
        if cached >= ctx and resumed:
            # full re-hit: the whole written context (prompt + generated)
            # is already resident in shared blocks — nothing to compute;
            # the pending token at index ctx is in seq_tokens and the next
            # decode picks it up
            plan = []
        elif cached >= ctx:
            # whole prompt cached: recompute ONLY the last position, for
            # its logits (the near-zero-prefill path).  Its scatter lands
            # inside the shared tail block, so _cow_for_write below gives
            # this writer a private copy first; re-quantization of the
            # copied int8 block is bit-stable (the absmax entry round-
            # trips exactly, docs/quantization.md), so the recomputed
            # block — and the sampled token — match the miss path bitwise.
            start = ctx - 1
            tb0 = self._seq_buckets[0]
            plan = [(start, 1, tb0,
                     bucket_batch(self._cache.blocks_for(start + tb0),
                                  self._width_buckets))]
        elif cached > 0:
            # uncached suffix only, through the SAME (T, W) rung ladder
            plan = self._chunk_plan(ctx, start=cached)
        else:
            plan = self._chunk_plan(ctx, force_chunked=resumed)
        # attribution: the admission segment ran from block allocation to
        # here; record it on the trace, then open the prefill segment —
        # with a prefix_reuse segment between them when the cache served
        # part of the context (the partition stays exact)
        now = time.perf_counter()
        if r.trace is not None:
            _trace.record_event("gen.admit", "serving", r.seg_t0, now,
                                ctx=r.trace,
                                args={"rid": r.rid, "resumed": resumed,
                                      "blocks": len(r.blocks or ()),
                                      "cached": cached,
                                      "replica": self._replica_id})
        if cached > 0:
            r.seg("prefix_reuse", now)
            now = time.perf_counter()
        r.seg("prefill", now)
        knobs = (_np.asarray([r.temperature], _np.float32),
                 _np.asarray([r.top_k], _np.int32),
                 _np.asarray([r.top_p], _np.float32))
        for (off, take, tb, wp) in plan:
            tokens, positions, table = self._chunk_inputs(r, off, take, tb,
                                                          wp)
            t_rung0 = time.perf_counter()
            with self._phase("step", "serving.prefill",
                             args={"rid": r.rid, "len": ctx,
                                   "bucket": tb, "off": off,
                                   "chunks": len(plan),
                                   "resumed": resumed}, ctx=r.trace):
                # the sampler reads the chunk's last VALID row; only the
                # final chunk's sample (global position prompt_len-1, the
                # same seed/counter as the unchunked program) is emitted —
                # intermediate chunks exist to fill the cache, and a model
                # that ``fills_without_head`` runs them with no head at all
                self._pass_chunks += 1
                if self._fills and off + take < ctx:
                    self._programs.run_fill(
                        self._cache, tokens, positions,
                        _np.asarray([take], _np.int32), table)
                else:
                    next_tok, _ = self._programs.run(
                        "gen_prefill", self._cache, tokens, positions,
                        _np.asarray([take], _np.int32), table,
                        _np.asarray([r.seed], _np.uint32),
                        _np.asarray([ctx], _np.uint32), *knobs)
                    self._count_sampler_step(*knobs)
            r.rung_s[tb] = r.rung_s.get(tb, 0.0) \
                + (time.perf_counter() - t_rung0)
            if self._windows:
                # what the chunk wrote and no later query sees goes back
                # before the next row's prefill asks for blocks
                self._slide(r, off + take, off + take)
        fed = sum(p[1] for p in plan)
        if resumed:
            self._counts["prefill_tokens"] += fed
            self._counts["prefill_chunks"] += len(plan)
            r.seg("decode", time.perf_counter())
            return
        # nothing of the prefill is read here: the context stands at the
        # prompt's end by count, and the first token stays on the device
        # for the decode step of this pass to be fed from, read after
        # that step's dispatch (:meth:`_land_firsts`) — at once where
        # nothing may stay in flight
        r.ctx_len = r.prompt_len
        self._firsts[r.rid] = _First(r, next_tok, self._programs.take_aux(),
                                     fed, len(plan))
        if not self._runs_ahead:
            self._land_firsts()

    def _land_firsts(self, ahead: bool = False) -> None:
        """Read and emit the first tokens that prefills left on the
        device, each as its own read in the order of the prefills: a
        prompt's token is served when its chunks have ended, not when
        the last admitted prompt's have.  With ``ahead`` the decode step
        they fed has been dispatched (they were ready before it started:
        the wait is for the chunks, with that step queued behind them);
        otherwise the pass had nothing to decode, a step failed before
        its dispatch, or the service leaves nothing in flight.  A
        prompt's full blocks are shown to the prefix index when its
        token has been read, as they were when a prefill read it itself:
        concurrent identical prompts then hit while the first is still
        decoding.  A read that fails costs no token: the request whose
        prefill it was is requeued, or failed past its budget, with
        nothing of it served, and the step fed from the token drops its
        row."""
        if not self._firsts:
            return
        firsts, self._firsts = self._firsts, {}
        which = "prefills_ahead" if ahead else "prefills_read"
        for r, token, aux, fed, chunks in firsts.values():
            try:
                with self._phase("step", "serving.prefill",
                                 args={"rid": r.rid, "first_token": True,
                                       "ahead": ahead}, ctx=r.trace):
                    tok = _synced(token, of="prefill",
                                  waited=self._phase_us)
            except Exception as exc:  # noqa: BLE001 — the device's error
                f = self._flight
                # (the flight's dict and list, changed in place: a step
                # emits to the rows it still names)
                if f is not None and f.lead.pop(r.rid, None) is not None:
                    f.step.rows[:] = [row for row in f.step.rows
                                      if row[1] is not r]
                r.ctx_len = 0            # prefilled anew, as a fresh one
                self._requeue_or_fail(r, exc)
                continue
            self._counts[which] += 1
            self._c_prefills[which].inc()
            with self._phase("emit", "serving.emit"):
                # (the host's count of the tokens fed beside the programs'
                # own counts of them: a reader of stats() finds them
                # agreeing)
                self._count_aux(aux)
                self._counts["prefill_tokens"] += fed
                self._counts["prefill_chunks"] += chunks
                if self._prefix is not None:
                    self._prefix.insert(r.seq_tokens[:r.ctx_len], r.blocks)
                r.seg("decode", time.perf_counter())
                self._emit_token(r, int(tok[0]))

    def _decode_step(self, batch: List[_GenRequest],
                     ahead: bool = False) -> None:
        """One decode iteration over exactly the requests in ``batch``
        (slots outside it stay inactive: length 0, null-block table) —
        the full running set normally, a bisection subset when isolating
        a poisoned request.  Tokens are batch-composition-independent
        (seeded per request), so subsets emit identical values.

        Mode dispatch (docs/generation.md "Speculative decoding"): with
        speculative decoding on and at least one slot holding draft
        proposals, the iteration is ONE multi-query verify step (slots
        without drafts ride along at chunk length 1); otherwise the
        classic single-token step.  Both paths emit identical token
        VALUES — they differ only in how many tokens one device dispatch
        yields.

        ``ahead`` lets the single-token step, or the block pass, of a
        service that runs no other kind leave what it returned on the
        device (docs/generation.md "the step in flight"); every other
        step is read before it returns."""
        cfg = self._config
        if self._block_len:
            self._block_step(batch, ahead and self._runs_ahead)
            return
        if cfg.speculative:
            drafts = self._propose_drafts(batch)
            if any(drafts.values()):
                self._spec_step(batch, drafts)
                return
        self._single_step(batch, ahead and self._runs_ahead)

    def _build_step(self, batch: Sequence[_GenRequest], T: int, feed=None,
                    sampler: bool = True, slots: Optional[int] = None,
                    width: Optional[int] = None,
                    lead=None) -> _StepInputs:
        """The host side of one model step, written once for every step
        kind.  The slots are walked ONCE: a row is a slot whose running
        request is in ``batch`` (slots outside it stay inactive: length
        0, null-block table).  A row feeds ``feed(request)`` — up to ``T``
        token ids — at positions ``ctx_len ..`` (as much further as
        ``lead``, the step in flight's, says of a row: that step wrote
        up to there) and will write as many positions there as it feeds;
        before that write its span is made private, then its
        ``tokens (S, T)``,
        ``positions``, ``lengths`` and, with ``sampler``, its seed, the
        index of the first token it produces (the counter) and its
        sampling knobs are filled.  The widest table needed is bucketed on
        the pow2 ladder and ``tables`` filled.  Last, BEFORE any dispatch
        (the paged pool is never half-written), the deterministic failure
        injection ``TPUMX_FAULT_GEN_STEP_FAIL``.

        An empty batch gives warm-up its zero operands: ``slots`` rows
        (prefill programs run at 1) at table width ``width``."""
        cfg = self._config
        S = slots or cfg.max_slots
        rids = {r.rid for r in batch if r.state == _RUNNING}
        lead = lead or {}
        # the token rows are laid out in one flat Python list and the
        # positions in one array expression, each converted once: a NumPy
        # assignment a row costs more than the row
        flat = [0] * (S * T)
        ctx = _np.zeros(S, _np.int32)
        lengths = _np.zeros(S, _np.int32)
        knobs = ()
        if sampler:
            seeds, counters, temperature, top_k, top_p = knobs = (
                _np.zeros(S, _np.uint32), _np.zeros(S, _np.uint32),
                _np.zeros(S, _np.float32), _np.zeros(S, _np.int32),
                _np.ones(S, _np.float32))
        rows, end = [], 0      # end: the last position any row writes, +1
        for i, r in enumerate(self._slots):
            if r is None or r.state != _RUNNING or r.rid not in rids:
                continue
            fed = feed(r)
            n, c = len(fed), r.ctx_len + lead.get(r.rid, 0)
            # copy-on-write append: a row about to scatter into a shared
            # block (refcount > 1) gets a private copy first — shared
            # prompt history is read-only to every writer (idempotent, so
            # bisection re-entry is safe).  Over the WHOLE span: a verify
            # step's REJECTED writes land at positions >= ctx_len too,
            # and must never touch a shared block — this is the rollback
            # guarantee (shared prefix blocks are physically unreachable
            # from a speculative scatter)
            self._cow_for_write(r, c, n)
            if self._windows:
                self._slide(r, r.ctx_len, c + n)
            rows.append((i, r))
            flat[i * T:i * T + n] = fed
            ctx[i] = c
            lengths[i] = n
            if sampler:
                seeds[i] = r.seed
                counters[i] = c + 1
                temperature[i] = r.temperature
                top_k[i] = r.top_k
                top_p[i] = r.top_p
            end = max(end, c + n)
        tokens = _np.array(flat, _np.int32).reshape(S, T)
        # a row's tokens sit at ctx_len .. ctx_len + T - 1; the other rows
        # stay at 0
        positions = _np.where(lengths[:, None] > 0,
                              ctx[:, None] + _np.arange(T, dtype=_np.int32),
                              0)
        w = width or bucket_batch(self._cache.blocks_for(end),
                                  self._width_buckets)
        tables = _np.zeros((S, w), _np.int32)
        for i, r in rows:
            blocks = r.blocks[:w]
            tables[i, :len(blocks)] = blocks
        if self._windows:
            tables = (tables, *self._ring_tables(rows, S, T))
        # warm-up's empty batch is no step: it does not advance the
        # injector's count of invocations
        if batch and _fault_injector().gen_step_fail(rids):
            from ...fault.inject import FaultInjectedError
            raise FaultInjectedError(
                f"injected decode-step failure "
                f"(TPUMX_FAULT_GEN_STEP_FAIL) at iteration "
                f"{self._iteration}, batch rids {sorted(rids)}")
        if batch and sampler:
            self._count_sampler_step(temperature, top_k, top_p)
        return _StepInputs(rows, int(w), tokens, positions, lengths, tables,
                           knobs)

    def _count_sampler_step(self, temperature, top_k, top_p) -> None:
        """Count one sampling program call by the body its sampler takes:
        the program decides from these same arrays, by the same
        function."""
        body = int(sampler_body(temperature, top_k, top_p,
                                self._model.vocab))
        self._counts[f"sampler_steps_{SAMPLER_BODIES[body]}"] += 1
        self._c_sampler_steps[body].inc()

    def _participated(self, r: _GenRequest, t0: float, t1: float,
                      running: int, iteration: Optional[int] = None,
                      **mode) -> None:
        """Orca attribution: the ONE shared decode step fans out a child
        participation span per active request, so each trace still shows
        every step that advanced it."""
        if r.trace is not None:
            _trace.record_event(
                "serving.decode.participate", "serving", t0, t1,
                ctx=r.trace, traced=True,
                args={"rid": r.rid,
                      "iteration": (self._iteration if iteration is None
                                    else iteration),
                      "running": running, **mode,
                      "replica": self._replica_id})

    def _single_step(self, batch: List[_GenRequest],
                     ahead: bool = False) -> None:
        """The classic one-token decode program (T=1, one sampled token
        per running row).  With a step in flight this one is built from
        counts alone — a row of that step sits one position further and
        takes its token from the device (``carry_tokens``), a row that
        joins from its prefill in this pass takes the token that left
        there (``place_first``), any other row that joins brings its own
        from the host — and dispatched BEFORE that step's tokens, and
        those first tokens, are read and emitted.  With ``ahead`` the
        step stays in flight itself; otherwise it is read before
        returning (a retry, a bisection, a service that leaves nothing
        in flight: no first token is unread by then)."""
        last, firsts = self._flight, self._firsts
        lead = last.lead if last is not None else {}
        with self._phase("build", "serving.decode.build"):
            b = self._build_step(
                batch, 1, lambda r: [0 if r.rid in lead or r.rid in firsts
                                     else r.seq_tokens[r.ctx_len]],
                lead=lead)
            tokens = b.tokens
            if last is not None or firsts:
                # whatever run() returned for the last step is what the
                # rows that continue are fed, as it is what they are
                # served; a row that joins from its prefill is fed what
                # that returned, put at its slot of the same array
                keep = _np.zeros(len(b.lengths), bool)
                prev = last.tokens if last is not None else self._no_tokens
                for i, r in b.rows:
                    first = firsts.get(r.rid)
                    keep[i] = first is not None or r.rid in lead
                    if first is not None:
                        prev = self._programs.place_first(prev, first.token,
                                                          i)
                tokens = self._programs.carry_tokens(prev, tokens, keep)
        t_step0 = time.perf_counter()
        with self._phase("step", "serving.decode",
                         args={"running": len(batch), "width": b.width,
                               "iteration": self._iteration}):
            next_tok, _ = self._programs.run("gen_decode", self._cache,
                                             tokens, *b.operands[1:])
            step = _Flight(
                b, next_tok, t_step0, time.perf_counter(), self._iteration,
                {r.rid: 1 for _, r in b.rows}, self._programs.take_aux())
            reads = self._fly(step, ahead)
        for f, read in reads:
            self._emit_flight(f, read)
        self._land_firsts(ahead=True)

    def _fly(self, step: _Flight, ahead: bool) -> list:
        """What follows a step's dispatch, inside its span: count it, then
        the reads — the last step's, which the device has had a whole
        host iteration to finish (if it fails, that step stays the one
        in flight and this one, fed from it, is dropped), and without
        ``ahead`` this step's own, which otherwise stays in flight.
        Returns ``[(step, what was read of it)]``, to be emitted in that
        order."""
        last = self._flight
        self._counts["steps_drained" if last is None else "steps_ahead"] += 1
        reads = [] if last is None else [(last, self._read(last))]
        self._flight = step if ahead else None
        if not ahead:
            reads.append((step, self._read(step)))
        return reads

    def _read(self, f: _Flight):
        """Wait for a step and read what the host needs of it: its
        tokens, and with a block pass's ``unmasked`` the experts it
        touched, in one go."""
        if f.block is None:
            return _synced(f.tokens, waited=self._phase_us)
        return _synced(f.tokens, f.block.touched, waited=self._phase_us)

    def _count_aux(self, auxes) -> None:
        """Sum finished programs' counts into ``stats()["counts"]``: every
        one was dispatched before a step whose tokens have been read, so
        the read here waits for nothing."""
        import jax

        for aux in jax.device_get(auxes):       # one transfer for them all
            for name, value in aux.items():
                self._counts[name] += int(value)

    def _land(self) -> None:
        """Read and emit the step in flight, if there is one, and the
        first tokens that prefills left on the device: whatever needs
        the values on the host or the rows at rest calls this first.  A
        read that fails costs no token: the rows keep the host's view,
        and the next step feeds them from it again."""
        f, self._flight = self._flight, None
        if f is not None:
            try:
                with self._phase("step", "serving.decode" if f.block is None
                                 else "serving.block_step",
                                 args={"iteration": f.iteration}):
                    read = self._read(f)
            except Exception as exc:  # noqa: BLE001 — the device's error
                self._note_step_failure(exc)
            else:
                self._emit_flight(f, read)
        self._land_firsts()

    def _emit_flight(self, f: _Flight, read) -> None:
        """Emit what a step gave its rows: a one-token step's tokens, a
        block pass's unmasked positions and commits.  A row that ended
        since the step was dispatched (an end-of-sequence id found a
        step late) or was cancelled takes nothing: its token is dropped,
        and its K/V at ``ctx_len`` is past what the prefix index is ever
        shown."""
        if f.block is not None:
            self._emit_block(f, *read)
            return
        next_tok = read
        with self._phase("emit", "serving.emit"):
            self._count_aux(f.aux)
            traced = _trace.enabled()
            for i, r in f.step.rows:
                if r.state != _RUNNING or r.cancel_requested:
                    continue
                r.decode_steps += 1
                if traced:
                    self._participated(r, f.t0, f.t1, len(f.step.rows),
                                       iteration=f.iteration)
                r.ctx_len += 1
                r.mode_tokens["single"] = r.mode_tokens.get("single", 0) + 1
                self._emit_token(r, int(next_tok[i]))

    def _propose_drafts(self, batch: List[_GenRequest]) -> Dict[int, List[int]]:
        """Draft proposals per request id (possibly empty lists).  Each
        row's proposal count is capped at ``remaining - 1`` so the verify
        emit (``accepted + 1`` tokens) can never overshoot ``max_new`` —
        which also keeps every verify write inside the request's
        worst-case block reservation."""
        cfg = self._config
        out: Dict[int, List[int]] = {}
        rids = {r.rid for r in batch if r.state == _RUNNING}
        if self._draft is not None:
            S = cfg.max_slots
            w = self._draft.window
            window = _np.zeros((S, w), _np.int32)
            positions = _np.zeros((S, w), _np.int32)
            n_valid = _np.zeros(S, _np.int32)
            live = []
            for i, r in enumerate(self._slots):
                if r is None or r.state != _RUNNING or r.rid not in rids:
                    continue
                n = min(r.ctx_len + 1, w)
                window[i, w - n:] = r.seq_tokens[
                    r.ctx_len + 1 - n:r.ctx_len + 1]
                positions[i] = _np.arange(r.ctx_len + 1 - w,
                                          r.ctx_len + 1, dtype=_np.int32)
                n_valid[i] = n
                live.append((i, r))
            if not live:
                return out
            props = self._draft.propose(window, positions, n_valid)
            for i, r in live:
                kmax = min(self._draft.k, r.max_new - r.n_generated - 1)
                out[r.rid] = [int(t) for t in props[i, :max(0, kmax)]]
            return out
        from .speculative import propose_ngram
        for r in batch:
            if r.state != _RUNNING or r.rid not in rids:
                continue
            kmax = min(cfg.draft_k, r.max_new - r.n_generated - 1)
            out[r.rid] = (propose_ngram(
                r.seq_tokens[:r.ctx_len + 1], kmax, cfg.draft_ngram)
                if kmax > 0 else [])
        return out

    def _emit_many(self, r: _GenRequest, toks: List[int]) -> int:
        """Emit consecutive tokens for one request; stops the moment a
        token finishes it (eos / max_new) — surplus verified tokens
        are simply discarded, exactly as if they were never
        computed.  Returns the number emitted."""
        n = 0
        for t in toks:
            if r.state != _RUNNING or self._killed:
                break
            r.ctx_len += 1
            self._emit_token(r, int(t))
            n += 1
        return n

    def _spec_step(self, batch: List[_GenRequest],
                   drafts: Dict[int, List[int]]) -> None:
        """One speculative iteration: feed ``[pending, d_1..d_s]`` per
        row through a single cache-aware multi-query verify step and emit
        the leading run of target-matching tokens (plus the bonus token).
        Rows with no drafts ride along at chunk length 1 — for them this
        IS the single-token step."""
        smax = max((len(drafts.get(r.rid, ())) for r in batch
                    if r.state == _RUNNING), default=0)
        tk = bucket_batch(smax + 1, self._verify_buckets)
        with self._phase("build", "serving.decode.build"):
            b = self._build_step(
                batch, tk,
                lambda r: [r.seq_tokens[r.ctx_len]] + drafts.get(r.rid, []))
        t_step0 = time.perf_counter()
        with self._phase("step", "serving.spec_verify",
                         args={"running": len(batch), "width": b.width,
                               "chunk": int(tk),
                               "iteration": self._iteration}):
            target, accepted = self._programs.run_verify(
                self._cache, *b.operands, waited=self._phase_us)
        t_step1 = time.perf_counter()
        with self._phase("emit", "serving.emit"):
            traced = _trace.enabled()
            bs = self._config.block_size
            quantized = self._cache.quantized
            for i, r in b.rows:
                s_i = int(b.lengths[i]) - 1  # drafts fed for this row
                n_emit = int(accepted[i]) + 1
                r.decode_steps += 1
                emitted = self._emit_many(
                    r, [int(t) for t in target[i, :n_emit]])
                acc = max(0, emitted - 1)
                r.draft_proposed += s_i
                r.draft_accepted += acc
                r.mode_tokens["spec"] = r.mode_tokens.get("spec", 0) + emitted
                self._counts["draft_proposed"] += s_i
                self._counts["draft_accepted"] += acc
                if s_i:
                    self._c_draft_proposed.inc(s_i)
                if acc:
                    self._c_draft_accepted.inc(acc)
                # int8 pool + partial rejection: the boundary block now holds
                # accepted entries requantized under a scale that saw the
                # rejected garbage — never index it for sharing (f32 pools
                # need no such cap: every write is position-exact)
                if quantized and s_i > acc and r.ctx_len % bs != 0:
                    safe = (r.ctx_len // bs) * bs
                    r.index_safe_len = (safe if r.index_safe_len is None
                                        else min(r.index_safe_len, safe))
                if traced:
                    self._participated(r, t_step0, t_step1, len(batch),
                                       mode="spec", proposed=s_i,
                                       accepted=acc)
        self._counts["spec_steps"] += 1
        self._counts["steps_drained"] += 1

    # -- generation by diffusion over blocks (docs/generation.md) -----------------
    def _open_block(self, r: _GenRequest) -> None:
        """A fresh block at ``r.ctx_len``: the tokens the sequence already
        holds there (a prompt's leftover, fewer than a block), MASK behind
        them."""
        L = self._block_len
        known = r.seq_tokens[r.ctx_len:r.ctx_len + L]
        n = L - len(known)
        r.block = known + [self._model.mask_id] * n
        r.block_masked = [False] * len(known) + [True] * n
        r.block_at = [-1] * L
        r.block_pass = 0

    def _block_prefill(self, r: _GenRequest) -> None:
        """Admission of a request of a block-diffusion model: the whole
        blocks of its context (the prompt's; after a preemption, all that
        was committed) go through the chunk plan into the cache under the
        block mask — offsets and lengths whole blocks, no logits, nothing
        emitted — and the tokens left over open the block in flight."""
        cfg = self._config
        L = self._block_len
        resumed = r.n_generated > 0
        ctx = r.ctx_len if r.ctx_len > 0 else (r.prompt_len // L) * L
        cached = min(r.cached_len, ctx)
        if cached >= ctx:
            plan = []
        elif cached > 0:
            plan = self._chunk_plan(ctx, start=cached)
        else:
            plan = self._chunk_plan(ctx, force_chunked=True)
        now = time.perf_counter()
        if r.trace is not None:
            _trace.record_event("gen.admit", "serving", r.seg_t0, now,
                                ctx=r.trace,
                                args={"rid": r.rid, "resumed": resumed,
                                      "blocks": len(r.blocks or ()),
                                      "cached": cached,
                                      "replica": self._replica_id})
        if cached > 0:
            r.seg("prefix_reuse", now)
            now = time.perf_counter()
        r.seg("prefill", now)
        for (off, take, tb, wp) in plan:
            tokens, positions, table = self._chunk_inputs(r, off, take, tb,
                                                          wp)
            t_rung0 = time.perf_counter()
            with self._phase("step", "serving.prefill",
                             args={"rid": r.rid, "len": ctx, "bucket": tb,
                                   "off": off, "chunks": len(plan),
                                   "resumed": resumed}, ctx=r.trace):
                self._pass_chunks += 1
                self._programs.run_fill(self._cache, tokens, positions,
                                        _np.asarray([take], _np.int32),
                                        table)
            r.rung_s[tb] = r.rung_s.get(tb, 0.0) \
                + (time.perf_counter() - t_rung0)
        # counted where the next block pass is, when that has been read
        self._prefill_uncounted[0] += len(plan)
        self._prefill_uncounted[1] += sum(p[1] for p in plan)
        r.seg("decode", time.perf_counter())
        if self._prefix is not None and not resumed and ctx > 0:
            self._prefix.insert(r.seq_tokens[:ctx], r.blocks)
        r.ctx_len = ctx
        self._open_block(r)

    def _block_step(self, batch: List[_GenRequest],
                    ahead: bool = False) -> None:
        """One pass of generation by diffusion over blocks, sibling of
        :meth:`_spec_step`: every running row feeds its block of ``L``
        token ids at ``ctx_len .. ctx_len + L - 1`` (K/V written there,
        an earlier pass's overwritten; copy-on-write over the span as the
        verify step does).  A row whose block holds MASK is on a denoise
        pass: the program unmasks its most confident masked positions.  A
        row with none left is on its commit pass — the same program, so
        rows in different passes share one batch — after which the block's
        K/V are those of its finished tokens: ``ctx_len`` moves a block
        on, a fresh block opens.

        Like :meth:`_single_step` the pass is built from counts alone
        and dispatched BEFORE the pass in flight is read: a denoise pass
        unmasks ``min(n_unmask, MASKs left)`` positions whatever the
        logits, so where a row of that pass stands after it — which pass
        of which block, how many MASKs — is known, and WHICH positions it
        unmasked with WHICH ids stays on the device: a row that
        continues its block takes its tokens and flags from there
        (``carry_block``), a row that opens a block (after its commit
        pass, or on joining) brings them from the host.  With ``ahead``
        the pass stays in flight itself; otherwise it is read before
        returning.  The values — the block's tokens to emit, the pass
        that unmasked each, an end-of-sequence id — reach the host when
        the pass lands (:meth:`_emit_block`)."""
        S, L = self._config.max_slots, self._block_len
        schedule = self._model.unmask_schedule
        last = self._flight
        lead = last.lead if last is not None else {}
        with self._phase("build", "serving.block.build"):
            fresh = [self._model.mask_id] * L
            b = self._build_step(
                batch, L, lambda r: fresh if r.rid in lead else r.block,
                sampler=False, lead=lead)
            masked = _np.zeros((S, L), bool)
            n_unmask = _np.zeros(S, _np.int32)
            keep = _np.zeros(S, bool)
            after, moves = {}, {}
            for i, r in b.rows:
                if r.rid in lead:
                    # past the pass in flight: on in its block, whose
                    # state the device has, or at a fresh one, all MASK
                    at, left = last.block.after[r.rid]
                    keep[i] = not lead[r.rid]
                    masked[i] = True
                else:
                    at, left = r.block_pass, sum(r.block_masked)
                    masked[i] = r.block_masked
                if left:
                    n_unmask[i] = n = schedule[min(at, len(schedule) - 1)]
                    after[r.rid] = (at + 1, left - min(n, left))
                    moves[r.rid] = 0
                else:
                    after[r.rid] = (0, L)
                    moves[r.rid] = L
            tokens = b.tokens
            if last is not None:
                tokens, masked = self._programs.carry_block(
                    last.tokens, last.block.tokens, last.block.masked,
                    tokens, masked, keep)
        t_step0 = time.perf_counter()
        with self._phase("step", "serving.block_step",
                         args={"running": len(b.rows), "width": b.width,
                               "iteration": self._iteration,
                               "ahead": last is not None}):
            # (positional: a wrapper around run_block hands it through)
            unmasked, touched, _ = self._programs.run_block(
                self._cache, tokens, *b.operands[1:], masked, n_unmask,
                False)
            prefill, self._prefill_uncounted = \
                tuple(self._prefill_uncounted), [0, 0]
            reads = self._fly(_Flight(
                b, unmasked, t_step0, time.perf_counter(), self._iteration,
                moves, (), _BlockPass(tokens, masked, touched, after,
                                      prefill)), ahead)
        for f, read in reads:
            self._emit_flight(f, read)

    def _emit_block(self, f: _Flight, unmasked, touched) -> None:
        """A block pass has been read: fill in what needed its values.
        A row on a denoise pass takes the ids of the positions it
        unmasked and the pass that unmasked them; a row on its commit
        pass emits its block.  Every ``block_*`` count of the pass (and
        of the prefill chunks dispatched before it) is made here, at one
        point of its life, so that a reader of ``stats()`` finds them
        belonging together."""
        with self._phase("emit", "serving.emit"):
            counts = self._counts
            rows = f.step.rows
            unmasked = unmasked.tolist()
            for i, r in rows:
                if r.state != _RUNNING or r.cancel_requested:
                    continue
                r.decode_steps += 1
                if f.lead[r.rid]:
                    counts["block_commit_row_passes"] += 1
                    self._commit_block(r)
                    continue
                for j, tok in enumerate(unmasked[i]):
                    if tok >= 0 and r.block_masked[j]:
                        r.block[j] = tok
                        r.block_masked[j] = False
                        r.block_at[j] = r.block_pass
                r.block_pass += 1
            counts["block_passes"] += 1
            counts["block_row_passes"] += len(rows)
            counts["block_ctx_tokens"] += \
                int(f.step.positions[:, -1].sum()) + len(rows)
            counts["block_experts_touched"] += int(touched)
            counts["block_prefill_chunks"] += f.block.prefill[0]
            counts["prefill_chunks"] += f.block.prefill[0]
            counts["prefill_tokens"] += f.block.prefill[1]

    def _commit_block(self, r: _GenRequest) -> None:
        """After a row's commit pass: emit the block's tokens the
        sequence does not hold yet (cut after an end-of-sequence id or at
        ``max_new_tokens``), move the context a block on and open the
        next block."""
        L = self._block_len
        ctx0 = r.ctx_len
        have = len(r.seq_tokens) - ctx0     # a prompt's leftover
        n = self._emit_many(r, r.block[have:])
        r.unmask_pass.extend(r.block_at[have:have + n])
        r.mode_tokens["block"] = r.mode_tokens.get("block", 0) + n
        self._counts["block_tokens_committed"] += n
        if r.state == _RUNNING:
            r.ctx_len = ctx0 + L
            self._open_block(r)
        else:
            # finished inside the block: what the sequence holds is what
            # the prefix index may see (full pages of it are whole blocks)
            r.ctx_len = len(r.seq_tokens)

    # -- failure isolation (docs/fault_tolerance.md serving rows) -----------------
    def _note_step_failure(self, exc: BaseException) -> None:
        self._counts["step_failures"] += 1
        self._consec_step_failures += 1
        self._c_step_fail.inc()

    def _decode_isolated(self, running: List[_GenRequest]) -> None:
        """Decode with bounded blast radius: run the full batch; on
        failure retry once (transient faults recover with zero client
        impact), then bisect so only the poisoned request is quarantined
        while every healthy slot still advances this iteration."""
        for attempt in (0, 1):
            try:
                # only the first attempt may leave its step in flight
                self._decode_step(running, ahead=attempt == 0)
                self._consec_step_failures = 0
                return
            except Exception as exc:  # noqa: BLE001 — isolate below
                self._note_step_failure(exc)
                # the retry and the bisection run with nothing in flight:
                # the step before the failed one is read and emitted
                # first, so no token of it is lost
                self._land()
                for r in running:  # attributed per request (wide event)
                    r.n_retries += 1
        self._bisect_decode(running)

    def _bisect_decode(self, group: List[_GenRequest],
                       cause: Optional[BaseException] = None) -> None:
        group = [r for r in group if r.state == _RUNNING]
        if not group:
            return
        if len(group) == 1:
            r = group[0]
            quarantined = False
            with self._lock:
                for i, s in enumerate(self._slots):
                    if s is r and r.state == _RUNNING:
                        self._counts["quarantined"] += 1
                        self._c_quarantine.inc()
                        self._release_slot_locked(
                            i, error=GenerationStepError(
                                f"request {r.rid} quarantined: decode step "
                                f"fails whenever it is scheduled "
                                f"(last error: {cause!r})"))
                        quarantined = True
                        break
            if quarantined:
                # postmortems start from data: the black box carries the
                # quarantined request's wide event (docs/observability.md)
                _flight.dump("gen_quarantine", extra={
                    "rid": r.rid, "replica": self._replica_id,
                    "cause": repr(cause), "request": r.wide_event})
            return
        mid = len(group) // 2
        for half in (group[:mid], group[mid:]):
            try:
                self._decode_step(half)
                self._consec_step_failures = 0
            except Exception as exc:  # noqa: BLE001 — keep narrowing
                self._note_step_failure(exc)
                self._bisect_decode(half, exc)

    def _requeue_or_fail(self, r: _GenRequest, exc: BaseException) -> None:
        """Blast-radius containment for one request (prefill error or an
        iteration error that never touched it): requeue it — bounded by
        the error-requeue budget — instead of failing it."""
        err = exc if isinstance(exc, ServingError) else ServingError(
            f"generation step failed: {exc!r}")
        failed = False
        with self._lock:
            for i, s in enumerate(self._slots):
                if s is r:
                    if r.n_requeues < self._max_error_requeues:
                        self._preempt_slot_locked(i, counter="requeued")
                    else:
                        self._release_slot_locked(
                            i, error=GenerationStepError(
                                f"request {r.rid} failed after "
                                f"{r.n_requeues} error requeues: {err}"))
                        failed = True
                    break
        if failed:
            _flight.dump("gen_requeue_budget", extra={
                "rid": r.rid, "replica": self._replica_id,
                "cause": repr(exc), "request": r.wide_event})

    def _absorb_iteration_error(self, exc: BaseException,
                                progress: Dict[int, int]) -> None:
        """An iteration blew up outside the isolated decode path: requests
        the failing iteration advanced keep their slots and keep decoding;
        untouched ones are requeued (bounded), never failed — the step-
        exception blast radius stays at zero healthy casualties."""
        for r in list(self._slots):
            if r is None or r.state != _RUNNING:
                continue
            touched = r.n_generated != progress.get(r.rid, r.n_generated)
            if not touched:
                self._requeue_or_fail(r, exc)

    def _emit_token(self, r: _GenRequest, tok: int) -> None:
        if self._killed:
            return  # a dead replica leaks nothing: the router may already
            #         have resubmitted this request elsewhere
        now = time.perf_counter()
        r.seq_tokens.append(tok)
        r.n_generated += 1
        if len(r.token_log) < 4096:
            r.token_log.append(now)
        if r.t_first is None:
            r.t_first = now
            # snapshot the lifetime partition AT the first token: these
            # components sum exactly to measured TTFT (the wide event's
            # ttft_breakdown_ms, docs/observability.md)
            r.seg(r.seg_state, now)
            r.breakdown_first = dict(r.breakdown)
            ttft = now - r.t_submit
            self._ttft.append(ttft)
            self._h_ttft.observe(ttft)
        else:
            itl = now - r.t_last
            self._itl.append(itl)
            self._h_itl.observe(itl)
        r.t_last = now
        self._token_times.append(now)
        self._counts["tokens"] += 1
        self._c_tokens.inc()
        r.out_queue.put(("tok", tok))
        if r.on_token is not None:
            try:
                r.on_token(r.rid, tok)
            except Exception:  # callbacks must not kill the engine
                pass
        if r.eos_token is not None and tok == r.eos_token:
            r.state = _FINISHED
            r.finish_reason = "eos"
            self._counts["finished"] += 1
        elif r.n_generated >= r.max_new:
            r.state = _FINISHED
            r.finish_reason = "max_new_tokens"
            self._counts["finished"] += 1

    # -- introspection ------------------------------------------------------------
    def _live_blocks_locked(self) -> int:
        """Blocks holding WRITTEN context across the running slots (owned
        blocks minus reservation/growth headroom)."""
        return sum(self._cache.blocks_for(r.ctx_len)
                   for r in self._slots
                   if r is not None and r.ctx_len > 0)

    def live_occupancy(self) -> float:
        """Fraction of the allocatable pool holding written KV context —
        unlike ``allocator.occupancy()`` (owned blocks), reservation and
        growth headroom do not count (``stats()["kv_blocks"]
        ["live_occupancy"]`` and its gauge)."""
        total = self._cache.num_blocks - 1
        with self._lock:
            live = self._live_blocks_locked()
        return live / total if total else 0.0

    def _update_gauges_locked(self) -> None:
        alloc = self._cache.allocator
        total = self._cache.num_blocks - 1
        running = sum(1 for r in self._slots if r is not None)
        self._g_running.set(running)
        self._g_waiting.set(len(self._waiting))
        self._g_blocks_used.set(alloc.num_used)
        self._g_blocks_free.set(alloc.num_free)
        self._g_live_occupancy.set(
            self._live_blocks_locked() / total if total else 0.0)
        if self._prefix is not None:
            self._g_blocks_shared.set(alloc.num_shared)
            self._g_pc_blocks.set(self._prefix.num_blocks)
            ev = self._prefix.evictions
            if ev > self._pc_evictions_seen:
                self._c_pc_evict.inc(ev - self._pc_evictions_seen)
                self._pc_evictions_seen = ev
            self._counts["prefix_evictions"] = ev
        for kind, gauge in zip(self._cache.kinds, self._g_kind_blocks):
            gauge.set(kind.allocator.num_used)
        occ = alloc.occupancy()
        self._peak_occupancy = max(self._peak_occupancy, occ)
        self._g_occupancy.set(occ)
        if self._iteration % 64 == 0:
            # periodic metric deltas into the flight recorder's note ring:
            # a dead replica's dump shows how its load evolved, not just
            # its final snapshot
            _flight.note("gen_metrics", {
                "replica": self._replica_id, "iteration": self._iteration,
                "running": running, "waiting": len(self._waiting),
                "occupancy": round(occ, 4),
                "tokens": self._counts["tokens"],
                "preempted": self._counts["preempted"],
                "step_failures": self._counts["step_failures"]})
        now = time.perf_counter()
        while self._token_times and \
                now - self._token_times[0] > self._TPS_WINDOW:
            self._token_times.popleft()
        self._g_tps.set(len(self._token_times) / self._TPS_WINDOW)

    def membership_history(self) -> List[Tuple[int, Tuple[int, ...]]]:
        """Per-iteration decode-batch membership ``(iteration, sorted
        request ids)`` — the observable form of iteration-level
        scheduling (tests assert a short request leaves and a queued one
        joins while a long one keeps decoding)."""
        return list(self._membership)

    def compile_stats(self) -> Dict[tuple, Dict[str, int]]:
        """Per-program-signature hit/miss counters (1 miss each after a
        covering :meth:`warmup`)."""
        return self._programs.compile_stats()

    def stats(self) -> dict:
        from .. import metrics as _smetrics

        with self._lock:
            counts = dict(self._counts)
            waiting = len(self._waiting)
            running = sum(1 for r in self._slots if r is not None)
            ttft = list(self._ttft)
            itl = list(self._itl)
        alloc = self._cache.allocator
        for k in self._cache.kinds:
            if k.state:
                # two gauges among the counts (docs/observability.md): the
                # slots whose state a row owns now, and what one slot holds
                counts["state_slots_live"] = k.allocator.num_used
                counts["state_bytes_per_slot"] = sum(
                    int(p.nbytes) for p in self._cache.pools[k.span]) \
                    // k.num_blocks
        # the loop's clocks, whole microseconds each: ``phase_ms`` and the
        # counts are two views of the same accumulators
        phase_us = {k: int(v) for k, v in dict(self._phase_us).items()}
        gc_us = int(self._gc.pause_us)
        alone, admitting = (int(self._iter_us[k])
                            for k in ("decode_only", "admitting"))
        counts.update({f"phase_us_{k}": v for k, v in phase_us.items()},
                      gc_pause_us=gc_us,
                      gc_collections_gen2=self._gc.collections[2],
                      iter_us=alone + admitting, iter_us_decode_only=alone,
                      iter_us_admitting=admitting,
                      iters_decode_only=self._iters_decode_only)
        pct = _smetrics.percentile
        return {
            "running": running,
            "waiting": waiting,
            "iterations": self._iteration,
            "phase_ms": {**{k: v / 1e3 for k, v in phase_us.items()},
                         "gc": gc_us / 1e3},
            "counts": counts,
            "kv_blocks": {
                "total": self._cache.num_blocks - 1,
                "used": alloc.num_used,
                "free": alloc.num_free,
                "shared": alloc.num_shared,
                "occupancy": round(alloc.occupancy(), 4),
                "live_occupancy": round(self.live_occupancy(), 4),
                "peak_occupancy": round(self._peak_occupancy, 4),
            },
            # every kind of the cache: the first is "kv_blocks" above
            "cache_kinds": {
                k.name: {"layers": k.n_layers, "window": k.window,
                         "total": k.num_blocks - 1,
                         "used": k.allocator.num_used,
                         "free": k.allocator.num_free}
                for k in self._cache.kinds},
            "prefix_cache": (None if self._prefix is None else {
                "blocks": self._prefix.num_blocks,
                "hits": counts["prefix_hits"],
                "misses": counts["prefix_misses"],
                "cached_tokens": counts["cached_tokens"],
                "prefill_tokens": counts["prefill_tokens"],
                "cow_copies": counts["cow_copies"],
                "evictions": self._prefix.evictions,
                "evict_walks": self._prefix.evict_walks,
                "evict_scanned": self._prefix.evict_scanned,
            }),
            "ttft_ms": {"p50": _ms(pct(ttft, 50)), "p99": _ms(pct(ttft, 99))},
            "inter_token_ms": {"p50": _ms(pct(itl, 50)),
                               "p99": _ms(pct(itl, 99))},
            "decode_mode": ("block" if self._block_len else
                            "spec" if self._config.speculative
                            else "single"),
            "block_diffusion": (None if not self._block_len else {
                "block_length": self._block_len,
                "passes": counts["block_passes"],
                "row_passes": counts["block_row_passes"],
                "commit_row_passes": counts["block_commit_row_passes"],
                "tokens_committed": counts["block_tokens_committed"],
                "experts_touched": counts["block_experts_touched"],
                "ctx_tokens": counts["block_ctx_tokens"],
                "prefill_chunks": counts["block_prefill_chunks"],
            }),
            "speculative": (None if not self._config.speculative else {
                "draft_mode": self._config.draft_mode,
                "draft_k": self._config.draft_k,
                "proposed_tokens": counts["draft_proposed"],
                "accepted_tokens": counts["draft_accepted"],
                "accepted_ratio": (
                    None if counts["draft_proposed"] == 0 else
                    round(counts["draft_accepted"]
                          / counts["draft_proposed"], 4)),
                "mean_accepted_len": (
                    None if counts["spec_steps"] == 0 else
                    round(counts["draft_accepted"]
                          / counts["spec_steps"], 4)),
                "spec_steps": counts["spec_steps"],
            }),
            "compiled_signatures": self._programs.compiled_signatures(),
            "decode_kernel": self._programs.kernel,
            "kv_dtype": self._config.kv_dtype or str(self._cache.dtype),
            "seq_buckets": list(self._seq_buckets),
            "width_buckets": list(self._width_buckets),
            "closed": self._closed,
            "killed": self._killed,
            "preemption": self._config.preemption,
            "watermarks": {"high": self._config.watermark_high,
                           "low": self._config.watermark_low},
            "consecutive_step_failures": self._consec_step_failures,
        }


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds * 1e3, 3)

"""mxnet_tpu.observability — the unified runtime observability subsystem.

The reference treats observability as a first-class subsystem: a 2,211-LoC
profiler with per-device stats, a ``ProfileOperator`` around every engine
op, aggregate tables, and a memory profiler behind 20+ C APIs (SURVEY.md
§5.1).  tpu-mx's answer is this package, wired through executor, module,
kvstore, io, amp and serving:

- :mod:`.metrics` — a process-wide, thread-safe labeled metrics registry
  (Counter/Gauge/Histogram with fixed buckets + reservoir percentiles),
  JSON :func:`snapshot` and Prometheus text exposition
  (:func:`dump_prometheus`, :mod:`.exposition` HTTP endpoint);
- :mod:`.tracing` — nested :class:`span`s that emit into the profiler's
  chrome-trace stream AND ``jax.profiler.TraceAnnotation``, lining host
  spans up with device traces on one perfetto timeline; plus the
  trace-context layer (``TraceContext`` ids propagated thread-locally and
  handed off explicitly across queue/thread/replica boundaries) and the
  per-request **wide-event** records behind :func:`recent_requests`;
- :mod:`.gc_watch` — the interpreter's collector on the loops' timeline:
  while a :class:`GcWatch` is open (an engine loop, a ``Module.fit`` call)
  every collection is a span (``serving.gc``, ``fit.gc``) and its pause a
  counter;
- :mod:`.flight_recorder` — the crash black box: bounded rings of recent
  spans/wide events/notes that dump to a timestamped JSON file on crash,
  SIGTERM, decode-step quarantine, and circuit-breaker open;
- :mod:`.recompile` — the compile-cache explainer/watchdog
  (``TPUMX_EXPLAIN_RECOMPILES=1`` logs human-readable miss causes;
  ``TPUMX_FREEZE_COMPILES=1`` + :func:`mark_warm` makes any post-warmup
  miss raise);
- :mod:`.device_scopes` — which part of the program a device operation
  belongs to: the resolver over the ``jax.named_scope`` names the program
  gives its device work (the device section of ``mx.profiler.dumps()``);
- :mod:`.telemetry` — grad/param norms, step loss, loss scale and
  nonfinite/skip counts computed inside the donated fused train step and
  fetched only every ``TPUMX_TELEMETRY_EVERY`` steps
  (``TPUMX_TELEMETRY=0`` keeps fused programs byte-identical).

One registry serves the whole process: ``observability.snapshot()`` shows
serving p50/p99/QPS next to train grad-norm/loss-scale/step-time, and
``dump_prometheus(path)`` / ``exposition.start_http_server`` expose the
same numbers to a scraper (docs/observability.md).
"""
from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_BUCKETS)
from .tracing import (span, current_span, span_stack, TraceContext,
                      new_trace, current_trace, use_context,
                      recent_requests, recent_spans)
from .gc_watch import GcWatch
from .recompile import (FreezeCompilesError, explain_key_diff,
                        last_explanations, mark_warm)
from . import device_scopes
from . import exposition
from . import flight_recorder
from . import gc_watch
from . import metrics
from . import recompile
from . import telemetry
from . import tracing

__all__ = ["registry", "snapshot", "to_prometheus", "dump_prometheus",
           "reset", "span", "current_span", "span_stack", "mark_warm",
           "TraceContext", "new_trace", "current_trace", "use_context",
           "recent_requests", "recent_spans", "GcWatch",
           "last_explanations", "explain_key_diff", "FreezeCompilesError",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "DEFAULT_BUCKETS", "device_scopes", "metrics", "tracing",
           "recompile",
           "telemetry", "exposition", "flight_recorder", "gc_watch"]

#: the process-wide default registry every subsystem records into
_default_registry = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-wide default :class:`MetricsRegistry`."""
    return _default_registry


def snapshot() -> dict:
    """One JSON-safe dict of every metric in the default registry."""
    return _default_registry.snapshot()


def to_prometheus() -> str:
    """Prometheus text exposition (format 0.0.4) of the default registry."""
    return _default_registry.to_prometheus()


def dump_prometheus(path: str) -> None:
    """Write the default registry's exposition text to ``path``."""
    _default_registry.dump_prometheus(path)


def reset() -> None:
    """Clear the default registry AND the recompile explainer state
    (tests/bench isolation).  Trace/wide-event rings and the flight
    recorder's note ring have their own ``clear()``s — a metrics reset
    must not erase the black box a postmortem is about to dump."""
    _default_registry.reset()
    recompile.reset()

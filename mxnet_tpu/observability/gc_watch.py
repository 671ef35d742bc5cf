"""The interpreter's collector on the loops' timeline: a collection's pause
as a span, and its microseconds as a counter.

A collection holds the interpreter whichever thread set it off, so to the
loop it stops — the engine's, ``Module.fit``'s — it is time in whatever span
that loop had open: 90-170 ms of ``serving.emit`` that emit never spent
(PERF.md section 6, PRs 45 and 49).  While a :class:`GcWatch` is open, ONE
``gc.callbacks`` hook for the process (counted by the watches open,
removed with the last) times every collection and

- adds it to process-wide totals, of which a watch reads its own share:
  ``pause_us`` and ``collections`` (by generation) since it opened —
  ``GenerationService.stats()`` hands them on as ``phase_ms["gc"]``,
  ``counts["gc_pause_us"]`` and ``counts["gc_collections_gen2"]``;
- while ``mx.profiler`` runs, writes it as a span under the watch's name
  (``serving.gc``, ``fit.gc``: a prefix the benchmark's reducer keeps),
  on the thread the collection ran on: a ``jax.profiler.TraceAnnotation``,
  so that a device-idle gap under it is the collector's and not the
  enclosing span's, and a chrome-trace event with ``args.generation`` and
  ``args.collected``.

The hook takes no lock.  A collection can start on a thread that holds the
engine's lock, the span ring's or the profiler's, none of them reentrant,
so it stays out of :func:`tracing.recent_spans` and appends to the
profiler's event list itself; and one collection runs at a time, so its
plain adds have one writer.
"""
from __future__ import annotations

import gc
import os
import threading
import time
from typing import Dict, List, Optional

from .. import profiler as _profiler

__all__ = ["GcWatch", "watchers"]

_install_lock = threading.Lock()
#: span name -> the watches open under it; the hook is in ``gc.callbacks``
#: exactly while this holds a name
_names: Dict[str, int] = {}
#: the process's totals: microseconds paused, then collections by generation
_totals: List[float] = [0.0, 0, 0, 0]
#: ``(t0_us, [(name, annotation or None)])`` of the collection under way
_under_way: Optional[tuple] = None


def _hook(phase: str, info: dict) -> None:
    global _under_way
    if phase == "start":
        spans = []
        if _profiler._state["running"]:
            for name in tuple(_names):
                try:
                    import jax

                    ann = jax.profiler.TraceAnnotation(name)
                    ann.__enter__()
                except Exception:  # no jax profiler here: host-only
                    ann = None
                spans.append((name, ann))
        _under_way = (time.perf_counter() * 1e6, spans)
        return
    t1 = time.perf_counter() * 1e6
    under_way, _under_way = _under_way, None
    if under_way is None:
        return          # installed while this collection ran
    t0, spans = under_way
    _totals[0] += t1 - t0
    _totals[1 + info["generation"]] += 1
    for name, ann in reversed(spans):
        if ann is not None:
            ann.__exit__(None, None, None)
        # (not ``_profiler._emit``: this collection may have started under
        # the profiler's lock, on this thread)
        _profiler._events.append({
            "ph": "X", "name": name, "cat": name.partition(".")[0],
            "pid": os.getpid(), "tid": threading.get_ident(), "ts": t0,
            "dur": t1 - t0,
            "args": {"generation": info["generation"],
                     "collected": info["collected"]}})


def watchers() -> int:
    """The watches open in this process (0: no hook in ``gc.callbacks``)."""
    return sum(_names.values())


class GcWatch:
    """``with GcWatch("fit.gc"):`` — while open, every collection is a span
    of that name and counts into this watch.  ``pause_us`` and
    ``collections`` read what the collector took of the time the watch has
    been open, over every time it was, and stay as they are once it is
    closed."""

    def __init__(self, name: str):
        self.name = name
        # the totals when it opened (None: closed), and its share of them
        # over the times it was open before
        self._base: Optional[list] = None
        self._closed: list = [0.0, 0, 0, 0]

    def open(self) -> None:
        with _install_lock:
            if self._base is not None:
                return
            if not _names:
                gc.callbacks.append(_hook)
            _names[self.name] = _names.get(self.name, 0) + 1
            self._base = list(_totals)

    def close(self) -> None:
        with _install_lock:
            if self._base is None:
                return
            self._closed, self._base = self._share(), None
            _names[self.name] -= 1
            if not _names[self.name]:
                del _names[self.name]
            if not _names:
                gc.callbacks.remove(_hook)

    def __enter__(self):
        self.open()
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def _share(self) -> list:
        base = self._base
        if base is None:
            return list(self._closed)
        return [c + t - b for c, t, b in zip(self._closed, _totals, base)]

    @property
    def pause_us(self) -> float:
        return self._share()[0]

    @property
    def collections(self) -> list:
        """Collections by generation ``[0, 1, 2]``."""
        return self._share()[1:]

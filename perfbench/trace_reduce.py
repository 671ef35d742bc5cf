"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, the table of device operations, and the idle gaps by what the host
was doing.  Reads the file with nothing but JAX
(``jax.profiler.ProfileData``).

The window is the interval of the host span ``perfbench.window`` that the
harness opens once the profiler runs and closes before it stops; every
event is clipped to it.  Device planes are ``/device:TPU:<n>``; their
operations are the events of the line ``XLA Ops``.  Host spans are the
``TraceAnnotation`` events the program's ``observability.span`` writes
while ``mx.profiler`` runs (``fit.batch``, ``serving.decode`` ...).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "perfbench.window"
SPAN_PREFIXES = ("fit.", "serving.", "executor.", "kvstore.", "perfbench.")
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"([.\-_]?\d+)?(\.remat\d*)?$")
_KIND = re.compile(r"kind=(k\w+)")


@dataclass
class Trace:
    window: tuple                      # (start_ns, end_ns)
    devices: dict = field(default_factory=dict)   # plane -> [(s, e, name)]
    spans: list = field(default_factory=list)     # [(s, e, name)] host


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, device_prefix="/device:TPU:"):
    """Read planes into a :class:`Trace`, clipped to the window span."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, spans, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = list(plane.lines)
            picked = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for ln in picked for e in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns, e.name))
    return clip(Trace(window, devices, spans))


def clip(trace):
    """Cut every event to the window (all events' extent without one)."""
    if trace.window is None:
        every = [ev for evs in trace.devices.values() for ev in evs] \
            + trace.spans
        if not every:
            raise ValueError("trace holds no device operation and no span")
        trace.window = (min(e[0] for e in every), max(e[1] for e in every))
    w0, w1 = trace.window

    def cut(evs):
        return sorted((max(s, w0), min(e, w1), n) for s, e, n in evs
                      if e > w0 and s < w1)

    trace.devices = {k: cut(v) for k, v in trace.devices.items()}
    trace.spans = cut(trace.spans)
    return trace


def union(intervals):
    """Merge ``[(start, end), ...]`` (sorted by start) into disjoint ones."""
    out = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_group(name):
    """One row per kind of operation.  A TPU event is named by its whole
    HLO text, ``%fusion.12 = (...) fusion(...), kind=kOutput, ...``: the
    row is the result's name without its number (and without the
    ``.remat`` of a rematerialised copy), and the fusion's kind
    (``kOutput`` is a fusion around a convolution or a matrix product)."""
    lhs, _, rest = name.partition(" = ")
    group = _SUFFIX.sub("", lhs.lstrip("%")) or lhs
    kind = _KIND.search(rest)
    return f"{group} {kind.group(1)}" if kind else group


class _SpanIndex:
    """Innermost host span at a time.  Spans that cover the whole window
    (``fit.epoch``) say nothing about a gap and are left out."""

    def __init__(self, spans, window):
        w0, w1 = window
        self.spans = sorted(s for s in spans
                            if s[1] - s[0] < 0.99 * (w1 - w0))
        self.starts = [s[0] for s in self.spans]
        self.reach, top = [], 0        # latest end among spans[:i + 1]
        for s in self.spans:
            top = max(top, s[1])
            self.reach.append(top)

    def at(self, t):
        best = None
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] >= t:
            s, e, n = self.spans[i]
            if e >= t and (best is None or e - s < best[0]):
                best = (e - s, n)
            i -= 1
        return best[1] if best else "outside any span"


def summarize(trace, top=10):
    """``busy_s`` (mean over the device planes), ``window_s``,
    ``device_ops`` ``[[group, seconds], ...]`` and ``idle_gaps``
    ``[[host span, seconds], ...]``, each the ``top`` largest, plus
    ``n_devices`` and the events' count."""
    w0, w1 = trace.window
    busy, ops, gaps, n_events = [], {}, {}, 0
    index = _SpanIndex(trace.spans, trace.window)
    for evs in trace.devices.values():
        n_events += len(evs)
        merged = union([(s, e) for s, e, _ in evs])
        busy.append(sum(e - s for s, e in merged))
        for s, e, n in evs:
            g = op_group(n)
            ops[g] = ops.get(g, 0) + (e - s)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = index.at((a + b) // 2)
                gaps[who] = gaps.get(who, 0) + (b - a)
    nd = max(1, len(trace.devices))

    def table(d):
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / nd / 1e9] for k, v in rows]

    return {"busy_s": sum(busy) / nd / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": table(ops), "idle_gaps": table(gaps),
            "n_devices": len(trace.devices), "n_events": n_events}


def op_seconds(trace, pattern):
    """Summed device seconds (mean over planes) of the operations whose
    name matches the regular expression ``pattern``, and their count."""
    rx = re.compile(pattern)
    total = count = 0
    for evs in trace.devices.values():
        for s, e, n in evs:
            if rx.search(n):
                total += e - s
                count += 1
    nd = max(1, len(trace.devices))
    return total / nd / 1e9, count


def span_counts(trace):
    """How many host spans of each name lie in the window."""
    out = {}
    for _, _, n in trace.spans:
        out[n] = out.get(n, 0) + 1
    return out

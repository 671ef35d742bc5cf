"""Device milliseconds, per unit of the counter ``per``, during which a
collective is the only thing the chip runs: the union of the intervals of
the events matching ``pattern`` (an ``all-reduce``, or the ``-done`` that
waits for one started earlier) less their overlap with every other
operation on the same chip, the mean over the chips.  What is left of a
collective's time is hidden under compute.  No such event: no metric."""
import re

from perfbench import trace_reduce


def reduce(params, src):
    if src.trace is None:
        return None
    rx = re.compile(params["pattern"])
    exposed, seen = 0, False
    for evs in src.trace.devices.values():
        mine = trace_reduce.union(sorted(
            (s, e) for s, e, n in evs if rx.search(n)))
        rest = trace_reduce.union(sorted(
            (s, e) for s, e, n in evs if not rx.search(n)))
        seen = seen or bool(mine)
        i = 0
        for s, e in mine:
            exposed += e - s
            while i < len(rest) and rest[i][1] <= s:
                i += 1
            j = i
            while j < len(rest) and rest[j][0] < e:
                exposed -= min(e, rest[j][1]) - max(s, rest[j][0])
                j += 1
    if not seen:
        return None
    per = src.counters.get(params["per"]) if params.get("per") else 1
    nd = max(1, len(src.trace.devices))
    return exposed / nd / 1e6 / per if per else None

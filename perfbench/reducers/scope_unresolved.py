"""Share of the device's busy time in the traced slice that the program's
resolver (``reducers/scope_ms.py``) could give to no scope: 100 x own time
of the events that resolve to nothing / own time of all events.  A program
without the resolver: no metric."""
from perfbench.reducers import scope_ms


def reduce(params, src):
    got = scope_ms.resolved(src)
    if got is None:
        return None
    events, _ = got
    busy = sum(ns for ns, _ in events)
    if not busy or not any(r is not None for _, r in events):
        return None
    return 100.0 * sum(ns for ns, r in events if r is None) / busy

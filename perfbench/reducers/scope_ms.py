"""Device milliseconds of the traced slice by the program's own scopes:
the summed own time (an event's duration less the events nested in it) of
the device events whose resolved ``kind/scope`` matches one of the regular
expressions ``scopes``, the mean over the chips, per unit of the counter
``per`` where given.  ``direction`` (``forward`` / ``backward``) keeps one
side of a training step.  ``share_of``: instead, 100 x that time over the
time of the events matching ``share_of`` (``"."``: all that resolved).

The program resolves its own events
(``mxnet_tpu.observability.device_scopes``, from the compiled text of the
programs that ran in the profiler's session: built here, after the window).
A program without the resolver (an older commit), or a trace in which
nothing resolves: no metric."""
import re

_last = (None, None)        # (the trace, what it resolved to)


def resolved(src):
    """``[(own ns, Resolved or None)]`` of every device event of the slice
    (all planes), ``planes``; None without a trace or a resolver."""
    global _last
    if src.trace is None or not src.trace.devices:
        return None
    if _last[0] is src.trace:
        return _last[1]
    try:
        from mxnet_tpu.observability import device_scopes
    except ImportError:
        return None
    table, out = device_scopes.table(), []
    for evs in src.trace.devices.values():
        if not evs:
            continue
        evs, own = zip(*device_scopes.self_times(evs))
        out += zip(own, table.resolve_stream([e[2] for e in evs]))
    got = (out, max(1, len(src.trace.devices)))
    _last = (src.trace, got)
    return got


def matching(events, patterns, direction=None):
    rx = [re.compile(p) for p in patterns]
    return sum(ns for ns, r in events if r is not None
               and (direction is None or r.direction == direction)
               and any(x.search(f"{r.kind}/{r.scope}") for x in rx))


def reduce(params, src):
    got = resolved(src)
    if got is None:
        return None
    events, planes = got
    if not any(r is not None for _, r in events):
        return None
    ns = matching(events, params["scopes"], params.get("direction"))
    if "share_of" in params:
        whole = matching(events, [params["share_of"]])
        return 100.0 * ns / whole if whole else None
    per = src.counters.get(params["per"]) if params.get("per") else 1
    return ns / planes / 1e6 / per if per else None

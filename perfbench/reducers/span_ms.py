"""Host milliseconds inside the program's own spans in the traced slice:
the summed duration of the host spans named in ``spans`` (each clipped to
the window), per unit of the counter ``per`` (``slice.steps``,
``slice.iterations``), or, without ``per``, per span.  ``percentile``
reads that percentile of the single spans' durations instead.  A program
that emits no such span (an older commit): no metric."""
from perfbench import harness


def reduce(params, src):
    if src.trace is None:
        return None
    names = set(params["spans"])
    ms = [(e - s) / 1e6 for s, e, n in src.trace.spans if n in names]
    if not ms:
        return None
    if "percentile" in params:
        return harness.percentile(ms, params["percentile"])
    per = src.counters.get(params["per"]) if params.get("per") else len(ms)
    return sum(ms) / per if per else None

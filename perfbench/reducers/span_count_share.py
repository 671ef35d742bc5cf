"""Share of host spans by count in the traced slice:
100 x spans named in ``num`` / spans named in ``den``."""
from perfbench import trace_reduce


def reduce(params, src):
    if src.trace is None:
        return None
    n = trace_reduce.span_counts(src.trace)
    den = sum(n.get(k, 0) for k in params["den"])
    if not den:
        return None
    return 100.0 * sum(n.get(k, 0) for k in params["num"]) / den

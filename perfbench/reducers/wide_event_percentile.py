"""A percentile over the program's wide events (one per request of the
window) of a field, or of one key of a field that is a dict.
params: ``field``, ``key`` (optional), ``percentile``; ``keys`` sums
several keys."""
from perfbench import harness


def reduce(params, src):
    values = []
    for ev in src.events:
        v = ev.get(params["field"])
        if isinstance(v, dict):
            keys = params.get("keys") or [params["key"]]
            v = sum(v.get(k, 0.0) for k in keys)
        if v is not None:
            values.append(float(v))
    return harness.percentile(values, params["percentile"])

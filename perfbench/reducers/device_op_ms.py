"""Summed device milliseconds of the operations whose name matches
``pattern`` in the traced slice, per step where ``per`` names a counter.
A later collective-time metric is this reducer with ``all-reduce``."""
from perfbench import trace_reduce


def reduce(params, src):
    if src.trace is None:
        return None
    seconds, count = trace_reduce.op_seconds(src.trace, params["pattern"])
    if not count:
        return None
    per = src.counters.get(params["per"]) if params.get("per") else 1
    return seconds * 1e3 / per if per else None

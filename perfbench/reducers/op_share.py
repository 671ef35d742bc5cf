"""Share of one group of device operations in another, by device time in
the traced slice: 100 x seconds of the operations that match ``num`` /
seconds of those that match ``den`` (regular expressions, searched in the
event's name).  No operation matches ``den``: no metric."""
from perfbench import trace_reduce


def reduce(params, src):
    if src.trace is None:
        return None
    den, count = trace_reduce.op_seconds(src.trace, params["den"])
    if not count or not den:
        return None
    return 100.0 * trace_reduce.op_seconds(src.trace, params["num"])[0] / den

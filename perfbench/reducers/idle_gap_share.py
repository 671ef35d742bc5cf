"""Share of the device's idle time in the traced slice that
``trace_reduce.summarize`` attributes to the ``owners`` named (host span
names, or ``outside any span``): 100 x their idle seconds / all idle
seconds.  A device that was never idle: no metric."""
from perfbench import trace_reduce


def reduce(params, src):
    if src.trace is None:
        return None
    gaps = trace_reduce.summarize(src.trace, top=10**6)["idle_gaps"]
    idle = sum(seconds for _, seconds in gaps)
    if not idle:
        return None
    owners = set(params["owners"])
    return 100.0 * sum(s for who, s in gaps if who in owners) / idle

"""Device busy milliseconds of the traced slice per step of it.
params: ``steps`` — the counter that holds the slice's steps."""


def reduce(params, src):
    steps = src.counters.get(params["steps"])
    if not src.summary or not steps:
        return None
    return src.summary["busy_s"] * 1e3 / steps

"""One counter over another, times ``scale``.
params: ``num``, ``den``, ``scale`` (default 1)."""


def reduce(params, src):
    num, den = src.counters.get(params["num"]), \
        src.counters.get(params["den"])
    if num is None or not den:
        return None
    return num / den * params.get("scale", 1)

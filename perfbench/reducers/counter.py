"""A counter the driver took over the window, times ``scale``.
params: ``counter``, ``scale`` (default 1)."""


def reduce(params, src):
    v = src.counters.get(params["counter"])
    return None if v is None else v * params.get("scale", 1)

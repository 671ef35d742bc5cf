"""A statistic over a list of seconds the benchmark's own wrapper timed,
in milliseconds.  params: ``timer``; ``percentile`` (default: the mean)."""
from perfbench import harness


def reduce(params, src):
    values = src.timers.get(params["timer"])
    if not values:
        return None
    if "percentile" in params:
        return harness.percentile(values, params["percentile"]) * 1e3
    return sum(values) / len(values) * 1e3

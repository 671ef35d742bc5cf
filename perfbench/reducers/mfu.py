"""Model FLOP/s utilisation: operations per unit of work from
perfbench/counts.py x units per second / (chips x peak).
params: ``count`` — a function of counts.py; ``count_args`` — keys of the
configuration/traffic passed to it; ``rate`` — the counter holding units
per second; ``peak`` (default ``bf16_flops_per_s``)."""
from perfbench import counts


def reduce(params, src):
    rate = src.counters.get(params["rate"])
    if rate is None or src.peaks is None:
        return None
    both = {**src.config, **src.traffic}
    flops = getattr(counts, params["count"])(
        **{k: both[v] for k, v in params.get("count_args", {}).items()})
    peak = src.peaks[params.get("peak", "bf16_flops_per_s")]
    return 100.0 * flops * rate / (src.counters.get("chips", 1) * peak)

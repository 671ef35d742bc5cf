"""A kernel's share of its roofline in the traced slice: the least time
the chip could take — ``bytes`` over peak bytes/s, or ``flops`` over peak
FLOP/s, whichever is larger — over the summed device time of the
operations that match ``pattern``.
params: ``pattern``; ``bytes`` and/or ``flops`` — counters the driver
filled from perfbench/counts.py for the work of the slice; ``flops_peak``
(default ``bf16_flops_per_s``).  Finds no such operation: no metric."""
from perfbench import trace_reduce


def reduce(params, src):
    if src.trace is None:
        return None
    seconds, count = trace_reduce.op_seconds(src.trace, params["pattern"])
    if not count or not seconds:
        return None
    least = 0.0
    if params.get("bytes") and src.counters.get(params["bytes"]):
        least = src.counters[params["bytes"]] / src.peaks["hbm_bytes_per_s"]
    if params.get("flops") and src.counters.get(params["flops"]):
        peak = src.peaks[params.get("flops_peak", "bf16_flops_per_s")]
        least = max(least, src.counters[params["flops"]] / peak)
    return 100.0 * least / seconds if least else None

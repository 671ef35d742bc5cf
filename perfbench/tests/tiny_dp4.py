"""The four-device training cell PERF.md section 7 specifies
(``resnet50-fit-dp4``), cut to a CPU's size, with the per-layer metrics it
would report: each a reducer and its parameters, as its ``metrics/*.json``
would hold them.  The cell itself waits for a driver whose reference
follows a batch of 1,024 (PERF.md section 7); the reducers and the
program's side are rehearsed here on four virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m perfbench.tests.tiny_dp4 <0|1>

prints the result line of one run (``--trace`` 0 or 1) as JSON."""
import importlib
import json
import sys

from perfbench import harness
from perfbench.tests import tiny

PER_STEP = {"per": "slice.steps"}
METRICS = {
    # collectives
    "dp4.allreduce_ms": ("device_op_ms", {"pattern": "all-reduce",
                                          **PER_STEP}),
    "dp4.allreduce_scope_ms": ("scope_ms", {
        "scopes": ["/kvstore\\.allreduce$"], **PER_STEP}),
    "dp4.allreduce_exposed_ms": ("collective_exposed_ms", {
        "pattern": "all-reduce", **PER_STEP}),
    # model step
    "dp4.conv_device_ms": ("scope_ms", {"scopes": ["/Convolution/"],
                                        **PER_STEP}),
    "dp4.bn_device_ms": ("scope_ms", {"scopes": ["/BatchNorm/"],
                                      **PER_STEP}),
    "dp4.update_device_ms": ("scope_ms", {"scopes": ["/optimizer\\.update$"],
                                          **PER_STEP}),
    "dp4.backward_share_pct": ("scope_ms", {
        "scopes": ["."], "direction": "backward", "share_of": "."}),
    "dp4.unscoped_pct": ("scope_unresolved", {}),
    # input, entry, device: spans that exist and no metric of a cell reads
    "dp4.input_span_ms": ("span_ms", {"spans": ["fit.input_wait"],
                                      **PER_STEP}),
    "dp4.dispatch_ms": ("span_ms", {
        "spans": ["executor.feed", "executor.fused_step"], **PER_STEP}),
    "dp4.sync_wait_ms": ("span_ms", {
        "spans": ["fit.update_metric", "fit.callbacks"], **PER_STEP}),
    "dp4.idle_unattributed_pct": ("idle_gap_share", {
        "owners": ["outside any span"]}),
    "dp4.device_idle_pct": ("device_idle", {}),
}
# every other scope of the step, so that the sum can be checked
REST = ("scope_ms", {"scopes": ["^(?!.*/(Convolution|BatchNorm)/)(?!.*/"
                                "(optimizer\\.update|kvstore\\.allreduce)$)"],
                     **PER_STEP})


def context(trace, seed=7, seconds=1.0, **hooks):
    ctx = tiny.fit_context(seed, seconds, trace, **hooks)
    ctx.workload = {"name": "resnet50-fit-dp4", "chips": 4}
    ctx.traffic.update(chips=4, contexts=4, batch=16)
    return ctx


def read(src, specs=None):
    """``{name: {"value": ...}}`` of ``specs`` (``METRICS`` and the rest)
    from a traced run's sources, as ``harness.result_line`` reads a cell's
    ``metrics/*.json``; a metric with nothing to read is left out."""
    out = {}
    for name, (reducer, params) in (specs or {**METRICS,
                                              "rest": REST}).items():
        value = importlib.import_module(
            "perfbench.reducers." + reducer).reduce(params, src)
        if value is not None:
            out[name] = {"value": float(value)}
    return out


def drive(ctx):
    """``tiny.drive`` with the cell's metrics read from ``METRICS`` (the
    cell is in no ``BENCHMARK.json`` yet)."""
    import jax

    harness.CompileClock.install()
    driver = importlib.import_module(
        "perfbench.drivers." + ctx.config["driver"])
    outcome = driver.run(ctx)
    line = harness.result_line(ctx, jax.devices(), outcome)
    if ctx.trace:
        src = outcome["sources"]
        line["metrics"].update(read(src))
        line["busy_ms_per_step"] = src.summary["busy_s"] * 1e3 \
            / src.counters["slice.steps"]
        from perfbench.reducers import scope_ms
        events, planes = scope_ms.resolved(src)
        line["own_ms_per_step"] = sum(ns for ns, _ in events) / planes \
            / 1e6 / src.counters["slice.steps"]
    return json.loads(json.dumps(line))


if __name__ == "__main__":
    print(json.dumps(drive(context(bool(int(sys.argv[1]))))))

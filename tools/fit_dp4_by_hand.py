"""By hand, PR 36: Module.fit over four contexts at the size PERF.md section 7
gives resnet50-fit-dp4, through the benchmark's own fit driver, with the one
thing the driver cannot do yet put in from outside: a reference that follows
the step as the program takes it (BatchNorm over a device's rows, gradients
averaged over the devices), one device's shard at a time so that it fits.

    chiprun --chips 4 --timeout 1800 -- python3 tools/fit_dp4_by_hand.py [tiny]
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))
TINY = len(sys.argv) > 1 and sys.argv[1] == "tiny"
os.environ["PERFBENCH_TRACE_KEEP"] = "1"

import numpy as np  # noqa: E402

from perfbench import harness  # noqa: E402
from perfbench.drivers import fit  # noqa: E402
from perfbench.reference import resnet as ref  # noqa: E402
from perfbench.tests import tiny_dp4  # noqa: E402

SHARDS = 4


def follow_sharded(params, batches, *, num_layers, lr, momentum,
                   dtype="float32"):
    """ref.follow with the step taken as four devices take it."""
    import jax
    import jax.numpy as jnp

    grad = jax.jit(jax.value_and_grad(ref.loss_fn), static_argnums=(3,))
    p0 = {k: np.asarray(v, np.float32) for k, v in params.items()}
    p = {k: jnp.asarray(v) for k, v in p0.items()}
    mom = {k: jnp.zeros_like(v) for k, v in p.items()}
    losses, grad1 = [], None
    with jax.default_matmul_precision("highest"):
        for i, (x, y) in enumerate(batches):
            n = len(x) // SHARDS
            total, loss = None, 0.0
            for s in range(SHARDS):
                l, g = grad(p, jnp.asarray(x[s * n:(s + 1) * n]),
                            jnp.asarray(y[s * n:(s + 1) * n], jnp.int32),
                            num_layers)
                loss += float(l) / SHARDS
                total = g if total is None else jax.tree_util.tree_map(
                    jnp.add, total, g)
            mom = {k: momentum * mom[k] - lr * total[k] / SHARDS for k in p}
            p = {k: p[k] + mom[k] for k in p}
            losses.append(loss)
            if i == 0:
                grad1 = {k: -np.asarray(v, np.float32) / lr
                         for k, v in mom.items()}
    delta = {k: np.asarray(p[k], np.float32) - p0[k] for k in p}
    return {"losses": losses, "grad1": grad1, "delta": delta}


def context(seed, seconds, trace):
    if TINY:
        return tiny_dp4.context(trace, seed, seconds)
    cfg = harness.load_json("configs", "resnet50.json")
    traffic = harness.load_json("traffic", "fit-b256.json")
    traffic.update(chips=4, contexts=4, batch=1024)
    return harness.Context({"name": "resnet50-fit-dp4", "chips": 4}, cfg,
                           traffic, seed, seconds, trace, time.perf_counter())


def one(seed, seconds, trace):
    import jax

    ctx = context(seed, seconds, trace)
    ctx.t_proc0 = time.perf_counter()
    out = fit.run(ctx)
    line = harness.result_line(ctx, jax.devices(), out)
    if trace:
        src = out["sources"]
        line["metrics"].update(tiny_dp4.read(src))
        fit5 = {}
        for name in ("fit.input_wait_ms", "fit.step_device_ms", "fit.mfu_pct",
                     "fit.compiles_in_window", "fit.device_idle_pct"):
            spec = harness.load_json("metrics", name + ".json")
            fit5[name] = (spec["reducer"], spec.get("params", {}))
        line["metrics"].update(tiny_dp4.read(src, fit5))
        line["spans"] = harness.trace_reduce.span_counts(src.trace)
    print("RESULT", json.dumps(line), flush=True)
    return line


def main():
    import jax

    fit.ref.follow = follow_sharded
    print("compile cache:", harness.enable_compile_cache(), flush=True)
    harness.CompileClock.install()
    print("devices:", jax.devices(), flush=True)
    seeds = (7, 8, 9) if TINY else (2147483611, 2147483612, 2147483613)
    secs = (1.0, 1.0, 1.0) if TINY else (51.0, 51.0, 30.0)
    one(seeds[0], secs[0], False)
    one(seeds[1], secs[1], False)
    one(seeds[2], secs[2], True)
    import scope_table

    if TINY:
        return
    scope_table.report("resnet50-fit-dp4",
                       keep=os.path.join(ROOT, "chiprun_out", "scopes",
                                         "resnet50-fit-dp4"))
    print("total seconds", time.perf_counter() - T0)


if __name__ == "__main__":
    main()

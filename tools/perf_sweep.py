"""One-shot perf sweep on the chip: runs the full matrix (layout x
fused-steps x BN-kernel), captures XLA cost analysis, and writes
/tmp/perf_sweep.json + a human summary.

Usage: python tools/perf_sweep.py [--quick]

The step construction intentionally mirrors bench.py's (bf16 cast,
log_softmax loss, momentum SGD, fold_in rng, donated carries) — if either
changes, change both, or the sweep stops measuring the reported path.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def build_step(layout, depth=50, side=224):
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.parallel.data_parallel import block_apply_fn

    import mxnet_tpu as mx

    ishape = (3, side, side) if layout == "NCHW" else (side, side, 3)
    net = gluon.model_zoo.vision.get_resnet(1, depth, classes=1000,
                                            layout=layout)
    net.initialize()
    # shape materialization runs eagerly op-by-op; pin it to the host CPU
    # backend (the timed jit program below transfers the params to the
    # chip on first call anyway)
    with mx.cpu():
        net(nd.array(np.zeros((1,) + ishape, np.float32)))
    apply_fn, params = block_apply_fn(net, is_train=True)

    def step(p, m, x, y, rng):
        def loss_of(q):
            qc = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), q)
            logits = apply_fn(qc, x.astype(jnp.bfloat16), rng).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

        loss, grads = jax.value_and_grad(loss_of)(p)
        m = jax.tree_util.tree_map(lambda mm, g: 0.9 * mm + g.astype(mm.dtype),
                                   m, grads)
        p = jax.tree_util.tree_map(lambda pp, mm: pp - 0.1 * mm, p, m)
        return loss, p, m

    return step, params, ishape


def measure(layout, K, bs, steps, depth=50, side=224):
    """Chained-args timing (every iteration depends on the previous one, so
    nothing can be cached/elided anywhere in the stack)."""
    step, params, ishape = build_step(layout, depth, side)
    rng0 = jax.random.PRNGKey(0)

    if K == 1:
        fn = jax.jit(step, donate_argnums=(0, 1))
    else:
        def multi(p, m, x, y, rng):
            def body(i, carry):
                pp, mm, _ = carry
                loss, pp, mm = step(pp, mm, x, y, jax.random.fold_in(rng, i))
                return (pp, mm, loss)

            p, m, loss = jax.lax.fori_loop(0, K, body,
                                           (p, m, jnp.float32(0)))
            return loss, p, m

        fn = jax.jit(multi, donate_argnums=(0, 1))

    x = jnp.asarray(np.random.rand(bs, *ishape).astype(np.float32))
    y = jnp.asarray(np.random.randint(0, 1000, (bs,)).astype(np.int32))
    p = jax.tree_util.tree_map(jnp.copy, params)
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    t0 = time.perf_counter()
    loss, p, m = fn(p, m, x, y, rng0)
    float(loss)
    compile_s = time.perf_counter() - t0
    reps = max(1, steps // K)
    t0 = time.perf_counter()
    for i in range(reps):
        loss, p, m = fn(p, m, x, y, jax.random.fold_in(rng0, i))
    float(loss)
    dt = time.perf_counter() - t0
    img_s = bs * K * reps / dt

    out = {"layout": layout, "K": K, "bs": bs, "img_per_sec": round(img_s, 1),
           "compile_s": round(compile_s, 1)}
    try:
        comp = fn.lower(p, m, x, y, rng0).compile()
        ca = comp.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        out["xla_flops"] = float(ca.get("flops", float("nan")))
        mem = comp.memory_analysis()
        out["temp_gb"] = round(mem.temp_size_in_bytes / 1e9, 2)
    except Exception as e:  # lower-after-donate can refuse; non-fatal
        out["cost_note"] = str(e)[:80]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="one config per layout, fewer steps")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model/shapes: validates the harness on CPU")
    ap.add_argument("--bs", type=int, default=512)
    args = ap.parse_args()
    steps = 8 if args.quick else 16
    depth, side = (18, 32) if args.smoke else (50, 224)
    if args.smoke:
        args.bs, steps = min(args.bs, 8), 2

    print("backend:", jax.default_backend(), jax.devices())
    results = []
    # bn=1: MXTPU_BN_PALLAS fused stats kernel (channels-minor only, hence
    # the NHWC-only rows).  Each measure() builds a fresh trace, so the
    # trace-time env read is honored per config within this process.
    # NHWC first: if the window dies mid-sweep, the A/B hypothesis answer
    # (is channels-last faster?) is the config we can least afford to lose
    configs = [("NHWC", 8, 0), ("NHWC", 8, 1), ("NCHW", 8, 0)] \
        if args.quick else \
        [("NHWC", 8, 0), ("NHWC", 8, 1), ("NCHW", 8, 0), ("NCHW", 1, 0),
         ("NHWC", 1, 0)]
    if args.smoke:
        configs = [("NCHW", 2, 0), ("NHWC", 2, 0), ("NHWC", 2, 1)]
    for layout, K, bn in configs:
        os.environ["MXTPU_BN_PALLAS"] = "1" if bn else "0"
        try:
            r = measure(layout, K, args.bs, steps, depth, side)
            r["bn_pallas"] = bn
        except Exception as e:
            r = {"layout": layout, "K": K, "bn_pallas": bn,
                 "error": f"{type(e).__name__}: {e}"[:200]}
        results.append(r)
        print(json.dumps(r), flush=True)
        # write after EVERY config: a timeout mid-sweep must not lose the
        # configs that did complete (cost round 5 its first window)
        with open("/tmp/perf_sweep.json", "w") as f:
            json.dump(results, f, indent=1)
    os.environ.pop("MXTPU_BN_PALLAS", None)
    ok = [r for r in results if "img_per_sec" in r]
    if ok:
        best = max(ok, key=lambda r: r["img_per_sec"])
        print(f"\nBEST: {best['layout']} K={best['K']} "
              f"bn_pallas={best.get('bn_pallas', 0)} -> "
              f"{best['img_per_sec']} img/s")


if __name__ == "__main__":
    main()

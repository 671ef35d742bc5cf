"""Perf probe for the ResNet-50 train step: ablations + XLA cost analysis.

Run on the real TPU chip: `python tools/perf_probe.py [--trace]`.
Feeds docs/perf_analysis.md (VERDICT r3 item 1).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timed(fn, *args, steps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace to /tmp/r50trace")
    ap.add_argument("--bs", type=int, default=512)
    args = ap.parse_args()
    bs = args.bs

    from mxnet_tpu import gluon, nd
    from mxnet_tpu.parallel.data_parallel import block_apply_fn

    net = gluon.model_zoo.vision.resnet50_v1(classes=1000)
    net.initialize()
    net(nd.array(np.zeros((1, 3, 224, 224), np.float32)))
    apply_fn, params = block_apply_fn(net, is_train=True)
    apply_inf, _ = block_apply_fn(net, is_train=False)

    x = jnp.asarray(np.random.rand(bs, 3, 224, 224).astype(np.float32))
    y = jnp.asarray(np.random.randint(0, 1000, (bs,)).astype(np.int32))
    rng = jax.random.PRNGKey(0)

    def loss_of(p, xx, dtype):
        pc = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
        logits = apply_fn(pc, xx.astype(dtype), rng).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    results = {}

    # 1. fwd-only inference, bf16
    fwd = jax.jit(lambda p, xx: apply_inf(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p),
        xx.astype(jnp.bfloat16), rng))
    dt = timed(fwd, params, x)
    results["fwd_inf_bf16"] = bs / dt

    # 2. fwd-only train mode (batch-stat BN), bf16
    fwd_t = jax.jit(lambda p, xx: apply_fn(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p),
        xx.astype(jnp.bfloat16), rng))
    dt = timed(fwd_t, params, x)
    results["fwd_train_bf16"] = bs / dt

    # 3. fwd+bwd, bf16
    g_bf16 = jax.jit(lambda p, xx: jax.grad(loss_of)(p, xx, jnp.bfloat16))
    dt = timed(g_bf16, params, x)
    results["fwdbwd_bf16"] = bs / dt

    # 4. fwd+bwd, f32 (MXU bf16-vs-f32 sanity: expect ~2-4x slower)
    g_f32 = jax.jit(lambda p, xx: jax.grad(loss_of)(p, xx, jnp.float32))
    dt = timed(g_f32, params, x, steps=5)
    results["fwdbwd_f32"] = bs / dt

    # 5. full step (grad + sgd), bf16 — the bench number
    def step(p, m, xx):
        loss, grads = jax.value_and_grad(
            lambda q: loss_of(q, xx, jnp.bfloat16))(p)
        m = jax.tree_util.tree_map(lambda mm, g: 0.9 * mm + g.astype(mm.dtype),
                                   m, grads)
        p = jax.tree_util.tree_map(lambda pp, mm: pp - 0.1 * mm, p, m)
        return loss, p, m

    momenta = {k: jnp.zeros_like(v) for k, v in params.items()}
    jstep = jax.jit(step)
    dt = timed(jstep, params, momenta, x)
    results["full_step_bf16"] = bs / dt

    # 6. K steps fused in one device program (lax.fori_loop): isolates
    # per-execution dispatch overhead from device compute
    K = 8

    def multi(p, m, xx):
        def body(_, carry):
            pp, mm = carry
            _, pp, mm = step(pp, mm, xx)
            return pp, mm

        p, m = jax.lax.fori_loop(0, K, body, (p, m))
        return p

    jmulti = jax.jit(multi)
    dt = timed(jmulti, params, momenta, x, steps=4)
    results["fused_%d_steps" % K] = bs * K / dt

    # cost analysis of the full step
    comp = jstep.lower(params, momenta, x).compile()
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", float("nan"))
    results["xla_flops_per_step"] = flops
    step_t = bs / results["full_step_bf16"]
    print(f"\nXLA-reported flops/step: {flops:.3e}")
    print(f"achieved: {flops / (bs / results['full_step_bf16']):.3e} FLOP/s "
          f"(step {step_t*1e3:.1f} ms)")
    try:
        mem = comp.memory_analysis()
        print(f"memory: {mem}")
    except Exception as e:
        print("memory_analysis unavailable:", e)

    for k, v in results.items():
        if "flops" not in k:
            print(f"{k:20s} {v:10.1f} img/s")

    if args.trace:
        with jax.profiler.trace("/tmp/r50trace"):
            for _ in range(3):
                out = jstep(params, momenta, x)
            jax.block_until_ready(out)
        print("trace written to /tmp/r50trace")


if __name__ == "__main__":
    main()

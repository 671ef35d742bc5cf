#!/usr/bin/env python
"""Collective-bandwidth benchmark (reference: tools/bandwidth/measure.py —
measures kvstore push+pull GB/s for ResNet-sized gradient sets).

TPU-native: measures psum (allreduce) over the device mesh — the primitive
the tpu_sync kvstore lowers to — for a configurable tensor-size schedule."""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def measure(sizes_mb, iters=10, axis="dp"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devices = np.asarray(jax.devices())
    mesh = Mesh(devices, (axis,))
    n = len(devices)

    for mb in sizes_mb:
        elems = int(mb * 1e6 / 4)
        x = jnp.ones((n, elems), jnp.float32)

        @jax.jit
        def allreduce(x):
            return jax.shard_map(
                lambda v: jax.lax.psum(v, axis),
                mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                check_vma=True)(x)

        allreduce(x).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = allreduce(x)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        # ring allreduce moves 2(n-1)/n of the data per device
        algo_bytes = 4 * elems * 2 * (n - 1) / n
        print(f"size {mb:8.1f} MB  time {dt*1e3:8.2f} ms  "
              f"busbw {algo_bytes/dt/1e9:8.2f} GB/s/device  ({n} devices)")


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes-mb", type=str, default="1,16,64,256")
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()
    measure([float(s) for s in args.sizes_mb.split(",")], iters=args.iters)

"""Synthetic image batches for the ``fit`` driver: ``n_batches`` distinct
float32 batches and integer labels, drawn from the seed on the host (the
program's ``NDArrayIter`` takes host arrays).  Every row differs."""
import numpy as np


def make(ctx):
    t = ctx.traffic
    n = t["n_batches"] * t["batch"]
    rng = ctx.rng(1)
    data = rng.random((n, 3, t["image"], t["image"]), dtype=np.float32)
    label = rng.integers(0, ctx.config["classes"], n).astype(np.float32)
    return data, label

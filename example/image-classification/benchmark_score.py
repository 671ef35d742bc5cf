#!/usr/bin/env python
"""Inference throughput benchmark over the model zoo (reference:
example/image-classification/benchmark_score.py — scores symbols at several
batch sizes and prints images/sec)."""
import argparse
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.parallel.data_parallel import block_apply_fn


def score(model_name, batch_size, image_shape=(3, 224, 224), steps=20,
          dtype="float32", layout="NCHW"):
    net = gluon.model_zoo.vision.get_model(model_name, classes=1000,
                                           layout=layout)
    net.initialize()
    c, h, w = image_shape
    ishape = (c, h, w) if layout == "NCHW" else (h, w, c)
    net(mx.nd.array(np.zeros((1,) + ishape, np.float32)))
    apply_fn, params = block_apply_fn(net, is_train=False)
    if dtype not in ("float32", "bfloat16"):
        raise ValueError(f"dtype must be float32 or bfloat16, got {dtype!r}")
    cdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    # cast weights ONCE outside the timed step — an in-step tree cast would
    # charge every iteration a full weight-tree convert and deflate the
    # bf16 number this script exists to measure
    params = jax.tree_util.tree_map(lambda a: a.astype(cdt), params)

    def fwd(p, x, chain):
        out = apply_fn(p, (x + chain).astype(cdt)).astype(jnp.float32)
        # data-dependent scalar threading each iteration's input through the
        # previous output, so no iteration can be served from the last
        return out, out.ravel()[0] * 0.0

    jfwd = jax.jit(fwd)
    x = jnp.asarray(np.random.rand(batch_size, *ishape)
                    .astype(np.float32))
    out, chain = jfwd(params, x, jnp.float32(0))
    out.block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        out, chain = jfwd(params, x, chain)
    out.block_until_ready()
    return batch_size * steps / (time.perf_counter() - t0)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--networks", type=str,
                        default="resnet50_v1,mobilenet1_0")
    parser.add_argument("--batch-sizes", type=str, default="1,16,32")
    parser.add_argument("--image-shape", type=str, default="3,224,224",
                        help="C,H,W order regardless of --layout (the "
                             "script permutes for NHWC itself)")
    parser.add_argument("--dtype", type=str, default="float32",
                        choices=("float32", "bfloat16"))
    parser.add_argument("--layout", type=str, default="NCHW",
                        choices=("NCHW", "NHWC"))
    parser.add_argument("--steps", type=int, default=20)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    shape = tuple(int(x) for x in args.image_shape.split(","))
    assert len(shape) == 3, "--image-shape must be C,H,W"
    for net in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            ips = score(net, bs, shape, steps=args.steps, dtype=args.dtype,
                        layout=args.layout)
            logging.info("network: %s, batch=%d, dtype=%s, layout=%s: "
                         "%.1f images/sec", net, bs, args.dtype,
                         args.layout, ips)

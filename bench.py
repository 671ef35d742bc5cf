"""Benchmark: ResNet-50 training throughput, images/sec/chip.

Matches the reference's headline number (`train_imagenet.py` throughput,
BASELINE.md: V100 fp32 298.51 img/s at bs=32; driver north star 1,200
img/s/chip on v4-32).  The whole train step — forward, backward, SGD+momentum
update — is one jitted XLA program with donated param buffers; bf16 compute
with f32 master weights (the TPU analogue of the reference's multi-precision
fp16 path, python/mxnet/optimizer.py:494).

Two measured paths:
- synthetic (the primary metric): the fused jitted step on synthetic tensors
  — the framework's compute ceiling.
- e2e (BENCH_MODE=both, default): the path BASELINE.json actually names —
  Module.fit over the native ImageRecordIter with KVStore `tpu_sync`
  (example/image-classification/train_imagenet.py's exact stack), reported
  in the same JSON line as "e2e_value".  BENCH_MODE=synthetic skips it;
  BENCH_MODE=e2e makes it the primary value.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

One process, one size: `python bench.py` IS the measurement.  It exits
non-zero when jax's first device is not a TPU (``BENCH_ALLOW_CPU=1`` lets a
CPU smoke of the code paths through — its numbers are not device metrics),
when the measurement raises, or when any optional block failed.  No probe,
no retry, no remembered number.  The compile cache is placed by
``mxnet_tpu.util.enable_compile_cache`` (docs/multichip.md).
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

NORTH_STAR = 1200.0  # img/s/chip (BASELINE.json)


def e2e_throughput(batch_size: int, batches: int = 10, warmup: int = 3):
    """(images/sec, fused) through Module.fit + native ImageRecordIter +
    tpu_sync — the north-star path itself (train_imagenet.py, common/fit.py).
    ``fused`` reports whether Module.fit ran on the fused whole-train-step
    program; BENCH_FUSED=0 forces the legacy per-param path for comparison."""
    import argparse
    import glob
    import shutil
    import tempfile

    if os.environ.get("BENCH_FUSED") == "0":
        os.environ["TPUMX_FUSED_STEP"] = "0"

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "example", "image-classification"))
    import mxnet_tpu as mx
    from common import data as cdata
    from symbols import resnet as resnet_sym

    num_examples = batch_size * (batches + warmup + 2)
    # dataset dir is size-keyed: a stale smaller .rec from a previous run
    # would silently starve the measurement (get_rec_iter only synthesizes
    # when the file is absent).  Stale sibling sizes are multi-GB — sweep them.
    data_dir = os.path.join(tempfile.gettempdir(),
                            f"bench_e2e_data_{num_examples}")
    for stale in glob.glob(os.path.join(tempfile.gettempdir(),
                                        "bench_e2e_data_*")):
        if stale != data_dir:
            shutil.rmtree(stale, ignore_errors=True)
    args = argparse.Namespace(
        data_train=None, data_val=None,
        data_dir=data_dir,
        image_shape="3,224,224", num_classes=100, resize=256,
        data_nthreads=int(os.environ.get("BENCH_E2E_NTHREADS", "8")),
        rgb_mean="123.68,116.779,103.939", rgb_std="1,1,1",
        synthetic=True, synthetic_size=num_examples,
        synthetic_encoding=os.environ.get("BENCH_E2E_ENCODING", "raw"),
        batch_size=batch_size, benchmark=False)
    kv = mx.kv.create("tpu_sync")
    train, _ = cdata.get_rec_iter(args, kv)
    net = resnet_sym.get_symbol(args.num_classes, 50, args.image_shape)
    mod = mx.mod.Module(net, label_names=["softmax_label"])

    marks = []

    def cb(param):
        marks.append((param.nbatch, time.perf_counter()))

    mod.fit(train, num_epoch=1, optimizer="sgd", kvstore=kv,
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            batch_end_callback=cb)
    usable = [(n, t) for n, t in marks if n >= warmup]
    if len(usable) < 2:
        raise RuntimeError(f"too few batches measured: {len(marks)}")
    (n0, t0), (n1, t1) = usable[0], usable[-1]
    return ((n1 - n0) * batch_size / (t1 - t0),
            getattr(mod, "_fused_step_count", 0) > 0)


def multichip_train_throughput(ndev: int = None):
    """images/sec/chip + allreduce bus bandwidth at ndev>1 — the SPMD fused
    train step (docs/multichip.md): Module.fit over a dp mesh with kvstore
    `tpu_sync`, batch sharded on the dp axis, gradients psum'd in-program.

    Also reports the LEGACY host-staged kvstore reduce bandwidth
    (KVStoreLocal._reduce, the path the SPMD program replaces) so the
    MULTICHIP_r*.json trend shows both sides.  On a host without a
    multi-chip backend the caller runs this in a virtual-device subprocess
    (numbers are wiring checks there, not bandwidth).
    """
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd, sym
    from mxnet_tpu.parallel.mesh import dp_mesh

    devs = jax.devices()
    ndev = min(ndev or int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8")),
               len(devs))
    if ndev < 2:
        raise RuntimeError(f"multichip bench needs >=2 devices, have {len(devs)}")
    batch = int(os.environ.get("BENCH_MULTICHIP_BATCH", "256"))
    steps = int(os.environ.get("BENCH_MULTICHIP_STEPS", "16"))
    dim, hidden, classes = 512, 1024, 64

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=hidden, name="fc1"),
                       act_type="relu")
    h = sym.Activation(sym.FullyConnected(h, num_hidden=hidden, name="fc2"),
                       act_type="relu")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(h, num_hidden=classes, name="fc3"), label,
        name="softmax")

    rs = np.random.RandomState(0)
    n = batch * steps
    it = mx.io.NDArrayIter(rs.rand(n, dim).astype(np.float32),
                           rs.randint(0, classes, n).astype(np.float32),
                           batch_size=batch)
    ctx_fn = mx.cpu if devs[0].platform == "cpu" else mx.tpu
    mod = mx.mod.Module(net, context=[ctx_fn(i) for i in range(ndev)])
    marks = []
    mod.fit(it, num_epoch=2, optimizer="sgd", kvstore="tpu_sync",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            batch_end_callback=lambda p: marks.append(
                (p.epoch * steps + p.nbatch, time.perf_counter())))
    fused = getattr(mod, "_fused_step_count", 0) > 0
    # epoch 2 only: epoch 1 pays the compile
    usable = [m for m in marks if m[0] >= steps]
    (n0, t0), (n1, t1) = usable[0], usable[-1]
    img_per_sec_chip = (n1 - n0) * batch / (t1 - t0) / ndev

    # in-program allreduce bus bandwidth (the tpu_sync reduce primitive)
    mesh = dp_mesh(ndev)
    elems = int(float(os.environ.get("BENCH_MULTICHIP_MB", "4")) * 1e6 / 4)
    x = jnp.ones((ndev, elems), jnp.float32)
    fn = jax.jit(jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                                  in_specs=jax.sharding.PartitionSpec("dp"),
                                  out_specs=jax.sharding.PartitionSpec("dp"),
                                  check_vma=True))
    fn(x).block_until_ready()
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    busbw = 4 * elems * 2 * (ndev - 1) / ndev / dt / 1e9

    # legacy host-staged kvstore reduce (what the SPMD program replaces;
    # exercises the batched-transfer + jitted tree-reduction hot path)
    kv = mx.kv.create("device")
    kv.init("g", nd.zeros((elems,)))
    vals = []
    for i in range(ndev):
        v = nd.ones((elems,))
        v._data = jax.device_put(v._data, devs[i])
        vals.append(v)
    out_nd = nd.zeros((elems,))
    kv.push("g", vals)
    kv.pull("g", out=out_nd)
    out_nd.wait_to_read()  # warm the jitted reduction
    t0 = time.perf_counter()
    for _ in range(iters):
        kv.push("g", vals)
        kv.pull("g", out=out_nd)
    out_nd.wait_to_read()
    dt = (time.perf_counter() - t0) / iters
    host_reduce = 4 * elems * 2 * (ndev - 1) / ndev / dt / 1e9

    return {
        "n_devices": ndev,
        "images_per_sec_per_chip": round(img_per_sec_chip, 2),
        "batch": batch,
        "fused_spmd": bool(fused),
        "allreduce_busbw_gbps": round(busbw, 3),
        "kvstore_host_reduce_gbps": round(host_reduce, 3),
        "platform": devs[0].platform,
    }


def _virtual_cpu_child(flag: str, ndev: int, marker: str):
    """Run ``bench.py <flag>`` in a child on an ``ndev``-device virtual CPU
    mesh (the tests/conftest.py recipe) and return its JSON record.  This
    process owns the chip, so the child is held to the CPU backend
    (``JAX_PLATFORMS=cpu``) and never initialises a TPU one.  What comes
    back is a CPU REHEARSAL of the multi-device wiring: its rates are
    renamed ``*_per_virtual_cpu_device`` so that none reads as a chip
    metric (docs/multichip.md)."""
    import re
    import subprocess

    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={ndev}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag],
        capture_output=True, text=True, env=env, timeout=900)
    for line in proc.stdout.splitlines():
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and marker in cand:
            out = {k.replace("_per_sec_per_chip",
                             "_per_sec_per_virtual_cpu_device"): v
                   for k, v in cand.items()}
            out["cpu_rehearsal"] = True
            return out
    raise RuntimeError(
        f"{flag} subprocess rc={proc.returncode}: "
        f"{(proc.stderr or proc.stdout).strip()[-300:]}")


def _multichip_block():
    """The multichip measurement for main(): inline when this process
    already sees >=2 devices, else a CPU rehearsal on a virtual mesh."""
    import jax

    if len(jax.devices()) >= 2:
        return multichip_train_throughput()
    return _virtual_cpu_child(
        "--multichip", int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8")),
        "n_devices")


def mp_sharded_train_throughput(dp: int = None, mp: int = None):
    """Partition-rule sharded model parallelism (docs/sharding.md):
    Module.fit over a ("dp","mp") mesh with the FSDP catch-all rules —
    img-or-tok/s/chip plus LIVE param+optimizer bytes per chip vs the
    replicated dp-only layout (the memory-reduction headline).  Runs in a
    virtual-device subprocess on 1-chip hosts (PR 4's recipe); numbers
    there are wiring checks, not bandwidth.  ``BENCH_MP=0`` skips."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.parallel.partition_rules import bytes_per_device

    devs = jax.devices()
    dp = dp or int(os.environ.get("BENCH_MP_DP", "2"))
    mp = mp or int(os.environ.get("BENCH_MP_DEVICES", "2"))
    if dp * mp > len(devs):
        raise RuntimeError(
            f"mp bench wants dp*mp={dp * mp} devices, have {len(devs)}")
    batch = int(os.environ.get("BENCH_MP_BATCH", "256"))
    steps = int(os.environ.get("BENCH_MP_STEPS", "16"))
    dim, hidden, classes = 512, 1024, 64

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=hidden,
                                          name="fc1"), act_type="relu")
    h = sym.Activation(sym.FullyConnected(h, num_hidden=hidden, name="fc2"),
                       act_type="relu")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(h, num_hidden=classes, name="fc3"), label,
        name="softmax")

    def run(env):
        prev = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            rs = np.random.RandomState(0)
            n = batch * steps
            it = mx.io.NDArrayIter(rs.rand(n, dim).astype(np.float32),
                                   rs.randint(0, classes, n).astype(
                                       np.float32),
                                   batch_size=batch)
            mod = mx.mod.Module(net, context=mx.cpu()
                                if devs[0].platform == "cpu" else None)
            marks = []
            mod.fit(it, num_epoch=2, optimizer="adam", kvstore="tpu_sync",
                    optimizer_params={"learning_rate": 1e-3},
                    batch_end_callback=lambda p: marks.append(
                        (p.epoch * steps + p.nbatch, time.perf_counter())))
            usable = [m for m in marks if m[0] >= steps]  # epoch 2 only
            (n0, t0), (n1, t1) = usable[0], usable[-1]
            arrs = [mod._exec.arg_dict[nm] for nm in mod._param_names]
            arrs += [mod._updater.states[i] for i in mod._updater.states]
            per_dev = bytes_per_device(arrs)
            return ((n1 - n0) * batch / (t1 - t0),
                    max(per_dev.values()) if per_dev else 0,
                    getattr(mod, "_fused_step_count", 0) > 0)
        finally:
            for k, v in prev.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    img_s, repl_bytes, fused_r = run({"TPUMX_DP_DEVICES": str(dp * mp)})
    img_mp, shard_bytes, fused_m = run({"TPUMX_DP_DEVICES": str(dp),
                                        "TPUMX_MP_DEVICES": str(mp)})
    return {
        "mesh": {"dp": dp, "mp": mp},
        "images_per_sec_per_chip": round(img_mp / (dp * mp), 2),
        "replicated_images_per_sec_per_chip": round(img_s / (dp * mp), 2),
        "batch": batch,
        "fused_spmd": bool(fused_m and fused_r),
        "param_opt_bytes_per_chip": int(shard_bytes),
        "replicated_param_opt_bytes_per_chip": int(repl_bytes),
        "memory_vs_replicated": round(shard_bytes / max(1, repl_bytes), 4),
        "platform": devs[0].platform,
    }


def _mp_sharded_block():
    """mp-sharded measurement for main(): inline when this process sees
    enough devices, else a CPU rehearsal on a virtual mesh."""
    import jax

    dp = int(os.environ.get("BENCH_MP_DP", "2"))
    mp = int(os.environ.get("BENCH_MP_DEVICES", "2"))
    if len(jax.devices()) >= dp * mp:
        return mp_sharded_train_throughput(dp, mp)
    return _virtual_cpu_child("--mp-sharded", dp * mp,
                              "memory_vs_replicated")


def serving_latency(requests: int = None, clients: int = None):
    """p50/p99 request latency + QPS through mxnet_tpu.serving under a
    concurrent mixed-shape workload (docs/serving.md)."""
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import serving, sym

    requests = requests or int(os.environ.get("BENCH_SERVING_REQUESTS", "256"))
    clients = clients or int(os.environ.get("BENCH_SERVING_CLIENTS", "8"))
    hidden, width = 256, 64
    data = sym.Variable("data")
    pooled = sym.sum(sym.Activation(data, act_type="tanh"), axis=1)
    net = sym.FullyConnected(
        sym.Activation(sym.FullyConnected(pooled, num_hidden=hidden, name="fc1"),
                       act_type="relu"),
        num_hidden=10, name="fc2")
    mod = mx.mod.Module(net, data_names=("data",), label_names=None)
    mod.bind(data_shapes=[("data", (8, 16, width))], for_training=False)
    mod.init_params(mx.init.Uniform(0.05))
    shapes = [(8, width), (16, width), (32, width)]
    svc = serving.InferenceService(
        mod, serving.ServingConfig(max_batch_size=8, batch_timeout_ms=1.0,
                                   shape_buckets=shapes, queue_bound=1024))
    svc.warmup(shapes)
    per_client = requests // clients
    errors = []

    def client(tid):
        rng = np.random.RandomState(tid)
        try:
            for i in range(per_client):
                x = rng.rand(*shapes[(tid + i) % len(shapes)]).astype(np.float32)
                svc.predict(x, timeout=120)
        except Exception as e:
            errors.append(repr(e))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(t,)) for t in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    wall = time.perf_counter() - t0
    stats = svc.stats()
    svc.stop()
    if errors:
        raise RuntimeError(f"{len(errors)} client errors: {errors[0]}")
    return {
        "p50_ms": stats["latency_ms"]["p50"],
        "p99_ms": stats["latency_ms"]["p99"],
        "qps": round(per_client * clients / wall, 1),
        "batch_occupancy": stats["batch_occupancy"],
        "post_warmup_compiles": stats["compile_cache"]["misses"]
        - stats.get("warmup_programs", 0),
        "requests": per_client * clients,
        "clients": clients,
    }


def mp_compute_train_throughput():
    """Tensor-parallel COMPUTE vs FSDP vs single-chip on the transformer
    train step (docs/sharding.md "compute partitioning"): per-step seconds
    for (a) mp=N with the GSPMD compute-partitioned matmuls, (b) mp=N with
    the PR-8 gather-compute-slice, and (c) mp=1 — the ROADMAP item-2 claim
    that more silicon now means faster steps, not just fewer bytes/chip.
    ``BENCH_MP_COMPUTE=0`` skips; runs in a virtual-device subprocess on
    1-chip hosts (wiring check there, bandwidth on real chips)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.parallel.mesh import make_mesh

    mp = int(os.environ.get("BENCH_MP_COMPUTE_DEVICES", "2"))
    devs = jax.devices()
    if mp > len(devs):
        raise RuntimeError(
            f"mp-compute bench wants {mp} devices, have {len(devs)}")
    steps = int(os.environ.get("BENCH_MP_COMPUTE_STEPS", "8"))
    batch = int(os.environ.get("BENCH_MP_COMPUTE_BATCH", "8"))
    T = 256
    cfg = tr.TransformerConfig(vocab=512, d_model=256, n_heads=8,
                               n_layers=4, d_ff=1024, max_len=T)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    momenta = jax.tree_util.tree_map(jnp.zeros_like, params)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, cfg.vocab, (batch, T)), jnp.int32)
    labels = jnp.asarray(rs.randint(0, cfg.vocab, (batch, T)), jnp.int32)
    positions = jnp.arange(T, dtype=jnp.int32)

    def time_leg(step, p, m):
        loss, p, m = step(p, m, tokens, labels, positions)  # compile+warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, p, m = step(p, m, tokens, labels, positions)
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / steps

    def fresh():
        return ({k: jnp.array(v, copy=True) for k, v in params.items()},
                {k: jnp.array(v, copy=True) for k, v in momenta.items()})

    # mp=1 oracle: the single-device jitted train step
    step1 = jax.jit(lambda p, m, t, l, pos: tr.train_step(p, m, t, l, pos,
                                                          cfg),
                    donate_argnums=(0, 1))
    p, m = fresh()
    t_mp1 = time_leg(step1, p, m)

    mesh = make_mesh({"dp": 1, "mp": mp}, install=False)
    legs = {}
    for name, compute in (("mp_compute", True), ("mp_fsdp", False)):
        step, shard_fn, _ = tr.make_partitioned_train_step(
            mesh, cfg, mp_compute=compute)
        p, m = fresh()
        legs[name] = time_leg(step, shard_fn(p), shard_fn(m))

    return {
        "mp": mp,
        "batch": batch,
        "seq_len": T,
        "step_seconds_mp1": round(t_mp1, 5),
        "step_seconds_mp_compute": round(legs["mp_compute"], 5),
        "step_seconds_mp_fsdp": round(legs["mp_fsdp"], 5),
        "compute_vs_fsdp": round(legs["mp_compute"] / legs["mp_fsdp"], 4),
        "compute_vs_mp1": round(legs["mp_compute"] / t_mp1, 4),
        "compute_not_slower_than_fsdp":
            legs["mp_compute"] <= legs["mp_fsdp"],
        "platform": devs[0].platform,
    }


def _mp_compute_block():
    """mp-compute measurement for main(): inline when this process sees
    enough devices, else a CPU rehearsal on a virtual mesh."""
    import jax

    mp = int(os.environ.get("BENCH_MP_COMPUTE_DEVICES", "2"))
    if len(jax.devices()) >= mp:
        return mp_compute_train_throughput()
    return _virtual_cpu_child("--mp-compute", mp, "step_seconds_mp_compute")


def lm_decode_throughput(requests: int = None, clients: int = None):
    """Continuous-batching generation under concurrent load
    (docs/generation.md): tokens/sec/chip, p50/p99 time-to-first-token and
    p99 inter-token latency through mxnet_tpu.serving.generation's paged
    decode loop, plus the engine's own health stats.  ``BENCH_DECODE=0``
    skips the block; the process registry snapshot rides on the result JSON
    like every other block."""
    import threading

    import jax
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    requests = requests or int(os.environ.get("BENCH_DECODE_REQUESTS", "48"))
    clients = clients or int(os.environ.get("BENCH_DECODE_CLIENTS", "8"))
    new_tokens = int(os.environ.get("BENCH_DECODE_NEW_TOKENS", "32"))
    # BENCH_DECODE_MP > 1 serves the mp-sharded model; since the per-head
    # shard_map'd kernel landed this decodes through the PAGED fast path
    # ("kernel": "paged" in the result) — heads permitting
    mp = int(os.environ.get("BENCH_DECODE_MP", "1") or 1)
    cfg = tr.TransformerConfig(vocab=512, d_model=256, n_heads=8,
                               n_layers=4, d_ff=1024, max_len=512)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    svc = GenerationService(
        params, cfg,
        GenerationConfig(max_slots=8, block_size=32, num_blocks=256,
                         seq_buckets=[64, 128, 256],
                         max_new_tokens=new_tokens, queue_bound=1024,
                         mp_devices=mp))
    warmed = svc.warmup()
    per_client = requests // clients
    errors = []

    def client(tid):
        rng = np.random.RandomState(tid)
        try:
            for i in range(per_client):
                prompt = rng.randint(0, cfg.vocab,
                                     int(rng.choice([24, 60, 120, 200])))
                svc.generate(prompt, max_new_tokens=new_tokens,
                             temperature=0.8 if (tid + i) % 2 else 0.0,
                             top_k=40, seed=tid * 1000 + i, timeout=600)
        except Exception as e:
            errors.append(repr(e))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(t,))
               for t in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    wall = time.perf_counter() - t0
    stats = svc.stats()
    compile_stats = svc.compile_stats()
    svc.stop()
    if errors:
        raise RuntimeError(f"{len(errors)} client errors: {errors[0]}")
    total_tokens = stats["counts"]["tokens"]
    n_chips = max(1, len(jax.local_devices()))
    return {
        # "paged" (Pallas block-table kernel) vs "gather" (dense XLA path):
        # the trajectory attributes decode wins to the active kernel
        "kernel": stats.get("decode_kernel", "gather"),
        # single/multistep/spec — which decode path served this run
        # (docs/generation.md "Speculative decoding")
        "decode_mode": stats.get("decode_mode", "single"),
        "mp_devices": mp,
        "tokens_per_sec": round(total_tokens / wall, 1),
        "tokens_per_sec_per_chip": round(total_tokens / wall / n_chips, 1),
        "ttft_p50_ms": stats["ttft_ms"]["p50"],
        "ttft_p99_ms": stats["ttft_ms"]["p99"],
        "inter_token_p99_ms": stats["inter_token_ms"]["p99"],
        "requests": per_client * clients,
        "clients": clients,
        "new_tokens_per_request": new_tokens,
        "decode_iterations": stats["iterations"],
        "kv_block_peak_occupancy": stats["kv_blocks"]["peak_occupancy"],
        "warmed_programs": warmed,
        "post_warmup_compiles": sum(
            st["misses"] for st in compile_stats.values()) - warmed,
    }


def speculative_decode_throughput():
    """Multi-token decoding (docs/generation.md "Speculative decoding"):
    the SAME greedy request set driven through the single-token baseline
    and every multi-token path — multistep scanned decode, n-gram
    speculative, and self-draft speculative (draft == target params: the
    acceptance-ratio upper bound) — reporting tokens/sec/chip, mean
    accepted draft length, and the speedup of the best mode over the
    baseline (acceptance: >= 2x).

    Methodology (CPU proxy): multi-token decoding amortizes
    PER-ITERATION DISPATCH — host scheduling, program launch, the
    host↔device round trip between steps — which is what bounds TPU
    decode at serving batch sizes.  The proxy model is deliberately
    sized so one decode step's CPU compute is comparable to that
    dispatch overhead (the TPU regime); at CPU-compute-bound shapes the
    amortization is invisible because the simulator pays ~per-token
    FLOP costs a real accelerator doesn't.  The measurement runs at
    ``BENCH_SPEC_SLOTS`` = 1: the latency-bound small-batch regime
    where one request's serial decode cannot fill the chip and every
    step pays full dispatch — exactly where multi-token decoding
    matters (at large batch the dispatch cost is already amortized
    ACROSS slots and all modes converge).  The self-draft run uses the
    target model as its own draft (no smaller checkpoint exists in the
    bench), so its absolute throughput is a LOWER bound for speculation
    — a real deployment's draft is several times cheaper — while its
    acceptance ratio (~1.0 with the window covering the full context)
    is the upper bound.  ``BENCH_SPEC=0`` skips; ``BENCH_SPEC_REQS`` /
    ``BENCH_SPEC_NEW_TOKENS`` size the workload,
    ``BENCH_SPEC_MULTISTEP_K`` / ``BENCH_SPEC_DRAFT_K`` the ladders."""
    import jax
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    reqs = int(os.environ.get("BENCH_SPEC_REQS", "16"))
    new_tokens = int(os.environ.get("BENCH_SPEC_NEW_TOKENS", "64"))
    ms_k = int(os.environ.get("BENCH_SPEC_MULTISTEP_K", "8"))
    draft_k = int(os.environ.get("BENCH_SPEC_DRAFT_K", "4"))
    slots = int(os.environ.get("BENCH_SPEC_SLOTS", "1"))
    cfg = tr.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                               n_layers=2, d_ff=256, max_len=512)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    # half random prompts, half periodic (the n-gram proposer's food)
    prompts = []
    for i in range(reqs):
        if i % 2:
            prompts.append(np.tile(rs.randint(0, cfg.vocab, 6),
                                   8)[:int(rs.choice([24, 48]))])
        else:
            prompts.append(rs.randint(0, cfg.vocab,
                                      int(rs.choice([24, 48]))))

    def gen_cfg(**kw):
        return GenerationConfig(max_slots=slots, block_size=16,
                                num_blocks=256, seq_buckets=[32, 64],
                                max_new_tokens=new_tokens,
                                queue_bound=1024, **kw)

    def run(gcfg, draft_params=None, draft_cfg=None):
        svc = GenerationService(params, cfg, gcfg,
                                draft_params=draft_params,
                                draft_cfg=draft_cfg)
        svc.warmup()
        outs = []
        t0 = time.perf_counter()
        # wave-paced at slot width: decode runs with an empty queue, so
        # the adaptive-k policy engages without an explicit bulk scope
        for i in range(0, reqs, slots):
            handles = [svc.submit(p, max_new_tokens=new_tokens)
                       for p in prompts[i:i + slots]]
            for h in handles:
                outs.append(h.result(900))
        wall = time.perf_counter() - t0
        stats = svc.stats()
        svc.stop()
        total = stats["counts"]["tokens"]
        n_chips = max(1, len(jax.local_devices()))
        spec = stats["speculative"] or {}
        return {
            "decode_mode": stats["decode_mode"],
            "tokens_per_sec": round(total / wall, 1),
            "tokens_per_sec_per_chip": round(total / wall / n_chips, 1),
            "decode_iterations": stats["iterations"],
            "accepted_ratio": spec.get("accepted_ratio"),
            "mean_accepted_len": spec.get("mean_accepted_len"),
            "wall_s": round(wall, 2),
        }, outs

    base, outs_base = run(gen_cfg())
    multistep, outs_ms = run(gen_cfg(multistep_k=ms_k))
    ngram, outs_ng = run(gen_cfg(speculative=True, draft_k=draft_k))
    self_draft, outs_sd = run(
        gen_cfg(speculative=True, draft_mode="model", draft_k=draft_k,
                draft_window=128),   # covers prompt+new: acceptance ~1.0
        draft_params=params, draft_cfg=cfg)

    def speedup(mode):
        return round(mode["tokens_per_sec_per_chip"]
                     / max(1e-9, base["tokens_per_sec_per_chip"]), 2)

    best = max((multistep, ngram, self_draft),
               key=lambda m: m["tokens_per_sec_per_chip"])
    return {
        "baseline": base,
        "multistep": multistep,
        "ngram_speculative": ngram,
        "self_draft_speculative": self_draft,
        # greedy bit-identity across every decode path (the correctness
        # criterion riding along with the perf number)
        "outputs_identical": outs_base == outs_ms == outs_ng == outs_sd,
        "multistep_k": ms_k,
        "draft_k": draft_k,
        "speedup_multistep": speedup(multistep),
        "speedup_ngram": speedup(ngram),
        "speedup_self_draft": speedup(self_draft),
        "speedup_best": speedup(best),
        "best_mode": best["decode_mode"],
        "requests": reqs,
        "new_tokens_per_request": new_tokens,
    }


def overload_serving():
    """Shared-prefix burst at ~2x sustained capacity (docs/generation.md
    "overload control"): the same workload is driven through incremental
    allocation + preemption AND the reserve-ahead baseline
    (TPUMX_GEN_PREEMPTION=0 semantics), reporting completed/shed/expired/
    preempted counts, p99 TTFT, and the steady-state KV occupancy each
    policy sustains — the occupancy gauge's number, with acceptance
    being incremental strictly above reserve-ahead.  ``BENCH_OVERLOAD=0``
    skips; ``BENCH_OVERLOAD_REQS`` sizes the burst and
    ``BENCH_OVERLOAD_RATE`` the arrival multiplier over capacity."""
    import threading

    import jax
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    reqs = int(os.environ.get("BENCH_OVERLOAD_REQS", "48"))
    rate = float(os.environ.get("BENCH_OVERLOAD_RATE", "2.0"))
    # generation-heavy shape (short prompt, long completion): this is
    # where reserve-ahead hurts — it pins ~10 worst-case blocks per
    # request while the written context starts at ~4
    new_tokens = 96
    cfg = tr.TransformerConfig(vocab=512, d_model=128, n_heads=8,
                               n_layers=2, d_ff=512, max_len=256)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    shared_prefix = rs.randint(0, cfg.vocab, 48)

    def run(preemption, kv_dtype=None, num_blocks=24):
        # the pool is the binding constraint (4 slots x worst-case ~9
        # blocks >> 23 allocatable): reserve-ahead idles slots on
        # head-of-line worst cases while incremental packs live contexts
        # up to the watermark — the occupancy gap under measurement
        svc = GenerationService(params, cfg, GenerationConfig(
            max_slots=4, block_size=16, num_blocks=num_blocks,
            seq_buckets=[64, 128], max_new_tokens=new_tokens,
            queue_bound=16, backpressure="shed_oldest",
            preemption=preemption, kv_dtype=kv_dtype))
        svc.warmup()
        # calibrate: one uncontended request gives the per-request service
        # time; the burst then arrives at `rate` x the slot-parallel rate
        t0 = time.perf_counter()
        svc.generate(np.concatenate([shared_prefix,
                                     rs.randint(0, cfg.vocab, 16)]),
                     max_new_tokens=new_tokens, timeout=300)
        per_req = time.perf_counter() - t0
        interarrival = per_req / (4 * rate)

        occ = []       # owned blocks (reservation + headroom included)
        live = []      # written-context blocks only — the honest number
        stop_sampling = threading.Event()

        def sampler():
            while not stop_sampling.wait(0.005):
                occ.append(svc._cache.allocator.occupancy())
                live.append(svc.live_occupancy())

        threading.Thread(target=sampler, daemon=True).start()
        handles = []
        t0 = time.perf_counter()
        for i in range(reqs):
            tail = rs.randint(0, cfg.vocab, int(rs.choice([4, 8, 16])))
            try:
                handles.append(svc.submit(
                    np.concatenate([shared_prefix, tail]),
                    max_new_tokens=new_tokens, deadline_ms=60_000.0))
            except Exception:
                pass  # reject under extreme pressure still counts below
            time.sleep(interarrival)
        completed = errors = 0
        for h in handles:
            try:
                h.result(600)
                completed += 1
            except Exception:
                errors += 1
        wall = time.perf_counter() - t0
        stop_sampling.set()
        stats = svc.stats()
        svc.stop()
        mid_occ = occ[len(occ) // 4: -len(occ) // 4 or None]
        mid_live = live[len(live) // 4: -len(live) // 4 or None]
        return {
            "completed": completed,
            "typed_errors": errors,
            "shed": stats["counts"]["shed"],
            "expired": stats["counts"]["expired"],
            "preempted": stats["counts"]["preempted"],
            "ttft_p99_ms": stats["ttft_ms"]["p99"],
            # owned-block occupancy flatters reserve-ahead (reserved tail
            # blocks count); live occupancy counts only written context
            "steady_occupancy": round(
                float(np.mean(mid_occ)) if mid_occ else 0.0, 4),
            "steady_live_occupancy": round(
                float(np.mean(mid_live)) if mid_live else 0.0, 4),
            "peak_occupancy": stats["kv_blocks"]["peak_occupancy"],
            "wall_s": round(wall, 2),
        }

    inc = run(True)
    base = run(False)
    # the int8 row (docs/quantization.md): the SAME device bytes buy ~2x
    # the blocks, so the identical burst runs against a doubled pool —
    # the density win expressed in the occupancy comparison's own units
    from mxnet_tpu.serving.generation.kv_cache import PagedKVCache

    pool_bytes = 24 * PagedKVCache.bytes_per_block(
        cfg.n_layers, cfg.n_heads, cfg.d_head, 16)
    nb_int8 = PagedKVCache.num_blocks_for_bytes(
        pool_bytes, cfg.n_layers, cfg.n_heads, cfg.d_head, 16,
        kv_dtype="int8")
    int8 = run(True, kv_dtype="int8", num_blocks=nb_int8)
    int8["num_blocks_same_bytes"] = nb_int8
    return {
        "incremental": inc,
        "reserve_ahead": base,
        "incremental_int8_kv": int8,
        # the acceptance number: context actually served per pool block
        "occupancy_gain": round(inc["steady_live_occupancy"]
                                - base["steady_live_occupancy"], 4),
        "requests": reqs,
        "rate_multiplier": rate,
        "shared_prefix_len": int(shared_prefix.size),
    }


def prefix_cache_serving():
    """Shared-system-prompt serving (docs/generation.md "prefix
    caching"): N requests over one long shared prompt, measured with the
    prefix cache on vs ``TPUMX_GEN_PREFIX_CACHE=0`` semantics on the SAME
    request set — TTFT p50/p99 and prefill tokens actually computed (the
    acceptance pair: p50 >= 3x lower and tokens <= 0.2x on a >=90%-shared
    workload), plus the router's shared-prefix affinity hit-rate over two
    replicas.  Requests are driven in slot-sized waves so TTFT measures
    admission+prefill, not queueing.  ``BENCH_PREFIX=0`` skips;
    ``BENCH_PREFIX_REQS`` sizes the set and ``BENCH_PREFIX_NEW_TOKENS``
    the decode horizon."""
    import jax
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)
    from mxnet_tpu.serving.router import GenerationRouter, RouterConfig

    reqs = int(os.environ.get("BENCH_PREFIX_REQS", "24"))
    new_tokens = int(os.environ.get("BENCH_PREFIX_NEW_TOKENS", "8"))
    slots = 4
    # a prefill-heavy shape: the system prompt is the workload, so the
    # hit-vs-miss delta is the prefill compute itself, not loop overhead
    cfg = tr.TransformerConfig(vocab=512, d_model=256, n_heads=8,
                               n_layers=3, d_ff=1024, max_len=512)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    # 224 shared tokens (14 blocks of 16) + <=14-token tails: every
    # request is >=94% shared prefix
    shared_prefix = rs.randint(0, cfg.vocab, 224)
    tails = [rs.randint(0, cfg.vocab, int(rs.choice([2, 6, 10, 14])))
             for _ in range(reqs)]
    prompts = [np.concatenate([shared_prefix, t]) for t in tails]
    total_prompt_tokens = int(sum(p.size for p in prompts))

    def gen_cfg(prefix_cache):
        # the 16/32 rungs matter: a <=14-token uncached suffix prefills
        # through a 16-wide chunk instead of padding to 64, so the hit
        # path's compute is the suffix, not the ladder floor
        return GenerationConfig(
            max_slots=slots, block_size=16, num_blocks=128,
            seq_buckets=[16, 32, 64, 128, 256],
            max_new_tokens=new_tokens, prefix_cache=prefix_cache)

    def run(prefix_cache):
        svc = GenerationService(params, cfg, gen_cfg(prefix_cache))
        svc.warmup()
        ttfts, outs = [], []
        t0 = time.perf_counter()
        for i in range(0, reqs, slots):   # wave-paced: no queue inflation
            handles = [svc.submit(p, max_new_tokens=new_tokens)
                       for p in prompts[i:i + slots]]
            for h in handles:
                outs.append(h.result(600))
                ttfts.append(h.ttft_ms)
        wall = time.perf_counter() - t0
        stats = svc.stats()
        svc.stop()
        ttfts.sort()
        pc = stats["prefix_cache"] or {}
        return {
            "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 3),
            "ttft_p99_ms": round(ttfts[int(len(ttfts) * 0.99)], 3),
            "prefill_tokens_computed": stats["counts"]["prefill_tokens"],
            "cached_tokens": stats["counts"]["cached_tokens"],
            "prefix_hits": pc.get("hits", 0),
            "cow_copies": pc.get("cow_copies", 0),
            "evictions": pc.get("evictions", 0),
            "wall_s": round(wall, 2),
        }, outs

    cached, outs_on = run(True)
    uncached, outs_off = run(False)

    # router affinity: the same shared-prefix stream over 2 replicas —
    # affinity concentrates the prefix on one engine's cache (hit-rate
    # toward 100%), plain least-loaded splits it
    def affinity_run(affinity):
        router = GenerationRouter(
            params, cfg, gen_config=gen_cfg(True),
            config=RouterConfig(num_replicas=2, affinity=affinity))
        router.warmup()
        handles = [router.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        for h in handles:
            h.result(600)
        hits = sum(rep.service.stats()["prefix_cache"]["hits"]
                   for rep in router._replicas)
        router.stop()
        return round(hits / max(1, reqs), 4)

    hit_rate_affine = affinity_run(True)
    hit_rate_plain = affinity_run(False)
    return {
        "cached": cached,
        "uncached": uncached,
        "outputs_identical": outs_on == outs_off,  # greedy bit-identity
        "ttft_p50_speedup": round(
            uncached["ttft_p50_ms"] / max(1e-9, cached["ttft_p50_ms"]), 2),
        "prefill_tokens_ratio": round(
            cached["prefill_tokens_computed"]
            / max(1, uncached["prefill_tokens_computed"]), 4),
        "router_affinity_hit_rate": hit_rate_affine,
        "router_plain_hit_rate": hit_rate_plain,
        "requests": reqs,
        "shared_prefix_len": int(shared_prefix.size),
        "shared_fraction": round(
            reqs * shared_prefix.size / total_prompt_tokens, 4),
    }


def quantized_serving():
    """Int8 serving density (docs/quantization.md): tokens/sec/chip,
    blocks/chip at identical pool bytes, and logits/perplexity deltas vs
    bf16 — int8 WEIGHTS (the ServingConfig.quantize path over a symbolic
    model) and the int8 KV CACHE (the generation engine's quantized pool)
    measured independently.  ``BENCH_QUANT=0`` skips;
    ``BENCH_QUANT_TOKENS`` sizes the decode horizon."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd, sym
    from mxnet_tpu import quantization as quant
    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)
    from mxnet_tpu.serving.generation.kv_cache import PagedKVCache

    new_tokens = int(os.environ.get("BENCH_QUANT_TOKENS", "48"))
    out = {}

    # -- int8 KV cache: tokens/sec + accuracy vs the bf16 pool ------------
    cfg = tr.TransformerConfig(vocab=512, d_model=256, n_heads=8,
                               n_layers=4, d_ff=1024, max_len=512)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, int(n))
               for n in rng.choice([24, 60, 120], size=12)]

    def drive(kv_dtype):
        svc = GenerationService(params, cfg, GenerationConfig(
            max_slots=8, block_size=32, num_blocks=256,
            seq_buckets=[64, 128], max_new_tokens=new_tokens,
            amp_dtype="bfloat16", kv_dtype=kv_dtype), start=False)
        svc.warmup()
        svc.start()
        t0 = time.perf_counter()
        outs = [svc.generate(p, seed=i, timeout=600)
                for i, p in enumerate(prompts)]
        wall = time.perf_counter() - t0
        stats = svc.stats()
        svc.stop()
        return outs, stats["counts"]["tokens"] / wall

    bf_out, bf_tps = drive(None)
    q_out, q_tps = drive("int8")
    agree = sum(a == b for o1, o2 in zip(bf_out, q_out)
                for a, b in zip(o1, o2))
    total = sum(len(o) for o in bf_out)

    # teacher-forced logit/perplexity delta: feed each bf16-generated
    # sequence through one cache-aware prefill under each pool dtype
    def nll_of(kv_dtype, seqs):
        nlls, max_delta = [], 0.0
        for toks in seqs:
            toks = np.asarray(toks, np.int32)[None, :64]
            T = toks.shape[1]
            bsz, W = 32, 4
            pool = lambda d: jnp.zeros((cfg.n_layers, 9, bsz, cfg.d_model),
                                       d)
            tables = np.arange(1, 1 + W, dtype=np.int32)[None, :]
            pos = np.arange(T, dtype=np.int32)[None, :]
            ln = np.array([T], np.int32)
            if kv_dtype == "int8":
                sc = jnp.ones((cfg.n_layers, 9, cfg.n_heads))
                logits, *_ = tr.transformer_lm_decode(
                    params, toks, pos, ln, pool(jnp.int8), pool(jnp.int8),
                    tables, cfg, compute_dtype=jnp.bfloat16,
                    attention_kernel="gather", k_scale=sc, v_scale=sc)
            else:
                logits, _, _ = tr.transformer_lm_decode(
                    params, toks, pos, ln, pool(jnp.bfloat16),
                    pool(jnp.bfloat16), tables, cfg,
                    compute_dtype=jnp.bfloat16, attention_kernel="gather")
            logp = jax.nn.log_softmax(logits[0, :-1].astype(jnp.float32))
            nll = -jnp.take_along_axis(
                logp, jnp.asarray(toks[0, 1:])[:, None], axis=1)
            nlls.append(float(jnp.mean(nll)))
        return float(np.mean(nlls))

    seqs = [list(np.concatenate([p, np.asarray(o, p.dtype)]))
            for p, o in zip(prompts, bf_out)]
    nll_bf = nll_of(None, seqs)
    nll_q = nll_of("int8", seqs)
    pool_bytes = 256 * PagedKVCache.bytes_per_block(
        cfg.n_layers, cfg.n_heads, cfg.d_head, 32, dtype=jnp.bfloat16)
    blocks_bf16 = 256
    blocks_int8 = PagedKVCache.num_blocks_for_bytes(
        pool_bytes, cfg.n_layers, cfg.n_heads, cfg.d_head, 32,
        kv_dtype="int8")
    out["kv_int8"] = {
        "tokens_per_sec_bf16": round(bf_tps, 1),
        "tokens_per_sec_int8": round(q_tps, 1),
        "greedy_token_agreement": round(agree / max(total, 1), 4),
        "perplexity_bf16": round(math.exp(nll_bf), 4),
        "perplexity_int8": round(math.exp(nll_q), 4),
        "perplexity_delta": round(math.exp(nll_q) - math.exp(nll_bf), 4),
        "blocks_per_chip_bf16": blocks_bf16,
        "blocks_per_chip_int8_same_bytes": blocks_int8,
        "block_budget_ratio": round(blocks_int8 / blocks_bf16, 4),
    }

    # -- int8 weights: the ServingConfig.quantize path --------------------
    data = sym.Variable("data")
    h = data
    for i in range(3):
        h = sym.Activation(sym.FullyConnected(h, num_hidden=256,
                                              name=f"fc{i}"),
                           act_type="relu")
    net = sym.FullyConnected(h, num_hidden=64, name="head")
    mod = mx.mod.Module(net, label_names=None, context=mx.context.current_context())
    mod.bind(data_shapes=[("data", (32, 128))], for_training=False)
    mod.init_params()
    X = np.random.RandomState(1).rand(256, 128).astype(np.float32)
    table = quant.calibrate_module(
        mod, mx.io.NDArrayIter(X, None, batch_size=32))

    from mxnet_tpu.serving.service import _ExecutorAdapter

    def fc_leg(quantize):
        ad = _ExecutorAdapter(
            mod._exec, ["data"], quantize=quantize,
            quantize_calibration=table if quantize else None)
        feed = {"data": X[:32]}
        outs = ad.run(feed)  # compile
        t0 = time.perf_counter()
        iters = 30
        for _ in range(iters):
            outs = ad.run(feed)
        np.asarray(outs[0])
        return (32 * iters / (time.perf_counter() - t0),
                np.asarray(outs[0]))

    f_sps, f_logits = fc_leg(None)
    q_sps, q_logits = fc_leg("int8")
    denom = float(np.abs(f_logits).max()) or 1.0
    out["weights_int8"] = {
        "samples_per_sec_f32": round(f_sps, 1),
        "samples_per_sec_int8": round(q_sps, 1),
        "max_logit_rel_delta": round(
            float(np.abs(q_logits - f_logits).max()) / denom, 5),
    }
    return out


def pallas_kernels_bench():
    """Per-kernel microbenchmarks (docs/pallas.md): paged decode attention,
    flash-attention forward+backward, and fused LayerNorm — each timed
    against its XLA-composed counterpart at serving/training-shaped inputs,
    reporting per-call µs and achieved GB/s so kernel regressions show up
    in the BENCH trajectory next to the e2e numbers.  ``BENCH_PALLAS=0``
    skips the block.  On CPU hosts the kernels run interpreted (numbers are
    parity-smoke only; the TPU rounds are the real measurement)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash_attention as fa
    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops import pallas_kernels as pk

    iters = int(os.environ.get("BENCH_PALLAS_ITERS", "30"))
    rs = np.random.RandomState(0)

    def timeit(fn):
        out = fn()                       # compile + warm
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    def entry(t_kernel, t_xla, nbytes):
        return {
            "kernel_us": round(t_kernel * 1e6, 2),
            "xla_us": round(t_xla * 1e6, 2),
            "speedup_vs_xla": round(t_xla / t_kernel, 3),
            "kernel_gbps": round(nbytes / t_kernel / 1e9, 2),
        }

    out = {"iters": iters, "interpreted": pk._use_interpret()}

    # -- paged decode attention: B slots of T=1 against a W-block table ----
    B, H, D, bs, W = 8, 8, 64, 32, 16
    nb = B * W + 1
    q = jnp.asarray(rs.randn(B, 1, H, D).astype(np.float32))
    # one layer of the layered, head-folded pool the kernel is handed whole
    kp = jnp.asarray(rs.randn(1, nb, bs, H * D).astype(np.float32))
    vp = jnp.asarray(rs.randn(1, nb, bs, H * D).astype(np.float32))
    tables = np.arange(1, B * W + 1, dtype=np.int32).reshape(B, W)
    positions = np.full((B, 1), W * bs - 1, np.int32)
    max_pos = np.full(B, W * bs - 1, np.int32)
    ctx_pos = np.arange(W * bs, dtype=np.int32)
    mask = jnp.asarray(ctx_pos[None, None, :] <= positions[:, :, None])
    scale = pa.attention_scale(D)
    jt = jnp.asarray(tables)

    @jax.jit
    def dense(q, kp, vp, jt, mask):
        k_ctx = kp[0][jt].reshape(B, W * bs, H, D)
        v_ctx = vp[0][jt].reshape(B, W * bs, H, D)
        return pa.paged_attention_reference(q, k_ctx, v_ctx, mask,
                                            jnp.float32(scale))

    kv_bytes = 2 * B * W * bs * H * D * 4   # the K/V context each token reads
    out["paged_attention"] = entry(
        timeit(lambda: pa.paged_attention(q, kp, vp, tables, positions,
                                          max_pos, scale)),
        timeit(lambda: dense(q, kp, vp, jt, mask)), kv_bytes)

    # -- flash attention fwd+bwd at a training shape -----------------------
    Bf, Tf, Hf, Df = 2, 512, 4, 64
    qf = jnp.asarray(rs.randn(Bf, Tf, Hf, Df).astype(np.float32))
    prev_gate = os.environ.get("TPUMX_PALLAS")

    def flash_grad():
        return jax.grad(lambda x: jnp.sum(
            pk.flash_attention(x, qf, qf, causal=True) ** 2))(qf)

    try:
        os.environ["TPUMX_PALLAS"] = "1"
        t_kernel = timeit(flash_grad)
        os.environ["TPUMX_PALLAS"] = "0"
        t_scan = timeit(flash_grad)
    finally:
        if prev_gate is None:
            os.environ.pop("TPUMX_PALLAS", None)
        else:
            os.environ["TPUMX_PALLAS"] = prev_gate
    # fwd+bwd reads q/k/v/g/o and writes dq/dk/dv ≈ 8 passes over (B,T,H,D)
    out["flash_attention_bwd"] = entry(t_kernel, t_scan,
                                       8 * Bf * Tf * Hf * Df * 4)

    # -- fused LayerNorm at the LM's channels-minor shape ------------------
    M, C = 4096, 512
    x = jnp.asarray(rs.randn(M, C).astype(np.float32))
    g = jnp.asarray(rs.rand(C).astype(np.float32))
    b = jnp.asarray(rs.randn(C).astype(np.float32))

    @jax.jit
    def ln_xla(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

    out["layer_norm_fused"] = entry(
        timeit(lambda: pk.layer_norm_fused(x, g, b)),
        timeit(lambda: ln_xla(x, g, b)), 2 * M * C * 4)
    return out


def telemetry_overhead(batch: int = None, steps: int = None):
    """Fused-step wall time with device-side telemetry ON vs OFF
    (docs/observability.md): the SAME bound module stepped through
    ``_try_fused_step`` under ``TPUMX_TELEMETRY=1`` then ``0`` — each env
    value keys its own cached program — reporting ``overhead_pct``
    (acceptance: < 3%).  ``BENCH_TELEMETRY=0`` skips the block."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import sym

    batch = batch or int(os.environ.get("BENCH_TELEMETRY_BATCH", "512"))
    steps = steps or int(os.environ.get("BENCH_TELEMETRY_STEPS", "30"))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=1024, name="fc1"),
                       act_type="relu")
    h = sym.Activation(sym.FullyConnected(h, num_hidden=1024, name="fc2"),
                       act_type="relu")
    net = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=64, name="fc3"),
                            label, name="softmax")
    r = np.random.RandomState(0)
    X = r.rand(batch, 512).astype(np.float32)
    Y = r.randint(0, 64, batch).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    mod = mx.mod.Module(net, context=mx.cpu()
                        if jax.default_backend() == "cpu" else None)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    batch0 = next(iter(it))
    prev = os.environ.get("TPUMX_TELEMETRY")

    def leg(env_val):
        os.environ["TPUMX_TELEMETRY"] = env_val
        if not mod._try_fused_step(batch0):  # compile + warm this leg's key
            raise RuntimeError("fused step unavailable for telemetry bench")
        mod._exec.outputs[0].wait_to_read()
        t0 = time.perf_counter()
        for _ in range(steps):
            mod._try_fused_step(batch0)
        mod._exec.outputs[0].wait_to_read()
        return (time.perf_counter() - t0) / steps

    try:
        t_on = leg("1")
        t_off = leg("0")
        # interleave a second pass to cancel clock/thermal drift
        t_on = min(t_on, leg("1"))
        t_off = min(t_off, leg("0"))
    finally:
        if prev is None:
            os.environ.pop("TPUMX_TELEMETRY", None)
        else:
            os.environ["TPUMX_TELEMETRY"] = prev
    return {
        "with_ms": round(t_on * 1e3, 4),
        "without_ms": round(t_off * 1e3, 4),
        "overhead_pct": round((t_on - t_off) / t_off * 100.0, 2),
        "steps": steps,
        "batch": batch,
    }


def tracing_overhead():
    """Generation decode throughput with the trace-context layer ON vs
    ``TPUMX_TRACING=0`` (docs/observability.md): the same request burst
    through two fresh engines, reporting ``overhead_pct`` (acceptance:
    < 2% — the per-request wide events, per-rung spans, and per-iteration
    decode participation fan-out must stay invisible next to the device
    work).  ``BENCH_TRACING=0`` skips the block."""
    import jax

    from mxnet_tpu.parallel import transformer as tr
    from mxnet_tpu.serving.generation import (GenerationConfig,
                                              GenerationService)

    reqs = int(os.environ.get("BENCH_TRACING_REQUESTS", "32"))
    new_tokens = int(os.environ.get("BENCH_TRACING_NEW_TOKENS", "24"))
    cfg = tr.TransformerConfig(vocab=512, d_model=128, n_heads=8,
                               n_layers=2, d_ff=512, max_len=256)
    params = tr.transformer_lm_init(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab, int(rs.choice([16, 40, 80])))
               for _ in range(reqs)]
    prev = os.environ.get("TPUMX_TRACING")

    def leg(env_val):
        os.environ["TPUMX_TRACING"] = env_val
        svc = GenerationService(params, cfg, GenerationConfig(
            max_slots=8, block_size=16, num_blocks=128,
            seq_buckets=[64, 128], max_new_tokens=new_tokens,
            queue_bound=1024), start=False)
        svc.warmup()
        hs = [svc.submit(p, max_new_tokens=new_tokens, seed=i)
              for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        svc.start()
        for h in hs:
            h.result(600)
        wall = time.perf_counter() - t0
        tokens = svc.stats()["counts"]["tokens"]
        svc.stop()
        return tokens / wall

    try:
        tps_on = leg("1")
        tps_off = leg("0")
        # interleave a second pass to cancel clock/thermal drift
        tps_on = max(tps_on, leg("1"))
        tps_off = max(tps_off, leg("0"))
    finally:
        if prev is None:
            os.environ.pop("TPUMX_TRACING", None)
        else:
            os.environ["TPUMX_TRACING"] = prev
    overhead_pct = (tps_off / tps_on - 1.0) * 100.0
    return {
        "tokens_per_sec_traced": round(tps_on, 1),
        "tokens_per_sec_untraced": round(tps_off, 1),
        "overhead_pct": round(overhead_pct, 2),
        "within_budget": overhead_pct < 2.0,
        "requests": reqs,
        "new_tokens_per_request": new_tokens,
    }


def checkpoint_overhead(batch: int = None, steps: int = None):
    """Fused-step wall time while async checkpoint snapshots are in flight
    vs without (docs/fault_tolerance.md): the SAME bound module stepped
    through ``_try_fused_step``, one leg saving every
    ``BENCH_CKPT_EVERY`` steps through the background writer, one leg
    clean — reporting ``overhead_pct`` (acceptance: < 5%).
    ``BENCH_CKPT=0`` skips the block."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.checkpoint import TrainCheckpointer

    batch = batch or int(os.environ.get("BENCH_CKPT_BATCH", "512"))
    steps = steps or int(os.environ.get("BENCH_CKPT_STEPS", "30"))
    # every-8 is already far denser than production cadences (O(100) steps);
    # on 1-core CI hosts the writer shares the "device" core, so denser
    # cadences overstate what a TPU host would see
    every = int(os.environ.get("BENCH_CKPT_EVERY", "8"))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=1024, name="fc1"),
                       act_type="relu")
    h = sym.Activation(sym.FullyConnected(h, num_hidden=1024, name="fc2"),
                       act_type="relu")
    net = sym.SoftmaxOutput(sym.FullyConnected(h, num_hidden=64, name="fc3"),
                            label, name="softmax")
    r = np.random.RandomState(0)
    X = r.rand(batch, 512).astype(np.float32)
    Y = r.randint(0, 64, batch).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    mod = mx.mod.Module(net, context=mx.cpu()
                        if jax.default_backend() == "cpu" else None)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    batch0 = next(iter(it))
    if not mod._try_fused_step(batch0):  # compile + warm
        raise RuntimeError("fused step unavailable for checkpoint bench")
    mod._exec.outputs[0].wait_to_read()

    def leg(ck):
        t0 = time.perf_counter()
        for i in range(steps):
            mod._try_fused_step(batch0)
            if ck is not None and (i + 1) % every == 0:
                ck.save(0, i + 1, i + 1, blocking=False)
        mod._exec.outputs[0].wait_to_read()
        return (time.perf_counter() - t0) / steps

    ckdir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        ck = TrainCheckpointer(mod, ckdir, keep=2)
        t_off = leg(None)
        t_on = leg(ck)
        # interleave a second pass to cancel clock/thermal drift
        t_off = min(t_off, leg(None))
        t_on = min(t_on, leg(ck))
        ck.manager.wait(timeout=120)
        from mxnet_tpu import observability as _obs

        counters = _obs.snapshot()["counters"]
        saved = sum(v for k, v in counters.items()
                    if k.startswith("checkpoint_saves_total"))
        saved_bytes = counters.get("checkpoint_save_bytes_total", 0)
        ck.close()
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    return {
        "with_ms": round(t_on * 1e3, 4),
        "without_ms": round(t_off * 1e3, 4),
        "overhead_pct": round((t_on - t_off) / t_off * 100.0, 2),
        "steps": steps,
        "batch": batch,
        "snapshot_every": every,
        "checkpoints_committed": int(saved),
        "checkpoint_bytes_total": int(saved_bytes),
    }


def main():
    # one size (BENCH_BATCH, default 512): a size that does not fit is a
    # failure, not a reason to report a smaller one
    batch_size = int(os.environ.get("BENCH_BATCH", "512"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.util import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("BENCH_ALLOW_CPU") != "1":
        sys.exit(f"bench.py: jax's first device is {dev.platform!r}, not a "
                 "TPU — nothing measured (BENCH_ALLOW_CPU=1 smokes the code "
                 "paths on the CPU; its numbers are not device metrics)")
    enable_compile_cache()

    from mxnet_tpu import gluon, nd
    from mxnet_tpu.parallel.data_parallel import block_apply_fn

    # NHWC puts C on the TPU's 128-lane minor dim (BENCH_LAYOUT=NCHW for the
    # reference-layout variant); parameters are stored OIHW either way
    layout = os.environ.get("BENCH_LAYOUT", "NCHW")
    ishape = (3, 224, 224) if layout == "NCHW" else (224, 224, 3)
    net = gluon.model_zoo.vision.resnet50_v1(classes=1000, layout=layout)
    net.initialize()
    # materialize shapes on the host CPU backend: the eager pass is ~270
    # tiny per-op dispatches
    import mxnet_tpu as _mx
    with _mx.cpu():
        net(nd.array(np.zeros((1,) + ishape, np.float32)))
    apply_fn, params = block_apply_fn(net, is_train=True)
    momenta = {k: jnp.zeros_like(v) for k, v in params.items()}

    def make_step(compute_dtype):
        """One fused SGD+momentum train step; ``compute_dtype`` is the AMP
        cast applied to params+input before the model body (None = pure
        f32 — the BENCH_AMP comparison baseline)."""

        def step(params, momenta, x, y, rng):
            def loss_of(p):
                if compute_dtype is not None:
                    p = jax.tree_util.tree_map(
                        lambda a: a.astype(compute_dtype), p)
                    x_c = x.astype(compute_dtype)
                else:
                    x_c = x
                logits = apply_fn(p, x_c, rng).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

            loss, grads = jax.value_and_grad(loss_of)(params)
            momenta = jax.tree_util.tree_map(
                lambda m, g: 0.9 * m + g.astype(m.dtype), momenta, grads)
            params = jax.tree_util.tree_map(lambda p, m: p - 0.1 * m,
                                            params, momenta)
            return loss, params, momenta

        return step

    step = make_step(jnp.bfloat16)
    jstep = jax.jit(step, donate_argnums=(0, 1))
    rng0 = jax.random.PRNGKey(0)

    # K train steps fused into ONE device program (lax.fori_loop): the
    # per-execution dispatch latency is paid once per K steps instead of
    # per step — same math, donated buffers, fresh rng per step.  Several K
    # values are tried and the best wins; comma-separated env to override.
    K_CANDIDATES = [int(k) for k in
                    os.environ.get("BENCH_FUSED_STEPS", "8,16").split(",")
                    if k.strip().isdigit() and int(k) > 1]

    def make_multi(K):
        def multi_step(params, momenta, x, y, rng):
            def body(i, carry):
                p, m, _ = carry
                loss, p, m = step(p, m, x, y, jax.random.fold_in(rng, i))
                return (p, m, loss)

            p, m, loss = jax.lax.fori_loop(
                0, K, body, (params, momenta, jnp.float32(0)))
            return loss, p, m

        return jax.jit(multi_step, donate_argnums=(0, 1))

    fused_img_per_sec = None
    x = jnp.asarray(np.random.rand(batch_size, *ishape).astype(np.float32))
    y = jnp.asarray(np.random.randint(0, 1000, (batch_size,))
                    .astype(np.int32))
    # fresh copies — donation consumes them
    p = jax.tree_util.tree_map(jnp.copy, params)
    m = jax.tree_util.tree_map(jnp.copy, momenta)
    loss, p, m = jstep(p, m, x, y, rng0)  # compile + warmup
    float(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        loss, p, m = jstep(p, m, x, y, jax.random.fold_in(rng0, i))
    float(loss)  # sync
    dt = time.perf_counter() - t0
    img_per_sec = batch_size * steps / dt
    best_K = None
    for K in K_CANDIDATES:
        jmulti = make_multi(K)
        reps = max(1, steps // K)
        p = jax.tree_util.tree_map(jnp.copy, params)
        m = jax.tree_util.tree_map(jnp.copy, momenta)
        loss, p, m = jmulti(p, m, x, y, rng0)  # compile + warmup
        float(loss)
        t0 = time.perf_counter()
        for i in range(reps):
            loss, p, m = jmulti(p, m, x, y, jax.random.fold_in(rng0, i))
        float(loss)
        dt = time.perf_counter() - t0
        k_img = batch_size * K * reps / dt
        if fused_img_per_sec is None or k_img > fused_img_per_sec:
            fused_img_per_sec, best_K = k_img, K
    result = {
        "metric": "resnet50_train_throughput",
        "value": round(img_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec / NORTH_STAR, 4),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }
    if fused_img_per_sec is not None:
        result["per_dispatch_value"] = result["value"]
        result["fused_steps"] = best_K
        result["fused_value"] = round(fused_img_per_sec, 2)
        if fused_img_per_sec > img_per_sec:
            result["value"] = round(fused_img_per_sec, 2)
            result["vs_baseline"] = round(fused_img_per_sec / NORTH_STAR, 4)
    if os.environ.get("BENCH_AMP", "1") == "1":
        # bf16-vs-f32 AMP speedup (docs/amp.md): the headline number IS the
        # bf16 path; re-run the identical fused step in pure f32 and report
        # the ratio the MXU's 2x bf16 rate buys (BENCH_AMP=0 skips)
        try:
            jstep32 = jax.jit(make_step(None), donate_argnums=(0, 1))
            p = jax.tree_util.tree_map(jnp.copy, params)
            m = jax.tree_util.tree_map(jnp.copy, momenta)
            loss, p, m = jstep32(p, m, x, y, rng0)  # compile + warmup
            float(loss)
            t0 = time.perf_counter()
            for i in range(steps):
                loss, p, m = jstep32(p, m, x, y, jax.random.fold_in(rng0, i))
            float(loss)
            dt = time.perf_counter() - t0
            f32_img_per_sec = batch_size * steps / dt
            result["resnet50_bf16_train_throughput"] = {
                "bf16_value": round(img_per_sec, 2),
                "f32_value": round(f32_img_per_sec, 2),
                "unit": "images/sec/chip",
                "speedup_vs_f32": round(img_per_sec / f32_img_per_sec, 4),
            }
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"amp bench failed: {type(e).__name__}: {e}\n")
            result["amp_error"] = f"{type(e).__name__}: {e}"
    mode = os.environ.get("BENCH_MODE", "both")
    if mode in ("both", "e2e"):
        try:
            e2e, e2e_fused = e2e_throughput(batch_size)
            result["e2e_value"] = round(e2e, 2)
            result["e2e_vs_synthetic"] = round(e2e / img_per_sec, 4)
            result["fused"] = bool(e2e_fused)
            if mode == "e2e":
                result["metric"] = "resnet50_train_throughput_e2e"
                result["value"] = round(e2e, 2)
                result["vs_baseline"] = round(e2e / NORTH_STAR, 4)
        except Exception as e:  # the synthetic number must still report
            sys.stderr.write(f"e2e path failed: {type(e).__name__}: {e}\n")
            result["e2e_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_SERVING", "1") == "1":
        try:
            result["serving_p99_latency"] = serving_latency()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"serving bench failed: {type(e).__name__}: {e}\n")
            result["serving_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_MULTICHIP", "1") == "1":
        try:
            result["multichip_train_throughput"] = _multichip_block()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"multichip bench failed: {type(e).__name__}: {e}\n")
            result["multichip_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_MP", "1") == "1":
        try:
            result["mp_sharded_train_throughput"] = _mp_sharded_block()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"mp-sharded bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["mp_sharded_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_MP_COMPUTE", "1") == "1":
        try:
            result["mp_compute_train_throughput"] = _mp_compute_block()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"mp-compute bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["mp_compute_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_TELEMETRY", "1") == "1":
        try:
            result["telemetry_overhead"] = telemetry_overhead()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"telemetry bench failed: {type(e).__name__}: {e}\n")
            result["telemetry_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_DECODE", "1") == "1":
        try:
            result["lm_decode_throughput"] = lm_decode_throughput()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"decode bench failed: {type(e).__name__}: {e}\n")
            result["decode_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_SPEC", "1") == "1":
        try:
            result["speculative_decode_throughput"] = \
                speculative_decode_throughput()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"speculative bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["spec_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_OVERLOAD", "1") == "1":
        try:
            result["overload_serving"] = overload_serving()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"overload bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["overload_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_PREFIX", "1") == "1":
        try:
            result["prefix_cache_serving"] = prefix_cache_serving()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"prefix-cache bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["prefix_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_QUANT", "1") == "1":
        try:
            result["quantized_serving"] = quantized_serving()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"quantized bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["quant_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_PALLAS", "1") == "1":
        try:
            result["pallas_kernels"] = pallas_kernels_bench()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"pallas bench failed: {type(e).__name__}: {e}\n")
            result["pallas_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_CKPT", "1") == "1":
        try:
            result["checkpoint_overhead"] = checkpoint_overhead()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"checkpoint bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["ckpt_error"] = f"{type(e).__name__}: {e}"
    if os.environ.get("BENCH_TRACING", "1") == "1":
        try:
            result["tracing_overhead"] = tracing_overhead()
        except Exception as e:  # optional block: failure is a field, not rc!=0
            sys.stderr.write(f"tracing bench failed: "
                             f"{type(e).__name__}: {e}\n")
            result["tracing_error"] = f"{type(e).__name__}: {e}"
    failed_blocks = [k for k in result if k.endswith("_error")]
    if failed_blocks:
        # a failed probe leaves a black box next to the artifact: dump the
        # flight recorder (spans/wide events/metrics of this very run) and
        # name the path in the result JSON
        try:
            from mxnet_tpu.observability import flight_recorder as _flight

            result["flight_record"] = _flight.dump(
                "bench_block_failed", extra={"blocks": failed_blocks})
        except Exception as e:
            result["flight_record_error"] = f"{type(e).__name__}: {e}"
    try:
        # every bench result carries the process registry (docs/
        # observability.md): compile-cache counters, serving p50/p99/QPS,
        # train telemetry — the run's health next to its headline number
        from mxnet_tpu import observability as _obs

        result["registry"] = _obs.snapshot()
    except Exception as e:
        result["registry_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result))
    if failed_blocks:
        # the record above names each failed block; the exit code says so
        # too — a failure is a failure
        sys.exit(f"bench.py: blocks failed: {failed_blocks}")


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        print(json.dumps(multichip_train_throughput()))
    elif "--mp-sharded" in sys.argv:
        print(json.dumps(mp_sharded_train_throughput()))
    elif "--mp-compute" in sys.argv:
        print(json.dumps(mp_compute_train_throughput()))
    else:
        main()

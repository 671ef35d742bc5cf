"""Tiny configurations for the CPU rehearsals: the cells' own files with
the sizes cut (never used on the chip), and a run without the look for a
chip — the drivers' Python argument, not a flag of the command."""
import json
import time

from perfbench import harness

CPU_HOOKS = {"device_prefix": "/host:CPU",
             "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def fit_context(seed=7, seconds=1.0, trace=False, **hooks):
    cfg = harness.load_json("configs", "resnet50.json")
    cfg.update(num_layers=18, classes=10)
    # at batch 8 the gradients are some ten times larger against the
    # weights than at batch 256: the rate is cut so that a step stays as
    # small against bfloat16's resolution as it is at the cell's own size
    cfg["optimizer_params"]["learning_rate"] = 0.005
    traffic = harness.load_json("traffic", "fit-b256.json")
    traffic.update(batch=8, image=64, trace_seconds=0.5)
    return harness.Context(
        {"name": "resnet50-fit-b256", "chips": 1}, cfg, traffic, seed,
        seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, **hooks})


def generation_context(traffic="decode-sat", seed=7, seconds=1.5,
                       trace=False, n_layers=2, **hooks):
    cfg = harness.load_json("configs", "gpt2-large.json")
    cfg.update(vocab=256, d_model=64, n_heads=4, n_layers=n_layers,
               d_ff=256, max_len=128, decode_kernel="gather",
               service={"max_slots": 4, "block_size": 8, "num_blocks": 96,
                        "seq_buckets": [16, 64, 127]})
    # the open loop has no cell yet (PERF.md section 7): its mix is here
    t = harness.load_json("traffic", "decode-sat.json")
    if traffic == "chat-steady":
        t.update(generator="open_loop", drain_seconds=10)
    t.update(prompt={"median": 24, "sigma": 0.7, "min": 4, "max": 64},
             output={"median": 16, "sigma": 0.5, "min": 4, "max": 32},
             ramp_seconds=0.5, trace_seconds=0.4, clients=8, rounds=128,
             rate=20.0)
    return harness.Context(
        {"name": "gpt2-large-" + traffic, "chips": 1}, cfg, t, seed,
        seconds, trace, time.perf_counter(), require_tpu=False,
        hooks={**CPU_HOOKS, **hooks})


def drive(ctx):
    """Everything ``run.py`` does after its look for a chip; returns the
    result line as a dict."""
    import importlib

    import jax

    harness.CompileClock.install()
    driver = importlib.import_module(
        "perfbench.drivers." + ctx.config["driver"])
    line = harness.result_line(ctx, jax.devices(), driver.run(ctx))
    return json.loads(json.dumps(line))

"""CPU rehearsal of the ``block_diffusion`` driver at a tiny configuration:
the rest of a run after the look for a chip, for both values of
``--trace``; the control (the reference one precision down, in the
program's place) comes out not correct; and so does a run whose block step
is altered underneath.  Numbers from these runs are counts and control
flow, never device metrics.
"""
import json

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import tiny, tiny_bd

CELL = "sdar-30b-a3b-blockdiff-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_block_diffusion_rehearsal(trace):
    line = tiny.drive(tiny_bd.block_diffusion_context(trace=trace))
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
        assert line["metrics"]["serve_tok_s"]["value"] > 0
        return
    got = set(line["metrics"])
    # the CPU's trace names no operation as the chip's does, and its one
    # plane is read as the device: the metrics that search operations or
    # host spans by name find nothing here (and leave themselves out)
    by_name = {"bd.moe_roofline_pct", "bd.block_attn_roofline_pct",
               "bd.route_device_ms", "bd.sample_device_ms",
               "bd.host_iter_ms", "bd.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["bd.tokens_per_row_pass"] <= 1.0
    assert 0 < m["bd.commit_pass_share_pct"] < 50
    assert 0 < m["bd.batch_occupancy_pct"] <= 100
    assert 0 < m["bd.experts_touched_pct"] <= 100
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench.drivers import block_diffusion as bd

    out = bd.run(tiny_bd.block_diffusion_context(trace=True))
    c = out["sources"].counters
    assert c["slice.moe_bytes"] > 0 and c["slice.moe_flops"] > 0
    assert c["slice.block_kv_bytes"] > 0 and c["slice.block_passes"] > 0
    assert not _failed(out["checks"])


def test_control_is_not_correct():
    """bfloat16 in the program's place, at a depth where its error shows
    (the tiny two-layer model is too shallow for the chip's limits)."""
    from perfbench.drivers import block_diffusion as bd

    out = bd.run(tiny_bd.block_diffusion_context(n_layers=8, control=True))
    assert "logit_rms_rel" in _failed(out["checks"])


def test_altered_block_step_is_not_correct():
    """The timed path broken underneath: every token a denoise pass
    unmasks is served as the next id up from the one the program chose."""
    def wrap(svc):
        inner = svc._programs.run_block

        def run_block(*args):
            unmasked, touched, logits = inner(*args)
            unmasked = np.where(unmasked >= 0, (unmasked + 1) % 96, -1)
            return unmasked, touched, logits

        svc._programs.run_block = run_block

    from perfbench.drivers import block_diffusion as bd

    out = bd.run(tiny_bd.block_diffusion_context(wrap_service=wrap))
    assert {"tie_gap_max", "tie_gap_mean"} & set(_failed(out["checks"]))


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(cfg["file"]) as f:
        config = json.load(f)
    published = config["published"]
    for key, value in published.items():      # only the depth is cut
        if key in cfg["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert cfg["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["vocab"] == published["vocab_size"]
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]

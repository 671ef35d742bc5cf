"""CPU rehearsal of the ``reason_decode`` driver at a tiny configuration:
the rest of a run after the look for a chip, for both values of
``--trace``; the control (the reference one precision down) and the three
planted faults (lam = 0; the cross layers reading zeros for the full
layer's K and V; M = 1) come out not correct, and so does a run whose
decode step is altered underneath.  Numbers from these runs are counts and
control flow, never device metrics.
"""
import json

import pytest

from perfbench import harness
from perfbench.tests import tiny, tiny_sby

CELL = "phi-4-mini-flash-reason-decode-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_reason_decode_rehearsal(trace):
    line = tiny.drive(tiny_sby.reason_decode_context(trace=trace))
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
        assert line["metrics"]["serve_tok_s"]["value"] > 0
        return
    got = set(line["metrics"])
    # the CPU's trace names no operation as the chip's does: the metrics
    # that search operations or host spans by name find nothing here
    by_name = {n for n in _metrics_of_cell() if n.endswith("_roofline_pct")} \
        | {"sby.prefill_device_share_pct", "sat.host_iter_ms", "sat.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["sat.batch_occupancy_pct"] <= 110
    assert m["sat.iter_ms"] > 0 and m["sat.preemptions"] == 0
    assert 0 < m["sby.steps_ahead_pct"] <= 100
    # every prompt position but a prompt's last ran no cross-decoder
    assert 80 < m["sby.cross_skipped_pct"] < 100
    # 3 state-space layers x (16 + 8) x 128 float32 as stored
    assert m["sby.state_bytes_per_slot"] == 3 * 24 * 128 * 4
    # window_blocks(8, 1, 4) = 4 a row at rest
    assert 0 < m["sby.window_blocks_per_row"] <= 4
    assert m["sby.cache_bytes_per_token"] > 0
    assert 0 < m["sat.kv_peak_occupancy_pct"] < 100
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench import counts_sambay as cs
    from perfbench.drivers import reason_decode as rd

    out = rd.run(tiny_sby.reason_decode_context(trace=True))
    c = out["sources"].counters
    # (half a second of slice on a loaded host may hold no decode step:
    # the counters are there, and follow from the slice's own counts)
    for name in ("cross_decode_bytes", "window_decode_bytes",
                 "ssm_decode_bytes", "ssm_decode_flops",
                 "scan_prefill_bytes", "prefill_attn_flops"):
        assert c["slice." + name] >= 0, name
    assert c["slice.ssm_decode_bytes"] == cs.ssm_decode_bytes(
        c["slice.ssm_decode_rows"], 128, 16, 4, 3)
    # ONE full layer's K and V (4 KV heads of 8), read by 3 layers
    assert c["slice.cross_decode_bytes"] == \
        c["slice.full_ctx_tokens"] * 4 * 2 * 8 * 2 * 3
    assert c["ssm_decode_rows"] > 0 < c["ssm_prefill_tokens"]
    assert c["ssm_prefill_tokens"] == c["prefill_tokens"]
    assert c["ssm_prefill_chunks"] >= c["ssm_rows_started"] > 0
    assert c["cross_prompt_positions"] == c["ssm_prefill_tokens"]
    assert c["cross_positions_run"] == c["ssm_rows_started"]
    assert c["decode_steps"] == c["steps_ahead"] + c["steps_drained"]
    assert c["state_bytes_per_slot"] >= cs.state_bytes_per_slot(128, 16, 4, 3)
    assert not _failed(out["checks"])


@pytest.mark.parametrize("hook", [
    dict(control=True), dict(fault="no_lambda"), dict(fault="no_shared_kv"),
    dict(fault="no_memory")],
    ids=["control", "no_lambda", "no_shared_kv", "no_memory"])
def test_the_control_and_the_planted_faults_are_not_correct(hook):
    """The reference one precision down in the program's place, and the
    reference with lam = 0, with the shared K and V zeroed, with M = 1."""
    from perfbench.drivers import reason_decode as rd

    out = rd.run(tiny_sby.reason_decode_context(**hook))
    assert "logit_row_med_rel" in _failed(out["checks"])


def test_altered_decode_step_is_not_correct():
    """The timed path broken underneath: every decode step serves the
    next id up from the one the program chose."""
    def wrap(svc):
        inner = svc._programs.run

        def run(kind, *args):
            toks, last = inner(kind, *args)
            return ((toks + 1) % 97 if kind == "gen_decode" else toks), last

        svc._programs.run = run

    from perfbench.drivers import reason_decode as rd

    out = rd.run(tiny_sby.reason_decode_context(wrap_service=wrap))
    assert {"tie_gap_max", "tie_gap_mean"} & set(_failed(out["checks"]))


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "reason-decode-sat"
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(cfg["file"]) as f:
        config = json.load(f)
    assert cfg["reduced"] == config["reduced"] == []
    for key, value in config["published"].items():  # nothing is cut
        assert config[key] == value, key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            config["sliding_window"]) == (2560, 40, 20, 10240, 32, 200064,
                                          512)
    assert config["vocab"] == config["vocab_size"]
    assert config["assumed_values"] == {"d_state": 16, "d_conv": 4,
                                        "expand": 2, "dt_rank": 160}
    assert {"mamba_sizes", "layer_kinds", "norms", "positions",
            "differential_attention", "window", "biases", "memory",
            "state_dtype", "weights", "sampling"} <= set(config["assumed"])
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic == {
        "generator": "closed_loop", "chips": 1, "clients": 256,
        "prompt": {"median": 1024, "sigma": 0.9, "min": 128, "max": 8192},
        "output": {"median": 3072, "sigma": 0.7, "min": 512, "max": 16384},
        "sampling": "greedy", "shared_prefix": 0, "ramp_seconds": 30,
        "trace_seconds": 3, "rounds": 6}
    service = config["service"]
    assert service["max_slots"] == 128
    assert traffic["clients"] == 2 * service["max_slots"]
    # the same 576 k tokens of the full kind as 36,000 blocks of 16
    assert service["num_blocks"] * service["block_size"] == 36000 * 16
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert traffic["prompt"]["max"] <= service["seq_buckets"][-1]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    # nine of gpt2-large-decode-sat's (the same layers) and eleven of its own
    assert len(_metrics_of_cell()) == 20
    assert len({n for n in _metrics_of_cell() if n.startswith("sat.")}) == 9
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]


def test_the_parameter_count_is_the_published_one():
    """3.85 B +- 1% from the shapes the reference makes its weights in."""
    from perfbench.reference import phi4_flash as ref

    config = harness.load_json("configs", "phi-4-mini-flash.json")
    n = 0
    for shape in ref.param_shapes(config).values():
        size = 1
        for dim in shape:
            size *= dim
        n += size
    assert abs(n / 3.85e9 - 1) < 0.01, n


def test_the_reference_imports_nothing_of_the_program():
    import perfbench.reference.phi4_flash as ref

    with open(ref.__file__) as f:
        assert "mxnet_tpu" not in f.read()

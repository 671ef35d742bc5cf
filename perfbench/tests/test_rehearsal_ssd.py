"""CPU rehearsal of the ``ssd_decode`` driver at a tiny configuration: the
rest of a run after the look for a chip, for both values of ``--trace``; the
control (the reference one precision down) and the four planted faults (a
state not carried across a chunk boundary; the residual multiplier dropped;
``1/sqrt(head size)`` for the attention multiplier; the norm before the
gate) come out not correct, and so does a run whose decode step is altered
underneath.  Numbers from these runs are counts and control flow, never
device metrics.
"""
import json

import pytest

from perfbench import harness
from perfbench.reference import granite_hybrid as ref
from perfbench.tests import tiny, tiny_ssd

CELL = "granite-4.0-h-micro-chat-decode-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_ssd_decode_rehearsal(trace):
    line = tiny.drive(tiny_ssd.ssd_decode_context(trace=trace))
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
        | {"ssd.prefill_device_share_pct", "sat.host_iter_ms", "sat.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["sat.batch_occupancy_pct"] <= 110
    assert m["sat.iter_ms"] > 0 and m["sat.preemptions"] == 0
    assert 0 < m["ssd.steps_ahead_pct"] <= 100
    assert 0 < m["ssd.rows_started_per_iter"] <= 4
    # 4 Mamba-2 layers x (16 + 8) x 128 float32 as stored
    assert m["ssd.state_bytes_per_slot"] == 4 * 24 * 128 * 4
    assert m["ssd.cache_bytes_per_token"] > 0
    assert 0 < m["sat.kv_peak_occupancy_pct"] < 100
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench import counts_ssd as cs
    from perfbench.drivers import ssd_decode as sd

    out = sd.run(tiny_ssd.ssd_decode_context(trace=True))
    c = out["sources"].counters
    # (half a second of slice on a loaded host may hold no decode step:
    # the counters are there, and follow from the slice's own counts)
    for name in ("full_decode_bytes", "ssd_decode_bytes", "ssd_decode_flops",
                 "scan_prefill_bytes", "scan_prefill_flops",
                 "prefill_attn_flops", "prefill_attn_bytes"):
        assert c["slice." + name] >= 0, name
    assert c["slice.ssd_decode_bytes"] == cs.ssd_decode_bytes(
        c["slice.ssd_decode_rows"], 4, 32, 16, 4, 4)
    # two attention layers' K and V (2 KV heads of 8, float32 here counted
    # as the cell's 2 B)
    assert c["slice.full_decode_bytes"] == \
        c["slice.full_ctx_tokens"] * 2 * 2 * 8 * 2 * 2
    assert c["ssd_decode_rows"] > 0 < c["ssd_prefill_tokens"]
    assert c["ssd_prefill_tokens"] == c["prefill_tokens"]
    assert c["ssd_prefill_chunks"] == c["prefill_chunks"] \
        >= c["ssd_rows_started"] > 0
    assert c["decode_steps"] == c["steps_ahead"] + c["steps_drained"]
    assert c["state_bytes_per_slot"] >= cs.state_bytes_per_slot(4, 32, 16, 4,
                                                                4)
    assert not _failed(out["checks"])


@pytest.mark.parametrize("hook", [dict(control=True)] + [
    dict(fault=f) for f in ref.FAULTS], ids=("control",) + ref.FAULTS)
def test_the_control_and_the_planted_faults_are_not_correct(hook):
    """The reference one precision down in the program's place, and the
    reference with each planted fault.  (Six layers deep the control's
    median row reads 0.024, a quarter of what forty layers give it on the
    chip, and it is its worst row, 0.069, that passes the limit.)"""
    from perfbench.drivers import ssd_decode as sd

    out = sd.run(tiny_ssd.ssd_decode_context(**hook))
    failed = set(_failed(out["checks"]))
    if "control" in hook:
        assert failed & {"logit_row_med_rel", "logit_row_max_rel"}
    else:
        assert "logit_row_med_rel" in failed


def test_altered_decode_step_is_not_correct():
    """The timed path broken underneath: every decode step serves the
    next id up from the one the program chose."""
    def wrap(svc):
        inner = svc._programs.run

        def run(kind, *args):
            toks, last = inner(kind, *args)
            return ((toks + 1) % 97 if kind == "gen_decode" else toks), last

        svc._programs.run = run

    from perfbench.drivers import ssd_decode as sd

    out = sd.run(tiny_ssd.ssd_decode_context(wrap_service=wrap))
    assert {"tie_gap_max", "tie_gap_mean"} & set(_failed(out["checks"]))


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "chat-decode-sat"
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(cfg["file"]) as f:
        config = json.load(f)
    assert cfg["reduced"] == config["reduced"] == []
    assert cfg["source"] == config["source"]
    for key, value in config["published"].items():  # nothing is cut
        assert config[key] == value, key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["shared_intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"], config["mamba_d_conv"]) == (
                2048, 32, 8, 8192, 40, 100352, 64, 64, 128, 4)
    assert [i for i, k in enumerate(config["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert (config["attention_multiplier"], config["embedding_multiplier"],
            config["residual_multiplier"], config["logits_scaling"]) == (
                0.015625, 12, 0.22, 8)
    assert config["vocab"] == config["vocab_size"]
    assert {"conv_over_xBC", "in_proj_order", "gate_then_norm",
            "scalar_A_and_D", "step", "attention_multiplier",
            "logits_scaling", "residual_multiplier", "mamba_chunk_size",
            "state_dtype", "state_layout", "max_len", "weights",
            "sampling"} <= set(config["assumed"])
    assert config["precision"] and config["deployment"] \
        and config["service_how"]
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic == {
        "generator": "closed_loop", "chips": 1, "clients": 128,
        "prompt": {"median": 768, "sigma": 1.1, "min": 64, "max": 16384},
        "output": {"median": 512, "sigma": 0.7, "min": 64, "max": 2048},
        "sampling": "greedy", "shared_prefix": 0, "ramp_seconds": 30,
        "trace_seconds": 3, "rounds": 6}
    service = config["service"]
    assert service["max_slots"] == 64 and service["block_size"] == 32
    assert traffic["clients"] == 2 * service["max_slots"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert traffic["prompt"]["max"] <= service["seq_buckets"][-1]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    # fourteen of trinity-mini-mixedlen-decode-sat's and nine of its own
    assert len(_metrics_of_cell()) == 23
    assert len({n for n in _metrics_of_cell() if n.startswith("sat.")}) == 14
    assert len({n for n in _metrics_of_cell() if n.startswith("ssd.")}) == 9
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]


def test_the_parameter_count_is_the_published_one():
    """3,191,396,096 (the published "3B") from the shapes the reference
    makes its weights in."""
    config = harness.load_json("configs", "granite-4.0-h-micro.json")
    n = 0
    for shape in ref.param_shapes(config).values():
        size = 1
        for dim in shape:
            size *= dim
        n += size
    assert n == 3191396096


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        assert "mxnet_tpu" not in f.read()

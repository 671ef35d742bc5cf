"""CPU rehearsal of the ``retention_decode`` driver at a tiny configuration:
the rest of a run after the look for a chip, for both values of
``--trace``; the control (the reference one precision down) and the planted
faults (the gate left out of the reference, its normaliser left out) come
out not correct, and so does a run whose decode step is altered underneath.
Numbers from these runs are counts and control flow, never device metrics.
"""
import json

import pytest

from perfbench import harness
from perfbench.tests import tiny, tiny_ret

CELL = "brumby-14b-retention-decode-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_retention_decode_rehearsal(trace):
    line = tiny.drive(tiny_ret.retention_decode_context(trace=trace))
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
        assert line["metrics"]["serve_tok_s"]["value"] > 0
        return
    got = set(line["metrics"])
    # the CPU's trace names no operation as the chip's does: the metrics
    # that search operations or host spans by name find nothing here
    by_name = {"ret.state_decode_roofline_pct",
               "ret.scan_prefill_roofline_pct",
               "ret.prefill_device_share_pct", "sat.host_iter_ms",
               "sat.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["sat.batch_occupancy_pct"] <= 110
    assert m["sat.iter_ms"] > 0 and m["sat.preemptions"] == 0
    assert 0 < m["ret.steps_ahead_pct"] <= 100
    assert 0 < m["ret.rows_started_per_iter"] < 1
    # 3 layers x 2 KV heads x 5 rows x (8 + 8) x 8 float32 as stored (S
    # over z and its tile's padding; 36 of a row's 40 lanes by the
    # mathematics)
    assert m["ret.state_bytes_per_slot"] == 3 * 2 * 5 * 16 * 8 * 4
    # slots live over slots: every slot was taken at some time
    assert m["sat.kv_peak_occupancy_pct"] == 100
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench import counts_retention as cr
    from perfbench.drivers import retention_decode as rd

    out = rd.run(tiny_ret.retention_decode_context(trace=True))
    c = out["sources"].counters
    for name in ("state_decode_bytes", "state_decode_flops",
                 "scan_prefill_flops", "scan_prefill_bytes"):
        assert c["slice." + name] > 0, name
    assert c["slice.state_decode_bytes"] == cr.decode_state_bytes(
        c["slice.retention_decode_rows"], 2, 8, 8, 3)
    assert c["retention_decode_rows"] > 0 < c["retention_prefill_tokens"]
    assert c["retention_prefill_tokens"] == c["prefill_tokens"]
    assert c["retention_prefill_chunks"] >= c["retention_rows_started"] > 0
    assert c["decode_steps"] == c["steps_ahead"] + c["steps_drained"]
    assert c["state_bytes_per_slot"] >= cr.state_bytes_per_slot(2, 8, 8, 3)
    assert not _failed(out["checks"])


@pytest.mark.parametrize("hook", [dict(control=True), dict(fault="no_gate"),
                                  dict(fault="no_norm")],
                         ids=["control", "no_gate", "no_norm"])
def test_the_control_and_the_planted_faults_are_not_correct(hook):
    """The reference one precision down in the program's place, and the
    reference with its gate or its normaliser left out."""
    from perfbench.drivers import retention_decode as rd

    out = rd.run(tiny_ret.retention_decode_context(**hook))
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

    from perfbench.drivers import retention_decode as rd

    out = rd.run(tiny_ret.retention_decode_context(wrap_service=wrap))
    assert {"tie_gap_max", "tie_gap_mean"} & set(_failed(out["checks"]))


def test_one_wrong_probe_row_is_not_correct():
    """What the worst row's limit is held against: one of the probe's
    sequences gets another position's logits at every decode step (two
    rows of like spread: sqrt 2, whatever the seed)."""
    import numpy as np

    def wrap(svc):
        inner = svc._programs.run

        def run(kind, *args):
            toks, last = inner(kind, *args)
            if kind == "gen_decode":
                last = np.array(last)
                last[0] = np.roll(last[0], 7)
            return toks, last

        svc._programs.run = run

    from perfbench.drivers import retention_decode as rd

    out = rd.run(tiny_ret.retention_decode_context(wrap_service=wrap))
    assert _failed(out["checks"]) == ["logit_row_max_rel"]
    worst = {c[0]: c[1] for c in out["checks"]}["logit_row_max_rel"]
    assert 1.2 < worst < 1.6


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "retention-decode-sat"
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(cfg["file"]) as f:
        config = json.load(f)
    published = config["published"]
    for key, value in published.items():     # no width is cut
        if key in cfg["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert cfg["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6 \
        and published["num_hidden_layers"] == 40
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["vocab_size"],
            config["max_position_embeddings"]) == (
        5120, 40, 8, 128, 17408, 151936, 32768)
    assert config["vocab"] == config["vocab_size"]
    assert config["max_len"] == published["max_position_embeddings"]
    assert "pipeline stages of 6 layers" in config["deployment"]
    assert {"power", "gate", "retention_eps", "state_layout",
            "phi_operands", "weights"} <= set(config["assumed"])
    assert "attention_form_switch" in config["left_out"]
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic == {
        "generator": "closed_loop", "chips": 1, "clients": 48,
        "prompt": {"median": 2048, "sigma": 1.0, "min": 256, "max": 24576},
        "output": {"median": 1024, "sigma": 0.5, "min": 256, "max": 4096},
        "sampling": "greedy", "shared_prefix": 0, "ramp_seconds": 20,
        "trace_seconds": 3, "rounds": 6}
    assert config["service"] == {"max_slots": 24,
                                 "seq_buckets": [128, 512, 24576]}
    assert traffic["clients"] == 2 * config["service"]["max_slots"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert traffic["prompt"]["max"] <= config["service"]["seq_buckets"][-1]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    # nine of gpt2-large-decode-sat's (scheduler, entry, cache, step,
    # device: the same layers) and six of its own
    assert len(_metrics_of_cell()) == 15
    assert len({n for n in _metrics_of_cell() if n.startswith("sat.")}) == 9
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]


def test_the_reference_imports_nothing_of_the_program():
    import perfbench.reference.brumby as ref

    with open(ref.__file__) as f:
        assert "mxnet_tpu" not in f.read()

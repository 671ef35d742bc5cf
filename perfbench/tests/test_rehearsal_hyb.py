"""CPU rehearsal of the ``hybrid_decode`` driver at a tiny configuration:
the rest of a run after the look for a chip, for both values of
``--trace``; the planted faults (the sink left out of the reference, its
window a block short) come out not correct, and so does a run whose decode
step is altered underneath.  Numbers from these runs are counts and control
flow, never device metrics.
"""
import json

import pytest

from perfbench import harness
from perfbench.tests import tiny, tiny_hyb

CELL = "mimo-v2.5-hybrid-decode-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_hybrid_decode_rehearsal(trace):
    line = tiny.drive(tiny_hyb.hybrid_decode_context(trace=trace))
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
        assert line["metrics"]["serve_tok_s"]["value"] > 0
        return
    got = set(line["metrics"])
    # the CPU's trace names no operation as the chip's does: the metrics
    # that search operations or host spans by name find nothing here
    by_name = {"hyb.full_decode_roofline_pct",
               "hyb.window_decode_roofline_pct",
               "hyb.prefill_attn_roofline_pct", "hyb.moe_roofline_pct",
               "hyb.prefill_device_share_pct", "sat.host_iter_ms",
               "sat.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["sat.batch_occupancy_pct"] <= 110
    assert m["sat.iter_ms"] > 0 and m["sat.preemptions"] == 0
    assert 0 < m["hyb.steps_ahead_pct"] <= 100
    assert 0 < m["hyb.assignments_held_pct"] < 100
    assert m["hyb.expert_load_max_over_mean"] >= 1.0
    # window 8, blocks of 4: window_blocks(8, 1, 4) = 4 a row at rest, and
    # window_blocks(8, 64, 4) = 19 for the one row inside a 64-token chunk
    assert 0 < m["hyb.window_blocks_per_row"] <= (3 * 4 + 19) / 4
    # a token costs 1 x (24 + 16) x 4 B in the full layer; the window
    # layers' fixed share is most of a short row's
    assert m["hyb.cache_bytes_per_token"] > 160
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench.drivers import hybrid_decode as hd

    out = hd.run(tiny_hyb.hybrid_decode_context(trace=True))
    c = out["sources"].counters
    for name in ("full_decode_bytes", "window_decode_bytes",
                 "full_decode_flops", "prefill_attn_flops", "moe_bytes",
                 "moe_flops"):
        assert c["slice." + name] > 0, name
    assert c["full_ctx_tokens"] > c["window_ctx_tokens"] > 0
    assert c["full_prefill_pairs"] > c["window_prefill_pairs"] > 0
    assert c["window_blocks_freed"] > 0
    assert c["expert_assignments"] > c["expert_assignments_held"] > 0
    assert c["decode_steps"] == c["steps_ahead"] + c["steps_drained"]
    assert not _failed(out["checks"])


@pytest.mark.parametrize("fault", ["no_sink", "short_window"])
def test_a_planted_fault_is_not_correct(fault):
    """The reference with the sink left out, or with a window 16 positions
    short (the tiny window is 32 here so that a block fits inside it)."""
    from perfbench.drivers import hybrid_decode as hd

    ctx = tiny_hyb.hybrid_decode_context(fault=fault)
    if fault == "short_window":
        ctx.config["sliding_window"] = 32
    out = hd.run(ctx)
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

    from perfbench.drivers import hybrid_decode as hd

    out = hd.run(tiny_hyb.hybrid_decode_context(wrap_service=wrap))
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

    from perfbench.drivers import hybrid_decode as hd

    out = hd.run(tiny_hyb.hybrid_decode_context(wrap_service=wrap))
    assert _failed(out["checks"]) == ["logit_row_max_rel"]
    worst = {c[0]: c[1] for c in out["checks"]}["logit_row_max_rel"]
    assert 1.2 < worst < 1.6


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "hybrid-decode-sat"
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(cfg["file"]) as f:
        config = json.load(f)
    published = config["published"]
    for key, value in published.items():     # no width is cut
        if key in cfg["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert cfg["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["head_dim"], config["v_head_dim"],
            config["num_key_value_heads"], config["swa_num_key_value_heads"],
            config["sliding_window"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            published["n_routed_experts"]) == (
        4096, 64, 192, 128, 4, 8, 128, 16384, 2048, 8, 256)
    n = config["num_hidden_layers"]
    assert config["hybrid_layer_pattern"][:n] == [0, 1, 1, 1, 1, 0, 1]
    assert config["moe_layer_freq"][:n] == [0, 1, 1, 1, 1, 1, 1]
    lo, hi = config["experts_held"]
    assert hi - lo == config["n_routed_experts"] == 16
    assert config["vocab"] == config["vocab_size"] == 19072
    assert config["vocab"] * 8 == published["vocab_size"]
    assert "16 chips share each layer" in config["deployment"]
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic == {
        "generator": "closed_loop", "chips": 1, "clients": 256,
        "prompt": {"median": 3072, "sigma": 0.9, "min": 256, "max": 14336},
        "output": {"median": 768, "sigma": 0.5, "min": 128, "max": 2048},
        "sampling": "greedy", "shared_prefix": 0, "ramp_seconds": 30,
        "trace_seconds": 3, "rounds": 6}
    assert traffic["clients"] == 2 * config["service"]["max_slots"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert traffic["prompt"]["max"] <= config["service"]["seq_buckets"][-1]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    # nine of gpt2-large-decode-sat's (scheduler, entry, cache, step,
    # device: the same layers) and ten of its own
    assert len(_metrics_of_cell()) == 19
    assert len({n for n in _metrics_of_cell() if n.startswith("sat.")}) == 9
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]


def test_the_reference_imports_nothing_of_the_program():
    import perfbench.reference.mimo_v2 as ref

    with open(ref.__file__) as f:
        assert "mxnet_tpu" not in f.read()

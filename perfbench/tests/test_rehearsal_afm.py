"""CPU rehearsal of the ``afmoe_decode`` driver at a tiny configuration: the
rest of a run after the look for a chip, for both values of ``--trace``; the
control (the reference one precision down) and the four planted faults come
out not correct, and so does a run whose decode step is altered underneath.
Numbers from these runs are counts and control flow, never device metrics.
"""
import json

import pytest

from perfbench import harness
from perfbench.reference import afmoe as ref
from perfbench.tests import tiny, tiny_afm

CELL = "trinity-mini-mixedlen-decode-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_afmoe_decode_rehearsal(trace):
    line = tiny.drive(tiny_afm.afmoe_decode_context(trace=trace))
    assert line["correct"] is True
    assert line["attempted"] >= 4 and line["failed"] == 0
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "serve_tok_s"}
        assert line["metrics"]["serve_tok_s"]["value"] > 0
        return
    got = set(line["metrics"])
    # the CPU's trace names no operation as the chip's does, and without
    # the kernel no trip is counted: the metrics that search operations or
    # host spans by name find nothing here
    by_name = {"afm.full_decode_roofline_pct",
               "afm.window_decode_roofline_pct",
               "afm.prefill_attn_roofline_pct", "afm.moe_roofline_pct",
               "afm.prefill_device_share_pct", "sat.host_iter_ms",
               "sat.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["sat.batch_occupancy_pct"] <= 110
    assert m["sat.iter_ms"] > 0 and m["sat.preemptions"] == 0
    assert 0 < m["afm.steps_ahead_pct"] <= 100
    assert 0 < m["afm.assignments_held_pct"] < 100
    assert m["afm.expert_load_max_over_mean"] >= 1.0
    assert m["afm.window_trips_per_row"] == 0       # no kernel, no trip
    # prompts from 5 and a window of 16: some rows decode under it
    assert 0 < m["afm.window_rows_past_pct"] < 100
    # window 16, blocks of 4: window_blocks(16, 1, 4) = 6 a row at rest,
    # and window_blocks(16, 64, 4) = 21 for the one row inside a chunk
    assert 0 < m["afm.window_blocks_per_row"] <= (3 * 6 + 21) / 4
    assert 0 < m["afm.window_pool_used_pct"] <= 100
    # a token costs 2 x (16 + 16) x 4 B in the one full layer; the window
    # layers' share is most of a short row's
    assert m["afm.cache_bytes_per_token"] > 256
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench.drivers import afmoe_decode as ad

    out = ad.run(tiny_afm.afmoe_decode_context(trace=True))
    c = out["sources"].counters
    for name in ("full_decode_bytes", "window_decode_bytes",
                 "full_decode_flops", "prefill_attn_flops", "moe_bytes",
                 "moe_flops"):
        assert c["slice." + name] > 0, name
    assert c["full_ctx_tokens"] > c["window_ctx_tokens"] > 0
    assert c["full_prefill_pairs"] > c["window_prefill_pairs"] > 0
    assert c["window_blocks_freed"] > 0
    assert c["expert_assignments"] > c["expert_assignments_held"] > 0
    assert c["shared_expert_tokens"] * 4 == c["expert_assignments"]
    assert 0 < c["window_rows_past"] < c["decode_rows"]
    assert c["window_layer_rows"] == 3 * c["decode_rows"]
    assert c["decode_steps"] == c["steps_ahead"] + c["steps_drained"]
    assert not _failed(out["checks"])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_not_correct(fault):
    from perfbench.drivers import afmoe_decode as ad

    out = ad.run(tiny_afm.afmoe_decode_context(fault=fault))
    assert "logit_row_med_rel" in _failed(out["checks"])


def test_the_control_is_not_correct():
    """The reference one precision down in the program's place."""
    from perfbench.drivers import afmoe_decode as ad

    out = ad.run(tiny_afm.afmoe_decode_context(control=True))
    assert "logit_row_med_rel" in _failed(out["checks"])


def test_the_probe_crosses_the_window_and_a_block_while_it_decodes():
    from perfbench.drivers import afmoe_decode as ad

    ctx = tiny_afm.afmoe_decode_context()

    class G:
        block_size, max_slots = 4, 4
    lens = ad.probe_lengths(ctx, G)
    assert lens[0] == 16 - ad.PROBE_DECODE // 2
    assert lens[1] % 4 == 4 - ad.PROBE_DECODE // 2 and len(lens) == 4
    assert max(lens) == 64 > 16 + 16       # window blocks freed and reused


def test_altered_decode_step_is_not_correct():
    """The timed path broken underneath: every decode step serves the
    next id up from the one the program chose."""
    def wrap(svc):
        inner = svc._programs.run

        def run(kind, *args):
            toks, last = inner(kind, *args)
            return ((toks + 1) % 97 if kind == "gen_decode" else toks), last

        svc._programs.run = run

    from perfbench.drivers import afmoe_decode as ad

    out = ad.run(tiny_afm.afmoe_decode_context(wrap_service=wrap))
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

    from perfbench.drivers import afmoe_decode as ad

    out = ad.run(tiny_afm.afmoe_decode_context(wrap_service=wrap))
    assert _failed(out["checks"]) == ["logit_row_max_rel"]
    worst = {c[0]: c[1] for c in out["checks"]}["logit_row_max_rel"]
    assert 1.2 < worst < 1.6


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "mixedlen-decode-sat"
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(cfg["file"]) as f:
        config = json.load(f)
    published = config["published"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = [json.loads(line) for line in f
               if '"name": "Trinity-Mini"' in line]
    if row:     # the catalog's config, every key under the same key
        assert published == row[0]["config"]
        assert cfg["source"] == config["source"] == row[0]["source_url"]
    for key, value in published.items():     # no width is cut
        if key in cfg["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert cfg["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts"]
    assert (config["hidden_size"], config["num_attention_heads"],
            config["head_dim"], config["num_key_value_heads"],
            config["sliding_window"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["num_shared_experts"], published["num_experts"],
            config["vocab_size"]) == (
        2048, 32, 128, 4, 2048, 6144, 1024, 8, 1, 128, 200192)
    n = config["num_hidden_layers"]
    assert n == 16 and config["num_dense_layers"] == 2
    assert config["layer_types"][:n] == (["sliding_attention"] * 3
                                         + ["full_attention"]) * 4
    lo, hi = config["experts_held"]
    assert hi - lo == config["num_experts"] == 16
    assert config["vocab"] == config["vocab_size"]
    assert "8 chips share each layer" in config["deployment"]
    assert set(config["reduced_how"]) == set(config["reduced"])
    assert all(len(why) > 20 for why in config["assumed"].values())
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic == {
        "generator": "closed_loop", "chips": 1, "clients": 128,
        "prompt": {"median": 1536, "sigma": 1.3, "min": 128, "max": 24576},
        "output": {"median": 1024, "sigma": 0.6, "min": 128, "max": 4096},
        "sampling": "greedy", "shared_prefix": 0, "ramp_seconds": 30,
        "trace_seconds": 3, "rounds": 6}
    assert traffic["clients"] == 2 * config["service"]["max_slots"]
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert traffic["prompt"]["max"] <= config["service"]["seq_buckets"][-1]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    # nine of gpt2-large-decode-sat's (scheduler, entry, cache, step,
    # device: the same layers) and thirteen of its own
    assert len(_metrics_of_cell()) == 22
    assert len({n for n in _metrics_of_cell() if n.startswith("sat.")}) == 9
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]
        entry = {m["name"]: m for m in bench["per_layer"]}[name]
        assert (entry["layer"], entry["unit"]) == (spec["layer"],
                                                   spec["unit"])


def test_the_mix_s_lengths_are_what_the_issue_says():
    """59% of the prompts are shorter than the window, a tenth are over
    8 k, 2% sit at the cap; the mean prompt is ~3.3 k, the mean output
    ~1.2 k."""
    from perfbench.generators.requests import lognormal_set

    t = harness.load_json("traffic", "mixedlen-decode-sat.json")
    p = lognormal_set(128, **{k: t["prompt"][k] for k in ("median", "sigma")},
                      lo=t["prompt"]["min"], hi=t["prompt"]["max"])
    o = lognormal_set(128, **{k: t["output"][k] for k in ("median", "sigma")},
                      lo=t["output"]["min"], hi=t["output"]["max"])
    assert 0.55 < (p < 2048).mean() < 0.62
    assert 0.08 < (p > 8192).mean() < 0.12
    assert 0.01 < (p == 24576).mean() < 0.04
    assert 3000 < p.mean() < 3600 and 1100 < o.mean() < 1300


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        assert "mxnet_tpu" not in f.read()

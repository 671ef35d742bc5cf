"""CPU rehearsal of the ``latent_decode`` driver at a tiny configuration:
the rest of a run after the look for a chip, for both values of
``--trace``; the control (the reference one precision down, in the
program's place) comes out not correct; and so do a run whose decode
step is altered underneath and one whose probe holds a wrong row.
Numbers from these runs are counts and control flow, never device
metrics.
"""
import json

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import tiny, tiny_lat

CELL = "dots-vlm1-latent-decode-sat"


def _failed(checks):
    return [c[0] for c in checks if not c[3]]


def _metrics_of_cell():
    return {m["name"] for m in harness.benchmark()["per_layer"]
            if CELL in m.get("workloads", [CELL])}


@pytest.mark.parametrize("trace", [False, True])
def test_latent_decode_rehearsal(trace):
    line = tiny.drive(tiny_lat.latent_decode_context(trace=trace))
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
    by_name = {"lat.mla_decode_roofline_pct", "lat.mla_prefill_roofline_pct",
               "lat.moe_roofline_pct", "lat.route_device_ms",
               "lat.sample_device_ms", "lat.prefill_device_share_pct",
               "sat.host_iter_ms", "sat.emit_ms"}
    assert _metrics_of_cell() - by_name <= got <= _metrics_of_cell()
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the scheduler's, the cache's and the device's metrics are the ones
    # gpt2-large-decode-sat reports: the same layers, the same reducers
    # a prefill's first token is emitted beside the step's: a hair over 100
    assert 0 < m["sat.batch_occupancy_pct"] <= 110
    assert m["sat.iter_ms"] > 0 and m["sat.preemptions"] == 0
    assert 0 < m["lat.steps_ahead_pct"] <= 100
    # experts 4-7 of 16, top-4 of the 2 best of 4 groups
    assert 0 < m["lat.assignments_held_pct"] < 100
    assert m["lat.expert_load_max_over_mean"] >= 1.0
    assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_slice_counters_feed_the_rooflines():
    from perfbench.drivers import latent_decode as ld

    out = ld.run(tiny_lat.latent_decode_context(trace=True))
    c = out["sources"].counters
    for name in ("mla_decode_bytes", "mla_decode_flops", "moe_bytes",
                 "moe_flops"):
        assert c["slice." + name] > 0, name
    assert c["latent_ctx_tokens"] > 0 and c["latent_prefill_pairs"] > 0
    assert c["expert_assignments"] > c["expert_assignments_held"] > 0
    assert c["decode_steps"] == c["steps_ahead"] + c["steps_drained"]
    assert not _failed(out["checks"])


def test_control_is_not_correct():
    """bfloat16 in the program's place, at a depth where its error shows
    (the tiny three-layer model is too shallow for the chip's limits)."""
    from perfbench.drivers import latent_decode as ld

    out = ld.run(tiny_lat.latent_decode_context(n_layers=9, control=True))
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

    from perfbench.drivers import latent_decode as ld

    out = ld.run(tiny_lat.latent_decode_context(wrap_service=wrap))
    assert {"tie_gap_max", "tie_gap_mean"} & set(_failed(out["checks"]))


def test_one_wrong_probe_row_is_not_correct():
    """What the worst row's limit is held against: one of the probe's
    sequences gets another position's logits at every decode step (the
    engine reads only the tokens of a step, so the window is served as it
    was)."""
    def wrap(svc):
        inner = svc._programs.run

        def run(kind, *args):
            toks, last = inner(kind, *args)
            if kind == "gen_decode":
                last = np.array(last)
                last[0] = np.roll(last[0], 7)
            return toks, last

        svc._programs.run = run

    from perfbench.drivers import latent_decode as ld

    out = ld.run(tiny_lat.latent_decode_context(wrap_service=wrap))
    assert _failed(out["checks"]) == ["logit_row_max_rel"]
    worst = {c[0]: c[1] for c in out["checks"]}["logit_row_max_rel"]
    assert 1.2 < worst < 1.6        # two rows of like spread: sqrt(2)


def test_every_seed_offers_one_schedule_with_ids_of_its_own():
    """The window ends inside the generator's first round, so the ORDER of
    the lengths is the cell's and not the seed's: the same prompt and
    output lengths request by request, the first generation's stagger
    too, and other token ids."""
    from perfbench.drivers import latent_decode as ld
    from perfbench.generators import closed_loop

    def specs(seed, wrap):
        ctx = tiny_lat.latent_decode_context(seed=seed)
        return closed_loop.Load(wrap(ctx), None).specs

    a, b = specs(7, ld._OneSchedule), specs(2147487911, ld._OneSchedule)
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert any((p != q).any() for (p, _), (q, _) in zip(a, b))
    again = specs(7, ld._OneSchedule)
    assert all((p == q).all() for (p, _), (q, _) in zip(a, again))
    plain = specs(7, lambda ctx: ctx)       # the generator's own order
    assert sorted(len(p) for p, _ in plain) == sorted(len(p) for p, _ in a)
    assert [len(p) for p, _ in plain] != [len(p) for p, _ in a]


def test_the_cell_s_files_are_what_benchmark_json_names():
    bench = harness.benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "latent-decode-sat"
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
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    lo, hi = config["experts_held"]
    assert hi - lo == config["n_routed_experts"] == 16
    assert config["vocab"] == config["vocab_size"] == 16160
    assert config["vocab"] * 8 == published["vocab_size"]
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    assert traffic == {
        "generator": "closed_loop", "chips": 1, "clients": 512,
        "prompt": {"median": 1024, "sigma": 0.8, "min": 128, "max": 4096},
        "output": {"median": 1024, "sigma": 0.5, "min": 256, "max": 2048},
        "sampling": "greedy", "shared_prefix": 0, "ramp_seconds": 20,
        "trace_seconds": 3, "rounds": 16}
    assert traffic["prompt"]["max"] + traffic["output"]["max"] \
        <= config["max_len"]
    assert traffic["prompt"]["max"] <= config["service"]["seq_buckets"][-1]
    assert CELL in {m["name"]: m for m in bench["end_to_end"]}[
        "serve_tok_s"]["workloads"]
    # nine of gpt2-large-decode-sat's (scheduler, entry, cache, step,
    # device: the same layers) and nine of its own
    assert len(_metrics_of_cell()) == 18
    assert len({n for n in _metrics_of_cell() if n.startswith("sat.")}) == 9
    for name in _metrics_of_cell():
        spec = harness.load_json("metrics", name + ".json")
        assert spec["reducer"] and spec["layer"]


def test_the_reference_imports_nothing_of_the_program():
    import perfbench.reference.dots_vlm1 as ref

    with open(ref.__file__) as f:
        assert "mxnet_tpu" not in f.read()

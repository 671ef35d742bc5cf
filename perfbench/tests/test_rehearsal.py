"""CPU rehearsals of each driver at a tiny configuration: the rest of a
run after the look for a chip, for both values of ``--trace``; the control
(the reference one precision down, in the program's place) comes out not
correct; and so does a run whose timed path is broken underneath.
Numbers from these runs are counts and control flow, never device metrics.
"""
import numpy as np
import pytest

from perfbench.tests import tiny


def _failed(line_or_checks):
    return [c[0] for c in line_or_checks if not c[3]]


@pytest.mark.parametrize("trace", [False, True])
def test_fit_rehearsal(trace):
    line = tiny.drive(tiny.fit_context(trace=trace))
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {"fit.input_wait_ms", "fit.step_device_ms", "fit.mfu_pct",
            "fit.compiles_in_window", "fit.device_idle_pct"} if trace \
        else {"setup_s", "train_img_s"}
    assert set(line["metrics"]) == want
    assert (line["metrics"].get("fit.compiles_in_window",
                                {"value": 0})["value"]) == 0
    if trace:
        assert line["device"]["busy_s"] > 0 and line["breakdown"]


def test_fit_control_is_not_correct():
    from perfbench.drivers import fit

    checks = fit.run(tiny.fit_context(control=True))["checks"]
    assert "delta_norm_gap" in _failed(checks)


def test_fit_step_that_returns_its_state_unchanged_is_not_correct():
    """The timed path broken underneath: after every step the parameters
    are put back to what they were before it."""
    kept = {}

    def undo(mod, n):
        import jax.numpy as jnp

        for name in mod._param_names:
            arr = mod._exec.arg_dict[name]
            if n == 1:      # remember what the first step left ...
                kept[name] = np.asarray(arr._data)
            else:           # ... and put it back after every later one
                arr._data = jnp.asarray(kept[name])

    line = tiny.drive(tiny.fit_context(after_step=undo))
    assert line["correct"] is False


@pytest.mark.parametrize("traffic,trace", [("decode-sat", False),
                                           ("decode-sat", True),
                                           ("chat-steady", False),
                                           ("chat-steady", True)])
def test_generation_rehearsal(traffic, trace):
    from perfbench.drivers import generation

    out = generation.run(tiny.generation_context(traffic, trace=trace))
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert not _failed(out["checks"])
    assert out["e2e"]["serve_tok_s"] > 0
    src = out["sources"]
    assert src.counters["iterations"] > 0 and src.counters["tokens"] > 0
    if traffic == "chat-steady":
        assert out["e2e"]["ttft_p95_ms"] > 0 and out["e2e"]["itl_p95_ms"] > 0
        assert len(src.events) == out["attempted"]
        assert min(src.timers["generator_late"]) >= 0.0
    if trace:
        assert src.trace is not None and src.counters["slice.kv_bytes"] > 0


def test_generation_control_is_not_correct():
    """bfloat16 in the program's place, at a depth where its error shows
    (the tiny two-layer model is too shallow for the chip's limits)."""
    from perfbench.drivers import generation

    out = generation.run(tiny.generation_context(n_layers=12, control=True))
    assert "logit_rms_rel" in _failed(out["checks"])


def test_generation_altered_token_is_not_correct():
    """The timed path broken underneath: every decode step serves the
    next token id up from the one the program chose."""
    def wrap(svc):
        inner = svc._programs.run

        def run(kind, *args, **kw):
            toks, last = inner(kind, *args, **kw)
            if kind == "gen_decode":
                toks = (np.array(toks) + 1) % svc._model_cfg.vocab
            return toks, last

        svc._programs.run = run

    from perfbench.drivers import generation

    out = generation.run(tiny.generation_context(wrap_service=wrap))
    assert {"tie_gap_max", "tie_gap_mean"} & set(_failed(out["checks"]))

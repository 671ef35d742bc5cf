"""The trace reducer: busy/idle, operation table, gap attribution — on a
hand-made trace whose numbers can be worked out on paper, and on a small
trace recorded on a TPU v5e (three steps of one jitted program inside
``fit.batch`` spans; ``recorded/small_tpu.xplane.pb``)."""
import os

import pytest

from perfbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "recorded", "small_tpu.xplane.pb")
HLO = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kOutput, calls=%c"


def hand_made():
    ms = 1_000_000
    dev = [(10 * ms, 20 * ms, HLO.format(1)),       # busy 10..20
           (15 * ms, 30 * ms, "%copy-done.4 = f32[8]{0} copy-done(%x)"),
           (50 * ms, 60 * ms, HLO.format(2)),       # busy 50..60
           (95 * ms, 120 * ms, HLO.format(3))]      # cut at the window's end
    spans = [(0, 100 * ms, "fit.epoch[0]"),         # covers all: left out
             (5 * ms, 45 * ms, "fit.batch"),
             (8 * ms, 12 * ms, "executor.fused_step"),
             (62 * ms, 90 * ms, "fit.batch")]
    return tr.clip(tr.Trace((0, 100 * ms), {"/device:TPU:0": dev}, spans))


def test_busy_idle_and_window():
    s = tr.summarize(hand_made())
    assert s["window_s"] == pytest.approx(0.100)
    # union: 10..30, 50..60, 95..100 = 35 ms
    assert s["busy_s"] == pytest.approx(0.035)
    assert s["n_devices"] == 1 and s["n_events"] == 4


def test_operation_table_groups_by_kind():
    ops = dict(tr.summarize(hand_made())["device_ops"])
    assert ops["fusion kOutput"] == pytest.approx(0.010 + 0.010 + 0.005)
    assert ops["copy-done"] == pytest.approx(0.015)
    assert tr.op_group("all-reduce.7") == "all-reduce"
    assert tr.op_group("%slice_bitcast_fusion.49.remat = f32[8]{0} fusion(), "
                       "kind=kLoop") == "slice_bitcast_fusion kLoop"


def test_gaps_go_to_the_innermost_host_span():
    gaps = dict(tr.summarize(hand_made())["idle_gaps"])
    # 0..10: midpoint 5 ms lies in fit.batch (5..45), not in fused_step
    # 30..50: midpoint 40 in fit.batch; 60..95: midpoint 77.5 in fit.batch
    assert gaps["fit.batch"] == pytest.approx(0.010 + 0.020 + 0.035)
    assert "fit.epoch[0]" not in gaps
    t = hand_made()
    t.spans = []
    assert dict(tr.summarize(t)["idle_gaps"]) == {
        "outside any span": pytest.approx(0.065)}


def test_pattern_seconds_and_span_counts():
    t = hand_made()
    seconds, n = tr.op_seconds(t, r"^%fusion")
    assert n == 3 and seconds == pytest.approx(0.025)
    assert tr.op_seconds(t, "all-reduce") == (0.0, 0)
    assert tr.span_counts(t)["fit.batch"] == 2


def test_recorded_tpu_trace():
    t = tr.load(DATA)
    s = tr.summarize(t)
    assert list(t.devices) == ["/device:TPU:0"]
    assert tr.span_counts(t) == {"fit.batch": 3}
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and s["idle_gaps"]
    # the gaps are the window less the busy time
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)

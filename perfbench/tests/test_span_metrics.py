"""The reducers over the program's phase spans and kernel names
(``span_ms``, ``idle_gap_share``, ``op_share``) on a hand-made trace whose
numbers can be worked out on paper, and the serving cell's span metrics on
the CPU rehearsal of its driver."""
import os
import shutil

import pytest

from perfbench import harness, trace_reduce as tr
from perfbench.reducers import idle_gap_share, op_share, span_ms
from perfbench.tests import tiny

MS = 1_000_000
PAGED = "%_paged_call_w{}_{}.{} = f32[32,1,1280]{{2,1,0}} custom-call(%a)"


def hand_made():
    """Two engine iterations of 50 ms in a 100 ms window: a decode step at
    table width 16, then one at 64 after a prefill chunk."""
    dev = [(10 * MS, 30 * MS, PAGED.format(16, "decode", 1)),
           (60 * MS, 64 * MS, PAGED.format(64, "t128_prefill", 2)),
           (65 * MS, 95 * MS, PAGED.format(64, "decode", 3)),
           (95 * MS, 98 * MS, "%fusion.7 = f32[8]{0} fusion(%p), kind=kLoop")]
    spans = []
    for t0 in (0, 50 * MS):
        spans += [(t0, t0 + 48 * MS, "serving.iteration"),
                  (t0, t0 + 2 * MS, "serving.schedule"),
                  (t0 + 3 * MS, t0 + 7 * MS, "serving.decode.build"),
                  (t0 + 8 * MS, t0 + 40 * MS, "serving.decode"),
                  (t0 + 8 * MS, t0 + 9 * MS, "serving.step.dispatch"),
                  (t0 + 9 * MS, t0 + 40 * MS, "serving.step.sync"),
                  (t0 + 41 * MS, t0 + 47 * MS, "serving.emit")]
    return tr.clip(tr.Trace((0, 100 * MS), {"/device:TPU:0": dev}, spans))


def sources(trace=None, **counters):
    return harness.Sources(counters=counters, trace=trace,
                           peaks={"hbm_bytes_per_s": 1e9})


def test_span_ms_per_counter_per_span_and_percentile():
    src = sources(hand_made(), **{"slice.iterations": 2})
    per = {"per": "slice.iterations"}
    assert span_ms.reduce({"spans": ["serving.schedule"], **per}, src) \
        == pytest.approx(2.0)
    # schedule 2 + build 4 + emit 6 + dispatch 1, each twice, over 2
    assert span_ms.reduce(
        {"spans": ["serving.schedule", "serving.decode.build",
                   "serving.emit", "serving.step.dispatch"], **per}, src) \
        == pytest.approx(13.0)
    # a name is a whole name: serving.decode is not serving.decode.build
    assert span_ms.reduce({"spans": ["serving.decode"]}, src) \
        == pytest.approx(32.0)
    # the second emit span is cut by nothing, the window cuts none here;
    # per span without a counter, and a percentile of the single spans
    assert span_ms.reduce({"spans": ["serving.emit", "serving.schedule"]},
                          src) == pytest.approx(4.0)
    assert span_ms.reduce({"spans": ["serving.emit", "serving.schedule"],
                           "percentile": 100}, src) == pytest.approx(6.0)


def test_span_ms_clips_to_the_window():
    t = hand_made()
    t.window = (0, 75 * MS)
    src = sources(tr.clip(t), **{"slice.iterations": 2})
    # the second decode span runs 58..90: 17 ms of it lie in the window
    assert span_ms.reduce({"spans": ["serving.decode"],
                           "per": "slice.iterations"}, src) \
        == pytest.approx((32.0 + 17.0) / 2)


def test_idle_gap_share_by_owner():
    src = sources(hand_made())
    # idle: 0..10 (midpoint 5: build), 30..60 (45: the first iteration's
    # emit), 64..65 (sync), 98..100 (99: after the last span, no owner)
    gaps = dict(tr.summarize(src.trace, top=99)["idle_gaps"])
    assert gaps == {"serving.decode.build": pytest.approx(0.010),
                    "serving.emit": pytest.approx(0.030),
                    "serving.step.sync": pytest.approx(0.001),
                    "outside any span": pytest.approx(0.002)}
    assert idle_gap_share.reduce({"owners": ["outside any span"]}, src) \
        == pytest.approx(100 * 2 / 43)
    assert idle_gap_share.reduce(
        {"owners": ["serving.emit", "serving.decode.build"]}, src) \
        == pytest.approx(100 * 40 / 43)


def test_op_share_and_the_decode_only_roofline():
    from perfbench.reducers import roofline

    src = sources(hand_made(), **{"slice.kv_bytes": 5e6})
    decode = r"_paged_call_w\d+_decode"
    assert op_share.reduce({"num": "_paged_call_w64_decode", "den": decode},
                           src) == pytest.approx(100 * 30 / 50)
    assert op_share.reduce({"num": "_prefill", "den": "_paged_call"}, src) \
        == pytest.approx(100 * 4 / 54)
    # 5 MB at 1 GB/s is 5 ms: over the decode calls' 50 ms, and over all
    # 54 ms that the old pattern (the wrapper's name alone) still finds
    assert roofline.reduce({"pattern": decode, "bytes": "slice.kv_bytes"},
                           src) == pytest.approx(10.0)
    assert roofline.reduce({"pattern": "_paged_call",
                            "bytes": "slice.kv_bytes"}, src) \
        == pytest.approx(100 * 5 / 54)
    # one row per width and phase in the operation table
    assert [g for g, _ in tr.summarize(src.trace)["device_ops"]] == [
        "_paged_call_w64_decode", "_paged_call_w16_decode",
        "_paged_call_w64_t128_prefill", "fusion kLoop"]


@pytest.mark.parametrize("reducer,params", [
    (span_ms, {"spans": ["serving.schedule"], "per": "slice.iterations"}),
    (idle_gap_share, {"owners": ["outside any span"]}),
    (op_share, {"num": "_paged_call_w64_decode",
                "den": r"_paged_call_w\d+_decode"})])
def test_nothing_to_read_is_no_metric(reducer, params):
    """An untraced run, and a program without the spans or the kernel
    names (an older commit): None, never an exception."""
    assert reducer.reduce(params, sources()) is None
    bare = tr.clip(tr.Trace(
        (0, 10 * MS), {"/device:TPU:0": [(0, 10 * MS, "%_paged_call.1 = x")]},
        [(1 * MS, 2 * MS, "serving.decode")]))
    assert reducer.reduce(params, sources(bare, **{"slice.iterations": 3})) \
        is None


def _cpu_trace(path):
    """The rehearsal's trace with the CPU's executor threads standing in
    for the device and the Python threads' annotations as the host spans
    (``trace_reduce.load`` with the rehearsal's ``/host:CPU`` prefix takes
    the whole plane as the device and keeps no span)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name.startswith("/host:CPU"))
    dev, spans, window = [], [], None
    for ln in host.lines:
        for e in ln.events:
            ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
            if ln.name.startswith("tf_XLA"):
                dev.append(ev)
            elif e.name == tr.WINDOW_SPAN:
                window = ev[:2]
            elif e.name.startswith(tr.SPAN_PREFIXES):
                spans.append(ev)
    return tr.clip(tr.Trace(window, {"/host:CPU": dev}, spans))


def test_serving_rehearsal_reports_the_span_metrics(monkeypatch):
    from perfbench.drivers import generation

    monkeypatch.setenv("PERFBENCH_TRACE_KEEP", "1")
    ctx = tiny.generation_context("decode-sat", trace=True)
    assert ctx.name == "gpt2-large-decode-sat"
    kept = os.path.join(harness.ROOT, ".perfbench_trace", ctx.name)
    try:
        src = generation.run(ctx)["sources"]
        src.trace = _cpu_trace(tr.find_xplane(kept))
    finally:
        shutil.rmtree(kept, ignore_errors=True)
    got = harness.per_layer_metrics(ctx, src)
    for name in ("sat.schedule_ms", "sat.build_ms", "sat.emit_ms",
                 "sat.host_iter_ms"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms", name
    assert got["sat.host_iter_ms"]["value"] >= sum(
        got[k]["value"] for k in ("sat.schedule_ms", "sat.build_ms",
                                  "sat.emit_ms"))
    # an iteration's host time is less than the iteration
    assert got["sat.host_iter_ms"]["value"] < got["sat.iter_ms"]["value"]
    gaps = tr.summarize(src.trace, top=10**6)["idle_gaps"]
    assert gaps[0][0].startswith("serving.")
    assert got["sat.idle_unattributed_pct"]["value"] < 50.0
    # every metric of the cell names a file and a reducer that exist
    mine = [m["name"] for m in harness.benchmark()["per_layer"]
            if ctx.name in m["workloads"]]
    assert len(mine) == 14 and set(got) <= set(mine)

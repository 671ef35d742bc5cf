"""CPU rehearsal of the four-device training cell ``resnet50-fit-dp4``
(``tiny_dp4.py``; PERF.md section 7) on four virtual CPU devices, in a
process of its own: both values of ``--trace``, the metric set, and that
the by-scope metrics with the unscoped rest add up to the device time they
were cut from.  Numbers from these runs are counts and control flow, never
device metrics."""
import json
import os
import subprocess
import sys

import pytest

from perfbench.tests import tiny_dp4

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    out = {}
    for trace in (0, 1):
        run = subprocess.run(
            [sys.executable, "-m", "perfbench.tests.tiny_dp4", str(trace)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
        assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-3000:]
        out[trace] = json.loads(run.stdout.strip().splitlines()[-1])
    return out


# read from the program's spans, or from idle gaps: in a CPU run the host
# plane stands in for the device, its spans are not gathered
# (``trace_reduce.load``) and it may never be idle, so these read on the
# chip only
CHIP_ONLY = {"dp4.input_span_ms", "dp4.dispatch_ms", "dp4.sync_wait_ms",
             "dp4.idle_unattributed_pct"}


@pytest.mark.parametrize("trace", [0, 1])
def test_dp4_rehearsal_runs_on_four_devices(lines, trace):
    line = lines[trace]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["count"] == 4
    if not trace:
        # the cell is in no BENCHMARK.json yet: only what every cell has
        assert set(line["metrics"]) == {"setup_s"}


def test_the_fit_driver_cannot_judge_a_four_device_step_yet(lines):
    """Why the cell is not in ``BENCHMARK.json`` (PERF.md section 7): the
    reference normalises over the whole batch where the program's
    BatchNorm sees a device's rows, and the driver counts the compiles of
    ``fit``'s closing ``get_params()`` into the window.  (On the chip the
    reference's step at 1,024 rows does not fit one chip either.)  When
    ``drivers/fit.py`` is repaired this test goes and ``correct`` is
    asserted above."""
    assert lines[0]["correct"] is False


@pytest.mark.parametrize("name", sorted(set(tiny_dp4.METRICS)
                                        - CHIP_ONLY))
def test_dp4_traced_run_reports(lines, name):
    assert lines[1]["metrics"][name]["value"] >= 0


def test_dp4_scope_metrics_read_what_the_step_does(lines):
    m = {k: v["value"] for k, v in lines[1]["metrics"].items()}
    for name in ("dp4.allreduce_ms", "dp4.allreduce_scope_ms",
                 "dp4.conv_device_ms", "dp4.bn_device_ms",
                 "dp4.update_device_ms"):
        assert m[name] > 0, name
    assert 0 < m["dp4.backward_share_pct"] < 100
    assert 0 <= m["dp4.unscoped_pct"] < 100
    assert m["dp4.allreduce_exposed_ms"] <= m["dp4.allreduce_ms"] * 1.0001


def test_dp4_scopes_and_the_unscoped_rest_add_up(lines):
    line = lines[1]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    named = (m["dp4.conv_device_ms"] + m["dp4.bn_device_ms"]
             + m["dp4.update_device_ms"] + m["dp4.allreduce_scope_ms"]
             + m["rest"])
    own = line["own_ms_per_step"]
    assert named + own * m["dp4.unscoped_pct"] / 100.0 == \
        pytest.approx(own, rel=1e-6)

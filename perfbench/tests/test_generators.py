"""The traffic generators: the same seed gives the same schedule and
lengths, another seed the same SET in another order; the open loop reports
how late it ran."""
import time

import numpy as np

from perfbench.generators import closed_loop, open_loop, requests
from perfbench.tests import tiny


def _lengths(reqs):
    return [len(p) for p, _ in reqs], [n for _, n in reqs]


def test_same_seed_same_requests_other_seed_same_set():
    a = requests.make_requests(tiny.generation_context(seed=2 ** 31 + 5), 64)
    b = requests.make_requests(tiny.generation_context(seed=2 ** 31 + 5), 64)
    c = requests.make_requests(tiny.generation_context(seed=6), 64)
    assert _lengths(a) == _lengths(b)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert _lengths(a) != _lengths(c)
    assert sorted(_lengths(a)[0]) == sorted(_lengths(c)[0])
    ctx = tiny.generation_context()
    assert all(4 <= len(p) <= 64 and len(p) + n <= ctx.config["max_len"]
               for p, n in a)


def test_arrival_gaps_are_one_set_with_the_mean_of_the_rate():
    gaps = requests.exponential_set(1000, 8.0)
    assert abs(gaps.mean() - 1 / 8.0) < 0.002
    x = open_loop.Load(tiny.generation_context("chat-steady", seed=1), None)
    y = open_loop.Load(tiny.generation_context("chat-steady", seed=2), None)
    z = open_loop.Load(tiny.generation_context("chat-steady", seed=1), None)
    assert np.array_equal(x.dues, z.dues)
    assert not np.array_equal(x.dues, y.dues)
    # the measured window holds the same number of arrivals and the same
    # set of gaps for every seed, all due inside it
    ramp, secs = x.traffic_ramp, x.seconds
    assert x.window == y.window and len(x.window) == round(20.0 * secs)
    wx, wy = x.dues[x.window.start:x.window.stop], \
        y.dues[y.window.start:y.window.stop]
    assert ramp <= wx.min() and wx.max() < ramp + secs
    assert np.allclose(np.sort(np.diff(wx, prepend=ramp)),
                       np.sort(np.diff(wy, prepend=ramp)))
    lens = lambda load: sorted(len(p) for p, _ in  # noqa: E731
                               load.specs[load.window.start:load.window.stop])
    assert lens(x) == lens(y)


def test_open_loop_times_from_due_and_reports_lateness():
    def slow_submit(prompt, n, on_token):   # a server that stalls 20 ms
        time.sleep(0.02)
        on_token(0, 1)

    ctx = tiny.generation_context("chat-steady", seconds=0.3)
    ctx.traffic.update(rate=100.0, ramp_seconds=0.0)
    load = open_loop.Load(ctx, slow_submit)
    load.start()
    time.sleep(0.4)
    load.stop()
    late = [r.submitted - r.due for r in load.records]
    assert len(late) >= 10 and min(late) >= 0.0
    # 100/s offered into 50/s served: the generator falls behind, and says so
    assert late[-1] > 0.1 and all(r.stamps[0] >= r.due for r in
                                  load.records)


def test_closed_loop_keeps_clients_in_flight():
    pending = []

    def submit(prompt, n, on_token):
        pending.append((n, on_token))

    ctx = tiny.generation_context("decode-sat")
    load = closed_loop.Load(ctx, submit)
    load.start()
    time.sleep(0.2)
    assert len(pending) == ctx.traffic["clients"]
    n, cb = pending[0]
    for _ in range(n):           # one request completes: one more is sent
        cb(0, 3)
    time.sleep(0.7)
    assert len(pending) == ctx.traffic["clients"] + 1
    load.stop()
    assert load.records[0].done and len(load.records[0].stamps) == n

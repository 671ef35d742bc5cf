"""Open loop: Poisson arrivals at the traffic file's fixed ``rate``
(requests per second), sent by ONE load thread on the schedule whatever
the server does.  Each request is timed from when it was DUE; how late the
generator submitted it is recorded beside it."""
from __future__ import annotations

import threading
import time

import numpy as np

from perfbench.generators.requests import (Record, exponential_set,
                                           make_requests)


class Load:
    """``window`` is the index range of the requests scheduled into the
    measured window: the same number, the same set of gaps and the same
    set of lengths for every seed, in another order; the ramp before it
    and the slack after it are sets of their own."""

    def __init__(self, ctx, submit, rate=None):
        t = ctx.traffic
        rate = rate or t["rate"]
        counts = [max(1, round(rate * t["ramp_seconds"])),
                  max(1, round(rate * ctx.seconds)),
                  max(8, round(rate * ctx.seconds * 0.25))]
        starts = [0.0, t["ramp_seconds"], t["ramp_seconds"] + ctx.seconds]
        dues, specs = [], []
        for part, (n, start) in enumerate(zip(counts, starts)):
            gaps = ctx.rng(10 + part).permutation(exponential_set(n, rate))
            dues.append(start + np.cumsum(gaps))
            specs += make_requests(ctx, n, stream=20 + part)
        self.dues = np.concatenate(dues)       # seconds after start()
        self.window = range(counts[0], counts[0] + counts[1])
        self.traffic_ramp, self.seconds = t["ramp_seconds"], ctx.seconds
        self.specs = specs
        self.submit = submit
        self.records = []
        self._stop = False
        self.exhausted = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="perfbench-load")

    def _run(self):
        for i, (pr, n) in enumerate(self.specs):
            due = self.t_start + float(self.dues[i])
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or self._stop:
                    break
                time.sleep(min(wait, 0.05))
            if self._stop:
                return
            rec = Record(i, pr, n, due)
            self.records.append(rec)
            try:
                rec.stream = self.submit(pr, n, rec.on_token)
            except Exception as exc:  # refused: a failed request
                rec.error = exc
            rec.submitted = time.perf_counter()
        self.exhausted = True   # the schedule is 1.25 x the run: a fault

    def start(self):
        self.t_start = time.perf_counter()
        self._thread.start()

    def stop(self):
        self._stop = True
        self._thread.join()

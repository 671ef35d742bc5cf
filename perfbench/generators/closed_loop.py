"""Closed loop: ``clients`` callers, each sending its next request when
the last one completes.  ONE load thread does all the submitting; the
engine's ``on_token`` callback stamps tokens and wakes it through a queue.
The first generation's output lengths are scaled by an even spread of
fractions (0.1 to 1) so that completions are staggered from the start."""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from perfbench.generators.requests import Record, make_requests


class Load:
    def __init__(self, ctx, submit):
        t = ctx.traffic
        self.clients = t["clients"]
        # rounds of ``clients`` requests, each round the same set of
        # lengths in another order: whatever prefix of them a run consumes,
        # every seed has consumed the same work but for the last round's
        # order
        self.specs = [spec for r in range(t["rounds"])
                      for spec in make_requests(ctx, self.clients, 20 + r)]
        stagger = ctx.rng(3).permutation(
            0.1 + 0.9 * (np.arange(self.clients) + 0.5) / self.clients)
        for i in range(self.clients):
            pr, n = self.specs[i]
            self.specs[i] = (pr, max(t["output"]["min"] // 4,
                                     int(n * stagger[i])))
        self.submit = submit
        self.records = []
        self._next = 0
        self._events = queue.SimpleQueue()
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="perfbench-load")

    def _send(self):
        if self._next >= len(self.specs):
            raise RuntimeError("closed loop ran out of its request pool")
        pr, n = self.specs[self._next]
        rec = Record(self._next, pr, n, time.perf_counter(),
                     on_done=self._events.put)
        self._next += 1
        self.records.append(rec)
        rec.submitted = rec.due
        try:
            rec.stream = self.submit(pr, n, rec.on_token)
        except Exception as exc:  # refused: a failed request
            rec.error = exc
            self._events.put(rec)

    def _run(self):
        for _ in range(self.clients):
            self._send()
        while not self._stop:
            try:
                self._events.get(timeout=0.5)
            except queue.Empty:
                # a request the engine failed never reaches its last token
                for rec in self.records:
                    if rec.error is None and not rec.done \
                            and rec.stream is not None \
                            and rec.stream.finished:
                        rec.error = rec.stream._req.error or "cut short"
                        self._events.put(rec)
                continue
            if not self._stop:
                self._send()

    def start(self):
        self._thread.start()

    def stop(self):
        """No further submissions; returns once the load thread ended."""
        self._stop = True
        self._events.put(None)
        self._thread.join()

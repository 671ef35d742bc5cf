"""The request mix both load generators read: lengths and arrival gaps.

Every seed gets the SAME set of prompt lengths, output lengths and gaps —
the stratified quantiles of the traffic file's distributions — in another
order, so that a seed changes the schedule and the token ids but not the
amount of work.  Token ids are uniform over the vocabulary, from the seed,
with no shared prefix.
"""
from __future__ import annotations

import math
import time
from statistics import NormalDist

import numpy as np


def lognormal_set(n, median, sigma, lo, hi):
    """``n`` stratified quantiles of a lognormal, clipped to ``[lo, hi]``."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_set(n, rate):
    """``n`` stratified quantiles of Poisson arrivals' gaps (seconds)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def make_requests(ctx, n, stream=2):
    """``n`` requests ``(prompt ids, max_new_tokens)`` for this seed."""
    t, rng = ctx.traffic, ctx.rng(stream)
    max_len, vocab = ctx.config["max_len"], ctx.config["vocab"]
    p, o = t["prompt"], t["output"]
    plen = rng.permutation(lognormal_set(n, p["median"], p["sigma"],
                                         p["min"], p["max"]))
    olen = rng.permutation(lognormal_set(n, o["median"], o["sigma"],
                                         o["min"], o["max"]))
    olen = np.minimum(olen, max_len - plen)
    ids = rng.integers(0, vocab, int(plen.sum()), dtype=np.int32)
    cuts = np.cumsum(plen)[:-1]
    return [(pr, int(n_out)) for pr, n_out in zip(np.split(ids, cuts), olen)]


class Record:
    """One request as the benchmark saw it: due and submit stamps, one
    stamp per output token (``on_token`` is what the engine calls), and
    how it ended.  ``on_done`` is called once with the record when its
    last token arrives."""
    __slots__ = ("idx", "prompt", "max_new", "due", "submitted", "stamps",
                 "tokens", "stream", "error", "done", "on_done")

    def __init__(self, idx, prompt, max_new, due, on_done=None):
        self.idx, self.prompt, self.max_new, self.due = \
            idx, prompt, max_new, due
        self.submitted = None
        self.stamps, self.tokens = [], []
        self.stream = self.error = None
        self.done = False
        self.on_done = on_done

    def on_token(self, _rid, tok):
        self.stamps.append(time.perf_counter())
        self.tokens.append(tok)
        if len(self.tokens) >= self.max_new:
            self.done = True
            if self.on_done is not None:
                self.on_done(self)

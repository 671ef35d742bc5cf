"""Fork and crash handlers (reference: src/initialize.cc:40-86 —
pthread_atfork engine stop/restart so DataLoader workers can fork safely,
plus a segfault handler printing a backtrace).

Python analogue: ``os.register_at_fork`` quiesces the native dependency
engine before a fork (its C++ worker threads do not survive into the
child), abandons the child's inherited engine handle without touching the
dead native state (a fresh engine is lazily created on first use), and
reseeds the child's PRNG stream so forked workers don't draw identical
randomness.  ``faulthandler`` covers the segfault-backtrace half.
"""
from __future__ import annotations

import faulthandler
import os

_installed = False


def _before_fork():
    from . import engine

    eng = engine._host_engine
    if eng is not None:
        try:
            eng.wait_all()  # quiesce: no op may straddle the fork
        except Exception:
            pass


def _after_in_child():
    from . import engine

    eng = engine._host_engine
    if eng is not None:
        # the native worker threads died with the fork: drop the handle
        # without running close() (which would join ghosts); leak the tiny
        # native struct — exactly the reference's Engine::Stop-without-join
        # child-side behavior
        eng._h = None
        engine._host_engine = None
    # reseed LAZILY: never touch jax here — creating a PRNGKey would
    # initialize a backend inside every forked DataLoader worker, and a
    # chip belongs to one process: the parent holds it, a child that
    # reached for it would fail or hang (docs/multichip.md).  Drop BOTH
    # the thread-local key and the materialized global base (diverting
    # _DEFAULT_SEED alone is ineffective once _base['key'] exists — every
    # child would re-derive the parent's stream); the next key use
    # rebuilds from the fresh seed.
    from . import random as _random

    if hasattr(_random._state, "key"):
        del _random._state.key
    _random._DEFAULT_SEED = int.from_bytes(os.urandom(4), "little")
    with _random._base_lock:
        _random._base["key"] = None
        _random._base["gen"] += 1
    # numpy's global RNG is NOT auto-reseeded at fork (stdlib random is):
    # the flip/crop transforms draw from it, and correlated workers make
    # identical augmentation decisions
    import numpy as _np

    _np.random.seed(int.from_bytes(os.urandom(4), "little"))


def install():
    global _installed
    if _installed:
        return
    _installed = True
    try:
        faulthandler.enable()
    except Exception:
        pass  # non-main-thread or closed stderr: backtraces just stay off
    os.register_at_fork(before=_before_fork, after_in_child=_after_in_child)

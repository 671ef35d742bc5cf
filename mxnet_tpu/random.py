"""Global PRNG stream.

Reference: per-device seeded generators (``src/common/random_generator.h`` —
CPU mt19937 / GPU Philox) behind ``mx.random.seed``.  TPU-native version: one
global threefry key split per consuming op, so every random op remains a pure
function of an explicit key (jit/vmap/shard-safe), while the user-facing API
stays stateful like the reference.
"""
from __future__ import annotations

import itertools
import threading

import jax

__all__ = ["seed", "next_key", "split_ahead", "fold_in", "get_state",
           "set_state"]

_state = threading.local()
_DEFAULT_SEED = 0
# global base: seed() updates it so threads created afterwards derive their
# stream from it; per-thread keys stay thread-local (swap_key temporarily
# installs TRACED keys during jit, which must never leak across threads)
_base = {"key": None, "gen": 0}
_base_lock = threading.Lock()


def _base_key():
    with _base_lock:
        if _base["key"] is None:
            _base["key"] = jax.random.PRNGKey(_DEFAULT_SEED)
        return _base["key"], _base["gen"]


_thread_seq = itertools.count(1)


def _thread_index() -> int:
    # The MAIN thread is structurally index 0 (not by touch order, which
    # races against worker threads): index 0 means "the seeded base key
    # itself", so mx.random.seed(N) fully determines the main thread's
    # stream across processes and runs — the reference's
    # same-seed-same-results contract for single-threaded programs.
    # threading.get_ident() could not provide this (idents vary with ASLR).
    if threading.current_thread() is threading.main_thread():
        return 0
    if not hasattr(_state, "seq"):
        # worker threads: distinct streams by first-touch ordinal.  Like
        # the reference's shared per-device generator, multi-threaded draw
        # REPRODUCIBILITY is not promised — only stream distinctness.
        _state.seq = next(_thread_seq)
    return _state.seq


def _get_key():
    base, gen = _base_key()
    if not hasattr(_state, "key") or getattr(_state, "gen", None) != gen:
        idx = _thread_index()
        _state.key = base if idx == 0 else jax.random.fold_in(base, idx)
        _state.gen = gen
    return _state.key


def ensure_key() -> None:
    """Materialize the stream key eagerly, OUTSIDE any trace.

    Must be called before code that may first-touch the stream while being
    traced (jit/eval_shape) — otherwise the lazily-created default key would
    be a tracer and leak into global state after the trace ends.
    """
    _get_key()


def seed(seed_state: int, ctx=None) -> None:
    """Seed the global stream (reference: ``mx.random.seed`` in
    python/mxnet/random.py).  Applies to this thread immediately and to every
    thread's NEXT draw (each derives a distinct stream from the new base)."""
    with _base_lock:
        _base["key"] = jax.random.PRNGKey(int(seed_state))
        _base["gen"] += 1
    # this thread re-derives its stream (base for the first-touch thread,
    # fold_in(seq) otherwise) on the next draw like everyone else — setting
    # _state.key directly here bypassed the seq bookkeeping
    if hasattr(_state, "key"):
        del _state.key


def split_ahead():
    """The split the NEXT :func:`next_key` will make, made now and not yet
    taken: the stream does not move.  ``next_key(ahead=...)`` takes it if
    nobody has drawn or seeded on this thread in between (the stream's key
    is still the very object that was split), and splits afresh otherwise —
    so the keys and their order are what they are without it.  For a
    caller that knows its next draw and has time now (the fused train
    step, while the device runs the step before)."""
    key = _get_key()
    new, sub = jax.random.split(key)
    return key, new, sub


def next_key(ahead=None):
    """Split one subkey off the global stream (``ahead``: a
    :func:`split_ahead` made earlier, used if the stream has not moved)."""
    key = _get_key()
    if ahead is not None and ahead[0] is key:
        _state.key, sub = ahead[1], ahead[2]
        return sub
    _state.key, sub = jax.random.split(key)
    return sub


def fold_in(data: int):
    return jax.random.fold_in(_get_key(), data)


def get_state():
    """The calling thread's raw PRNG key data as a host uint32 array
    (checkpointing: a resumed run's dropout/sampling streams continue
    exactly where the interrupted run stopped).  Returns None if the key
    cannot be read (e.g. a traced key is installed)."""
    import numpy as _np

    try:
        key = _get_key()
        try:  # new-style typed keys carry their raw words behind key_data
            data = jax.random.key_data(key)
        except (AttributeError, TypeError):
            data = key
        return _np.asarray(data)
    except Exception:
        return None


def set_state(data) -> None:
    """Install raw key data captured by :func:`get_state` as this thread's
    stream key (bypasses the base/seq derivation — the restored stream IS
    the checkpointed one)."""
    import numpy as _np

    import jax.numpy as jnp

    arr = jnp.asarray(_np.asarray(data, dtype=_np.uint32))
    cur = _get_key()
    typed = False
    try:  # the live key decides the representation to restore into
        jax.random.key_data(cur)
        typed = cur.dtype != arr.dtype
    except (AttributeError, TypeError):
        typed = False
    if typed:
        arr = jax.random.wrap_key_data(arr)
    _state.key = arr
    _state.gen = _base_key()[1]


def swap_key(new_key):
    """Swap the global stream key (used by traced CachedOps to thread a traced
    key through jit so dropout masks differ per call); returns the old key."""
    old = _get_key()
    _state.key = new_key
    return old


def _install_samplers():
    """Re-export the nd.random samplers at mx.random.* (reference:
    python/mxnet/random.py exposes uniform/normal/... top-level — the form
    most 1.x scripts call).  Installed lazily at import-time from
    __init__ to avoid a circular import with the ndarray package."""
    import sys

    from .ndarray import random as _ndr

    mod = sys.modules[__name__]
    for name in _ndr.__all__:
        if not hasattr(mod, name):
            setattr(mod, name, getattr(_ndr, name))
            __all__.append(name)

"""Misc utilities (reference: python/mxnet/util.py)."""
from __future__ import annotations

import functools
import os

__all__ = ["makedirs", "get_gpu_count", "use_np_shape", "is_np_shape",
           "enable_compile_cache"]


def makedirs(d):
    os.makedirs(os.path.expanduser(d), exist_ok=True)


def get_gpu_count():
    from .context import num_tpus

    return num_tpus()


def use_np_shape(func):
    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        return func(*args, **kwargs)

    return wrapper


def is_np_shape():
    return False


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for a run on the chip;
    returns the directory in use.

    Placed from outside: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a FIXED path (it is part of the cache key's
    environment, so a directory that moves never hits), listed in
    ``.gitignore``.  Called by the entry points that run on the chip
    (chip_smoke.py, tools/tpu_parity.py), never on
    ``import mxnet_tpu``.
    """
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""Device context abstraction.

TPU-native analogue of the reference's ``Context`` (``include/mxnet/base.h:133-139``
— kCPU / kGPU / kCPUPinned / kCPUShared).  Here the device kinds are
cpu / tpu; each context maps onto a concrete ``jax.Device``.  Unlike the
reference there is no per-device stream management in Python — XLA owns
scheduling inside a compiled program and the JAX runtime owns async dispatch
between them.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_devices", "num_tpus"]


class Context:
    """A device context: ``Context('tpu', 0)`` or via helpers ``mx.tpu(0)``.

    Mirrors the user-facing behavior of the reference Context
    (``python/mxnet/context.py``): usable as a ``with`` scope that sets the
    default device for array creation, hashable, comparable.
    """

    # devtype string -> devtypeid, mirroring the reference's numeric dev types.
    devtype2id = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devid2type = {v: k for k, v in devtype2id.items()}

    _tls = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_type = device_type.device_type
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devtype2id:
                raise ValueError(
                    f"unknown device type {device_type!r}; expected one of {list(self.devtype2id)}"
                )
            self.device_type = device_type
            self.device_id = device_id

    @property
    def device_typeid(self) -> int:
        return self.devtype2id[self.device_type]

    # -- mapping onto jax devices -------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """The concrete jax.Device this context denotes."""
        kind = "cpu" if self.device_type in ("cpu", "cpu_pinned", "cpu_shared") else None
        if kind == "cpu":
            devs = jax.devices("cpu") if _has_platform("cpu") else jax.devices()
        else:
            # tpu/gpu: an accelerator, or an error — handing back CPU
            # devices here would hide a missing chip from the caller
            devs = _accelerator_devices()
            if not devs:
                raise RuntimeError(
                    f"context {self}: jax sees no accelerator (devices: "
                    f"{jax.devices()}); use mx.cpu() to run on the host")
        if self.device_id >= len(devs):
            raise ValueError(
                f"context {self} out of range: only {len(devs)} {self.device_type} device(s) visible"
            )
        return devs[self.device_id]

    # -- scope protocol -----------------------------------------------------------
    def __enter__(self) -> "Context":
        if not hasattr(Context._tls, "stack"):
            Context._tls.stack = []
        Context._tls.stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        stack = getattr(Context._tls, "stack", None)
        if not stack:
            raise RuntimeError(
                "Context.__exit__ without a matching __enter__")
        stack.pop()

    # -- value semantics ----------------------------------------------------------
    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self) -> int:
        return hash((self.device_type, self.device_id))

    def __repr__(self) -> str:
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    def empty_cache(self) -> None:
        """Release cached device memory (reference: MXStorageEmptyCache)."""
        try:
            self.jax_device.memory_stats()  # touch; jax has no public cache-drop
        except Exception:
            pass


def _has_platform(name: str) -> bool:
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerator_devices():
    for plat in ("tpu", "gpu"):
        try:
            devs = jax.devices(plat)
            if devs:
                return devs
        except RuntimeError:
            continue
    # default platform devices that are not cpu
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    return devs


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def gpu(device_id: int = 0) -> Context:
    # kept for API compatibility with the reference; maps to an accelerator.
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    stack = getattr(Context._tls, "stack", None)
    if stack:
        return stack[-1]
    if Context._default is None:
        Context._default = default_context()
    return Context._default


def num_devices() -> int:
    return jax.device_count()


def num_tpus() -> int:
    return len(_accelerator_devices())


def default_context() -> Context:
    """The best available context: tpu if it is the default jax backend, else cpu.

    Resolved lazily (NOT at import) — initializing the TPU client is slow and
    exclusive, and must not happen when the user forces JAX_PLATFORMS=cpu.
    """
    import os

    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and all(p.strip() in ("cpu", "") for p in plats.split(",")):
        return cpu(0)
    if jax.default_backend() != "cpu" and _accelerator_devices():
        return tpu(0)
    return cpu(0)


Context._default = None

"""TrainCheckpointer: the complete donated fused-step state ↔ disk.

What one training checkpoint must carry for an *identical* resumed loss
trajectory (docs/fault_tolerance.md):

- parameters + aux states (BatchNorm running stats) — device-copied off
  the executor's donated buffers (``Executor.snapshot_arrays``; sharded
  mp leaves gather through the host so the file always holds full,
  replicated-identical arrays restorable under ANY mesh shape);
- optimizer state — the Updater's per-slot ``create_state`` pytrees,
  including AMP ``(master_f32, inner)`` master weights, device-copied the
  same way;
- the optimizer's host counters (``num_update``, per-slot update counts) —
  Adam's bias correction reads them, so dropping them would silently
  change the resumed trajectory;
- the AMP loss-scaler ``(scale, good_steps)`` state;
- the global RNG key (dropout streams resume where they left off);
- the data position: epoch, batches-completed-in-epoch, global step —
  ``Module.fit(resume=True)`` fast-forwards the iterator mid-epoch.

Capture happens on the fit thread as cheap device-side copies (the next
step's donation cannot invalidate them); the device→host transfer,
serialization and atomic commit run on the manager's writer thread.
"""
from __future__ import annotations

import logging
from typing import Dict, Optional

import numpy as _np

from ..base import MXNetError
from .manager import CheckpointManager

__all__ = ["TrainCheckpointer", "ResumePoint", "capture_train_state",
           "restore_train_state"]

_logger = logging.getLogger("mxnet_tpu.checkpoint")


class ResumePoint:
    """Where a restored run continues."""

    __slots__ = ("epoch", "nbatch", "global_step", "step")

    def __init__(self, epoch: int, nbatch: int, global_step: int):
        self.epoch = int(epoch)
        self.nbatch = int(nbatch)          # batches completed in `epoch`
        self.global_step = int(global_step)
        self.step = self.global_step

    def __repr__(self):
        return (f"ResumePoint(epoch={self.epoch}, nbatch={self.nbatch}, "
                f"global_step={self.global_step})")


def _pack_states_device(states: Dict) -> Dict:
    """Device-copy every NDArray leaf of the Updater's state structures
    (donation-safe snapshot, no host sync)."""
    import jax.numpy as jnp

    from ..ndarray.ndarray import NDArray

    def cp(s):
        if s is None:
            return None
        if isinstance(s, (tuple, list)):
            return tuple(cp(x) for x in s)
        if isinstance(s, NDArray):
            x = s._data
            if x is None:
                return None
            try:
                multi = len(x.devices()) > 1
            except Exception:
                multi = False
            # sharded/multi-device leaves gather via host (same rule as
            # Executor.snapshot_arrays); single-device leaves copy on device
            return _np.asarray(x) if multi else jnp.array(x, copy=True)
        return s
    return {int(k): cp(v) for k, v in states.items()}


def _states_from_host(tree: Dict):
    """Rebuild Updater.states NDArray structures from the pickled host
    tree (mirrors Updater.set_states' unpack)."""
    from ..ndarray import array as nd_array

    def un(s):
        if s is None:
            return None
        if isinstance(s, (tuple, list)):
            return tuple(un(x) for x in s)
        if isinstance(s, _np.ndarray):
            return nd_array(s)
        return s
    return {int(k): un(v) for k, v in tree.items()}


def capture_train_state(mod) -> tuple:
    """Snapshot a Module's full train state as ``(arrays, opt_tree, meta)``:
    device-side copies only — safe against the next step's donation, no
    host sync on the calling thread (single-device layouts)."""
    if mod._exec is None or not mod.params_initialized:
        raise MXNetError("capture_train_state: module is not "
                         "bound/initialized")
    args, aux = mod._exec.snapshot_arrays()
    param_names = set(mod._param_names)
    arrays = {"params": {k: v for k, v in args.items() if k in param_names},
              "aux": aux}
    opt_tree = None
    meta: Dict[str, object] = {}
    if getattr(mod, "_updater", None) is not None:
        opt_tree = _pack_states_device(mod._updater.states)
    if getattr(mod, "_optimizer", None) is not None:
        meta["optimizer"] = {
            "num_update": int(mod._optimizer.num_update),
            "index_update_count": {
                str(k): int(v) for k, v in
                mod._optimizer._index_update_count.items()},
        }
    if getattr(mod, "_loss_scaler", None) is not None:
        # raw device scalars: the writer thread floats them into the
        # manifest, so AMP checkpoints add no sync to the fit thread
        s = mod._loss_scaler.state()
        meta["scaler"] = [s[0], s[1]]
    from .. import random as _random

    rng = _random.get_state()
    if rng is not None:
        meta["rng"] = [int(x) for x in _np.asarray(rng).ravel()]
    return arrays, opt_tree, meta


def restore_train_state(mod, info, arrays, opt_tree) -> ResumePoint:
    """Install a restored checkpoint (from ``CheckpointManager.restore``)
    into a bound Module: params, aux, optimizer state + host counters,
    loss-scaler state, RNG.  Returns the resume point."""
    import jax.numpy as jnp

    params = arrays.get("params", {})
    missing = sorted(n for n in mod._param_names
                     if n not in params and n in (mod._exec.arg_dict or {}))
    if missing:
        raise MXNetError(
            f"checkpoint {info.path} is missing parameter {missing[0]!r} "
            f"required by the bound symbol ({len(missing)} missing in "
            "total)")
    for n, v in params.items():
        dst = mod._exec.arg_dict.get(n)
        if dst is None:
            continue
        if tuple(dst.shape) != tuple(v.shape):
            raise MXNetError(
                f"checkpoint {info.path}: parameter {n!r} has shape "
                f"{tuple(v.shape)}, bound symbol expects "
                f"{tuple(dst.shape)}")
        dst._data = jnp.asarray(v, dtype=dst._data.dtype)
    for n, v in arrays.get("aux", {}).items():
        dst = mod._exec.aux_dict.get(n)
        if dst is not None:
            dst._data = jnp.asarray(v, dtype=dst._data.dtype)
    if getattr(mod, "_sync_params_from_exec", None) is not None:
        mod._sync_params_from_exec()
    if opt_tree is not None and getattr(mod, "_updater", None) is not None:
        mod._updater.states = _states_from_host(opt_tree)
        if hasattr(mod, "_drop_fused_plan"):
            mod._drop_fused_plan()  # the fused step's plan held the old ones
    meta = info.meta
    opt_meta = meta.get("optimizer")
    if opt_meta and getattr(mod, "_optimizer", None) is not None:
        mod._optimizer.num_update = int(opt_meta["num_update"])
        mod._optimizer._index_update_count = {
            int(k): int(v)
            for k, v in opt_meta["index_update_count"].items()}
    if meta.get("scaler") is not None \
            and getattr(mod, "_loss_scaler", None) is not None:
        s = meta["scaler"]
        mod._loss_scaler.set_state((jnp.float32(s[0]), jnp.float32(s[1])))
    if meta.get("rng") is not None:
        from .. import random as _random

        _random.set_state(_np.asarray(meta["rng"], dtype=_np.uint32))
    return ResumePoint(meta.get("epoch", 0), meta.get("nbatch", 0),
                       meta.get("global_step", info.step))


class TrainCheckpointer:
    """Periodic async + final synchronous checkpoints for ``Module.fit``.

    ``every``: global-step cadence of async saves (0 = only preemption
    saves).  ``keep``: retained checkpoint count.  The module must be
    bound with initialized params and optimizer before ``capture``/
    ``restore`` (fit guarantees this).
    """

    def __init__(self, module, directory: str, every: int = 0,
                 keep: int = 3):
        if not (hasattr(module, "_exec") and hasattr(module, "_updater")):
            raise MXNetError(
                "TrainCheckpointer needs a Module-like with a bound "
                f"executor and updater; got {type(module).__name__}")
        self.module = module
        self.manager = CheckpointManager(directory, keep=keep)
        self.every = int(every or 0)
        self._preempt = None

    # -- preemption wiring --------------------------------------------------------
    def attach_preemption(self, handler) -> None:
        self._preempt = handler

    # -- capture ------------------------------------------------------------------
    def capture(self) -> tuple:
        return capture_train_state(self.module)

    # -- save ---------------------------------------------------------------------
    def save(self, epoch: int, nbatch: int, global_step: int,
             blocking: bool = False) -> None:
        arrays, opt_tree, meta = self.capture()
        meta.update({"epoch": int(epoch), "nbatch": int(nbatch),
                     "global_step": int(global_step)})
        self.manager.save(arrays, opt_tree, meta, step=int(global_step),
                          blocking=blocking)

    def after_batch(self, epoch: int, nbatch: int,
                    global_step: int) -> bool:
        """fit's per-batch hook.  Returns True when a preemption fired: the
        final checkpoint has been written SYNCHRONOUSLY and fit must exit
        gracefully."""
        if self._preempt is not None and self._preempt.poll(global_step):
            _logger.info(
                "preemption signal at epoch %d batch %d (step %d): writing "
                "final synchronous checkpoint", epoch, nbatch, global_step)
            self.save(epoch, nbatch, global_step, blocking=True)
            return True
        if self.every and global_step % self.every == 0:
            self.save(epoch, nbatch, global_step, blocking=False)
        return False

    def close(self) -> None:
        self.manager.close()

    # -- restore ------------------------------------------------------------------
    def restore(self) -> Optional[ResumePoint]:
        """Load the newest VALID checkpoint into the module (params, aux,
        optimizer state + counters, scaler, RNG) and return the resume
        point, or None when the directory holds no valid checkpoint."""
        res = self.manager.restore()
        if res is None:
            return None
        info, arrays, opt_tree = res
        return restore_train_state(self.module, info, arrays, opt_tree)

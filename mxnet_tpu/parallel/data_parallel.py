"""Fused SPMD data-parallel training.

Replaces the reference's DataParallelExecutorGroup + kvstore push/pull loop
(executor_group.py:143, model.py:145-177): instead of slicing the batch across
per-device executors and reducing grads key-by-key, the *whole* train step —
forward, backward, allreduce, optimizer — is one jitted XLA program over a
device mesh.  The batch is sharded on the `dp` axis; parameters are replicated
(or sharded on `tp` for tensor parallelism); XLA inserts ICI allreduces where
the gradient of a replicated parameter meets sharded activations.  This is the
path that must hit the ≥1,200 img/s/chip north star (BASELINE.md).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple

import numpy as _np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import autograd
from ..gluon.block import Block
from ..ndarray.ndarray import NDArray
from .mesh import get_mesh

__all__ = ["DataParallelTrainer", "block_apply_fn", "block_train_fn"]


def block_apply_fn(block: Block, is_train: bool = True):
    """Extract a pure fn(params_dict, x, rng) -> out from a Gluon block.

    The params dict holds *every* parameter including non-differentiable aux
    state (BatchNorm running stats); aux updates made during a traced forward
    are discarded.  For training use :func:`block_train_fn`, which threads aux
    state functionally.
    """
    train_fn, init_params, init_aux = block_train_fn(block, is_train=is_train)
    aux_names = set(init_aux)

    def apply_fn(params: Dict[str, jnp.ndarray], x, rng=None):
        out, _ = train_fn({k: v for k, v in params.items()
                           if k not in aux_names},
                          {k: params[k] for k in aux_names}, x, rng)
        return out

    return apply_fn, {**init_params, **init_aux}


def block_train_fn(block: Block, is_train: bool = True):
    """Extract fn(params, aux, x, rng) -> (out, new_aux) from a Gluon block.

    ``params`` are the differentiable leaves; ``aux`` the non-differentiable
    state leaves (``grad_req == "null"`` — BatchNorm running stats and
    frozen parameters).  Layers mutate aux in place during the traced
    forward (basic_layers.py BatchNorm writes the moving averages into the
    Parameter); here those writes are captured *inside* the trace and
    returned as ``new_aux``, making aux a functional carry the caller
    threads through steps — the TPU-side answer to the reference's in-op
    aux-state mutation (src/operator/nn/batch_norm.cc).
    """
    from .. import random as _random

    # materialize the calling thread's stream key OUTSIDE any trace: the
    # first swap_key inside a jitted apply_fn would otherwise create the
    # key mid-trace and leak a tracer into global state, poisoning every
    # later eager op in the process (the verify-skill gotcha, caught live
    # by the bench synthetic->e2e sequence)
    _random.ensure_key()

    pd = block.collect_params()
    param_names = [n for n in pd if pd[n].grad_req != "null"]
    aux_names = [n for n in pd if pd[n].grad_req == "null"]

    def apply_fn(params: Dict[str, jnp.ndarray], aux: Dict[str, jnp.ndarray],
                 x, rng=None):
        saved = {}
        for name in param_names:
            saved[name] = pd[name]._data._data
            pd[name]._data._data = params[name]
        for name in aux_names:
            saved[name] = pd[name]._data._data
            pd[name]._data._data = aux[name]
        saved_key = _random.swap_key(rng if rng is not None else jax.random.PRNGKey(0))
        try:
            with autograd.pause(train_mode=is_train):
                out = block(NDArray(x))
            new_aux = {n: pd[n]._data._data for n in aux_names}
        finally:
            _random.swap_key(saved_key)
            for name, s in saved.items():
                pd[name]._data._data = s
        out = out._data if isinstance(out, NDArray) else tuple(o._data for o in out)
        return out, new_aux

    try:
        init_params = {n: pd[n].data()._data for n in param_names}
        init_aux = {n: pd[n].data()._data for n in aux_names}
    except Exception as e:
        raise RuntimeError(
            "block has uninitialized (deferred-shape) parameters; run one "
            "forward pass or construct layers with in_units/in_channels before "
            "creating a DataParallelTrainer") from e
    return apply_fn, init_params, init_aux


class DataParallelTrainer:
    """One-program-per-step data-parallel trainer.

    loss_fn(pred, y) -> scalar-per-sample array.  Optimizer: SGD w/ momentum
    + optional weight decay, fused into the step (extend via `update_fn`).
    """

    def __init__(self, block: Block, loss_fn: Callable, lr: float = 0.1,
                 momentum: float = 0.9, weight_decay: float = 0.0,
                 mesh: Optional[Mesh] = None, dp_axis: str = "dp",
                 compute_dtype=None, update_fn: Optional[Callable] = None,
                 donate: bool = True, compression_params: Optional[Dict] = None):
        self._mesh = mesh
        if self._mesh is None:
            fallback = get_mesh()
            # only adopt the ambient mesh if it actually has our axis — a
            # leftover global mesh from unrelated work (say an ep-only MoE
            # mesh) would otherwise crash every sharding constraint here
            if fallback is not None and dp_axis in fallback.shape:
                self._mesh = fallback
        self._axis = dp_axis
        self._block = block
        if isinstance(loss_fn, Block):
            # gluon Loss blocks work as-is: run them over NDArray views of
            # the traced values inside the step (same mechanism hybridize
            # uses), so users pass gluon.loss.* directly
            _loss_block = loss_fn

            def loss_fn(pred, y):  # noqa: F811
                # pause: without it a step() issued inside autograd.record()
                # would record the block's traced ops on the global eager
                # tape and poison the next eager backward (same guard as
                # block_train_fn above)
                with autograd.pause(train_mode=True):
                    out = _loss_block(NDArray(pred), NDArray(y))
                return out._data

        self._loss_fn = loss_fn
        self._lr = lr
        self._momentum = momentum
        self._wd = weight_decay
        self._compute_dtype = compute_dtype
        self._update_fn = update_fn
        self._apply_fn, self.params, self.aux = block_train_fn(
            block, is_train=True)
        self.momenta = {k: jnp.zeros_like(v) for k, v in self.params.items()}
        self._step_fn = None
        self._donate = donate
        self._compression = None
        self.residuals = None
        if compression_params is not None:
            from .compression import GradientCompression

            self._compression = GradientCompression(**compression_params)
            if self._compression.type == "none":
                self._compression = None
        if self._compression is not None:
            # per-device error-feedback residual: leading axis = dp shard
            ndev = self._mesh.shape[self._axis] if self._mesh is not None else 1
            self.residuals = {
                k: jnp.zeros((ndev,) + v.shape, jnp.float32)
                for k, v in self.params.items()}
        if self._mesh is not None:
            self._place_params()
        elif donate:
            # no mesh -> _place_params made no copies, so params/aux still
            # alias the gluon block's live buffers; donation would delete
            # them out from under the block on the first step
            self.params = {k: jnp.copy(v) for k, v in self.params.items()}
            self.aux = {k: jnp.copy(v) for k, v in self.aux.items()}

    def _place_params(self):
        repl = NamedSharding(self._mesh, PartitionSpec())
        self.params = {k: jax.device_put(v, repl) for k, v in self.params.items()}
        self.momenta = {k: jax.device_put(v, repl) for k, v in self.momenta.items()}
        self.aux = {k: jax.device_put(v, repl) for k, v in self.aux.items()}
        if self.residuals is not None:
            shard = NamedSharding(self._mesh, PartitionSpec(self._axis))
            self.residuals = {k: jax.device_put(v, shard)
                              for k, v in self.residuals.items()}

    def _build_step(self):
        apply_fn = self._apply_fn
        loss_fn = self._loss_fn
        lr, mom, wd = self._lr, self._momentum, self._wd
        cdt = self._compute_dtype
        update_fn = self._update_fn

        def loss_of(p, aux, x, y, rng):
            pc = p if cdt is None else jax.tree_util.tree_map(
                lambda a: a.astype(cdt), p)
            xin = x if cdt is None else x.astype(cdt)
            pred, new_aux = apply_fn(pc, aux, xin, rng)
            return jnp.mean(loss_fn(pred, y).astype(jnp.float32)), new_aux

        def apply_update(params, momenta, grads):
            if update_fn is not None:
                return update_fn(params, momenta, grads)
            new_momenta = jax.tree_util.tree_map(
                lambda m, g: mom * m + g, momenta, grads)
            new_params = jax.tree_util.tree_map(
                lambda p, m: p * (1.0 - lr * wd) - lr * m.astype(p.dtype),
                params, new_momenta)
            return new_params, new_momenta

        if self._compression is not None:
            return self._build_compressed_step(loss_of, apply_update)

        def step(params, momenta, aux, x, y, rng):
            (loss, new_aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, aux, x, y, rng)
            new_params, new_momenta = apply_update(params, momenta, grads)
            return loss, new_params, new_momenta, new_aux

        if self._mesh is None:
            return jax.jit(step,
                           donate_argnums=(0, 1, 2) if self._donate else ())
        repl = NamedSharding(self._mesh, PartitionSpec())
        shard = NamedSharding(self._mesh, PartitionSpec(self._axis))
        return jax.jit(
            step,
            in_shardings=({k: repl for k in self.params},
                          {k: repl for k in self.momenta},
                          {k: repl for k in self.aux}, shard, shard, repl),
            out_shardings=(repl, {k: repl for k in self.params},
                           {k: repl for k in self.momenta},
                           {k: repl for k in self.aux}),
            donate_argnums=(0, 1, 2) if self._donate else (),
        )

    def _build_compressed_step(self, loss_of, apply_update):
        """2-bit compressed allreduce: each device quantizes its *local* mean
        gradient with a per-device error-feedback residual, the dequantized
        values are pmean'd over the dp axis, and the optimizer consumes the
        result — the tpu_sync analogue of the reference's worker-quantize →
        server-dequantize-merge path (gradient_compression.h:111-121), with
        the wire replaced by ICI and the 16× saving realized in the collective
        input's bit width.
        """
        gc = self._compression
        axis = self._axis

        def compress_grads(g, residuals):
            dq, new_res = {}, {}
            for k in g:
                d, r = gc.quantize_dequantize(g[k].astype(jnp.float32),
                                              residuals[k][0])
                dq[k] = d
                new_res[k] = r[None]
            return dq, new_res

        def local_grads(params, aux, residuals, x, y, rng):
            # runs per device under shard_map: x/y/residuals are local shards
            (loss, new_aux), g = jax.value_and_grad(
                loss_of, has_aux=True)(params, aux, x, y, rng)
            dq, new_res = compress_grads(g, residuals)
            mean = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, axis), dq)
            # aux (BN running stats) computed from per-device batch stats:
            # average across the dp axis so the carry stays replicated
            new_aux = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, axis), new_aux)
            return jax.lax.pmean(loss, axis), mean, new_res, new_aux

        def step(params, momenta, aux, residuals, x, y, rng):
            if self._mesh is not None:

                P = PartitionSpec
                loss, grads, new_res, new_aux = jax.shard_map(
                    local_grads, mesh=self._mesh,
                    in_specs=(P(), P(), P(axis), P(axis), P(axis), P()),
                    out_specs=(P(), P(), P(axis), P()),
                    # pallas_call can't declare varying-mesh-axes metadata
                    check_vma=False,
                )(params, aux, residuals, x, y, rng)
            else:
                (loss, new_aux), g = jax.value_and_grad(
                    loss_of, has_aux=True)(params, aux, x, y, rng)
                grads, new_res = compress_grads(g, residuals)
            new_params, new_momenta = apply_update(params, momenta, grads)
            return loss, new_params, new_momenta, new_res, new_aux

        donate = (0, 1, 2, 3) if self._donate else ()
        if self._mesh is None:
            return jax.jit(step, donate_argnums=donate)
        repl = NamedSharding(self._mesh, PartitionSpec())
        shard = NamedSharding(self._mesh, PartitionSpec(self._axis))
        return jax.jit(
            step,
            in_shardings=({k: repl for k in self.params},
                          {k: repl for k in self.momenta},
                          {k: repl for k in self.aux},
                          {k: shard for k in self.params}, shard, shard, repl),
            out_shardings=(repl, {k: repl for k in self.params},
                           {k: repl for k in self.momenta},
                           {k: shard for k in self.params},
                           {k: repl for k in self.aux}),
            donate_argnums=donate,
        )

    def step(self, x, y, rng=None):
        """Run one fused train step; returns scalar loss (async)."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        if isinstance(x, NDArray):
            x = x._data
        if isinstance(y, NDArray):
            y = y._data
        from .. import random as _random

        _random.ensure_key()
        if rng is None:
            rng = _random.next_key()
        if self._mesh is not None:
            shard = NamedSharding(self._mesh, PartitionSpec(self._axis))
            x = jax.device_put(x, shard)
            y = jax.device_put(y, shard)
        if self._compression is not None:
            (loss, self.params, self.momenta, self.residuals,
             self.aux) = self._step_fn(
                self.params, self.momenta, self.aux, self.residuals, x, y, rng)
        else:
            loss, self.params, self.momenta, self.aux = self._step_fn(
                self.params, self.momenta, self.aux, x, y, rng)
        return loss

    def write_back(self):
        """Copy trained params + aux state back into the Gluon block's buffers
        (re-placed on a single device so the eager frontend can keep using
        them)."""
        pd = self._block.collect_params()
        for name, v in self.params.items():
            pd[name]._data._data = jax.device_put(_np.asarray(v))
        for name, v in self.aux.items():
            pd[name]._data._data = jax.device_put(_np.asarray(v))

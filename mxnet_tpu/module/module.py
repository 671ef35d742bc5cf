"""Module: symbolic training on one or more devices (reference:
python/mxnet/module/module.py — bind :364, init_optimizer :474).

TPU-native: one Executor compiles the whole fwd+bwd graph to a single XLA
program.  Data parallelism over a device mesh is expressed by sharding the
batch dimension (parallel/), not by per-device executor replicas — the
reference's DataParallelExecutorGroup becomes a sharding annotation.
"""
from __future__ import annotations

import logging
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as _np
import jax.numpy as jnp

from .. import ndarray as nd
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..initializer import InitDesc, Uniform
from ..model import (_create_kvstore, _fused_step_allowed, _initialize_kvstore,
                     _update_params, _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from ..ndarray.ndarray import NDArray
from ..observability import tracing as _tracing
from ..optimizer import Optimizer, Updater, create as _create_optimizer, get_updater
from .base_module import BaseModule, _check_input_names

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._state_names = list(state_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names + self._state_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._compression_params = compression_params
        self._fused_step_count = 0
        self._prepared = None  # (batch, plan) readied by prepare()
        self._shared_bound = False
        self._amp_cfg = None      # resolved at bind (env TPUMX_AMP*)
        self._loss_scaler = None  # created at init_optimizer when needed
        # partition rules (docs/sharding.md): ordered (regex, PartitionSpec)
        # pairs accepted at bind()/fit() — or via TPUMX_SHARD_RULES — that
        # shard params/grads/optimizer state on the mp axis of the
        # ("dp","mp") mesh when TPUMX_MP_DEVICES widens model parallelism
        self._shard_rules = None
        _check_input_names(symbol, self._data_names, "data", True)
        _check_input_names(symbol, self._label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        self._symbol.save(f"{prefix}-symbol.json")
        self.save_params(f"{prefix}-{epoch:04d}.params")
        if save_optimizer_states:
            self.save_optimizer_states(f"{prefix}-{epoch:04d}.states")

    # -- properties ---------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        if self._exec.outputs:
            return [(n, o.shape) for n, o in
                    zip(self._output_names, self._exec.outputs)]
        _, out_shapes, _ = self._symbol.infer_shape(**self._shape_kwargs())
        return list(zip(self._output_names, out_shapes))

    def _shape_kwargs(self):
        return dict(self._data_shapes + self._label_shapes)

    def _dp_size(self) -> int:
        """Effective data-parallel width: ``TPUMX_DP_DEVICES`` when set (>1),
        else the number of bound contexts.  >1 routes fit through the SPMD
        fused step (docs/multichip.md)."""
        import os

        env = os.environ.get("TPUMX_DP_DEVICES", "")
        if env:
            try:
                n = int(env)
            except ValueError:
                n = 0
            if n > 1:
                return n
        return len(self._context)

    def _mp_size(self) -> int:
        """Model-parallel width (``TPUMX_MP_DEVICES``): >1 adds an ``mp``
        axis to the fused-step mesh and shards params/grads/optimizer state
        over it per the bound partition rules (docs/sharding.md)."""
        import os

        env = os.environ.get("TPUMX_MP_DEVICES", "")
        try:
            n = int(env) if env else 0
        except ValueError:
            n = 0
        return n if n > 1 else 1

    def _pp_size(self) -> int:
        """Pipeline-parallel width (``TPUMX_PP_DEVICES``): >1 adds a ``pp``
        axis to the fused-step mesh and, when the bound symbol is
        stage-stackable (symbol/staging.py), runs the repeated body as a
        GPipe microbatch round-robin inside the ONE donated program
        (docs/sharding.md)."""
        import os

        env = os.environ.get("TPUMX_PP_DEVICES", "")
        try:
            n = int(env) if env else 0
        except ValueError:
            n = 0
        return n if n > 1 else 1

    # -- binding ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write", shard_rules=None):
        if shard_rules is not None:
            self._shard_rules = shard_rules
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        def _norm(shapes):
            out = []
            for s in shapes or []:
                if isinstance(s, tuple) and len(s) == 2 and isinstance(s[0], str):
                    out.append((s[0], tuple(s[1])))
                else:  # DataDesc
                    out.append((s.name, tuple(s.shape)))
            return out

        self._data_shapes = _norm(data_shapes)
        self._label_shapes = _norm(label_shapes)
        shape_kwargs = self._shape_kwargs()

        # AMP casting policy (env-driven, docs/amp.md): bind a CONVERTED
        # symbol — matmul/conv inputs cast to the target dtype in-graph,
        # softmax/norm/loss inputs forced back to f32 — while self._symbol
        # (arguments, checkpoints, user introspection) stays the original.
        # TPUMX_AMP=0/unset leaves this path untouched.
        from .. import amp as _amp

        self._amp_cfg = _amp.active_config()
        bind_symbol = self._symbol
        if self._amp_cfg is not None:
            bind_symbol = _amp.convert_symbol(self._symbol,
                                              self._amp_cfg.dtype)

        req = {}
        for n in self._symbol.list_arguments():
            if n in self._data_names:
                req[n] = "write" if inputs_need_grad else "null"
            elif n in self._label_names or n in self._state_names:
                req[n] = "null"
            elif n in self._fixed_param_names or not for_training:
                req[n] = "null"
            else:
                req[n] = grad_req
        self._exec = bind_symbol.simple_bind(
            ctx=self._context[0], grad_req=req, **shape_kwargs)
        self._maybe_attach_spmd_mesh()
        # shared binding may alias param buffers with another module's
        # executor — donation in the fused path would invalidate them
        self._shared_bound = shared_module is not None
        if shared_module is not None and shared_module._exec is not None:
            self._exec.copy_params_from(*shared_module.get_params())
        if self._arg_params is not None:
            self._exec.copy_params_from(self._arg_params, self._aux_params,
                                        allow_extra_params=True)

    def _maybe_attach_spmd_mesh(self):
        """Annotate the executor with its SPMD mesh when this Module is
        bound for multi-device training (several contexts,
        ``TPUMX_DP_DEVICES``, or ``TPUMX_MP_DEVICES``): the SPMD fused step
        then shards the batch across the ``dp`` axis and allreduces
        gradients in-program, replacing the reference's per-device executor
        groups + host kvstore reduce.  With model parallelism
        (``TPUMX_MP_DEVICES`` > 1) the mesh gains an ``mp`` axis and the
        bound partition rules (``shard_rules`` at bind/fit,
        ``TPUMX_SHARD_RULES``, or the FSDP catch-all default) resolve to a
        per-param spec pytree that shards params, gradients, and optimizer
        state over it (docs/sharding.md).  Best-effort: anything the SPMD
        program can't express (indivisible batch, RNN carry states,
        un-inferable output shapes) leaves the annotation off and fit takes
        the legacy path."""
        import os

        ndev = self._dp_size()
        mp = self._mp_size()
        pp = self._pp_size()
        if (ndev * mp * pp <= 1 or not self.for_training or self._state_names
                or os.environ.get("TPUMX_FUSED_STEP", "1") == "0"
                or os.environ.get("TPUMX_FUSED_STEP_SPMD", "1") == "0"):
            return
        rules = None
        if mp > 1:
            from ..parallel import partition_rules as _pr

            env_rules = _pr.rules_from_env()
            rules = (self._shard_rules or env_rules
                     or _pr.DEFAULT_FSDP_RULES)
            # unknown mesh-axis names in a rule must raise a clear error
            # NOW, not surface as an opaque shard_map failure (or a silent
            # legacy-path fallback) three layers down
            axes = ("dp",) + (("pp",) if pp > 1 else ()) \
                + (("mp",) if mp > 1 else ())
            _pr.validate_rule_axes(
                rules, axes,
                source=("TPUMX_SHARD_RULES" if self._shard_rules is None
                        and env_rules is not None else "shard_rules"))
        try:
            self._attach_spmd_mesh(ndev, mp, pp, rules)
        except Exception as e:
            self.logger.warning(
                "SPMD fused step unavailable (%s); multi-device fit will use "
                "the legacy executor-group path", e)

    def _attach_spmd_mesh(self, ndev, mp, pp, rules):
        from ..parallel.mesh import make_mesh

        devices = None
        if len(self._context) > 1 and mp <= 1 and pp <= 1:
            devices = [c.jax_device for c in self._context]
        pipeline = None
        if pp > 1:
            # stage-stackable symbols pipeline the repeated body over a pp
            # axis (symbol/staging.py); anything else drops pp with a
            # logged reason and trains on the dp×mp mesh
            pipeline = self._plan_pipeline(ndev, pp)
            if pipeline is None:
                pp = 1
        axes = {"dp": ndev}
        if pp > 1:
            axes["pp"] = pp
        if mp > 1:
            axes["mp"] = mp
        if ndev * mp * pp <= 1:
            return
        mesh = make_mesh(axes, devices=devices, install=False)
        param_specs = None
        compute = False
        if mp > 1:
            from ..parallel import partition_rules as _pr

            shapes = {n: tuple(self._exec.arg_dict[n].shape)
                      for n in self._param_names
                      if n not in self._fixed_param_names
                      and n in self._exec.arg_dict}
            param_specs = _pr.make_param_specs(rules, shapes, mesh,
                                               mp_axis="mp")
            # tensor-parallel COMPUTE (docs/sharding.md): explicit
            # column/row rule sets partition the matmuls via GSPMD; the
            # FSDP catch-all keeps gather-compute-slice.  TPUMX_MP_COMPUTE=0
            # pins the gather path byte-for-byte (keys included).  The
            # pipelined program is a shard_map — mp stays a storage axis
            # under pp.
            compute = (pp <= 1 and _pr.mp_compute_enabled()
                       and _pr.rules_compute_partitionable(rules))
        self._exec.set_spmd(
            mesh, batch_args=self._data_names + self._label_names,
            param_specs=param_specs, compute=compute, pipeline=pipeline)

    def _plan_pipeline(self, ndev, pp):
        """(plan, n_micro) when the bound symbol splits into ``pp`` stages
        and the microbatch count divides the per-dp-shard batch; None (with
        a logged reason) otherwise."""
        import os

        import jax

        from ..symbol.staging import PlanError, plan_pipeline

        batch = self._data_shapes[0][1][0] if self._data_shapes else 0
        if not batch or batch % ndev:
            return None
        local_batch = batch // ndev
        env = os.environ.get("TPUMX_PP_MICROBATCHES", "")
        try:
            n_micro = int(env) if env else 0
        except ValueError:
            n_micro = 0
        if not n_micro:
            n_micro = next((m for m in (4 * pp, 2 * pp, pp)
                            if m <= local_batch and local_batch % m == 0), 0)
        if n_micro < 1 or local_batch % n_micro:
            self.logger.warning(
                "pipeline: local batch %d has no usable microbatch count "
                "(TPUMX_PP_MICROBATCHES=%s); dropping the pp axis",
                local_batch, env or "auto")
            return None
        structs = {n: jax.ShapeDtypeStruct(tuple(a.shape), a._data.dtype)
                   for n, a in list(self._exec.arg_dict.items())
                   + list(self._exec.aux_dict.items())}
        try:
            plan = plan_pipeline(
                self._exec._symbol._entries, pp, structs,
                input_names=(self._data_names + self._label_names
                             + self._state_names))
        except PlanError as e:
            self.logger.warning(
                "pipeline: symbol is not stage-stackable (%s); dropping "
                "the pp axis", e)
            return None
        self.logger.info("pipeline: %s, %d microbatches", plan.describe(),
                         n_micro)
        return (plan, n_micro)

    # -- params -------------------------------------------------------------------
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before init_params"
        attrs = self._symbol.attr_dict()

        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                # copy=True: the executor must own its param buffers uniquely
                # (same-dtype astype aliases, and the fused step DONATES them)
                arr._data = jnp.array(arg_params[name]._data,
                                      dtype=arr._data.dtype, copy=True)
            elif arg_params is not None and not allow_missing:
                # a partial checkpoint with allow_missing=False must raise,
                # not silently fall through to the initializer (reference
                # module.py init_params)
                raise MXNetError(
                    f"parameter {name} not present in arg_params "
                    "(pass allow_missing=True to initialize it instead)")
            elif initializer is not None:
                desc = InitDesc(name, attrs.get(name))
                initializer(desc, arr)
            elif not allow_missing:
                raise MXNetError(
                    f"missing parameter {name} and no initializer given")
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                arr._data = jnp.array(aux_params[name]._data,
                                      dtype=arr._data.dtype, copy=True)
            elif aux_params is not None and not allow_missing:
                raise MXNetError(
                    f"aux state {name} not present in aux_params "
                    "(pass allow_missing=True to initialize it instead)")
            elif initializer is not None:
                desc = InitDesc(name, attrs.get(name))
                initializer(desc, arr)
        self.params_initialized = True
        self._params_dirty = False
        self._sync_params_from_exec()

    def _sync_params_from_exec(self):
        self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        self._aux_params = dict(self._exec.aux_dict)

    def get_params(self):
        assert self.params_initialized
        self._sync_params_from_exec()
        if self._fused_step_count:
            # NDArray.copy() shares the device buffer; under the fused path
            # the executor's buffers are donated every step, so a snapshot
            # must own fresh device memory to survive the next step.  Under
            # partition rules the live params are mp-sharded: gather through
            # the host so the snapshot (and any checkpoint written from it)
            # holds the same full arrays as the replicated layout
            # (docs/sharding.md — save under one mesh, restore under
            # another).
            if self._exec is not None and self._exec._spmd_param_specs:
                deep = lambda v: NDArray(jnp.asarray(_np.asarray(v._data)))
            else:
                deep = lambda v: NDArray(jnp.array(v._data, copy=True))
            return ({k: deep(v) for k, v in self._arg_params.items()},
                    {k: deep(v) for k, v in self._aux_params.items()})
        return ({k: v.copy() for k, v in self._arg_params.items()},
                {k: v.copy() for k, v in self._aux_params.items()})

    # -- optimizer ----------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),), force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        # effective dp width (TPUMX_DP_DEVICES can widen a single-context
        # module): a >1 width must materialize the collective store rather
        # than collapse to kv=None the way num_device==1 does
        kv, update_on_kvstore = _create_kvstore(
            kvstore, self._dp_size(),
            {n: self._exec.arg_dict[n] for n in self._param_names})
        batch_size = self._data_shapes[0][1][0] if self._data_shapes else 1
        if kv and "dist" in kv.type and "_sync" in kv.type:
            batch_size *= kv.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            # the non-kvstore updater indexes params as i*num_device+k
            # (model._update_params), so idx2name must cover every device
            # slot or lr_mult/wd_mult and per-param state misroute
            ndev = len(self._context)
            idx2name = {i * ndev + k: n
                        for i, n in enumerate(self._param_names)
                        for k in range(ndev)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = _create_optimizer(optimizer, sym=self._symbol,
                                          param_idx2name=idx2name,
                                          **optimizer_params)
        self._optimizer = optimizer
        self._kvstore = kv
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        self._drop_fused_plan()  # another optimizer, updater and scaler
        if kv:
            if self._compression_params:
                kv.set_gradient_compression(self._compression_params)
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            _initialize_kvstore(
                kvstore=kv,
                param_arrays=[[self._exec.arg_dict[n]] for n in self._param_names],
                arg_params={n: self._exec.arg_dict[n] for n in self._param_names},
                param_names=self._param_names,
                update_on_kvstore=update_on_kvstore)
        if not update_on_kvstore:
            self._updater = get_updater(self._optimizer)
        # traced loss scaling (docs/amp.md): created once per optimizer init
        # so its (scale, good_steps) device state persists across batches,
        # epochs, AND rebinds (_reshape_exec re-runs bind, not this)
        from .. import amp as _amp

        self._loss_scaler = _amp.make_loss_scaler(self._amp_cfg)
        if self._loss_scaler is not None and not _fused_step_allowed(
                self._optimizer, self._kvstore, self._update_on_kvstore,
                self._dp_size()):
            self.logger.warning(
                "AMP loss scaling requires the fused train step; this "
                "configuration falls back to the legacy path and trains "
                "UNSCALED (docs/amp.md)")
            self._loss_scaler = None
        self.optimizer_initialized = True
        if hasattr(self, "_preload_opt_states"):
            self.load_optimizer_states(self._preload_opt_states)
            del self._preload_opt_states

    # -- compute ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        feed = {}
        for (name, _), arr in zip(self._data_shapes, data_batch.data):
            feed[name] = arr
        if self._label_shapes and data_batch.label:
            for (name, _), arr in zip(self._label_shapes, data_batch.label):
                feed[name] = arr
        # allow shape change (new bucket/batch size): rebind cheaply
        cur = dict(self._data_shapes)
        new_shapes = {n: tuple(a.shape) for n, a in
                      zip([s[0] for s in self._data_shapes], data_batch.data)}
        if any(cur[n] != s for n, s in new_shapes.items()):
            self._reshape_exec(data_batch)
        self._exec.forward(is_train=is_train, **feed)

    def _reshape_exec(self, data_batch):
        data_shapes = [(n, tuple(a.shape)) for (n, _), a in
                       zip(self._data_shapes, data_batch.data)]
        label_shapes = None
        if self._label_shapes and data_batch.label:
            label_shapes = [(n, tuple(a.shape)) for (n, _), a in
                            zip(self._label_shapes, data_batch.label)]
        arg_params, aux_params = self.get_params()
        self.binded = False
        self.bind(data_shapes, label_shapes, for_training=self.for_training,
                  inputs_need_grad=self.inputs_need_grad, force_rebind=True)
        self._exec.copy_params_from(arg_params, aux_params, allow_extra_params=True)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    # -- fused whole-train-step ---------------------------------------------------
    def _fused_ready(self) -> bool:
        """Whether a step of this module may be the fused program's: read
        when a step plan is built, not at every step — what could change
        the answer later drops the plan or sits in :meth:`_fused_guard`."""
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized):
            return False
        ndev = self._dp_size()
        if not _fused_step_allowed(self._optimizer, self._kvstore,
                                   self._update_on_kvstore, ndev):
            return False
        if self._updater is None or self._shared_bound or self.inputs_need_grad:
            return False
        if self._exec is None or self._exec._grouped is not None:
            return False
        if self._exec._monitor_callback is not None:
            return False  # per-step introspection wants the legacy path
        # every gradient-taking argument must be a parameter we can update
        if set(self._exec._grad_arg_names) - set(self._param_names):
            return False
        if ndev > 1:
            # multi-device: the SPMD mesh must be attached and the global
            # batch must shard evenly across it (the dp axis only; the mp
            # axis never sees the batch dimension)
            if self._exec._spmd_ndev() != ndev:
                return False
            batch = self._data_shapes[0][1][0] if self._data_shapes else 0
            if not batch or batch % ndev:
                return False
        if self._mp_size() > 1:
            # model parallelism needs the 2-D mesh + resolved specs attached,
            # and an optimizer whose update is elementwise in the weight
            # (the shard-wise update contract, optimizer.py)
            mesh = self._exec._spmd_mesh
            if mesh is None or "mp" not in mesh.axis_names:
                return False
            if self._exec._spmd_param_specs and not getattr(
                    self._optimizer, "update_step_elementwise", True):
                return False
        return True

    def _drop_fused_plan(self):
        """Forget the fused step's plan (docs/fused_step.md): called by
        whatever replaces something the plan holds — another optimizer or
        updater, a loss scaler, the state holders of a loaded checkpoint.
        The next step builds it again (or takes the legacy path)."""
        self._prepared = None
        if self._exec is not None:
            self._exec._fused_plan = None

    def _fused_guard(self) -> tuple:
        """What a plan was built under that nobody can name a hook for:
        the optimizer's baked-in hyperparameters and multipliers, the
        scaler's, and the environment's switches.  One tuple compare a
        step; everything else that a plan depends on drops it by name
        (:meth:`_drop_fused_plan`)."""
        import os

        from ..observability import telemetry as _tele

        opt, sc, env = self._optimizer, self._loss_scaler, os.environ.get
        ndev = self._dp_size()
        return (opt.fused_static_key(), getattr(opt, "_mult_epoch", 0),
                sc, None if sc is None else sc.static_key(),
                _tele.enabled(), ndev,
                _fused_step_allowed(opt, self._kvstore,
                                    self._update_on_kvstore, ndev),
                env("TPUMX_MP_DEVICES"), env("TPUMX_PP_DEVICES"),
                env("TPUMX_PALLAS"))

    def _fused_plan(self, guard):
        """The executor's kept plan if it still stands, else None."""
        plan = self._exec._fused_plan
        if plan is not None and plan.guard == guard \
                and plan.states_of is self._updater.states:
            return plan
        return None

    def _build_fused_plan(self, guard):
        """The plan of this module's fused step, built when its first step
        runs and after whatever dropped it; None when the step belongs to
        the legacy path (:meth:`_fused_ready`)."""
        if not self._fused_ready():
            return None
        grad_names = set(self._exec._grad_arg_names)
        # idx: the legacy i*num_device+k slot scheme (k=0 slot), where
        # num_device is the CONTEXT count exactly as init_optimizer's
        # idx2name uses it — lr_mult/wd_mult lookups and optimizer-state
        # checkpoints stay compatible with the per-device updater layout
        # (TPUMX_DP_DEVICES widens the mesh, not the slot scheme)
        nslot = len(self._context)
        updates = [(n, i * nslot) for i, n in enumerate(self._param_names)
                   if n in grad_names]
        states = {}
        for name, idx in updates:
            if idx not in self._updater.states:
                self._updater.states[idx] = \
                    self._optimizer.create_state_multi_precision(
                        idx, self._exec.arg_dict[name])
            states[name] = self._updater.states[idx]
        plan = self._exec.plan_fused_step(
            self._optimizer, states, updates, num_steps=1,
            kvstore=self._kvstore, loss_scaler=self._loss_scaler)
        plan.guard, plan.states_of = guard, self._updater.states
        return plan

    def _prepare_fused(self, data_batch, at_call: bool):
        """Ready the fused step's launch for ``data_batch`` — everything
        that needs no result of the step before it and changes nothing a
        callback can see: the plan validated, the batch's shapes checked
        against the bound ones, the batch placed on the mesh.  Returns the
        plan, or None when there is nothing to launch from.  ``prepare``
        calls this while the device runs the step before; a batch that
        nobody prepared gets the same at the call (``at_call``), which
        alone may build a plan or rebind for another shape."""
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized and self._updater is not None):
            return None
        with _tracing.span("executor.prepare", cat="executor"):
            guard = self._fused_guard()
            plan = self._fused_plan(guard)
            if not at_call and plan is None:
                return None
            if any(shape != tuple(a.shape) for (_, shape), a in
                   zip(self._data_shapes, data_batch.data)):
                if not at_call or (plan is None and not self._fused_ready()):
                    return None
                self._reshape_exec(data_batch)  # a new executor: no plan
                plan = None
            if plan is None:
                plan = self._build_fused_plan(guard)
                if plan is None:
                    return None
            if plan.spmd:
                # one device_put per array with a NamedSharding on the
                # batch axis, mutating the batch's NDArrays in place:
                # executor feed AND device-side metrics (labels vs sharded
                # outputs) stay consistent (dp=1 × mp>1 meshes still need
                # the batch placed over the full mesh device set — P('dp')
                # replicates it across mp)
                from ..io import shard_data_batch

                shard_data_batch(data_batch, self._exec._spmd_mesh,
                                 self._exec._spmd_axis)
            return plan

    def prepare(self, data_batch, sparse_row_id_fn=None):
        """``fit`` hands the NEXT batch over between ``update_metric`` and
        the callbacks, while the device still runs the step: ready its
        launch now (docs/fused_step.md "What prepare does").  Nothing a
        callback at this batch can see changes."""
        self._prepared = None
        if self._exec is not None and self._exec._fused_plan is not None:
            plan = self._prepare_fused(data_batch, at_call=False)
            if plan is not None:
                try:  # weakly: a batch nobody steps on is not kept alive
                    self._prepared = (weakref.ref(data_batch), plan)
                except TypeError:
                    pass  # a list of batches: prepared at its call

    def _try_fused_step(self, data_batch) -> bool:
        """Forward + backward + full optimizer update as ONE donated XLA
        program (Executor.run_fused_step over the kept plan).  Optimizer
        state lives in the legacy Updater's slots (device-side, updated in
        place) so save/load_optimizer_states round-trip unchanged."""
        prepared, self._prepared = self._prepared, None
        plan = None
        if prepared is not None and prepared[0]() is data_batch:
            # a callback ran since: the plan must still stand
            plan = self._fused_plan(self._fused_guard())
            if plan is not prepared[1]:
                plan = None
        if plan is None:
            plan = self._prepare_fused(data_batch, at_call=True)
            if plan is None:
                return False
        ex = self._exec
        arg_dict = ex.arg_dict
        for (name, _), arr in zip(self._data_shapes, data_batch.data):
            arg_dict[name]._data = arr._data
        if self._label_shapes and data_batch.label:
            for (name, _), arr in zip(self._label_shapes, data_batch.label):
                arg_dict[name]._data = arr._data
        if not ex.run_fused_step(plan):
            return False  # mixed update counts: the per-param loop's
        self._params_dirty = True
        self._fused_step_count += 1
        # telemetry stays device-side across steps; only every
        # TPUMX_TELEMETRY_EVERY-th step materializes the handful of scalars
        # into registry gauges — the no-per-batch-asnumpy property holds
        if ex._telemetry_last is not None:
            from ..observability import telemetry as _tele

            if self._fused_step_count % _tele.every() == 0:
                _tele.publish(ex.telemetry_snapshot())
        return True

    def update(self):
        assert self.binded and self.params_initialized and self.optimizer_initialized
        self._params_dirty = True
        param_arrays = [[self._exec.arg_dict[n]] for n in self._param_names]
        grad_arrays = [[self._exec.grad_dict.get(n)] for n in self._param_names]
        if self._update_on_kvstore:
            _update_params_on_kvstore(param_arrays, grad_arrays, self._kvstore,
                                      self._param_names)
        else:
            _update_params(param_arrays, grad_arrays, updater=self._updater,
                           num_device=len(self._context), kvstore=self._kvstore,
                           param_names=self._param_names)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        return list(self._exec.outputs)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        # device=True: metrics that can accumulate device-side do so without
        # asnumpy() — the host sync happens once, at get()/epoch boundaries.
        # Under SPMD the outputs live sharded on the dp mesh: labels must
        # join them there (sharded on the batch axis, or replicated when the
        # final batch doesn't divide) so the device-side comparison stays one
        # in-program computation — per-shard counts combined by an XLA psum,
        # never a per-batch host sync.
        if (self._exec is not None and self._exec._spmd_active
                and self._exec._spmd_mesh is not None):
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            mesh = self._exec._spmd_mesh
            axis = self._exec._spmd_axis
            ndev = self._exec._spmd_ndev()
            for l in labels or []:
                if isinstance(l, NDArray) and l._data is not None:
                    spec = PartitionSpec(axis) if l.shape \
                        and l.shape[0] % ndev == 0 else PartitionSpec()
                    l._data = jax.device_put(
                        l._data, NamedSharding(mesh, spec))
        eval_metric.update_dict(
            dict(zip(self._label_names, labels or [])),
            dict(zip(self._output_names, self._exec.outputs)),
            device=True)

    # -- states -------------------------------------------------------------------
    def get_states(self, merge_multi_context=True):
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        if states is not None:
            for n, s in zip(self._state_names, states):
                self._exec.arg_dict[n]._data = s._data
        elif value is not None:
            for n in self._state_names:
                arr = self._exec.arg_dict[n]
                arr[:] = value

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as f:
                f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())
            self._drop_fused_plan()  # the state's holders are new ones

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        arg_params, aux_params = self.get_params()
        self.bind(data_shapes, label_shapes, for_training=self.for_training,
                  inputs_need_grad=self.inputs_need_grad, force_rebind=True)
        self._exec.copy_params_from(arg_params, aux_params, allow_extra_params=True)

    # -- fault tolerance ----------------------------------------------------------
    def capture_train_state(self):
        """Device-copied snapshot of the COMPLETE train state (params, aux,
        optimizer state incl. AMP masters, optimizer counters, loss-scaler,
        RNG) as ``(arrays, opt_tree, meta)`` — what one fault-tolerant
        checkpoint persists (docs/fault_tolerance.md).  Safe against the
        fused step's buffer donation: nothing here aliases a donated
        buffer."""
        from ..checkpoint.train_state import capture_train_state

        return capture_train_state(self)

    def restore_train_state(self, info, arrays, opt_tree):
        """Install a checkpoint loaded by ``CheckpointManager.restore``
        into this bound module; returns the ``ResumePoint``."""
        from ..checkpoint.train_state import restore_train_state

        return restore_train_state(self, info, arrays, opt_tree)

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
        self._drop_fused_plan()

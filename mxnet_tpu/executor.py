"""Executor: a bound symbol, compiled to whole-graph HLO.

Reference: ``GraphExecutor`` (``src/executor/graph_executor.h:57``,
``Forward``/``Backward`` at graph_executor.cc:61,74) which builds a gradient
graph, plans memory, and pushes per-node engine ops.

TPU-native design (SURVEY.md §7): the *entire* forward (and forward+backward)
graph is traced once and compiled by XLA as a single program —
the reference's segment bulking (``CreateCachedSegOpr``,
graph_executor.cc:1365) taken to its limit.  Memory planning, inplace
optimization and scheduling all fall to XLA buffer assignment.  Aux-state
updates (BatchNorm running stats) are returned functionally from the compiled
program and written back to the executor's aux buffers, replacing the
reference's in-place aux mutation.
"""
from __future__ import annotations

import operator
import threading
import warnings
from typing import Dict, List, Optional

import numpy as _np
import jax
import jax.numpy as jnp

# the CPU backend ignores donation (tests run there); the per-compile warning
# would otherwise drown every fused-step test run
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")

from .base import MXNetError
from .context import Context
from .ndarray.ndarray import NDArray
from .symbol.graph import trace
from . import random as _random
from .observability import device_scopes as _device_scopes
from .observability import tracing as _tracing

__all__ = ["Executor", "compile_cache_stats", "reset_compile_cache_stats"]

# process-wide compile-cache accounting: every _jit_cache lookup lands here,
# so a serving layer (or a test) can assert "zero recompiles after warmup"
# by snapshotting misses across a workload (mxnet_tpu.serving stats use it)
_cache_stats = {"hits": 0, "misses": 0}
_cache_by_site: dict = {}
# the fused step's plan (docs/fused_step.md): a warm fit reads builds 0,
# uniquify runs 0 and one reuse a step
_PLAN_HELP = {
    "fused_plan_builds": "Fused step plans built (first step, rebind, "
                         "another optimizer, multipliers, scaler, "
                         "telemetry, a loaded state)",
    "fused_plan_reuses": "Fused steps launched from a kept plan with every "
                         "donated array the last launch's own",
    "fused_uniquify_runs": "Fused steps that checked their donated buffers "
                           "for aliases (an array came from outside the "
                           "last launch)",
}
_plan_stats = dict.fromkeys(_PLAN_HELP, 0)
_cache_stats_lock = threading.Lock()


def compile_cache_stats() -> dict:
    """Process-wide executor compile-cache counters ({"hits", "misses"}),
    plus a ``"by_site"`` breakdown per program kind (fwd/fwdbwd/bwdg/
    fused_step).  A miss is a program compile (new ``_jit_cache``
    signature); a hit reuses an already-compiled program.  Beside them
    the fused step's plan counters ``fused_plan_builds`` /
    ``fused_plan_reuses`` / ``fused_uniquify_runs`` (docs/fused_step.md).  Under
    ``TPUMX_EXPLAIN_RECOMPILES``/``TPUMX_FREEZE_COMPILES`` every miss is
    additionally explained (and, post-warmup, refused) by
    :mod:`mxnet_tpu.observability.recompile`."""
    with _cache_stats_lock:
        out = dict(_cache_stats)
        out["by_site"] = {k: dict(v) for k, v in _cache_by_site.items()}
        out.update(_plan_stats)
        return out


def reset_compile_cache_stats() -> None:
    with _cache_stats_lock:
        _cache_stats["hits"] = 0
        _cache_stats["misses"] = 0
        _cache_by_site.clear()
        for k in _plan_stats:
            _plan_stats[k] = 0


_recompile_mod = None


def _note_cache(hit: bool, site=None, key=None) -> None:
    """Count a cache lookup; with a ``site``, also feed the recompile
    explainer/watchdog — which may raise :class:`FreezeCompilesError` on a
    post-warmup miss BEFORE any compile work happens."""
    kind = site[0] if isinstance(site, tuple) and site else None
    with _cache_stats_lock:
        _cache_stats["hits" if hit else "misses"] += 1
        if kind is not None:
            per = _cache_by_site.setdefault(kind, {"hits": 0, "misses": 0})
            per["hits" if hit else "misses"] += 1
    if site is None:
        return
    global _recompile_mod
    if _recompile_mod is None:
        from .observability import recompile as _r

        _recompile_mod = _r
    if hit:
        _recompile_mod.note_hit(site)
    else:
        _recompile_mod.note_miss(site, key)


def _note_plan(what: str) -> None:
    """Count a step plan's build, reuse or aliasing check
    (:func:`compile_cache_stats`, and ``<what>_total`` in
    ``observability.registry()``)."""
    with _cache_stats_lock:
        _plan_stats[what] += 1
    from .observability import registry as _registry

    _registry().counter(what + "_total", help=_PLAN_HELP[what]).inc()


class _Fixed:
    """A state leaf that is no ``NDArray`` (a plain number): read as it is
    at every launch and never written, as ``optimizer._unpack_state_into``
    leaves it."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    _data = property(lambda self: self.value, lambda self, v: None)


def _state_holders(s):
    """An optimizer state as ``optimizer._pack_state`` lays it out, with a
    holder in each leaf's place (the ``NDArray`` itself): what a plan reads
    at launch and writes when the step lands."""
    if isinstance(s, (tuple, list)):
        return tuple(_state_holders(x) for x in s)
    return s if s is None or isinstance(s, NDArray) else _Fixed(s)


class _FusedPlan:
    """What :meth:`Executor.plan_fused_step` keeps for
    :meth:`Executor.run_fused_step` (docs/fused_step.md "The step plan").
    ``guard`` and ``states_of`` are its owner's (``Module``): what the plan
    was built under, compared before each reuse."""

    __slots__ = ("fn", "key", "site", "lookup_counted", "optimizer",
                 "indices", "num_steps", "scaler", "tele_on", "gnames",
                 "d_h", "s_tree", "b_names",
                 "o_names", "a_names", "b_h", "r_h", "spmd", "ndev", "shard",
                 "repl", "d_shardings", "seen_d", "seen_r", "ahead", "guard",
                 "states_of")

    def scalar(self, name, value):
        """One of a step's scalars (``lr`` / ``wd`` / ``t``: floats, or a
        tuple of them an inner step) as the program's argument: the device
        array made for this value before — at the step before (a rate that
        has not changed), or ahead of this one while the device ran (the
        count a step on) — else a new one, kept for the next step."""
        held = self.ahead.get(name)
        if held is None or held[0] != value:
            held = self.ahead[name] = (value, jax.device_put(
                _np.asarray(value, _np.float32)))
        return held[1]


def _ones_cotangent(x):
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.ones_like(x)
    return _np.zeros(x.shape, jax.dtypes.float0)


class Executor:
    def __init__(self, symbol, ctx: Context, args: Dict[str, NDArray],
                 args_grad: Dict[str, NDArray], grad_req: Dict[str, str],
                 aux_states: Dict[str, NDArray], group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx
        self.arg_dict = args
        self.grad_dict = args_grad or {}
        self.grad_req = grad_req
        self.aux_dict = aux_states or {}
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._out_names = symbol.list_outputs()
        self._outputs: List[NDArray] = []
        self._cached_grads: Optional[Dict[str, object]] = None
        self._monitor_callback = None
        self._jit_cache: Dict[tuple, object] = {}
        self._fused_probe = None  # (program, arg shapes) of the last fused_step
        self._fused_plan = None  # the kept step plan (plan_fused_step)
        # SPMD data-parallel annotation (set_spmd): when a mesh is attached,
        # fused_step compiles ONE shard_map program over it — batch args
        # sharded on the dp axis, params/optimizer state replicated+donated,
        # gradients allreduced in-program (docs/multichip.md).  With
        # partition specs attached too (docs/sharding.md), params/grads/
        # optimizer state live SHARDED per-leaf on the model axes of an N-D
        # ("dp","mp") mesh instead of replicated.
        self._spmd_mesh = None
        self._spmd_axis = "dp"
        self._spmd_param_specs: Dict[str, tuple] = {}
        self._spmd_batch_args: frozenset = frozenset()
        self._spmd_out_is_batch: List[bool] = []
        # tensor-parallel COMPUTE (docs/sharding.md): with compute=True the
        # fused step compiles as a GSPMD global-view jit whose matmuls XLA
        # partitions along the rule specs — no per-leaf all_gather forward
        self._spmd_compute = False
        # pipeline parallelism (docs/sharding.md): (PipelinePlan, n_micro)
        # when the mesh carries a "pp" axis and the bound symbol is
        # stage-stackable — the fused program runs the body as a microbatch
        # round-robin over the pp ranks (parallel/pipeline.py)
        self._spmd_pipeline = None
        self._spmd_active = False  # a fused SPMD step has run (buffers live
        # replicated/sharded on the mesh; eager paths must reconcile)
        # device-side train telemetry (docs/observability.md): last-step
        # scalars + cross-step accumulators, all LAZY device values — no
        # host sync until telemetry.publish() at a log boundary
        self._telemetry_last: Optional[Dict[str, object]] = None
        self._telemetry_accum: Dict[str, object] = {}
        self._grad_arg_names = sorted(
            n for n in self._arg_names if self.grad_req.get(n, "null") != "null"
            and n in self.grad_dict)
        self._grouped = None
        self._group2ctx = group2ctx
        if group2ctx:
            from .symbol.placement import GroupedProgram

            self._grouped = GroupedProgram(symbol, group2ctx, ctx,
                                           self._grad_arg_names)
            # place bound params on their group devices (the reference's
            # AssignContext does the same for per-group arg arrays)
            for n in self._arg_names:
                if n in self.arg_dict:
                    self.arg_dict[n]._data = jax.device_put(
                        self.arg_dict[n]._data, self._grouped.arg_device(n))

    # -- public mirror of the reference Executor API ------------------------------
    @property
    def outputs(self) -> List[NDArray]:
        return self._outputs

    @property
    def arg_arrays(self) -> List[NDArray]:
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self) -> List[Optional[NDArray]]:
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self) -> List[NDArray]:
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._out_names, self._outputs))

    # -- SPMD annotation ----------------------------------------------------------
    def set_spmd(self, mesh, batch_args, axis: str = "dp",
                 param_specs=None, compute: bool = False,
                 pipeline=None) -> None:
        """Attach a data-parallel mesh to this executor (or detach with
        ``mesh=None``).  ``batch_args`` are the argument names carrying the
        batch dimension (data + labels): they shard on ``axis``; every other
        input of the fused-step program stays replicated — unless
        ``param_specs`` (a name -> PartitionSpec mapping from
        :mod:`mxnet_tpu.parallel.partition_rules`) says a parameter lives
        sharded on the mesh's model axes, in which case that param, its
        gradient, and its optimizer state (including AMP f32 master weights)
        are stored and donated SHARDED (docs/sharding.md).  The mesh — and
        each non-trivial spec — becomes part of ``_signature`` so a program
        compiled for one device count / layout is never served to another;
        with ``param_specs=None`` the signature stays byte-identical to the
        dp-only layout.

        ``compute=True`` (tensor-parallel compute, docs/sharding.md) makes
        the fused step a GSPMD global-view program: the specs become
        ``with_sharding_constraint`` pins and XLA partitions the matmuls
        themselves — the forward never materializes a full copy of a
        rule-sharded weight (vs. the default FSDP gather-compute-slice).
        Only meaningful with ``param_specs``; keys its own programs via a
        ``("mp_compute", 1)`` signature component.

        ``pipeline=(plan, n_micro)`` (a :class:`~mxnet_tpu.symbol.staging
        .PipelinePlan`) runs the plan's body as a GPipe microbatch
        round-robin over the mesh's ``"pp"`` axis inside the same single
        donated program; the signature gains ``("pp", n_stages, n_micro)``
        plus the full mesh axis map."""
        if mesh is None:
            self._spmd_mesh = None
            self._spmd_batch_args = frozenset()
            self._spmd_param_specs = {}
            self._spmd_out_is_batch = []
            self._spmd_compute = False
            self._spmd_pipeline = None
            return
        ndev = int(mesh.shape[axis])
        batch_args = frozenset(batch_args)
        bdims = set()
        for n in batch_args:
            if n not in self.arg_dict:
                raise MXNetError(f"set_spmd: unknown batch argument {n!r}")
            shape = self.arg_dict[n].shape
            if not shape:
                raise MXNetError(f"set_spmd: batch argument {n!r} is scalar")
            bdims.add(shape[0])
        if len(bdims) != 1:
            raise MXNetError(
                f"set_spmd: batch arguments disagree on the leading "
                f"(batch) dimension: {sorted(bdims)}")
        (batch,) = bdims
        if batch % ndev:
            raise MXNetError(
                f"set_spmd: batch size {batch} not divisible by the dp "
                f"mesh size {ndev}")
        # which outputs carry the batch dimension (static, from whole-graph
        # shape inference at the bound global shapes): those reassemble
        # sharded on the dp axis; the rest are made replica-invariant via
        # pmean inside the program
        shape_kwargs = {n: self.arg_dict[n].shape for n in self._arg_names}
        _, out_shapes, _ = self._symbol.infer_shape(**shape_kwargs)
        self._spmd_out_is_batch = [
            bool(s) and len(s) > 0 and s[0] == batch for s in out_shapes]
        specs = {}
        if param_specs:
            from .parallel.partition_rules import spec_tuple

            for n, s in param_specs.items():
                if n not in self.arg_dict:
                    raise MXNetError(
                        f"set_spmd: partition spec for unknown argument "
                        f"{n!r}")
                if n in batch_args:
                    raise MXNetError(
                        f"set_spmd: {n!r} is a batch argument; batch args "
                        f"shard on the {axis!r} axis, not via param_specs")
                st = spec_tuple(s)
                if any(e is not None for e in st):
                    specs[n] = st
        if pipeline is not None:
            plan, n_micro = pipeline
            if "pp" not in mesh.axis_names:
                raise MXNetError("set_spmd: pipeline requires a 'pp' mesh "
                                 "axis")
            if int(plan.n_stages) != int(mesh.shape["pp"]):
                raise MXNetError(
                    f"set_spmd: plan has {plan.n_stages} stages but the pp "
                    f"axis is {int(mesh.shape['pp'])} wide")
            local_batch = batch // ndev
            if int(n_micro) < 1 or local_batch % int(n_micro):
                raise MXNetError(
                    f"set_spmd: local batch {local_batch} not divisible by "
                    f"{n_micro} microbatches (TPUMX_PP_MICROBATCHES)")
            pipeline = (plan, int(n_micro))
        self._spmd_mesh = mesh
        self._spmd_axis = axis
        self._spmd_param_specs = specs
        self._spmd_batch_args = batch_args
        # the pipelined program is a shard_map: GSPMD compute partitioning
        # only applies on the pipeline-free mesh (docs/sharding.md)
        self._spmd_compute = bool(compute and specs and pipeline is None)
        self._spmd_pipeline = pipeline

    def _spmd_ndev(self) -> int:
        if self._spmd_mesh is None:
            return 1
        return int(self._spmd_mesh.shape[self._spmd_axis])

    def _spmd_total(self) -> int:
        """Total devices of the attached mesh (dp × model axes) — the SPMD
        trigger: a ("dp":1, "mp":2) mesh is still a 2-device SPMD program
        even though the dp width is 1."""
        if self._spmd_mesh is None:
            return 1
        return int(self._spmd_mesh.devices.size)

    # -- compilation --------------------------------------------------------------
    def _site(self, kind: str) -> tuple:
        """Recompile-explainer call-site identity: program kind + the
        symbol's output names — stable across rebinds of the SAME model
        (where recompile bugs bite) yet distinct between models."""
        return (kind,) + tuple(self._out_names)

    def _graph_quantized(self) -> bool:
        """Whether the bound symbol contains int8 serving ops (computed
        once per executor; quantization.convert_symbol inserts them)."""
        cached = getattr(self, "_quantized_graph", None)
        if cached is None:
            from .quantization.convert import count_quantized_nodes

            cached = count_quantized_nodes(self._symbol) > 0
            self._quantized_graph = cached
        return cached

    def _signature(self, is_train: bool) -> tuple:
        sig = [is_train]
        # the Pallas kernel layer changes the traced program (fused LN et
        # al., docs/pallas.md): with the gate ON its programs key
        # separately, so a cross-process A/B — or an ill-advised mid-run
        # env flip — recompiles (and is explained) instead of silently
        # serving the other implementation.  Gate OFF appends NOTHING:
        # TPUMX_PALLAS=0 signatures are byte-identical to the pre-kernel
        # layout, preserving warm caches and freeze sets.
        from .ops.pallas_kernels import pallas_enabled

        if pallas_enabled():
            sig.append(("pallas", 1))
        # int8-quantized graphs (docs/quantization.md) key their own
        # program family — a float and a quantized bind of the same model
        # never share a cached program.  Unquantized graphs append
        # NOTHING, so TPUMX_QUANT=0 signatures stay byte-identical.
        if self._graph_quantized():
            sig.append(("quant", "int8"))
        for n in self._arg_names:
            a = self.arg_dict[n]
            sig.append((n, a.shape, str(a.dtype)))
        # aux states are program inputs too: a rebind changing only aux
        # shapes/dtypes must key a fresh program, not reuse (or miscount) the
        # cached one
        for n in self._aux_names:
            a = self.aux_dict[n]
            sig.append(("aux", n, a.shape, str(a.dtype)))
        if self._spmd_mesh is not None:
            # mesh shape + participating device count: an 8-device SPMD
            # program must never be served to a 1-device rebind (nor a dp=4
            # one to dp=8 after a TPUMX_DP_DEVICES change)
            sig.append(("mesh", self._spmd_axis, self._spmd_ndev(),
                        int(self._spmd_mesh.devices.size),
                        tuple(sorted(self._spmd_batch_args))))
            if self._spmd_param_specs or self._spmd_pipeline is not None:
                # partition-rule layout (docs/sharding.md): the full mesh
                # axis map plus each sharded param's resolved spec key their
                # own programs — and feed the recompile explainer's
                # "spec p('dp',None)→p('dp','mp') (name)" causes.  With no
                # specs (rules=None) and no pipeline these entries are
                # ABSENT and the signature stays byte-identical to the
                # dp-only layout.
                sig.append(("meshshape", tuple(
                    (str(a), int(self._spmd_mesh.shape[a]))
                    for a in self._spmd_mesh.axis_names)))
            if self._spmd_param_specs:
                for n in sorted(self._spmd_param_specs):
                    sig.append(("spec", n, self._spmd_param_specs[n]))
                if self._spmd_compute:
                    # tensor-parallel COMPUTE keys its own programs; with
                    # TPUMX_MP_COMPUTE=0 this component is absent and the
                    # key is byte-identical to the FSDP gather layout
                    sig.append(("mp_compute", 1))
            if self._spmd_pipeline is not None:
                plan, n_micro = self._spmd_pipeline
                sig.append(("pp", int(plan.n_stages), int(n_micro)))
        return tuple(sig)

    def _get_fwd(self, is_train: bool):
        key = ("fwd", self._signature(is_train))
        _note_cache(hit=key in self._jit_cache, site=self._site("fwd"),
                    key=key)
        if key not in self._jit_cache:
            entries = self._symbol._entries

            def fwd(arg_vals, aux_vals, rng):
                env = dict(arg_vals)
                env.update(aux_vals)
                aux_updates: Dict[str, object] = {}
                outs = trace(entries, env, is_train, rng,
                             collect_aux=aux_updates if is_train else None)
                return outs, aux_updates

            self._jit_cache[key] = jax.jit(fwd)
        return self._jit_cache[key]

    def _get_fwdbwd(self):
        key = ("fwdbwd", self._signature(True))
        _note_cache(hit=key in self._jit_cache, site=self._site("fwdbwd"),
                    key=key)
        if key not in self._jit_cache:
            entries = self._symbol._entries
            gnames = self._grad_arg_names

            def fwdbwd(arg_vals, aux_vals, rng):
                def f(gvals):
                    env = dict(arg_vals)
                    env.update(gvals)
                    env.update(aux_vals)
                    aux_updates: Dict[str, object] = {}
                    outs = trace(entries, env, True, rng, collect_aux=aux_updates)
                    return outs, aux_updates

                gvals0 = {n: arg_vals[n] for n in gnames}
                (outs, aux_updates), vjp = jax.vjp(f, gvals0)
                cts = ([_ones_cotangent(o) for o in outs],
                       {k: _np.zeros(v.shape, jax.dtypes.float0) if not jnp.issubdtype(v.dtype, jnp.inexact)
                        else jnp.zeros_like(v) for k, v in aux_updates.items()})
                (grads,) = vjp(cts)
                return outs, aux_updates, grads

            self._jit_cache[key] = jax.jit(fwdbwd)
        return self._jit_cache[key]

    def _get_bwd_with_grads(self):
        key = ("bwdg", self._signature(True))
        _note_cache(hit=key in self._jit_cache, site=self._site("bwdg"),
                    key=key)
        if key not in self._jit_cache:
            entries = self._symbol._entries
            gnames = self._grad_arg_names

            def bwd(arg_vals, aux_vals, rng, out_cts):
                def f(gvals):
                    env = dict(arg_vals)
                    env.update(gvals)
                    env.update(aux_vals)
                    outs = trace(entries, env, True, rng, collect_aux={})
                    return outs

                gvals0 = {n: arg_vals[n] for n in gnames}
                outs, vjp = jax.vjp(f, gvals0)
                (grads,) = vjp(out_cts)
                return grads

            self._jit_cache[key] = jax.jit(bwd)
        return self._jit_cache[key]

    def _collect_vals(self):
        arg_vals = {n: self.arg_dict[n]._data for n in self._arg_names}
        aux_vals = {n: self.aux_dict[n]._data for n in self._aux_names}
        return arg_vals, aux_vals

    def _spmd_place_eager(self):
        """Reconcile buffer placement for the NON-fused paths (plain
        forward/backward, eval/score) after the fused SPMD step replicated
        params over the mesh: a single-device feed would otherwise make the
        jitted program reject the mixed device sets.  Batch args shard on
        the dp axis when divisible (GSPMD then partitions the eval across
        the mesh for free); everything else replicates.  Every device_put is
        a no-op once placement is right."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh, axis = self._spmd_mesh, self._spmd_axis
        ndev = self._spmd_ndev()
        shard = NamedSharding(mesh, PartitionSpec(axis))
        repl = NamedSharding(mesh, PartitionSpec())
        for n in self._arg_names:
            a = self.arg_dict[n]
            if a._data is None:
                continue
            if n in self._spmd_batch_args and a.shape \
                    and a.shape[0] % ndev == 0:
                a._data = jax.device_put(a._data, shard)
            elif n in self._spmd_param_specs:
                # rule-sharded params stay in their spec layout: the jitted
                # eval program is a global-view computation, so GSPMD
                # gathers transiently where needed without ever
                # materializing a replicated persistent copy
                a._data = jax.device_put(a._data, NamedSharding(
                    mesh, PartitionSpec(*self._spmd_param_specs[n])))
            else:
                a._data = jax.device_put(a._data, repl)
        for n in self._aux_names:
            a = self.aux_dict[n]
            if a._data is not None:
                a._data = jax.device_put(a._data, repl)

    # -- execution ----------------------------------------------------------------
    def forward(self, is_train: bool = False, **kwargs) -> List[NDArray]:
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"forward: unknown argument {k!r}")
            if isinstance(v, NDArray):
                self.arg_dict[k]._data = v._data
            else:
                self.arg_dict[k]._data = jnp.asarray(v)
        if self._spmd_active and self._spmd_mesh is not None:
            self._spmd_place_eager()
        arg_vals, aux_vals = self._collect_vals()
        rng = _random.next_key()
        self._cached_grads = None
        if self._grouped is not None:
            env = dict(arg_vals)
            env.update(aux_vals)
            with_grad = bool(is_train and self._grad_arg_names)
            outs, aux_updates, grads = self._grouped.forward(
                env, rng, is_train, with_grad=with_grad)
            if with_grad:
                self._cached_grads = grads
        elif is_train and self._grad_arg_names:
            fn = self._get_fwdbwd()
            outs, aux_updates, grads = fn(arg_vals, aux_vals, rng)
            self._cached_grads = grads
        else:
            fn = self._get_fwd(is_train)
            outs, aux_updates = fn(arg_vals, aux_vals, rng)
        self._outputs = [NDArray(o) for o in outs]
        for k, v in aux_updates.items():
            self.aux_dict[k]._data = v
        self._last_rng = rng
        if self._monitor_callback is not None:
            for name, out in zip(self._out_names, self._outputs):
                self._monitor_callback(name, out)
        return self._outputs

    def backward(self, out_grads=None, is_train: bool = True) -> None:
        """Write gradients into the bound grad arrays.

        With no out_grads (the fit path), gradients were fused into the
        forward program (see _get_fwdbwd) — this just commits them, honoring
        grad_req write/add (the reference's kAddTo — exec_pass.h OpExecutor req).
        """
        if out_grads is None:
            if self._cached_grads is None:
                raise MXNetError("backward called before forward(is_train=True)")
            grads = self._cached_grads
        else:
            if getattr(self, "_last_rng", None) is None:
                raise MXNetError("backward called before forward(is_train=True)")
            if not isinstance(out_grads, (list, tuple)):
                out_grads = [out_grads]
            arg_vals, aux_vals = self._collect_vals()
            cts = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                   for g in out_grads]
            if self._spmd_active and self._spmd_mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                mesh, axis = self._spmd_mesh, self._spmd_axis
                ndev = self._spmd_ndev()
                cts = [jax.device_put(c, NamedSharding(
                    mesh, PartitionSpec(axis)
                    if c.shape and c.shape[0] % ndev == 0 else
                    PartitionSpec())) for c in cts]
            if self._grouped is not None:
                env = dict(arg_vals)
                env.update(aux_vals)
                _, _, grads = self._grouped.forward(
                    env, self._last_rng, True, with_grad=True, out_cts=cts)
            else:
                fn = self._get_bwd_with_grads()
                grads = fn(arg_vals, aux_vals, self._last_rng, cts)
        for n in self._grad_arg_names:
            g = self.grad_dict[n]
            req = self.grad_req.get(n, "write")
            gn = grads.get(n) if isinstance(grads, dict) else grads[n]
            if gn is None:  # no gradient path reached this argument
                gn = jnp.zeros_like(g._data)
            if req == "add":
                if self._grouped is not None:
                    gn = jax.device_put(gn, list(g._data.devices())[0])
                g._data = g._data + gn
            else:
                g._data = gn

    # -- fused whole-train-step ---------------------------------------------------
    def _get_fused_step(self, optimizer, mults_by_name, num_steps: int,
                        kvstore=None, scaler=None,
                        master_names: frozenset = frozenset(),
                        telemetry: bool = False, state_specs=None):
        spmd = self._spmd_total() > 1
        pspecs = dict(self._spmd_param_specs) if spmd else {}
        # tensor-parallel compute (docs/sharding.md): GSPMD global-view jit
        # instead of the shard_map gather-compute-slice program
        mp_compute = bool(spmd and pspecs and self._spmd_compute)
        pp_cfg = self._spmd_pipeline if spmd else None
        reqs = tuple(sorted((n, self.grad_req.get(n, "write"))
                            for n in self._grad_arg_names))
        key = ("fused_step", self._signature(True), int(num_steps),
               optimizer.fused_static_key(),
               tuple(sorted(mults_by_name.items())), reqs)
        if spmd:
            key = key + ("spmd", type(kvstore).__name__ if kvstore is not None
                         else None)
        if scaler is not None or master_names:
            # AMP components key their own programs: toggling the scaler or
            # the master-weight layout must compile fresh, while the plain
            # f32 key (and its cached program) stays byte-identical to the
            # pre-AMP layout
            key = key + ("amp",
                         None if scaler is None else scaler.static_key(),
                         tuple(sorted(master_names)))
        if telemetry:
            # telemetry outputs key their own program; with TPUMX_TELEMETRY=0
            # this component is absent and key + traced program are
            # byte-identical to the pre-telemetry layout
            key = key + ("telemetry",)
        _note_cache(hit=key in self._jit_cache,
                    site=self._site("fused_step"), key=key)
        if key not in self._jit_cache:
            entries = self._symbol._entries
            gnames = list(self._grad_arg_names)
            req_of = dict(reqs)
            axis = self._spmd_axis if spmd else None
            # partition-rule sharded layout (docs/sharding.md): params,
            # grads, and optimizer state enter and leave the program as
            # model-axis SHARDS.  The forward/backward runs on gathered
            # (full) params — FSDP semantics, numerically identical to the
            # replicated layout — then each gradient is sliced back to this
            # device's shard and the (elementwise) optimizer update runs
            # shard-wise, so the persistent donated buffers never hold more
            # than 1/mp of any rule-matched leaf.
            if pp_cfg is not None:
                # pipelined body (docs/sharding.md): the plan's prologue/
                # round-robin/epilogue replaces the flat whole-graph trace;
                # same env contract, same outputs
                plan, n_micro = pp_cfg

                def trace_model(env, rng, aux_dict):
                    return plan.apply(env, True, rng, aux_dict, n_micro)
            else:
                def trace_model(env, rng, aux_dict):
                    return trace(entries, env, True, rng,
                                 collect_aux=aux_dict)
            tele_axes = None
            if pspecs and not mp_compute:
                mesh_sizes = {str(a): int(self._spmd_mesh.shape[a])
                              for a in self._spmd_mesh.axis_names}
                spec_of = {n: pspecs.get(n, ()) for n in gnames}

                def _axes_of(entry):
                    return entry if isinstance(entry, tuple) else (entry,)

                tele_axes = tuple(sorted({ax for s in spec_of.values()
                                          for entry in s if entry
                                          for ax in _axes_of(entry)}))

                def _gather_full(x, spec):
                    # minor-most axis first: reassembles exactly the
                    # NamedSharding block layout of the stored shard
                    for dim, entry in enumerate(spec):
                        if entry is None:
                            continue
                        for ax in reversed(_axes_of(entry)):
                            x = jax.lax.all_gather(x, ax, axis=dim,
                                                   tiled=True)
                    return x

                def _shard_of(x, spec):
                    for dim, entry in enumerate(spec):
                        if entry is None:
                            continue
                        idx, nshard = 0, 1
                        for ax in _axes_of(entry):
                            idx = idx * mesh_sizes[ax] \
                                + jax.lax.axis_index(ax)
                            nshard *= mesh_sizes[ax]
                        size = x.shape[dim] // nshard
                        x = jax.lax.dynamic_slice_in_dim(
                            x, idx * size, size, axis=dim)
                    return x

                def gather_pvals(pv):
                    return {n: _gather_full(v, spec_of[n])
                            for n, v in pv.items()}

                def slice_grad(n, g):
                    return _shard_of(g, spec_of[n])
            else:
                def gather_pvals(pv):
                    return pv

                def slice_grad(n, g):
                    return g
            if spmd and not mp_compute and kvstore is not None \
                    and hasattr(kvstore, "reduce_in_program"):
                # tpu_sync: the store IS the collective boundary — its
                # in-trace hook emits the psum (kvstore.py)
                def allreduce(g):
                    return kvstore.reduce_in_program(g, axis)
            elif spmd and not mp_compute:
                from .parallel.collectives import allreduce as _psum

                def allreduce(g):
                    return {n: _psum(v, axis) for n, v in g.items()}
            else:
                # mp-compute (GSPMD global view): the gradient of the global
                # batch is computed directly — XLA inserts whatever
                # collectives the partitioning needs; there is no per-shard
                # sum to combine
                allreduce = None
            # GSPMD has no named axes in-trace: telemetry norms/loss are
            # already global values there
            tele_pmean = None if mp_compute else axis

            from .optimizer import fused_apply_update

            def one_step(pvals, svals, gprev, other_vals, aux_vals,
                         lr_i, wd, t_i, rng, sc=None):
                def f(gvals):
                    env = dict(other_vals)
                    env.update(gvals)
                    env.update(aux_vals)
                    aux_updates: Dict[str, object] = {}
                    outs = trace_model(env, rng, aux_updates)
                    return outs, aux_updates

                # forward/backward over the FULL params (all_gather of the
                # stored shards under partition rules; identity otherwise)
                p_full = gather_pvals(pvals)
                (outs, aux_updates), vjp = jax.vjp(f, p_full)
                if scaler is None:
                    out_cts = [_ones_cotangent(o) for o in outs]
                else:
                    # loss scaling: the scale rides the cotangent seed, so
                    # every gradient leaves the vjp pre-multiplied by it
                    # (Micikevicius et al. 2018 §4; docs/amp.md)
                    out_cts = [scaler.scale_cotangent(_ones_cotangent(o),
                                                      sc[0])
                               if jnp.issubdtype(o.dtype, jnp.inexact)
                               else _ones_cotangent(o) for o in outs]
                cts = (out_cts,
                       {k: _np.zeros(v.shape, jax.dtypes.float0)
                        if not jnp.issubdtype(v.dtype, jnp.inexact)
                        else jnp.zeros_like(v)
                        for k, v in aux_updates.items()})
                (grads,) = vjp(cts)
                if pp_cfg is not None:
                    # combine over the pp axis (parallel/pipeline.py):
                    # prologue + stage param cotangents are rank-gated
                    # (nonzero on one pp rank) → psum; epilogue params are
                    # exact and replica-invariant already → identity
                    grads = {
                        n: (jax.lax.psum(g, "pp")
                            if plan.pp_combine(n) == "psum" else g)
                        for n, g in grads.items() if g is not None}
                if allreduce is not None:
                    # in-program allreduce over the dp axis: per-shard grad
                    # sums combine into the full-batch gradient, exactly what
                    # the 1-device trace computes (rescale_grad then divides
                    # by the GLOBAL batch in the optimizer, unchanged)
                    with jax.named_scope("kvstore.allreduce"):
                        grads = allreduce(
                            {n: grads[n] for n in gnames
                             if grads.get(n) is not None})
                        # per-shard batch stats (BatchNorm running
                        # averages): average across replicas so the
                        # committed aux carry is replica-invariant
                        aux_updates = {
                            k: (jax.lax.pmean(v, axis)
                                if jnp.issubdtype(v.dtype, jnp.inexact)
                                else v)
                            for k, v in aux_updates.items()}
                finite = None
                if scaler is not None:
                    # all-finite check on the (scaled, already-reduced)
                    # grads; under SPMD the count is additionally combined
                    # over the dp mesh through the same collective boundary
                    # so every replica takes the SAME skip/apply branch
                    nonfin = scaler.nonfinite_count(
                        {n: g for n, g in grads.items() if g is not None})
                    if allreduce is not None:
                        with jax.named_scope("kvstore.allreduce"):
                            if kvstore is not None and hasattr(
                                    kvstore, "all_finite_in_program"):
                                nonfin = kvstore.all_finite_in_program(
                                    nonfin, axis)
                            else:
                                nonfin = allreduce(
                                    {"_amp_nonfinite": nonfin})[
                                    "_amp_nonfinite"]
                    finite = nonfin == 0
                    grads = {n: scaler.unscale(g, sc[0])
                             for n, g in grads.items() if g is not None}
                new_grads = {}
                for n in gnames:
                    g = grads.get(n)
                    if g is None:  # no gradient path reached this argument
                        g = jnp.zeros_like(pvals[n])
                    else:
                        # under partition rules: keep only this device's
                        # shard of the (full, already dp-allreduced)
                        # gradient — the layout the stored grad buffer,
                        # grad carry, and shard-wise update all share
                        g = slice_grad(n, g)
                    if req_of[n] == "add":
                        g = gprev[n] + g
                    new_grads[n] = g

                def apply_updates(_):
                    new_p, new_s = {}, {}
                    with jax.named_scope("optimizer.update"):
                        for n in gnames:
                            lm, wm, dt = mults_by_name[n]
                            new_p[n], new_s[n] = fused_apply_update(
                                optimizer, pvals[n], new_grads[n], svals[n],
                                lr_i * lm, wd * wm, t_i + dt,
                                n in master_names)
                    return new_p, new_s

                if scaler is None:
                    new_p, new_s = apply_updates(None)
                    return outs, aux_updates, new_grads, new_p, new_s
                # overflow: skip the whole update (params, optimizer state,
                # AND the BatchNorm running-stat commit — a nonfinite batch
                # must not poison the aux carry), then back the scale off
                new_p, new_s = jax.lax.cond(
                    finite, apply_updates,
                    lambda _: ({n: pvals[n] for n in gnames},
                               {n: svals[n] for n in gnames}), None)
                aux_updates = {
                    k: (jnp.where(finite, v, aux_vals[k].astype(v.dtype))
                        if jnp.issubdtype(v.dtype, jnp.inexact) else v)
                    for k, v in aux_updates.items()}
                return (outs, aux_updates, new_grads, new_p, new_s,
                        scaler.next_state(sc, finite))

            def fused_core(pvals, gvals, svals, other_vals, aux_vals,
                           lr_vec, wd, t_vec, rng, sc_state):
                rng0 = jax.random.fold_in(rng, 0) if num_steps > 1 else rng
                res = one_step(pvals, svals, gvals, other_vals, aux_vals,
                               lr_vec[0], wd, t_vec[0], rng0, sc_state)
                if scaler is None:
                    outs, auxu, grads, p, s = res
                    sc = None
                else:
                    outs, auxu, grads, p, s, sc = res
                if num_steps > 1:
                    aux_full = dict(aux_vals)
                    aux_full.update(auxu)

                    def body(i, carry):
                        if scaler is None:
                            p, s, aux, grads, outs = carry
                            o2, au, g2, p2, s2 = one_step(
                                p, s, grads, other_vals, aux,
                                lr_vec[i], wd, t_vec[i],
                                jax.random.fold_in(rng, i))
                            sc2 = ()
                        else:
                            p, s, aux, grads, outs, sc = carry
                            o2, au, g2, p2, s2, sc2 = one_step(
                                p, s, grads, other_vals, aux,
                                lr_vec[i], wd, t_vec[i],
                                jax.random.fold_in(rng, i), sc)
                        aux2 = dict(aux)
                        aux2.update(au)
                        return (p2, s2, aux2, g2, o2) if scaler is None \
                            else (p2, s2, aux2, g2, o2, sc2)

                    carry0 = (p, s, aux_full, grads, outs) if scaler is None \
                        else (p, s, aux_full, grads, outs, sc)
                    res = jax.lax.fori_loop(1, num_steps, body, carry0)
                    if scaler is None:
                        p, s, aux_full, grads, outs = res
                    else:
                        p, s, aux_full, grads, outs, sc = res
                    auxu = {k: aux_full[k] for k in auxu}
                ret = (outs, auxu, grads, p, s) if scaler is None \
                    else (outs, auxu, grads, p, s, sc)
                if telemetry:
                    # device-side train telemetry as extra program outputs
                    # (docs/observability.md): grads are post-allreduce and
                    # params post-update (replica-invariant under SPMD); the
                    # step-loss mean pmeans over the dp axis inside
                    # compute_in_program so every replica reports the
                    # global-batch value
                    from .observability import telemetry as _obs_tele

                    with jax.named_scope("telemetry"):
                        ret = ret + (_obs_tele.compute_in_program(
                            outs, grads, p,
                            scaler_state=sc if scaler is not None else None,
                            pmean_axis=tele_pmean, psum_axes=tele_axes),)
                return ret

            if scaler is None:
                def fused(pvals, gvals, svals, other_vals, aux_vals,
                          lr_vec, wd, t_vec, rng):
                    return fused_core(pvals, gvals, svals, other_vals,
                                      aux_vals, lr_vec, wd, t_vec, rng, None)
            else:
                def fused(pvals, gvals, svals, other_vals, aux_vals,
                          lr_vec, wd, t_vec, rng, sc_state):
                    return fused_core(pvals, gvals, svals, other_vals,
                                      aux_vals, lr_vec, wd, t_vec, rng,
                                      sc_state)

            if mp_compute:
                # GSPMD global view (docs/sharding.md "compute
                # partitioning"): ONE jit traced at GLOBAL shapes — the same
                # math as the single-device fused step — with the rule specs
                # pinned via with_sharding_constraint so XLA partitions the
                # matmuls themselves (column-parallel QKV/FFN-in,
                # row-parallel proj/FFN-out, one reduce per block).  No
                # all_gather of any rule-sharded weight appears in the
                # traced program; numerics match mp=1 to reduction-order
                # (tests assert rtol 1e-5).
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                mesh = self._spmd_mesh
                spec_of_c = {n: pspecs.get(n, ()) for n in gnames}
                wsc = jax.lax.with_sharding_constraint

                def _pin(v, spec):
                    return wsc(v, NamedSharding(mesh, P(*spec)))

                def fused_gspmd(pvals, gvals, svals, batch_vals, const_vals,
                                aux_vals, lr_vec, wd, t_vec, rng, *sc):
                    pvals = {n: _pin(v, spec_of_c[n])
                             for n, v in pvals.items()}
                    batch_vals = {n: _pin(v, (axis,))
                                  for n, v in batch_vals.items()}
                    other_vals = dict(const_vals)
                    other_vals.update(batch_vals)
                    res = fused(pvals, gvals, svals, other_vals, aux_vals,
                                lr_vec, wd, t_vec, rng, *sc)
                    outs, auxu, grads, p, s = res[:5]
                    # pin the persistent (donated) carries back to their
                    # stored layout so the program's outputs alias its
                    # inputs and the steady state never re-lays-out
                    grads = {n: _pin(v, spec_of_c[n])
                             for n, v in grads.items()}
                    p = {n: _pin(v, spec_of_c[n]) for n, v in p.items()}
                    if state_specs is not None:
                        s = {n: jax.tree_util.tree_map(
                            lambda leaf, sp: wsc(leaf,
                                                 NamedSharding(mesh, sp)),
                            s[n], state_specs[n]) for n in s}
                    return (outs, auxu, grads, p, s) + tuple(res[5:])

                self._jit_cache[key] = jax.jit(fused_gspmd,
                                               donate_argnums=(0, 1, 2))
            elif spmd:
                from jax.sharding import PartitionSpec as P

                mesh = self._spmd_mesh
                out_is_batch = list(self._spmd_out_is_batch)

                def shard_step(pvals, gvals, svals, batch_vals, const_vals,
                               aux_vals, lr_vec, wd, t_vec, rng, *sc):
                    # decorrelate per-shard randomness (dropout etc.); nets
                    # without in-graph randomness stay bitwise replica-equal
                    rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
                    other_vals = dict(const_vals)
                    other_vals.update(batch_vals)
                    res = fused(pvals, gvals, svals, other_vals, aux_vals,
                                lr_vec, wd, t_vec, rng, *sc)
                    outs, rest = res[0], res[1:]
                    # non-batch-major outputs (scalar losses etc.) must leave
                    # the program replica-invariant; batch-major ones
                    # reassemble to the global batch via the out_spec
                    outs = [o if ob else jax.lax.pmean(o, axis)
                            for o, ob in zip(outs, out_is_batch)]
                    return (outs,) + tuple(rest)

                if pspecs:
                    # per-leaf specs (docs/sharding.md): params/grads keep
                    # their rule-resolved layout through the program; each
                    # optimizer-state leaf inherits its param's spec when
                    # shapes match (momentum, Adam moments, AMP f32 masters)
                    # and replicates otherwise (scalars) — `state_specs` is
                    # that pytree, built by fused_step from the live states
                    pspec_tree = {n: P(*spec_of[n]) for n in gnames}
                    gspec_tree = pspec_tree
                    sspec_tree = state_specs
                else:
                    pspec_tree = gspec_tree = sspec_tree = P()

                def fused_spmd(pvals, gvals, svals, batch_vals, const_vals,
                               aux_vals, lr_vec, wd, t_vec, rng, *sc):
                    out_specs = ([P(axis) if ob else P()
                                  for ob in out_is_batch],
                                 P(), gspec_tree, pspec_tree, sspec_tree)
                    in_specs = (pspec_tree, gspec_tree, sspec_tree, P(axis),
                                P(), P(), P(), P(), P(), P())
                    if scaler is not None:
                        out_specs = out_specs + (P(),)
                        in_specs = in_specs + (P(),)
                    if telemetry:
                        # replica-invariant scalars (norms on the allreduced
                        # grads, pmean'd loss): replicated out-spec
                        out_specs = out_specs + (P(),)
                    return jax.shard_map(
                        shard_step, mesh=mesh,
                        in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)(
                        pvals, gvals, svals, batch_vals, const_vals,
                        aux_vals, lr_vec, wd, t_vec, rng, *sc)

                self._jit_cache[key] = jax.jit(fused_spmd,
                                               donate_argnums=(0, 1, 2))
            else:
                self._jit_cache[key] = jax.jit(fused, donate_argnums=(0, 1, 2))
        return self._jit_cache[key], key

    def fused_step(self, optimizer, states: Dict[str, object],
                   updates, feed: Optional[Dict[str, object]] = None,
                   num_steps: Optional[int] = None,
                   kvstore=None, loss_scaler=None) -> List[NDArray]:
        """One donated XLA program per train step: forward + backward + the
        full optimizer update + aux-state commit (SURVEY.md §7 taken to its
        limit — the reference's ``CreateCachedSegOpr`` bulking over the whole
        step).

        ``updates`` is a list of ``(arg_name, optimizer_index)`` covering
        exactly the gradient-taking arguments; ``states`` maps each arg name
        to its optimizer state as created by ``Optimizer.create_state``
        (NDArray structures — updated in place, so checkpoint round-trips keep
        working).  Param, grad, and state buffers are DONATED to the program:
        any outside alias of those exact buffers is dead after this call
        (docs/fused_step.md).

        ``num_steps`` fuses k whole steps into one dispatch via
        ``lax.fori_loop`` over the same batch; when None it reads
        ``engine.fusion_hint()`` (the bulk-scope knob, default 1).

        With an SPMD mesh attached (``set_spmd``), the program is a
        ``shard_map`` over it: batch args shard on the dp axis, everything
        else is replicated, gradients allreduce in-program via psum —
        routed through ``kvstore.reduce_in_program`` when the bound store
        (``tpu_sync``) provides the hook (docs/multichip.md).

        AMP (docs/amp.md): ``loss_scaler`` (an ``amp.LossScaler``) threads
        scale-apply / grad-unscale / the all-finite check / the skip-update
        ``lax.cond`` / the scale update through the SAME single program —
        its tiny ``(scale, good_steps)`` state rides as an extra program
        input/output.  ``multi_precision`` optimizers whose states carry
        ``(master_f32, inner)`` pytrees (low-precision weights) update the
        f32 master in-program and recast the weight from it each step.

        This is :meth:`plan_fused_step` then :meth:`run_fused_step`: a
        caller that steps again and again (``Module``) keeps the plan and
        calls the second alone (docs/fused_step.md "The step plan").
        """
        plan = self.plan_fused_step(optimizer, states, updates, num_steps,
                                    kvstore, loss_scaler)
        if not self.run_fused_step(plan, feed):
            raise MXNetError(
                "fused_step: params carry mixed update counts; use the "
                "legacy per-param update path")
        return self._outputs

    def plan_fused_step(self, optimizer, states, updates,
                        num_steps: Optional[int] = None, kvstore=None,
                        loss_scaler=None) -> "_FusedPlan":
        """Everything a fused step derives from what does not change from
        one step to the next (docs/fused_step.md "The step plan"): the
        checks, the multipliers, the master-weight and state layouts, the
        program and its key, and the ``NDArray`` HOLDERS whose ``_data`` are
        the program's arguments and take its results — holders and not
        arrays, so that ``set_params``, a loaded state or a caller's write
        to ``arr._data`` between two steps is what the next step reads.
        Arguments as :meth:`fused_step`'s.  The plan is kept as
        ``self._fused_plan`` until something that it depends on changes:
        who changes it drops it (``Module._drop_fused_plan``,
        :meth:`set_monitor_callback`; a rebind makes a new executor)."""
        from . import engine as _engine
        from .observability import telemetry as _obs_tele
        from .optimizer import _pack_state, fused_mults

        if self._grouped is not None:
            raise MXNetError("fused_step does not support group2ctx placement")
        gnames = self._grad_arg_names
        if {n for n, _ in updates} != set(gnames):
            raise MXNetError(
                "fused_step: updates must cover exactly the gradient-taking "
                f"arguments {gnames}, got {sorted(n for n, _ in updates)}")
        if num_steps is None:
            num_steps = _engine.fusion_hint()
        num_steps = max(1, int(num_steps))
        indices = [idx for _, idx in updates]
        mults_by_idx = fused_mults(optimizer, indices)
        mults_by_name = {n: mults_by_idx[idx] for n, idx in updates}
        spmd = self._spmd_total() > 1
        # static per-param master-weight layout (create_state_multi_precision
        # returns (master_f32, inner) exactly when _needs_master holds)
        master_names = frozenset(
            n for n in gnames if optimizer._needs_master(self.arg_dict[n]))
        tele_on = _obs_tele.enabled()
        svals = {n: _pack_state(states[n]) for n in gnames}
        state_specs = None
        if spmd and self._spmd_param_specs:
            # per-leaf optimizer-state specs (docs/sharding.md): a state
            # leaf with its param's shape (momentum, Adam moments, AMP f32
            # master weights) shards exactly like the param; anything else
            # (scalar counters) replicates.  The structure is static per
            # compile key (optimizer statics + master layout), so the spec
            # pytree never varies under a cached program.
            from jax.sharding import PartitionSpec as _P

            def _sspecs(n):
                pshape = tuple(self.arg_dict[n].shape)
                ps = _P(*self._spmd_param_specs.get(n, ()))
                return jax.tree_util.tree_map(
                    lambda leaf: ps if tuple(leaf.shape) == pshape else _P(),
                    svals[n])

            state_specs = {n: _sspecs(n) for n in gnames}
        fn, key = self._get_fused_step(
            optimizer, mults_by_name, num_steps,
            kvstore=kvstore if spmd else None, scaler=loss_scaler,
            master_names=master_names, telemetry=tele_on,
            state_specs=state_specs)
        plan = _FusedPlan()
        plan.fn, plan.key, plan.site = fn, key, self._site("fused_step")
        plan.optimizer, plan.indices = optimizer, indices
        plan.num_steps, plan.scaler, plan.tele_on = num_steps, loss_scaler, \
            tele_on
        plan.gnames = list(gnames)
        # the state's leaves in the order the program's pytree flattens
        # them, as holders (an NDArray is a leaf to jax; _pack_state's
        # tuples for lists, so the structure is the packed one)
        s_h, plan.s_tree = jax.tree_util.tree_flatten(
            {n: _state_holders(states[n]) for n in gnames})
        # the donated arguments' holders: parameters, gradients, state
        plan.d_h = [self.arg_dict[n] for n in gnames] \
            + [self.grad_dict[n] for n in gnames] + s_h
        rest = [n for n in self._arg_names if n not in set(gnames)]
        plan.b_names = [n for n in rest if spmd and n in self._spmd_batch_args]
        plan.o_names = [n for n in rest if n not in set(plan.b_names)]
        plan.a_names = list(self._aux_names)
        plan.b_h = [self.arg_dict[n] for n in plan.b_names]
        plan.r_h = [self.arg_dict[n] for n in plan.o_names] \
            + [self.aux_dict[n] for n in plan.a_names]
        plan.spmd = spmd
        plan.seen_d = plan.seen_r = None
        plan.ahead = {}
        plan.lookup_counted = True
        plan.guard = plan.states_of = None  # its owner's, if it has one
        if spmd:
            from jax.sharding import NamedSharding, PartitionSpec

            mesh = self._spmd_mesh
            plan.ndev = self._spmd_ndev()
            plan.shard = NamedSharding(mesh, PartitionSpec(self._spmd_axis))
            plan.repl = NamedSharding(mesh, PartitionSpec())
            if state_specs is not None:
                # rule-sharded params, grads and state land (and stay) in
                # their PartitionSpec layout
                of = [NamedSharding(mesh, PartitionSpec(
                    *self._spmd_param_specs.get(n, ()))) for n in gnames]
                plan.d_shardings = of + of + [
                    NamedSharding(mesh, sp) for sp in
                    jax.tree_util.tree_leaves(state_specs)]
            else:
                plan.d_shardings = plan.repl
        self._fused_plan = plan
        _note_plan("fused_plan_builds")
        return plan

    def run_fused_step(self, plan: "_FusedPlan", feed=None) -> bool:
        """One step of ``plan``: what is left of a step once its plan is
        there — the update counts and the step's scalars, the fed batch,
        the key, the holders' arrays, the call, and the results back into
        the holders.  False, with nothing done, when the params carry mixed
        update counts (the per-param loop's case)."""
        from . import engine as _engine
        from .optimizer import fused_advance

        # two spans, so that a device-idle gap names its owner: the host
        # work before dispatch, and the dispatch itself
        with _tracing.span("executor.feed", cat="executor"):
            scalars = fused_advance(plan.optimizer, plan.indices,
                                    plan.num_steps)
            if scalars is None:
                return False
            for k, v in (feed or {}).items():
                if k not in self.arg_dict:
                    raise MXNetError(f"fused_step: unknown argument {k!r}")
                self.arg_dict[k]._data = v._data if isinstance(v, NDArray) \
                    else jnp.asarray(v)
            fn = plan.fn
            args, rng = self._fused_args(plan, scalars)
            if plan.lookup_counted:
                plan.lookup_counted = False  # the build's own lookup
            else:
                # a reused program is a hit of the compile cache, as ever
                _note_cache(True, site=plan.site, key=plan.key)
            if self._fused_probe is None or self._fused_probe[0] is not fn:
                # once per program: the argument shapes, for
                # fused_step_hlo() (placement only where it was chosen: an
                # uncommitted array follows the others, as in the call
                # itself)
                # (the thunk holds the program and its shapes, not this
                # executor: the device-scope resolver may read a profiler
                # session after the executor is gone)
                self._fused_probe = (fn, _device_scopes.text_thunk(fn, args))
                _device_scopes.register(self)
        with _tracing.span("executor.fused_step", cat="executor"):
            res = fn(*args)
        if plan.tele_on:
            res, tele_vals = res[:-1], res[-1]
            self._note_telemetry(tele_vals)
        outs, aux_updates, new_grads, new_p, new_s = res[:5]
        if plan.scaler is not None:
            plan.scaler.set_state(res[5])
        self._outputs = [NDArray(o) for o in outs]
        for k, v in aux_updates.items():
            self.aux_dict[k]._data = v
        gnames = plan.gnames
        landed = [new_p[n] for n in gnames] + [new_grads[n] for n in gnames] \
            + jax.tree_util.tree_leaves(new_s)
        for h, v in zip(plan.d_h, landed):
            h._data = v
        # what the next launch may donate as it is: program outputs are
        # always distinct, and carry the placement the program gave them
        plan.seen_d = landed
        if plan.spmd:
            plan.seen_r = self._fused_rest(plan)
        # the device runs the step now: what the next launch will need and
        # that is known already is made ready here, not in the gap after
        # the step (used only if it still holds then: the key if nobody
        # draws in between, the count if nobody moves it)
        plan.ahead["key"] = _random.split_ahead()
        plan.scalar("t", tuple(t + plan.num_steps for t in scalars[2]))
        self._cached_grads = None
        self._last_rng = rng
        if _engine.is_naive():  # NaiveEngine forces sync, as everywhere else
            for o in self._outputs:
                o.wait_to_read()
            for h in plan.d_h[:len(gnames)]:
                h.wait_to_read()
        if self._monitor_callback is not None:
            for name, out in zip(self._out_names, self._outputs):
                self._monitor_callback(name, out)
        return True

    @staticmethod
    def _fused_rest(plan):
        """The arrays of a plan's non-donated, non-batch arguments now."""
        rest = [h._data for h in plan.r_h]
        if plan.scaler is not None:
            rest.extend(plan.scaler.state())
        return rest

    def _fused_args(self, plan, scalars):
        """The arguments of one launch, gathered from the plan's holders:
        ``(args, rng)``.  Donated buffers are made unique, and under SPMD
        everything is placed on the mesh, only when an array is not the one
        the plan's last launch returned — the first step, or one after
        ``set_params`` / ``init_params`` / a loaded state / a caller's
        write."""
        from .optimizer import uniquify_donated

        donated = [h._data for h in plan.d_h]
        seen = plan.seen_d
        fresh = seen is None or not all(map(operator.is_, donated, seen))
        if fresh:
            # aliased buffers (jax's constant cache hands one zero buffer
            # to several fresh arrays) can only come from outside a launch.
            # Before replication: single-device buffer pointers are
            # readable here, while multi-shard arrays only fall back to
            # id() (constant-cache aliases would then slip through and XLA
            # rejects a twice-donated buffer)
            _note_plan("fused_uniquify_runs")
            donated = uniquify_donated(donated)
            if plan.spmd:
                # one device_put per array, no per-device Python splits:
                # everything replicated — except rule-sharded
                # params/grads/state, which land in their PartitionSpec
                # layout
                donated = jax.device_put(donated, plan.d_shardings)
        else:
            _note_plan("fused_plan_reuses")
        gnames = plan.gnames
        n = len(gnames)
        pvals = dict(zip(gnames, donated[:n]))
        gvals = dict(zip(gnames, donated[n:2 * n]))
        svals = jax.tree_util.tree_unflatten(plan.s_tree, donated[2 * n:])
        rng = _random.next_key(plan.ahead.pop("key", None))
        lr_vec, wd, t_vec = map(plan.scalar, ("lr", "wd", "t"), scalars)
        rest = self._fused_rest(plan)
        batch = ()
        if plan.spmd:
            # the batch lands sharded on the dp axis (Module.prepare has
            # placed it as a rule, while the device ran the step before),
            # everything else replicated: placed arrays go back into their
            # holders, so that the next step knows them
            batch_vals = {}
            for name, h in zip(plan.b_names, plan.b_h):
                v = h._data
                if getattr(v, "sharding", None) != plan.shard:
                    if not v.shape or v.shape[0] % plan.ndev:
                        raise MXNetError(
                            f"fused_step: batch dim of {name!r} ({v.shape}) "
                            f"not divisible by the dp mesh size {plan.ndev}")
                    v = h._data = jax.device_put(v, plan.shard)
                batch_vals[name] = v
            batch = (batch_vals,)
            if plan.seen_r is None or not all(map(operator.is_, rest, plan.seen_r)):
                rest = jax.device_put(rest, plan.repl)
                for h, v in zip(plan.r_h, rest):
                    h._data = v
            self._spmd_active = True
        no, nr = len(plan.o_names), len(plan.r_h)
        other = dict(zip(plan.o_names, rest[:no]))
        aux_vals = dict(zip(plan.a_names, rest[no:nr]))
        sc_args = () if plan.scaler is None else (tuple(rest[nr:]),)
        return (pvals, gvals, svals, *batch, other, aux_vals, lr_vec, wd,
                t_vec, rng, *sc_args), rng

    def fused_step_hlo(self) -> str:
        """Optimised HLO text of the program the last :meth:`fused_step`
        ran, compiled again from its recorded argument shapes — the way to
        see what the compiler put in (an ``all-reduce`` under a dp mesh,
        chip_smoke.py ``--chips 4``)."""
        if self._fused_probe is None:
            raise MXNetError("fused_step_hlo: no fused step has run")
        return self._fused_probe[1]()

    def device_programs(self):
        """What ``observability.device_scopes`` reads: ``(kind, key,
        launches: nobody counts them, thunk for the optimised HLO text)``
        of the fused step this executor ran last."""
        if self._fused_probe is None:
            return []
        return [("fused_step", 0, None, self._fused_probe[1])]

    # -- train telemetry ----------------------------------------------------------
    def _note_telemetry(self, vals: Dict[str, object]) -> None:
        """Fold one fused step's telemetry outputs into the executor-held
        device scalars: nonfinite/skip counts accumulate (lazy jnp adds, no
        sync), everything else keeps the last-step value."""
        from .observability import telemetry as _obs_tele

        self._telemetry_last = dict(vals)
        for k in _obs_tele.ACCUMULATING:
            v = vals.get(k)
            if v is None:
                continue
            prev = self._telemetry_accum.get(k)
            self._telemetry_accum[k] = v if prev is None else prev + v

    def telemetry_snapshot(self) -> Dict[str, object]:
        """The current telemetry DEVICE scalars (last-step values, with the
        nonfinite/skip counters replaced by their cross-step totals).  Hand
        to ``observability.telemetry.publish`` at a log boundary — that is
        the single host sync."""
        if self._telemetry_last is None:
            return {}
        out = dict(self._telemetry_last)
        out.update(self._telemetry_accum)
        return out

    # -- checkpoint capture -------------------------------------------------------
    def snapshot_arrays(self, include_aux: bool = True):
        """Donation-safe snapshot of the bound argument (and aux) buffers:
        ``({name: array}, {aux_name: array})``.

        Single-device buffers are copied ON DEVICE (``jnp.array(copy=True)``
        — an async D2D copy, no host sync, no executor-cache compile), so
        the fit thread can hand the snapshot to the async checkpoint writer
        and keep stepping: the next fused step donates the ORIGINAL buffers,
        never these copies.  Multi-device buffers (replicated or
        partition-rule sharded over the mp axis) gather through the host
        instead — the snapshot then holds the full array, identical to the
        replicated layout, so a checkpoint written from it restores under
        any mesh shape (docs/sharding.md).
        """
        def snap(a):
            x = a._data
            if x is None:
                return None
            try:
                multi = len(x.devices()) > 1
            except Exception:
                multi = False
            return _np.asarray(x) if multi else jnp.array(x, copy=True)

        args = {n: snap(self.arg_dict[n]) for n in self._arg_names
                if n in self.arg_dict}
        aux = {}
        if include_aux:
            aux = {n: snap(self.aux_dict[n]) for n in self._aux_names
                   if n in self.aux_dict}
        return ({k: v for k, v in args.items() if v is not None},
                {k: v for k, v in aux.items() if v is not None})

    # -- params & misc ------------------------------------------------------------
    def copy_params_from(self, arg_params: Dict[str, NDArray],
                         aux_params: Optional[Dict[str, NDArray]] = None,
                         allow_extra_params: bool = False) -> None:
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = v._data.astype(self.arg_dict[k]._data.dtype)
            elif not allow_extra_params:
                raise MXNetError(f"copy_params_from: unknown argument {k!r}")
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._data = v._data.astype(self.aux_dict[k]._data.dtype)
            elif not allow_extra_params:
                raise MXNetError(f"copy_params_from: unknown aux state {k!r}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes, carrying over current params/aux
        (reference: Executor.reshape shares the bound arrays)."""
        new_exec = self._symbol.simple_bind(
            ctx=self._ctx, grad_req=self.grad_req,
            group2ctx=self._group2ctx, **kwargs)
        param_names = set(new_exec._arg_names) - set(kwargs)
        new_exec.copy_params_from(
            {n: self.arg_dict[n] for n in param_names
             if n in self.arg_dict and self.arg_dict[n].shape == new_exec.arg_dict[n].shape},
            {n: v for n, v in self.aux_dict.items()},
            allow_extra_params=True)
        return new_exec

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback
        # a monitor wants the legacy path's per-step introspection: the
        # plan's owner decides again
        self._fused_plan = None

    def debug_str(self) -> str:
        lines = [f"Symbol outputs: {self._out_names}"]
        for n in self._arg_names:
            lines.append(f"arg {n}: {self.arg_dict[n].shape}")
        return "\n".join(lines)

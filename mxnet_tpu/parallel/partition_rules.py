"""Partition rules: regex -> PartitionSpec sharding of named param trees.

The dp-only mesh from the SPMD fused step (docs/multichip.md) replicates
every parameter and optimizer slot on every chip, capping trainable model
size at one chip's HBM.  This module removes that cap the GSPMD way
(SNIPPETS.md [2]'s ``match_partition_rules`` / ``make_shard_and_gather_fns``
pattern): an ORDERED list of ``(regex, PartitionSpec)`` rules is matched
against flattened parameter names — first match wins, unmatched params
replicate — and the resulting spec pytree tells the fused train step which
mesh axes each weight, gradient, and optimizer-state leaf lives sharded on.

Semantics (docs/sharding.md):

- first match wins; later rules never override an earlier match;
- scalars and single-element leaves are never partitioned;
- unmatched params REPLICATE (the safe default — the reference pattern
  raises instead; a training framework cannot, because aux-shaped oddballs
  always exist);
- divisibility fallback: when a matched spec names a mesh axis whose size
  does not divide the corresponding dim, the axis is DROPPED from that dim
  (rather than erroring) so a rule set written for one model keeps working
  on another — the explainer surfaces the resolved spec either way;
- the ``FSDP`` sentinel spec shards the first divisible dim on the model
  axis — ZeRO-style fully-sharded storage for "everything else" rules.

``Executor.fused_step`` composes these specs into the donated shard_map
program over a 2-D ``("dp", "mp")`` mesh: tensor-parallel storage for
rule-matched matmul weights, FSDP-style fully-sharded optimizer state
(including AMP f32 master weights) for the rest, batch still sharded on
``dp`` via :func:`mxnet_tpu.io.shard_data_batch`.
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as _np

__all__ = ["FSDP", "DEFAULT_FSDP_RULES", "match_partition_rules",
           "resolve_spec", "make_param_specs", "spec_tuple", "spec_str",
           "shard_params", "gather_params", "make_shard_and_gather_fns",
           "rules_from_env", "bytes_per_device", "max_bytes_per_device",
           "rules_compute_partitionable", "validate_rule_axes",
           "mp_compute_enabled"]

#: sentinel spec: shard the first divisible dim on the model axis
#: (ZeRO/FSDP-style fully-sharded storage)
FSDP = "fsdp"

#: the catch-all rule set used when model parallelism is requested
#: (``TPUMX_MP_DEVICES`` > 1) without an explicit rules dict: every
#: non-scalar param fully-shards its first divisible dim on ``mp``
DEFAULT_FSDP_RULES = ((r".*", FSDP),)


def _partition_spec_cls():
    from jax.sharding import PartitionSpec

    return PartitionSpec


def spec_tuple(spec) -> tuple:
    """A PartitionSpec (or tuple/list form, or the ``FSDP`` sentinel) as a
    hashable tuple of per-dim entries (``None``, axis name, or tuple of
    axis names) — the form stored in executor compile keys."""
    if spec is None:
        return ()
    if isinstance(spec, str):
        return (spec,) if spec == FSDP else (spec,)
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            out.append(tuple(str(a) for a in entry))
        else:
            out.append(str(entry))
    return tuple(out)


def spec_str(spec) -> str:
    """Human-readable ``p('dp',None)`` rendering (recompile-explainer and
    log format; docs/sharding.md)."""
    parts = []
    for entry in spec_tuple(spec):
        if entry is None:
            parts.append("None")
        elif isinstance(entry, tuple):
            parts.append("(" + "+".join(f"'{a}'" for a in entry) + ")")
        else:
            parts.append(f"'{entry}'")
    return "p(" + ",".join(parts) + ")"


def _shape_of(leaf) -> tuple:
    if hasattr(leaf, "shape"):
        return tuple(leaf.shape)
    return tuple(leaf)


def match_partition_rules(rules, params: Dict[str, object]):
    """Match ordered ``(regex, spec)`` rules against a flat name->leaf dict.

    ``params`` maps names to arrays (anything with ``.shape``) or shape
    tuples.  Returns ``{name: raw spec}`` where the raw spec is whatever the
    first matching rule carried (PartitionSpec, tuple form, or ``FSDP``);
    unmatched and scalar/size-1 leaves map to the replicated spec ``()``.
    ``re.search`` semantics, like the reference pattern (SNIPPETS.md [2]).
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    out = {}
    for name, leaf in params.items():
        shape = _shape_of(leaf)
        if len(shape) == 0 or int(_np.prod(shape)) <= 1:
            out[name] = ()
            continue
        for pat, spec in compiled:
            if pat.search(name) is not None:
                out[name] = spec
                break
        else:
            out[name] = ()
    return out


def resolve_spec(spec, shape: Tuple[int, ...], mesh, mp_axis: str = "mp"):
    """Resolve one raw spec against a concrete shape + mesh.

    - the ``FSDP`` sentinel becomes ``mp_axis`` on the first dim the axis
      size divides (replicated when none divides);
    - axes absent from the mesh are dropped;
    - a dim whose size the named axes do not divide drops axes from the
      right until it does (the divisibility FALLBACK — never an error);
    - entries beyond ``len(shape)`` are trimmed.

    Returns the resolved spec as a plain tuple (``spec_tuple`` form).
    """
    sizes = {str(a): int(mesh.shape[a]) for a in mesh.axis_names}
    if spec == FSDP or spec == (FSDP,):
        n = sizes.get(mp_axis, 1)
        if n > 1:
            for dim, d in enumerate(shape):
                if d % n == 0 and d >= n:
                    return tuple(mp_axis if i == dim else None
                                 for i in range(len(shape)))
        return ()
    raw = spec_tuple(spec)[:len(shape)]
    out: List[object] = []
    for dim, entry in enumerate(raw):
        if entry is None:
            out.append(None)
            continue
        axes = list(entry) if isinstance(entry, tuple) else [entry]
        axes = [a for a in axes if a in sizes]
        while axes:
            prod = 1
            for a in axes:
                prod *= sizes[a]
            if prod <= shape[dim] and shape[dim] % prod == 0:
                break
            axes.pop()  # drop the minor-most axis rather than erroring
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def make_param_specs(rules, params: Dict[str, object], mesh,
                     mp_axis: str = "mp") -> Dict[str, tuple]:
    """rules + name->leaf/shape dict + mesh -> ``{name: resolved spec
    tuple}`` containing ONLY the params that actually shard (trivial
    replicated specs are omitted, keeping compile keys clean)."""
    raw = match_partition_rules(rules, params)
    out = {}
    for name, spec in raw.items():
        resolved = resolve_spec(spec, _shape_of(params[name]), mesh,
                                mp_axis=mp_axis)
        if any(e is not None for e in resolved):
            out[name] = resolved
    return out


def sharding_for_spec(mesh, spec):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*spec_tuple(spec)))


def shard_params(params: Dict[str, object], specs: Dict[str, object], mesh):
    """Place a name->array dict over the mesh per ``specs`` (one
    ``device_put`` per leaf; names without a spec replicate).  No-op for
    arrays already laid out right — the steady-state case."""
    import jax

    out = {}
    for name, v in params.items():
        out[name] = jax.device_put(
            v, sharding_for_spec(mesh, specs.get(name, ())))
    return out


def gather_params(params: Dict[str, object], mesh=None):
    """Gather a (possibly sharded) name->array dict to fully-replicated
    arrays — the host-copy / checkpoint boundary.  With ``mesh=None`` the
    gather happens through host memory (works for any source layout)."""
    import jax
    import jax.numpy as jnp

    if mesh is not None:
        repl = sharding_for_spec(mesh, ())
        return {n: jax.device_put(v, repl) for n, v in params.items()}
    return {n: jnp.asarray(_np.asarray(v)) for n, v in params.items()}


def make_shard_and_gather_fns(specs: Dict[str, object], mesh):
    """``(shard_fn, gather_fn)`` closures over a spec dict + mesh — the
    SNIPPETS.md [2] API shape, used by checkpoint restore (rescatter under
    a new mesh) and by tests."""
    def shard_fn(params):
        return shard_params(params, specs, mesh)

    def gather_fn(params):
        return gather_params(params, mesh)

    return shard_fn, gather_fn


def mp_compute_enabled() -> bool:
    """``TPUMX_MP_COMPUTE`` gate (default ON): whether compute-partitionable
    rule sets run the GSPMD tensor-parallel-compute fused step.  ``=0``
    restores the FSDP gather-compute-slice program byte-for-byte, compile
    keys included (docs/sharding.md)."""
    return os.environ.get("TPUMX_MP_COMPUTE", "1") != "0"


def rules_compute_partitionable(rules) -> bool:
    """Whether a rule set describes a COMPUTE partitioning: every spec is an
    explicit per-dim placement (Megatron column/row style) that XLA's SPMD
    partitioner can push through the matmuls.  A rule carrying the ``FSDP``
    sentinel makes the whole set storage-only — FSDP means
    gather-compute-slice by construction, so those keep the PR-8 path."""
    for _pat, spec in rules or ():
        if spec == FSDP or spec == (FSDP,):
            return False
    return True


def validate_rule_axes(rules, axis_names, source: str = "shard_rules"):
    """Raise :class:`~mxnet_tpu.base.MXNetError` when any rule names a mesh
    axis that does not exist, identifying the rule, the bad axis, and the
    mesh axes — instead of the opaque shard_map/NamedSharding error the
    stale name would otherwise surface as three layers down.

    ``axis_names``: the bound mesh's axis names (a Mesh is accepted too).
    """
    from ..base import MXNetError

    if not rules:
        return
    if hasattr(axis_names, "axis_names"):
        axis_names = axis_names.axis_names
    known = {str(a) for a in axis_names}
    for pat, spec in rules:
        if spec == FSDP or spec == (FSDP,):
            continue
        for entry in spec_tuple(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                if str(a) not in known:
                    raise MXNetError(
                        f"{source}: rule {pat!r} names mesh axis {a!r}, "
                        f"which is not in the bound mesh "
                        f"(axes: {sorted(known)})")


def rules_from_env(env: Optional[str] = None):
    """Parse ``TPUMX_SHARD_RULES`` into a rules list, or None when unset.

    Format: semicolon-separated ``regex=spec`` entries, matched in order.
    A spec is comma-separated per-dim entries: an axis name, ``+``-joined
    axis names, or ``-``/``None`` for replicated on that dim; the bare word
    ``fsdp`` is the FSDP sentinel and ``-`` alone means replicated.
    Example: ``TPUMX_SHARD_RULES='.*_weight=mp,-;.*=fsdp'``.
    """
    if env is None:
        env = os.environ.get("TPUMX_SHARD_RULES", "")
    env = env.strip()
    if not env:
        return None
    rules = []
    for item in env.split(";"):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            raise ValueError(
                f"TPUMX_SHARD_RULES entry {item!r} is not 'regex=spec'")
        pat, spec_s = item.rsplit("=", 1)
        spec_s = spec_s.strip()
        if spec_s.lower() == FSDP:
            rules.append((pat, FSDP))
            continue
        entries: List[object] = []
        for dim in spec_s.split(","):
            dim = dim.strip()
            if dim in ("-", "", "None", "none"):
                entries.append(None)
            elif "+" in dim:
                entries.append(tuple(a.strip() for a in dim.split("+")))
            else:
                entries.append(dim)
        while entries and entries[-1] is None:
            entries.pop()
        rules.append((pat, tuple(entries)))
    return rules


# -- live-memory accounting ---------------------------------------------------------
def bytes_per_device(arrays) -> Dict[object, int]:
    """Per-device live bytes of a collection of (possibly sharded) device
    arrays — the memory-reduction headline's measurement (docs/sharding.md
    memory math; the sharding tests assert on it).  Accepts any iterable /
    pytree of jax arrays or NDArrays."""
    import jax

    out: Dict[object, int] = {}
    leaves = jax.tree_util.tree_leaves(arrays)
    for leaf in leaves:
        buf = getattr(leaf, "_data", leaf)
        if buf is None or not hasattr(buf, "addressable_shards"):
            continue
        for shard in buf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + int(
                shard.data.nbytes)
    return out


def max_bytes_per_device(arrays) -> int:
    per = bytes_per_device(arrays)
    return max(per.values()) if per else 0

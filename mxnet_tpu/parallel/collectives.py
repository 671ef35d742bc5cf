"""Collectives over mesh axes.

Replaces the reference's three comm backends (CommCPU/CommDevice trees
comm.h:103,451; NCCL kvstore_nccl.h:285,402; ps-lite push/pull) with XLA
collectives that lower onto ICI: psum (allreduce), all_gather, psum_scatter
(reduce_scatter), ppermute (ring), all_to_all.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .mesh import get_mesh

__all__ = ["allreduce", "allgather", "reduce_scatter", "broadcast", "all_to_all",
           "allreduce_tree", "allreduce_grads_spmd"]


def allreduce(x, axis_name: str):
    """Inside shard_map/pjit: psum over the named axis."""
    return lax.psum(x, axis_name)


def allgather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x, axis_name: str, src: int = 0):
    """Broadcast src's shard to all members of the axis."""
    n = lax.axis_size(axis_name)
    if not isinstance(src, jax.core.Tracer):
        # static src (incl. numpy ints): validate now — an out-of-range src
        # would make the mask never fire and psum return silent ZEROS, the
        # worst kind of collective bug to debug downstream
        import operator

        src = operator.index(src)
        if not 0 <= src < n:
            raise ValueError(f"broadcast src={src} out of range for axis "
                             f"{axis_name!r} of size {n}")
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def allreduce_tree(values: List, mesh: Mesh = None, axis: str = "dp"):
    """Host-level list-of-per-device-arrays allreduce: builds a one-shot
    shard_map program (the API shape of Comm::Reduce+Broadcast, comm.h:57)."""
    mesh = mesh or get_mesh()
    if mesh is None or len(values) == 1:
        acc = values[0]
        for v in values[1:]:
            acc = acc + v
        return [acc] * len(values)
    if len(values) != mesh.shape[axis]:
        # a mismatched list would shard (k, ...) over the axis and sum
        # interleaved partials — silently corrupt gradients; fall back to the
        # host-side reduction instead
        acc = values[0]
        for v in values[1:]:
            acc = acc + v
        return [acc] * len(values)
    stacked = jnp.stack([v for v in values])

    def _reduce(x):
        return lax.psum(x, axis)

    fn = jax.shard_map(_reduce, mesh=mesh,
                          in_specs=PartitionSpec(axis),
                          out_specs=PartitionSpec(axis), check_vma=True)
    out = fn(stacked)
    return [out[i] for i in range(len(values))]


def allreduce_grads_spmd(grads: Dict[str, jnp.ndarray], axis: str = "dp"):
    """Allreduce a grad pytree inside an SPMD region (used by the fused
    data-parallel train step)."""
    return jax.tree_util.tree_map(lambda g: lax.pmean(g, axis), grads)

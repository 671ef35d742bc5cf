"""All-to-all (Ulysses-style) sequence parallelism.

Second long-context strategy (besides ring attention): activations are
sequence-sharded between attention calls; inside attention, an all_to_all
re-shards from sequence → heads so each device computes full-sequence
attention for a head subset, then all_to_all back.  ICI all_to_all is cheap
on TPU; this trades ring latency for two transposes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from .mesh import get_mesh
from .ring_attention import local_attention

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      impl: str = "dense"):
    """Call INSIDE shard_map; q,k,v: (B, Tlocal, H, D) sequence-sharded.

    all_to_all: (B, T/n, H, D) → (B, T, H/n, D); local full attention;
    inverse.  ``impl="flash"`` runs the inner full-sequence attention as
    the streaming Pallas kernel (ops/flash_attention.py) — unlike the ring,
    Ulysses needs no cross-step bias, so flash composes directly and the
    per-device attention memory drops from O(T^2) scores to O(T).
    """
    def seq2head(x):
        # split heads across the axis, gather sequence
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    if impl == "flash":
        from ..ops.flash_attention import flash_attention as attn
    elif impl == "dense":
        attn = local_attention
    else:
        raise ValueError(f"unknown impl {impl!r}")
    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    out = attn(qh, kh, vh, causal=causal)
    return head2seq(out)


def ulysses_attention_sharded(q, k, v, mesh: Optional[Mesh] = None,
                              axis_name: str = "sp", causal: bool = False,
                              impl: str = "dense"):
    """Host entry.  Validates the mesh/shape contract up front — a missing
    axis or an indivisible head count otherwise surfaces as an opaque
    shard_map/all_to_all error three layers down (the same discipline as
    ``partition_rules.validate_rule_axes``)."""
    from ..base import MXNetError

    mesh = mesh or get_mesh()
    if mesh is None or axis_name not in mesh.axis_names:
        axes = sorted(str(a) for a in mesh.axis_names) if mesh is not None \
            else []
        raise MXNetError(
            f"ulysses_attention_sharded: axis {axis_name!r} is not in the "
            f"bound mesh (axes: {axes})")
    n = int(mesh.shape[axis_name])
    heads = q.shape[2]
    if heads % n:
        raise MXNetError(
            f"ulysses_attention_sharded: {heads} heads not divisible by "
            f"mesh axis {axis_name!r} of size {n}")
    spec = PartitionSpec(None, axis_name, None, None)

    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)

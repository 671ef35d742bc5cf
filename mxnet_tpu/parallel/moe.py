"""Expert parallelism: Switch-style top-1 MoE over an `ep` mesh axis.

The reference has no MoE (2018 codebase, SURVEY.md §2.3 'ABSENT'); the TPU
build adds it as a first-class capability: experts live one-per-device on the
`ep` axis, tokens are dispatched with `lax.all_to_all` over ICI (the
sharded-embedding pattern SURVEY.md §5.8 maps row-sparse pulls to), processed
by the local expert, and returned. Fixed capacity keeps every shape static
for XLA; over-capacity tokens fall through with zero output (standard Switch
semantics).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from .mesh import get_mesh

__all__ = ["moe_dispatch_combine", "moe_apply_sharded", "top1_routing",
           "moe_partition_rules"]


def moe_partition_rules(axis_name: str = "ep"):
    """Expert placement as a rule set (docs/sharding.md): expert param
    stacks (leading expert dim) shard dim 0 over the expert axis, the
    router replicates — the same ordered regex→PartitionSpec form
    `Module.fit(shard_rules=...)` and `partition_rules.make_param_specs`
    consume, so this module's hand-rolled ``pspec`` tree in
    :func:`moe_apply_sharded` is expressible (and testable) as data.
    The expert axis is the model axis: on the fused-step ("dp","mp") mesh
    pass ``axis_name="mp"``."""
    return (
        (r"router", ()),                       # replicated gate
        (r"expert|w_in$|w_out$", (axis_name,)),  # one expert per shard
    )


def top1_routing(x, router_w, num_experts, capacity):
    """Top-1 router (Switch). Returns (dispatch (E, C, B), combine (E, C, B)).

    dispatch is a 0/1 tensor placing token b in expert e's slot c; combine is
    dispatch scaled by the softmax gate probability.
    """
    logits = jnp.dot(x, router_w)                      # (B, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                # (B,)
    gate = jnp.max(probs, axis=-1)                     # (B,)
    onehot = jax.nn.one_hot(expert, num_experts, dtype=x.dtype)  # (B, E)
    # position of each token within its expert's queue — accumulate in int32:
    # a bf16 cumsum saturates above 256 tokens and collides capacity slots
    pos_i = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    pos = (pos_i * onehot.astype(jnp.int32) - 1).astype(jnp.float32)
    kept = (pos < capacity) & (onehot > 0)
    pos_clip = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_clip, capacity, dtype=x.dtype)     # (B, E, C)
    dispatch = jnp.where(kept[..., None], slot, 0.0)   # (B, E, C)
    dispatch = jnp.transpose(dispatch, (1, 2, 0))      # (E, C, B)
    combine = dispatch * gate[None, None, :]
    return dispatch, combine


def moe_dispatch_combine(x, router_w, expert_fn, expert_params,
                         axis_name: str = "ep", capacity_factor: float = 2.0):
    """Run INSIDE shard_map. x: (B_local, D); one expert per device.

    dispatch → all_to_all over `axis_name` → local expert → all_to_all back
    → combine. Returns (B_local, D).
    """

    n = lax.axis_size(axis_name)
    B, D = x.shape
    capacity = max(1, int(B * capacity_factor / n))
    dispatch, combine = top1_routing(x, router_w, n, capacity)
    # gather this device's tokens for every expert: (E, C, D)
    expert_inputs = jnp.einsum("ecb,bd->ecd", dispatch, x)
    # all_to_all: axis 0 (experts) ↔ devices; device e receives the (C, D)
    # blocks destined for ITS expert from every source device → (E, C, D)
    # where axis 0 is now the source device
    expert_inputs = lax.all_to_all(expert_inputs, axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
    shaped = expert_inputs.reshape(n * capacity, D)
    processed = expert_fn(expert_params, shaped).reshape(n, capacity, -1)
    processed = lax.all_to_all(processed, axis_name, split_axis=0,
                               concat_axis=0, tiled=False)
    return jnp.einsum("ecb,ecd->bd", combine, processed)


def moe_apply_sharded(x, router_w, expert_params, expert_fn: Callable,
                      mesh: Optional[Mesh] = None, axis_name: str = "ep",
                      capacity_factor: float = 2.0):
    """Host entry: x (B, D) batch-sharded over `axis_name`; expert_params has
    a leading expert dim of size mesh.shape[axis_name]; router replicated."""
    mesh = mesh or get_mesh()
    pspec = jax.tree_util.tree_map(lambda _: PartitionSpec(axis_name),
                                   expert_params)

    def inner(xs, rw, ep):
        ep = jax.tree_util.tree_map(lambda p: p[0], ep)  # drop expert dim
        return moe_dispatch_combine(xs, rw, expert_fn, ep,
                                    axis_name=axis_name,
                                    capacity_factor=capacity_factor)


    fn = jax.shard_map(inner, mesh=mesh,
                          in_specs=(PartitionSpec(axis_name), PartitionSpec(),
                                    pspec),
                          out_specs=PartitionSpec(axis_name), check_vma=False)
    return fn(x, router_w, expert_params)

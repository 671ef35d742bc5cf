"""Pipeline parallelism via shard_map + ppermute microbatching.

The reference has only inter-layer model parallelism with cross-device copies
(`group2ctx` + _CrossDeviceCopy nodes, SURVEY.md §2.3); this provides true
GPipe-style pipelining: stages live on the `pp` mesh axis, microbatches flow
stage-to-stage over ICI with a steady-state bubble of (S-1)/(M+S-1).

The tick loop is a ``lax.scan`` (not ``fori_loop``) so the WHOLE schedule is
reverse-differentiable: ``jax.grad`` through :func:`pipeline_apply` replays
the ring backwards (ppermute transposes to the inverse permutation), which is
what lets ``Executor.fused_step`` trace forward+backward+update over a
pipelined model as ONE donated program (docs/sharding.md).  Gradient
bookkeeping contract under ``shard_map(check=False)``:

- the microbatch input is consumed through a ``rank == 0`` select, so its
  cotangent — and every parameter upstream of it — is nonzero ONLY on stage
  0: combine those with ``psum`` over the pp axis;
- each stage's parameters are used only on their own rank: also ``psum``;
- :func:`psum_bcast` replicates the last stage's committed outputs with a
  custom VJP whose backward is the identity (the raw ``psum`` transposes to
  another psum under ``check=False``, which would multiply every cotangent
  flowing through the pipeline output by the stage count) — downstream
  (replicated) consumers then see exact gradients with NO pp combination.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from .mesh import get_mesh

__all__ = ["pipeline_apply", "pipeline_apply_sharded", "psum_bcast"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_bcast(x, axis_name: str):
    """``lax.psum`` whose transpose is the IDENTITY, for replicating a value
    that is nonzero on exactly one member of ``axis_name`` (the pipeline's
    last-stage outputs) to all members.

    Inside ``shard_map(check=False)`` the stock ``psum`` transposes to a
    psum of the cotangents, so a replicated consumer downstream would inject
    ``axis_size`` copies of the gradient back into the pipeline.  Since every
    rank's downstream cotangent is replica-invariant here, the identity
    backward is exact.
    """
    return lax.psum(x, axis_name)


def _psum_bcast_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _psum_bcast_bwd(axis_name, _res, ct):
    return (ct,)


psum_bcast.defvjp(_psum_bcast_fwd, _psum_bcast_bwd)


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   axis_name: str = "pp"):
    """Run INSIDE shard_map.

    stage_fn(params, x) -> y             one pipeline stage (same shape in/out)
    stage_params                         this device's stage params (leading
                                         stage dim already split by shard_map,
                                         or sliced via ``lax.axis_index``)
    x_microbatches: (M, ...) microbatches; only stage 0's input is used.

    Returns (M, ...) outputs valid on the LAST stage (others zeros); combine
    with :func:`psum_bcast` to replicate them across the axis with correct
    gradients.  Differentiable end to end (the round-robin is a ``lax.scan``
    over M + S - 1 ticks).
    """

    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    T = M + n - 1
    state = jnp.zeros_like(x_microbatches[0])
    outputs = jnp.zeros_like(x_microbatches)
    # mark carries as device-varying over the pp axis up front: the loop body
    # makes them varying (rank-dependent writes), and the scan carry type
    # must be invariant across iterations.  Older jax has neither lax.pcast
    # nor vma tracking — there the zeros carries are already fine.
    if hasattr(lax, "pcast"):
        state = lax.pcast(state, (axis_name,), to="varying")
        outputs = lax.pcast(outputs, (axis_name,), to="varying")

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if still available)
        mb_idx = jnp.clip(t, 0, M - 1)
        inject = jnp.where(t < M, 1.0, 0.0).astype(state.dtype)
        state = jnp.where(rank == 0,
                          x_microbatches[mb_idx] * inject, state)
        # every stage computes
        y = stage_fn(stage_params, state)
        # last stage commits its finished microbatch: microbatch t-(n-1)
        out_idx = jnp.clip(t - (n - 1), 0, M - 1)
        commit = jnp.logical_and(t >= n - 1, rank == n - 1)
        outputs = outputs.at[out_idx].set(
            jnp.where(commit, y, outputs[out_idx]))
        # shift activations one stage down the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        state = lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(tick, (state, outputs),
                                   jnp.arange(T, dtype=jnp.int32))
    return outputs


def pipeline_apply_sharded(stage_fn: Callable, stacked_params, x_microbatches,
                           mesh: Optional[Mesh] = None, axis_name: str = "pp"):
    """Host entry: stacked_params has a leading stage dimension of size
    mesh.shape[axis_name]; x_microbatches (M, B, ...) is replicated."""
    mesh = mesh or get_mesh()
    pspec = jax.tree_util.tree_map(lambda _: PartitionSpec(axis_name), stacked_params)

    def inner(params, x):
        # shard_map splits the stage dim; drop it inside
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        out = pipeline_apply(stage_fn, params, x, axis_name)
        # outputs are zeros except on the last stage → replicate them with
        # the transpose-correct broadcast so grads flow through unscaled
        return psum_bcast(out, axis_name)


    fn = jax.shard_map(inner, mesh=mesh,
                          in_specs=(pspec, PartitionSpec()),
                          out_specs=PartitionSpec(), check_vma=False)
    return fn(stacked_params, x_microbatches)

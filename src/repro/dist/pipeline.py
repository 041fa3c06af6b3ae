"""GPipe-style microbatch pipeline over one mesh axis.

The mesh axis is treated as a ring of pipeline stages: stage parameters are
sharded over their leading (stage) dimension, microbatches enter at stage 0
and activations hop one stage per step via ``lax.ppermute``.  With ``M``
microbatches and ``P`` stages the schedule runs ``M + P - 1`` steps -- the
classic GPipe trapezoid -- and every chip computes its stage for a
different microbatch at every interior step, so the per-step permute (one
microbatch of activations over the interconnect) overlaps the stage
compute, the same partition-streaming idea the chip level applies to
HBM->VMEM block copies.  DESIGN.md §5 places this schedule next to the
ring/serpentine collective matmuls of ``dist.overlap``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def dcn_stages(plan) -> int:
    """Stage count the hierarchical plan's DCN sub-plan prescribes.

    The DCN level's partition count is the host-level decomposition the
    planner chose (``repro.plan``); pipeline stages over the "pod" axis
    realize exactly those partitions.  Returns 1 when the plan is None or
    has no DCN level (single-host meshes).
    """
    lp = plan.level("DCN") if plan is not None else None
    return lp.np if lp is not None else 1


def make_pipeline(mesh: Mesh, stage_fn: Callable[[PyTree, jax.Array], jax.Array],
                  axis: str = "pod", plan: Optional[Any] = None):
    """Build ``fn(stage_params, microbatches) -> outputs`` (DESIGN.md §5).

    ``stage_params`` is a pytree whose leaves carry a leading stage
    dimension equal to the ``axis`` size; ``microbatches`` is an
    ``(n_microbatches, ...)`` stack.  The result equals applying the stages
    sequentially to every microbatch (stage order = position along the mesh
    axis); shapes must be stage-invariant (GPipe homogeneity).  Like the
    ring matmuls, the per-step ``ppermute`` hop is independent of the
    stage compute, so XLA overlaps transfer with work -- the GPipe
    trapezoid is the CC partition stream with stages as partitions.

    ``plan`` (a ``repro.plan.HierarchicalPlan``) maps the stages onto the
    planner's DCN sub-plan: when the plan partitioned the DCN level, the
    mesh axis carrying the stages must realize exactly that partition count
    (a mismatch is a coherence bug -- the state shards the planner sized
    for one host would straddle stage boundaries).
    """
    n_stages = dict(mesh.shape)[axis]
    if plan is not None:
        want = dcn_stages(plan)
        if want > 1 and want != n_stages:
            raise ValueError(
                f"plan's DCN sub-plan prescribes {want} stages but mesh "
                f"axis {axis!r} has {n_stages}")
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def pipe_local(stage_params: PyTree, mbs: jax.Array) -> jax.Array:
        params = jax.tree.map(lambda a: a[0], stage_params)  # my stage's slice
        n_mb = mbs.shape[0]
        idx = jax.lax.axis_index(axis)
        out_struct = jax.eval_shape(stage_fn, params, mbs[0])
        if out_struct.shape != mbs.shape[1:] or out_struct.dtype != mbs.dtype:
            raise ValueError(
                f"stage output {out_struct.shape}/{out_struct.dtype} must "
                f"match microbatch {mbs.shape[1:]}/{mbs.dtype} "
                f"(GPipe homogeneity)")
        outputs0 = jnp.zeros((n_mb,) + out_struct.shape, out_struct.dtype)
        carry0 = jnp.zeros(out_struct.shape, out_struct.dtype)

        def body(t, state):
            carry, outputs = state
            # Stage 0 injects microbatch t; later stages consume the carry
            # their predecessor forwarded last step.
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_mb - 1), 0, keepdims=False)
            y = stage_fn(params, jnp.where(idx == 0, feed, carry))
            # The last stage retires microbatch t - (P-1) once it is valid.
            t_out = t - (n_stages - 1)
            is_tail = jnp.logical_and(idx == n_stages - 1,
                                      jnp.logical_and(t_out >= 0, t_out < n_mb))
            slot = jnp.where(is_tail, t_out, n_mb)    # n_mb is OOB -> dropped
            outputs = outputs.at[slot].set(y, mode="drop")
            carry = jax.lax.ppermute(y, axis, perm)
            return carry, outputs

        _, outputs = jax.lax.fori_loop(0, n_mb + n_stages - 1, body,
                                       (carry0, outputs0))
        # Only the tail stage wrote real values; share them with the ring.
        return jax.lax.psum(outputs, axis)

    sharded = jax.shard_map(
        pipe_local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def pipeline(stage_params: PyTree, microbatches: jax.Array) -> jax.Array:
        leaves = jax.tree.leaves(stage_params)
        for leaf in leaves:
            if leaf.shape[0] != n_stages:
                raise ValueError(
                    f"leading stage dim {leaf.shape[0]} != mesh axis "
                    f"{axis!r} size {n_stages}")
        return sharded(stage_params, microbatches)

    return pipeline

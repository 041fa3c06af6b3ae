"""Cache-conscious blocked matmul Pallas kernel.

The block shapes, grid, and traversal order come from the paper's run-time
decomposer (``core.autotile.plan_matmul``): each grid step is one *task* of
the paper -- a (bm x bk) x (bk x bn) partial product whose working set was
sized to fit VMEM -- and the sequential grid traversal is the worker's
stream of tasks (Fig. 2).

Orders:
  * ``cc``    -- row-major, K innermost: output-stationary; the f32
    accumulator block stays in VMEM across the K stream (spatial locality
    of consecutive tasks, §2.2.1).
  * ``srrc``  -- serpentine over the N-block dimension: consecutive output
    tiles in a row share the same A blocks while B blocks alternate
    direction, maximizing reuse of co-resident operands -- the
    shared-cache-aware goal of §2.2.2 mapped to the (HBM -> VMEM) level.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.autotile import MatmulTilePlan
from repro.core.plan import leaf_matmul_plan
from repro.hw.tpu import chip_spec


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, gk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == gk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_cc(
    a: jax.Array,                  # (M, K)
    b: jax.Array,                  # (K, N)
    plan: Optional[MatmulTilePlan] = None,
    order: str = "cc",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blocked matmul with decomposer-chosen tiles. Pads ragged edges."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if plan is None:
        # VMEM leaf of the hierarchical planner (memoized per shape/dtype).
        plan = leaf_matmul_plan(m, k, n, dtype_bytes=a.dtype.itemsize,
                                order=order)
    bm, bk, bn = plan.bm, plan.bk, plan.bn
    gm, gn, gk = plan.grid

    pm, pk, pn = gm * bm - m, gk * bk - k, gn * bn - n
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    bp = jnp.pad(b, ((0, pk), (0, pn))) if (pk or pn) else b

    serp = plan.order == "srrc" or order == "srrc"

    def a_map(i, j, kk):
        return (i, kk)

    def b_map(i, j, kk):
        if serp:
            j = jax.lax.select(i % 2 == 1, gn - 1 - j, j)
        return (kk, j)

    def o_map(i, j, kk):
        if serp:
            j = jax.lax.select(i % 2 == 1, gn - 1 - j, j)
        return (i, j)

    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    out = pl.pallas_call(
        functools.partial(_mm_kernel, gk=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        # The tiles were planned against the chip's usable VMEM; without
        # the limit Mosaic holds the kernel to its far smaller default.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=chip_spec().usable_vmem,
        ) if not interpret else None,
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]

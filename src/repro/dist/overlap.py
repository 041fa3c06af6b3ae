"""Ring collective matmuls: stream mesh-level partitions while computing.

The paper's CC/SRRC schedules keep one partition resident while the next is
being fetched from the level above.  At the mesh level the "level above" is
the interconnect: these kernels decompose the contraction into one partition
per chip and overlap the ``lax.ppermute`` transfer of the next partition
with the MXU work on the current one (XLA turns the independent permute
into an async collective-permute-start/done pair around the dot).
DESIGN.md §5 is the architecture reference for everything in this module.

Two streaming orders, chosen by the scheduler in ``core.schedule``
(``ring_stream_order``), not hard-coded here:

  * ``ring``       -- CC order: one ICI direction, the whole chunk hops
    forward each step.
  * ``serpentine`` -- SRRC order: both ICI directions concurrently, each
    carrying half of every chunk, so the per-link bytes per step halve and
    effective interconnect bandwidth roughly doubles (the §2.2.2
    shared-resource idea applied to the two directions of a ring link).

The kernels:

  * ``make_ag_matmul`` -- all-gather matmul: x is k-sharded (the layout a
    preceding row-parallel layer leaves it in), w is n-sharded; each ring
    step multiplies the resident k-chunk of x against the matching rows of
    the local w shard.  Output is n-sharded; globally ``y == x @ w``.
  * ``make_rs_matmul`` -- reduce-scatter matmul: x is k-sharded, w is
    k-sharded (row-parallel); the partial-sum accumulator for each output
    row block rides the ring, each chip adding its local contribution.
    Output is m-sharded; globally ``y == x @ w``.
  * ``overlap_matmul`` -- the dispatch ``models/layers.py`` calls for every
    tensor-parallel projection; routes through the kernels above when the
    active sharding rules request it and falls back (returns None) under
    GSPMD rules or non-dividing shapes.

The per-step block compute reuses the chip-level decomposer: on TPU the
local dot runs the Pallas ``matmul_cc`` kernel under the memoized VMEM
leaf of the hierarchical planner (``repro.plan.leaf_matmul_plan`` -- the
same shard shape re-plans once, not per trace); elsewhere it lowers to
``jnp.dot``.  That nesting -- a chip-level cache-conscious plan inside
every mesh-level ring step -- is the paper's hierarchy recursion
(DESIGN.md §5/§6).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

#: Collective-matmul modes the overlap layer understands ("gspmd" means
#: "do not use this module at all" and is handled by the dispatch caller).
MODES = ("ring", "serpentine")


# ---------------------------------------------------------------------------
# Plan-time ring schedule (DESIGN.md §5)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RingPlan:
    """Plan-time schedule of one ring collective (DESIGN.md §5).

    Holds the per-step chunk-owner offsets chosen by the SRRC scheduler
    (``core.schedule.ring_stream_order``) and the ``ppermute`` permutation
    lists built once here -- the kernels close over them instead of
    rebuilding the perm inside every ring step.  ``bwd_*`` fields are None
    in single-direction ("ring") mode.
    """

    p: int
    mode: str                                   # "ring" | "serpentine"
    fwd_offsets: Tuple[int, ...]                # step s consumes (rank - off)
    fwd_perm: Tuple[Tuple[int, int], ...]       # i -> i+1 ring shift
    bwd_offsets: Optional[Tuple[int, ...]] = None
    bwd_perm: Optional[Tuple[Tuple[int, int], ...]] = None

    @property
    def bidirectional(self) -> bool:
        return self.bwd_perm is not None

    def describe(self) -> list:
        """One printable line per ring step showing the ``ppermute``(s) the
        step issues -- the ``benchmarks/run.py --dry`` plan output."""

        def fmt(perm):
            return " ".join(f"{a}>{b}" for a, b in perm)

        lines = []
        for s in range(self.p):
            fwd = f"fwd:src=rank-{self.fwd_offsets[s]}"
            if s < self.p - 1:
                fwd += f";ppermute={fmt(self.fwd_perm)}"
            else:
                fwd += ";last_step=no_permute"
            if not self.bidirectional:
                lines.append(fwd)
                continue
            hops_back = (self.p - self.bwd_offsets[s]) % self.p
            bwd = f"bwd:src=rank+{hops_back}"
            if s < self.p - 1:
                bwd += f";ppermute={fmt(self.bwd_perm)}"
            else:
                bwd += ";last_step=no_permute"
            lines.append(f"{fwd}|{bwd}")
        return lines


@lru_cache(maxsize=64)
def plan_ring(p: int, mode: str = "ring") -> RingPlan:
    """Build the plan-time schedule for a ``p``-way ring axis (DESIGN.md §5).

    The streaming order comes from the paper's scheduler
    (``core.schedule.ring_stream_order``): "ring" uses the CC order (one
    ICI direction), "serpentine" the SRRC order (both directions
    concurrently, each carrying half of every chunk).  Permutation lists
    are materialized once here, at plan time, and closed over by the
    kernels -- never rebuilt inside a ring step.
    """
    from repro.core.schedule import ring_stream_order

    if mode not in MODES:
        raise ValueError(f"unknown collectives mode {mode!r}; one of {MODES}")
    order = ring_stream_order(p, "cc" if mode == "ring" else "srrc")
    fwd = tuple(step[0] for step in order)
    fwd_perm = tuple((i, (i + 1) % p) for i in range(p))
    if mode == "ring":
        return RingPlan(p=p, mode=mode, fwd_offsets=fwd, fwd_perm=fwd_perm)
    bwd = tuple(step[1] for step in order)
    bwd_perm = tuple((i, (i - 1) % p) for i in range(p))
    # A physical ring shifts chunks one hop per step; verify the scheduler's
    # order is realizable before the kernels trust it.
    assert all((fwd[s + 1] - fwd[s]) % p == 1 for s in range(p - 1)), fwd
    assert all((bwd[s + 1] - bwd[s]) % p == p - 1 for s in range(p - 1)), bwd
    return RingPlan(p=p, mode=mode, fwd_offsets=fwd, fwd_perm=fwd_perm,
                    bwd_offsets=bwd, bwd_perm=bwd_perm)


# ---------------------------------------------------------------------------
# Per-step block compute (chip-level decomposer nested in the mesh step)
# ---------------------------------------------------------------------------


def _block_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """One ring step's block product, decomposer-tiled on TPU.

    The tile plan is the VMEM leaf of the hierarchical planner
    (``repro.plan.leaf_matmul_plan``, memoized per local-shard shape): a
    chip-level cache-conscious sub-plan inside every mesh-level ring step
    -- the paper's hierarchy recursion (DESIGN.md §5/§6).
    """
    if jax.default_backend() == "tpu":
        from repro.core.plan import leaf_matmul_plan
        from repro.kernels.matmul_cc import matmul_cc

        plan = leaf_matmul_plan(a.shape[0], a.shape[1], b.shape[1],
                                dtype_bytes=a.dtype.itemsize)
        return matmul_cc(a, b, plan=plan)
    return jnp.dot(a, b)


def _check_div(name: str, dim: int, n: int, over: str = "ring axis") -> None:
    if dim % n != 0:
        raise ValueError(
            f"{name}={dim} must divide evenly over the {n}-way {over}")


def _lead_spec(batch_axes: Tuple[str, ...]):
    if not batch_axes:
        return None
    return batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)


def _batch_extent(mesh: Mesh, batch_axes: Tuple[str, ...]) -> int:
    sizes = dict(mesh.shape)
    return math.prod(sizes.get(a, 1) for a in batch_axes) if batch_axes else 1


# ---------------------------------------------------------------------------
# All-gather matmul
# ---------------------------------------------------------------------------


def make_ag_matmul(mesh: Mesh, axis: str = "model", mode: str = "ring",
                   batch_axes: Tuple[str, ...] = ()):
    """All-gather matmul ``y = x @ w`` with x sharded on k and w on n
    (DESIGN.md §5).

    Ring mode: at step s each chip holds the k-chunk originally owned by
    chip ``(i - s) mod p``, multiplies it against the matching row band of
    its w shard, and forwards it -- the permute of step s overlaps the dot
    of step s (the all-gather never materializes the full x).

    Serpentine mode: each chip's k-chunk is split in half; the low half
    streams forward, the high half backward, and each step computes two
    half-chunk dots against the matching w row bands.  Both ICI directions
    carry traffic every step, so the per-link bytes halve (requires an even
    per-chip chunk, ``k % 2p == 0``).

    ``batch_axes`` names the mesh axes the leading (m) dim of x stays
    sharded over across the ring -- the batch/data axes of the active rules
    -- so routing a model projection through here never gathers the batch.
    """
    p = dict(mesh.shape)[axis]
    plan = plan_ring(p, mode)
    d = _batch_extent(mesh, batch_axes)
    lead = _lead_spec(batch_axes)

    def ag_local(x_blk: jax.Array, w_blk: jax.Array) -> jax.Array:
        # x_blk: (m_local, k/p) -- my k-chunk; w_blk: (k, n/p) -- my n cols.
        m, kb = x_blk.shape
        nb = w_blk.shape[1]
        idx = jax.lax.axis_index(axis)
        acc0 = jnp.zeros((m, nb), jnp.promote_types(x_blk.dtype, w_blk.dtype))

        def rows_for(src, col0, width):
            # Row band of w matching columns [col0, col0+width) of the chunk
            # owned by chip ``src``.
            return jax.lax.dynamic_slice(
                w_blk, (src * kb + col0, 0), (width, nb))

        if not plan.bidirectional:
            offs = jnp.asarray(plan.fwd_offsets, jnp.int32)

            def step(carry, off):
                chunk, acc = carry
                src = (idx - off) % p
                acc = acc + _block_matmul(chunk, rows_for(src, 0, kb))
                chunk = jax.lax.ppermute(chunk, axis, plan.fwd_perm)
                return (chunk, acc), None

            (chunk, acc), _ = jax.lax.scan(step, (x_blk, acc0), offs[:-1])
            src = (idx - offs[-1]) % p
            return acc + _block_matmul(chunk, rows_for(src, 0, kb))

        half = kb // 2
        f_offs = jnp.asarray(plan.fwd_offsets, jnp.int32)
        b_offs = jnp.asarray(plan.bwd_offsets, jnp.int32)

        def compute(lo, hi, acc, off_f, off_b):
            src_f = (idx - off_f) % p
            src_b = (idx - off_b) % p
            acc = acc + _block_matmul(lo, rows_for(src_f, 0, half))
            return acc + _block_matmul(hi, rows_for(src_b, half, kb - half))

        def step(carry, offs_s):
            lo, hi, acc = carry
            off_f, off_b = offs_s
            acc = compute(lo, hi, acc, off_f, off_b)
            lo = jax.lax.ppermute(lo, axis, plan.fwd_perm)
            hi = jax.lax.ppermute(hi, axis, plan.bwd_perm)
            return (lo, hi, acc), None

        (lo, hi, acc), _ = jax.lax.scan(
            step, (x_blk[:, :half], x_blk[:, half:], acc0),
            (f_offs[:-1], b_offs[:-1]))
        return compute(lo, hi, acc, f_offs[-1], b_offs[-1])

    sharded = jax.shard_map(
        ag_local, mesh=mesh,
        in_specs=(P(lead, axis), P(None, axis)),
        out_specs=P(lead, axis),
        check_vma=False,
    )

    @jax.jit
    def ag_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
        if x.shape[1] != w.shape[0]:
            # The ring slices w by dynamic_slice, which would clamp a
            # mismatched contraction dim into silent garbage.
            raise ValueError(f"contraction mismatch: x {x.shape} @ w {w.shape}")
        _check_div("k", x.shape[1], p)
        _check_div("n", w.shape[1], p)
        if d > 1:
            _check_div("m", x.shape[0], d,
                       f"batch axes {batch_axes!r}")
        if plan.bidirectional and (x.shape[1] // p) % 2 != 0:
            raise ValueError(
                f"serpentine all-gather needs an even per-chip k chunk to "
                f"split across both ICI directions: k={x.shape[1]} over the "
                f"{p}-way ring leaves kb={x.shape[1] // p} (odd); pad k to "
                f"a multiple of {2 * p} or use mode='ring'")
        return sharded(x, w)

    return ag_matmul


def make_ag_matmul_fused(mesh: Mesh, axis: str = "model", mode: str = "ring",
                         n_out: int = 2, batch_axes: Tuple[str, ...] = ()):
    """Fused all-gather matmuls: several column-parallel projections of the
    SAME input share one ring (first bullet of the ROADMAP overlap item).

    q/k/v (and the SwiGLU wg/wi) each used to issue an independent
    all-gather ring over the same ``x``: p-1 hops of the identical k-chunk
    per projection.  Here the chunk hops ONCE per ring step and every step
    multiplies it against the matching row band of *each* weight shard --
    ``n_out`` dots per hop, one stream of ``x`` per block.  Outputs are
    each n-sharded over ``axis``, exactly as the unfused kernels produce,
    and each ``y_i == x @ w_i`` globally (same per-column accumulation
    order, so the fusion is bitwise-identical to the unfused rings).

    Serpentine mode streams the two chunk halves in both ICI directions as
    in ``make_ag_matmul`` (``2 * n_out`` half-chunk dots per step).
    """
    p = dict(mesh.shape)[axis]
    plan = plan_ring(p, mode)
    d = _batch_extent(mesh, batch_axes)
    lead = _lead_spec(batch_axes)

    def ag_local(x_blk: jax.Array, *w_blks: jax.Array):
        m, kb = x_blk.shape
        idx = jax.lax.axis_index(axis)
        accs = tuple(
            jnp.zeros((m, w.shape[1]),
                      jnp.promote_types(x_blk.dtype, w.dtype))
            for w in w_blks)

        def rows_for(w_blk, src, col0, width):
            return jax.lax.dynamic_slice(
                w_blk, (src * kb + col0, 0), (width, w_blk.shape[1]))

        if not plan.bidirectional:
            offs = jnp.asarray(plan.fwd_offsets, jnp.int32)

            def compute(chunk, accs, off):
                src = (idx - off) % p
                return tuple(
                    acc + _block_matmul(chunk, rows_for(w, src, 0, kb))
                    for acc, w in zip(accs, w_blks))

            def step(carry, off):
                chunk, accs = carry
                accs = compute(chunk, accs, off)
                chunk = jax.lax.ppermute(chunk, axis, plan.fwd_perm)
                return (chunk, accs), None

            (chunk, accs), _ = jax.lax.scan(step, (x_blk, accs), offs[:-1])
            return compute(chunk, accs, offs[-1])

        half = kb // 2
        f_offs = jnp.asarray(plan.fwd_offsets, jnp.int32)
        b_offs = jnp.asarray(plan.bwd_offsets, jnp.int32)

        def compute(lo, hi, accs, off_f, off_b):
            src_f = (idx - off_f) % p
            src_b = (idx - off_b) % p
            return tuple(
                acc + _block_matmul(lo, rows_for(w, src_f, 0, half))
                + _block_matmul(hi, rows_for(w, src_b, half, kb - half))
                for acc, w in zip(accs, w_blks))

        def step(carry, offs_s):
            lo, hi, accs = carry
            off_f, off_b = offs_s
            accs = compute(lo, hi, accs, off_f, off_b)
            lo = jax.lax.ppermute(lo, axis, plan.fwd_perm)
            hi = jax.lax.ppermute(hi, axis, plan.bwd_perm)
            return (lo, hi, accs), None

        (lo, hi, accs), _ = jax.lax.scan(
            step, (x_blk[:, :half], x_blk[:, half:], accs),
            (f_offs[:-1], b_offs[:-1]))
        return compute(lo, hi, accs, f_offs[-1], b_offs[-1])

    sharded = jax.shard_map(
        ag_local, mesh=mesh,
        in_specs=(P(lead, axis),) + (P(None, axis),) * n_out,
        out_specs=tuple(P(lead, axis) for _ in range(n_out)),
        check_vma=False,
    )

    @jax.jit
    def ag_matmul_fused(x: jax.Array, *ws: jax.Array):
        if len(ws) != n_out:
            raise ValueError(f"expected {n_out} weights, got {len(ws)}")
        for w in ws:
            if x.shape[1] != w.shape[0]:
                raise ValueError(
                    f"contraction mismatch: x {x.shape} @ w {w.shape}")
            _check_div("n", w.shape[1], p)
        _check_div("k", x.shape[1], p)
        if d > 1:
            _check_div("m", x.shape[0], d, f"batch axes {batch_axes!r}")
        if plan.bidirectional and (x.shape[1] // p) % 2 != 0:
            raise ValueError(
                f"serpentine all-gather needs an even per-chip k chunk: "
                f"k={x.shape[1]} over the {p}-way ring leaves "
                f"kb={x.shape[1] // p} (odd); pad k to a multiple of "
                f"{2 * p} or use mode='ring'")
        return sharded(x, *ws)

    return ag_matmul_fused


# ---------------------------------------------------------------------------
# Reduce-scatter matmul
# ---------------------------------------------------------------------------


def make_rs_matmul(mesh: Mesh, axis: str = "model", mode: str = "ring",
                   batch_axes: Tuple[str, ...] = ()):
    """Reduce-scatter matmul ``y = x @ w`` with x and w sharded on k
    (DESIGN.md §5).

    Ring mode: each output row block's partial-sum accumulator travels the
    ring once, visiting every chip; chip i computes row block
    ``(i + p-1 - s) mod p`` of its local partial product at step s, so the
    accumulator it forwards is always the one its successor must extend
    (the reduce-scatter is the ring itself -- no (m, n) intermediate is
    ever materialized).

    Serpentine mode: the output columns are split in half; the low-column
    accumulators ride the forward ring, the high-column ones the backward
    ring, so both ICI directions carry half-width accumulators every step
    (requires an even n).

    ``batch_axes`` keeps the leading (m) dim sharded over the batch/data
    axes across the ring, as in ``make_ag_matmul``.
    """
    p = dict(mesh.shape)[axis]
    plan = plan_ring(p, mode)
    d = _batch_extent(mesh, batch_axes)
    lead = _lead_spec(batch_axes)
    out_axes = tuple(batch_axes) + (axis,)
    out_lead = out_axes[0] if len(out_axes) == 1 else out_axes

    def rs_local(x_blk: jax.Array, w_blk: jax.Array) -> jax.Array:
        # x_blk: (m_local, k/p) -- my k columns; w_blk: (k/p, n) -- my rows.
        m, kb = x_blk.shape
        n = w_blk.shape[1]
        mb = m // p
        idx = jax.lax.axis_index(axis)
        out_dtype = jnp.promote_types(x_blk.dtype, w_blk.dtype)

        def rows(r):
            return jax.lax.dynamic_slice(x_blk, (r * mb, 0), (mb, kb))

        if not plan.bidirectional:
            offs = jnp.asarray(plan.fwd_offsets, jnp.int32)

            def partial(off):
                r = (idx + (p - 1) - off) % p
                return _block_matmul(rows(r), w_blk).astype(out_dtype)

            def step(acc, off):
                return jax.lax.ppermute(acc + partial(off), axis,
                                        plan.fwd_perm), None

            acc, _ = jax.lax.scan(step, jnp.zeros((mb, n), out_dtype),
                                  offs[:-1])
            return acc + partial(offs[-1])

        half = n // 2
        w_lo, w_hi = w_blk[:, :half], w_blk[:, half:]
        f_offs = jnp.asarray(plan.fwd_offsets, jnp.int32)
        b_offs = jnp.asarray(plan.bwd_offsets, jnp.int32)

        def partials(off_f, off_b):
            r_f = (idx + (p - 1) - off_f) % p
            s_b = (p - off_b) % p        # steps the backward stream has taken
            r_b = (idx - (p - 1) + s_b) % p
            return (_block_matmul(rows(r_f), w_lo).astype(out_dtype),
                    _block_matmul(rows(r_b), w_hi).astype(out_dtype))

        def step(carry, offs_s):
            acc_f, acc_b = carry
            off_f, off_b = offs_s
            pf, pb = partials(off_f, off_b)
            acc_f = jax.lax.ppermute(acc_f + pf, axis, plan.fwd_perm)
            acc_b = jax.lax.ppermute(acc_b + pb, axis, plan.bwd_perm)
            return (acc_f, acc_b), None

        (acc_f, acc_b), _ = jax.lax.scan(
            step,
            (jnp.zeros((mb, half), out_dtype),
             jnp.zeros((mb, n - half), out_dtype)),
            (f_offs[:-1], b_offs[:-1]))
        pf, pb = partials(f_offs[-1], b_offs[-1])
        return jnp.concatenate([acc_f + pf, acc_b + pb], axis=1)

    sharded = jax.shard_map(
        rs_local, mesh=mesh,
        in_specs=(P(lead, axis), P(axis, None)),
        out_specs=P(out_lead, None),
        check_vma=False,
    )

    @jax.jit
    def rs_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
        if x.shape[1] != w.shape[0]:
            raise ValueError(f"contraction mismatch: x {x.shape} @ w {w.shape}")
        _check_div("k", x.shape[1], p)
        _check_div("m", x.shape[0], d * p,
                   f"ring axis x batch axes {batch_axes!r}" if d > 1
                   else "ring axis")
        if plan.bidirectional and w.shape[1] % 2 != 0:
            raise ValueError(
                f"serpentine reduce-scatter needs an even n to split the "
                f"output columns across both ICI directions: n={w.shape[1]} "
                f"(odd); pad n or use mode='ring'")
        return sharded(x, w)

    return rs_matmul


# ---------------------------------------------------------------------------
# Dispatch (models/layers.py -> overlap layer)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=64)
def ring_kernel(mesh: Mesh, axis: str, kind: str, mode: str,
                batch_axes: Tuple[str, ...] = ()) -> Callable:
    """Memoized kernel factory (DESIGN.md §5): one shard_map/jit build per
    (mesh, axis, kind, mode, batch_axes) -- the model forward asks for a
    kernel once per projection per trace, so the factory must not rebuild
    (and the LRU bound evicts kernels of meshes long gone, e.g. across
    elastic restarts).  ``kind`` is "ag" (all-gather), "rs"
    (reduce-scatter), or "agf<N>" (N-output fused all-gather)."""
    if kind.startswith("agf"):
        return make_ag_matmul_fused(mesh, axis=axis, mode=mode,
                                    n_out=int(kind[3:]),
                                    batch_axes=batch_axes)
    make = make_ag_matmul if kind == "ag" else make_rs_matmul
    return make(mesh, axis=axis, mode=mode, batch_axes=batch_axes)


def overlap_matmul(x: jax.Array, w: jax.Array,
                   parallel: str) -> Optional[jax.Array]:
    """Route a ``(..., k) @ (k, n)`` projection through the ring kernels
    when the active sharding rules request ring/serpentine collectives
    (DESIGN.md §5).

    ``parallel`` is the weight's tensor-parallel orientation under the
    rules: "column" (n sharded over the TP axis -> all-gather ring) or
    "row" (k sharded over the TP axis -> reduce-scatter ring).  Returns
    None when the caller should fall back to a plain einsum: no active
    overlap context (``dist.sharding.active_overlap``), TP axis of size 1,
    or shapes that do not divide the ring -- mirroring the per-tensor
    divisibility guards GSPMD rules apply in ``dist.sharding``.
    """
    from repro.dist.sharding import active_overlap

    ctx = active_overlap()
    if ctx is None:
        return None
    mesh, axis, mode, batch_axes = ctx
    p = dict(mesh.shape).get(axis, 1)
    if p <= 1:
        return None
    lead, k = x.shape[:-1], x.shape[-1]
    n = w.shape[-1]
    m = math.prod(lead) if lead else 1
    d = _batch_extent(mesh, batch_axes)
    if k != w.shape[0] or k % p or m % d:
        return None
    serp = mode == "serpentine"
    if parallel == "column":
        if n % p or (serp and (k // p) % 2):
            return None
        kind = "ag"
    elif parallel == "row":
        if m % (d * p) or (serp and n % 2):
            return None
        kind = "rs"
    else:
        raise ValueError(f"parallel must be 'column' or 'row', got {parallel!r}")
    y = ring_kernel(mesh, axis, kind, mode, batch_axes)(x.reshape(m, k), w)
    return y.reshape(*lead, n)


def overlap_matmul_fused(x: jax.Array,
                         ws: Sequence[jax.Array]) -> Optional[list]:
    """Route several column-parallel projections of the same ``x`` through
    ONE all-gather ring (``make_ag_matmul_fused``): the q/k/v and SwiGLU
    fusion ``models/layers.py`` asks for.  Returns the list of outputs, or
    None when the caller should fall back to per-weight ``tp_matmul`` --
    no active overlap context, a degenerate ring, or any shape that does
    not divide it (the same guards as ``overlap_matmul``, applied to every
    weight)."""
    from repro.dist.sharding import active_overlap

    ctx = active_overlap()
    if ctx is None or len(ws) < 2:
        return None
    mesh, axis, mode, batch_axes = ctx
    p = dict(mesh.shape).get(axis, 1)
    if p <= 1:
        return None
    lead, k = x.shape[:-1], x.shape[-1]
    m = math.prod(lead) if lead else 1
    d = _batch_extent(mesh, batch_axes)
    if k % p or m % d or (mode == "serpentine" and (k // p) % 2):
        return None
    if any(w.shape[0] != k or w.shape[-1] % p for w in ws):
        return None
    fn = ring_kernel(mesh, axis, f"agf{len(ws)}", mode, batch_axes)
    ys = fn(x.reshape(m, k), *ws)
    return [y.reshape(*lead, w.shape[-1]) for y, w in zip(ys, ws)]

"""``repro.plan`` -- one recursive planner for the whole memory hierarchy.

The paper's contribution is a *run-time system* that decomposes a data
parallel computation against the memory hierarchy.  This module is the
single entry point that realizes it end to end: ``plan_run`` walks a
``MemoryLevel`` tree from the outermost level inward and runs the paper's
Algorithm-1 / §2.1.1 search once **per level**, with that level's phi:

  ============  ==============  =====================================
  level         phi             TCL (budget) of the search
  ============  ==============  =====================================
  DCN           ``phi_mesh``    one host's ICI domain (all its HBMs)
  ICI           ``phi_mesh``    one chip's HBM
  VMEM          ``phi_tpu``     the chip's usable VMEM (tile search)
  L3/L2/L1      ``phi_simple``  the cache's per-core share
                / ``phi_c``
  ============  ==============  =====================================

Each level's chosen ``np`` threads *down* as the next level's worker count
(the search lower bound): the partition count is a single global quantity
the walk refines level by level -- the paper's nested decomposition,
realized as one API.  At interconnect (mesh) levels the raw ``np*`` is
additionally *quantized* to the smallest mesh-axis divisor >= ``np*``
(ROADMAP: FSDP degree quantization); both values are recorded in the
sub-plan.

The result is a ``HierarchicalPlan``: a serializable (``to_json`` /
``from_json``) tree of per-level ``LevelPlan`` records that every consumer
reads instead of re-planning -- ``dist.sharding`` derives the FSDP degree
from the ICI sub-plan, ``dist.pipeline`` maps stages onto the DCN sub-plan,
``dist.overlap`` / ``kernels.matmul_cc`` pull their ``MatmulTilePlan`` from
the VMEM leaf, and ``benchmarks/run.py --only plan`` / ``launch/dryrun.py``
print the full tree.

The legacy entry points (``dist.sharding.mesh_decomposition``,
``core.autotile.plan_matmul``, ``core.decompose.Decomposer.decompose``) are
thin wrappers over single-level ``plan_run`` calls.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Any, Dict, Iterator, List, Mapping, Optional, Sequence, Tuple

from repro.core.autotile import MatmulTilePlan
from repro.core.decompose import (
    NoValidDecomposition,
    PhiFn,
    _next_structurally_valid,
    find_optimal_np,
    make_phi_mesh,
    phi_simple,
    validate_np,
)
from repro.core.distribution import (
    Array1DDistribution,
    Distribution,
    ReplicatedDistribution,
)
from repro.core.hierarchy import MemoryLevel

__all__ = [
    "MESH_LEVEL_NAMES",
    "PAGE_ALIGN",
    "PAGE_BUFFERING",
    "PAGE_LEVEL_NAMES",
    "HierarchicalPlan",
    "LevelPlan",
    "PlanError",
    "PlanPolicy",
    "Workload",
    "leaf_matmul_plan",
    "page_vmem_bytes",
    "plan_run",
    "quantize_divisor",
]


class PlanError(RuntimeError):
    """A structurally inadmissible plan for the caller's context (e.g. a
    decode plan with a DCN level handed to a single-replica engine).
    Carries the offending level name and the full plan so callers can
    report or re-plan instead of string-matching an assert message."""

    def __init__(self, message: str, *, level: Optional[str] = None,
                 plan: Optional["HierarchicalPlan"] = None):
        super().__init__(message)
        self.level = level
        self.plan = plan

#: Interconnect level names: the level *below* holds the copies the search
#: partitions against (per-host ICI domains under DCN, per-chip HBMs under
#: ICI), so the budget is one child copy and np quantizes to its extent.
MESH_LEVEL_NAMES = ("DCN", "ICI")

#: Fallback sharding granule: one (sublane x lane) f32 register tile.
DEFAULT_GRANULE = 8 * 128 * 4

#: Levels whose leaf budget a decode KV *page* is fit against (the TPU
#: scratchpad, or the per-core L2 share on the CPU path).
PAGE_LEVEL_NAMES = ("VMEM", "L2")

#: KV pages are sized in whole sublane groups of tokens: the cache's
#: sequence dim is the second-minor dim of each (page_tokens, head_dim)
#: register tile, so a page that is not a sublane multiple pads up anyway.
PAGE_ALIGN = 8

#: Streaming pages are double-buffered (the next page's DMA overlaps the
#: current page's attention math), so two pages are resident at once.
PAGE_BUFFERING = 2


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def page_vmem_bytes(page_tokens: int, n_kv: int, head_dim: int,
                    q_heads: int, dtype_bytes: int, lane: int = 128) -> int:
    """VMEM that ``kernels.paged_attention`` holds to stream one page of
    ``page_tokens`` tokens -- the page level's phi.

    Counted in Mosaic's tiled layout, not in raw KV bytes: a K or V block
    is ``(page_tokens, n_kv, head_dim)`` with ``head_dim`` on the lanes
    (the pool pads it to whole lane rows) and the KV heads on the
    sublanes (padded first to 8 by the kernel itself, then to the dtype's
    sublane granule), and both are double-buffered.  The head-batched
    QK^T and PV contractions add one relaid copy of the K and V blocks and
    two f32 ``(q_heads, page_tokens, lanes)`` products (both terms fitted
    to what the v5e compiler allocates), plus the per-slot query/output
    blocks, running max/sum/accumulator scratch and ``(kv, group, page)``
    f32 score temporaries.  Checked against the compiler's own VMEM
    accounting in ``tests/test_chip_compile.py``."""
    sub = max(8, (4 // max(1, dtype_bytes)) * 8)
    kv = _round_up(max(1, n_kv), 8)
    g = max(1, -(-max(1, q_heads) // max(1, n_kv)))
    h = kv * g
    lanes = _round_up(head_dim, lane)
    kv_token = _round_up(kv, sub) * lanes * dtype_bytes
    pages = PAGE_BUFFERING * 2 * page_tokens * kv_token
    relaid = 2 * page_tokens * kv_token
    products = 2 * max(1, q_heads) * page_tokens * lanes * 4
    q_out = PAGE_BUFFERING * (kv * _round_up(g, sub) + _round_up(h, sub)) \
        * lanes * dtype_bytes
    scratch = 3 * _round_up(h, 8) * lanes * 4
    scores = 4 * kv * _round_up(g, 8) * _round_up(page_tokens, lane) * 4
    return pages + relaid + products + q_out + scratch + scores


# ---------------------------------------------------------------------------
# Inputs: what to plan (Workload) and how (PlanPolicy)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Workload:
    """What is being decomposed, one description for every level.

    ``state_bytes``/``replicated_bytes`` feed the interconnect levels (the
    shardable training/serving state and the per-copy pinned reserve --
    activations, non-shardable buffers).  ``matmul`` is the per-chip local
    ``C[m,n] = A[m,k] @ B[k,n]`` the VMEM level tiles.  ``domain`` is a
    paper-style ``Distribution`` composite for host-cache levels (the CPU
    path).  ``overhead`` is the ``phi_mesh`` transient-copy factor
    (gradient buckets, all-gather destinations -- ``ModelConfig.overhead``).

    The decode (serving) workload adds the KV-cache terms (``repro.serve``):
    ``kv_bytes_per_token`` is the *global* per-token KV footprint (bytes x
    heads x layers), ``kv_layers``/``kv_heads`` its layer count and
    shardable head extent (0: one latent read as both K and V, MLA),
    ``q_heads`` the query heads attending to it (0: as many as KV heads),
    ``max_tokens`` the per-sequence resident-token bound.  Mesh levels then choose the KV head sharding (recorded as
    ``detail["kv_shard"]``), and the ``PAGE_LEVEL_NAMES`` leaf runs the
    page search: partition one sequence's resident KV token range until
    one partition -- a *page* -- fits the leaf budget double-buffered.
    """

    state_bytes: int = 0
    replicated_bytes: int = 0
    matmul: Optional[Tuple[int, int, int]] = None
    dtype_bytes: int = 2
    overhead: float = 1.0
    domain: Optional[Tuple[Distribution, ...]] = None
    kv_bytes_per_token: int = 0
    kv_layers: int = 1
    kv_heads: int = 0
    q_heads: int = 0
    max_tokens: int = 0


@dataclass(frozen=True)
class PlanPolicy:
    """How to search.

    ``n_workers`` seeds the outermost level (1 allows full replication, the
    mesh default); ``max_np`` caps a level's partition count by name (e.g.
    the FSDP capacity of the data axes at "ICI"); ``quantize`` enables the
    divisor quantization at mesh levels; ``tcl`` restricts the host-cache
    search to one named level (the ``Decomposer`` wrapper -- other cache
    levels become pass-through containers); ``cache_phi`` is the footprint
    estimator for host-cache levels; ``spec`` carries the MXU/lane/sublane
    alignment constants for the VMEM tile search.
    """

    strategy: str = "cache_conscious"   # | "horizontal"
    n_workers: int = 1
    quantize: bool = True
    max_np: Mapping[str, int] = field(default_factory=dict)
    tcl: Optional[str] = None
    cache_phi: PhiFn = phi_simple
    order: str = "cc"
    vmem_fraction: float = 1.0
    spec: Optional[Any] = None          # hw.tpu.TPUSpec
    use_tuned: bool = True              # consult experiments/tuning.json
                                        # (precedence analytic < tuned)


# ---------------------------------------------------------------------------
# Outputs: one LevelPlan per level, folded into a HierarchicalPlan tree
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LevelPlan:
    """One level's share of the nested decomposition.

    ``np_raw`` is the Algorithm-1 result; ``np`` the realized count after
    divisor quantization (equal at non-mesh levels).  ``extent`` is the
    realizable cap (child copies at mesh levels, 0 = unbounded at cache
    levels).  ``detail`` is a JSON-safe, kind-specific payload (the tile
    plan fields at VMEM, shard bytes at mesh levels).
    """

    level: str
    kind: str                    # mesh | cache | tile | container | leaf
    phi: str = ""
    budget_bytes: int = 0
    granule_bytes: int = 0
    n_workers: int = 1
    extent: int = 1
    np_raw: int = 1
    np: int = 1
    partition_bytes: float = 0.0
    fits: bool = True
    detail: Mapping[str, Any] = field(default_factory=dict)

    @property
    def replicated(self) -> bool:
        return self.np_raw <= 1


_LEVEL_FIELDS = ("level", "kind", "phi", "budget_bytes", "granule_bytes",
                 "n_workers", "extent", "np_raw", "np", "partition_bytes",
                 "fits")


@dataclass(frozen=True)
class HierarchicalPlan:
    """Serializable tree of per-level sub-plans (outermost level first)."""

    plan: LevelPlan
    child: Optional["HierarchicalPlan"] = None

    # ------------------------------------------------------------- traversal
    def nodes(self) -> Iterator["HierarchicalPlan"]:
        node: Optional[HierarchicalPlan] = self
        while node is not None:
            yield node
            node = node.child

    def levels(self) -> List[LevelPlan]:
        return [n.plan for n in self.nodes()]

    def find(self, name: str) -> Optional["HierarchicalPlan"]:
        for n in self.nodes():
            if n.plan.level == name:
                return n
        return None

    def level(self, name: str) -> Optional[LevelPlan]:
        sub = self.find(name)
        return sub.plan if sub is not None else None

    def leaf(self) -> LevelPlan:
        node = self
        while node.child is not None:
            node = node.child
        return node.plan

    def tile_plan(self) -> Optional[MatmulTilePlan]:
        """The VMEM level's ``MatmulTilePlan`` (None if no tile level)."""
        for lp in self.levels():
            if lp.kind == "tile":
                return MatmulTilePlan(**lp.detail["tile"])
        return None

    def page_plan(self) -> Optional[Mapping[str, Any]]:
        """The decode workload's KV page record (None if no page level):
        ``{"page_tokens", "page_bytes", "tok_bytes", "kv_shard", ...}`` --
        the leaf ``repro.serve`` sizes its paged KV cache from."""
        for lp in self.levels():
            if lp.kind == "page":
                return lp.detail["page"]
        return None

    def page_table(self) -> Optional[Mapping[str, Any]]:
        """The page level's pool geometry (None if no page level):
        ``{"pages_per_slot", "pages_total", "slots_bound"}`` -- the bounds
        the paged engine's ``PagePool`` must respect.  ``pages_per_slot``
        caps one sequence (``ceil(max_tokens / page_tokens)``);
        ``pages_total`` is how many *logical* pages (global token-bytes)
        the innermost mesh level's HBM leftover can hold after the
        replicated reserve, accounting for KV replication over the
        unsharded part of the model axis (0 = no mesh level to bound it)."""
        for lp in self.levels():
            if lp.kind == "page":
                return lp.detail.get("page_table")
        return None

    def prefix_budget(self) -> Optional[int]:
        """The mesh-level HBM leftover, in the scheduler's LOGICAL bytes
        (global per-token KV x tokens), that the cross-request prefix
        cache may keep resident (None if no page level; see
        ``serve/prefix.py``).  Recorded by the page level as
        ``detail["page_table"]["prefix_budget_bytes"]``; plans serialized
        before the field existed fall back to the equivalent
        ``pages_total`` x global page bytes product."""
        ptab = self.page_table()
        if ptab is None:
            return None
        if "prefix_budget_bytes" in ptab:
            return int(ptab["prefix_budget_bytes"])
        page = self.page_plan() or {}
        global_page = (int(page.get("page_tokens", 0))
                       * int(page.get("tok_bytes", 0))
                       * int(page.get("layers", 1))
                       * int(page.get("kv_shard", 1)))
        return int(ptab.get("pages_total", 0)) * global_page

    def chunk_tokens(self) -> Optional[int]:
        """The prefill CHUNK length -- the page level's ``page_tokens``
        (None if no page level).  The page is, by construction, the
        VMEM-fitting double-buffered slice of one sequence's KV stream,
        so it is also the natural unit to decompose prefill *time* into:
        the engine cuts prompts into chunks of this many tokens and
        interleaves them with decode ticks."""
        page = self.page_plan()
        return int(page["page_tokens"]) if page else None

    def replicas(self) -> int:
        """The DCN level's realized partition count for a decode workload
        -- the number of serving replicas the fleet stands up (1 when the
        plan has no DCN level).  ``repro.cluster`` is the consumer: the
        DCN level places whole replicas (request-level data parallelism,
        ``detail["placement"] == "replicas"``), so the cluster's width is
        the planner's outermost decision, not a config file's."""
        dcn = self.level("DCN")
        if dcn is None:
            return 1
        return int(dcn.detail.get("replicas", dcn.np))

    def kv_shard(self) -> int:
        """The KV head sharding degree the innermost mesh level chose for a
        decode workload (1 when no mesh level carries one)."""
        shard = 1
        for lp in self.levels():
            if lp.kind == "mesh" and "kv_shard" in lp.detail:
                shard = int(lp.detail["kv_shard"])
        return shard

    # ------------------------------------------------------------------ JSON
    def to_dict(self) -> dict:
        d: Dict[str, Any] = {f: getattr(self.plan, f) for f in _LEVEL_FIELDS}
        d["detail"] = dict(self.plan.detail)
        d["child"] = self.child.to_dict() if self.child is not None else None
        return d

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_dict(cls, d: dict) -> "HierarchicalPlan":
        kw = {f: d[f] for f in _LEVEL_FIELDS}
        child = d.get("child")
        return cls(
            plan=LevelPlan(detail=dict(d.get("detail") or {}), **kw),
            child=cls.from_dict(child) if child else None,
        )

    @classmethod
    def from_json(cls, s: str) -> "HierarchicalPlan":
        return cls.from_dict(json.loads(s))

    # --------------------------------------------------------------- display
    def describe(self) -> List[str]:
        """One printable line per level, indented by depth (the tree the
        CI dry plan and ``benchmarks/run.py --only plan`` print)."""
        lines = []
        for depth, lp in enumerate(self.levels()):
            ind = "  " * depth
            if lp.kind == "mesh":
                lines.append(
                    f"{ind}{lp.level}[mesh] np_raw={lp.np_raw} "
                    f"quantized={lp.np} extent={lp.extent} "
                    f"workers={lp.n_workers} budget={_fmt(lp.budget_bytes)} "
                    f"shard={_fmt(int(lp.detail.get('shard_bytes', 0)))} "
                    f"fits={lp.fits} phi={lp.phi}")
            elif lp.kind == "tile":
                t = lp.detail["tile"]
                lines.append(
                    f"{ind}{lp.level}[tile] block={t['bm']}x{t['bk']}x"
                    f"{t['bn']} np={lp.np} workers={lp.n_workers} "
                    f"vmem={_fmt(t['est_vmem_bytes'])}/"
                    f"{_fmt(lp.budget_bytes)} order={t['order']} "
                    f"fits={lp.fits} phi={lp.phi} "
                    f"src={t.get('source', 'analytic')}")
            elif lp.kind == "page":
                pg = lp.detail["page"]
                lines.append(
                    f"{ind}{lp.level}[page] page_tokens={pg['page_tokens']} "
                    f"page={_fmt(pg['page_bytes'])} x{pg['buffering']} "
                    f"vmem={_fmt(pg['vmem_bytes'])} "
                    f"kv_shard={pg['kv_shard']} np={lp.np} "
                    f"budget={_fmt(lp.budget_bytes)} fits={lp.fits} "
                    f"phi={lp.phi} src={pg.get('source', 'analytic')}")
            elif lp.kind == "cache":
                lines.append(
                    f"{ind}{lp.level}[cache] np={lp.np} "
                    f"workers={lp.n_workers} budget={_fmt(lp.budget_bytes)} "
                    f"part={_fmt(int(lp.partition_bytes))} fits={lp.fits} "
                    f"phi={lp.phi}")
            elif lp.kind == "leaf":
                lines.append(
                    f"{ind}{lp.level}[leaf] granule={lp.granule_bytes}B "
                    f"size={_fmt(lp.budget_bytes)}")
            else:
                lines.append(
                    f"{ind}{lp.level}[container] size={_fmt(lp.budget_bytes)}")
        return lines


def _fmt(b: float) -> str:
    for unit, s in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if b >= s:
            return f"{b / s:.1f}{unit}"
    return f"{int(b)}B"


# ---------------------------------------------------------------------------
# FSDP degree quantization (ROADMAP open item)
# ---------------------------------------------------------------------------


def quantize_divisor(np_raw: int, extent: int, multiple_of: int = 1) -> int:
    """Smallest divisor of ``extent`` >= ``np_raw`` (and a multiple of
    ``multiple_of``).

    A mesh axis can only realize shard counts that divide its extent
    (uneven shards force GSPMD's padded layouts); the legacy rules rounded
    any 1 < np* < extent all the way up to full-axis sharding.  The planner
    instead quantizes to the nearest realizable degree: np*=5 on an 8-chip
    axis -> 8, np*=5 on a 12-chip axis -> 6, and np*=3 on a 12-chip axis
    stays 3 -- collectives stay as cheap as the memory budget allows.

    ``multiple_of`` carries the level above's partition count: this level's
    partitions refine the outer ones only when the outer count divides the
    inner, otherwise a partition would straddle an outer-copy (host)
    boundary.  Falls back to ignoring the constraint when no such divisor
    exists (e.g. the cap cut the extent below it).
    """
    np_raw = max(1, np_raw)
    if extent <= 0:
        return np_raw
    multiple_of = max(1, multiple_of)
    for d in range(1, extent + 1):
        if extent % d == 0 and d >= np_raw and d % multiple_of == 0:
            return d
    if multiple_of > 1:
        return quantize_divisor(np_raw, extent, 1)
    return extent


# ---------------------------------------------------------------------------
# Per-kind level planners
# ---------------------------------------------------------------------------


def _granule_below(level: MemoryLevel) -> int:
    for lvl in level.levels():
        if lvl.cache_line_size is not None:
            return lvl.cache_line_size
    return DEFAULT_GRANULE


def _classify(level: MemoryLevel, workload: Workload,
              policy: PlanPolicy) -> str:
    if level.name in MESH_LEVEL_NAMES and level.child is not None:
        return "mesh"
    if level.name == "VMEM" and workload.matmul is not None:
        return "tile"
    if (workload.kv_bytes_per_token > 0 and workload.matmul is None
            and workload.domain is None and level.name in PAGE_LEVEL_NAMES):
        return "page"
    if workload.domain is not None:
        if policy.tcl is not None:
            if level.name == policy.tcl:
                return "cache"
        elif level.cache_line_size is not None and level.name != "VREG":
            return "cache"
    if level.child is None:
        return "leaf"
    return "container"


def _record_level(level: MemoryLevel, kind: str, n_workers: int) -> LevelPlan:
    return LevelPlan(
        level=level.name or kind,
        kind=kind,
        budget_bytes=level.per_core_size(),
        granule_bytes=level.cache_line_size or 0,
        n_workers=n_workers,
        extent=max(1, len(level.siblings)),
    )


def _plan_mesh_level(level: MemoryLevel, workload: Workload,
                     policy: PlanPolicy, n_workers: int) -> LevelPlan:
    """Algorithm 1 with one child copy as the TCL (HBM under ICI, a host's
    ICI domain under DCN) -- ``dist.sharding.mesh_decomposition`` run at an
    arbitrary interconnect level."""
    child = level.child
    budget = child.size
    granule = _granule_below(child)
    extent = max(1, len(child.siblings))
    cap = policy.max_np.get(level.name)
    if cap:
        extent = min(extent, max(1, cap))
    phi = make_phi_mesh(overhead=workload.overhead)
    if workload.kv_heads > 0 and level.name == "DCN":
        # Decode workload at the DCN level: the placement unit is a whole
        # REPLICA (request-level data parallelism), not a KV head slice --
        # heads shard over the ICI below, and DCN's hosts each hold a full
        # model copy plus one share of the fleet's resident KV stream.
        # ``state_bytes`` is one replica's shardable KV, so the fleet
        # demand is ``state * extent``; Algorithm 1 partitions it against
        # one host's ICI domain, seeded by the caller's requested replica
        # count (``PlanPolicy.n_workers``) -- memory pressure can only
        # RAISE the replica count, never shrink it below the request.
        fleet = [Array1DDistribution(
            length=max(1, workload.state_bytes) * extent, element_size=1)]
        if workload.replicated_bytes:
            fleet.append(ReplicatedDistribution(workload.replicated_bytes))
        try:
            np_raw = find_optimal_np(budget, granule, fleet, n_workers, phi,
                                     max_np=extent)
            fits = True
        except NoValidDecomposition:
            np_raw, fits = extent, False
        np_q = (quantize_divisor(np_raw, extent, multiple_of=n_workers)
                if policy.quantize else np_raw)
        part = sum(phi(granule, d, np_q) for d in fleet)
        return LevelPlan(
            level=level.name, kind="mesh", phi="phi_mesh",
            budget_bytes=budget, granule_bytes=granule,
            n_workers=max(1, n_workers), extent=extent,
            np_raw=np_raw, np=np_q, partition_bytes=part, fits=fits,
            detail={
                "tcl_level": child.name,
                "sharded_bytes": workload.state_bytes * extent,
                "replicated_bytes": workload.replicated_bytes,
                "shard_bytes": -(-max(1, workload.state_bytes) * extent
                                 // np_q),
                "overhead": workload.overhead,
                "placement": "replicas",
                "replicas": np_q,
            },
        )
    dists: List[Distribution] = [
        Array1DDistribution(length=max(1, workload.state_bytes),
                            element_size=1)
    ]
    if workload.replicated_bytes:
        dists.append(ReplicatedDistribution(workload.replicated_bytes))
    if policy.strategy == "horizontal":
        np_raw = min(extent, max(1, n_workers))
        fits = validate_np(budget, granule, dists, np_raw, phi) == 1
    else:
        try:
            np_raw = find_optimal_np(budget, granule, dists, n_workers, phi,
                                     max_np=extent)
            fits = True
        except NoValidDecomposition:
            np_raw, fits = extent, False
    # Quantize to a realizable divisor that is also a multiple of the level
    # above's partition count (n_workers) -- inner partitions must refine
    # the outer ones, never straddle a host boundary.
    #
    # A decode workload (kv_heads > 0) partitions the KV cache over its
    # heads instead: the only degrees one mesh axis realizes for a cache
    # tensor are "unsharded" and "the whole axis" (GSPMD NamedSharding --
    # sub-axis sharding is the same open ROADMAP item as FSDP sub-axis
    # degrees), and the head count must divide evenly, so the shard degree
    # snaps to the axis extent when the heads fill it and to 1 otherwise.
    if workload.kv_heads > 0:
        head_extent = (extent if extent > 1
                       and workload.kv_heads % extent == 0 else 1)
        np_q = (head_extent if (np_raw > 1 and policy.quantize
                                and head_extent > 1)
                else (1 if policy.quantize else np_raw))
        if np_q < np_raw:
            fits = validate_np(budget, granule, dists, np_q, phi) == 1
    else:
        np_q = (quantize_divisor(np_raw, extent, multiple_of=n_workers)
                if policy.quantize else np_raw)
    part = sum(phi(granule, d, np_q) for d in dists)
    shard = -(-max(1, workload.state_bytes) // np_q)
    detail: Dict[str, Any] = {
        "tcl_level": child.name,
        "sharded_bytes": workload.state_bytes,
        "replicated_bytes": workload.replicated_bytes,
        "shard_bytes": shard,
        "overhead": workload.overhead,
    }
    if workload.kv_heads > 0:
        detail["kv_heads"] = workload.kv_heads
        detail["kv_shard"] = np_q
    return LevelPlan(
        level=level.name, kind="mesh", phi="phi_mesh",
        budget_bytes=budget, granule_bytes=granule,
        n_workers=max(1, n_workers), extent=extent,
        np_raw=np_raw, np=np_q, partition_bytes=part, fits=fits,
        detail=detail,
    )


def _plan_tile_level(level: MemoryLevel, workload: Workload,
                     policy: PlanPolicy, n_workers: int) -> LevelPlan:
    """The chip-level tile search (``core.autotile``) as one plan level."""
    from repro.core import autotile

    spec = policy.spec or _default_spec()
    m, k, n = workload.matmul
    budget = int(level.per_core_size() * policy.vmem_fraction)
    tuning = None
    if policy.strategy == "horizontal":
        tile = autotile.plan_matmul_horizontal(
            m, k, n, dtype_bytes=workload.dtype_bytes,
            n_workers=n_workers, spec=spec)
    else:
        tile = autotile._search_matmul_tiles(
            m, k, n, workload.dtype_bytes, spec, policy.order,
            n_workers, budget)
        if policy.use_tuned:
            tile, tuning = autotile.apply_tuned_matmul(
                tile, workload.dtype_bytes, spec, budget)
    detail: Dict[str, Any] = {"tile": {f: getattr(tile, f) for f in (
        "m", "k", "n", "bm", "bk", "bn", "order", "np",
        "est_vmem_bytes", "strategy", "source")}}
    if tuning is not None:
        detail["tuning"] = tuning
    return LevelPlan(
        level=level.name, kind="tile", phi="phi_tpu",
        budget_bytes=budget,
        granule_bytes=level.cache_line_size or DEFAULT_GRANULE,
        n_workers=max(1, n_workers), extent=max(1, tile.n_tasks),
        np_raw=tile.np, np=tile.np,
        partition_bytes=float(tile.est_vmem_bytes),
        fits=tile.est_vmem_bytes <= budget,
        detail=detail,
    )


def _plan_page_level(level: MemoryLevel, workload: Workload,
                     policy: PlanPolicy, n_workers: int,
                     kv_shard: int = 1,
                     mesh_budget_bytes: int = 0) -> LevelPlan:
    """The decode KV page search (``repro.serve``): Algorithm 1 over one
    sequence's resident token range.

    The streamed working set of one decode attention step is one layer's
    KV slice of one sequence after head sharding, so the domain element is
    ``kv_bytes_per_token / (kv_layers * kv_shard)`` bytes and the search
    partitions ``max_tokens`` of them until one partition -- a *page*,
    sublane-aligned -- fits the leaf budget as the kernel lays it out in
    VMEM (``page_vmem_bytes``: tiled, double-buffered, with the kernel's
    temporaries).  The smallest np that fits gives the largest page, i.e.
    the fewest page-boundary crossings per token, exactly the paper's
    "largest partition that still fits the TCL" optimality argument.  The
    budget is also the ``vmem_limit_bytes`` the kernel compiles under
    (``detail["page"]["vmem_limit_bytes"]``).
    """
    budget = int(level.per_core_size() * policy.vmem_fraction)
    granule = level.cache_line_size or DEFAULT_GRANULE
    layers = max(1, workload.kv_layers)
    shard = max(1, kv_shard)
    tok_bytes = max(1, -(-workload.kv_bytes_per_token // (layers * shard)))
    tokens = max(PAGE_ALIGN, workload.max_tokens)
    dist = Array1DDistribution(length=tokens, element_size=tok_bytes)
    geometry = _page_geometry(workload, tok_bytes, shard)

    def phi_page(_line: int, d: Distribution, np_: int) -> float:
        toks = -(-math.ceil(d.get_average_partition_size(np_))
                 // PAGE_ALIGN) * PAGE_ALIGN
        return float(page_vmem_bytes(toks, *geometry))

    try:
        # The mesh partitioning was already consumed by the per-shard
        # element size (``/ kv_shard``): the page search covers ONE
        # sequence's per-chip stream, so it starts at a single partition
        # rather than inheriting the mesh np as a lower bound -- a
        # per-shard slice that fits whole gets exactly one page.
        np_raw = find_optimal_np(budget, granule, [dist], 1,
                                 phi_page, max_np=tokens)
        fits = True
    except NoValidDecomposition:
        # Even a single sublane group of tokens overflows the leaf: page at
        # the alignment floor and record the miss.
        np_raw, fits = -(-tokens // PAGE_ALIGN), False
    per_partition = -(-tokens // np_raw)
    page_tokens = -(-per_partition // PAGE_ALIGN) * PAGE_ALIGN
    source = "analytic"
    tuning = None
    if policy.use_tuned and fits:
        tuned_pt, tuning = _tuned_page_tokens(policy, tok_bytes, tokens,
                                              budget, geometry)
        if tuned_pt is not None:
            page_tokens, source = tuned_pt, "tuned"
    page_bytes = page_tokens * tok_bytes
    vmem_bytes = page_vmem_bytes(page_tokens, *geometry)
    n_pages = -(-tokens // page_tokens)
    # Pool geometry (the paged engine's bounds, DESIGN.md §8): one logical
    # page costs ``page_tokens x kv_bytes_per_token`` GLOBAL bytes; the
    # innermost mesh level's per-chip HBM leftover after the replicated
    # reserve holds ``free x kv_shard`` logical bytes per data shard (one
    # logical byte is stored once per model-axis replica group, i.e.
    # ``extent / kv_shard`` copies across the ``extent``-chip domain).
    global_page_bytes = page_tokens * max(1, workload.kv_bytes_per_token)
    per_chip_free = max(0, mesh_budget_bytes - workload.replicated_bytes)
    pages_total = (per_chip_free * max(1, kv_shard)) // global_page_bytes \
        if mesh_budget_bytes else 0
    return LevelPlan(
        level=level.name, kind="page", phi="phi_page",
        budget_bytes=budget, granule_bytes=granule,
        n_workers=max(1, n_workers), extent=n_pages,
        np_raw=np_raw, np=n_pages,
        partition_bytes=float(vmem_bytes), fits=fits,
        detail={"page": {
            "page_tokens": page_tokens,
            "page_bytes": page_bytes,
            "vmem_bytes": vmem_bytes,
            "vmem_limit_bytes": budget,
            "tok_bytes": tok_bytes,
            "tokens": tokens,
            "layers": layers,
            "kv_shard": max(1, kv_shard),
            "align": PAGE_ALIGN,
            "buffering": PAGE_BUFFERING,
            "source": source,
        }, "page_table": {
            "pages_per_slot": n_pages,
            "pages_total": int(pages_total),
            "slots_bound": int(pages_total // n_pages) if pages_total else 0,
            # The mesh-level HBM leftover in LOGICAL bytes (global token
            # bytes, like the scheduler's budget): what the prefix cache
            # (serve/prefix.py) may keep resident across requests.
            "prefix_budget_bytes": int(
                per_chip_free * max(1, kv_shard)) if mesh_budget_bytes
            else 0,
        }, **({"tuning": tuning} if tuning is not None else {})},
    )


def _page_geometry(workload: Workload, tok_bytes: int, kv_shard: int
                   ) -> Tuple[int, int, int, int]:
    """``(n_kv, head_dim, q_heads, dtype_bytes)`` of one chip's page as the
    paged kernel sees it (``page_vmem_bytes``'s shape arguments): heads
    sharded ``kv_shard`` ways; a head-less (MLA latent) cache is one KV
    "head" as wide as the whole per-token slice, read as both K and V."""
    dtype_bytes = max(1, workload.dtype_bytes)
    width = max(1, tok_bytes // dtype_bytes)
    if workload.kv_heads > 0:
        n_kv = max(1, workload.kv_heads // kv_shard)
        head_dim = max(1, width // (2 * n_kv))
    else:
        n_kv, head_dim = 1, width
    q_heads = (max(1, workload.q_heads // kv_shard) if workload.q_heads
               else n_kv)
    return n_kv, head_dim, q_heads, dtype_bytes


def _tuned_page_tokens(policy: PlanPolicy, tok_bytes: int, tokens: int,
                       budget: int, geometry: Tuple[int, int, int, int]
                       ) -> Tuple[Optional[int], Optional[dict]]:
    """A measured ``page_tokens`` winner for this decode shape, re-checked
    against the page level's own invariants (sublane alignment, the page's
    VMEM footprint within the leaf budget); ``(None, None)`` leaves the
    analytic page standing."""
    from repro.tune.cache import bucket_paged, lookup_tuned

    spec = policy.spec or _default_spec()
    entry = lookup_tuned("paged_attention", spec.name,
                         bucket_paged(tok_bytes, tokens))
    if entry is None:
        return None, None
    pt = entry.get("block", {}).get("page_tokens")
    if not (isinstance(pt, int) and pt >= PAGE_ALIGN
            and pt % PAGE_ALIGN == 0):
        return None, None
    pt = min(pt, -(-tokens // PAGE_ALIGN) * PAGE_ALIGN)
    if page_vmem_bytes(pt, *geometry) > budget:
        return None, None
    return pt, {
        "speedup": entry.get("speedup", 1.0),
        "median_us": entry.get("median_us", 0.0),
        "analytic_us": entry.get("analytic_us", 0.0),
        "analytic_block": entry.get("analytic_block", {}),
        "fingerprint": entry.get("fingerprint", ""),
    }


def _plan_cache_level(level: MemoryLevel, workload: Workload,
                      policy: PlanPolicy, n_workers: int) -> LevelPlan:
    """The paper's host-cache search (``Decomposer``) as one plan level."""
    dists = list(workload.domain)
    budget = level.per_core_size()
    line = level.cache_line_size or 64
    phi = policy.cache_phi
    if policy.strategy == "horizontal":
        np_raw = _next_structurally_valid(dists, max(1, n_workers), 1 << 30)
        if np_raw is None:
            raise NoValidDecomposition("horizontal: nWorkers not admissible")
        fits = validate_np(budget, line, dists, np_raw, phi) == 1
    else:
        np_raw = find_optimal_np(budget, line, dists, n_workers, phi)
        fits = True
    part = sum(phi(line, d, np_raw) for d in dists)
    return LevelPlan(
        level=level.name, kind="cache",
        phi=getattr(phi, "__name__", "phi"),
        budget_bytes=budget, granule_bytes=line,
        n_workers=max(1, n_workers), extent=0,
        np_raw=np_raw, np=np_raw, partition_bytes=part, fits=fits,
    )


def _default_spec():
    from repro.hw.tpu import chip_spec

    return chip_spec()


# ---------------------------------------------------------------------------
# The recursive walk
# ---------------------------------------------------------------------------


def plan_run(hierarchy: MemoryLevel, workload: Workload,
             policy: PlanPolicy = PlanPolicy()) -> HierarchicalPlan:
    """Decompose ``workload`` against the whole ``hierarchy``.

    Walks the level chain outermost-in.  At interconnect levels the search
    partitions state against one child copy; the child copy level itself
    (e.g. HBM under ICI) is consumed by that search, so the plan shows one
    node per *decision* -- ``DCN -> ICI/HBM -> VMEM -> VREG`` is a 4-level
    plan over a 5-level memory chain.  Each level's realized ``np`` threads
    down as the next level's worker count; crossing from the mesh into a
    chip divides it by the chip count (each chip's residual share of the
    global partitioning -- one partition -- seeds the tile search).
    """
    nodes: List[LevelPlan] = []
    np_thread = max(1, policy.n_workers)
    kv_shard = 1
    mesh_budget = 0
    level: Optional[MemoryLevel] = hierarchy
    while level is not None:
        kind = _classify(level, workload, policy)
        if kind == "mesh":
            node = _plan_mesh_level(level, workload, policy, np_thread)
            nodes.append(node)
            np_thread = node.np
            if node.detail.get("placement") == "replicas":
                # Replica placement partitions REQUESTS across the fleet,
                # not one request's state: each replica re-runs the inner
                # walk as a full single-host instance, so the fleet width
                # must not thread down as the next level's worker count.
                np_thread = 1
            if "kv_shard" in node.detail:
                kv_shard = int(node.detail["kv_shard"])
            mesh_budget = node.budget_bytes      # innermost mesh level wins
            nxt = level.child
            if nxt is not None and nxt.name not in MESH_LEVEL_NAMES:
                copies = max(1, len(nxt.siblings))   # the consumed TCL level
                np_thread = max(1, -(-np_thread // copies))
                nxt = nxt.child
            level = nxt
            continue
        if kind == "tile":
            node = _plan_tile_level(level, workload, policy, np_thread)
            nodes.append(node)
            np_thread = node.np
        elif kind == "page":
            node = _plan_page_level(level, workload, policy, np_thread,
                                    kv_shard, mesh_budget_bytes=mesh_budget)
            nodes.append(node)
            np_thread = node.np_raw
        elif kind == "cache":
            node = _plan_cache_level(level, workload, policy, np_thread)
            nodes.append(node)
            np_thread = node.np
        else:
            nodes.append(_record_level(level, kind, np_thread))
        level = level.child

    hp: Optional[HierarchicalPlan] = None
    for node in reversed(nodes):
        hp = HierarchicalPlan(plan=node, child=hp)
    return hp


# ---------------------------------------------------------------------------
# Cached leaf extraction (the overlap / kernel consumers)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=512)
def leaf_matmul_plan(
    m: int,
    k: int,
    n: int,
    dtype_bytes: int = 2,
    order: str = "cc",
    n_workers: int = 1,
    vmem_fraction: float = 1.0,
) -> MatmulTilePlan:
    """Memoized VMEM-leaf tile plan for a local ``(m, k) @ (k, n)`` block.

    ``dist.overlap``'s ring kernels and ``kernels.matmul_cc`` pull their
    ``MatmulTilePlan`` from here -- one single-chip ``plan_run`` per
    (shape, dtype), reused across every ring step and retrace (the planner
    successor of ``autotile.plan_matmul_cached``).
    """
    spec = _default_spec()
    hp = plan_run(
        spec.hierarchy(),
        Workload(matmul=(m, k, n), dtype_bytes=dtype_bytes),
        PlanPolicy(order=order, n_workers=n_workers,
                   vmem_fraction=vmem_fraction, spec=spec),
    )
    return hp.tile_plan()

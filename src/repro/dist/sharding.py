"""Logical-axis sharding rules, chosen by the paper's decomposer.

Model code names *logical* axes ("embed", "heads", "batch", ...); a
``ShardingRules`` table maps each logical axis to zero or more mesh axes.
The table itself is not hand-written per architecture: the mesh is treated
as the outermost level of the memory hierarchy (DESIGN.md §2) and the
FSDP / replicated choice for parameters is made by the paper's Algorithm 1
(``find_optimal_np`` with ``phi_mesh``) against the per-chip HBM budget of
``tpu_hierarchy(..., mesh_devices=N)``:

  * ``np* == 1``  -- one partition: the parameter+optimizer state fits each
    chip's HBM after tensor parallelism, so params replicate over the data
    axes (cheapest collectives -- the mesh analogue of "the whole domain
    fits the TCL").
  * ``np* > 1``   -- the state must be decomposed harder: params shard over
    the data axes (FSDP), exactly like the binary search relaxing np until
    the partition fits.

Tensor-parallel ("model" axis) rules are structural -- they follow from the
architecture's divisibilities (heads, experts, vocab) -- while the
memory-driven FSDP degree is the decomposer's call.  ``mesh_decomposition``
exposes the raw search result for tests and diagnostics.
"""

from __future__ import annotations

import threading
from math import prod
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.decompose import (
    NoValidDecomposition,
    find_optimal_np,
    make_phi_mesh,
)
from repro.core.distribution import Array1DDistribution, ReplicatedDistribution
from repro.core.hierarchy import MemoryLevel
from repro.core.plan import HierarchicalPlan, PlanPolicy, Workload, plan_run

AxisRule = Union[None, str, Tuple[str, ...]]
PyTree = Any

#: Resident bytes per parameter of the training state: fp32 master copy,
#: AdamW mu + nu (fp32 default), and the bf16 compute cast made each step.
TRAIN_STATE_BYTES_PER_PARAM = 4 + 4 + 4 + 2

#: Collective-matmul schedules the rules may request (DESIGN.md §5):
#: "gspmd" leaves collectives to XLA; "ring"/"serpentine" route the
#: tensor-parallel projections through ``dist.overlap``'s streaming matmuls.
COLLECTIVES = ("gspmd", "ring", "serpentine")


# ---------------------------------------------------------------------------
# Rules table
# ---------------------------------------------------------------------------


def _rule_axes(rule: AxisRule) -> Tuple[str, ...]:
    if rule is None:
        return ()
    if isinstance(rule, str):
        return (rule,)
    return tuple(rule)


def _build_spec(table: Dict[str, AxisRule],
                axes: Sequence[Optional[str]]) -> P:
    """PartitionSpec from logical axes via the table; a mesh axis is used at
    most once (first logical axis wins, matching GSPMD's constraint)."""
    used: set = set()
    entries = []
    for ax in axes:
        names = [n for n in _rule_axes(table.get(ax) if ax else None)
                 if n not in used]
        used.update(names)
        if not names:
            entries.append(None)
        elif len(names) == 1:
            entries.append(names[0])
        else:
            entries.append(tuple(names))
    return P(*entries)


@dataclass
class ShardingRules:
    """Logical-axis -> mesh-axis tables for parameters and activations.

    ``meta`` carries the decomposer's provenance (mesh np*, budget, fit);
    it is advisory and deliberately optional so callers may rebuild rules
    positionally (``ShardingRules(param_rules, act_rules)``).
    """

    param_rules: Dict[str, AxisRule]
    act_rules: Dict[str, AxisRule]
    meta: Dict[str, Any] = field(default_factory=dict)

    def param_spec(self, axes: Sequence[Optional[str]]) -> P:
        return _build_spec(self.param_rules, axes)

    def act_spec(self, axes: Sequence[Optional[str]]) -> P:
        return _build_spec(self.act_rules, axes)


# ---------------------------------------------------------------------------
# Mesh-level decomposition (Algorithm 1 at the outermost level)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshDecomposition:
    """Result of the mesh-level Algorithm 1 run."""

    np: int                    # smallest partition count that fits per-chip HBM
    budget_bytes: int          # TCL_PER_CORE: one chip's HBM
    granule_bytes: int         # sharding granule (the mesh "cache line")
    sharded_bytes: int         # state the search may partition
    replicated_bytes: int      # state pinned to every chip
    fits: bool                 # False: even the max realizable np overflows

    @property
    def replicated(self) -> bool:
        return self.np <= 1


def mesh_hierarchy(mesh, spec=None) -> MemoryLevel:
    """The mesh in the paper's schema: [DCN ->] ICI -> per-chip HBM -> VMEM
    -> VREG.  A mesh with a "pod" axis gets a DCN level above the ICI (one
    ICI domain per pod -- the hierarchical planner runs Algorithm 1 at both
    interconnect levels, DESIGN.md §6)."""
    from repro.hw.tpu import chip_spec

    hosts = dict(mesh.shape).get("pod", 1)
    return (spec or chip_spec()).hierarchy(
        mesh_devices=mesh.size // max(1, hosts), hosts=hosts)


def mesh_plan(
    mesh,
    *,
    state_bytes: int = 0,
    act_bytes: int = 0,
    hierarchy: Optional[MemoryLevel] = None,
    max_np: Optional[int] = None,
    overhead: float = 1.0,
    matmul: Optional[Tuple[int, int, int]] = None,
    dtype_bytes: int = 2,
    spec=None,
) -> HierarchicalPlan:
    """``plan_run`` over this mesh's memory hierarchy.

    The one planning call the distribution layer makes: the returned
    ``HierarchicalPlan`` carries the DCN sub-plan (``dist.pipeline`` stage
    count), the ICI sub-plan (FSDP degree, raw and divisor-quantized), and
    -- when ``matmul`` local shapes are given -- the VMEM tile leaf.
    ``max_np`` caps the ICI partition count (the FSDP capacity of the data
    axes); ``overhead`` is the per-arch ``phi_mesh`` transient-copy factor.
    """
    hierarchy = hierarchy or mesh_hierarchy(mesh, spec)
    caps = {"ICI": max_np} if max_np else {}
    return plan_run(
        hierarchy,
        Workload(state_bytes=state_bytes, replicated_bytes=act_bytes,
                 matmul=matmul, dtype_bytes=dtype_bytes, overhead=overhead),
        PlanPolicy(max_np=caps, spec=spec),
    )


def mesh_decomposition(
    hierarchy: MemoryLevel,
    sharded_bytes: int,
    replicated_bytes: int = 0,
    max_np: int = 1 << 16,
) -> MeshDecomposition:
    """Run Algorithm 1 with the per-chip HBM as the TCL.

    A thin wrapper over a single-level ``plan_run`` (``repro.plan``): the
    planner's ICI node runs exactly this search -- the shardable training
    state (a 1-D byte range) plus a replicated remainder, the smallest
    partition count whose per-chip footprint (``phi_mesh``) fits one HBM
    copy.  Returns the *raw* np (legacy contract; the planner's quantized
    degree lives in the sub-plan).  The search is bounded by the smaller of
    ``max_np`` and the hierarchy's chip count -- a shard count above the
    number of chips is not realizable, so when nothing fits the
    decomposition saturates at that bound with ``fits=False`` (shard as
    hard as the mesh allows).
    """
    hp = plan_run(
        hierarchy,
        Workload(state_bytes=sharded_bytes, replicated_bytes=replicated_bytes),
        PlanPolicy(max_np={"ICI": max_np, "DCN": max_np}, quantize=False),
    )
    lp = hp.level("ICI")
    if lp is None:
        # Hierarchy without an interconnect level: search it directly.
        hbm = hierarchy.find("HBM") or hierarchy
        budget = hbm.per_core_size()
        granule = hbm.cache_line_size or 8 * 128 * 4
        dists = [Array1DDistribution(length=max(1, sharded_bytes),
                                     element_size=1)]
        if replicated_bytes:
            dists.append(ReplicatedDistribution(replicated_bytes))
        try:
            np_ = find_optimal_np(budget, granule, dists, 1, make_phi_mesh(),
                                  max_np=max_np)
            fits = True
        except NoValidDecomposition:
            np_, fits = max_np, False
        return MeshDecomposition(
            np=np_, budget_bytes=budget, granule_bytes=granule,
            sharded_bytes=sharded_bytes, replicated_bytes=replicated_bytes,
            fits=fits,
        )
    return MeshDecomposition(
        np=lp.np_raw, budget_bytes=lp.budget_bytes,
        granule_bytes=lp.granule_bytes,
        sharded_bytes=sharded_bytes, replicated_bytes=replicated_bytes,
        fits=lp.fits,
    )


# ---------------------------------------------------------------------------
# Rule construction
# ---------------------------------------------------------------------------


def _axis_sizes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def _data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def default_rules(
    mesh,
    *,
    state_bytes: int = 0,
    act_bytes: int = 0,
    hierarchy: Optional[MemoryLevel] = None,
    seq_sharded: bool = False,
    overhead: float = 1.0,
    plan: Optional[HierarchicalPlan] = None,
) -> ShardingRules:
    """Architecture-independent rules: TP over "model" for the structural
    axes, batch over the data axes, and the FSDP / replicated choice made by
    the hierarchical planner (``repro.plan``) over ``state_bytes`` (0 bytes
    -> trivially fits -> replicated).  Pass ``plan`` to consume an existing
    ``HierarchicalPlan`` instead of re-planning; the plan (and its
    raw/quantized FSDP degrees) rides in ``meta`` either way."""
    sizes = _axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    data = _data_axes(mesh)
    fsdp_capacity = max(1, prod(sizes[a] for a in data))
    if plan is None:
        plan = mesh_plan(
            mesh,
            state_bytes=state_bytes // max(1, model_n),
            act_bytes=act_bytes,
            hierarchy=hierarchy,
            max_np=fsdp_capacity,
            overhead=overhead,
        )
    dec = plan.level("ICI") or plan.leaf()
    dcn = plan.level("DCN")
    embed_rule: AxisRule = None
    if not dec.replicated and data:
        embed_rule = data[0] if len(data) == 1 else data
    param_rules: Dict[str, AxisRule] = {
        "embed": embed_rule,
        "heads": "model",
        "mlp": "model",
        "mlp_expert": "model",
        "vocab": "model",
        "experts": None,
        "layers": None,
    }
    act_rules: Dict[str, AxisRule] = {
        "batch": data[0] if len(data) == 1 else (data or None),
        "seq": None,
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "kv_seq": "model" if seq_sharded else None,
        "mlp": "model",
        "experts": None,
        "state_heads": "model",
        "state_inner": None,
        "vocab": "model",
        "layers": None,
    }
    return ShardingRules(param_rules, act_rules, meta={
        "mesh_np": dec.np_raw,
        "fsdp_degree": dec.np,           # divisor-quantized (ROADMAP item)
        "mesh_budget_bytes": dec.budget_bytes,
        "mesh_fits": dec.fits,
        "fsdp": not dec.replicated,
        "fsdp_capacity": fsdp_capacity,
        "dcn_np": dcn.np if dcn is not None else 1,
        "plan": plan,
    })


def arch_rules(
    cfg: ModelConfig,
    mesh,
    seq_sharded: bool = False,
    hierarchy: Optional[MemoryLevel] = None,
    act_bytes: int = 0,
    state_bytes_per_param: int = TRAIN_STATE_BYTES_PER_PARAM,
    plan: Optional[HierarchicalPlan] = None,
) -> ShardingRules:
    """Rules for one architecture on one mesh.

    Structural (divisibility-driven) TP choices come from ``cfg``; the
    memory-driven FSDP degree comes from the hierarchical planner
    (``repro.plan``) run on this architecture's resident-state footprint
    with its ``cfg.overhead`` phi_mesh factor.  Pass ``hierarchy`` to
    decompose against a different machine (tests shrink the HBM budget to
    force the replicated -> FSDP flip); pass ``act_bytes`` to reserve
    per-chip HBM for activations (they do not shrink with the param np);
    pass ``state_bytes_per_param`` for non-training memory models (serving
    holds only the bf16 weights, no master copy or optimizer moments);
    pass ``plan`` to consume an existing ``HierarchicalPlan`` instead of
    re-planning.
    """
    sizes = _axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    state_bytes = cfg.param_count() * state_bytes_per_param
    rules = default_rules(
        mesh,
        state_bytes=state_bytes,
        act_bytes=act_bytes,
        hierarchy=hierarchy,
        seq_sharded=seq_sharded,
        overhead=cfg.overhead,
        plan=plan,
    )
    pr, ar = dict(rules.param_rules), dict(rules.act_rules)

    # Structural TP refinements: drop mesh axes the architecture cannot fill.
    if cfg.n_heads % model_n != 0:
        ar["heads"] = None
    if cfg.n_kv_heads % model_n != 0:
        ar["kv_heads"] = None
    if cfg.vocab_size % model_n != 0:
        pr["vocab"] = None
        ar["vocab"] = None
    if cfg.ssm is not None:
        n_state_heads = (cfg.ssm.expand * cfg.d_model) // max(1, cfg.ssm.head_dim)
        if n_state_heads % model_n != 0:
            ar["state_heads"] = None
    if cfg.xlstm is not None and cfg.n_heads % model_n != 0:
        # xLSTM state heads == n_heads (e.g. 4) -- far short of a 16-wide
        # model axis.  Sub-axis sharding: drop the head axis and shard the
        # per-head state inner dim instead (mLSTM's dh, sLSTM's d/H), iff
        # BOTH divide the axis -- one rule covers every state leaf, and
        # the matrix state C:(..., H, dh, dh) then splits on dim 3 where
        # it used to fail pjit's divisibility check on dim 2.
        from repro.models.xlstm import _round128

        ar["state_heads"] = None
        dh = _round128(cfg.xlstm.mlstm_proj_factor * cfg.d_model) \
            // max(1, cfg.n_heads)
        dhs = cfg.d_model // max(1, cfg.n_heads)
        if dh % model_n == 0 and dhs % model_n == 0:
            ar["state_inner"] = "model"
    if cfg.moe is not None:
        # Expert parallelism when the expert count fills the model axis
        # (dispatch stays shard-local per expert group); tensor-parallel
        # experts otherwise -- see models/moe.py for the measured rationale.
        if cfg.moe.n_experts % model_n == 0 and model_n > 1:
            pr["experts"] = "model"
            pr["mlp_expert"] = None
            ar["experts"] = "model"
        else:
            pr["experts"] = None
            pr["mlp_expert"] = "model"
            ar["experts"] = None
    return ShardingRules(pr, ar, meta=rules.meta)


def with_collectives(rules: ShardingRules, mode: str,
                     axis: str = "model") -> ShardingRules:
    """Request ring/serpentine overlap collectives for the TP projections
    (DESIGN.md §5).

    The choice rides in ``rules.meta`` so it scopes exactly like the rules
    themselves: model code traced under ``use_mesh_rules(mesh, rules)``
    sees it through ``active_overlap`` and routes its matmuls through
    ``dist.overlap``; the same code under plain rules stays on GSPMD's
    default collectives.  ``axis`` names the mesh axis the ring runs over.
    """
    if mode not in COLLECTIVES:
        raise ValueError(f"unknown collectives {mode!r}; one of {COLLECTIVES}")
    meta = dict(rules.meta)
    meta["collectives"] = mode
    meta["overlap_axis"] = axis
    return ShardingRules(dict(rules.param_rules), dict(rules.act_rules),
                         meta=meta)


def resolve_collectives(rules: ShardingRules, mode: str) -> ShardingRules:
    """Resolve a collectives request ("auto" included) against the mesh
    decomposition -- the one policy shared by the train and serve step
    factories.

    "auto" enables the serpentine overlap exactly when the mesh-level
    decomposer chose FSDP (``rules.meta["fsdp"]``): that is the regime
    where every step re-gathers parameter shards over the wire, so hiding
    the transfers behind the ring matmuls pays (DESIGN.md §5).  Explicit
    "ring"/"serpentine" always apply; "gspmd" leaves XLA's defaults.
    """
    if mode == "auto":
        mode = "serpentine" if rules.meta.get("fsdp") else "gspmd"
    if mode != "gspmd":
        rules = with_collectives(rules, mode)
    return rules


def with_kv_sharding(rules: ShardingRules, kv_shard: int,
                     axis: str = "model") -> ShardingRules:
    """KV-cache sharding from the decode plan's mesh level (``repro.serve``).

    The hierarchical planner's decode workload records the KV head shard
    degree it chose at the innermost mesh level
    (``HierarchicalPlan.kv_shard()``: the full ``axis`` extent when the
    memory search demanded sharding and the head count divides it, else
    1).  This rewrites the activation rules so the lowered cache layout
    realizes exactly that choice: heads sharded over ``axis`` when
    ``kv_shard > 1``, fully replicated KV otherwise -- and never the
    legacy auto-policy's sequence fallback, which the plan does not model.

    The same choice covers the POOLED layout (``repro.serve.pages``): the
    page pool's head dim carries the same "kv_heads" logical axis, and its
    page dim ("kv_pages") is pinned unsharded -- a page is the VMEM
    streaming granule of one chip, so splitting a page across chips would
    break the plan's block-size = page-size identity.  The per-slot page
    table and position vector replicate (scalar bookkeeping).
    """
    ar = dict(rules.act_rules)
    ar["kv_heads"] = axis if kv_shard > 1 else None
    ar["kv_seq"] = None
    ar["kv_pages"] = None
    meta = dict(rules.meta)
    meta["kv_shard"] = int(kv_shard)
    return ShardingRules(dict(rules.param_rules), ar, meta=meta)


def with_vmem_limit(rules: ShardingRules,
                    vmem_limit_bytes: int) -> ShardingRules:
    """The decode plan's page-level VMEM budget, carried in ``meta`` to the
    paged-attention kernel traced under these rules (``active_vmem_limit``):
    the budget the page was sized against is the limit it compiles under."""
    meta = dict(rules.meta)
    meta["vmem_limit_bytes"] = int(vmem_limit_bytes)
    return ShardingRules(dict(rules.param_rules), dict(rules.act_rules),
                         meta=meta)


def with_batch_guard(rules: ShardingRules, mesh, global_batch: int) -> ShardingRules:
    """Trim the batch rule to the mesh axes whose product divides the global
    batch (a batch that cannot split evenly replicates instead of erroring)."""
    sizes = _axis_sizes(mesh)
    kept: list = []
    prod = 1
    for a in _rule_axes(rules.act_rules.get("batch")):
        size = sizes.get(a, 1)
        if size and global_batch % (prod * size) == 0:
            kept.append(a)
            prod *= size
    ar = dict(rules.act_rules)
    ar["batch"] = None if not kept else (kept[0] if len(kept) == 1 else tuple(kept))
    return ShardingRules(dict(rules.param_rules), ar, meta=dict(rules.meta))


# ---------------------------------------------------------------------------
# Shardings from rules
# ---------------------------------------------------------------------------


def _divisible_spec(spec: P, shape: Sequence[int], sizes: Dict[str, int]) -> P:
    """Drop mesh axes from dims they do not divide evenly (per-tensor guard:
    a 2-head KV projection on a 4-way model axis stays unsharded rather than
    forcing GSPMD's padded uneven layout)."""
    entries = []
    for i, entry in enumerate(spec):
        names = list(_rule_axes(entry))
        while names and shape[i] % prod(sizes.get(n, 1) for n in names) != 0:
            names.pop()
        entries.append(None if not names else
                       (names[0] if len(names) == 1 else tuple(names)))
    return P(*entries)


def logical_sharding(
    mesh: Mesh,
    rules: ShardingRules,
    axes: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
    kind: str = "param",
) -> NamedSharding:
    """NamedSharding for one tensor from its logical axes (with the
    per-tensor divisibility guard when ``shape`` is known)."""
    spec = rules.param_spec(axes) if kind == "param" else rules.act_spec(axes)
    if shape is not None:
        spec = _divisible_spec(spec, shape, _axis_sizes(mesh))
    return NamedSharding(mesh, spec)


def param_shardings(mesh: Mesh, rules: ShardingRules, specs: PyTree) -> PyTree:
    """NamedSharding pytree matching a ``ParamSpec`` tree."""
    from repro.models.params import spec_tree_map

    return spec_tree_map(
        lambda _, s: logical_sharding(mesh, rules, s.axes, s.shape, "param"),
        specs,
    )


# ---------------------------------------------------------------------------
# Active-rules context (constrain / active_rule inside model code)
# ---------------------------------------------------------------------------


_CTX = threading.local()


def _active() -> Optional[Tuple[Mesh, ShardingRules]]:
    stack = getattr(_CTX, "stack", None)
    return stack[-1] if stack else None


@contextmanager
def use_mesh_rules(mesh: Mesh, rules: ShardingRules):
    """Activate (mesh, rules) for ``constrain``/``active_rule`` in model code
    traced under this context (trace-time scoping, like the paper's runtime
    carrying the hierarchy through the decomposition)."""
    stack = getattr(_CTX, "stack", None)
    if stack is None:
        stack = _CTX.stack = []
    stack.append((mesh, rules))
    try:
        yield
    finally:
        stack.pop()


def active_rule(logical_axis: str) -> AxisRule:
    """The mesh axes the active rules map ``logical_axis`` to (None outside
    any ``use_mesh_rules`` context or for unmapped axes)."""
    ctx = _active()
    if ctx is None:
        return None
    return ctx[1].act_rules.get(logical_axis)


def active_overlap() -> Optional[Tuple[Mesh, str, str, Tuple[str, ...]]]:
    """The overlap-collectives request of the active rules (DESIGN.md §5).

    Returns ``(mesh, axis, mode, batch_axes)`` when the rules traced under
    ``use_mesh_rules`` carry a ``with_collectives`` request and the ring
    axis actually exists with size > 1; None under GSPMD rules, outside any
    context, or on a degenerate axis.  ``batch_axes`` are the mesh axes the
    activations' batch dim shards over -- ``dist.overlap`` keeps the
    leading matmul dim sharded over them so routing never gathers the
    batch.
    """
    ctx = _active()
    if ctx is None:
        return None
    mesh, rules = ctx
    mode = rules.meta.get("collectives", "gspmd")
    if mode == "gspmd":
        return None
    axis = rules.meta.get("overlap_axis", "model")
    sizes = _axis_sizes(mesh)
    if sizes.get(axis, 1) <= 1:
        return None
    batch = tuple(a for a in _rule_axes(rules.act_rules.get("batch"))
                  if a != axis and sizes.get(a, 1) > 1)
    return mesh, axis, mode, batch


def active_vmem_limit() -> Optional[int]:
    """The active rules' planned VMEM limit (``with_vmem_limit``); None
    outside any ``use_mesh_rules`` context or when the rules carry none."""
    ctx = _active()
    return None if ctx is None else ctx[1].meta.get("vmem_limit_bytes")


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Pin ``x`` to the sharding its logical axes imply under the active
    rules; the identity outside a ``use_mesh_rules`` context (single-host
    smoke tests run the same model code unsharded)."""
    ctx = _active()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = _divisible_spec(rules.act_spec(axes), x.shape, _axis_sizes(mesh))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

"""Empirical neighborhood sweep around the planner's analytic tiles.

The paper's runtime picks block shapes *analytically* (``phi_tpu`` inside
Algorithm 1); this module adds the empirical half of Rasch's
analytic-plus-autotuning argument (PAPERS.md): for each Pallas kernel the
analytic block is the **center** of a small neighborhood -- power-of-two,
sublane/MXU-aligned perturbations of each block extent -- every candidate
is pre-filtered through the *same* VMEM working-set model the planner uses
(``_matmul_vmem_bytes`` / ``_attn_vmem_bytes`` / ``phi_page``'s buffered
page / ``ssd_workset_bytes``), the survivors are timed with warmup +
``block_until_ready`` medians, and the winner is persisted to
``experiments/tuning.json`` (``repro.tune.cache``) for the planner to
consult on the next run.

``dry=True`` stops after enumeration + VMEM filtering (the CI smoke: the
candidate set is proven budget-clean without timing anything).  On CPU the
kernels run in Pallas interpret mode -- CPU medians count as the perf
trajectory until hardware shows up (ROADMAP).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

from repro.core.autotile import (
    AttentionTilePlan,
    MatmulTilePlan,
    _align_block,
    _attn_vmem_bytes,
    _matmul_vmem_bytes,
    _round_down,
    _round_up,
    _search_matmul_tiles,
    clamp_attention_plan,
    plan_attention,
)
from repro.hw.tpu import TPUSpec, chip_spec
from repro.tune.cache import (
    TuningEntry,
    bucket_attention,
    bucket_matmul,
    bucket_paged,
    bucket_ssd,
    hw_fingerprint,
    record_tuned,
)

__all__ = [
    "Candidate",
    "SweepResult",
    "default_sweeps",
    "run_sweeps",
    "sweep_attention",
    "sweep_matmul",
    "sweep_paged",
    "sweep_ssd",
    "time_callable",
]


# ---------------------------------------------------------------------------
# Timing
# ---------------------------------------------------------------------------


def time_callable(fn: Callable[[], Any], warmup: int = 2,
                  iters: int = 5) -> float:
    """Median wall seconds of ``fn()`` after ``warmup`` discarded calls,
    each call synchronized with ``block_until_ready`` (jax dispatch is
    async; un-synchronized timings measure nothing)."""
    import jax

    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------


@dataclass
class Candidate:
    """One swept block assignment: the extents, its working-set estimate
    under the planner's model, and (after timing) the measured median."""

    block: Dict[str, int]
    est_vmem_bytes: int
    fits: bool
    median_us: Optional[float] = None

    @property
    def label(self) -> str:
        return "/".join(f"{k}={v}" for k, v in sorted(self.block.items()))


@dataclass
class SweepResult:
    kernel: str
    bucket: str
    workload: Dict[str, Any]
    budget_bytes: int
    center: Dict[str, int]
    candidates: List[Candidate] = field(default_factory=list)  # fit only
    rejected: int = 0            # enumerated but over the VMEM budget
    entry: Optional[TuningEntry] = None      # None on a dry run

    @property
    def winner(self) -> Optional[Candidate]:
        timed = [c for c in self.candidates if c.median_us is not None]
        return min(timed, key=lambda c: c.median_us) if timed else None

    @property
    def analytic_us(self) -> Optional[float]:
        for c in self.candidates:
            if c.block == self.center and c.median_us is not None:
                return c.median_us
        return None


def _finish(result: SweepResult, spec: TPUSpec, dry: bool,
            make_fn: Callable[[Candidate], Callable[[], Any]],
            warmup: int, iters: int,
            workload: Mapping[str, Any]) -> SweepResult:
    """Time every fitting candidate and fold the winner into an entry."""
    if dry:
        return result
    for cand in result.candidates:
        fn = make_fn(cand)
        cand.median_us = time_callable(fn, warmup=warmup, iters=iters) * 1e6
    win = result.winner
    analytic_us = result.analytic_us
    if win is None or analytic_us is None:
        return result
    result.entry = TuningEntry(
        kernel=result.kernel,
        arch=spec.name,
        bucket=result.bucket,
        fingerprint=hw_fingerprint(),
        block=dict(win.block),
        analytic_block=dict(result.center),
        median_us=round(win.median_us, 3),
        analytic_us=round(analytic_us, 3),
        speedup=round(analytic_us / max(win.median_us, 1e-9), 4),
        workload=dict(workload),
    )
    return result


def _dedup_fitting(raw: List[Dict[str, int]], est: Callable[[Mapping], int],
                   budget: int) -> (List[Candidate], int):
    seen, fitting, rejected = set(), [], 0
    for block in raw:
        key = tuple(sorted(block.items()))
        if key in seen:
            continue
        seen.add(key)
        e = est(block)
        if e <= budget:
            fitting.append(Candidate(block=block, est_vmem_bytes=e,
                                     fits=True))
        else:
            rejected += 1
    return fitting, rejected


def _dtype_of(dtype_bytes: int):
    import jax.numpy as jnp

    return {2: jnp.bfloat16, 4: jnp.float32}.get(dtype_bytes, jnp.float32)


# ---------------------------------------------------------------------------
# matmul_cc
# ---------------------------------------------------------------------------


def _extent_options(center: int, dim: int, spec: TPUSpec) -> List[int]:
    """Power-of-two perturbations of one block extent: half and double the
    center, re-aligned to the same MXU/sublane granule the analytic search
    uses, clamped to the (padded) problem dim."""
    unit = spec.mxu if dim > spec.mxu else 8
    opts = {center}
    opts.add(_round_down(center // 2, unit))
    opts.add(_align_block(center * 2, dim, spec.mxu))
    return sorted(o for o in opts if o >= 1)


def sweep_matmul(m: int, k: int, n: int, dtype_bytes: int = 2,
                 spec: Optional[TPUSpec] = None, order: str = "cc",
                 vmem_fraction: float = 1.0, warmup: int = 1,
                 iters: int = 5, dry: bool = False,
                 interpret: Optional[bool] = None) -> SweepResult:
    spec = spec or chip_spec()
    budget = int(spec.usable_vmem * vmem_fraction)
    center = _search_matmul_tiles(m, k, n, dtype_bytes, spec, order, 1,
                                  budget)
    raw = [
        {"bm": bm, "bk": bk, "bn": bn}
        for bm in _extent_options(center.bm, m, spec)
        for bk in _extent_options(center.bk, k, spec)
        for bn in _extent_options(center.bn, n, spec)
    ]
    fitting, rejected = _dedup_fitting(
        raw, lambda b: _matmul_vmem_bytes(b["bm"], b["bk"], b["bn"],
                                          dtype_bytes), budget)
    result = SweepResult(
        kernel="matmul_cc",
        bucket=bucket_matmul(m, k, n, dtype_bytes),
        workload={"m": m, "k": k, "n": n, "dtype_bytes": dtype_bytes},
        budget_bytes=budget,
        center={"bm": center.bm, "bk": center.bk, "bn": center.bn},
        candidates=fitting, rejected=rejected,
    )
    if dry:
        return result

    import jax
    from repro.kernels.matmul_cc import matmul_cc

    dt = _dtype_of(dtype_bytes)
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), dt)
    b = jax.random.normal(kb, (k, n), dt)

    def make_fn(cand: Candidate):
        plan = dataclasses.replace(
            center, bm=cand.block["bm"], bk=cand.block["bk"],
            bn=cand.block["bn"], est_vmem_bytes=cand.est_vmem_bytes)
        f = jax.jit(lambda x, y, p=plan: matmul_cc(
            x, y, plan=p, interpret=interpret))
        return lambda: f(a, b)

    return _finish(result, spec, dry, make_fn, warmup, iters,
                   result.workload)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


def sweep_attention(q_len: int, kv_len: int, head_dim: int,
                    dtype_bytes: int = 2, heads: int = 4, batch: int = 1,
                    causal: bool = True, spec: Optional[TPUSpec] = None,
                    vmem_fraction: float = 1.0, warmup: int = 1,
                    iters: int = 5, dry: bool = False,
                    interpret: Optional[bool] = None) -> SweepResult:
    spec = spec or chip_spec()
    budget = int(spec.usable_vmem * vmem_fraction)
    sub = spec.sublane(dtype_bytes)
    analytic = plan_attention(q_len, kv_len, head_dim,
                              dtype_bytes=dtype_bytes, spec=spec,
                              vmem_fraction=vmem_fraction, use_tuned=False)
    # Sweep the blocks the kernel will actually run (the wrapper clamps a
    # block larger than the sequence), re-aligned to the sublane granule:
    # candidates must be 8-aligned to be admissible as tuned entries, and
    # the kernel's own pad/clamp makes the aligned block equivalent.
    clamped = clamp_attention_plan(analytic, q_len, kv_len,
                                   dtype_bytes=dtype_bytes)
    center = dataclasses.replace(
        clamped,
        block_q=min(_round_up(clamped.block_q, 8), _round_up(q_len, sub)),
        block_kv=min(_round_up(clamped.block_kv, 8),
                     _round_up(kv_len, sub)))

    def q_opts(c: int) -> List[int]:
        opts = {c, max(8, _round_down(c // 2, 8)),
                min(_round_up(c * 2, sub), _round_up(q_len, sub))}
        return sorted(o for o in opts if o >= 8)

    def kv_opts(c: int) -> List[int]:
        opts = {c, max(8, _round_down(c // 2, 8)),
                min(_round_up(c * 2, sub), _round_up(kv_len, sub))}
        return sorted(o for o in opts if o >= 8)

    raw = [{"block_q": bq, "block_kv": bkv}
           for bq in q_opts(center.block_q)
           for bkv in kv_opts(center.block_kv)]
    fitting, rejected = _dedup_fitting(
        raw, lambda b: _attn_vmem_bytes(b["block_q"], b["block_kv"],
                                        head_dim, dtype_bytes), budget)
    result = SweepResult(
        kernel="flash_attention",
        bucket=bucket_attention(q_len, kv_len, head_dim, dtype_bytes),
        workload={"q_len": q_len, "kv_len": kv_len, "head_dim": head_dim,
                  "dtype_bytes": dtype_bytes, "heads": heads,
                  "batch": batch, "causal": causal},
        budget_bytes=budget,
        center={"block_q": center.block_q, "block_kv": center.block_kv},
        candidates=fitting, rejected=rejected,
    )
    if dry:
        return result

    import jax
    from repro.kernels.flash_attention import flash_attention

    dt = _dtype_of(dtype_bytes)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (batch, heads, q_len, head_dim), dt)
    k = jax.random.normal(kk, (batch, heads, kv_len, head_dim), dt)
    v = jax.random.normal(kv, (batch, heads, kv_len, head_dim), dt)

    def make_fn(cand: Candidate):
        plan = dataclasses.replace(
            center, block_q=cand.block["block_q"],
            block_kv=cand.block["block_kv"],
            est_vmem_bytes=cand.est_vmem_bytes)
        f = jax.jit(lambda a, b, c, p=plan: flash_attention(
            a, b, c, causal=causal, plan=p, interpret=interpret))
        return lambda: f(q, k, v)

    return _finish(result, spec, dry, make_fn, warmup, iters,
                   result.workload)


# ---------------------------------------------------------------------------
# paged_attention (the plan's page level)
# ---------------------------------------------------------------------------


def sweep_paged(max_tokens: int = 256, n_kv: int = 2, group: int = 2,
                head_dim: int = 32, slots: int = 4, dtype_bytes: int = 4,
                spec: Optional[TPUSpec] = None, vmem_fraction: float = 1.0,
                warmup: int = 1, iters: int = 5, dry: bool = False,
                interpret: Optional[bool] = None) -> SweepResult:
    """Sweep the decode page size -- the block of ``kernels.
    paged_attention`` IS the plan's page, so the candidate set perturbs
    ``page_tokens`` and each candidate re-lays the pool at that granule."""
    from repro.core.plan import (
        PAGE_ALIGN,
        PlanPolicy,
        Workload,
        page_vmem_bytes,
        plan_run,
    )

    spec = spec or chip_spec()
    budget = int(spec.usable_vmem * vmem_fraction)
    tok_bytes = 2 * n_kv * head_dim * dtype_bytes      # K + V, one layer
    hp = plan_run(
        spec.hierarchy(),
        Workload(kv_bytes_per_token=tok_bytes, kv_layers=1, kv_heads=n_kv,
                 q_heads=n_kv * group, dtype_bytes=dtype_bytes,
                 max_tokens=max_tokens),
        PlanPolicy(spec=spec, vmem_fraction=vmem_fraction, use_tuned=False))
    page = hp.page_plan()
    center_pt = int(page["page_tokens"])
    cap = _round_up(max_tokens, PAGE_ALIGN)
    raw_pts = {center_pt,
               max(PAGE_ALIGN, _round_down(center_pt // 2, PAGE_ALIGN)),
               min(cap, _round_up(center_pt * 2, PAGE_ALIGN))}
    raw = [{"page_tokens": pt} for pt in sorted(raw_pts)]
    fitting, rejected = _dedup_fitting(
        raw, lambda b: page_vmem_bytes(b["page_tokens"], n_kv, head_dim,
                                       n_kv * group, dtype_bytes), budget)
    result = SweepResult(
        kernel="paged_attention",
        bucket=bucket_paged(tok_bytes, max_tokens),
        workload={"max_tokens": max_tokens, "n_kv": n_kv, "group": group,
                  "head_dim": head_dim, "slots": slots,
                  "dtype_bytes": dtype_bytes, "tok_bytes": tok_bytes},
        budget_bytes=budget,
        center={"page_tokens": center_pt},
        candidates=fitting, rejected=rejected,
    )
    if dry:
        return result

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import paged_attention

    dt = _dtype_of(dtype_bytes)
    h = n_kv * group
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((slots, h, head_dim)), dt)

    def make_fn(cand: Candidate):
        pt = cand.block["page_tokens"]
        n_logical = -(-max_tokens // pt)
        p_total = 1 + slots * n_logical          # + reserved null page
        k_pages = jnp.asarray(
            rng.standard_normal((p_total, pt, n_kv, head_dim)), dt)
        v_pages = jnp.asarray(
            rng.standard_normal((p_total, pt, n_kv, head_dim)), dt)
        table = jnp.asarray(
            1 + rng.permutation(slots * n_logical).reshape(slots, n_logical),
            jnp.int32)
        lengths = jnp.asarray(
            rng.integers(max_tokens // 2, max_tokens + 1, slots), jnp.int32)
        f = jax.jit(lambda qq, kk, vv, tb, ln: paged_attention(
            qq, kk, vv, tb, ln, page_tokens=pt, interpret=interpret))
        return lambda: f(q, k_pages, v_pages, table, lengths)

    return _finish(result, spec, dry, make_fn, warmup, iters,
                   result.workload)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


def sweep_ssd(seq_len: int = 256, n_heads: int = 2, head_dim: int = 32,
              state_dim: int = 32, dtype_bytes: int = 4, batch: int = 1,
              spec: Optional[TPUSpec] = None, warmup: int = 1,
              iters: int = 5, dry: bool = False,
              interpret: Optional[bool] = None) -> SweepResult:
    from repro.models.mamba2 import choose_chunk, ssd_workset_bytes

    spec = spec or chip_spec()
    budget = spec.usable_vmem // 2           # choose_chunk's own budget
    center_c = choose_chunk(seq_len, n_heads, head_dim, state_dim,
                            dtype_bytes=dtype_bytes, spec=spec,
                            use_tuned=False)
    cap = min(_round_up(seq_len, 8), 1024)
    raw_cs = {center_c, max(16, _round_down(center_c // 2, 8)),
              min(cap, _round_up(center_c * 2, 8))}
    raw = [{"chunk": c} for c in sorted(raw_cs)]
    fitting, rejected = _dedup_fitting(
        raw, lambda b: ssd_workset_bytes(b["chunk"], n_heads, head_dim,
                                         state_dim, dtype_bytes), budget)
    result = SweepResult(
        kernel="ssd_scan",
        bucket=bucket_ssd(seq_len, n_heads, head_dim, state_dim,
                          dtype_bytes),
        workload={"seq_len": seq_len, "n_heads": n_heads,
                  "head_dim": head_dim, "state_dim": state_dim,
                  "dtype_bytes": dtype_bytes, "batch": batch},
        budget_bytes=budget,
        center={"chunk": center_c},
        candidates=fitting, rejected=rejected,
    )
    if dry:
        return result

    import jax
    import jax.numpy as jnp
    from repro.kernels.ssd_scan import ssd_scan

    dt = _dtype_of(dtype_bytes)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (batch, seq_len, n_heads, head_dim), dt)
    dts = jax.nn.softplus(jax.random.normal(
        keys[1], (batch, seq_len, n_heads), jnp.float32)) * 0.5
    A = -jnp.exp(jax.random.normal(keys[2], (n_heads,), jnp.float32) * 0.3)
    Bm = jax.random.normal(keys[3], (batch, seq_len, state_dim), dt)
    Cm = jax.random.normal(keys[4], (batch, seq_len, state_dim), dt)

    def make_fn(cand: Candidate):
        c = cand.block["chunk"]
        f = jax.jit(lambda *args: ssd_scan(*args, chunk=c,
                                           interpret=interpret))
        return lambda: f(x, dts.astype(dt), A, Bm, Cm)

    return _finish(result, spec, dry, make_fn, warmup, iters,
                   result.workload)


# ---------------------------------------------------------------------------
# Orchestration (the repro-tune CLI and benchmarks/run.py drive this)
# ---------------------------------------------------------------------------

#: Kernel name -> sweep function; the order is the CLI's report order.
SWEEPS = {
    "matmul_cc": sweep_matmul,
    "flash_attention": sweep_attention,
    "paged_attention": sweep_paged,
    "ssd_scan": sweep_ssd,
}


def default_sweeps(quick: bool = False) -> Dict[str, Dict[str, Any]]:
    """The stock sweep workloads: serving/training-shaped but small enough
    to time in interpret mode on CPU.  Buckets are power-of-two, so these
    cover every shape in the same bucket."""
    if quick:
        return {
            "matmul_cc": {"m": 256, "k": 256, "n": 256, "dtype_bytes": 4},
            "flash_attention": {"q_len": 128, "kv_len": 128, "head_dim": 64,
                                "dtype_bytes": 4},
            "paged_attention": {"max_tokens": 64, "n_kv": 2, "group": 2,
                                "head_dim": 16, "slots": 2,
                                "dtype_bytes": 4},
            "ssd_scan": {"seq_len": 128, "n_heads": 2, "head_dim": 16,
                         "state_dim": 16, "dtype_bytes": 4},
        }
    return {
        "matmul_cc": {"m": 512, "k": 512, "n": 512, "dtype_bytes": 4},
        "flash_attention": {"q_len": 256, "kv_len": 256, "head_dim": 64,
                            "dtype_bytes": 4},
        "paged_attention": {"max_tokens": 256, "n_kv": 2, "group": 2,
                            "head_dim": 32, "slots": 4, "dtype_bytes": 4},
        "ssd_scan": {"seq_len": 256, "n_heads": 2, "head_dim": 32,
                     "state_dim": 32, "dtype_bytes": 4},
    }


def run_sweeps(kernels: Optional[Sequence[str]] = None,
               quick: bool = False, dry: bool = False,
               warmup: int = 1, iters: int = 5,
               spec: Optional[TPUSpec] = None,
               out_path: Optional[str] = None,
               write: bool = True) -> List[SweepResult]:
    """Run the stock sweeps and (unless ``dry`` or ``write=False``) merge
    the winners into the tuning artifact."""
    workloads = default_sweeps(quick)
    names = list(kernels) if kernels else list(SWEEPS)
    results = []
    for name in names:
        if name not in SWEEPS:
            raise KeyError(f"unknown kernel {name!r}; known: {list(SWEEPS)}")
        kw = dict(workloads[name])
        kw.update(dry=dry, warmup=warmup, iters=iters)
        if spec is not None:
            kw["spec"] = spec
        results.append(SWEEPS[name](**kw))
    if not dry and write:
        entries = [r.entry for r in results if r.entry is not None]
        if entries:
            record_tuned(entries, path=out_path)
    return results

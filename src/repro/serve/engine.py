"""``ServeEngine`` -- the plan-driven serving engine (the only serving
surface; ``launch/serve.py`` is a thin CLI over it).

One declarative call -- ``ServeEngine(cfg, mesh, policy).generate(prompts)``
-- and every batch/page/shard choice falls out of ``plan_run``:

  * ``plan_decode`` builds the decode workload (per-token KV bytes x heads
    x layers, ``core.plan.Workload``) and walks the mesh hierarchy once.
    The innermost mesh level chooses the **KV head sharding**
    (``kv_shard``), the VMEM leaf the **page size** (``page_tokens``).
  * ``serve.kvcache.PageSpec`` turns the page into the allocation granule;
    cache buffers are whole pages, grown one page at a time.
  * ``serve.scheduler.ServeScheduler`` admits/evicts requests so the
    resident KV footprint never exceeds the planned budget (continuous
    batching at cohort granularity, prefill/decode interleaved).
  * ``serve.steps.make_serve_steps(..., decode_plan=...)`` lowers the steps
    with exactly the plan's cache sharding.

Two batching engines share the plan (``ServePolicy.batching``):

  * ``"cohort"`` (PR 4, the A/B baseline): the batch unit is a *cohort*
    of same-shape prompts (the family decode step carries one scalar
    position per batch); mixed prompt lengths run as concurrently decoded
    cohorts, one decode step per cohort per engine tick.
  * ``"paged"`` (DESIGN.md §8): a fixed batch of decode *slots* over one
    global page pool (``serve.pages``).  Decode is per-slot end to end --
    position vectors, per-row kv_len masks, a Pallas paged-attention
    gather through per-slot page tables -- so a finished slot's pages
    free immediately and the slot is backfilled by a NEW request
    mid-flight, and the whole run is one jit bucket.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.plan import (
    HierarchicalPlan,
    PlanError,
    PlanPolicy,
    Workload,
    plan_run,
)
from repro.obs import MetricsView, Registry, RingLog, Tracer
from repro.serve.kvcache import (
    PageSpec,
    align_capacity,
    cache_capacity,
    grow_cache,
    kv_token_bytes,
    page_spec_from_plan,
    request_state_bytes,
    take_slots,
)
from repro.serve.sampling import SamplingConfig, sample, step_key
from repro.serve.scheduler import Request, ServeScheduler
from repro.serve.steps import ServeSteps, make_serve_steps

PyTree = Any


# ---------------------------------------------------------------------------
# The decode plan
# ---------------------------------------------------------------------------


def plan_decode(
    cfg: ModelConfig,
    mesh,
    *,
    max_len: int = 4096,
    batch: int = 1,
    dtype_bytes: int = 2,
    spec=None,
    hierarchy=None,
    cluster: Optional[int] = None,
) -> HierarchicalPlan:
    """``plan_run`` over the decode workload: the serving counterpart of
    ``dist.sharding.mesh_plan``.

    The mesh hierarchy's interconnect level spans the tensor-parallel
    ("model") axis -- the axis KV heads can shard over; the KV cache's
    batch dim already shards over the data axes, so the shardable state is
    one data-shard's resident KV (``kv_bytes_per_token * max_len * batch /
    data_n``) and the per-chip weight shard rides along as the replicated
    reserve.  ``max_len`` bounds one sequence's resident tokens (the page
    search domain) and ``batch`` the concurrently resident sequences.

    ``cluster=N`` plans a MULTI-REPLICA fleet: the hierarchy grows a DCN
    level over N hosts and the requested replica count seeds the
    outermost search, so the DCN level's realized ``np`` (``replicas()``)
    is the fleet width ``repro.cluster`` stands up -- memory pressure can
    raise it, never shrink it.  Without ``cluster``, a plan containing a
    DCN level is inadmissible: one ``ServeEngine`` cannot realize
    multi-host placement, so the walk raises a structured ``PlanError``
    (the old single-replica guarantee, now a typed failure instead of a
    CI grep).
    """
    sizes = dict(mesh.shape)
    model_n = max(1, sizes.get("model", 1))
    total = 1
    for v in sizes.values():
        total *= v
    data_n = max(1, total // model_n)
    tok_bytes, layers, heads = kv_token_bytes(cfg, dtype_bytes)
    kv_state = (tok_bytes * max_len * batch) // data_n
    weights = cfg.param_count() * dtype_bytes // model_n
    stream = batch * cfg.d_model * dtype_bytes * 4
    fixed = batch * request_state_bytes(cfg, enc_len=max_len,
                                        dtype_bytes=dtype_bytes) // data_n
    if hierarchy is None:
        if spec is None:
            from repro.hw.tpu import chip_spec
            spec = chip_spec()
        hierarchy = spec.hierarchy(mesh_devices=model_n,
                                   hosts=max(1, cluster or 1))
    plan = plan_run(
        hierarchy,
        Workload(
            state_bytes=max(1, kv_state),
            replicated_bytes=weights + stream + fixed,
            overhead=cfg.overhead,
            dtype_bytes=dtype_bytes,
            kv_bytes_per_token=tok_bytes,
            kv_layers=max(1, layers),
            kv_heads=heads,
            q_heads=cfg.n_heads if tok_bytes else 0,
            max_tokens=max_len,
        ),
        PlanPolicy(spec=spec, n_workers=max(1, cluster or 1)),
    )
    if cluster is None and plan.level("DCN") is not None:
        raise PlanError(
            "decode plan contains a DCN level but no cluster was "
            "requested: a single ServeEngine cannot realize multi-host "
            "placement -- pass cluster=N and serve it with repro.cluster, "
            "or plan against a single-host hierarchy",
            level="DCN", plan=plan)
    return plan


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServePolicy:
    """Engine knobs. Everything memory-shaped defaults from the plan; the
    overrides exist for tests and for operators who know better.

    ``batching`` selects the engine: "cohort" (PR 4's position-homogeneous
    cohorts -- the A/B baseline), "paged" (the global page pool with
    per-slot continuous batching, DESIGN.md §8; families without a paged
    decode path -- VLM -- fall back to cohort), or "auto" (paged exactly
    when the decode plan exposes a page level to size the pool from AND
    the family has a per-slot decode path).

    ``prefill`` selects how the paged engine fills a new slot's KV:
    "chunked" cuts the prompt into planned-page-sized chunks written
    directly into pool pages, interleaving decode ticks for resident
    slots between chunks (DESIGN.md §10); "monolithic" runs the same
    direct-to-pool path as one whole-prompt chunk (the TTFT/stall A/B
    baseline -- identical tokens, no interleave).  Cohort batching
    ignores it.

    ``prefix_cache`` turns on cross-request KV reuse in the paged engine
    (DESIGN.md §11): "radix" keeps finished prompt pages resident in a
    refcounted radix tree (budgeted by ``plan.prefix_budget()``, the
    mesh-level HBM leftover) so a request sharing a cached prefix
    prefills only its unshared suffix; "off" disables it.  Families
    without exact cross-request KV reuse (enc-dec, vlm) and cohort
    batching ignore it.
    """

    max_new_tokens: int = 16
    max_slots: int = 8              # sequences per cohort / decode slots
    max_len: int = 4096             # per-sequence planning bound (tokens)
    kv_fraction: float = 0.8        # share of post-weights HBM given to KV
    kv_budget_bytes: Optional[int] = None   # override the planned budget
    batching: str = "cohort"        # | "paged" | "auto"
    prefill: str = "chunked"        # | "monolithic" (paged engine only)
    prefix_cache: str = "off"       # | "radix" (paged engine only)
    sampling: SamplingConfig = field(default_factory=SamplingConfig)

    def __post_init__(self):
        if self.batching not in ("cohort", "paged", "auto"):
            raise ValueError(f"unknown batching {self.batching!r}; "
                             f"one of ('cohort', 'paged', 'auto')")
        if self.prefill not in ("chunked", "monolithic"):
            raise ValueError(f"unknown prefill {self.prefill!r}; "
                             f"one of ('chunked', 'monolithic')")
        if self.prefix_cache not in ("off", "radix"):
            raise ValueError(f"unknown prefix_cache {self.prefix_cache!r}; "
                             f"one of ('off', 'radix')")


@dataclass
class _PagedSession:
    """Device state the paged engine keeps ALIVE between ``generate``
    calls when the prefix cache is on: the pool's refcounts, the pooled
    cache buffers (they hold the cached prefixes' KV) and the radix tree
    itself.  Rebuilt whenever the pool geometry changes (the cached pages
    would not survive a reshape)."""

    key: Any
    pool: Any                       # serve.pages.PagePool
    cache: PyTree                   # pooled cache pytree
    prefix: Any                     # serve.prefix.RadixPrefixCache


@dataclass
class _Run:
    """Engine-side state of one admitted cohort."""

    cid: int
    reqs: List[Request]
    steps: ServeSteps
    cache: PyTree
    next_tokens: Any                # (B, 1) int32 -- last sampled token
    capacity: Optional[int]         # growable token capacity (None: fixed)
    pos: int                        # tokens written so far per slot
    active: Dict[int, int]          # rid -> slot index, still decoding


class ServeEngine:
    """Plan-driven serving engine (see module docstring)."""

    #: Ring-buffer bounds (DESIGN.md §13): the tracer's event ring, the
    #: interleave log, and each request's token-time log all cap here --
    #: overflow drops the oldest entry and counts it (``tracer.dropped``,
    #: ``interleave_dropped``, ``token_times_dropped``).
    TRACE_CAPACITY = 65536
    LOG_CAPACITY = 65536
    TOKEN_TIMES_CAPACITY = 8192

    def __init__(
        self,
        cfg: ModelConfig,
        mesh=None,
        policy: ServePolicy = ServePolicy(),
        dtype=None,
        params: Optional[PyTree] = None,
        seed: int = 0,
        spec=None,
        hierarchy=None,
        replica: int = 0,
    ):
        import jax
        import jax.numpy as jnp

        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.cfg = cfg
        self.mesh = mesh
        # Arrays the engine makes outside its jitted steps (weights, the
        # page pool, per-tick uploads) are made on the mesh's first device,
        # so a replica on a one-device mesh keeps everything on its chip.
        self.device = mesh.devices.flat[0]
        self.policy = policy
        self.dtype = dtype if dtype is not None else jnp.float32
        self._dtype_bytes = jnp.dtype(self.dtype).itemsize
        self.plan = plan_decode(
            cfg, mesh, max_len=policy.max_len,
            batch=policy.max_slots, dtype_bytes=self._dtype_bytes,
            spec=spec, hierarchy=hierarchy)
        self.page: PageSpec = page_spec_from_plan(self.plan, cfg,
                                                  self._dtype_bytes)
        self.scheduler = ServeScheduler(
            self._kv_budget(), self.page, max_slots=policy.max_slots)
        from repro.serve.pages import PAGED_FAMILIES
        self.batching = policy.batching
        if self.batching == "auto":
            # Paged exactly when the plan exposes a page level to size the
            # pool from (token-free families have none) and the family has
            # a per-slot decode path; explicit "paged" still serves
            # page-free families (xLSTM) at slot granularity.
            self.batching = ("paged" if self.plan.page_plan() is not None
                             and cfg.family in PAGED_FAMILIES else "cohort")
        elif self.batching == "paged" and cfg.family not in PAGED_FAMILIES:
            self.batching = "cohort"        # no paged decode path: fall back
        from repro.models.model import build_model
        self.model = build_model(cfg, remat="none")
        if params is None:
            with jax.default_device(self.device):
                params = self.model.init(jax.random.PRNGKey(seed),
                                         dtype=jnp.float32)
        self.params = params
        self._steps_cache: Dict[Any, ServeSteps] = {}
        self._paged_steps_cache: Dict[Any, Any] = {}
        self._paged_session: Optional[_PagedSession] = None
        self._live_pool = None          # the CURRENT run's PagePool
        self._live_sched = None         # ... and PagedScheduler (telemetry)
        self._stream_cb = None          # per-call on_token callback
        self._stream_ix: Dict[int, int] = {}    # rid -> index in this call
        self._next_rid = 0
        self._t_submit: Dict[int, float] = {}   # rid -> submit monotonic s
        # The metrics spine (DESIGN.md §13): one typed Registry per
        # engine, one Tracer per replica (pid = replica id so a merged
        # cluster trace shows the fleet on one timeline).  The legacy
        # ``engine.metrics`` dict API lives on as a MetricsView over the
        # registry -- every pre-existing key keeps its name and meaning,
        # but counts are now monotonic Counters, peaks are Gauges, and
        # latency distributions are log-bucket Histograms.
        self.replica = int(replica)
        self.obs = Registry()
        self.tracer = Tracer(capacity=self.TRACE_CAPACITY,
                             pid=self.replica)
        o = self.obs
        for name in ("tokens", "tokens_recomputed", "decode_steps",
                     "cohorts", "evictions", "slot_steps",
                     "active_slot_steps", "backfills", "stalls",
                     "prefill_chunks", "prefill_tokens", "prefix_hits",
                     "prefix_misses", "prefix_hit_tokens", "pages_saved",
                     "cow_copies", "prefix_nodes_inserted",
                     "interleave_dropped", "token_times_dropped"):
            o.counter(name)
        o.set("page_tokens", self.page.page_tokens, unit="tokens")
        o.set("page_bytes", self.page.page_bytes, unit="B")
        o.set("budget_bytes", self.scheduler.budget_bytes, unit="B")
        o.set("kv_shard", self.plan.kv_shard())
        o.histogram("ttft_s", unit="s")
        o.histogram("inter_token_s", unit="s")
        o.histogram("queue_wait_s", unit="s")
        self.metrics: MetricsView = MetricsView(o, objects={
            "batching": self.batching,
            "plan_page_table": dict(self.plan.page_table() or {}),
            "capacities": [],
            "prefix_cache": policy.prefix_cache,
        })

    # ------------------------------------------------------------- plan reads
    def _kv_budget(self) -> int:
        """The fleet KV budget in the scheduler's *logical* bytes.

        The scheduler bills each page once (logical bytes: tokens x global
        per-token KV).  Physically the cache shards over the data axes but
        replicates over the model axis wherever the plan left it unsharded
        (``kv_shard < model_n``), so one logical byte costs
        ``model_n / kv_shard`` physical bytes -- the fleet HBM headroom is
        divided by that replication factor.  Weights are TP-sharded over
        "model" and (in the serving memory model) replicated over the data
        axes, so one weight copy per data shard is reserved first.
        """
        if self.policy.kv_budget_bytes is not None:
            return int(self.policy.kv_budget_bytes)
        ici = self.plan.level("ICI")
        sizes = dict(self.mesh.shape)
        n_dev = 1
        for v in sizes.values():
            n_dev *= v
        model_n = max(1, sizes.get("model", 1))
        data_n = max(1, n_dev // model_n)
        hbm_total = (ici.budget_bytes if ici is not None
                     else self.plan.leaf().budget_bytes) * n_dev
        weights = self.cfg.param_count() * self._dtype_bytes * data_n
        replication = max(1, model_n // max(1, self.plan.kv_shard()))
        budget = int(self.policy.kv_fraction
                     * max(0, hbm_total - weights) / replication)
        return max(self.page.page_bytes, budget)

    # -------------------------------------------------------------- telemetry
    def stats(self) -> Dict[str, Any]:
        """One consolidated telemetry dict -- pool, slots, prefix tree --
        that every consumer (the cluster router, the ``/stats`` endpoint,
        benchmarks) reads instead of poking ``metrics`` internals.

        Page counts come from the LIVE pool when one exists (mid-generate,
        or a persistent radix session holding cached prefixes), so a
        replica's memory pressure is visible from outside while a request
        is resident; otherwise they fall back to the last run's geometry,
        then to the plan's ``page_table``."""
        ptab = dict(self.plan.page_table() or {})
        pages_total = int(self.metrics.get("pages_total")
                          or ptab.get("pages_total") or 0)
        free_pages, used_pages = pages_total, 0
        slots_total = int(self.policy.max_slots)
        slots_free = slots_total
        pool = self._live_pool
        if pool is not None:
            pages_total = pool.pages_total - 1      # minus the null page
            # The pool publishes occupancy gauges on every alloc/free
            # (DESIGN.md §13); read those so the router's ``free_pages``
            # policy and this view observe the same instrument.
            free_pages = int(self.obs.value("free_pages",
                                            pool.free_pages))
            used_pages = int(self.obs.value("used_pages",
                                            pool.used_pages))
        sched = self._live_sched
        if sched is not None:
            slots_free = max(0, slots_total - len(sched.active()))
        out = {
            "batching": self.batching,
            "free_pages": int(free_pages),
            "used_pages": int(used_pages),
            "pages_total": int(pages_total),
            "slots_free": slots_free,
            "slots_total": slots_total,
            "page_tokens": self.page.page_tokens,
            "page_bytes": self.page.page_bytes,
            "kv_shard": self.plan.kv_shard(),
            "tokens": int(self.metrics.get("tokens", 0)),
            "decode_steps": int(self.metrics.get("decode_steps", 0)),
            "prefill_chunks": int(self.metrics.get("prefill_chunks", 0)),
            "prefix_nodes": 0,
            "prefix_pages": 0,
            "prefix_resident_bytes": 0,
        }
        sess = self._paged_session
        if sess is not None and sess.prefix is not None:
            out["prefix_nodes"] = sess.prefix.n_nodes
            out["prefix_pages"] = sess.prefix.n_pages
            out["prefix_resident_bytes"] = sess.prefix.resident_bytes
        return out

    # -------------------------------------------------------- token streaming
    def _notify(self, rid: int, tok: Optional[int]) -> None:
        """Forward one delivered token (or a ``None`` stream reset after a
        recompute preemption: earlier tokens will re-emit) to the caller's
        ``on_token(index_in_call, token)`` callback."""
        cb = self._stream_cb
        if cb is None:
            return
        ix = self._stream_ix.get(rid)
        if ix is not None:
            cb(ix, tok)

    # --------------------------------------------------------------- requests
    def _normalize_prompt(self, prompt) -> Dict[str, np.ndarray]:
        if isinstance(prompt, dict):
            return {k: np.asarray(v) for k, v in prompt.items()}
        return {"tokens": np.asarray(prompt, dtype=np.int32)}

    def _make_request(self, prompt, max_new: int,
                      paged: bool = False) -> Request:
        feats = self._normalize_prompt(prompt)
        if "tokens" in feats:
            plen = int(feats["tokens"].shape[-1])
        else:
            plen = int(feats["embeds"].shape[0])
        enc_len = (int(feats["enc_embeds"].shape[0])
                   if "enc_embeds" in feats else 0)
        rid = self._next_rid
        self._next_rid += 1
        # Fixed-extent caches (sliding-window rings) allocate their full
        # window-clamped capacity at admission and never grow, so the slot
        # must be billed for all of it up front; growable caches pin only
        # prompt + the first decode page (the Request default).  The paged
        # pool has no ring buffers -- windowed slots grow page by page and
        # reclaim out-of-window pages -- so admission is always prompt + 1.
        admit_tokens = None
        if not paged and not self._growable() and self.cfg.sliding_window:
            admit_tokens = min(plen + max_new + 1, self.cfg.sliding_window)
        return Request(
            rid=rid, prompt_len=plen, max_new=max_new,
            state_bytes=request_state_bytes(self.cfg, enc_len,
                                            self._dtype_bytes),
            features=feats, group=(plen, enc_len),
            admit_tokens=admit_tokens)

    # ------------------------------------------------------------------ steps
    def _growable(self) -> bool:
        tok_bytes, _, _ = kv_token_bytes(self.cfg, self._dtype_bytes)
        return tok_bytes > 0 and not self.cfg.sliding_window

    def _steps(self, n_slots: int, prompt_len: int, capacity: int
               ) -> ServeSteps:
        from repro.configs.base import ShapeConfig

        key = (n_slots, prompt_len, capacity)
        ss = self._steps_cache.get(key)
        if ss is None:
            shape = ShapeConfig("serve", prompt_len, n_slots, "decode")
            ss = make_serve_steps(
                self.cfg, shape, self.mesh, dtype=self.dtype,
                max_len_extra=capacity - prompt_len,
                decode_plan=self.plan)
            self._steps_cache[key] = ss
        return ss

    # ---------------------------------------------------------------- prefill
    def _stack_features(self, reqs: List[Request]) -> Dict[str, Any]:
        import jax.numpy as jnp

        keys = reqs[0].features.keys()
        out = {}
        for k in keys:
            arrs = [r.features[k] for r in reqs]
            axis = 1 if k == "positions_3d" else 0
            out[k] = jnp.stack([jnp.asarray(a) for a in arrs], axis=axis)
        if self.cfg.family == "vlm" and "positions_3d" not in out:
            s = out["embeds"].shape[1] if "embeds" in out else \
                out["tokens"].shape[1]
            out["positions_3d"] = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, None],
                (3, len(reqs), s))
        return out

    def _prefill_cohort(self, cid: int, reqs: List[Request],
                        outputs: Dict[int, List[int]],
                        scfg: SamplingConfig, step: int) -> _Run:
        prompt_len = reqs[0].prompt_len
        max_new = max(r.max_new for r in reqs)
        if self._growable():
            capacity = align_capacity(prompt_len + 1, self.page)
        else:
            capacity = prompt_len + max_new + 1
        ss = self._steps(len(reqs), prompt_len, capacity)
        batch = self._stack_features(reqs)
        for r in reqs:
            now = time.monotonic()
            t_sub = self._t_submit.get(r.rid, now)
            self.tracer.complete("queue_wait", t_sub, now, tid=r.rid + 1,
                                 args={"rid": r.rid, "cohort": cid})
            self.obs.observe("queue_wait_s", now - t_sub)
        tp0 = time.monotonic()
        logits, cache = ss.prefill(self.params, batch)
        self.tracer.complete("prefill", tp0, time.monotonic(), tid=0,
                             args={"cohort": cid, "slots": len(reqs),
                                   "prompt": prompt_len})
        toks = sample(logits, scfg, step_key(scfg, step))
        run = _Run(
            cid=cid, reqs=reqs, steps=ss, cache=cache,
            next_tokens=toks[:, None],
            capacity=(cache_capacity(self.cfg, cache)
                      if self._growable() else None),
            pos=prompt_len,
            active={r.rid: i for i, r in enumerate(reqs)})
        self.metrics["cohorts"] += 1
        if run.capacity is not None:
            self.metrics["capacities"].append(run.capacity)
        self._emit(run, toks, outputs, scfg)
        return run

    # ----------------------------------------------------------------- decode
    def _emit(self, run: _Run, toks, outputs: Dict[int, List[int]],
              scfg: SamplingConfig) -> None:
        toks = np.asarray(toks).reshape(-1)
        for r in list(run.reqs):
            slot = run.active.get(r.rid)
            if slot is None:
                continue
            t = int(toks[slot])
            outputs[r.rid].append(t)
            now = time.monotonic()
            if len(outputs[r.rid]) == 1:
                self.tracer.instant("first_token", tid=r.rid + 1,
                                    args={"rid": r.rid})
                self.obs.observe(
                    "ttft_s", now - self._t_submit.get(r.rid, now))
            self._notify(r.rid, t)
            self.metrics["tokens"] += 1
            if len(outputs[r.rid]) >= r.max_new or \
                    (scfg.eos_id is not None and t == scfg.eos_id):
                del run.active[r.rid]
                self.scheduler.finish(run.cid, r.rid)
                self.tracer.complete(
                    "request", self._t_submit.get(r.rid, now), now,
                    tid=r.rid + 1,
                    args={"rid": r.rid, "tokens": len(outputs[r.rid])})

    def _compact(self, run: _Run) -> None:
        """Drop finished slots from the cohort batch: slice the cache (and
        the pending next-token column) down to the survivors so their
        pages release immediately instead of at whole-cohort retirement.
        Called at growth boundaries -- the moment freed pages pay for
        themselves -- since each new batch shape is another jit bucket."""
        import jax.numpy as jnp

        if not run.active or len(run.active) == len(run.reqs):
            return
        keep = [r for r in run.reqs if r.rid in run.active]
        idx = [run.active[r.rid] for r in keep]
        run.cache = take_slots(run.cache, idx)
        run.next_tokens = jnp.take(run.next_tokens,
                                   jnp.asarray(idx), axis=0)
        run.reqs = keep
        run.active = {r.rid: i for i, r in enumerate(keep)}
        self.scheduler.shrink_slots(run.cid, [r.rid for r in keep])

    def _ensure_capacity(self, run: _Run, runs: Dict[int, "_Run"],
                         outputs: Dict[int, List[int]]) -> None:
        if run.capacity is None or run.pos + 1 <= run.capacity:
            return
        # Before asking for more pages, release the ones finished slots
        # still pin (growth is where a smaller batch pays for the retrace).
        self._compact(run)
        needed = run.capacity + self.page.page_tokens
        while not self.scheduler.reserve(run.cid, needed):
            victim = self.scheduler.youngest_other(run.cid)
            if victim is None or victim not in runs:
                raise RuntimeError(
                    f"KV budget {self.scheduler.budget_bytes} cannot hold "
                    f"one growing cohort; raise kv_budget_bytes")
            # Recompute preemption: requeue the victim's unfinished
            # requests.  ``tokens`` stays a monotonic count of delivered
            # tokens; the invalidated work moves into the
            # ``tokens_recomputed`` counter instead of subtracting (a
            # decrement made the count transiently negative when a
            # preemption landed before the victim's first token re-emit).
            for r in self.scheduler.evict(victim):
                self.obs.inc("tokens_recomputed", len(outputs[r.rid]))
                self.tracer.instant(
                    "preempt", tid=r.rid + 1,
                    args={"rid": r.rid, "cohort": victim,
                          "tokens_lost": len(outputs[r.rid])})
                outputs[r.rid] = []
                self._notify(r.rid, None)
            del runs[victim]
            self.metrics["evictions"] += 1
        run.cache = grow_cache(self.cfg, run.cache, needed)
        run.capacity = needed
        self.metrics["capacities"].append(needed)

    def _decode_cohort(self, run: _Run, runs: Dict[int, "_Run"],
                       outputs: Dict[int, List[int]],
                       scfg: SamplingConfig, step: int) -> None:
        import jax.numpy as jnp

        self._ensure_capacity(run, runs, outputs)
        batch = {"tokens": run.next_tokens}
        if self.cfg.family == "vlm":
            batch["positions_3d"] = jnp.broadcast_to(
                run.cache["pos"][None, None, None],
                (3, len(run.reqs), 1)).astype(jnp.int32)
        td0 = time.monotonic()
        logits, run.cache = run.steps.decode(self.params, run.cache, batch)
        toks = sample(logits, scfg, step_key(scfg, step))
        self.tracer.complete("decode_tick", td0, time.monotonic(), tid=0,
                             args={"cohort": run.cid,
                                   "active": len(run.active)})
        run.next_tokens = toks[:, None].astype(jnp.int32)
        run.pos += 1
        self.metrics["decode_steps"] += 1
        # Utilization: this step decoded len(reqs) rows, of which only the
        # still-active ones deliver a token (finished slots ride along
        # until the next growth-boundary compaction -- the cohort tax the
        # paged engine's backfill removes).
        self.metrics["slot_steps"] += len(run.reqs)
        self.metrics["active_slot_steps"] += len(run.active)
        self._emit(run, toks, outputs, scfg)

    # --------------------------------------------------------------- generate
    def generate(
        self,
        prompts: Sequence[Any],
        max_new_tokens=None,
        sampling: Optional[SamplingConfig] = None,
        on_token=None,
    ) -> List[List[int]]:
        """Serve ``prompts`` (token-id sequences, or per-family feature
        dicts without the batch dim), returning each request's generated
        token ids in submission order.  ``max_new_tokens`` is one int for
        all requests or a per-request sequence.  Continuous batching: admissions
        (prefills) interleave with one decode step per live cohort per
        tick, and the resident KV footprint stays inside the planned
        budget throughout (asserted every tick).

        ``on_token(i, tok)`` streams each delivered token as it is
        sampled (``i`` = the request's index in this call) -- the HTTP
        front end's chunked-transfer hook.  A recompute preemption
        invalidates a request's streamed tokens; the callback receives
        ``on_token(i, None)`` and the tokens re-emit from scratch.
        """
        scfg = sampling or self.policy.sampling
        max_new = (max_new_tokens if max_new_tokens is not None
                   else self.policy.max_new_tokens)
        if isinstance(max_new, int):
            max_new = [max_new] * len(prompts)
        if len(max_new) != len(prompts):
            raise ValueError(
                f"max_new_tokens: expected one int or {len(prompts)} "
                f"entries, got {len(max_new)}")
        if not prompts:
            return []
        import jax

        self._stream_cb = on_token
        try:
            with jax.default_device(self.device):
                if self.batching == "paged":
                    return self._generate_paged(prompts, max_new, scfg)
                return self._generate_cohort(prompts, max_new, scfg)
        finally:
            self._stream_cb = None
            self._stream_ix = {}

    def _generate_cohort(self, prompts: Sequence[Any], max_new: List[int],
                         scfg: SamplingConfig) -> List[List[int]]:
        reqs = [self._make_request(p, n) for p, n in zip(prompts, max_new)]
        self._stream_ix = {r.rid: i for i, r in enumerate(reqs)}
        for r in reqs:
            self.scheduler.submit(r)
            self._t_submit[r.rid] = time.monotonic()
            self.tracer.instant("submit", tid=r.rid + 1,
                                args={"rid": r.rid,
                                      "prompt": r.prompt_len})
        outputs: Dict[int, List[int]] = {r.rid: [] for r in reqs}
        runs: Dict[int, _Run] = {}
        step = 0
        while self.scheduler.has_work():
            progressed = False
            for cid, batch in self.scheduler.admit():
                runs[cid] = self._prefill_cohort(cid, batch, outputs,
                                                 scfg, step)
                step += 1
                progressed = True
            for cid in sorted(runs):
                run = runs.get(cid)
                if run is None:
                    continue            # evicted by a sibling's growth
                if not run.active:
                    del runs[cid]
                    continue
                self._decode_cohort(run, runs, outputs, scfg, step)
                step += 1
                progressed = True
                if not run.active:
                    del runs[cid]
            assert self.scheduler.allocated_bytes <= \
                self.scheduler.budget_bytes, "resident KV exceeded the plan"
            self.scheduler.assert_reconciled()
            if not progressed:
                raise RuntimeError("scheduler stalled with pending work")
        self.metrics["peak_resident_bytes"] = self.scheduler.peak_bytes
        self.metrics["pages_allocated"] = self.scheduler.pages_allocated
        self.metrics["pages_released"] = self.scheduler.pages_released
        self._finalize_utilization()
        return [outputs[r.rid] for r in reqs]

    def _finalize_utilization(self) -> None:
        steps = self.metrics["slot_steps"]
        self.metrics["slot_utilization"] = (
            self.metrics["active_slot_steps"] / steps if steps else 0.0)

    # ------------------------------------------------------- paged batching
    def _paged_slots(self, reqs: List[Request]) -> int:
        """Decode-batch width: ``max_slots`` capped at the trace -- never
        allocate (and bill utilization for) slots no request can occupy,
        so ``slot_utilization`` is comparable with cohort mode even when
        requests < max_slots."""
        return max(1, min(self.policy.max_slots, len(reqs)))

    def _paged_geometry(self, reqs: List[Request], n_slots: int):
        """Pool geometry from the plan (DESIGN.md §8): the logical table
        width is the plan's per-slot page bound, stretched to the longest
        submitted request; the physical pool is the planned KV budget in
        pages (priced as the pool lays them out, ``pool_page_bytes``),
        capped at what the slots can ever pin (plus the null
        page).  With the prefix cache on, the cap doubles (still inside
        the budget): cached prefixes occupy pool pages BESIDE the live
        slots' working set, up to ``plan.prefix_budget()``."""
        from repro.serve.pages import pool_page_bytes

        page = self.page
        if page.page_bytes <= 0:          # token-free family (xLSTM)
            return 1, 2
        ptab = self.plan.page_table() or {}
        need = max(page.pages_for(r.prompt_len + r.max_new + 1)
                   for r in reqs)
        pages_per_slot = max(int(ptab.get("pages_per_slot") or 1), need)
        budget_pages = max(1, self.scheduler.budget_bytes
                           // pool_page_bytes(self.cfg, page))
        slot_pages = n_slots * pages_per_slot
        extra = 0
        if self.policy.prefix_cache == "radix" and \
                self.cfg.family in self._prefix_families():
            budget = self.plan.prefix_budget() or 0
            extra = min(budget // pool_page_bytes(self.cfg, page),
                        slot_pages)
        pages_total = 1 + min(budget_pages, slot_pages + extra)
        return pages_per_slot, pages_total

    def _prefix_families(self):
        from repro.serve.prefix import PREFIX_FAMILIES
        return PREFIX_FAMILIES

    def _paged_steps(self, cache, n_slots: int, pages_total: int,
                     pages_per_slot: int, enc_max: int = 0):
        from repro.serve.steps import make_paged_steps

        key = (n_slots, pages_total, pages_per_slot,
               self.page.page_tokens, enc_max)
        ss = self._paged_steps_cache.get(key)
        if ss is None:
            ss = make_paged_steps(
                self.cfg, self.mesh, cache,
                n_slots=n_slots, max_len=self.policy.max_len,
                dtype=self.dtype, decode_plan=self.plan)
            self._paged_steps_cache[key] = ss
        return ss

    def _encode_req(self, steps, req: Request):
        """Enc-dec admission: run the encoder + cross projections once for
        this request (jit bucket per encoder length).  ``None`` for every
        other family."""
        if self.cfg.family != "enc_dec":
            return None
        import jax.numpy as jnp

        enc = jnp.asarray(np.asarray(req.features["enc_embeds"]))[None]
        return steps.encode(self.params, enc)

    def _apply_prefix_hit(self, cache: PyTree, slot: int, hit) -> PyTree:
        """Realize a ``PrefixHit`` on the device cache: copy the CoW
        source page into the slot's private copy (the only page-sized
        device copy in the whole hit path) and restore the recurrent
        state snapshot into the slot's state rows.  Shared full pages
        need no device work at all -- the slot's page table already
        points at them."""
        import jax
        import jax.numpy as jnp

        cache = dict(cache)
        if hit.cow is not None and cache.get("pool"):
            src, dst = hit.cow
            cache["pool"] = {
                k: buf.at[:, dst].set(buf[:, src])
                for k, buf in cache["pool"].items()}
        if hit.state is not None and cache.get("state"):
            cache["state"] = jax.tree.map(
                lambda a, s: (a.at[:, slot].set(
                    jnp.asarray(s).astype(a.dtype)) if a.ndim >= 2
                    else a.at[slot].set(jnp.asarray(s).astype(a.dtype))),
                cache["state"], hit.state)
        return cache

    def _ensure_paged_session(self, n_slots: int, pages_per_slot: int,
                              pages_total: int, enc_max: int = 0
                              ) -> _PagedSession:
        """The persistent radix session (pool + pooled cache + tree) for
        this geometry, creating or rebuilding it on a geometry change.
        Factored out of ``_generate_paged`` so the disaggregation import
        path can materialize the session BEFORE any generate call."""
        from repro.serve.pages import PagePool, init_paged_cache
        from repro.serve.prefix import STATE_FAMILIES, RadixPrefixCache

        geo_key = (n_slots, pages_per_slot, pages_total, enc_max)
        sess = self._paged_session
        if sess is not None and sess.key == geo_key:
            return sess
        pool = PagePool(pages_total, obs=self.obs, tracer=self.tracer)
        cache = init_paged_cache(self.cfg, self.model, n_slots,
                                 pages_total, self.page.page_tokens,
                                 pages_per_slot, self.dtype,
                                 enc_len=enc_max)
        budget = self.plan.prefix_budget()
        if not budget:                    # no page level (xLSTM): fall back
            budget = self.scheduler.budget_bytes
        prefix = RadixPrefixCache(
            self.page.page_tokens, max(0, self.page.page_bytes), budget,
            pool, has_state=self.cfg.family in STATE_FAMILIES,
            obs=self.obs, tracer=self.tracer)
        self._paged_session = _PagedSession(geo_key, pool, cache, prefix)
        return self._paged_session

    # ------------------------------------------- disaggregation page hooks
    def export_pages(self, tokens) -> Optional[Dict[str, Any]]:
        """Serialize the radix-cached KV pages covering ``tokens``' leading
        page-aligned blocks (prefill-role replicas: run ``generate`` with
        ``max_new_tokens=1`` first so the prompt's pages are published to
        the tree).  Returns ``{"tokens", "page_tokens", "pages", "snaps"}``
        with ``pages`` a list of per-page ``{buffer: np.ndarray}`` dicts in
        logical order, or None when nothing is cached (prefix cache off,
        family not prefix-cacheable, or a cold tree)."""
        from repro.serve.pages import export_pool_pages

        sess = self._paged_session
        if sess is None or sess.prefix is None:
            return None
        toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
        covered, pids, snaps = sess.prefix.match(toks)
        if covered <= 0:
            return None
        return {
            "tokens": toks[:covered].tolist(),
            "page_tokens": int(self.page.page_tokens),
            "pages": export_pool_pages(sess.cache, pids),
            "snaps": snaps,
        }

    def import_pages(self, tokens, payloads, snaps=None,
                     n_slots: int = 1) -> int:
        """Install serialized KV pages into THIS engine's pool and radix
        tree (the foreign-pool import, decode-role replicas): allocate
        local pages, write the payload buffers, publish the chain so the
        next ``generate`` sharing the prefix starts at the boundary.
        Returns the number of prompt tokens now resident locally.

        Requires ``ServePolicy(prefix_cache="radix")`` and a stable pool
        geometry (``policy.max_len`` bounding every request) -- a later
        geometry change rebuilds the session and drops imported pages."""
        from repro.serve.pages import install_pool_pages

        if self.policy.prefix_cache != "radix" or \
                self.cfg.family not in self._prefix_families():
            raise PlanError("import_pages needs ServePolicy(prefix_cache="
                            "'radix') and a prefix-cacheable family")
        toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
        req = self._make_request(toks, self.policy.max_new_tokens,
                                 paged=True)
        self._next_rid -= 1               # synthetic request: no rid burn
        pages_per_slot, pages_total = self._paged_geometry([req], n_slots)
        sess = self._ensure_paged_session(n_slots, pages_per_slot,
                                          pages_total, 0)
        pool, prefix = sess.pool, sess.prefix
        n = len(payloads)
        if n == 0:
            return 0
        pids = pool.alloc(n)
        if pids is None:
            prefix.release_pages(need=n)
            pids = pool.alloc(n)
        if pids is None:
            raise RuntimeError(
                f"page pool ({pool.pages_total - 1} pages) cannot hold a "
                f"{n}-page import; raise kv_budget_bytes")
        sess.cache = install_pool_pages(sess.cache, pids, payloads)
        prefix.insert(toks, list(pids), snaps=dict(snaps or {}))
        # The tree now holds one reference per inserted page; drop ours
        # (uninserted tail pages -- budget pressure -- return to the pool).
        pool.free(pids)
        # Resident coverage, not nodes created: a re-import of an
        # already-published prefix is an idempotent success, and tail
        # pages dropped under budget pressure are not counted.
        return int(prefix.match(toks)[0])

    def _generate_paged(self, prompts: Sequence[Any], max_new: List[int],
                        scfg: SamplingConfig) -> List[List[int]]:
        """Per-slot continuous batching over the global page pool.

        A fixed batch of ``max_slots`` decode slots shares ONE page pool,
        ONE jitted decode program (static pool/table/slot shapes -- no
        per-capacity retraces) and ONE jitted chunked-prefill program per
        distinct chunk length.  Prefill is CHUNKED (DESIGN.md §10): a new
        request's prompt is cut into planned-page-sized chunks written
        straight into the slot's pool pages -- no staging cache, no
        post-prefill copy -- and every tick runs at most one chunk per
        prefilling slot before the decode step for the resident slots, so
        a long prompt never blocks decode for more than one chunk.
        ``policy.prefill == "monolithic"`` runs the same direct-to-pool
        path as one whole-prompt chunk (the A/B baseline).  A finished
        slot's pages free immediately and the slot is backfilled
        mid-flight -- the utilization win over cohort mode.
        """
        import jax
        import jax.numpy as jnp

        from repro.serve.pages import (
            PagePool,
            PagedScheduler,
            init_paged_cache,
            reset_slot,
        )

        reqs = [self._make_request(p, n, paged=True)
                for p, n in zip(prompts, max_new)]
        self._stream_ix = {r.rid: i for i, r in enumerate(reqs)}
        outputs: Dict[int, List[int]] = {r.rid: [] for r in reqs}
        n_slots = self._paged_slots(reqs)
        page = self.page
        window = self.cfg.sliding_window
        pages_per_slot, pages_total = self._paged_geometry(reqs, n_slots)
        enc_max = max((r.group[1] for r in reqs), default=0)
        prefix_on = (self.policy.prefix_cache == "radix"
                     and self.cfg.family in self._prefix_families())
        if prefix_on:
            # Cross-call persistence: the pool's refcounts, the cached
            # prefixes' device pages and the radix tree survive between
            # generate() calls as long as the geometry matches.
            sess = self._ensure_paged_session(n_slots, pages_per_slot,
                                              pages_total, enc_max)
            pool, cache, prefix = sess.pool, sess.cache, sess.prefix
        else:
            pool = PagePool(pages_total, obs=self.obs, tracer=self.tracer)
            cache = init_paged_cache(self.cfg, self.model, n_slots,
                                     pages_total, page.page_tokens,
                                     pages_per_slot, self.dtype,
                                     enc_len=enc_max)
            prefix = None
        sched = PagedScheduler(pool, page, n_slots, pages_per_slot,
                               window=window, prefix=prefix)
        self._live_pool = pool          # router/stats() telemetry handles:
        self._live_sched = sched        # live reads while generate runs
        steps = self._paged_steps(cache, n_slots, pages_total,
                                  pages_per_slot, enc_max)
        self.metrics["pages_total"] = pages_total - 1     # usable pages
        self.metrics["pages_per_slot"] = pages_per_slot
        # Chunk length: the planner's page (KV write granule == page ->
        # every full chunk fills exactly one fresh page); token-free
        # families chunk by the planner's page token count anyway (state
        # advances chunkwise, nothing to page).  "monolithic" (or no page
        # geometry at all) degenerates to one whole-prompt chunk.
        chunk_tokens = self.plan.chunk_tokens() or page.page_tokens
        if self.policy.prefill == "monolithic" or chunk_tokens <= 0:
            chunk_tokens = 0                  # whole prompt per chunk
        trace = RingLog(maxlen=self.LOG_CAPACITY)
        self.metrics["interleave"] = trace

        table_np = np.zeros((n_slots, pages_per_slot), np.int32)
        pos_np = np.zeros((n_slots,), np.int32)
        next_np = np.zeros((n_slots, 1), np.int32)
        ever_occupied: set = set()
        requeued: set = set()           # rids re-admitting after preemption
        prefills: Dict[int, int] = {}   # slot -> prompt tokens prefilled
        chunk_snaps: Dict[int, Dict[int, Any]] = {}  # slot -> {tokens: state}
        peak_pages = 0
        t0 = time.monotonic()
        token_times: Dict[int, RingLog] = {
            r.rid: RingLog(maxlen=self.TOKEN_TIMES_CAPACITY) for r in reqs}
        self.metrics["token_times"] = token_times
        self.metrics["start_time"] = t0
        for r in reqs:
            sched.submit(r)
            self._t_submit[r.rid] = time.monotonic()
            self.tracer.instant("submit", tid=r.rid + 1,
                                args={"rid": r.rid,
                                      "prompt": r.prompt_len})
        step = 0

        def clear_slot(i: int) -> None:
            table_np[i] = 0
            pos_np[i] = 0
            next_np[i, 0] = 0

        def push_table(i: int) -> None:
            row = [p if p is not None else 0 for p in sched.slots[i].pages]
            table_np[i, :len(row)] = row
            table_np[i, len(row):] = 0

        def emit_token(slot: int, rid: int, max_new_bound: int,
                       tok: int) -> None:
            """Deliver one sampled token for a slot: record it, queue it
            as the slot's next input, reclaim out-of-window pages, and
            retire the slot when its request is done (pages free at once
            -- the next admission backfills)."""
            outputs[rid].append(tok)
            now = time.monotonic()
            times = token_times[rid]
            if len(outputs[rid]) == 1:
                self.tracer.instant("first_token", tid=rid + 1,
                                    args={"rid": rid, "slot": slot})
                self.obs.observe(
                    "ttft_s", now - self._t_submit.get(rid, t0))
            elif len(times):
                self.obs.observe("inter_token_s", now - times[-1])
            times.append(now)
            self._notify(rid, tok)
            self.metrics["tokens"] += 1
            next_np[slot, 0] = tok
            if window:
                sched.reclaim_window(slot, window)
            if len(outputs[rid]) >= max_new_bound or \
                    (scfg.eos_id is not None and tok == scfg.eos_id):
                sched.finish(slot)
                clear_slot(slot)
                self.tracer.complete(
                    "request", self._t_submit.get(rid, t0), now,
                    tid=rid + 1,
                    args={"rid": rid, "tokens": len(outputs[rid])})

        def preempt(victim: int) -> None:
            """Recompute preemption: the victim's tokens (and any partial
            prefill) regenerate from scratch after re-admission.  The
            delivered-token count stays monotonic -- invalidated tokens
            move into ``tokens_recomputed`` (subtracting here used to
            drive ``metrics["tokens"]`` transiently negative until the
            victim re-emitted)."""
            vreq = sched.evict(victim)
            self.obs.inc("tokens_recomputed", len(outputs[vreq.rid]))
            self.tracer.instant(
                "preempt", tid=vreq.rid + 1,
                args={"rid": vreq.rid, "slot": victim,
                      "tokens_lost": len(outputs[vreq.rid])})
            outputs[vreq.rid] = []
            token_times[vreq.rid].clear()   # keeps its dropped count
            self._notify(vreq.rid, None)
            requeued.add(vreq.rid)
            prefills.pop(victim, None)
            chunk_snaps.pop(victim, None)
            clear_slot(victim)
            self.metrics["evictions"] += 1

        while sched.has_work():
            progressed = False
            # Capacity FIRST, oldest request first: growth claims its pages
            # before admission can hand the last free ones to a new request
            # whose just-run prefill an older grower would immediately
            # evict.  An older slot preempts strictly-younger victims
            # (recompute); a slot with no younger victim STALLS this tick
            # (pages pinned, decode skipped) -- the oldest slot always
            # progresses, so no eviction ping-pong.  Prefilling slots
            # claim capacity in the chunk phase instead (ahead of their
            # chunk front, not their decode position).
            stalled: set = set()
            for i in sorted(sched.active(),
                            key=lambda j: sched.slots[j].rid):
                if sched.slots[i] is None or i in prefills:
                    continue                  # evicted by an older grower
                while not sched.ensure_capacity(i):
                    if sched.table_full(i):
                        stalled.add(i)    # eviction cannot widen the table
                        self.metrics["stalls"] += 1
                        break
                    victim = sched.victim(i)
                    if victim is None:
                        if len(sched.active()) == 1:
                            raise RuntimeError(
                                f"page pool ({pool.pages_total - 1} pages)"
                                f" cannot hold one growing sequence; "
                                f"raise kv_budget_bytes")
                        stalled.add(i)
                        self.metrics["stalls"] += 1
                        break
                    preempt(victim)

            # Admission: a slot + its first page (token-free: none); the
            # prompt itself streams in below, one chunk per tick, straight
            # into pool pages.  Enc-dec runs its encoder once here and
            # installs the cross K/V into the slot's state rows.  A prefix
            # hit starts the slot at ``hit.tokens`` with the shared pages
            # already in its table: CoW-copy the divergent page, restore
            # the state snapshot, and prefill covers only the suffix.
            for slot, req, pages, hit in sched.admit(chunked=True):
                now = time.monotonic()
                t_sub = self._t_submit.get(req.rid, t0)
                self.tracer.complete("queue_wait", t_sub, now,
                                     tid=req.rid + 1,
                                     args={"rid": req.rid, "slot": slot})
                self.obs.observe("queue_wait_s", now - t_sub)
                cache = reset_slot(self.cfg, self.model, cache, slot,
                                   cross_kv=self._encode_req(steps, req),
                                   enc_len=req.group[1])
                table_np[slot] = 0
                push_table(slot)
                pos_np[slot] = sched.slots[slot].pos
                next_np[slot, 0] = 0
                prefills[slot] = sched.slots[slot].pos
                if hit is not None:
                    cache = self._apply_prefix_hit(cache, slot, hit)
                    self.metrics["prefix_hits"] += 1
                    self.metrics["prefix_hit_tokens"] += hit.tokens
                    self.metrics["pages_saved"] += \
                        hit.tokens // page.page_tokens
                    if hit.cow is not None:
                        self.metrics["cow_copies"] += 1
                        self.tracer.instant(
                            "cow_copy", tid=req.rid + 1,
                            args={"rid": req.rid, "slot": slot,
                                  "src": hit.cow[0], "dst": hit.cow[1]})
                elif prefix is not None:
                    self.metrics["prefix_misses"] += 1
                # A backfill is a NEW request taking a previously used
                # slot mid-flight; a preempted request's own recompute
                # re-admission is not one.
                if slot in ever_occupied and req.rid not in requeued:
                    self.metrics["backfills"] += 1
                requeued.discard(req.rid)
                ever_occupied.add(slot)
                progressed = True

            # Chunk phase: one chunk per prefilling slot per tick, BEFORE
            # the decode step -- a prefilling slot rides through the decode
            # batch (its garbage write at the chunk front is overwritten by
            # the next chunk; its recurrent state is restored below), so
            # chunks and decode ticks interleave instead of serializing.
            for slot in sorted(prefills):
                s = sched.slots[slot]
                if s is None or slot not in prefills:
                    continue                  # preempted by a sibling chunk
                req, plen = s.req, s.req.prompt_len
                done = prefills[slot]
                # A prefix hit can start mid-page; the first suffix chunk
                # realigns to the chunk grid (cold starts reduce to the
                # plain ``min(chunk, remaining)``).
                c = plen - done if chunk_tokens <= 0 else \
                    min(chunk_tokens - done % chunk_tokens, plen - done)
                if window and prefix is None:
                    # Behind the front.  With the prefix cache on, prompt
                    # pages must SURVIVE to insertion below -- window
                    # reclaim resumes at decode (the tree's reference then
                    # keeps them resident through it).
                    sched.reclaim_window(slot, window)
                # The final chunk also claims the page of the first decode
                # position: the slot decodes in this same tick, and a
                # prompt that ends on a page boundary would otherwise
                # write that token's K/V through an unallocated (null)
                # table entry, and lose it.
                upto = done + c + (1 if done + c >= plen else 0)
                grew = True
                while not sched.ensure_capacity(slot, upto=upto):
                    if sched.table_full(slot):
                        raise RuntimeError(
                            f"slot {slot}: prompt needs more than the "
                            f"{pages_per_slot}-page table")
                    victim = sched.victim(slot)
                    if victim is None:
                        if len(sched.active()) == 1:
                            raise RuntimeError(
                                f"page pool ({pool.pages_total - 1} pages)"
                                f" cannot hold one prefill chunk; "
                                f"raise kv_budget_bytes")
                        stalled.add(slot)
                        self.metrics["stalls"] += 1
                        grew = False
                        break
                    preempt(victim)
                if not grew:
                    continue                  # retry the chunk next tick
                peak_pages = max(peak_pages, pool.used_pages)
                if page.page_bytes > 0:
                    # CoW safety: every page this chunk writes must be
                    # PRIVATE (refcount 1) -- shared prefix pages sit
                    # strictly below the suffix front and are mapped
                    # read-only (see models/layers.paged_attention_block).
                    for j in range(done // page.page_tokens,
                                   -(-(done + c) // page.page_tokens)):
                        p = s.pages[j] if j < len(s.pages) else None
                        assert p is None or pool.refcount(p) == 1, \
                            f"chunk would write shared page {p} (rc=" \
                            f"{pool.refcount(p)})"
                push_table(slot)
                cache["table"] = jnp.asarray(table_np)
                toks = jnp.asarray(
                    np.asarray(req.features["tokens"][done:done + c],
                               np.int32))[None]
                tc0 = time.monotonic()
                logits, cache = steps.prefill_chunk(
                    self.params, cache, toks, jnp.int32(done),
                    jnp.int32(slot))
                self.tracer.complete(
                    "prefill_chunk", tc0, time.monotonic(),
                    tid=req.rid + 1,
                    args={"rid": req.rid, "slot": slot, "done": done,
                          "tokens": c})
                self.metrics["prefill_chunks"] += 1
                self.metrics["prefill_tokens"] += c
                trace.append(("chunk", slot, done, c))
                done += c
                prefills[slot] = done
                s.pos = done
                pos_np[slot] = done
                progressed = True
                if prefix is not None and prefix.has_state and \
                        cache.get("state") and \
                        done % page.page_tokens == 0:
                    # Page-boundary state snapshot (host copy): the radix
                    # node for this block restores it on a future hit.
                    chunk_snaps.setdefault(slot, {})[done] = jax.tree.map(
                        lambda a: (np.asarray(a[:, slot]) if a.ndim >= 2
                                   else np.asarray(a[slot])),
                        cache["state"])
                if done >= plen:
                    del prefills[slot]
                    if prefix is not None:
                        self.metrics["prefix_nodes_inserted"] += \
                            prefix.insert(
                                np.asarray(req.features["tokens"]),
                                list(s.pages),
                                snaps=chunk_snaps.pop(slot, None))
                    tok = int(np.asarray(
                        sample(logits, scfg,
                               step_key(scfg, step))).reshape(-1)[0])
                    step += 1
                    emit_token(slot, req.rid, req.max_new, tok)

            active = [i for i in sched.active()
                      if i not in stalled and i not in prefills]
            if active and page.page_bytes > 0 and prefix is not None:
                # CoW safety for decode writes: the write position's page
                # is always private (shared prefix pages end strictly
                # below the suffix, and positions only grow).
                for i in active:
                    s = sched.slots[i]
                    j = s.pos // page.page_tokens
                    p = s.pages[j] if j < len(s.pages) else None
                    assert p is None or pool.refcount(p) == 1, \
                        f"decode would write shared page {p} (rc=" \
                        f"{pool.refcount(p)})"
            if active:
                # Refresh the device-side page tables from the scheduler:
                # growth appended pages, reclaim nulled out-of-window ones.
                for i in sched.active():
                    push_table(i)
                cache["table"] = jnp.asarray(table_np)
                cache["pos"] = jnp.asarray(pos_np)
                # Stalled AND prefilling slots still ride through the
                # decode batch.  Their KV writes land on the null page or
                # at the chunk front (overwritten by the next chunk), but
                # RECURRENT state (Mamba/xLSTM) would advance on the
                # discarded tick and corrupt the slot on resume -- so
                # snapshot their state rows and restore them after the
                # step.
                frozen = sorted({i for i in (set(stalled) | set(prefills))
                                 if sched.slots[i] is not None})
                snapshot = None
                if frozen and cache.get("state"):
                    sl = jnp.asarray(frozen)
                    # Slot axis is 1 for layer-stacked buffers, 0 for
                    # per-slot vectors (enc-dec's ``enc_len``).
                    snapshot = jax.tree.map(
                        lambda a: a[:, sl] if a.ndim >= 2 else a[sl],
                        cache["state"])
                td0 = time.monotonic()
                logits, cache = steps.decode(
                    self.params, cache, {"tokens": jnp.asarray(next_np)})
                if snapshot is not None:
                    cache["state"] = jax.tree.map(
                        lambda ns, snap: (ns.at[:, sl].set(snap)
                                          if ns.ndim >= 2
                                          else ns.at[sl].set(snap)),
                        cache["state"], snapshot)
                self.tracer.complete("decode_tick", td0, time.monotonic(),
                                     tid=0, args={"active": len(active)})
                trace.append(("decode", tuple(active)))
                toks = np.asarray(
                    sample(logits, scfg, step_key(scfg, step))).reshape(-1)
                step += 1
                self.metrics["decode_steps"] += 1
                self.metrics["slot_steps"] += n_slots
                self.metrics["active_slot_steps"] += len(active)
                for i in active:
                    s = sched.slots[i]
                    s.pos += 1
                    pos_np[i] = s.pos
                    emit_token(i, s.rid, s.req.max_new, int(toks[i]))
                progressed = True

            peak_pages = max(peak_pages, pool.used_pages)
            if prefix is None:
                assert pool.used_pages == sched.used_pages_by_slots(), \
                    "page pool out of sync with the slot tables"
            else:
                # Shared pages carry one refcount per mapping: every slot
                # table entry plus every radix-tree node.
                assert pool.total_refs == sched.used_pages_by_slots() \
                    + prefix.n_pages, "refcount ledger out of sync"
            assert pool.pages_allocated - pool.pages_released == \
                pool.used_pages, "page accounting leak"
            if not progressed:
                raise RuntimeError("scheduler stalled with pending work")

        self.metrics["peak_resident_bytes"] = peak_pages * page.page_bytes
        self.metrics["peak_pages"] = peak_pages
        self.obs.set_max("pool_peak_pages", peak_pages, unit="pages")
        self.metrics["pages_allocated"] = pool.pages_allocated
        self.metrics["pages_released"] = pool.pages_released
        # Ring-buffer drop accounting (satellite of DESIGN.md §13): the
        # bounded interleave/token-time logs shed oldest entries instead
        # of growing without limit; surface how many were shed.
        self.obs.inc("interleave_dropped", trace.dropped)
        self.obs.inc("token_times_dropped",
                     sum(t.dropped for t in token_times.values()))
        if prefix is not None:
            seen = prefix.hits + prefix.misses
            self.metrics["prefix_hit_rate"] = \
                prefix.hits / seen if seen else 0.0
            self.metrics["prefix_resident_pages"] = prefix.n_pages
            self.metrics["prefix_resident_bytes"] = prefix.resident_bytes
            self.metrics["prefix_evicted_pages"] = prefix.evicted_pages
            self.metrics["prefix_budget_bytes"] = prefix.budget_bytes
            sess = self._paged_session
            if sess is not None and sess.pool is pool:
                sess.cache = cache    # carry the device pages forward
        self._finalize_utilization()
        return [outputs[r.rid] for r in reqs]

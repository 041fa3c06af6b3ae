"""The global KV page pool: plan-sized pages, per-slot tables, slot-level
admission (DESIGN.md §8).

PR 4's cohort engine made the plan's VMEM page the *growth* granule, but
allocation stayed per cohort: a finished slot's pages were pinned until its
whole cohort retired (or the next growth-boundary compaction).  This module
makes the page a real ALLOCATION unit across requests, the way hierarchical
runtimes own placement instead of the caller (Thibault et al.; Rasch's
(de/re)-composition):

  * ``PagePool`` -- the physical pool: ``pages_total`` pages of
    ``page_plan()["page_tokens"]`` tokens each, a free list, and cumulative
    alloc/release counters (the accounting the property tests pin).
    Physical page 0 is the reserved *null page*: empty slots' decode
    writes land there and nothing ever reads it unmasked.
  * ``PagedScheduler`` -- slot-level admission, pure python: a fixed batch
    of decode *slots*, FIFO admission of one request per free slot
    (``pages_for(prompt + 1)`` pages up front), one-page growth, youngest
    -slot recompute preemption, and sliding-window page reclaim (a page
    wholly below ``pos - window`` frees immediately -- the paged answer to
    the ring buffer).  A finished slot frees its pages at once and is
    backfilled by the next pending request mid-flight: continuous batching
    at slot granularity.
  * ``init_paged_cache`` / ``reset_slot`` -- the pooled cache pytree the
    paged decode step (``Model.decode_step_paged``) consumes: ``pool``
    (one shared ``(L, P, T, KV, D)`` buffer per attention-layer group, the
    head dim padded to whole lane rows; MLA's is a single ``lat`` latent
    buffer), ``state`` (per-slot recurrent/conv buffers, batch on axis 1;
    enc-dec adds per-slot cross K/V), ``table`` (the per-slot page table) and the per-slot position
    vector ``pos``.  ``reset_slot`` re-initializes a slot's state rows at
    admission; prompt KV reaches the pages via chunked prefill
    (``Model.prefill_chunk``), never via a post-prefill copy.

One decode jit bucket serves the whole run -- pool, table and slot count
are static shapes -- where the cohort engine retraces per capacity step.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

from repro.configs.base import ModelConfig
from repro.serve.kvcache import PageSpec
from repro.serve.scheduler import Request

PyTree = Any

#: Families with a per-slot paged decode path (``Model.decode_step_paged``).
#: MLA's latent cache pages like KV (one shared "lat" pool buffer) and
#: enc-dec pages its decoder self-attn KV (cross K/V is per-slot state --
#: it never grows).  Only vlm still falls back to cohort batching: its
#: 3-D mrope positions don't fit the per-slot position vector yet.
PAGED_FAMILIES = ("dense", "moe", "hybrid_ssm", "xlstm", "mla_moe",
                  "enc_dec")


# ---------------------------------------------------------------------------
# Physical pool
# ---------------------------------------------------------------------------


class PagePool:
    """Refcounting free-list allocator over the physical page pool.

    ``pages_total`` includes the reserved null page 0, which is never
    allocated or freed.  Every page carries a reference count: ``alloc``
    hands out pages at refcount 1, ``incref`` adds a read-only mapping
    (the prefix cache sharing one physical page into several slot tables
    and/or its radix tree), and ``free`` *decrefs* -- the page returns to
    the free list only when its last reference drops.  Freeing a page
    that holds no reference (double free, or a scheduler bug returning a
    page it never owned) raises instead of silently corrupting the free
    list.

    ``pages_allocated`` / ``pages_released`` count PHYSICAL transitions
    (free list -> used and back), so
    ``pages_allocated - pages_released == used_pages`` stays an invariant
    under sharing and ``assert_reconciled`` pins it after every op.
    """

    def __init__(self, pages_total: int, obs=None, tracer=None):
        if pages_total < 2:
            raise ValueError(
                f"pages_total must be >= 2 (null page + one usable page), "
                f"got {pages_total}")
        self.pages_total = int(pages_total)
        # pop() yields ascending physical ids -- deterministic layouts.
        self._free = list(range(self.pages_total - 1, 0, -1))
        self._rc = [0] * self.pages_total
        self.pages_allocated = 0
        self.pages_released = 0
        # Observability hooks (DESIGN.md §13): the pool is the single
        # writer of the occupancy gauges the engine's stats() view, the
        # cluster router's ``free_pages`` policy and the plan-vs-actual
        # report all read; alloc/free land in the trace as instants.
        self.obs = obs
        self.tracer = tracer
        self._publish()

    def _publish(self) -> None:
        if self.obs is not None:
            self.obs.set("free_pages", self.free_pages, unit="pages")
            self.obs.set("used_pages", self.used_pages, unit="pages")
            self.obs.set_max("pool_peak_pages", self.used_pages,
                             unit="pages")

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.pages_total - 1) - len(self._free)

    @property
    def total_refs(self) -> int:
        """Sum of live refcounts: slot-table references + prefix-tree
        references (the ledger the engine reconciles every tick)."""
        return sum(self._rc)

    def refcount(self, pid: int) -> int:
        return self._rc[pid]

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` physical pages at refcount 1, or None when the pool
        cannot hold them (never a partial grant)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for i in out:
            self._rc[i] = 1
        self.pages_allocated += n
        self._publish()
        if self.tracer is not None:
            self.tracer.instant("page_alloc",
                                args={"n": n, "free": self.free_pages})
        return out

    def incref(self, pid: int) -> None:
        """Add a reference to a LIVE page (a shared read-only mapping).
        Increffing a free page would resurrect it without removing it
        from the free list, so that raises."""
        if pid <= 0 or pid >= self.pages_total:
            raise ValueError(f"incref of invalid page id {pid}")
        if self._rc[pid] <= 0:
            raise ValueError(f"incref of free page {pid}")
        self._rc[pid] += 1

    def free(self, ids: Sequence[int]) -> None:
        """Drop one reference per id; a page returns to the free list
        (and counts as released) only at refcount zero."""
        for i in ids:
            if i == 0:
                raise ValueError("page 0 is the reserved null page")
            if i < 0 or i >= self.pages_total or self._rc[i] <= 0:
                raise ValueError(
                    f"double free (or free of never-allocated page) {i}")
            self._rc[i] -= 1
            if self._rc[i] == 0:
                self._free.append(i)
                self.pages_released += 1
        self._publish()
        if self.tracer is not None:
            self.tracer.instant("page_free",
                                args={"n": len(ids),
                                      "free": self.free_pages})

    def assert_reconciled(self) -> None:
        """Flow counters vs free list vs refcounts (the property tests'
        per-op pin)."""
        assert self.pages_allocated - self.pages_released == \
            self.used_pages, "page flow counters do not reconcile"
        assert len(set(self._free)) == len(self._free), \
            "free list holds a duplicate page"
        assert all(self._rc[i] == 0 for i in self._free), \
            "free list holds a referenced page"
        assert self._rc[0] == 0, "null page acquired a refcount"
        live = sum(1 for c in self._rc if c > 0)
        assert live == self.used_pages, \
            "refcounted pages do not match used pages"


# ---------------------------------------------------------------------------
# Slot-level scheduler
# ---------------------------------------------------------------------------


@dataclass
class SlotState:
    """One occupied decode slot.  ``pages`` maps logical page index ->
    physical page id, ``None`` marking a window-reclaimed page (its tokens
    fell out of the sliding window; the table keeps pointing at the null
    page and the kernel's window mask never reads them)."""

    rid: int
    req: Request
    pos: int                        # resident tokens (prompt, then +1/step)
    pages: List[Optional[int]] = field(default_factory=list)

    @property
    def live_pages(self) -> List[int]:
        return [p for p in self.pages if p is not None]


class PagedScheduler:
    """Slot-level admission under the page-pool budget (pure python).

    The schedulable unit is one SLOT of a fixed decode batch -- not a
    cohort -- so a finished sequence's pages free immediately and the slot
    is backfilled by the next pending request between decode ticks.
    Rules:

      * **admit**   FIFO: the head request takes any free slot iff the pool
        can grant its LIVE page demand -- ``pages_for(prompt + 1)`` minus
        the pages wholly below ``prompt - window`` for sliding-window
        families (those logical pages are born reclaimed: placeholder
        ``None`` entries, never allocated, masked by the kernel), and 0
        for token-free families.  A lone head that can never fit an empty
        pool raises.
      * **grow**    one page per slot when ``pos + 1`` crosses the slot's
        capacity; refusal (pool empty) makes the engine preempt or stall.
      * **victim**  the slot holding the newest request strictly younger
        than the grower's (least sunk cost; rids survive requeueing so a
        preempted request keeps its seniority).  A grower with no younger
        victim STALLS for the tick instead -- pages pinned, decode
        skipped -- so mutual eviction ping-pong cannot happen and the
        oldest request always progresses.
      * **reclaim** pages wholly below ``pos - window`` free immediately
        (sliding-window families only).
    """

    def __init__(self, pool: PagePool, page: PageSpec, n_slots: int,
                 pages_per_slot: int, window: int = 0, prefix=None):
        self.pool = pool
        self.page = page
        self.n_slots = max(1, n_slots)
        self.pages_per_slot = max(1, pages_per_slot)
        self.window = max(0, window)
        self.prefix = prefix            # serve.prefix.RadixPrefixCache|None
        self.slots: List[Optional[SlotState]] = [None] * self.n_slots
        self.pending: Deque[Request] = deque()
        self.n_evictions = 0

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Pool alloc with prefix-cache back-pressure: when the free list
        cannot grant ``n`` pages, evict unreferenced radix-tree pages (LRU)
        before giving up -- live slots outrank cached prefixes."""
        ids = self.pool.alloc(n)
        if ids is None and self.prefix is not None:
            self.prefix.release_pages(need=n)
            ids = self.pool.alloc(n)
        return ids

    # ----------------------------------------------------------- inventory
    def has_work(self) -> bool:
        return bool(self.pending) or any(s is not None for s in self.slots)

    def active(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def used_pages_by_slots(self) -> int:
        return sum(len(s.live_pages) for s in self.slots if s is not None)

    def _admit_pages(self, req: Request) -> Tuple[int, int]:
        """``(live, dead)`` logical page counts at admission: only ``live``
        pages are allocated; ``dead`` pages are wholly below
        ``prompt - window`` (their tokens can never attend) and enter the
        slot as ``None`` placeholders -- the same state window reclaim
        leaves behind -- so a long windowed prompt is billed for its
        RESIDENT window, not its full length."""
        if self.page.page_bytes <= 0:
            return 0, 0                   # token-free family (xLSTM)
        total = self.page.pages_for(req.prompt_len + 1)
        dead = 0
        if self.window:
            dead = max(0, req.prompt_len - self.window) \
                // self.page.page_tokens
        return total - dead, dead

    # ----------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        self.pending.append(req)

    def admit(self, chunked: bool = False
              ) -> List[Tuple[int, Request, List[Optional[int]], Any]]:
        """Fill free slots from the queue head.  Returns
        ``[(slot, request, logical_pages, hit), ...]`` where
        ``logical_pages`` maps logical page index -> physical id, with
        ``None`` marking born-reclaimed out-of-window pages, and ``hit``
        is the ``serve.prefix.PrefixHit`` this admission matched (None
        without a prefix cache or on a miss); the engine prefills each
        request and installs it into its slot.

        ``chunked`` admits for CHUNKED prefill: the slot starts at
        ``pos = 0`` with only its FIRST page allocated -- the engine grows
        it page by page ahead of the chunk front (``ensure_capacity(slot,
        upto=...)``) and window-reclaims behind it, so a long windowed
        prompt's peak page usage is its resident window, same as the
        monolithic admission bill.  With a prefix cache attached, chunked
        admission first consults the radix tree: a hit starts the slot at
        ``pos = hit.tokens`` with the shared prefix pages mapped read-only
        (increffed) into its table -- prefill covers only the unshared
        suffix."""
        out: List[Tuple[int, Request, List[Optional[int]], Any]] = []
        for slot, s in enumerate(self.slots):
            if s is not None or not self.pending:
                continue
            head = self.pending[0]
            live, dead = self._admit_pages(head)
            if chunked:
                hit = None
                if self.prefix is not None and head.features and \
                        "tokens" in head.features:
                    import numpy as np
                    hit = self.prefix.admit(
                        np.asarray(head.features["tokens"]).reshape(-1))
                if hit is not None:
                    self.pending.popleft()
                    self.slots[slot] = SlotState(
                        rid=head.rid, req=head, pos=hit.tokens,
                        pages=list(hit.pages))
                    out.append((slot, head, list(hit.pages), hit))
                    continue
                first = min(live, 1)
                ids = self._alloc(first)
                if ids is None and first:
                    if not any(x is not None for x in self.slots) and not out:
                        raise ValueError(
                            f"request {head.rid} needs at least 1 KV page; "
                            f"the pool holds {self.pool.pages_total - 1} -- "
                            f"raise kv_budget_bytes")
                    break
                self.pending.popleft()
                self.slots[slot] = SlotState(rid=head.rid, req=head,
                                             pos=0, pages=list(ids or []))
                out.append((slot, head, list(ids or []), None))
                continue
            ids = self._alloc(live)
            if ids is None:
                if not any(x is not None for x in self.slots) and not out:
                    raise ValueError(
                        f"request {head.rid} needs {live} KV pages; the "
                        f"pool holds {self.pool.pages_total - 1} -- raise "
                        f"kv_budget_bytes or shorten the prompt")
                break                     # wait for running slots to free
            self.pending.popleft()
            pages: List[Optional[int]] = [None] * dead + list(ids)
            self.slots[slot] = SlotState(rid=head.rid, req=head,
                                         pos=head.prompt_len,
                                         pages=pages)
            out.append((slot, head, list(pages), None))
        return out

    # -------------------------------------------------------------- growth
    def ensure_capacity(self, slot: int, upto: Optional[int] = None) -> bool:
        """Make room in ``slot`` for tokens up to position ``upto``
        (exclusive; default ``pos + 1`` -- one more decode token).  Grows
        page by page.  True when capacity exists or was granted; False
        when the pool is exhausted (the engine then preempts and retries)
        or the slot's logical page table is full (``pages_per_slot`` --
        check ``table_full`` to tell the cases apart: eviction cannot
        help a full table).  Chunked prefill passes ``upto = done +
        chunk`` to allocate just ahead of the chunk front."""
        s = self.slots[slot]
        if self.page.page_bytes <= 0:
            return True
        need = s.pos + 1 if upto is None else upto
        while need > len(s.pages) * self.page.page_tokens:
            if len(s.pages) >= self.pages_per_slot:
                return False
            ids = self._alloc(1)
            if ids is None:
                return False
            s.pages.extend(ids)
        return True

    def table_full(self, slot: int) -> bool:
        """True when the slot has exhausted its logical page table (its
        sequence hit the ``pages_per_slot`` bound)."""
        s = self.slots[slot]
        return self.page.page_bytes > 0 and len(s.pages) >= \
            self.pages_per_slot

    def victim(self, protect: int) -> Optional[int]:
        """Preemption victim: the occupied slot holding the newest request
        STRICTLY YOUNGER than ``protect``'s (rids are assigned at
        submission and survive requeueing, so re-admitted requests keep
        their seniority).  Restricting victims to younger slots is what
        makes preemption livelock-free: two growing slots can never evict
        each other in a ping-pong -- the younger one *stalls* (keeps its
        pages, skips the tick) until the older finishes, and the oldest
        slot always makes progress."""
        mine = self.slots[protect].rid
        others = [i for i, s in enumerate(self.slots)
                  if s is not None and i != protect and s.rid > mine]
        if not others:
            return None
        return max(others, key=lambda i: self.slots[i].rid)

    def evict(self, slot: int) -> Request:
        """Recompute preemption: free the slot's pages and requeue its
        request at the FRONT of the queue."""
        s = self.slots[slot]
        self.pool.free(s.live_pages)
        self.slots[slot] = None
        self.pending.appendleft(s.req)
        self.n_evictions += 1
        return s.req

    # ---------------------------------------------------------- retirement
    def finish(self, slot: int) -> None:
        s = self.slots[slot]
        self.pool.free(s.live_pages)
        self.slots[slot] = None

    def reclaim_window(self, slot: int, window: int) -> List[int]:
        """Free pages wholly below ``pos - window`` (their tokens can never
        attend again).  The page table keeps its logical shape; freed
        entries are masked by the kernel's window mask even after the
        physical page is rewritten by another slot."""
        s = self.slots[slot]
        if not window or self.page.page_bytes <= 0:
            return []
        lo = s.pos - window
        freed: List[int] = []
        for j, p in enumerate(s.pages):
            if p is not None and (j + 1) * self.page.page_tokens <= lo:
                freed.append(p)
                s.pages[j] = None
        if freed:
            self.pool.free(freed)
        return freed


# ---------------------------------------------------------------------------
# Pooled cache pytree
# ---------------------------------------------------------------------------


def _n_attn_apps(cfg: ModelConfig) -> int:
    s = cfg.ssm
    return -(-cfg.n_layers // s.attn_every) if (s and s.attn_every) else 0


#: Lane width of a TPU vector register: the minor dim of every VMEM tile.
POOL_LANES = 128


def pool_head_dim(head_dim: int) -> int:
    """Head dim of a K/V page pool: ``head_dim`` padded to whole lane rows.

    The paged kernel reads pages row-major, head dim on the lanes.  A pool
    whose head dim is narrower than a lane row would be given another HBM
    layout by default (tokens on the lanes), and every step would then
    convert the whole pool to row-major around the kernel: two more
    copies of the pool in HBM.  Padded, row-major is the default layout
    and costs what the tiles would pad anyway.  The pad lanes stay zero."""
    return -(-head_dim // POOL_LANES) * POOL_LANES


def pool_page_bytes(cfg: ModelConfig, page: PageSpec) -> int:
    """HBM bytes of one pool page: ``page.page_bytes`` with the K/V head
    dim as the pool stores it (``pool_head_dim``; MLA's latent is kept
    unpadded)."""
    hd = cfg.head_dim
    if cfg.mla is not None or not hd:
        return page.page_bytes
    return page.page_bytes * pool_head_dim(hd) // hd


def init_paged_cache(cfg: ModelConfig, model, n_slots: int, n_pages: int,
                     page_tokens: int, n_logical_pages: int,
                     dtype, enc_len: int = 0) -> PyTree:
    """The pooled cache pytree ``Model.decode_step_paged`` consumes.

    ``pool`` holds the shared page pool per attention-layer group
    (``(L, n_pages, page_tokens, KV, D)`` -- MLA's is one ``lat`` buffer
    of ``concat(ckv, k_rope)`` rows with a single shared latent head),
    ``state`` the per-slot recurrent/conv buffers (batch on axis 1, taken
    from the family's ``init_cache`` shapes; enc-dec adds the per-slot
    cross K/V -- sized ``enc_len``, the max encoder length this run
    serves -- and its valid-length vector), ``table`` the
    ``(n_slots, n_logical_pages)`` page table (0 = null page) and ``pos``
    the per-slot position vector.
    """
    import jax.numpy as jnp

    fam = cfg.family
    if fam not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged serving is not implemented for family {fam!r}")
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    pool_hd = pool_head_dim(hd)

    def pool_kv(nl):
        return {"k": jnp.zeros((nl, n_pages, page_tokens, kv, pool_hd),
                               dtype),
                "v": jnp.zeros((nl, n_pages, page_tokens, kv, pool_hd),
                               dtype)}

    cache: Dict[str, Any] = {
        "table": jnp.zeros((n_slots, n_logical_pages), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
        "pool": {},
        "state": {},
    }
    if fam in ("dense", "moe"):
        cache["pool"] = pool_kv(cfg.n_layers)
    elif fam == "mla_moe":
        m = cfg.mla
        lat_dim = m.kv_lora_rank + m.rope_head_dim
        cache["pool"] = {"lat": jnp.zeros(
            (cfg.n_layers, n_pages, page_tokens, 1, lat_dim), dtype)}
    elif fam == "hybrid_ssm":
        base = model.init_cache(n_slots, page_tokens, dtype)
        cache["state"] = {"mamba": base["mamba"]}
        n_apps = _n_attn_apps(cfg)
        if n_apps:
            cache["pool"] = pool_kv(n_apps)
    elif fam == "xlstm":
        base = model.init_cache(n_slots, page_tokens, dtype)
        cache["state"] = {"mlstm": base["mlstm"], "slstm": base["slstm"]}
    elif fam == "enc_dec":
        nd = cfg.enc_dec.n_decoder_layers
        cache["pool"] = pool_kv(nd)
        cache["state"] = {
            "cross_k": jnp.zeros((nd, n_slots, enc_len, kv, hd), dtype),
            "cross_v": jnp.zeros((nd, n_slots, enc_len, kv, hd), dtype),
            "enc_len": jnp.zeros((n_slots,), jnp.int32),
        }
    return cache


#: Per-slot recurrent-state groups per family (reset at admission).
_STATE_GROUPS = {"hybrid_ssm": ("mamba",), "xlstm": ("mlstm", "slstm")}


def reset_slot(cfg: ModelConfig, model, cache: PyTree, slot: int,
               cross_kv: Optional[Tuple[Any, Any]] = None,
               enc_len: int = 0) -> PyTree:
    """Reset one slot's per-slot state rows for a fresh (chunked) prefill.

    Chunked prefill writes KV straight into pool pages, so admission only
    has to (a) reset the slot's recurrent/conv state rows to the family's
    ``init_cache`` values -- NOT zeros: mLSTM/sLSTM stabilizer rows
    initialize to the running-max floor -- and (b) for enc-dec, install
    the request's pre-computed cross K/V (``cross_kv``: ``(nd, 1, Se, KV,
    HD)`` each) and its valid length.  The pool itself needs no reset:
    chunk writes land exactly on the slot's allocated pages.
    """
    import jax

    state_groups = _STATE_GROUPS.get(cfg.family, ())
    new_cache = dict(cache)
    if state_groups:
        fresh = model.init_cache(1, cache["pool"]["k"].shape[2]
                                 if cache.get("pool") else 1,
                                 jax.tree.leaves(cache["state"])[0].dtype)
        state = dict(cache["state"])
        for g in state_groups:
            state[g] = jax.tree.map(
                lambda dst, src: dst.at[:, slot].set(
                    src[:, 0].astype(dst.dtype)),
                state[g], fresh[g])
        new_cache["state"] = state
    if cfg.family == "enc_dec":
        import jax.numpy as jnp

        ck, cv = cross_kv
        state = dict(new_cache["state"])
        se = ck.shape[2]
        for name, src in (("cross_k", ck), ("cross_v", cv)):
            dst = state[name]
            row = jnp.zeros(dst.shape[:1] + dst.shape[2:], dst.dtype)
            row = row.at[:, :se].set(src[:, 0].astype(dst.dtype))
            state[name] = dst.at[:, slot].set(row)
        state["enc_len"] = state["enc_len"].at[slot].set(enc_len)
        new_cache["state"] = state
    return new_cache


# ---------------------------------------------------------------------------
# Sharding axes for the pooled layout (consumed by serve.steps)
# ---------------------------------------------------------------------------


def paged_cache_logical_axes(cfg: ModelConfig, cache: PyTree) -> PyTree:
    """Logical sharding axes for the pooled cache: pool KV shards over
    heads exactly like the dense cache (``with_kv_sharding`` decides
    whether "kv_heads" maps to the model axis); the page dim ("kv_pages")
    is a pool dim and never shards -- a page is the VMEM streaming granule
    of ONE chip.  Per-slot state reuses the dense cache's axis names via
    ``launch.specs.cache_logical_axes``."""
    from repro.launch.specs import cache_logical_axes

    axes: Dict[str, Any] = {
        "table": (None, None),
        "pos": (None,),
        "pool": {},
        "state": {},
    }
    if cache.get("pool"):
        pool_ax = ("layers", "kv_pages", None, "kv_heads", None)
        axes["pool"] = {name: pool_ax[:cache["pool"][name].ndim]
                        for name in cache["pool"]}
    if cache.get("state"):
        state = dict(cache["state"])
        cross = {}
        for name in ("cross_k", "cross_v"):
            if name in state:
                state.pop(name)
                cross[name] = ("layers", None, None, "kv_heads", None)
        if "enc_len" in state:
            state.pop("enc_len")
            cross["enc_len"] = (None,)
        axes["state"] = cache_logical_axes(cfg, state, False) if state \
            else {}
        axes["state"].update(cross)
    return axes


# ---------------------------------------------------------------------------
# Page serialization (prefill/decode disaggregation, repro.cluster)
# ---------------------------------------------------------------------------


def export_pool_pages(cache: PyTree, page_ids: Sequence[int]) -> List[Dict[str, Any]]:
    """Serialize physical pool pages as host arrays, one payload per page.

    Each payload maps the pool's buffer names ("k"/"v", or "lat" for MLA)
    to an ``(L, page_tokens, KV, D)`` numpy array -- the full cross-layer
    slice of ONE physical page.  This is the wire unit of prefill/decode
    disaggregation: a prefill replica exports the pages a finished prompt
    occupies and streams them (in ring order) to a decode replica, which
    installs them into its own pool under fresh physical ids.  Payloads
    are keyed by *position in the logical page chain*, never by physical
    id: physical numbering is private to each replica's pool.
    """
    import numpy as np

    payloads: List[Dict[str, Any]] = []
    for pid in page_ids:
        payloads.append({name: np.asarray(buf[:, int(pid)])
                         for name, buf in cache["pool"].items()})
    return payloads


def install_pool_pages(cache: PyTree, page_ids: Sequence[int],
                       payloads: Sequence[Dict[str, Any]]) -> PyTree:
    """Install serialized page payloads into this pool's physical pages.

    ``page_ids`` are freshly allocated pages in the *receiving* pool
    (same length as ``payloads``); the i-th payload lands on the i-th
    page.  Buffer names and per-page shapes must match the receiving
    pool's layout -- geometry comes from the same ``HierarchicalPlan``
    on both sides, so a mismatch means the replicas were planned against
    different hierarchies and is an error, not a fallback.
    """
    import jax.numpy as jnp

    if len(page_ids) != len(payloads):
        raise ValueError(f"{len(page_ids)} pages for {len(payloads)} payloads")
    pool = dict(cache["pool"])
    for name in pool:
        buf = pool[name]
        for pid, payload in zip(page_ids, payloads):
            if name not in payload:
                raise ValueError(f"payload missing pool buffer {name!r}")
            data = jnp.asarray(payload[name], buf.dtype)
            if data.shape != buf.shape[:1] + buf.shape[2:]:
                raise ValueError(
                    f"page payload {name!r} shape {data.shape} != pool "
                    f"page shape {buf.shape[:1] + buf.shape[2:]}")
            buf = buf.at[:, int(pid)].set(data)
        pool[name] = buf
    new_cache = dict(cache)
    new_cache["pool"] = pool
    return new_cache

"""Acceptance tests for the cross-request radix prefix cache (ISSUE 8,
DESIGN.md §11).

* **Pool refcount guards** -- ``PagePool.free`` is a decref: double
  frees and frees of never-allocated pages raise instead of silently
  corrupting the free list, and a page shared by incref stays OUT of the
  free list until its last reference drops.
* **Radix-tree properties** -- under random insert / match / evict / pool
  -pressure sequences the invariants hold after every op: refcounts equal
  the number of references (simulated slot tables + tree nodes), pool
  flow counters reconcile (``assert_reconciled``), and the resident tree
  never exceeds ``prefix_budget``.
* **Token identity** -- for all four served families, greedy generation
  with ``prefix_cache="radix"`` is token-identical to the cold-cache run
  when the shared prefix ends mid-page (forcing the CoW path on
  attention families) and exactly on a page boundary -- with the engine
  metrics pinning the hit length (``prefix_hit_tokens == N``, rounded
  down to page granularity for recurrent-state families), pages saved
  and the CoW count.
* **Cross-call persistence** -- the radix tree and its device pages
  survive between ``generate`` calls: a second call's request hits a
  prefix inserted by the first call.
* **Plan accessor** -- ``plan.prefix_budget()`` reads the page level's
  recorded HBM leftover and survives JSON round-trips (including plans
  serialized before the field existed).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_model_config
from repro.hw.tpu import chip_spec
from repro.launch.mesh import make_host_mesh
from repro.serve import ServeEngine, ServePolicy

#: Tiny forced VMEM so the planned page is small and sharing/CoW is
#: exercised with short prompts (as in test_serve_paged).
SMALL = dict(vmem_bytes=800 << 10, vmem_reserved_bytes=0)

#: One arch per served family: dense, MoE (sliding-window), hybrid SSM,
#: xLSTM (token-free -- state snapshots only).
FOUR_FAMILIES = ["llama3.2-1b", "mixtral-8x7b", "zamba2-1.2b", "xlstm-1.3b"]

#: Families whose hits restore a recurrent-state snapshot and therefore
#: round DOWN to page boundaries (serve.prefix.STATE_FAMILIES).
STATE_ARCHS = {"zamba2-1.2b", "xlstm-1.3b"}


# ---------------------------------------------------------------------------
# Satellite: PagePool refcount guards
# ---------------------------------------------------------------------------


class TestPoolRefcounts:
    def test_double_free_raises(self):
        from repro.serve.pages import PagePool

        pool = PagePool(5)
        ids = pool.alloc(2)
        pool.free(ids)
        with pytest.raises(ValueError, match="double free"):
            pool.free([ids[0]])

    def test_free_of_never_allocated_page_raises(self):
        from repro.serve.pages import PagePool

        pool = PagePool(5)
        pool.alloc(1)
        with pytest.raises(ValueError, match="double free|never-allocated"):
            pool.free([3])                # page 3 was never handed out

    def test_null_page_free_raises(self):
        from repro.serve.pages import PagePool

        with pytest.raises(ValueError, match="null page"):
            PagePool(5).free([0])

    def test_shared_page_survives_first_free(self):
        from repro.serve.pages import PagePool

        pool = PagePool(5)
        (pid,) = pool.alloc(1)
        pool.incref(pid)                  # second mapping (rc=2)
        before = pool.free_pages
        pool.free([pid])                  # decref: still referenced
        assert pool.free_pages == before
        assert pool.refcount(pid) == 1
        assert pool.used_pages == 1       # physically still used
        pool.free([pid])                  # last reference: really freed
        assert pool.refcount(pid) == 0
        assert pool.used_pages == 0
        with pytest.raises(ValueError, match="double free"):
            pool.free([pid])
        pool.assert_reconciled()

    def test_incref_of_free_page_raises(self):
        from repro.serve.pages import PagePool

        pool = PagePool(5)
        with pytest.raises(ValueError, match="free page"):
            pool.incref(1)                # never allocated
        with pytest.raises(ValueError, match="invalid page"):
            pool.incref(0)                # the null page


# ---------------------------------------------------------------------------
# Satellite: radix-tree property test
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000),
       page_tokens=st.sampled_from([4, 8]),
       pool_pages=st.integers(6, 24),
       budget_pages=st.integers(1, 8),
       vocab=st.sampled_from([2, 3]))
def test_radix_tree_invariants(seed, page_tokens, pool_pages, budget_pages,
                               vocab):
    """Random insert/match/evict sequences against a simulated slot
    population.  After EVERY operation:

      * ``pool.total_refs`` equals the number of slot-table references
        plus the tree's page references (every mapping is one refcount);
      * pool flow counters reconcile (``assert_reconciled``: cumulative
        alloc - release == used, free list duplicate-free, refcounts
        consistent with the free list);
      * the resident tree never exceeds the ``prefix_budget`` it was
        given (evicting down to the budget on every insert).

    The tiny vocabulary makes random prompts collide on prefixes, so the
    hit path (increfs + CoW allocation) is genuinely exercised."""
    from repro.serve.pages import PagePool
    from repro.serve.prefix import RadixPrefixCache

    rng = np.random.default_rng(seed)
    t = page_tokens
    page_bytes = t * 16
    pool = PagePool(pool_pages + 1)       # +1: reserved null page 0
    cache = RadixPrefixCache(t, page_bytes, budget_pages * page_bytes,
                             pool, has_state=False)
    tables = {}                           # sid -> simulated slot pages
    next_sid = 0

    def check(inflight=0):
        # ``inflight``: references held by a request mid-prefill, before
        # its page table is published into ``tables``.
        pool.assert_reconciled()
        slot_refs = sum(len(v) for v in tables.values())
        assert pool.total_refs == slot_refs + cache.n_pages + inflight, \
            "refcounts out of sync with references"
        assert cache.resident_bytes <= cache.budget_bytes, \
            "resident tree exceeded prefix_budget"
        assert cache.n_pages * page_bytes <= cache.resident_bytes + 1e-9

    for _ in range(60):
        op = rng.random()
        if op < 0.5:
            # "Run a request": match, then allocate the suffix pages the
            # way chunked prefill would, publish on completion.
            plen = int(rng.integers(1, 5 * t))
            toks = rng.integers(0, vocab, plen).astype(np.int64)
            hit = cache.admit(toks)
            pages = list(hit.pages) if hit else []
            check(inflight=len(pages))
            aborted = False
            while len(pages) * t < plen + 1:
                ids = pool.alloc(1)
                if ids is None:
                    cache.release_pages(need=1)
                    ids = pool.alloc(1)
                if ids is None:
                    # Pool exhausted mid-prefill: recompute preemption --
                    # drop every reference this request took.
                    pool.free(pages)
                    aborted = True
                    break
                pages.extend(ids)
            check(inflight=0 if aborted else len(pages))
            if aborted:
                continue
            cache.insert(toks, pages)
            tables[next_sid] = pages
            next_sid += 1
        elif op < 0.8 and tables:
            # Finish a request: its slot's references drop; pages the
            # tree also holds stay resident.
            sid = rng.choice(list(tables))
            pool.free(tables.pop(sid))
        else:
            # Pool pressure / explicit eviction.
            cache.release_pages(need=int(rng.integers(1, 4)))
        check()

    # Drain: finish every request, then evict the whole tree -- the pool
    # must reconcile back to empty (alloc == release, no leaked refs).
    for pages in tables.values():
        pool.free(pages)
    tables.clear()
    cache.clear()
    check()
    assert pool.used_pages == 0
    assert pool.total_refs == 0
    assert pool.pages_allocated == pool.pages_released


# ---------------------------------------------------------------------------
# Token identity: prefix-hit generation == cold-cache generation
# ---------------------------------------------------------------------------


def _engine(cfg, prefix, max_len, max_slots=1):
    return ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=4, max_len=max_len,
                           max_slots=max_slots, batching="paged",
                           prefix_cache=prefix),
        spec=chip_spec(**SMALL))


def _shared_prefix_prompts(cfg, T, geometry, rng):
    """Two prompts sharing ``N`` tokens: ``N = 2.5 pages`` (mid-page --
    the divergence point is inside a completed page, forcing CoW on
    attention families) or ``N = 2 pages`` (exact boundary).  Tails are
    long enough that the FIRST request's divergent page completes (only
    completed pages enter the tree) and differ at their first token."""
    n = 2 * T + (T // 2 if geometry == "mid_page" else 0)
    shared = rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
    tails = [rng.integers(0, cfg.vocab_size, T // 2 + 2, dtype=np.int32)
             for _ in range(2)]
    tails[1][0] = (tails[0][0] + 1) % cfg.vocab_size
    return n, [np.concatenate([shared, t]) for t in tails]


@pytest.mark.parametrize("geometry", ["mid_page", "page_boundary"])
@pytest.mark.parametrize("arch", FOUR_FAMILIES)
def test_prefix_hit_token_identity(arch, geometry):
    cfg = get_model_config(arch).reduced()
    rng = np.random.default_rng(0xA11CE)
    probe = _engine(cfg, "off", max_len=64)
    T = probe.page.page_tokens
    max_len = 4 * T + 8
    n, prompts = _shared_prefix_prompts(cfg, T, geometry, rng)
    # max_slots=1 serializes the two requests through one slot, so the
    # second admission sees the first request's published prefix.
    cold = _engine(cfg, "off", max_len).generate(prompts, max_new_tokens=4)
    warm_eng = _engine(cfg, "radix", max_len)
    warm = warm_eng.generate(prompts, max_new_tokens=4)
    assert warm == cold, f"{arch}/{geometry}: prefix hit changed tokens"

    m = warm_eng.metrics
    assert m["prefix_hits"] == 1
    # Attention families reuse the shared prefix exactly (CoW inside the
    # divergent page); recurrent-state families round down to the page
    # boundary where a state snapshot exists.
    expect = (n // T) * T if arch in STATE_ARCHS else n
    assert m["prefix_hit_tokens"] == expect, \
        f"{arch}/{geometry}: hit {m['prefix_hit_tokens']} != {expect}"
    assert m["pages_saved"] > 0
    if geometry == "mid_page" and arch not in STATE_ARCHS:
        assert m["cow_copies"] == 1, "mid-page divergence must CoW"
    else:
        assert m["cow_copies"] == 0
    # The suffix is the only prefill the second request ran.
    plen = len(prompts[0])
    assert m["prefill_tokens"] == plen + (plen - expect)


def test_prefix_cache_persists_across_generate_calls():
    cfg = get_model_config("llama3.2-1b").reduced()
    rng = np.random.default_rng(0xBEEF)
    eng = _engine(cfg, "radix", max_len=136)
    T = eng.page.page_tokens
    shared = rng.integers(0, cfg.vocab_size, 3 * T, dtype=np.int32)
    tails = [rng.integers(0, cfg.vocab_size, T - 2, dtype=np.int32)
             for _ in range(2)]
    tails[1][0] = (tails[0][0] + 1) % cfg.vocab_size
    a, b = [np.concatenate([shared, t]) for t in tails]
    out_a = eng.generate([a], max_new_tokens=4)
    assert eng.metrics["prefix_hits"] == 0
    out_b = eng.generate([b], max_new_tokens=4)
    assert eng.metrics["prefix_hits"] == 1
    assert eng.metrics["prefix_hit_tokens"] == 3 * T
    # And the hit run emits exactly what a cold engine emits.
    cold = _engine(cfg, "off", max_len=136)
    assert cold.generate([a], max_new_tokens=4) == out_a
    assert cold.generate([b], max_new_tokens=4) == out_b


def test_identical_prompt_rehit_cows_final_page():
    """A fully-cached prompt still computes its LAST token (the logits
    source): the hit caps at ``prompt_len - 1`` and CoWs the final
    matched page instead of replaying the whole prompt."""
    cfg = get_model_config("llama3.2-1b").reduced()
    rng = np.random.default_rng(3)
    eng = _engine(cfg, "radix", max_len=136)
    T = eng.page.page_tokens
    prompt = rng.integers(0, cfg.vocab_size, 3 * T, dtype=np.int32)
    out1 = eng.generate([prompt], max_new_tokens=4)
    out2 = eng.generate([prompt], max_new_tokens=4)
    assert out1 == out2
    m = eng.metrics
    assert m["prefix_hits"] == 1
    assert m["prefix_hit_tokens"] == 3 * T - 1
    assert m["cow_copies"] == 1


# ---------------------------------------------------------------------------
# Plan accessor
# ---------------------------------------------------------------------------


def test_prefix_budget_accessor_and_roundtrip():
    from repro.core.plan import HierarchicalPlan

    cfg = get_model_config("llama3.2-1b").reduced()
    eng = _engine(cfg, "off", max_len=64)
    plan = eng.plan
    ptab = plan.page_table()
    assert ptab is not None and "prefix_budget_bytes" in ptab
    budget = plan.prefix_budget()
    assert budget == ptab["prefix_budget_bytes"] and budget > 0
    # JSON round-trip preserves it.
    rt = HierarchicalPlan.from_json(plan.to_json())
    assert rt.prefix_budget() == budget
    # Plans serialized BEFORE the field existed fall back to the
    # pages_total x global-page-bytes product.
    d = rt.to_dict()

    def strip(node):
        if node is None:
            return
        pt = (node.get("detail") or {}).get("page_table")
        if pt is not None:
            pt.pop("prefix_budget_bytes", None)
        strip(node.get("child"))

    strip(d)
    legacy = HierarchicalPlan.from_dict(d)
    page = legacy.page_plan()
    expect = (legacy.page_table()["pages_total"] * page["page_tokens"]
              * page["tok_bytes"] * page["layers"] * page["kv_shard"])
    assert legacy.prefix_budget() == expect


def test_xlstm_prefix_budget_is_none():
    """Token-free families have no page level: the accessor returns None
    and the engine falls back to the scheduler budget."""
    cfg = get_model_config("xlstm-1.3b").reduced()
    eng = _engine(cfg, "off", max_len=64)
    assert eng.plan.prefix_budget() is None

"""Differential-testing net over chunked prefill (DESIGN.md §10).

Four nets, one per failure mode the chunked path could introduce:

* **Chunk-decomposition identity** -- for every served family (incl. the
  newly paged MLA and enc-dec), cutting the prompt into planned-page
  chunks must produce exactly the tokens of the whole-prompt
  (monolithic) run through the same direct-to-pool path.  Prompt lengths
  are chosen so ``prompt_len % page_tokens != 0``: the partial final
  chunk is its own jit bucket and the most likely place for an
  off-by-one.
* **Interleave** -- a resident decode slot keeps emitting tokens while a
  long prompt prefills chunk by chunk (the engine trace shows decode
  events BETWEEN chunk events, at most one chunk per slot between
  consecutive decodes), and prefill never stages KV outside the pool
  (``install_slot`` is gone; the chunks' pages ARE the decode cache).
* **Scheduler properties** -- under randomized admission / preemption /
  chunk / reclaim sequences, page-flow counters reconcile every tick, a
  decode slot stalls only when eviction provably cannot help, and every
  request still completes with its exact token count.
* **One layer body** -- cohort prefill, chunked prefill, cohort decode
  and paged decode all execute the SAME ``_tf_layer`` function object
  (the PR's refactor), and a chunk-written pool reads identically under
  the Pallas paged kernel and the ``kernels/ref.py`` gather.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_model_config
from repro.hw.tpu import chip_spec
from repro.launch.mesh import make_host_mesh
from repro.serve import ServeEngine, ServePolicy

#: Tiny forced VMEM so the planned page (= prefill chunk) is small and the
#: chunk loop actually runs several iterations per prompt.
SMALL = dict(vmem_bytes=800 << 10, vmem_reserved_bytes=0)

#: Every family with a paged decode path (serve.pages.PAGED_FAMILIES).
PAGED_ARCHS = [
    "llama3.2-1b",        # dense
    "mixtral-8x7b",       # moe + sliding window
    "deepseek-v2-236b",   # mla_moe (paged latent cache)
    "whisper-large-v3",   # enc_dec (paged decoder self-KV + cross state)
    "zamba2-1.2b",        # hybrid_ssm (pool + per-slot recurrent state)
    "xlstm-1.3b",         # token-free (state only; chunks cut state scans)
]


def _prompt(cfg, plen, rng):
    if cfg.family == "enc_dec":
        return {
            "enc_embeds": (rng.standard_normal((10, cfg.d_model))
                           .astype(np.float32) * 0.02),
            "tokens": rng.integers(0, cfg.vocab_size, plen, dtype=np.int32),
        }
    return rng.integers(0, cfg.vocab_size, plen, dtype=np.int32)


def _engine(cfg, prefill, max_slots=2):
    return ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(batching="paged", prefill=prefill,
                           max_len=256, max_slots=max_slots),
        spec=chip_spec(**SMALL))


def _chunk_tokens(eng):
    return eng.plan.chunk_tokens() or eng.page.page_tokens


# ---------------------------------------------------------------- identity
@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_chunked_prefill_token_identical_to_monolithic(arch):
    """Chunk boundaries must be invisible: same tokens whether the prompt
    enters the pool whole or page by page, with a partial final chunk."""
    cfg = get_model_config(arch).reduced()
    chunked = _engine(cfg, "chunked")
    t = _chunk_tokens(chunked)
    plen = 2 * t + 3                      # 3 chunks, final one partial
    rng = np.random.default_rng(7)
    prompts = [_prompt(cfg, plen, rng), _prompt(cfg, t - 1, rng)]

    outs_k = chunked.generate(prompts, max_new_tokens=4)
    mono = _engine(cfg, "monolithic")
    outs_m = mono.generate(prompts, max_new_tokens=4)

    assert outs_k == outs_m, arch
    assert all(len(o) == 4 for o in outs_k)
    # The chunked run really chunked: ceil(plen/t) + 1 for the short one.
    assert chunked.metrics["prefill_chunks"] == -(-plen // t) + 1
    assert mono.metrics["prefill_chunks"] == 2


# --------------------------------------------------------------- interleave
def test_decode_interleaves_with_long_prefill_and_zero_copies():
    """While a long prompt streams into the pool, the resident slot's
    decode keeps ticking: the trace has decode events between the long
    prompt's chunk events, never more than one chunk per slot between
    consecutive decode ticks, and the staging copy is gone."""
    cfg = get_model_config("llama3.2-1b").reduced()
    eng = _engine(cfg, "chunked")
    t = _chunk_tokens(eng)
    rng = np.random.default_rng(3)
    # Short prompt first: it finishes prefill in one chunk and decodes
    # while the long prompt is still streaming in.
    prompts = [_prompt(cfg, t - 2, rng), _prompt(cfg, 4 * t, rng)]
    outs = eng.generate(prompts, max_new_tokens=[8, 2])
    assert len(outs[0]) == 8 and len(outs[1]) == 2

    trace = eng.metrics["interleave"]
    long_chunks = [i for i, ev in enumerate(trace)
                   if ev[0] == "chunk" and ev[3] == t]    # full => long slot
    decodes = [i for i, ev in enumerate(trace) if ev[0] == "decode"]
    assert len(long_chunks) == 4
    # Decode ticks strictly between the long prompt's first and last chunk:
    # prefill streams THROUGH live decoding, not ahead of it.
    assert [i for i in decodes if long_chunks[0] < i < long_chunks[-1]], \
        f"no decode tick interleaved mid-prefill: {trace}"
    # Stall bound: at most one chunk per slot between consecutive decode
    # ticks -- a decoder is never held for a multi-chunk prefill burst.
    bounds = [-1] + decodes + [len(trace)]
    for lo, hi in zip(bounds, bounds[1:]):
        slots = [ev[1] for ev in trace[lo + 1:hi] if ev[0] == "chunk"]
        assert len(slots) == len(set(slots)), trace
    # Zero post-prefill copies: the staging/copy entry point is gone -- the
    # pages the chunks wrote ARE the cache decode reads.
    import repro.serve.pages as pages
    assert not hasattr(pages, "install_slot")
    assert eng.metrics["prefill_chunks"] == 5    # 4 long + 1 short


# ------------------------------------------------------------- properties
@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 10**6),
    n_slots=st.integers(1, 3),
    page_tokens=st.sampled_from([4, 8]),
    pool_pages=st.integers(2, 12),
    n_req=st.integers(1, 5),
)
def test_scheduler_page_flow_and_stall_bound(seed, n_slots, page_tokens,
                                             pool_pages, n_req):
    """Pure-python simulation of the engine's tick discipline over the
    real ``PagedScheduler``: random prompt/new lengths, chunked
    admission, at most one chunk per prefilling slot per tick, youngest
    -victim preemption and per-tick decode.  Invariants, EVERY tick:

      * ``pool.used_pages == sched.used_pages_by_slots()`` and
        ``pages_allocated - pages_released == used_pages`` (no leak, no
        double-free, under preemption included);
      * a decode slot stalls only when eviction provably cannot help
        (no strictly-younger victim exists);

    and at termination every request has its exact token count and the
    pool is empty."""
    from repro.serve.kvcache import PageSpec
    from repro.serve.pages import PagePool, PagedScheduler
    from repro.serve.scheduler import Request

    rng = np.random.default_rng(seed)
    page = PageSpec(page_tokens=page_tokens, token_bytes=16)
    pool = PagePool(pool_pages + 1)       # +1: reserved null page 0
    sched = PagedScheduler(pool, page, n_slots, pages_per_slot=16, window=0)
    reqs = [Request(rid=i, prompt_len=int(rng.integers(1, 4 * page_tokens)),
                    max_new=int(rng.integers(1, 6)))
            for i in range(n_req)]
    # A request that can never fit the pool alone would rightly stall the
    # oldest slot forever; the engine sizes pools to the plan, so skip.
    if max(page.pages_for(r.prompt_len + r.max_new) for r in reqs) \
            > pool_pages:
        return
    for r in reqs:
        sched.submit(r)

    emitted = {r.rid: 0 for r in reqs}
    prefills = {}
    ticks = 0
    while sched.has_work():
        ticks += 1
        assert ticks < 10_000, "scheduler livelock"
        stalled = set()

        def grow(slot, upto=None):
            while not sched.ensure_capacity(slot, upto=upto):
                if sched.table_full(slot):
                    raise AssertionError("table sized to never fill here")
                victim = sched.victim(slot)
                if victim is None:
                    # Stall is legal ONLY when no younger slot exists to
                    # evict -- the oldest request always progresses.
                    assert all(
                        s is None or s.rid <= sched.slots[slot].rid
                        for i, s in enumerate(sched.slots) if i != slot)
                    stalled.add(slot)
                    return False
                vreq = sched.evict(victim)
                emitted[vreq.rid] = 0     # recompute preemption
                prefills.pop(victim, None)
            return True

        for i in sorted(sched.active(),
                        key=lambda j: sched.slots[j].rid):
            if sched.slots[i] is None or i in prefills:
                continue
            grow(i)
        for slot, req, _pages, _hit in sched.admit(chunked=True):
            prefills[slot] = 0
        # chunk phase: at most ONE chunk per prefilling slot per tick.
        for slot in sorted(prefills):
            s = sched.slots[slot]
            if s is None or slot not in prefills:
                continue
            done = prefills[slot]
            c = min(page_tokens, s.req.prompt_len - done)
            if not grow(slot, upto=done + c):
                continue
            done += c
            prefills[slot] = done
            s.pos = done
            if done >= s.req.prompt_len:
                del prefills[slot]
                emitted[s.req.rid] += 1   # prefill samples the first token
                if emitted[s.req.rid] >= s.req.max_new:
                    sched.finish(slot)
        # decode phase: every live, non-stalled, non-prefilling slot
        # decodes THIS tick -- prefill never starves a decoder.
        for i in list(sched.active()):
            if i in stalled or i in prefills or sched.slots[i] is None:
                continue
            s = sched.slots[i]
            s.pos += 1
            emitted[s.rid] += 1
            if emitted[s.rid] >= s.req.max_new:
                sched.finish(i)
        # flow invariants, every tick
        assert pool.used_pages == sched.used_pages_by_slots()
        assert pool.pages_allocated - pool.pages_released == pool.used_pages
    assert all(emitted[r.rid] == r.max_new for r in reqs)
    assert pool.used_pages == 0


# ------------------------------------------------------------ one body
def test_single_layer_body_across_all_paths(monkeypatch):
    """Cohort prefill, chunked prefill, cohort decode and paged decode all
    execute the ONE module-level ``_tf_layer`` -- no forked layer bodies.
    A spy swapped in for the module global must see every path."""
    import jax
    import jax.numpy as jnp

    import repro.models.model as M
    from repro.serve.pages import init_paged_cache

    cfg = get_model_config("llama3.2-1b").reduced()
    model = M.build_model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)

    calls = []
    orig = M._tf_layer

    def spy(lp, x, cfg_, kind, attn, capacity_factor):
        calls.append(kind)
        return orig(lp, x, cfg_, kind, attn, capacity_factor)

    monkeypatch.setattr(M, "_tf_layer", spy)

    def ran(tag, fn):
        before = len(calls)
        out = fn()
        assert len(calls) > before, f"{tag} bypassed _tf_layer"
        return out

    _, cache = ran("cohort prefill", lambda: model.prefill(
        params, {"tokens": jnp.asarray(toks)[None]}, max_len=12,
        dtype=jnp.float32))
    ran("cohort decode", lambda: model.decode_step(
        params, cache, {"tokens": jnp.asarray([[3]], jnp.int32)},
        dtype=jnp.float32))

    pcache = init_paged_cache(cfg, model, 2, 6, 4, 4, jnp.float32)
    pcache["table"] = jnp.zeros((2, 4), jnp.int32).at[0, :3].set(
        jnp.arange(1, 4))
    _, pcache = ran("chunked prefill", lambda: model.prefill_chunk(
        params, pcache, {"tokens": jnp.asarray(toks)[None],
                         "pos0": jnp.int32(0), "slot": jnp.int32(0)},
        dtype=jnp.float32))
    pcache["pos"] = jnp.asarray([8, 0], jnp.int32)
    ran("paged decode", lambda: model.decode_step_paged(
        params, pcache, {"tokens": jnp.asarray([[3], [0]], jnp.int32)},
        dtype=jnp.float32))


def test_chunk_written_pool_reads_same_under_kernel_and_ref():
    """The pages a chunked prefill writes are one cache, two readers: the
    Pallas paged kernel and the ``kernels/ref.py`` gather must agree on a
    decode step over the chunk-written pool."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref
    from repro.models.model import build_model
    from repro.serve.pages import init_paged_cache

    cfg = get_model_config("llama3.2-1b").reduced()
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    T, NP, plen = 4, 4, 11                # 3 chunks, partial final one
    toks = rng.integers(0, cfg.vocab_size, plen, dtype=np.int32)

    cache = init_paged_cache(cfg, model, 2, NP + 2, T, NP, jnp.float32)
    cache["table"] = jnp.zeros((2, NP), jnp.int32).at[0, :3].set(
        jnp.arange(1, 4))
    done = 0
    while done < plen:
        c = min(T, plen - done)
        _, cache = model.prefill_chunk(
            params, cache,
            {"tokens": jnp.asarray(toks[done:done + c])[None],
             "pos0": jnp.int32(done), "slot": jnp.int32(0)},
            dtype=jnp.float32)
        done += c

    k_pool = cache["pool"]["k"][0]        # layer 0: (P, T, KV, D)
    v_pool = cache["pool"]["v"][0]
    q = jnp.asarray(rng.standard_normal(
        (2, cfg.n_heads, cfg.head_dim)).astype(np.float32))
    lengths = jnp.asarray([plen, 0], jnp.int32)
    out_k = paged_attention(q, k_pool, v_pool, cache["table"], lengths,
                            page_tokens=T)
    out_r = paged_attention_ref(q, k_pool, v_pool, cache["table"], lengths)
    np.testing.assert_allclose(np.asarray(out_k[0]), np.asarray(out_r[0]),
                               rtol=2e-4, atol=2e-5)


def test_chunk_tiles_read_pages_of_earlier_chunks():
    """A prompt over several pages, cut into page-sized chunks: each later
    chunk's query tiles read the pages earlier chunks wrote, and the
    tokens match the cohort path, which attends without pages at all.
    The engine counts the pages the chunk kernel fetched: each chunk's
    live pages once per query tile, less a page a tile shares with the
    one before it."""
    from repro.kernels.paged_attention import prefill_page_reads

    cfg = get_model_config("llama3.2-1b").reduced()
    eng = _engine(cfg, "chunked", max_slots=1)
    t = _chunk_tokens(eng)
    bq = eng.plan.page_plan()["prefill_block_q"]
    assert bq < t                         # several tiles in every chunk
    plen = 4 * t + 5                      # 5 chunks, final one partial
    rng = np.random.default_rng(11)
    prompt = _prompt(cfg, plen, rng)
    outs = eng.generate([prompt], max_new_tokens=3)

    cohort = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(batching="cohort", max_len=256, max_slots=1),
        spec=chip_spec(**SMALL))
    assert outs == cohort.generate([prompt], max_new_tokens=3)
    pps = eng.metrics["pages_per_slot"]
    want = sum(prefill_page_reads(a, min(t, plen - a), bq, t, 0, pps)
               for a in range(0, plen, t))
    assert eng.metrics["prefill_kv_page_reads"] == want
    # Later chunks re-read earlier pages: more fetches than chunks.
    assert want > eng.metrics["prefill_chunks"]

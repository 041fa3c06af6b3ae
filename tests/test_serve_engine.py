"""ServeEngine acceptance tests (ISSUE 4).

* Greedy decode is token-identical to the legacy ``launch/serve.py`` loop
  (prefill + argmax decode over ``make_serve_steps``) for all four
  served model families: dense, MoE, hybrid-SSM, xLSTM.
* Continuous batching sustains mixed prompt lengths with the resident KV
  bytes never exceeding the planned budget (the engine asserts it every
  tick; the test additionally checks the recorded peak).
* The engine is plan-driven end to end: page size and cache capacities
  come from ``plan_run``'s decode-workload tree (the sharding side of the
  acceptance criterion is covered by the subprocess test in
  ``test_serve_plan_sharding.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_model_config
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.serve import (
    SamplingConfig,
    ServeEngine,
    ServePolicy,
    kv_token_bytes,
    make_serve_steps,
)

#: One arch per served model family (the "all four model families" of the
#: satellite checklist): dense attention, MoE (sliding-window ring cache),
#: hybrid SSM (Mamba2 + shared attention), and pure-recurrent xLSTM.
FOUR_FAMILIES = ["llama3.2-1b", "mixtral-8x7b", "zamba2-1.2b", "xlstm-1.3b"]


def _legacy_greedy(cfg, mesh, prompts, n_new):
    """The pre-engine serving loop (ex ``launch/serve.py``): one batch, one
    full-capacity cache, argmax decode."""
    plen = len(prompts[0])
    shape = ShapeConfig("legacy", plen, len(prompts), "decode")
    ss = make_serve_steps(cfg, shape, mesh, dtype=jnp.float32,
                          max_len_extra=n_new + 1)
    params = ss.model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = {"tokens": jnp.stack([jnp.asarray(p) for p in prompts])}
    logits, cache = ss.prefill(params, batch)
    out = [[] for _ in prompts]
    for _ in range(n_new):
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for b in range(len(prompts)):
            out[b].append(int(nxt[b, 0]))
        logits, cache = ss.decode(params, cache, {"tokens": nxt})
    return out


@pytest.mark.parametrize("arch", FOUR_FAMILIES)
def test_engine_greedy_matches_legacy_loop(arch):
    cfg = get_model_config(arch).reduced()
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    B, plen, n_new = 2, 12, 4
    prompts = [rng.integers(0, cfg.vocab_size, plen, dtype=np.int32)
               for _ in range(B)]
    legacy = _legacy_greedy(cfg, mesh, prompts, n_new)
    engine = ServeEngine(cfg, mesh, policy=ServePolicy(
        max_new_tokens=n_new, max_len=plen + n_new + 1))
    assert engine.generate(prompts) == legacy, arch


def test_mixed_prompt_lengths_stay_inside_budget():
    """Continuous batching over mixed prompt lengths under a budget small
    enough to force several admission waves; every request completes and
    the recorded resident peak never crosses the planned budget."""
    cfg = get_model_config("llama3.2-1b").reduced()
    tok_bytes, _, _ = kv_token_bytes(cfg, 4)
    budget = tok_bytes * 40 * 2          # ~two sequences of ~40 tokens
    engine = ServeEngine(cfg, make_host_mesh(), policy=ServePolicy(
        max_new_tokens=5, max_len=64, max_slots=2,
        kv_budget_bytes=budget))
    rng = np.random.default_rng(0)
    lens = (8, 8, 16, 16, 8)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lens]
    outs = engine.generate(prompts)
    assert [len(o) for o in outs] == [5] * len(lens)
    assert engine.metrics["peak_resident_bytes"] <= budget
    assert engine.metrics["cohorts"] >= 3     # mixed lengths => >= 3 cohorts


def test_page_growth_and_eviction_under_pressure():
    """A small forced VMEM shrinks the planned page; decode grows the cache
    page by page, and when the budget cannot hold two growing cohorts the
    younger one is preempted (recompute eviction) and still completes."""
    from repro.hw.tpu import chip_spec

    cfg = get_model_config("llama3.2-1b").reduced()
    tok_bytes, _, _ = kv_token_bytes(cfg, 4)
    small = chip_spec(vmem_bytes=800 << 10, vmem_reserved_bytes=0)
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)

    engine = ServeEngine(cfg, mesh, policy=ServePolicy(
        max_new_tokens=40, max_len=64), spec=small)
    assert engine.page.page_tokens < 64       # the plan shrank the page
    outs = engine.generate([rng.integers(0, 256, 8, dtype=np.int32)])
    assert len(outs[0]) == 40
    caps = engine.metrics["capacities"]
    assert len(caps) > 1, "decode never grew the cache"
    assert all(c % engine.page.page_tokens == 0 for c in caps), \
        "capacities are not whole pages"

    budget = tok_bytes * 64
    engine = ServeEngine(cfg, mesh, policy=ServePolicy(
        max_new_tokens=30, max_len=64, max_slots=1,
        kv_budget_bytes=budget), spec=small)
    outs = engine.generate(
        [rng.integers(0, 256, 8, dtype=np.int32) for _ in range(2)])
    assert [len(o) for o in outs] == [30, 30]
    assert engine.metrics["evictions"] >= 1
    assert engine.metrics["peak_resident_bytes"] <= budget


def test_compaction_frees_finished_slots_at_growth():
    """A slot that finishes early is sliced out of the cohort at the next
    growth boundary (its pages release before new ones are reserved), and
    the surviving request's greedy tokens are unchanged -- decode rows are
    batch-independent."""
    from repro.hw.tpu import chip_spec

    cfg = get_model_config("llama3.2-1b").reduced()
    mesh = make_host_mesh()
    small = chip_spec(vmem_bytes=800 << 10, vmem_reserved_bytes=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, 8, dtype=np.int32) for _ in range(2)]

    solo = ServeEngine(cfg, mesh, policy=ServePolicy(
        max_new_tokens=30, max_len=64), spec=small)
    ref = solo.generate([prompts[1]])[0]

    engine = ServeEngine(cfg, mesh, policy=ServePolicy(
        max_new_tokens=30, max_len=64), spec=small)
    outs = engine.generate(prompts, max_new_tokens=[6, 30])
    assert [len(o) for o in outs] == [6, 30]
    assert outs[1] == ref                      # compaction changed nothing
    # Growth happened after the early finisher left, so the freed slot's
    # pages never inflated the peak: one surviving slot at final capacity.
    assert len(engine.metrics["capacities"]) > 1
    final_cap = engine.metrics["capacities"][-1]
    assert engine.scheduler.peak_bytes <= \
        engine.page.page_bytes * (engine.page.pages_for(final_cap) + 2)


def test_engine_consumes_plan_page_size():
    """Plan-driven end to end: the engine's page granule equals the decode
    plan's page level, and every cache capacity it allocates is a whole
    number of those pages."""
    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(cfg, make_host_mesh(), policy=ServePolicy(
        max_new_tokens=4, max_len=48))
    page = engine.plan.page_plan()
    assert page is not None
    assert engine.page.page_tokens == page["page_tokens"]
    rng = np.random.default_rng(0)
    engine.generate([rng.integers(0, 256, 9, dtype=np.int32)])
    assert engine.metrics["capacities"], "no capacity was recorded"
    assert all(c % page["page_tokens"] == 0
               for c in engine.metrics["capacities"])


def test_seeded_sampling_is_deterministic():
    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(cfg, make_host_mesh(), policy=ServePolicy(
        max_new_tokens=4, max_len=32))
    p = [np.random.default_rng(0).integers(0, 256, 8, dtype=np.int32)]
    for scfg in (SamplingConfig("temperature", temperature=0.7, seed=3),
                 SamplingConfig("top_k", top_k=5, seed=3)):
        a = engine.generate(p, sampling=scfg)
        b = engine.generate(p, sampling=scfg)
        assert a == b and len(a[0]) == 4, scfg.kind
    greedy = engine.generate(p)
    assert greedy == engine.generate(p)


def test_eos_stops_a_slot_early():
    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(cfg, make_host_mesh(), policy=ServePolicy(
        max_new_tokens=6, max_len=32))
    p = [np.random.default_rng(0).integers(0, 256, 8, dtype=np.int32)]
    full = engine.generate(p)[0]
    # First token that did not already occur earlier in the continuation
    # (an earlier duplicate would stop the rerun at the duplicate).
    i = next((i for i in range(1, len(full))
              if full[i] not in full[:i]), None)
    if i is None:
        pytest.skip("degenerate continuation: every token repeats")
    stopped = engine.generate(
        p, sampling=SamplingConfig("greedy", eos_id=full[i]))[0]
    assert stopped == full[:i + 1]

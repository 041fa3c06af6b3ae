"""Benchmark harness -- one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  table3   -- temporal-locality benchmarks, CC vs horizontal (Table 3)
  table4   -- no-temporal-locality group: overhead parity check (Table 4)
  table5   -- TCL-size sensitivity sweep (Table 5 / Fig. 9)
  fig10    -- per-stage breakdown of MatMult (Fig. 10)
  fig11    -- cluster-level scaling model (Fig. 11)
  roofline -- §Roofline summary of every dry-run cell (single-pod)
  plans    -- decomposer tile plans for the TPU kernels (DESIGN.md §2)
  collectives -- A/B per-step timings of the overlap layer's matmuls
             (gspmd vs ring vs serpentine, DESIGN.md §5; needs >= 2
             devices -- force them with
             XLA_FLAGS=--xla_force_host_platform_device_count=4)
  serve    -- tok/s of the plan-driven serving engine (repro.serve) and
             planned-vs-naive KV page sizes; with --dry, the decode plan
             tree + the DCN-free / VMEM-fit assertions CI greps
             (DESIGN.md §7)
  paged    -- tok/s + slot-utilization A/B of the paged page-pool engine
             vs the cohort baseline on a mixed-length trace; with --dry,
             the pool-geometry-matches-page_plan assertion CI greps
             (DESIGN.md §8)
  prefill  -- TTFT + decode-stall A/B of chunked vs monolithic prefill
             (a long prompt backfilling while a resident slot decodes);
             with --dry, the chunk-equals-planned-page assertion CI
             greps (DESIGN.md §10)

Usage: ``python -m benchmarks.run [--quick] [--only table3,roofline]
                                  [--collectives gspmd|ring|serpentine]``

``--collectives`` with ``--dry`` prints the plan-time ring schedule (one
line per step showing the ppermute(s) it issues -- both directions under
serpentine) and, when devices allow, the collective-permute count of the
lowered HLO.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def table3(quick: bool) -> list:
    from benchmarks.paper_cpu import (
        bench_gaussianblur,
        bench_matmult,
        bench_mattrans,
        bench_sor,
    )

    out = []
    out.append(bench_matmult(n=512 if quick else 768, tcl="L2").csv())
    out.append(bench_mattrans(n=2048 if quick else 4096).csv())
    out.append(bench_gaussianblur(n=1024 if quick else 2048,
                                  radius=5).csv())
    out.append(bench_sor(n=1024 if quick else 2048,
                         sweeps=2 if quick else 4).csv())
    return out


def table4(quick: bool) -> list:
    from benchmarks.paper_cpu import bench_crypt, bench_series, bench_wordcount

    out = []
    out.append(bench_crypt(mb=8 if quick else 16).csv())
    out.append(bench_series(n=4000 if quick else 8000).csv())
    out.append(bench_wordcount(mb=4 if quick else 8).csv())
    return out


def table5(quick: bool) -> list:
    from benchmarks.paper_cpu import HIER, tcl_sweep_matmult

    res = tcl_sweep_matmult(n=384 if quick else 768)
    best_tcl = min(res, key=res.get)
    l1 = HIER.find("L1").size if HIER.find("L1") else 0
    l2 = HIER.find("L2").size if HIER.find("L2") else 0
    lines = []
    for tcl, t in sorted(res.items()):
        tag = "L1" if tcl == l1 else ("L2" if tcl == l2 else "")
        lines.append(f"tcl_sweep_matmult_tcl{tcl}{tag},{t * 1e6:.0f},"
                     f"best={tcl == best_tcl}")
    lines.append(
        f"tcl_sweep_summary,0,best_tcl={best_tcl};L1={l1};L2={l2};"
        f"best_between_L1_and_L2={l1 <= best_tcl <= l2}")
    return lines


def fig10(quick: bool) -> list:
    from benchmarks.paper_cpu import bench_matmult

    r = bench_matmult(n=512 if quick else 768, tcl="L2")
    t = r.times
    tot = max(t.total, 1e-12)
    return [
        f"fig10_breakdown_decomposition,{t.decomposition * 1e6:.0f},"
        f"pct={100 * t.decomposition / tot:.2f}",
        f"fig10_breakdown_scheduling,{t.scheduling * 1e6:.0f},"
        f"pct={100 * t.scheduling / tot:.2f}",
        f"fig10_breakdown_execution,{t.execution * 1e6:.0f},"
        f"pct={100 * t.execution / tot:.2f}",
        f"fig10_breakdown_reduction,{t.reduction * 1e6:.0f},"
        f"pct={100 * t.reduction / tot:.2f}",
    ]


def fig11(quick: bool) -> list:
    """Cluster-level scaling (Fig. 11), reproduced as a model over the
    dry-run roofline terms: per-node work shrinks with node count while the
    cache-conscious decomposition keeps per-worker partitions TCL-sized
    regardless of scale -- the paper's observation that horizontal gains
    from scale-out are ephemeral."""
    from repro.core import matmul_domain, paper_system_a, find_optimal_np
    from repro.core.decompose import phi_simple, validate_np

    lines = []
    n = 8192
    for nodes in (1, 2, 4, 8):
        workers = 8 * nodes
        rows_per_node = n // nodes
        # Horizontal: partition size shrinks with scale (ephemeral locality).
        hz_bytes = 3 * (rows_per_node // 8) * n * 4
        # Cache-conscious: partition size pinned to the TCL at any scale.
        domain = matmul_domain(rows_per_node, n, n, 4)
        np_ = find_optimal_np(64 << 10, 64, domain, 8, phi_simple)
        cc_bytes = sum(phi_simple(64, d, np_) for d in domain)
        lines.append(
            f"fig11_nodes{nodes},0,horizontal_partition_bytes={hz_bytes};"
            f"cc_partition_bytes={cc_bytes:.0f};cc_fits_64k={cc_bytes <= 64 << 10}")
    return lines


def roofline(quick: bool) -> list:
    from benchmarks.roofline_table import load_cells, nominate_hillclimb, summary_csv

    cells = load_cells("16x16")
    if not cells:
        return ["roofline_missing,0,run launch/dryrun.py first"]
    out = summary_csv(cells)
    noms = nominate_hillclimb(cells)
    for k, v in noms.items():
        out.append(f"roofline_nominee_{k},0,{v['arch']}x{v['shape']}")
    return out


def plans(quick: bool) -> list:
    from repro.core.autotile import plan_attention, plan_matmul
    from repro.models.mamba2 import choose_chunk

    out = []
    t0 = time.perf_counter()
    p = plan_matmul(8192, 8192, 8192, dtype_bytes=2)
    dt = time.perf_counter() - t0
    out.append(f"plan_matmul_8k,{dt * 1e6:.0f},"
               f"bm={p.bm};bk={p.bk};bn={p.bn};np={p.np};"
               f"vmem={p.est_vmem_bytes}")
    t0 = time.perf_counter()
    a = plan_attention(32768, 32768, 128, dtype_bytes=2)
    dt = time.perf_counter() - t0
    out.append(f"plan_attention_32k,{dt * 1e6:.0f},"
               f"bq={a.block_q};bkv={a.block_kv};vmem={a.est_vmem_bytes}")
    t0 = time.perf_counter()
    c = choose_chunk(4096, 64, 64, 64)
    dt = time.perf_counter() - t0
    out.append(f"plan_ssd_chunk,{dt * 1e6:.0f},chunk={c}")
    return out


#: Set from --hosts / --chips in main(): the forced hierarchy the "plan"
#: section walks (CI runs a 2-host x 4-chip dry plan on every run).
_PLAN_HOSTS = 1
_PLAN_CHIPS = 8


def plan_tree(quick: bool) -> list:
    """--only plan: the full hierarchical plan tree (``repro.plan``).

    Walks ``plan_run`` over a ``--hosts`` x ``--chips`` TPU hierarchy --
    DCN -> ICI/HBM -> VMEM -> VREG -- for a real architecture's training
    state (with its per-arch phi_mesh ``overhead``) and for a synthetic
    65 GiB state whose np* (5 on 16 GiB chips) is not a mesh-axis divisor,
    so the printed tree demonstrates the FSDP degree quantization
    (``np_raw=5 quantized=8``).  Pure planning: no jax, no timed loops.
    """
    from repro.configs import get_model_config
    from repro.core.plan import Workload, plan_run
    from repro.dist.sharding import TRAIN_STATE_BYTES_PER_PARAM
    from repro.hw.tpu import chip_spec

    del quick
    spec = chip_spec()
    hier = spec.hierarchy(mesh_devices=_PLAN_CHIPS, hosts=_PLAN_HOSTS)
    out = []
    cfg = get_model_config("llama3.2-1b")
    hp = plan_run(hier, Workload(
        state_bytes=cfg.param_count() * TRAIN_STATE_BYTES_PER_PARAM,
        overhead=cfg.overhead,
        matmul=(4096, cfg.d_model, cfg.d_ff),
        dtype_bytes=2,
    ))
    for i, line in enumerate(hp.describe()):
        out.append(f"plan_tree_{cfg.arch}_{i},0,{line}")
    hp = plan_run(hier, Workload(state_bytes=65 << 30))
    for i, line in enumerate(hp.describe()):
        out.append(f"plan_tree_65GiB_state_{i},0,{line}")
    return out


def collectives_plan(mode: str) -> list:
    """--collectives=ring|serpentine under --dry: the plan-time ring
    schedule, one line per step showing the ppermute(s) it issues (forward
    AND backward under serpentine), plus -- when the host exposes >= 2
    devices (CI forces 4) -- the collective-permute count of the lowered
    kernels (DESIGN.md §5)."""
    import jax
    import jax.numpy as jnp
    from repro.dist.overlap import make_ag_matmul, make_rs_matmul, plan_ring
    from repro.launch.mesh import make_mesh

    n_dev = jax.device_count()
    p = n_dev if n_dev >= 2 else 4
    plan = plan_ring(p, mode)
    out = []
    for s, desc in enumerate(plan.describe()):
        out.append(f"ring_plan_{mode}_step{s},0,{desc}")
    if n_dev < 2:
        out.append(f"ring_hlo_{mode},0,skipped=1 device "
                   "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        return out
    mesh = make_mesh((p,), ("model",))
    x = jax.ShapeDtypeStruct((4 * p, 2 * p), jnp.float32)
    w = jax.ShapeDtypeStruct((2 * p, 2 * p), jnp.float32)
    for kind, make in (("ag", make_ag_matmul), ("rs", make_rs_matmul)):
        fn = make(mesh, axis="model", mode=mode)
        mlir = fn.lower(x, w).as_text()
        # One collective_permute per ICI direction in the ring-step body:
        # 1 under ring, 2 under serpentine (the both-direction evidence).
        out.append(f"ring_hlo_{kind}_{mode},0,devices={p};"
                   f"collective_permutes={mlir.count('collective_permute')};"
                   f"directions={2 if mode == 'serpentine' else 1}")
    return out


#: Set from --collectives in main(): "gspmd" benches all three schedules,
#: "ring"/"serpentine" restrict the A/B to gspmd vs that schedule.
_AB_MODE = "gspmd"


def collectives_bench(quick: bool) -> list:
    """§Perf A/B: per-step timings of one TP projection under gspmd (XLA's
    own collectives), the ring, and the serpentine overlap matmuls
    (DESIGN.md §5), next to the estimated per-link wire bytes
    (``launch.specs.overlap_wire_bytes``).  Needs >= 2 devices;
    ``--collectives`` narrows the comparison to gspmd vs one schedule."""
    import statistics

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist.overlap import make_ag_matmul, make_rs_matmul
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import overlap_wire_bytes

    n_dev = jax.device_count()
    if n_dev < 2:
        return ["collectives_ab_skip,0,needs >=2 devices "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)"]
    p = n_dev
    mesh = make_mesh((p,), ("model",))
    m = 256 if quick else 1024
    k = n = 16 * p
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    iters = 10 if quick else 30
    fns = {
        "ag_gspmd": jax.jit(
            lambda a, b: a @ b,
            in_shardings=(NamedSharding(mesh, P(None, "model")),
                          NamedSharding(mesh, P(None, "model"))),
            out_shardings=NamedSharding(mesh, P(None, "model"))),
        "ag_ring": make_ag_matmul(mesh, "model", mode="ring"),
        "ag_serpentine": make_ag_matmul(mesh, "model", mode="serpentine"),
        "rs_gspmd": jax.jit(
            lambda a, b: a @ b,
            in_shardings=(NamedSharding(mesh, P(None, "model")),
                          NamedSharding(mesh, P("model", None))),
            out_shardings=NamedSharding(mesh, P("model", None))),
        "rs_ring": make_rs_matmul(mesh, "model", mode="ring"),
        "rs_serpentine": make_rs_matmul(mesh, "model", mode="serpentine"),
    }
    if _AB_MODE != "gspmd":
        fns = {name: fn for name, fn in fns.items()
               if name.endswith("_gspmd") or name.endswith(f"_{_AB_MODE}")}
    out = []
    for name, fn in fns.items():
        fn(x, w).block_until_ready()        # compile + warm
        steps = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn(x, w).block_until_ready()
            steps.append(time.perf_counter() - t0)
        kind, _, mode = name.partition("_")
        per_link = overlap_wire_bytes(
            m, k, n, p, kind=kind,
            mode=mode if mode in ("ring", "serpentine") else "ring",
            dtype_bytes=4)
        out.append(
            f"collectives_ab_{name},{statistics.median(steps) * 1e6:.0f},"
            f"p={p};min_us={min(steps) * 1e6:.0f};iters={iters};"
            f"est_wire_bytes_per_link={per_link}")
    return out


def serve_dry() -> list:
    """--only serve --dry: the decode plan tree end to end, no model math.

    Walks ``repro.serve.plan_decode`` for a forced single-host 4-way
    tensor-parallel mesh (DCN-free by construction: one host, so the
    hierarchy tops out at the ICI) and asserts the page level picked a
    page whose kernel working set fits the VMEM leaf -- the CI serve smoke
    gate (``ci/run_tests.sh`` greps ``dcn_free=True`` and
    ``page_fits_vmem=True``).
    """
    from jax.sharding import AbstractMesh
    from repro.configs import get_model_config
    from repro.serve import page_spec_from_plan, plan_decode

    mesh = AbstractMesh((1, 4), ("data", "model"))
    cfg = get_model_config("llama3.2-1b")
    hp = plan_decode(cfg, mesh, max_len=32768, batch=8)
    out = []
    for i, line in enumerate(hp.describe()):
        out.append(f"serve_plan_{cfg.arch}_{i},0,{line}")
    levels = [lp.level for lp in hp.levels()]
    page = hp.page_plan()
    vmem = hp.level("VMEM")
    fits = (page is not None and vmem is not None
            and page["vmem_bytes"] <= vmem.budget_bytes)
    spec = page_spec_from_plan(hp, cfg)
    out.append(
        f"serve_dry_summary,0,levels={'>'.join(levels)};"
        f"dcn_free={'DCN' not in levels};"
        f"page_tokens={page['page_tokens'] if page else 0};"
        f"kv_shard={hp.kv_shard()};"
        f"page_fits_vmem={fits};"
        f"global_page_bytes={spec.page_bytes}")
    return out


def paged_dry() -> list:
    """--only paged --dry: pool geometry end to end, no model math.

    Builds a paged engine on the host mesh and asserts its pool geometry
    is taken VERBATIM from ``plan_run``'s page level: the pool's page size
    equals ``page_plan()["page_tokens"]``, the per-slot table width covers
    the plan's ``page_table()["pages_per_slot"]`` bound, and the physical
    pool never exceeds the plan's ``pages_total`` budget bound (the engine
    applies ``kv_fraction < 1`` on top).  CI greps
    ``pool_matches_plan=True`` (``ci/run_tests.sh``).
    """
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=4, max_slots=2, max_len=64,
                           batching="paged"))
    rng = np.random.default_rng(0)
    engine.generate([rng.integers(0, 256, 9, dtype=np.int32)])
    m = engine.metrics
    page = engine.plan.page_plan()
    ptab = engine.plan.page_table() or {}
    pool_ok = (
        m["batching"] == "paged"
        and page is not None
        and m["page_tokens"] == page["page_tokens"]
        and m["pages_per_slot"] >= int(ptab.get("pages_per_slot", 1))
        and (not ptab.get("pages_total")
             or m["pages_total"] <= ptab["pages_total"])
        and m["pages_total"] >= 1
        and m["pages_allocated"] == m["pages_released"]  # drained pool
    )
    return [
        f"paged_dry_geometry,0,page_tokens={m['page_tokens']};"
        f"pages_total={m['pages_total']};pages_per_slot={m['pages_per_slot']};"
        f"plan_pages_per_slot={ptab.get('pages_per_slot')};"
        f"plan_pages_total={ptab.get('pages_total')};"
        f"pool_matches_plan={pool_ok}",
    ]


def paged_bench(quick: bool) -> list:
    """--only paged: tok/s + slot-utilization of the paged engine vs the
    PR 4 cohort engine on a mixed-length trace (mixed prompt lengths AND
    mixed max_new, so cohorts drag finished slots while the page pool
    backfills them)."""
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    rng = np.random.default_rng(0)
    lens = (16, 16, 32, 16, 32, 16) if not quick else (16, 16, 32)
    news = (24, 6, 24, 6, 24, 6) if not quick else (12, 3, 12)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lens]
    out = []
    results = {}
    for mode in ("cohort", "paged"):
        engine = ServeEngine(
            cfg, make_host_mesh(),
            policy=ServePolicy(max_slots=2, max_len=128, batching=mode))
        t0 = time.perf_counter()
        outs = engine.generate(prompts, max_new_tokens=list(news))
        dt = time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        m = engine.metrics
        results[mode] = (outs, m)
        out.append(
            f"paged_ab_{mode},{dt / max(1, n_tok) * 1e6:.0f},"
            f"tok_s={n_tok / max(dt, 1e-9):.1f};tokens={n_tok};"
            f"slot_utilization={m['slot_utilization']:.3f};"
            f"backfills={m.get('backfills', 0)};"
            f"decode_steps={m['decode_steps']}")
    same = results["cohort"][0] == results["paged"][0]
    cu = results["cohort"][1]["slot_utilization"]
    pu = results["paged"][1]["slot_utilization"]
    out.append(
        f"paged_ab_summary,0,token_identical={same};"
        f"util_cohort={cu:.3f};util_paged={pu:.3f};"
        f"paged_util_higher={pu > cu}")
    return out


def prefill_dry() -> list:
    """--only prefill --dry: chunk geometry, no timing.

    Runs one chunked-prefill request end to end and asserts every full
    prefill chunk in the engine's interleave trace is EXACTLY the
    planner's page (``plan.chunk_tokens()`` == ``page_plan()``'s
    ``page_tokens`` -- the VMEM-fitting double-buffered KV slice, reused
    as the prefill quantum, DESIGN.md §10).  CI greps
    ``chunk_matches_page=True`` (``ci/run_tests.sh``).
    """
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=2, max_slots=2, max_len=128,
                           batching="paged", prefill="chunked"))
    t = engine.plan.chunk_tokens()
    page = engine.plan.page_plan()
    rng = np.random.default_rng(0)
    plen = 2 * (t or 16) + 3                 # multi-chunk, partial final
    engine.generate([rng.integers(0, 256, plen, dtype=np.int32)])
    chunks = [ev for ev in engine.metrics["interleave"]
              if ev[0] == "chunk"]
    full = [c for _, _, _, c in chunks if c == t]
    ok = (
        t is not None
        and page is not None
        and t == page["page_tokens"]
        and len(chunks) == -(-plen // t)
        and len(full) == plen // t
        and sum(c for _, _, _, c in chunks) == plen
    )
    return [
        f"prefill_dry_chunks,0,chunk_tokens={t};"
        f"page_tokens={page['page_tokens'] if page else None};"
        f"chunks={len(chunks)};prompt_tokens={plen};"
        f"chunk_matches_page={ok}",
    ]


def prefill_bench(quick: bool) -> list:
    """--only prefill: TTFT + decode-stall A/B, chunked vs monolithic.

    One long prompt arrives while a short request is already decoding.
    Monolithic prefill runs the whole prompt between two of the resident
    slot's decode ticks -- its max inter-token gap absorbs the entire
    prefill.  Chunked prefill pays one page-sized chunk per tick, so the
    resident slot's worst stall is bounded by a chunk.  Reports the long
    request's time-to-first-token and the short request's max inter-token
    gap for both modes, from the engine's per-token timestamps.
    """
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    rng = np.random.default_rng(0)
    out = []
    results = {}
    for mode in ("monolithic", "chunked"):
        engine = ServeEngine(
            cfg, make_host_mesh(),
            policy=ServePolicy(max_slots=2, max_len=256, batching="paged",
                               prefill=mode))
        t = engine.plan.chunk_tokens() or engine.page.page_tokens
        long_plen = (4 if quick else 6) * t
        # Two short requests fill both slots; the long one backfills the
        # early finisher's slot and prefills WHILE request 0 still decodes
        # -- its inter-token gaps are where a monolithic prefill shows up.
        prompts = [rng.integers(0, cfg.vocab_size, t - 2, dtype=np.int32),
                   rng.integers(0, cfg.vocab_size, t - 2, dtype=np.int32),
                   rng.integers(0, cfg.vocab_size, long_plen,
                                dtype=np.int32)]
        outs = engine.generate(
            prompts, max_new_tokens=[12 if quick else 24, 2, 2])
        m = engine.metrics
        times = m["token_times"]
        ttft_long = times[2][0] - m["start_time"]
        gaps = np.diff(np.asarray([m["start_time"]] + times[0]))
        results[mode] = (outs, ttft_long, float(gaps.max()))
        n_tok = sum(len(o) for o in outs)
        out.append(
            f"prefill_ab_{mode},{ttft_long * 1e6:.0f},"
            f"ttft_long_ms={ttft_long * 1e3:.1f};"
            f"max_stall_short_ms={float(gaps.max()) * 1e3:.1f};"
            f"tokens={n_tok};prefill_chunks={m['prefill_chunks']};"
            f"chunk_tokens={t};long_prompt={long_plen}")
    # Token identity chunked-vs-monolithic is the test suite's job
    # (tests/test_serve_prefill.py, at controlled context lengths --
    # random-init logits go argmax-unstable at this prompt scale).
    out.append(
        f"prefill_ab_summary,0,"
        f"stall_mono_ms={results['monolithic'][2] * 1e3:.1f};"
        f"stall_chunked_ms={results['chunked'][2] * 1e3:.1f};"
        f"chunked_stall_lower="
        f"{results['chunked'][2] < results['monolithic'][2]}")
    return out


def prefix_dry() -> list:
    """--only prefix --dry: radix-cache capacity vs the plan, no timing.

    Builds the paged engine with ``prefix_cache="radix"``, runs two
    prompts sharing a page-aligned prefix through one slot, and asserts
    the cache's byte budget is EXACTLY the mesh-level HBM leftover the
    planner recorded (``plan.prefix_budget()``, from
    ``detail["page_table"]["prefix_budget_bytes"]`` -- DESIGN.md §11),
    with the second request hitting the first's published pages.  CI
    greps ``prefix_budget_matches_plan=True`` (``ci/run_tests.sh``).
    """
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=2, max_slots=1, max_len=160,
                           batching="paged", prefix_cache="radix"))
    t = engine.page.page_tokens
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 3 * t, dtype=np.int32)
    tails = [rng.integers(0, cfg.vocab_size, t - 2, dtype=np.int32)
             for _ in range(2)]
    tails[1][0] = (tails[0][0] + 1) % cfg.vocab_size
    engine.generate([np.concatenate([shared, tl]) for tl in tails])
    m = engine.metrics
    plan_budget = engine.plan.prefix_budget()
    cache_budget = engine._paged_session.prefix.budget_bytes
    ok = (
        plan_budget is not None
        and plan_budget > 0
        and m["prefix_budget_bytes"] == plan_budget
        and cache_budget == plan_budget
        and m["prefix_hits"] == 1
        and m["prefix_hit_tokens"] == 3 * t
        and m["pages_saved"] > 0
    )
    return [
        f"prefix_dry_budget,0,plan_budget={plan_budget};"
        f"cache_budget={cache_budget};"
        f"metric_budget={m['prefix_budget_bytes']};"
        f"hits={m['prefix_hits']};hit_tokens={m['prefix_hit_tokens']};"
        f"pages_saved={m['pages_saved']};"
        f"resident_pages={m['prefix_resident_pages']};"
        f"prefix_budget_matches_plan={ok}",
    ]


def prefix_bench(quick: bool) -> list:
    """--only prefix: shared-system-prompt A/B, cached vs cold TTFT.

    The workload millions of deployments run: every request opens with
    the same system prompt.  Three single-request ``generate`` calls
    through one radix engine: X compiles every chunk bucket (its timings
    are discarded), Y measures a COLD prompt (disjoint tokens -- a
    radix miss, full prefill), Z measures a CACHED prompt sharing Y's
    page-aligned system prefix -- admission starts chunked prefill at
    the first unshared token, so Z prefills only the tail.  The tail is
    sized to the final chunk bucket X already compiled (``t - 2``), so
    the A/B is pure prefill work, not compile skew.  Reports TTFT and
    prefill tokens for both, from the engine's per-token timestamps.
    """
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    n_new = 4 if quick else 8
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=n_new, max_slots=1, max_len=256,
                           batching="paged", prefix_cache="radix"))
    t = engine.page.page_tokens
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, 3 * t, dtype=np.int32)

    def prompt(prefix, seed):
        r = np.random.default_rng(seed)
        return np.concatenate(
            [prefix, r.integers(0, cfg.vocab_size, t - 2, dtype=np.int32)])

    def run(p):
        before = engine.metrics["prefill_tokens"]   # counters accumulate
        engine.generate([p], max_new_tokens=n_new)
        m = engine.metrics
        # token_times is keyed by rid, which counts across calls.
        (times,) = m["token_times"].values()
        return (times[0] - m["start_time"],
                m["prefill_tokens"] - before, m)

    warmup = rng.integers(0, cfg.vocab_size, 4 * t - 2, dtype=np.int32)
    run(warmup)                             # X: compile, discard timings
    cold_ttft, cold_tokens, _ = run(prompt(system, 1))      # Y: radix miss
    hot_ttft, hot_tokens, m = run(prompt(system, 2))        # Z: radix hit
    return [
        f"prefix_ab_cold,{cold_ttft * 1e6:.0f},"
        f"ttft_ms={cold_ttft * 1e3:.2f};prefill_tokens={cold_tokens};"
        f"prompt_tokens={4 * t - 2}",
        f"prefix_ab_cached,{hot_ttft * 1e6:.0f},"
        f"ttft_ms={hot_ttft * 1e3:.2f};prefill_tokens={hot_tokens};"
        f"hit_tokens={m['prefix_hit_tokens']};"
        f"pages_saved={m['pages_saved']};cow_copies={m['cow_copies']}",
        f"prefix_ab_summary,0,shared_tokens={3 * t};"
        f"ttft_cold_ms={cold_ttft * 1e3:.2f};"
        f"ttft_cached_ms={hot_ttft * 1e3:.2f};"
        f"prefill_saved_tokens={cold_tokens - hot_tokens};"
        f"cached_ttft_lower={hot_ttft < cold_ttft}",
    ]


def serve_bench(quick: bool) -> list:
    """--only serve: tok/s of the plan-driven engine on this host, next to
    the planned-vs-naive page sizes (naive = the legacy loop's allocation
    granule: one full ``max_len`` buffer per request up front)."""
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy, kv_token_bytes

    cfg = get_model_config("llama3.2-1b").reduced()
    n_new = 8 if quick else 24
    max_len = 128 if quick else 256
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=n_new, max_slots=4,
                           max_len=max_len))
    rng = np.random.default_rng(0)
    lens = (16, 16, 32, 32, 16, 48) if not quick else (16, 16, 32)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lens]
    t0 = time.perf_counter()
    outs = engine.generate(prompts)
    dt = time.perf_counter() - t0
    n_tok = sum(len(o) for o in outs)
    m = engine.metrics
    tok_bytes, _, _ = kv_token_bytes(cfg, 4)
    naive_tokens = max_len                  # legacy: full buffer up front
    naive_resident = naive_tokens * tok_bytes * len(prompts)
    return [
        f"serve_toks,{dt / max(1, n_tok) * 1e6:.0f},"
        f"tok_s={n_tok / max(dt, 1e-9):.1f};tokens={n_tok};"
        f"requests={len(prompts)};cohorts={m['cohorts']}",
        f"serve_pages,0,planned_page_tokens={m['page_tokens']};"
        f"naive_page_tokens={naive_tokens};"
        f"planned_peak_resident={m.get('peak_resident_bytes', 0)};"
        f"naive_resident={naive_resident};"
        f"kv_shard={m['kv_shard']};evictions={m['evictions']}",
    ]


def tune_bench(quick: bool) -> list:
    """--only tune: tuned-vs-analytic kernel times (DESIGN.md §9).

    Runs the neighborhood sweep (``repro.tune.sweep``) around each kernel's
    analytic block and reports the winner next to the analytic center --
    the measured evidence behind every ``src=tuned`` line in the plan tree.
    Winners are NOT persisted from a benchmark run (that is ``repro-tune``'s
    job); this section only measures.
    """
    from repro.tune.sweep import run_sweeps

    results = run_sweeps(quick=quick, warmup=1, iters=3 if quick else 5,
                         write=False)
    out = []
    for r in results:
        e = r.entry
        if e is None:
            out.append(f"tune_{r.kernel},0,no_timed_candidates=1")
            continue
        win = "/".join(f"{k}={v}" for k, v in sorted(e.block.items()))
        ana = "/".join(f"{k}={v}"
                       for k, v in sorted(e.analytic_block.items()))
        out.append(
            f"tune_{r.kernel},{e.median_us:.0f},"
            f"analytic_us={e.analytic_us:.0f};speedup={e.speedup};"
            f"winner={win};analytic={ana};bucket={r.bucket};"
            f"candidates={len(r.candidates)};rejected={r.rejected};"
            f"tuned_beats_analytic={e.speedup > 1.0}")
    return out


def tune_dry() -> list:
    """--only tune --dry: enumerate + VMEM-filter the sweep neighborhoods
    without timing anything -- the CI tune smoke gate (``ci/run_tests.sh``
    greps ``all_candidates_fit_vmem=True``)."""
    from repro.tune.sweep import run_sweeps

    results = run_sweeps(quick=True, dry=True)
    out = []
    all_fit = True
    for r in results:
        fit = all(c.est_vmem_bytes <= r.budget_bytes for c in r.candidates)
        all_fit &= fit and bool(r.candidates)
        center = "/".join(f"{k}={v}" for k, v in sorted(r.center.items()))
        out.append(
            f"tune_dry_{r.kernel},0,bucket={r.bucket};center={center};"
            f"candidates={len(r.candidates)};rejected={r.rejected};"
            f"budget={r.budget_bytes};fit={fit}")
    out.append(f"tune_dry_summary,0,kernels={len(results)};"
               f"all_candidates_fit_vmem={all_fit}")
    return out


def _nocsv(d) -> str:
    """A dict rendered without commas (the row format's field separator)."""
    return "/".join(f"{k}:{v}" for k, v in dict(d or {}).items())


def _cluster_ab(policy: str, quick: bool) -> dict:
    """One routed A/B arm: a 2-replica thread-transport cluster under a
    memory-SKEWED workload -- one long request pins most of replica 0's
    page pool, then a burst of short requests arrives while it decodes.
    ``free_pages`` routes the burst around the page-poor replica;
    ``round_robin`` alternates it into the queue behind the long request.
    Prefix cache and affinity are OFF so the A/B isolates placement."""
    import numpy as np
    from repro.cluster import EngineSpec, ServeCluster
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import plan_decode

    cfg = get_model_config("llama3.2-1b").reduced()
    max_len = 192
    long_new = 32 if quick else 64
    spec = EngineSpec(arch="llama3.2-1b", max_new_tokens=long_new,
                      max_slots=1, max_len=max_len, prefix_cache="off")
    plan = plan_decode(cfg, make_host_mesh(), max_len=max_len, cluster=2)
    cluster = ServeCluster.from_plan(plan, spec, transport="thread",
                                     policy=policy, affinity=False)
    rng = np.random.default_rng(0)

    def prompt(n, seed):
        return np.random.default_rng(seed).integers(
            0, cfg.vocab_size, n, dtype=np.int32).tolist()

    try:
        # Build + compile both replicas' chunk buckets outside the clock.
        for rep in cluster.replicas:
            rep.generate([prompt(96, 10 + rep.replica)], 1).wait(600)
            rep.generate([prompt(24, 20 + rep.replica)], 1).wait(600)
        long_cr = cluster.submit(prompt(96, 1), long_new)
        t0 = time.perf_counter()
        while long_cr.ttft() is None:       # decoding: its pages are held
            if long_cr.done() or time.perf_counter() - t0 > 300:
                break
            time.sleep(0.005)
        burst = [cluster.submit(prompt(24, 100 + i), 2) for i in range(4)]
        for cr in burst:
            cr.result(timeout=600)
        long_cr.result(timeout=600)
        ttfts = [cr.ttft() for cr in burst]
        return {
            "policy": policy,
            "burst_replicas": [cr.replica for cr in burst],
            "long_replica": long_cr.replica,
            "mean_ttft": sum(ttfts) / len(ttfts),
            "max_ttft": max(ttfts),
        }
    finally:
        cluster.close()


def cluster_bench(quick: bool) -> list:
    """--only cluster: free_pages-vs-round_robin TTFT A/B under the
    memory-skewed workload (DESIGN.md §12) -- the Silva et al. claim,
    measured: placing by available pool memory instead of work count
    keeps the short burst's TTFT off the long request's decode tail."""
    arms = {p: _cluster_ab(p, quick) for p in ("round_robin", "free_pages")}
    rr, fp = arms["round_robin"], arms["free_pages"]
    out = []
    for a in (rr, fp):
        out.append(
            f"cluster_ab_{a['policy']},{a['mean_ttft'] * 1e6:.0f},"
            f"mean_burst_ttft_ms={a['mean_ttft'] * 1e3:.2f};"
            f"max_burst_ttft_ms={a['max_ttft'] * 1e3:.2f};"
            f"long_replica={a['long_replica']};"
            f"burst_replicas={'/'.join(str(r) for r in a['burst_replicas'])}")
    out.append(
        f"cluster_ab_summary,0,replicas=2;"
        f"ttft_rr_ms={rr['mean_ttft'] * 1e3:.2f};"
        f"ttft_free_pages_ms={fp['mean_ttft'] * 1e3:.2f};"
        f"speedup={rr['mean_ttft'] / max(fp['mean_ttft'], 1e-9):.2f};"
        f"free_pages_ttft_lower={fp['mean_ttft'] < rr['mean_ttft']}")
    return out


def cluster_dry() -> list:
    """--only cluster --dry: the fleet-vs-plan assertions CI gates
    (``ci/run_tests.sh`` greps ``replicas_match_plan=True`` and
    ``pool_matches_plan=True``): the cluster stands up exactly the DCN
    level's np replicas, each replica's pool geometry is the single-host
    plan's page_table (the DCN level chooses WIDTH, never reshapes the
    per-replica subtree), and a DCN-bearing plan without ``cluster=``
    raises the structured ``PlanError``."""
    from repro.cluster import ServeCluster, StubSpec
    from repro.configs import get_model_config
    from repro.hw.tpu import chip_spec
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import PlanError, plan_decode

    cfg = get_model_config("llama3.2-1b").reduced()
    mesh = make_host_mesh()
    spec = chip_spec()
    plan = plan_decode(cfg, mesh, max_len=256, spec=spec, cluster=2)
    single = plan_decode(cfg, mesh, max_len=256, spec=spec)
    dcn = plan.level("DCN")
    cluster = ServeCluster.from_plan(plan, StubSpec(), transport="thread")
    try:
        n = len(cluster.replicas)
    finally:
        cluster.close()
    replicas_match = dcn is not None and n == dcn.np == plan.replicas()
    pool_match = (dict(plan.page_table() or {})
                  == dict(single.page_table() or {}))
    try:
        plan_decode(cfg, mesh, max_len=256, spec=spec,
                    hierarchy=spec.hierarchy(mesh_devices=1, hosts=2))
        guard = False
    except PlanError:
        guard = True
    return [
        f"cluster_dry_plan,0,dcn_np={dcn.np if dcn else 0};"
        f"replicas={plan.replicas()};fleet={n};"
        f"placement={dcn.detail.get('placement') if dcn else None}",
        f"cluster_dry_pool,0,"
        f"cluster_page_table={_nocsv(plan.page_table())};"
        f"single_page_table={_nocsv(single.page_table())}",
        f"cluster_dry_summary,0,replicas_match_plan={replicas_match};"
        f"pool_matches_plan={pool_match};dcn_guard_raises={guard}",
    ]


def _obs_workload(tracing: bool = True):
    """The obs section's shared recorded workload: a reduced paged+radix
    engine over three prompts sharing a 12-token prefix.  Returns the
    engine (registry populated, tracer holding the request spans)."""
    import numpy as np
    from repro.configs import get_model_config
    from repro.launch.mesh import make_host_mesh
    from repro.serve import ServeEngine, ServePolicy

    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=6, max_slots=4, max_len=128,
                           batching="paged", prefix_cache="radix"))
    engine.tracer.enabled = tracing
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 256, 12, dtype=np.int32)
    engine.generate(
        [np.concatenate([shared,
                         rng.integers(0, 256, 4 + i, dtype=np.int32)])
         for i in range(3)])
    return engine


def obs_dry() -> list:
    """--only obs --dry: the observability spine end to end, no timing.

    Runs the shared workload, exports the tracer's Chrome/Perfetto JSON
    and validates it against the ``trace_event`` schema
    (``repro.obs.validate_events``), then walks plan-vs-actual
    (DESIGN.md §13) asserting every residual is finite and the pool's
    observed peak landed inside the plan's ``page_table`` budget.  CI
    greps ``trace_schema_ok=True``, ``plan_vs_actual_ok=True`` and
    ``pool_peak_within_plan=True`` (``ci/run_tests.sh``).
    """
    import json
    import math
    import tempfile

    from repro.obs import plan_vs_actual, validate_events

    engine = _obs_workload()
    with tempfile.NamedTemporaryFile("r", suffix=".json") as tf:
        engine.tracer.export_chrome(tf.name)
        doc = json.load(open(tf.name))
    events = doc.get("traceEvents", [])
    problems = validate_events(events)
    names = sorted({e.get("name") for e in events if e.get("ph") != "M"})
    schema_ok = (not problems and doc.get("displayTimeUnit") == "ms"
                 and {"request", "prefill_chunk", "decode_tick",
                      "queue_wait"} <= set(names))
    rows = plan_vs_actual(engine.plan, engine.obs)
    out = []
    finite = bool(rows)
    pool_ok = False
    for r in rows:
        ratio = r["ratio"]
        finite = finite and ratio is not None and math.isfinite(ratio)
        if r["metric"] == "pool_pages":
            pool_ok = bool(r["observed"] is not None and r["predicted"]
                           and r["observed"] <= r["predicted"])
        out.append(
            f"obs_dry_planvsactual_{r['level']}_{r['metric']},0,"
            f"predicted={r['predicted']};observed={r['observed']};"
            f"ratio={ratio};unit={r['unit']};"
            f"within_band={r['within_band']}")
    out.append(
        f"obs_dry_summary,0,trace_events={len(events)};"
        f"trace_problems={len(problems)};"
        f"trace_schema_ok={schema_ok};plan_vs_actual_ok={finite};"
        f"pool_peak_within_plan={pool_ok}")
    return out


def obs_bench(quick: bool) -> list:
    """--only obs: overhead A/B of the observability spine + the latency
    percentile surface it produces.

    The same workload runs through two identical paged engines, tracer
    on vs off (the registry stays on both sides -- it IS the metrics
    spine, there is no without-registry engine anymore), reporting the
    per-token cost of tracing, the TTFT / inter-token percentiles the
    log-bucket histograms yield, and the plan-vs-actual residual rows --
    the committable calibration trajectory (BENCH_10.json)."""
    from repro.obs import plan_vs_actual

    out = []
    reps = 1 if quick else 2
    results = {}
    _obs_workload()                 # compile warmup outside both arms
    for tracing in (False, True):
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            engine = _obs_workload(tracing=tracing)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
            results[tracing] = engine
        n_tok = int(engine.obs.value("tokens", 0))
        tag = "on" if tracing else "off"
        out.append(
            f"obs_ab_trace_{tag},{best / max(1, n_tok) * 1e6:.0f},"
            f"tokens={n_tok};tok_s={n_tok / max(best, 1e-9):.1f};"
            f"trace_events={len(engine.tracer.export_events())}")
    eng = results[True]
    for hname in ("ttft_s", "inter_token_s", "queue_wait_s"):
        h = eng.obs.get(hname)
        out.append(
            f"obs_latency_{hname},{h.mean * 1e6:.1f},"
            f"count={h.count};p50_us={h.percentile(50) * 1e6:.1f};"
            f"p95_us={h.percentile(95) * 1e6:.1f};"
            f"p99_us={h.percentile(99) * 1e6:.1f}")
    for r in plan_vs_actual(eng.plan, eng.obs):
        out.append(
            f"obs_planvsactual_{r['level']}_{r['metric']},0,"
            f"predicted={r['predicted']};observed={r['observed']};"
            f"ratio={r['ratio']};unit={r['unit']};"
            f"within_band={r['within_band']}")
    return out


SECTIONS = {
    "table3": table3,
    "table4": table4,
    "table5": table5,
    "fig10": fig10,
    "fig11": fig11,
    "roofline": roofline,
    "plans": plans,
    "plan": plan_tree,
    "collectives": collectives_bench,
    "serve": serve_bench,
    "paged": paged_bench,
    "prefill": prefill_bench,
    "prefix": prefix_bench,
    "tune": tune_bench,
    "cluster": cluster_bench,
    "obs": obs_bench,
}


def dry(_quick: bool, collectives: str = "gspmd") -> list:
    """CI smoke: exercise the decomposer planning paths (chip and mesh
    level) without running any timed benchmark loops.  With
    ``--collectives`` also print the overlap layer's ring schedule."""
    from repro.configs import get_model_config
    from repro.dist.sharding import arch_rules, mesh_decomposition, mesh_hierarchy
    from jax.sharding import AbstractMesh

    out = plans(True)
    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch in ("llama3.2-1b", "deepseek-v2-236b"):
        cfg = get_model_config(arch)
        rules = arch_rules(cfg, mesh)
        out.append(
            f"dry_mesh_rules_{arch},0,"
            f"embed={rules.param_rules['embed']};np={rules.meta['mesh_np']};"
            f"fits={rules.meta['mesh_fits']}")
    dec = mesh_decomposition(mesh_hierarchy(mesh), sharded_bytes=1 << 40,
                             max_np=16)
    out.append(f"dry_mesh_decomposition_1TiB,0,np={dec.np};fits={dec.fits}")
    if collectives != "gspmd":
        out.extend(collectives_plan(collectives))
    return out


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` -> dict with numbers parsed (best-effort; a token
    without '=' keeps the raw string under ``_raw``)."""
    out = {}
    raw = []
    for tok in derived.split(";"):
        if "=" not in tok:
            if tok:
                raw.append(tok)
            continue
        k, _, v = tok.partition("=")
        if v in ("True", "False"):
            out[k] = v == "True"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    if raw:
        out["_raw"] = ";".join(raw)
    return out


def _write_json(path: str, rows: list, argv: list) -> None:
    """The committable ``BENCH_<n>.json`` artifact: every CSV row of the
    run, parsed, plus enough provenance (backend, device, argv) to read a
    number a year later.  Schema checked by the CI smoke."""
    import json

    import jax

    dev = jax.devices()[0]
    doc = {
        "schema": "repro-bench-v1",
        "created_unix": int(time.time()),
        "argv": argv,
        "backend": dev.platform,
        "device": dev.device_kind,
        "device_count": len(jax.devices()),
        "rows": [
            {"section": sec, "name": name, "us_per_call": us,
             "derived": _parse_derived(derived)}
            for sec, name, us, derived in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)


def _collect(rows: list, section: str, line: str) -> None:
    print(line)
    name, _, rest = line.partition(",")
    us, _, derived = rest.partition(",")
    try:
        us_f = float(us)
    except ValueError:
        us_f = 0.0
    rows.append((section, name, us_f, derived))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--dry", action="store_true",
                    help="plan-only smoke run (CI): no timed benchmarks")
    ap.add_argument("--json", default="",
                    help="also write every row to a BENCH_<n>.json artifact "
                         "(schema repro-bench-v1; the committable perf "
                         "trajectory)")
    ap.add_argument("--collectives", default="gspmd",
                    choices=("gspmd", "ring", "serpentine"),
                    help="overlap-layer collective schedule (DESIGN.md §5): "
                         "with --dry, print its ring plan + lowered-HLO "
                         "permute count; with --only collectives, restrict "
                         "the A/B to gspmd vs this schedule")
    ap.add_argument("--hosts", type=int, default=1,
                    help="--only plan: hosts (DCN copies) of the forced "
                         "hierarchy the plan tree is walked over")
    ap.add_argument("--chips", type=int, default=8,
                    help="--only plan: chips per host of the forced "
                         "hierarchy")
    args = ap.parse_args()
    global _AB_MODE, _PLAN_HOSTS, _PLAN_CHIPS
    _AB_MODE = args.collectives
    _PLAN_HOSTS, _PLAN_CHIPS = args.hosts, args.chips
    if args.collectives != "gspmd":
        # The ring needs >1 device to mean anything; force a 4-way host
        # platform unless the caller already chose (must precede jax import,
        # which only the section bodies perform).
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows: list = []
    if args.dry:
        # CI gate: unlike the benchmark sections below, failures here must
        # propagate to a nonzero exit, not become an _ERROR CSV row.
        print("name,us_per_call,derived")
        # Dedicated dry smokes (serve: decode plan tree + page/DCN
        # assertions; paged: pool geometry vs the plan's page level; tune:
        # sweep enumeration + VMEM filter) -- any --only list made up
        # entirely of these runs them in order.
        dry_sections = {"serve": serve_dry, "paged": paged_dry,
                        "prefill": prefill_dry, "prefix": prefix_dry,
                        "tune": tune_dry, "cluster": cluster_dry,
                        "obs": obs_dry}
        only = [s.strip() for s in args.only.split(",") if s.strip()]
        if only and all(s in dry_sections for s in only):
            for s in only:
                for line in dry_sections[s]():
                    _collect(rows, s, line)
        else:
            for line in dry(args.quick, args.collectives):
                _collect(rows, "dry", line)
        if args.json:
            _write_json(args.json, rows, sys.argv[1:])
        return
    names = args.only.split(",") if args.only else list(SECTIONS)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        fn = SECTIONS[name.strip()]
        try:
            for line in fn(args.quick):
                _collect(rows, name.strip(), line)
        except Exception as e:  # record it, run the other sections, fail
            _collect(rows, name.strip(), f"{name}_ERROR,0,{e!r}")
            failed.append(name.strip())
        sys.stdout.flush()
    if args.json:
        _write_json(args.json, rows, sys.argv[1:])
    if failed:
        sys.exit(f"sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()

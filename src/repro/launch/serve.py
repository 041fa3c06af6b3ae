"""Serving launcher: a thin CLI over ``repro.serve.ServeEngine``.

``python -m repro.launch.serve --arch <id> --tokens 32`` (also installed as
the ``repro-serve`` console script).  Every batch/page/shard choice falls
out of the hierarchical planner's decode workload (DESIGN.md §7/§8): the
CLI only names the architecture, the prompt mix, the sampling config, and
``--batching {cohort,paged,auto}`` -- "auto" (default) picks the paged
page-pool engine whenever the decode plan exposes a page level (and the
family has a per-slot decode path), falling back to cohort batching.
``--prefix {off,radix}`` turns on the cross-request radix prefix cache
(DESIGN.md §11) in the paged engine.  ``--reduced false`` serves the
architecture at its published widths instead of the smoke-test preset.

``--cluster N`` serves through ``repro.cluster`` instead of one engine
(DESIGN.md §12): ``plan_decode(cluster=N)`` grows a DCN level whose
realized ``np`` is the fleet width, N replica hosts stand up behind a
router (``--policy {free_pages,least_loaded,round_robin}``), and
``--serve`` additionally binds the streaming HTTP front end
(``--port``).  ``--disagg P:D`` splits the fleet into prefill and
decode roles with ring-ordered KV page streaming between them
(``--cluster`` total must equal P+D); page transfer needs the prompt to
span at least one planned page, so at reduced scale pass
``--vmem_kib 800`` to force a small page.
"""

from __future__ import annotations

import sys
import time


def main(argv=None) -> int:
    import numpy as np

    from repro.configs import get_model_config, parse_cli
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import SamplingConfig

    overrides, _ = parse_cli(argv if argv is not None else sys.argv[1:])
    arch = overrides.pop("arch", "llama3.2-1b")
    reduced = overrides.pop("reduced", "true").lower() in ("1", "true",
                                                           "yes")
    n_new = int(overrides.pop("tokens", "16"))
    batch = int(overrides.pop("batch", "4"))
    prompt_len = int(overrides.pop("prompt_len", "64"))
    mixed = overrides.pop("mixed", "0").lower() in ("1", "true", "yes")
    kind = overrides.pop("sampling", "greedy")
    temperature = float(overrides.pop("temperature", "1.0"))
    top_k = int(overrides.pop("top_k", "0"))
    seed = int(overrides.pop("seed", "0"))
    batching = overrides.pop("batching", "auto")
    prefill = overrides.pop("prefill", "chunked")
    prefix = overrides.pop("prefix", "off")
    cluster = int(overrides.pop("cluster", "0"))
    disagg = overrides.pop("disagg", "")
    policy = overrides.pop("policy", "free_pages")
    serve_http = overrides.pop("serve", "0").lower() in ("1", "true", "yes")
    port = int(overrides.pop("port", "8480"))
    transport = overrides.pop("transport", "thread")
    vmem_kib = int(overrides.pop("vmem_kib", "0"))
    trace_path = overrides.pop("trace", "")
    metrics_interval = float(overrides.pop("metrics_interval", "0"))
    show_stats = overrides.pop("stats", "0").lower() in ("1", "true",
                                                         "yes")

    enable_compile_cache()
    cfg = get_model_config(arch)
    if reduced:
        cfg = cfg.reduced()
    sampling = SamplingConfig(kind=kind, temperature=temperature,
                              top_k=top_k or (40 if kind == "top_k" else 0),
                              seed=seed)
    if batching not in ("cohort", "paged", "auto"):
        raise SystemExit(f"--batching must be cohort|paged|auto, "
                         f"got {batching!r}")
    if prefill not in ("chunked", "monolithic"):
        raise SystemExit(f"--prefill must be chunked|monolithic, "
                         f"got {prefill!r}")
    if prefix not in ("off", "radix"):
        raise SystemExit(f"--prefix must be off|radix, got {prefix!r}")
    if cluster or disagg:
        return _main_cluster(
            arch=arch, cfg=cfg, reduced=reduced, n_new=n_new, batch=batch,
            prompt_len=prompt_len, seed=seed, prefix=prefix or "radix",
            cluster=cluster, disagg=disagg, policy=policy,
            serve_http=serve_http, port=port, transport=transport,
            vmem_kib=vmem_kib, trace_path=trace_path,
            show_stats=show_stats)
    engine = build_engine(cfg, n_new=n_new, batch=batch,
                          max_len=prompt_len + n_new + 1, batching=batching,
                          prefill=prefill, prefix=prefix, sampling=sampling,
                          seed=seed)

    rng = np.random.default_rng(seed)
    prompts = []
    for i in range(batch):
        plen = prompt_len if not mixed else max(8, prompt_len // (1 + i % 2))
        prompts.append(engine_prompt(cfg, plen, rng))

    stop_metrics = None
    if metrics_interval > 0:
        stop_metrics = _metrics_ticker(engine.obs, metrics_interval)
    t0 = time.perf_counter()
    try:
        outs = engine.generate(prompts)
    finally:
        if stop_metrics is not None:
            stop_metrics()
    dt = time.perf_counter() - t0

    n_tok = sum(len(o) for o in outs)
    m = engine.metrics
    print(f"[serve] arch={arch} requests={batch} prompt={prompt_len}"
          f"{' (mixed)' if mixed else ''} sampling={kind} "
          f"batching={m['batching']}")
    print(f"[serve] plan: page_tokens={m['page_tokens']} "
          f"page_bytes={m['page_bytes']} kv_shard={m['kv_shard']} "
          f"budget={m['budget_bytes'] / 2**30:.1f}GiB")
    print(f"[serve] {n_tok} tokens in {dt:.2f}s ({n_tok / max(dt, 1e-9):.1f} "
          f"tok/s); cohorts={m['cohorts']} decode_steps={m['decode_steps']} "
          f"evictions={m['evictions']} "
          f"prefill_chunks={m.get('prefill_chunks', 0)} "
          f"slot_utilization={m.get('slot_utilization', 0.0):.2f} "
          f"backfills={m.get('backfills', 0)} "
          f"peak_resident={m.get('peak_resident_bytes', 0)}B")
    if m.get("prefix_cache") == "radix":
        print(f"[serve] prefix: hits={m.get('prefix_hits', 0)} "
              f"hit_tokens={m.get('prefix_hit_tokens', 0)} "
              f"pages_saved={m.get('pages_saved', 0)} "
              f"cow_copies={m.get('cow_copies', 0)} "
              f"hit_rate={m.get('prefix_hit_rate', 0.0):.2f} "
              f"resident_pages={m.get('prefix_resident_pages', 0)} "
              f"budget={m.get('prefix_budget_bytes', 0)}B")
    print(f"[serve] sample continuation ids: {outs[0][:8]}")
    if trace_path:
        engine.tracer.export_chrome(trace_path)
        print(f"[serve] trace: {len(engine.tracer.export_events())} events"
              f" -> {trace_path} (chrome://tracing / ui.perfetto.dev)")
    if show_stats:
        # The registry's formatted snapshot (DESIGN.md §13): sorted
        # keys, units annotated -- identical shape across cohort, paged
        # and cluster modes.
        print("[serve] metrics registry:")
        print(engine.obs.format_table())
    return 0


def build_engine(cfg, *, n_new: int, batch: int, max_len: int,
                 batching: str = "auto", prefill: str = "chunked",
                 prefix: str = "off", sampling=None, seed: int = 0):
    """The engine ``repro-serve`` serves with: float32, on every device of
    this host, ``batch`` decode slots planned for ``max_len`` tokens each.
    ``batching="auto"`` resolves inside ``ServeEngine`` against its own
    decode plan: paged exactly when the plan exposes a page level and the
    family has a per-slot decode path; "cohort" keeps the PR 4 engine as
    the A/B baseline."""
    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    from repro.serve import SamplingConfig, ServeEngine, ServePolicy

    return ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=n_new, max_slots=max(1, batch),
                           max_len=max_len, batching=batching,
                           prefill=prefill, prefix_cache=prefix,
                           sampling=sampling or SamplingConfig()),
        dtype=jnp.float32, seed=seed)


def _metrics_ticker(registry, interval_s: float):
    """Print the registry snapshot every ``interval_s`` on a daemon
    thread (``--metrics-interval``); returns a stop() callable."""
    import threading

    stop = threading.Event()

    def run():
        n = 0
        while not stop.wait(interval_s):
            n += 1
            snap = registry.snapshot()
            keys = ("tokens", "decode_steps", "prefill_chunks",
                    "free_pages", "used_pages", "evictions", "stalls")
            line = " ".join(f"{k}={snap[k]}" for k in keys if k in snap)
            print(f"[metrics t+{n * interval_s:.1f}s] {line}")

    threading.Thread(target=run, name="metrics-ticker",
                     daemon=True).start()
    return stop.set


def _main_cluster(*, arch, cfg, reduced, n_new, batch, prompt_len, seed,
                  prefix, cluster, disagg, policy, serve_http, port, transport,
                  vmem_kib=0, trace_path="", show_stats=False) -> int:
    """``repro-serve --cluster N [--disagg P:D] [--serve]``: the fleet
    width comes from the plan's DCN level, each replica hosts one
    single-host ``ServeEngine``, the router places by ``--policy``."""
    import numpy as np

    from repro.cluster import (ClusterServer, DisaggCluster, EngineSpec,
                               ServeCluster)
    from repro.hw.tpu import chip_spec
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import plan_decode

    if disagg:
        p, d = (int(x) for x in disagg.split(":"))
        cluster = cluster or (p + d)
    chip = (("vmem_bytes", vmem_kib << 10),
            ("vmem_reserved_bytes", 0)) if vmem_kib else ()
    # Engines run float32 (EngineSpec), so plan with their KV width --
    # the guard below compares against the geometry they will realize.
    plan = plan_decode(cfg, make_host_mesh(),
                       max_len=prompt_len + n_new + 1, dtype_bytes=4,
                       spec=chip_spec(**dict(chip)),
                       cluster=max(1, cluster))
    dcn = plan.level("DCN")
    page_tokens = int((plan.page_plan() or {}).get("page_tokens", 0) or 0)
    if disagg and page_tokens and prompt_len < page_tokens:
        # Disaggregation streams COMPLETED pages; a prompt inside one
        # page has nothing to export.  At reduced scale the default
        # chip's VMEM page covers the whole sequence, so the demo needs
        # a forced-small page.
        raise SystemExit(
            f"--disagg needs the prompt to span >= 1 planned page, but "
            f"page_tokens={page_tokens} > prompt_len={prompt_len}; "
            f"raise --prompt_len or shrink the page with --vmem_kib 800")
    spec = EngineSpec(arch=arch, reduced=reduced, max_new_tokens=n_new,
                      max_slots=1,
                      max_len=prompt_len + n_new + 1,
                      prefix_cache="radix" if prefix == "off" else prefix,
                      chip=chip)
    print(f"[cluster] arch={arch} replicas={plan.replicas()} "
          f"(DCN np={dcn.np if dcn else 1}) policy={policy} "
          f"transport={transport}"
          + (f" disagg={disagg}" if disagg else ""))
    if disagg:
        front = DisaggCluster.from_plan(plan, spec, split=disagg,
                                        transport=transport, policy=policy)
    else:
        front = ServeCluster.from_plan(plan, spec, transport=transport,
                                       policy=policy)
    try:
        if serve_http:
            if disagg:
                raise SystemExit("--serve fronts a ServeCluster; run "
                                 "--disagg without --serve (the HTTP "
                                 "front end routes whole requests)")
            srv = ClusterServer(front, port=port).start()
            host, bound = srv.address
            print(f"[cluster] serving on http://{host}:{bound} "
                  f"(/generate /healthz /stats); ctrl-c to stop")
            try:
                srv.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                srv.close()
            return 0
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, cfg.vocab_size, prompt_len,
                                dtype=np.int32).tolist()
                   for _ in range(batch)]
        import time as _time

        t0 = _time.perf_counter()
        if disagg:
            outs = [front.generate(p, n_new) for p in prompts]
        else:
            outs = front.generate(prompts, n_new)
        dt = _time.perf_counter() - t0
        n_tok = sum(len(o) for o in outs)
        for st in front.stats():
            print(f"[cluster] replica {st.replica} role={st.role} "
                  f"free_pages={st.free_pages}/{st.pages_total} "
                  f"slots={st.slots_free}/{st.slots_total} "
                  f"prefix_nodes={st.prefix_nodes} tokens={st.tokens}")
        print(f"[cluster] {n_tok} tokens in {dt:.2f}s "
              f"({n_tok / max(dt, 1e-9):.1f} tok/s)")
        print(f"[cluster] sample continuation ids: {outs[0][:8]}")
        if trace_path and hasattr(front, "trace_events"):
            from repro.obs import write_chrome
            evs = front.trace_events()
            write_chrome(trace_path, evs)
            pids = sorted({e.get("pid") for e in evs
                           if e.get("ph") != "M"})
            print(f"[cluster] trace: {len(evs)} events from pids {pids} "
                  f"-> {trace_path} (one timeline; pid = replica id, "
                  f"pid {len(front.replicas)} = router)")
        if show_stats:
            for st in front.stats():
                if not st.metrics:
                    continue
                print(f"[cluster] replica {st.replica} metrics registry:")
                for k in sorted(st.metrics):
                    v = st.metrics[k]
                    if isinstance(v, float):
                        v = f"{v:.6g}"
                    print(f"  {k} {v}")
    finally:
        front.close()
    return 0


def engine_prompt(cfg, prompt_len: int, rng):
    """A synthetic prompt in the family's input format (frontend stubs per
    the assignment: VLM/audio cells receive precomputed embeddings)."""
    import numpy as np

    if cfg.family == "vlm":
        return {
            "embeds": (rng.standard_normal((prompt_len, cfg.d_model))
                       .astype(np.float32) * 0.02),
            "positions_3d": np.broadcast_to(
                np.arange(prompt_len, dtype=np.int32)[None], (3, prompt_len)),
        }
    if cfg.family == "enc_dec":
        return {
            "enc_embeds": (rng.standard_normal((prompt_len, cfg.d_model))
                           .astype(np.float32) * 0.02),
            "tokens": rng.integers(0, cfg.vocab_size, prompt_len,
                                   dtype=np.int32),
        }
    return rng.integers(0, cfg.vocab_size, prompt_len, dtype=np.int32)


if __name__ == "__main__":
    sys.exit(main())

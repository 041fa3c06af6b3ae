"""repro.obs: the unified metrics spine, request tracing, and
plan-vs-actual accounting (DESIGN.md §13).

What is pinned here:

  * Tracer: nested spans export balanced, chronologically ordered
    Chrome ``trace_event`` JSON that ``validate_events`` accepts, for
    any nesting shape; the ring bounds memory and counts drops.
  * Metrics: ``Counter`` is monotonic (negative increments raise --
    the recompute-preemption fix), ``Histogram`` percentiles track a
    sorted-list oracle within one log-bucket of relative error, and
    ``MetricsView`` keeps the engine's legacy ``self.metrics[...]``
    read/write surface working on top of the registry.
  * Engine integration: a recorded paged workload populates the
    registry, ``engine.stats()`` keeps its keys, ``tokens`` never goes
    negative under recompute preemption (the discarded work lands in
    ``tokens_recomputed`` instead), and the interleave/token-time logs
    are bounded with exposed drop counts.
  * Plan-vs-actual: for all four served families the observed pool
    peak lands inside the plan's ``page_table`` budget and every
    residual is finite.
  * Cluster: the router's placement instants and both replicas' spans
    merge onto one timeline; ``/metrics`` exposition parses.
"""

import json
import math
import random

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_model_config
from repro.hw.tpu import chip_spec
from repro.launch.mesh import make_host_mesh
from repro.obs import (Counter, Gauge, Histogram, MetricsView, Registry,
                       RingLog, Tracer, merge_events, plan_vs_actual,
                       prometheus_lines, validate_events)
from repro.serve import ServeEngine, ServePolicy
from repro.serve.pages import pool_page_bytes

FOUR_FAMILIES = ["llama3.2-1b", "mixtral-8x7b", "zamba2-1.2b", "xlstm-1.3b"]

SMALL = dict(vmem_bytes=800 << 10, vmem_reserved_bytes=0)


# ---------------------------------------------------------------------------
# Tracer: spans, ordering, ring bounds, Chrome schema
# ---------------------------------------------------------------------------


def _nest(tracer, shape, depth=0):
    """Open one span per entry of ``shape`` (an int tree encoded as a
    list of child counts per level), recursively."""
    for i, kids in enumerate(shape):
        with tracer.span(f"s{depth}_{i}"):
            tracer.instant(f"i{depth}_{i}")
            if depth + 1 < len(shape):
                _nest(tracer, shape[: kids + 1], depth + 1)


@settings(max_examples=30, deadline=None)
@given(width=st.integers(1, 4), kids=st.integers(0, 3),
       depth=st.integers(1, 3))
def test_span_nesting_exports_valid_balanced_trace(width, kids, depth):
    tracer = Tracer(pid=7)
    _nest(tracer, [kids] * width * depth)
    events = tracer.chrome_events()
    assert validate_events(events) == []
    begins = [e for e in events if e["ph"] == "B"]
    ends = [e for e in events if e["ph"] == "E"]
    assert len(begins) == len(ends) >= width
    # Chronological within the export (metadata events lead).
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert all(e["pid"] == 7 for e in events)


def test_span_is_exception_safe():
    tracer = Tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("outer"):
            raise RuntimeError("boom")
    assert validate_events(tracer.chrome_events()) == []


def test_tracer_ring_bounds_and_drop_count():
    tracer = Tracer(capacity=8)
    for i in range(50):
        tracer.instant(f"e{i}")
    assert tracer.dropped == 42
    events = tracer.export_events()
    assert len(events) == 8
    assert events[0]["name"] == "e42"        # oldest dropped first


def test_tracer_disabled_records_nothing():
    tracer = Tracer(enabled=False)
    with tracer.span("s"):
        tracer.instant("i")
    assert tracer.export_events() == []


def test_export_chrome_file_loads_in_perfetto_shape(tmp_path):
    tracer = Tracer(pid=3, process_name="replica-3")
    with tracer.span("request", tid=5, args={"rid": 4}):
        tracer.complete("prefill_chunk", tracer.now() - 1e-3,
                        tracer.now(), tid=5)
    path = tmp_path / "trace.json"
    tracer.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    assert validate_events(doc["traceEvents"]) == []
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"B", "E", "X", "M"} <= phases
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"] == "replica-3"


def test_merge_events_interleaves_timelines():
    a, b = Tracer(pid=0), Tracer(pid=1, process_name="one")
    a.instant("a0")
    b.instant("b0")
    a.instant("a1")
    merged = merge_events(a.chrome_events(), b.chrome_events())
    assert validate_events(merged) == []
    body = [e for e in merged if e["ph"] != "M"]
    assert [e["ts"] for e in body] == sorted(e["ts"] for e in body)
    assert {e["pid"] for e in body} == {0, 1}
    # Metadata events lead so Perfetto names processes before samples.
    assert merged[0]["ph"] == "M"


def test_validate_events_flags_garbage():
    assert validate_events([{"ph": "B"}])           # missing keys
    assert validate_events([{"name": "x", "ph": "?", "ts": 0.0,
                             "pid": 0, "tid": 0}])  # unknown phase
    assert validate_events([{"name": "x", "ph": "E", "ts": 0.0,
                             "pid": 0, "tid": 0}])  # E without B


def test_ringlog_bounds_and_read_patterns():
    log = RingLog(maxlen=4)
    for i in range(10):
        log.append(i)
    assert list(log) == [6, 7, 8, 9]
    assert log.dropped == 6
    assert len(log) == 4
    assert log[0] == 6 and log[-1] == 9
    assert log[1:3] == [7, 8]
    assert [0] + log == [0, 6, 7, 8, 9]      # benchmark __radd__ pattern
    log.clear()
    assert list(log) == [] and log.dropped == 6


# ---------------------------------------------------------------------------
# Metrics: counters, histograms, registry, view
# ---------------------------------------------------------------------------


def test_counter_is_monotonic():
    c = Counter("tokens")
    c.inc()
    c.inc(5)
    assert c.value == 6
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 6


def test_gauge_set_max_tracks_peak():
    g = Gauge("peak")
    g.set_max(3)
    g.set_max(1)
    assert g.value == 3
    g.set(0)
    assert g.value == 0


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 300), seed=st.integers(0, 10_000),
       p=st.sampled_from([50, 90, 95, 99, 100]))
def test_histogram_percentile_tracks_sorted_oracle(n, seed, p):
    rng = random.Random(seed)
    h = Histogram("lat")
    values = [rng.uniform(1e-5, 100.0) for _ in range(n)]
    for v in values:
        h.observe(v)
    values.sort()
    rank = max(1, math.ceil(p / 100 * n))
    oracle = values[rank - 1]
    got = h.percentile(p)
    # The log buckets guarantee one-bucket relative resolution.
    assert oracle * (1 - 1e-9) <= got <= oracle * h.growth * (1 + 1e-9)


def test_histogram_empty_and_overflow():
    h = Histogram("lat")
    assert h.percentile(50) == 0.0
    h.observe(1e9)                            # beyond the top bound
    assert h.percentile(99) == 1e9            # overflow reports true max


def test_registry_snapshot_and_prometheus():
    r = Registry()
    r.inc("tokens", 3)
    r.set("free_pages", 7, unit="pages")
    r.observe("ttft_s", 0.25)
    snap = r.snapshot()
    assert snap["tokens"] == 3 and snap["free_pages"] == 7
    assert snap["ttft_s.count"] == 1
    text = r.to_prometheus(labels={"replica": "0"})
    assert '# TYPE repro_tokens counter' in text
    assert 'repro_tokens{replica="0"} 3' in text
    assert 'repro_free_pages{replica="0"} 7' in text
    table = r.format_table()
    assert any("free_pages" in line and "pages" in line
               for line in table.splitlines())


def test_registry_type_conflict_raises():
    r = Registry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.gauge("x")


def test_prometheus_lines_from_remote_snapshot():
    lines = prometheus_lines({"tokens": 5, "nan": float("nan"),
                              "note": "text", "flag": True},
                             labels={"replica": "1", "role": "serve"})
    joined = "\n".join(lines)
    assert 'repro_tokens{replica="1",role="serve"} 5' in joined
    assert "nan" not in joined and "note" not in joined \
        and "flag" not in joined


def test_metrics_view_keeps_legacy_surface():
    r = Registry()
    r.counter("tokens")
    view = MetricsView(r, objects={"batching": "paged"})
    view["tokens"] += 2                      # legacy += on a counter
    assert view["tokens"] == 2 == r.value("tokens")
    with pytest.raises(ValueError):
        view["tokens"] = 1                   # decrement refused
    view["new_scalar"] = 4.5                 # unknown scalars -> gauges
    assert isinstance(r.get("new_scalar"), Gauge)
    view["trace"] = ["a", "b"]               # non-scalars -> side table
    assert view["trace"] == ["a", "b"]
    assert view["batching"] == "paged"
    assert {"tokens", "new_scalar", "trace", "batching"} <= set(view)


# ---------------------------------------------------------------------------
# Engine integration: registry-backed metrics on a recorded workload
# ---------------------------------------------------------------------------


def _paged_engine(arch="llama3.2-1b", **pol):
    cfg = get_model_config(arch).reduced()
    defaults = dict(max_new_tokens=6, max_slots=2, max_len=128,
                    batching="paged")
    defaults.update(pol)
    return ServeEngine(cfg, make_host_mesh(), policy=ServePolicy(**defaults),
                       spec=chip_spec(**SMALL))


def test_engine_registry_view_equivalence():
    engine = _paged_engine(prefix_cache="radix", max_slots=4)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 256, 12, dtype=np.int32)
    outs = engine.generate(
        [np.concatenate([shared, rng.integers(0, 256, 4 + i,
                                              dtype=np.int32)])
         for i in range(3)])
    m = engine.metrics
    # Every legacy key the benchmarks read is still served by the view.
    for key in ("tokens", "decode_steps", "prefill_chunks", "page_tokens",
                "pages_total", "peak_pages", "slot_utilization",
                "interleave", "token_times", "batching"):
        assert key in m, key
    assert m["tokens"] == sum(len(o) for o in outs) \
        == engine.obs.value("tokens")
    assert m["batching"] == "paged"
    st_keys = set(engine.stats())
    assert {"tokens", "free_pages", "used_pages"} <= st_keys
    # Latency surface: one TTFT per request, inter-token fills the rest.
    assert engine.obs.get("ttft_s").count == 3
    assert engine.obs.get("inter_token_s").count == sum(
        len(o) for o in outs) - 3
    assert engine.obs.get("queue_wait_s").count == 3
    # The registry round-trips through Prometheus exposition.
    assert "repro_tokens" in engine.obs.to_prometheus()


def test_engine_trace_has_request_spans():
    engine = _paged_engine()
    rng = np.random.default_rng(1)
    engine.generate([rng.integers(0, 256, 9, dtype=np.int32)])
    events = engine.tracer.chrome_events()
    assert validate_events(events) == []
    names = {e["name"] for e in events}
    assert {"submit", "queue_wait", "prefill_chunk", "first_token",
            "decode_tick", "request"} <= names
    req = [e for e in events if e["name"] == "request" and e["ph"] == "X"]
    assert req and req[0]["tid"] == req[0]["args"]["rid"] + 1


def test_tokens_never_negative_under_recompute_preemption():
    """The satellite fix: preemption used to SUBTRACT the discarded
    tokens from ``metrics['tokens']``, which could swing it transiently
    negative.  Now the counter is monotonic and the discarded work is
    accounted in ``tokens_recomputed``."""
    cfg = get_model_config("llama3.2-1b").reduced()
    mesh = make_host_mesh()
    probe = ServeEngine(cfg, mesh,
                        policy=ServePolicy(max_len=128, batching="paged"),
                        spec=chip_spec(**SMALL))
    t = probe.page.page_tokens
    engine = ServeEngine(
        cfg, mesh,
        policy=ServePolicy(max_len=4 * t, max_slots=2, batching="paged",
                           kv_budget_bytes=3 * pool_page_bytes(cfg,
                                                               probe.page)),
        spec=chip_spec(**SMALL))
    rng = np.random.default_rng(0)
    deep, shallow = 3 * t - 8, 2 * t - 8
    outs = engine.generate(
        [rng.integers(0, 256, 8, dtype=np.int32) for _ in range(2)],
        max_new_tokens=[deep, shallow])
    delivered = sum(len(o) for o in outs)
    m = engine.metrics
    assert m["evictions"] >= 1               # the preemption path ran
    assert m["tokens_recomputed"] >= 1
    assert m["tokens"] >= delivered >= 0     # monotonic: emitted >= kept
    assert m["tokens"] - m["tokens_recomputed"] == delivered
    # The preempted request's token-time log was reset, not negated.
    assert all(len(times) <= ServeEngine.TOKEN_TIMES_CAPACITY
               for times in m["token_times"].values())


def test_interleave_and_token_times_are_bounded():
    engine = _paged_engine()
    engine.LOG_CAPACITY = 8                  # shrink the rings for test
    engine.TOKEN_TIMES_CAPACITY = 4
    rng = np.random.default_rng(2)
    outs = engine.generate([rng.integers(0, 256, 9, dtype=np.int32)],
                           max_new_tokens=[12])
    m = engine.metrics
    assert len(outs[0]) == 12
    assert len(m["interleave"]) <= 8
    assert all(len(v) <= 4 for v in m["token_times"].values())
    # Drops are observable, not silent.
    assert m["interleave_dropped"] >= 1
    assert m["token_times_dropped"] >= 1


# ---------------------------------------------------------------------------
# Plan-vs-actual
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FOUR_FAMILIES)
def test_plan_vs_actual_pool_peak_within_budget(arch):
    engine = _paged_engine(arch)
    rng = np.random.default_rng(0)
    page = getattr(engine, "page", None)
    t = page.page_tokens if page is not None else 12
    engine.generate([rng.integers(0, engine.cfg.vocab_size, 8,
                                  dtype=np.int32) for _ in range(2)],
                    max_new_tokens=[t + 2, 4])
    rows = plan_vs_actual(engine.plan, engine.obs)
    assert len(rows) >= len(list(engine.plan.levels()))
    by_metric = {r["metric"]: r for r in rows}
    if engine.plan.page_table():
        # The acceptance bound: the pool's observed peak lands inside
        # the plan's page_table budget.  (xLSTM is fully recurrent --
        # no page level, so the bound is vacuous there.)
        pool = by_metric["pool_pages"]
        assert pool["observed"] >= 1         # the pool actually ran
        assert pool["observed"] <= pool["predicted"]
    for r in rows:
        if r["ratio"] is not None:
            assert math.isfinite(r["ratio"]), r
    # (The vmem_working_set row is only within band on realistic chip
    # specs -- the forced-tiny SMALL VMEM clamps to the minimum page,
    # which no longer fits double-buffered; obs_dry checks the realistic
    # case.  Here finiteness plus the pool bound is the contract.)


def test_plan_vs_actual_flags_overrun():
    engine = _paged_engine()
    rng = np.random.default_rng(0)
    engine.generate([rng.integers(0, 256, 9, dtype=np.int32)])
    engine.obs.set_max(
        "pool_peak_pages",
        10 * int(engine.plan.page_table()["pages_total"]))
    rows = plan_vs_actual(engine.plan, engine.obs)
    pool = next(r for r in rows if r["metric"] == "pool_pages")
    assert pool["ratio"] > 1 and not pool["within_band"]
    from repro.obs import format_report
    report = format_report(rows)
    assert any("outside band" in line for line in report)
    assert any("--calibrate" in line for line in report)


# ---------------------------------------------------------------------------
# Cluster: one timeline, one exposition
# ---------------------------------------------------------------------------


def test_cluster_trace_merges_router_and_replicas():
    from repro.cluster import EngineSpec, ServeCluster
    from repro.serve.engine import plan_decode

    cfg = get_model_config("llama3.2-1b").reduced()
    plan = plan_decode(cfg, make_host_mesh(), max_len=256,
                       spec=chip_spec(), cluster=2)
    cluster = ServeCluster.from_plan(plan, EngineSpec(max_slots=2),
                                     transport="thread",
                                     policy="round_robin", affinity=False)
    try:
        rng = np.random.default_rng(0)
        outs = cluster.generate(
            [rng.integers(0, 256, 8, dtype=np.int32) for _ in range(2)],
            max_new_tokens=4)
        assert [len(o) for o in outs] == [4, 4]
        events = cluster.trace_events()
        assert validate_events(events) == []
        routes = [e for e in events if e["name"] == "route"]
        assert len(routes) >= 2
        assert all(e["pid"] == 2 for e in routes)   # router's own pid
        req_pids = {e["pid"] for e in events if e["name"] == "request"}
        assert req_pids == {0, 1}            # both replicas on the timeline
        text = cluster.prometheus()
        assert "repro_route_decisions" in text
        assert 'repro_tokens{replica="0",role="serve"}' in text
        assert 'repro_replica_free_pages{replica="1",role="serve"}' in text
    finally:
        cluster.close()


def test_replica_stats_forward_registry_snapshot():
    from repro.cluster import EngineSpec, Replica

    rep = Replica(EngineSpec(max_slots=2), replica=0, transport="thread")
    try:
        rng = np.random.default_rng(4)
        rep.generate([rng.integers(0, 256, 8, dtype=np.int32)], 4).wait()
        st_ = rep.stats()
        assert st_.metrics.get("decode_steps", 0) >= 1
        assert st_.metrics.get("free_pages") == st_.free_pages
        assert rep.trace() and validate_events(rep.trace()) == []
    finally:
        rep.close()

"""repro.cluster: the DCN level as a real placement decision (DESIGN.md
§12).

What is pinned here:

  * Router properties: the ``free_pages`` policy always lands on the
    argmax-free-pages admissible replica, ties break deterministically
    (outstanding load, then lowest id), drained replicas are never
    admitted.  Prefix affinity overrides the policy only after a prefix
    has a home.
  * The worker protocol (both transports): instruction queue in, demuxed
    token streams / results / errors / telemetry ticks out; drain
    requeues not-yet-started work; the straggler sweep drains on routed
    TTFT evidence.
  * Plan admissibility: ``plan_decode`` raises the structured
    ``PlanError`` on a DCN-bearing plan without ``cluster=``;
    ``cluster=N`` realizes N replicas WITHOUT reshaping the per-replica
    page geometry.
  * Token identity: a routed 2-replica cluster emits byte-identical
    per-request streams to a single ``ServeEngine``, for all four served
    families; disaggregated prefill->decode is token-identical too, and
    decode admission is gated on the last page's arrival.
"""

import json
import time
import urllib.request

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cluster import (ClusterServer, DisaggCluster, PageStreamReceiver,
                           EngineSpec, Replica, ReplicaStats, Router,
                           ServeCluster, StubSpec, export_transfer,
                           import_transfer, transfer_order)
from repro.configs import get_model_config
from repro.ft.resilience import StragglerPolicy
from repro.hw.tpu import chip_spec
from repro.launch.mesh import make_host_mesh
from repro.serve import ServeEngine, ServePolicy
from repro.serve.engine import PlanError, plan_decode

#: One arch per served family, as in test_serve_engine: dense attention,
#: MoE (sliding-window), hybrid SSM (Mamba2 + shared attn), xLSTM.
FOUR_FAMILIES = ["llama3.2-1b", "mixtral-8x7b", "zamba2-1.2b", "xlstm-1.3b"]

#: Tiny forced VMEM so the planned page is small and several pages per
#: sequence are exercised (the same knob the paged/prefix tests use).
SMALL = dict(vmem_bytes=800 << 10, vmem_reserved_bytes=0)


def _stats(free, drained=(), queued=None):
    return [ReplicaStats(replica=i, free_pages=f,
                         queued=0 if queued is None else queued[i],
                         drained=i in drained)
            for i, f in enumerate(free)]


# ---------------------------------------------------------------------------
# Router properties
# ---------------------------------------------------------------------------


@settings(max_examples=100, deadline=None)
@given(f0=st.integers(0, 64), f1=st.integers(0, 64), f2=st.integers(0, 64),
       drain0=st.booleans(), drain1=st.booleans())
def test_free_pages_routes_to_argmax_admissible(f0, f1, f2, drain0, drain1):
    drained = {i for i, d in ((0, drain0), (1, drain1)) if d}
    router = Router(3, "free_pages", affinity=False)
    stats = _stats([f0, f1, f2], drained=drained)
    pick = router.route(stats)
    live = [i for i in range(3) if i not in drained]
    assert pick in live                      # drained never admitted
    assert stats[pick].free_pages == max(stats[i].free_pages for i in live)
    # Deterministic: equal-load ties go to the LOWEST admissible id.
    best = max(stats[i].free_pages for i in live)
    assert pick == min(i for i in live if stats[i].free_pages == best)


def test_free_pages_tie_breaks_on_load_then_id():
    router = Router(2, "free_pages", affinity=False)
    assert router.route(_stats([8, 8])) == 0            # pure tie: lowest id
    assert router.route(_stats([8, 8], queued=[3, 0])) == 1   # load breaks it
    assert router.route(_stats([8, 9], queued=[0, 5])) == 1   # memory first


def test_all_drained_raises():
    router = Router(2, "free_pages")
    router.drain(0)
    with pytest.raises(RuntimeError, match="drained"):
        router.route(_stats([4, 4], drained={1}))


def test_round_robin_cycles_admissible_only():
    router = Router(3, "round_robin", affinity=False)
    stats = _stats([1, 1, 1], drained={1})
    assert [router.route(stats) for _ in range(4)] == [0, 2, 0, 2]


def test_least_loaded_prefers_fewest_outstanding():
    router = Router(3, "least_loaded", affinity=False)
    assert router.route(_stats([0, 0, 0], queued=[2, 0, 1])) == 1


def test_prefix_affinity_sticks_after_first_placement():
    router = Router(2, "free_pages", page_tokens=4)
    toks = list(range(8))                   # two full pages
    assert router.route(_stats([1, 9]), toks) == 1
    # The home replica keeps the prefix even once it is page-poor...
    assert router.route(_stats([9, 1]), toks) == 1
    # ...but a sub-page prompt has no affinity key and follows the policy.
    assert router.route(_stats([9, 1]), list(range(3))) == 0
    # A drained home is rerouted (and re-homed) instead of starved.
    router.drain(1)
    assert router.route(_stats([9, 1], drained={1}), toks) == 0


def test_straggler_sweep_drains_and_undrain_forgets():
    pol = StragglerPolicy(k=1.0, min_samples=2)
    router = Router(3, "round_robin", affinity=False, straggler=pol)
    for _ in range(4):
        router.note_latency(0, 0.01)
        router.note_latency(1, 0.01)
        router.note_latency(2, 5.0)         # the outlier
    assert router.sweep_stragglers() == [2]
    assert 2 in router.drained
    router.undrain(2)
    assert 2 not in router.drained
    assert pol.history.get(2) is None       # fresh samples after re-admit
    assert router.sweep_stragglers() == []


# ---------------------------------------------------------------------------
# Worker protocol (stub engines: no JAX)
# ---------------------------------------------------------------------------


def test_thread_replica_streams_and_ticks():
    rep = Replica(StubSpec(), replica=0, transport="thread")
    try:
        got = []
        call = rep.generate([[1, 2, 3]], 4, on_token=lambda i, t: got.append(t))
        assert call.wait(30) == [[6, 7, 8, 9]]
        assert got == [6, 7, 8, 9]          # streamed == returned
        assert call.first_token_time is not None
        st = rep.stats()
        assert st.tokens == 4 and st.replica == 0
    finally:
        rep.close()


def test_proc_replica_same_protocol_over_spawn():
    rep = Replica(StubSpec(), replica=1, transport="proc")
    try:
        got = []
        call = rep.generate([[5, 5]], 3, on_token=lambda i, t: got.append(t))
        assert call.wait(120) == [[10, 11, 12]]
        assert got == [10, 11, 12]
        for _ in range(200):                # the tick is asynchronous
            if rep.last_stats is not None:
                break
            time.sleep(0.05)
        st = rep.stats()
        assert st.replica == 1 and st.tokens == 3
    finally:
        rep.close()


def test_worker_error_reply_keeps_replica_alive():
    rep = Replica(StubSpec(), replica=0, transport="thread")
    try:
        bad = rep.submit("no_such_op", None)
        with pytest.raises(RuntimeError, match="no_such_op"):
            bad.wait(30)
        assert rep.generate([[1]], 1).wait(30) == [[1]]
    finally:
        rep.close()


def test_drain_requeues_pending_requests():
    slow = Replica(StubSpec(delay_s=0.2), replica=0, transport="thread")
    fast = Replica(StubSpec(), replica=1, transport="thread")
    cluster = ServeCluster([slow, fast], Router(2, "round_robin",
                                                affinity=False))
    try:
        first = cluster.submit([1], 4)      # replica 0, starts immediately
        assert first.replica == 0
        for _ in range(100):
            if first.call.started:
                break
            time.sleep(0.01)
        queued = cluster.submit([2], 2)     # round robin -> 1
        queued2 = cluster.submit([3], 2)    # round robin -> 0: queues
        assert queued2.replica == 0
        moved = cluster.drain_replica(0)
        assert queued2.rid in moved and queued2.replica == 1
        assert queued2.result(30) == [3, 4]
        assert queued.result(30) == [2, 3]
        assert first.result(30) == [1, 2, 3, 4]     # in-flight: finishes
        # Drained replica takes no NEW work.
        after = cluster.submit([4], 1)
        assert after.replica == 1
    finally:
        cluster.close()


def test_cluster_stats_marks_drained():
    cluster = ServeCluster([Replica(StubSpec(), replica=i)
                            for i in range(2)],
                           Router(2, "free_pages", affinity=False))
    try:
        cluster.router.drain(1)
        st = cluster.stats()
        assert [s.drained for s in st] == [False, True]
    finally:
        cluster.close()


# ---------------------------------------------------------------------------
# Plan admissibility (satellite: the structured PlanError)
# ---------------------------------------------------------------------------


def test_dcn_plan_without_cluster_raises_plan_error():
    cfg = get_model_config("llama3.2-1b").reduced()
    spec = chip_spec(**SMALL)
    with pytest.raises(PlanError) as ei:
        plan_decode(cfg, make_host_mesh(), max_len=64, spec=spec,
                    hierarchy=spec.hierarchy(mesh_devices=1, hosts=2))
    assert ei.value.level == "DCN"
    assert ei.value.plan is not None and ei.value.plan.level("DCN") is not None


def test_cluster_plan_width_without_reshaping_replica_geometry():
    cfg = get_model_config("llama3.2-1b").reduced()
    spec = chip_spec(**SMALL)
    mesh = make_host_mesh()
    fleet = plan_decode(cfg, mesh, max_len=64, spec=spec, cluster=2)
    single = plan_decode(cfg, mesh, max_len=64, spec=spec)
    dcn = fleet.level("DCN")
    assert fleet.replicas() == dcn.np == 2
    assert dcn.detail["placement"] == "replicas"
    # The DCN level chooses WIDTH; the per-replica subtree is untouched.
    assert dict(fleet.page_table()) == dict(single.page_table())
    assert fleet.page_plan()["page_tokens"] == \
        single.page_plan()["page_tokens"]
    assert single.replicas() == 1 and single.level("DCN") is None


# ---------------------------------------------------------------------------
# engine.stats() (satellite: consolidated telemetry)
# ---------------------------------------------------------------------------


def test_engine_stats_consolidates_pool_and_prefix():
    cfg = get_model_config("llama3.2-1b").reduced()
    engine = ServeEngine(
        cfg, make_host_mesh(),
        policy=ServePolicy(max_new_tokens=2, max_len=64, max_slots=1,
                           batching="paged", prefix_cache="radix"),
        spec=chip_spec(**SMALL))
    keys = {"batching", "free_pages", "used_pages", "pages_total",
            "slots_free", "slots_total", "page_tokens", "page_bytes",
            "kv_shard", "tokens", "decode_steps", "prefill_chunks",
            "prefix_nodes", "prefix_pages", "prefix_resident_bytes"}
    before = engine.stats()
    assert keys <= set(before)
    assert before.pages_total if False else before["pages_total"] > 0
    t = engine.page.page_tokens
    rng = np.random.default_rng(0)
    engine.generate([rng.integers(0, cfg.vocab_size, 2 * t + 1,
                                  dtype=np.int32)], 2)
    after = engine.stats()
    # Live pool telemetry: the radix tree keeps the prompt's completed
    # pages resident, so the pool is visibly less free than the plan.
    assert after["prefix_nodes"] >= 1
    assert after["prefix_pages"] >= 1
    assert after["free_pages"] < after["pages_total"]
    assert after["used_pages"] > 0
    assert after["tokens"] >= 2


# ---------------------------------------------------------------------------
# Token identity (satellite: routed cluster == single engine, 4 families)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FOUR_FAMILIES)
def test_cluster_token_identical_to_single_engine(arch):
    cfg = get_model_config(arch).reduced()
    policy = ServePolicy(max_new_tokens=3, max_len=64, max_slots=1,
                         batching="paged", prefix_cache="radix")
    single = ServeEngine(cfg, make_host_mesh(), policy=policy,
                         spec=chip_spec(**SMALL))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32).tolist()
               for n in (9, 13, 8)]
    ref = [single.generate([p], 3)[0] for p in prompts]

    plan = plan_decode(cfg, make_host_mesh(), max_len=64,
                       spec=chip_spec(**SMALL), cluster=2)
    spec = EngineSpec(arch=arch, max_new_tokens=3, max_slots=1, max_len=64,
                      chip=tuple(SMALL.items()))
    cluster = ServeCluster.from_plan(plan, spec, transport="thread",
                                     policy="free_pages")
    try:
        assert len(cluster.replicas) == plan.replicas() == 2
        streamed = {i: [] for i in range(len(prompts))}
        crs = [cluster.submit(p, 3,
                              on_token=lambda _i, t, j=j: (
                                  streamed[j].clear() if t is None
                                  else streamed[j].append(t)))
               for j, p in enumerate(prompts)]
        got = [cr.result(timeout=600) for cr in crs]
        assert got == ref, arch
        assert [streamed[j] for j in range(len(prompts))] == ref, arch
        # Every replica engine's pool geometry is the plan's page_table.
        for rep in cluster.replicas:
            if rep.engine is not None:
                assert rep.engine.metrics["plan_page_table"] == \
                    dict(single.plan.page_table() or {}), arch
    finally:
        cluster.close()


# ---------------------------------------------------------------------------
# Disaggregation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ring", "serpentine"])
@pytest.mark.parametrize("p", [1, 2, 3, 4, 7])
def test_transfer_order_covers_every_page_once(p, mode):
    order = transfer_order(p, mode)
    assert sorted(order) == list(range(p)), (p, mode, order)


def test_receiver_gates_admission_on_last_page():
    recv = PageStreamReceiver(3)
    recv.receive(0, {"k": 0})
    recv.receive(2, {"k": 2})
    assert not recv.complete
    with pytest.raises(RuntimeError, match="gated"):
        recv.payloads()                     # page 1 never arrived
    recv.receive(1, {"k": 1})
    assert recv.payloads() == [{"k": 0}, {"k": 1}, {"k": 2}]


def test_disagg_prefill_decode_token_identical():
    cfg = get_model_config("llama3.2-1b").reduced()
    policy = ServePolicy(max_new_tokens=4, max_len=128, max_slots=1,
                         batching="paged", prefix_cache="radix")
    single = ServeEngine(cfg, make_host_mesh(), policy=policy,
                         spec=chip_spec(**SMALL))
    t = single.page.page_tokens
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 2 * t + 3,
                          dtype=np.int32).tolist()
    ref = single.generate([prompt], 4)[0]

    plan = plan_decode(cfg, make_host_mesh(), max_len=128,
                       spec=chip_spec(**SMALL), cluster=2)
    spec = EngineSpec(arch="llama3.2-1b", max_new_tokens=4, max_slots=1,
                      max_len=128, chip=tuple(SMALL.items()))
    dc = DisaggCluster.from_plan(plan, spec, split="1:1",
                                 transport="thread")
    try:
        got = dc.generate(prompt, 4)
        assert got == ref
        # The transferred pages produced a real prefix hit on decode.
        dec = dc.decode[0].engine
        assert dec.metrics["prefix_hit_tokens"] >= 2 * t
        # And the export endpoint round-trips standalone too.
        tr = export_transfer(dc.prefill[0], prompt)
        assert tr.n_pages == 2 and tr.first_token == ref[0]
        assert sorted(tr.order) == list(range(tr.n_pages))
        assert import_transfer(dc.decode[0], tr) == 2 * t
    finally:
        dc.close()


def test_disagg_split_must_partition_planned_fleet():
    cfg = get_model_config("llama3.2-1b").reduced()
    plan = plan_decode(cfg, make_host_mesh(), max_len=64,
                       spec=chip_spec(**SMALL), cluster=2)
    with pytest.raises(ValueError, match="partition"):
        DisaggCluster.from_plan(plan, StubSpec(), split="2:2")


# ---------------------------------------------------------------------------
# HTTP front end (stub cluster: protocol only)
# ---------------------------------------------------------------------------


def test_http_generate_streams_chunked_ndjson():
    cluster = ServeCluster([Replica(StubSpec(), replica=i)
                            for i in range(2)],
                           Router(2, "round_robin", affinity=False))
    srv = ClusterServer(cluster).start()
    try:
        host, port = srv.address
        base = f"http://{host}:{port}"
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert json.loads(r.read()) == \
                {"ok": True, "replicas": 2, "admissible": 2}
        req = urllib.request.Request(
            f"{base}/generate",
            data=json.dumps({"tokens": [1, 2],
                             "max_new_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("Transfer-Encoding") == "chunked"
            lines = [json.loads(x) for x in r.read().splitlines()
                     if x.strip()]
        assert [l["token"] for l in lines if "token" in l] == [3, 4, 5]
        assert lines[-1]["done"] and lines[-1]["tokens"] == [3, 4, 5]
        with urllib.request.urlopen(f"{base}/stats", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["policy"] == "round_robin"
        assert len(doc["replicas"]) == 2
        assert {"free_pages", "slots_free", "prefix_nodes"} <= \
            set(doc["replicas"][0])
        with urllib.request.urlopen(f"{base}/nope", timeout=10) as r:
            pass
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        srv.close()
        cluster.close()

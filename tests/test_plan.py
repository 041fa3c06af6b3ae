"""Hierarchical planner tests (``repro.plan``, the one-API redesign).

Covers the ISSUE-3 acceptance surface: the 4-level DCN -> ICI/HBM -> VMEM
-> VREG plan on a 2-host hierarchy, JSON round-trip identity, equivalence
of the legacy entry points (``mesh_decomposition``, ``plan_matmul``,
``Decomposer.decompose``) with the planner sub-plans they now wrap, the
FSDP degree quantization, and a hand-computed 2-host nested search.
"""

import dataclasses

import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_model_config
from repro.core import Decomposer, find_optimal_np, matmul_domain, phi_simple
from repro.core.autotile import plan_matmul
from repro.core.hierarchy import paper_system_a, tpu_hierarchy
from repro.dist.pipeline import dcn_stages
from repro.dist.sharding import arch_rules, mesh_decomposition, mesh_plan
from repro.plan import (
    HierarchicalPlan,
    PlanPolicy,
    Workload,
    leaf_matmul_plan,
    plan_run,
    quantize_divisor,
)

GiB = 1 << 30


def _hier(hosts=2, chips=8, hbm_gb=16):
    return tpu_hierarchy(
        hbm_bytes=hbm_gb * GiB, vmem_bytes=96 << 20,
        mesh_devices=chips, hosts=hosts)


class TestHierarchyWithDCN:
    def test_level_names_and_sizes(self):
        h = _hier(hosts=2, chips=8)
        assert [l.name for l in h.levels()] == \
            ["DCN", "ICI", "HBM", "VMEM", "VREG"]
        ici = h.find("ICI")
        # One ICI copy per host, 8 chips each; DCN holds both.
        assert ici.siblings == [list(range(8)), list(range(8, 16))]
        assert ici.size == 8 * 16 * GiB
        assert h.size == 2 * ici.size
        assert h.find("HBM").n_cores == 16

    def test_single_host_unchanged(self):
        h = tpu_hierarchy(hbm_bytes=16 * GiB, vmem_bytes=96 << 20,
                          mesh_devices=8)
        assert [l.name for l in h.levels()] == ["ICI", "HBM", "VMEM", "VREG"]
        assert h.siblings == [list(range(8))]

    def test_hosts_require_mesh(self):
        with pytest.raises(ValueError):
            tpu_hierarchy(hbm_bytes=1, vmem_bytes=1, hosts=2)


class TestQuantizeDivisor:
    def test_rounds_to_smallest_divisor(self):
        assert quantize_divisor(5, 16) == 8
        assert quantize_divisor(5, 8) == 8
        assert quantize_divisor(3, 12) == 3   # already a divisor
        assert quantize_divisor(5, 12) == 6
        assert quantize_divisor(1, 8) == 1
        assert quantize_divisor(8, 8) == 8
        assert quantize_divisor(9, 8) == 8   # saturates at the extent
        assert quantize_divisor(3, 6) == 3

    def test_unbounded_extent_passthrough(self):
        assert quantize_divisor(5, 0) == 5

    def test_multiple_of_outer_partitions(self):
        # Inner partitions must refine the outer level's: a divisor that
        # does not contain the outer np would straddle a host boundary.
        assert quantize_divisor(3, 6, multiple_of=2) == 6
        assert quantize_divisor(3, 12, multiple_of=4) == 4
        assert quantize_divisor(1, 8, multiple_of=2) == 2
        # No qualifying divisor -> fall back to the unconstrained rule.
        assert quantize_divisor(3, 6, multiple_of=7) == 3


class TestPlanTree:
    """Acceptance: plan_run on tpu_hierarchy(hosts=2, mesh_devices=8)."""

    def test_four_levels(self):
        hp = plan_run(_hier(), Workload(state_bytes=65 * GiB,
                                        matmul=(512, 512, 512)))
        levels = hp.levels()
        assert [lp.level for lp in levels] == ["DCN", "ICI", "VMEM", "VREG"]
        assert [lp.kind for lp in levels] == ["mesh", "mesh", "tile", "leaf"]
        # The ICI node consumed HBM as its TCL.
        assert hp.level("ICI").detail["tcl_level"] == "HBM"
        assert [lp.phi for lp in levels[:3]] == \
            ["phi_mesh", "phi_mesh", "phi_tpu"]

    def test_json_round_trip_identity(self):
        hp = plan_run(_hier(), Workload(state_bytes=65 * GiB,
                                        matmul=(512, 512, 512)))
        assert HierarchicalPlan.from_json(hp.to_json()) == hp
        # And the reconstructed leaf still yields the same tile plan.
        rt = HierarchicalPlan.from_json(hp.to_json())
        assert rt.tile_plan() == hp.tile_plan()

    def test_describe_mentions_dcn_and_quantization(self):
        hp = plan_run(_hier(), Workload(state_bytes=65 * GiB))
        text = "\n".join(hp.describe())
        assert "DCN[mesh]" in text
        assert "quantized=" in text


class TestHandComputedNestedSearch:
    """65 GiB state over 2 hosts x 8 chips of 16 GiB HBM, hand-computed:

    DCN: budget = one host's ICI domain = 8 x 16 = 128 GiB >= 65 GiB, so
         np=1 (replicated across hosts).
    ICI: workers threaded from DCN (1), budget = 16 GiB; smallest np with
         65/np <= 16 is np*=5; quantized to the 8-chip divisor -> 8.
    """

    def test_per_level_np(self):
        hp = plan_run(_hier(hosts=2, chips=8), Workload(state_bytes=65 * GiB))
        dcn, ici = hp.level("DCN"), hp.level("ICI")
        assert (dcn.np_raw, dcn.np) == (1, 1)
        assert ici.n_workers == 1                  # threaded from DCN's np
        assert (ici.np_raw, ici.np) == (5, 8)
        assert ici.budget_bytes == 16 * GiB
        assert dcn.budget_bytes == 128 * GiB

    def test_dcn_partitions_when_host_overflows(self):
        # 4 chips/host -> 64 GiB hosts: the DCN level itself must split the
        # 65 GiB state (np=2), and that np seeds the ICI search's workers.
        hp = plan_run(_hier(hosts=2, chips=4), Workload(state_bytes=65 * GiB))
        dcn, ici = hp.level("DCN"), hp.level("ICI")
        assert (dcn.np_raw, dcn.np) == (2, 2)
        assert ici.n_workers == 2
        assert (ici.np_raw, ici.np) == (5, 8)

    def test_ici_degree_refines_dcn_partitions(self):
        # Oversubscribed 20 GiB hosts of 3 x 16 GiB chips, 33 GiB state:
        # DCN np=2 (16.5 GiB/host fits), ICI np*=3 (11 GiB/chip fits) --
        # but 3 global shards cannot refine 2 host shards, so the
        # quantizer must pick the next divisor that contains them: 6.
        h = tpu_hierarchy(hbm_bytes=16 * GiB, vmem_bytes=96 << 20,
                          mesh_devices=3, hosts=2, ici_bytes=20 * GiB)
        hp = plan_run(h, Workload(state_bytes=33 * GiB))
        dcn, ici = hp.level("DCN"), hp.level("ICI")
        assert dcn.np == 2
        assert ici.np_raw == 3
        assert ici.np == 6

    def test_overhead_scales_the_search(self):
        fits = plan_run(_hier(), Workload(state_bytes=15 * GiB))
        tight = plan_run(_hier(), Workload(state_bytes=15 * GiB,
                                           overhead=2.0))
        assert fits.level("ICI").np_raw == 1
        assert tight.level("ICI").np_raw == 2     # 30 GiB effective footprint
        assert tight.level("ICI").detail["overhead"] == 2.0


class TestWrapperEquivalence:
    """The legacy entry points are thin wrappers over plan_run."""

    def test_mesh_decomposition_matches_ici_subplan(self):
        h = tpu_hierarchy(hbm_bytes=16 * GiB, vmem_bytes=96 << 20,
                          mesh_devices=16)
        dec = mesh_decomposition(h, sharded_bytes=65 * GiB, max_np=16)
        lp = plan_run(h, Workload(state_bytes=65 * GiB),
                      PlanPolicy(max_np={"ICI": 16})).level("ICI")
        assert dec.np == lp.np_raw == 5
        assert dec.budget_bytes == lp.budget_bytes
        assert dec.granule_bytes == lp.granule_bytes
        assert dec.fits == lp.fits

    def test_mesh_decomposition_matches_on_two_host_hierarchy(self):
        # The acceptance budget-flip property holds through the DCN walk:
        # the ICI sub-plan of the 2-host hierarchy reproduces the FSDP
        # choice of the flat mesh_decomposition over the same 16 chips.
        h2 = _hier(hosts=2, chips=8)
        flat = tpu_hierarchy(hbm_bytes=16 * GiB, vmem_bytes=96 << 20,
                             mesh_devices=16)
        for state in (1 * GiB, 65 * GiB, 300 * GiB):
            dec = mesh_decomposition(flat, sharded_bytes=state, max_np=16)
            lp = plan_run(h2, Workload(state_bytes=state)).level("ICI")
            assert dec.np == lp.np_raw, state
            assert dec.replicated == lp.replicated, state

    def test_plan_matmul_equals_planner_leaf(self):
        for shape in ((512, 512, 512), (2048, 1024, 4096), (1000, 3000, 500)):
            m, k, n = shape
            direct = plan_matmul(m, k, n, dtype_bytes=2)
            hp = plan_run(_hier(), Workload(matmul=shape, dtype_bytes=2))
            assert hp.tile_plan() == direct, shape
            assert leaf_matmul_plan(m, k, n, dtype_bytes=2) == direct, shape

    def test_decomposer_matches_direct_search(self):
        hier = paper_system_a()
        domain = matmul_domain(1024, 1024, 1024, element_size=4)
        plan = Decomposer(hier, tcl="L2").decompose(domain, n_workers=4)
        l2 = hier.find("L2")
        direct = find_optimal_np(l2.per_core_size(), l2.cache_line_size,
                                 list(domain), 4, phi_simple)
        assert plan.np == direct

    def test_decomposer_int_tcl_matches_direct_search(self):
        domain = matmul_domain(2000, 2000, 2000, element_size=4)
        plan = Decomposer(paper_system_a(), tcl=128 << 10).decompose(
            domain, n_workers=8)
        assert plan.np == 400                      # paper §4.4.4 anchor


class TestRulesConsumeThePlan:
    MESH = AbstractMesh((4, 4), ("data", "model"))

    def _hier(self, hbm_gb):
        return tpu_hierarchy(hbm_bytes=int(hbm_gb * GiB),
                             vmem_bytes=96 << 20, mesh_devices=16)

    def test_meta_records_raw_and_quantized_degree(self):
        cfg = get_model_config("llama3.2-1b")
        tight = arch_rules(cfg, self.MESH, hierarchy=self._hier(0.25))
        assert tight.meta["mesh_np"] >= 1
        assert tight.meta["fsdp_degree"] >= tight.meta["mesh_np"]
        assert tight.meta["fsdp_capacity"] % tight.meta["fsdp_degree"] == 0
        assert tight.meta["plan"].level("ICI") is not None

    def test_explicit_plan_is_consumed_not_replanned(self):
        cfg = get_model_config("llama3.2-1b")
        hp = mesh_plan(self.MESH, state_bytes=1, hierarchy=self._hier(64),
                       max_np=4)
        rules = arch_rules(cfg, self.MESH, plan=hp)
        assert rules.meta["plan"] is hp
        assert rules.param_rules["embed"] is None   # np=1 plan -> replicated

    def test_mesh_plan_threads_spec_into_tile_search(self):
        from repro.hw import chip_spec

        spec = chip_spec("tpu_v5e", mxu=256)
        hp = mesh_plan(self.MESH, matmul=(8192, 8192, 8192), spec=spec)
        t = hp.tile_plan()
        assert t.bm % 256 == 0 and t.bk % 256 == 0 and t.bn % 256 == 0

    def test_quantized_degree_on_six_chip_axis(self):
        # np*=5 on a 6-chip extent quantizes to 6, not a power of two.
        h = tpu_hierarchy(hbm_bytes=16 * GiB, vmem_bytes=96 << 20,
                          mesh_devices=6)
        lp = plan_run(h, Workload(state_bytes=80 * GiB)).level("ICI")
        assert (lp.np_raw, lp.np) == (5, 6)


class TestPipelineMapsOntoDCN:
    def test_dcn_stages(self):
        hp = plan_run(_hier(hosts=2, chips=4), Workload(state_bytes=65 * GiB))
        assert dcn_stages(hp) == 2
        flat = plan_run(tpu_hierarchy(hbm_bytes=16 * GiB,
                                      vmem_bytes=96 << 20, mesh_devices=8),
                        Workload(state_bytes=GiB))
        assert dcn_stages(flat) == 1
        assert dcn_stages(None) == 1

    def test_make_pipeline_rejects_stage_mismatch(self):
        from repro.dist.pipeline import make_pipeline

        hp = plan_run(_hier(hosts=2, chips=4), Workload(state_bytes=65 * GiB))
        mesh = AbstractMesh((4,), ("pod",))
        with pytest.raises(ValueError, match="DCN sub-plan prescribes 2"):
            make_pipeline(mesh, lambda p, x: x, axis="pod", plan=hp)


class TestOverheadField:
    def test_model_config_carries_overhead(self):
        assert get_model_config("llama3.2-1b").overhead == 1.0
        assert get_model_config("mixtral-8x7b").overhead == 1.25
        cfg = dataclasses.replace(get_model_config("llama3.2-1b"),
                                  overhead=1.5)
        assert cfg.reduced().overhead == 1.5

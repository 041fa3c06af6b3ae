"""Mesh-as-outermost-memory-level tests (repro.dist.sharding).

The acceptance property of the distribution layer: the FSDP / replicated
choice is made by the paper's machinery (``find_optimal_np`` + ``phi_mesh``
against the mesh-extended ``tpu_hierarchy``), not a hard-coded table --
shrinking the per-chip HBM budget flips ``arch_rules``/``default_rules``
from replicated to FSDP-sharded parameters.
"""

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_model_config
from repro.core.decompose import make_phi_mesh, phi_mesh
from repro.core.distribution import Array1DDistribution, ReplicatedDistribution
from repro.core.hierarchy import tpu_hierarchy
from repro.dist.sharding import (
    ShardingRules,
    active_rule,
    arch_rules,
    constrain,
    default_rules,
    mesh_decomposition,
    use_mesh_rules,
    with_batch_guard,
)

MESH = AbstractMesh((4, 4), ("data", "model"))


def _hier(hbm_gb: float):
    return tpu_hierarchy(
        hbm_bytes=int(hbm_gb * (1 << 30)),
        vmem_bytes=96 << 20,
        mesh_devices=MESH.size,
    )


class TestMeshHierarchy:
    def test_mesh_level_schema(self):
        h = _hier(16)
        assert [l.name for l in h.levels()] == ["ICI", "HBM", "VMEM", "VREG"]
        hbm = h.find("HBM")
        # Each chip owns one HBM copy: TCL_PER_CORE is the full per-chip HBM.
        assert hbm.per_core_size() == 16 << 30
        assert hbm.cores_per_copy == 1
        assert hbm.n_cores == MESH.size
        # The sharding granule plays the cache-line role at this level.
        assert hbm.cache_line_size == 8 * 128 * 4
        # Round-trips through the paper's JSON schema like any other level.
        assert h.to_dict()["child"]["cacheLineSize"] == 8 * 128 * 4

    def test_chip_hierarchy_unchanged_without_mesh(self):
        h = tpu_hierarchy(hbm_bytes=16 << 30, vmem_bytes=128 << 20)
        assert [l.name for l in h.levels()] == ["HBM", "VMEM", "VREG"]


class TestPhiMesh:
    def test_pads_to_granule(self):
        dist = Array1DDistribution(length=1000, element_size=1)
        # 1000/8 = 125 bytes -> padded up to one 4096-byte granule.
        assert phi_mesh(4096, dist, 8) == 4096

    def test_monotone_in_np(self):
        dist = Array1DDistribution(length=1 << 30, element_size=1)
        vals = [phi_mesh(4096, dist, np_) for np_ in (1, 2, 4, 8, 16)]
        assert vals == sorted(vals, reverse=True)

    def test_replicated_term_ignores_np(self):
        rep = ReplicatedDistribution(nbytes=12345)
        assert phi_mesh(1, rep, 1) == phi_mesh(1, rep, 64) == 12345

    def test_overhead_factor(self):
        dist = Array1DDistribution(length=1 << 20, element_size=1)
        assert make_phi_mesh(overhead=2.0)(1, dist, 4) == \
            2 * phi_mesh(1, dist, 4)


class TestMeshDecomposition:
    def test_fit_gives_single_partition(self):
        dec = mesh_decomposition(_hier(16), sharded_bytes=1 << 30)
        assert dec.np == 1 and dec.replicated and dec.fits

    def test_overflow_relaxes_np(self):
        # 65 GiB of state against 16 GiB chips: Algorithm 1 must relax np to
        # the smallest partition count whose shard fits (5), like the paper's
        # binary search -- not jump to the mesh capacity.
        dec = mesh_decomposition(_hier(16), sharded_bytes=65 << 30, max_np=16)
        assert dec.np == 5 and not dec.replicated and dec.fits

    def test_replicated_term_shrinks_budget(self):
        with_act = mesh_decomposition(
            _hier(16), sharded_bytes=64 << 30,
            replicated_bytes=8 << 30, max_np=16)
        without = mesh_decomposition(_hier(16), sharded_bytes=64 << 30,
                                     max_np=16)
        assert with_act.np > without.np

    def test_non_power_of_two_max_np_is_probed(self):
        # Regression: a 6-chip data axis must probe np=5 and np=6, not stop
        # after the 1,2,4 doubling sequence and falsely report overflow.
        dec = mesh_decomposition(_hier(16), sharded_bytes=80 << 30, max_np=6)
        assert dec.np == 5 and dec.fits

    def test_saturates_when_nothing_fits(self):
        dec = mesh_decomposition(_hier(0.001), sharded_bytes=64 << 30,
                                 max_np=16)
        assert dec.np == 16 and not dec.fits


class TestDecomposerDrivenRules:
    """Acceptance: shrinking the mesh-level HBM budget flips the param rules
    replicated -> FSDP via find_optimal_np + phi_mesh."""

    def test_arch_rules_flip_on_hbm_budget(self):
        cfg = get_model_config("llama3.2-1b")  # ~1.5e9 params, ~20 GB state
        roomy = arch_rules(cfg, MESH, hierarchy=_hier(64))
        tight = arch_rules(cfg, MESH, hierarchy=_hier(0.25))
        assert roomy.param_rules["embed"] is None          # fits: replicated
        assert tight.param_rules["embed"] == "data"        # overflow: FSDP
        assert roomy.meta["mesh_np"] == 1
        assert tight.meta["mesh_np"] > 1
        # TP choices are structural, not budget-driven.
        assert roomy.param_rules["heads"] == tight.param_rules["heads"] == "model"

    def test_default_rules_flip_on_hbm_budget(self):
        roomy = default_rules(MESH, state_bytes=1 << 30, hierarchy=_hier(64))
        tight = default_rules(MESH, state_bytes=1 << 40, hierarchy=_hier(1))
        assert roomy.param_rules["embed"] is None
        assert tight.param_rules["embed"] == "data"
        assert not roomy.meta["fsdp"] and tight.meta["fsdp"]

    def test_activation_reserve_can_force_fsdp(self):
        cfg = get_model_config("llama3.2-1b")
        h = _hier(6)  # 6 GiB chips: the ~4 GiB TP-resident state barely fits
        alone = arch_rules(cfg, MESH, hierarchy=h)
        crowded = arch_rules(cfg, MESH, hierarchy=h, act_bytes=3 << 30)
        assert alone.param_rules["embed"] is None
        assert crowded.param_rules["embed"] == "data"

    def test_structural_divisibility_guards(self):
        import dataclasses
        cfg = get_model_config("llama3.2-1b")
        cfg = dataclasses.replace(cfg, n_kv_heads=2)  # 2 % 4 != 0
        rules = arch_rules(cfg, MESH)
        assert rules.act_rules["kv_heads"] is None
        assert rules.act_rules["heads"] == "model"


class TestRulesMechanics:
    def test_act_spec_and_dedupe(self):
        rules = ShardingRules(
            {"embed": "data"},
            {"batch": ("data",), "heads": "model", "dup": "data"},
        )
        assert rules.act_spec(("batch", None, "heads")) == \
            P("data", None, "model")
        # A mesh axis is used at most once per spec (first logical axis wins).
        assert rules.act_spec(("batch", "dup")) == P("data", None)

    def test_with_batch_guard_trims_indivisible(self):
        rules = default_rules(MESH, hierarchy=_hier(64))
        ok = with_batch_guard(rules, MESH, 8)       # 8 % 4 == 0
        bad = with_batch_guard(rules, MESH, 6)      # 6 % 4 != 0
        assert ok.act_rules["batch"] == "data"
        assert bad.act_rules["batch"] is None

    def test_constrain_is_identity_outside_context(self):
        import jax.numpy as jnp
        x = jnp.ones((4, 4))
        assert constrain(x, ("batch", "embed")) is x
        assert active_rule("kv_seq") is None

    def test_active_rule_inside_context(self):
        rules = default_rules(MESH, hierarchy=_hier(64), seq_sharded=True)
        with use_mesh_rules(MESH, rules):
            assert active_rule("kv_seq") == "model"
            assert active_rule("experts") is None
        assert active_rule("kv_seq") is None


class TestXLSTMStateSharding:
    """Regression: the production 16x16 mesh rejected xLSTM's decode cache
    (pjit: ``cache['mlstm']['C']`` dim 2 is H=4 state heads, not divisible
    by the 16-wide model axis) and the 32k calibration cells failed.  The
    rules must fall back to SUB-AXIS sharding: heads unsharded, the
    per-head state inner dim (mLSTM dh=1024, sLSTM d/H=512) carries TP."""

    PROD = AbstractMesh((16, 16), ("data", "model"))

    def test_state_inner_carries_tp_when_heads_cannot(self):
        cfg = get_model_config("xlstm-1.3b")
        rules = arch_rules(cfg, self.PROD, state_bytes_per_param=2)
        assert rules.act_rules["state_heads"] is None
        assert rules.act_rules["state_inner"] == "model"

    def test_every_cache_dim_divides_its_mesh_axis(self):
        import jax.numpy as jnp
        import jax.tree_util as tu

        from repro.launch.specs import cache_logical_axes
        from repro.models.model import build_model

        cfg = get_model_config("xlstm-1.3b")
        rules = arch_rules(cfg, self.PROD, state_bytes_per_param=2)
        model = build_model(cfg, remat="none")
        cache = jax.eval_shape(lambda: model.init_cache(32, 64, jnp.float32))
        axes = cache_logical_axes(cfg, cache, long_context=False)
        sizes = dict(self.PROD.shape)
        is_axes = lambda n: isinstance(n, tuple)
        leaves = tu.tree_leaves_with_path(cache)
        specs = tu.tree_leaves(axes, is_leaf=is_axes)
        assert len(leaves) == len(specs)
        for (path, leaf), ax in zip(leaves, specs):
            spec = rules.act_spec(ax)
            for dim, entry in zip(leaf.shape, tuple(spec)):
                for mesh_ax in ((entry,) if isinstance(entry, str)
                                else (entry or ())):
                    assert dim % sizes[mesh_ax] == 0, \
                        (tu.keystr(path), leaf.shape, spec)

    def test_small_model_axis_still_shards_heads(self):
        cfg = get_model_config("xlstm-1.3b")         # 4 state heads
        mesh = AbstractMesh((4, 4), ("data", "model"))
        rules = arch_rules(cfg, mesh, state_bytes_per_param=2)
        assert rules.act_rules["state_heads"] == "model"
        assert rules.act_rules["state_inner"] is None

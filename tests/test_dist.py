"""Distributed-runtime tests that need >1 device: run in subprocesses with
``--xla_force_host_platform_device_count`` (the main test process must keep
the single-device view)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(n: int, body: str) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


def test_ag_matmul_ring_matches_reference():
    run_with_devices(4, """
        from repro.dist.overlap import make_ag_matmul
        mesh = make_mesh((4,), ("model",))
        fn = make_ag_matmul(mesh, axis="model")
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (64, 32), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 48), jnp.float32)
        y = fn(x, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)
        print("ag_matmul ok")
    """)


def test_rs_matmul_ring_matches_reference():
    run_with_devices(4, """
        from repro.dist.overlap import make_rs_matmul
        mesh = make_mesh((4,), ("model",))
        fn = make_rs_matmul(mesh, axis="model")
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (64, 32), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 48), jnp.float32)
        y = fn(x, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-4, atol=1e-4)
        print("rs_matmul ok")
    """)


def test_pipeline_gpipe_matches_sequential():
    run_with_devices(4, """
        from repro.dist.pipeline import make_pipeline
        mesh = make_mesh((4,), ("pod",))

        def stage_fn(params, x):
            return jnp.tanh(x @ params["w"])

        key = jax.random.PRNGKey(0)
        stages = {"w": jax.random.normal(key, (4, 16, 16)) * 0.5}
        mbs = jax.random.normal(jax.random.fold_in(key, 1), (6, 8, 16))
        fn = make_pipeline(mesh, stage_fn, axis="pod")
        out = fn(stages, mbs)

        ref = mbs
        for sidx in range(4):
            ref = jnp.tanh(ref @ stages["w"][sidx])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("pipeline ok")
    """)


def test_train_step_loss_decreases_on_mesh():
    """End-to-end SPMD training sanity: tiny model, 2x2 mesh, loss drops."""
    run_with_devices(4, """
        from repro.configs import get_model_config, get_shape, TrainConfig
        from repro.configs.base import ShapeConfig
        from repro.launch.trainer import make_train_step, init_sharded_state
        from repro.data import SyntheticLMDataset

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        shape = ShapeConfig("tiny", seq_len=32, global_batch=8, kind="train")
        train = TrainConfig(learning_rate=3e-3, warmup_steps=5,
                            total_steps=60, remat="none")
        ts = make_train_step(cfg, shape, mesh, train)
        params, opt = init_sharded_state(ts, mesh, 0, train)

        ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        losses = []
        for step in range(40):
            batch = ds.batch(step % 4, 8)
            batch = {k: jax.device_put(v, ts.batch_sharding[k])
                     for k, v in batch.items()}
            params, opt, metrics = ts.fn(params, opt, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.5, losses[::8]
        print("first/last:", losses[0], losses[-1])
    """)


def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (4,1) mesh, restore on (2,2): topology-independent ckpt."""
    import tempfile
    tmp = tempfile.mkdtemp()
    run_with_devices(4, f"""
        from repro.configs import get_model_config, TrainConfig
        from repro.configs.base import ShapeConfig
        from repro.launch.trainer import make_train_step, init_sharded_state
        from repro.ckpt import CheckpointManager

        cfg = get_model_config("qwen2-0.5b").reduced()
        shape = ShapeConfig("tiny", seq_len=16, global_batch=4, kind="train")
        train = TrainConfig(remat="none")

        mesh1 = make_mesh((4, 1), ("data", "model"))
        ts1 = make_train_step(cfg, shape, mesh1, train)
        params, opt = init_sharded_state(ts1, mesh1, 0, train)
        mgr = CheckpointManager({tmp!r}, keep=2)
        mgr.save(7, (params, opt), blocking=True)

        # "Relaunch" on a different topology.
        mesh2 = make_mesh((2, 2), ("data", "model"))
        ts2 = make_train_step(cfg, shape, mesh2, train)
        p2, o2 = init_sharded_state(ts2, mesh2, 1, train)

        from repro.launch.trainer import _flatten_with_paths
        flat_s = _flatten_with_paths((ts2.param_sharding, ts2.opt_sharding))
        def reshard(key, arr):
            s = flat_s.get(key)
            return jax.device_put(arr, s) if s is not None else jnp.asarray(arr)
        template = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), (p2, o2))
        restored, manifest = mgr.restore_latest(template, reshard=reshard)
        assert manifest["step"] == 7
        rp, ro = restored
        a = np.asarray(jax.tree.leaves(params)[0])
        b = np.asarray(jax.tree.leaves(rp)[0])
        np.testing.assert_array_equal(a, b)
        print("elastic restore ok")
    """)


def test_collectives_equivalence_gspmd_ring_serpentine():
    """Acceptance: gspmd / ring / serpentine agree to fp32 tolerance on a
    4-device host mesh, for both the all-gather and reduce-scatter rings."""
    run_with_devices(4, """
        from repro.dist.overlap import make_ag_matmul, make_rs_matmul
        mesh = make_mesh((4,), ("model",))
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (64, 32), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 48), jnp.float32)
        ref = np.asarray(x @ w)                     # the gspmd path
        for make, name in ((make_ag_matmul, "ag"), (make_rs_matmul, "rs")):
            for mode in ("ring", "serpentine"):
                y = np.asarray(make(mesh, axis="model", mode=mode)(x, w))
                np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5,
                                           err_msg=f"{name}/{mode}")
        print("collectives equivalence ok")
    """)


def test_serpentine_odd_size_error_messages():
    """Serpentine needs an even per-chip k chunk (ag) / even n (rs); the
    error must name the mode and the fix."""
    run_with_devices(4, """
        from repro.dist.overlap import make_ag_matmul, make_rs_matmul
        mesh = make_mesh((4,), ("model",))
        ag = make_ag_matmul(mesh, axis="model", mode="serpentine")
        try:
            ag(jnp.zeros((8, 12)), jnp.zeros((12, 8)))   # kb = 3, odd
            raise SystemExit("expected ValueError for odd k chunk")
        except ValueError as e:
            assert "serpentine" in str(e) and "even" in str(e), e
            assert "mode='ring'" in str(e), e
        rs = make_rs_matmul(mesh, axis="model", mode="serpentine")
        try:
            rs(jnp.zeros((8, 8)), jnp.zeros((8, 5)))     # n = 5, odd
            raise SystemExit("expected ValueError for odd n")
        except ValueError as e:
            assert "serpentine" in str(e) and "even" in str(e), e
        print("odd-size error messages ok")
    """)


def test_model_forward_equivalence_under_overlap_collectives():
    """The layers-level dispatch: a full model forward under
    with_collectives(ring|serpentine) matches the gspmd forward."""
    run_with_devices(4, """
        from repro.configs import get_model_config
        from repro.configs.base import ShapeConfig
        from repro.dist.sharding import (arch_rules, use_mesh_rules,
                                         with_collectives)
        from repro.launch.specs import make_batch
        from repro.models.model import build_model

        mesh = make_mesh((1, 4), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        model = build_model(cfg, remat="none")
        params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        shape = ShapeConfig("tiny", seq_len=16, global_batch=4, kind="train")
        batch = make_batch(cfg, shape, np.random.default_rng(0),
                           dtype=jnp.float32)
        rules = arch_rules(cfg, mesh)
        outs = {}
        for mode in ("gspmd", "ring", "serpentine"):
            r = with_collectives(rules, mode) if mode != "gspmd" else rules
            def fwd(p, b, r=r):
                with use_mesh_rules(mesh, r):
                    return model.forward(p, b, dtype=jnp.float32)[0]
            outs[mode] = np.asarray(jax.jit(fwd)(params, batch))
        np.testing.assert_allclose(outs["ring"], outs["gspmd"],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(outs["serpentine"], outs["gspmd"],
                                   rtol=2e-4, atol=2e-4)
        print("model forward equivalence ok")
    """)


def test_train_step_with_serpentine_collectives():
    """Trainer wiring: TrainConfig(collectives="serpentine") trains (grads
    flow through both ppermute directions) and the loss decreases."""
    run_with_devices(4, """
        from repro.configs import get_model_config, TrainConfig
        from repro.configs.base import ShapeConfig
        from repro.data import SyntheticLMDataset
        from repro.launch.trainer import make_train_step, init_sharded_state

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        shape = ShapeConfig("tiny", seq_len=32, global_batch=8, kind="train")
        train = TrainConfig(learning_rate=3e-3, warmup_steps=5,
                            total_steps=60, remat="none",
                            collectives="serpentine")
        ts = make_train_step(cfg, shape, mesh, train)
        assert ts is not None
        params, opt = init_sharded_state(ts, mesh, 0, train)
        ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        losses = []
        for step in range(20):
            batch = ds.batch(step % 4, 8)
            batch = {k: jax.device_put(v, ts.batch_sharding[k])
                     for k, v in batch.items()}
            params, opt, metrics = ts.fn(params, opt, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses
        print("serpentine train:", losses[0], "->", losses[-1])
    """)

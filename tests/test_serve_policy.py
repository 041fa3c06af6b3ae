"""Serve cache-policy tests: the auto policy must pick head sharding when
kv_heads divides the model axis, sequence sharding otherwise, and never
produce duplicate-axis specs (subprocess with a multi-device mesh)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=420,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


def test_auto_policy_head_shards_when_divisible():
    _run("""
        from repro.configs import get_model_config
        from repro.configs.base import ShapeConfig
        from repro.launch.trainer import make_serve_steps

        import dataclasses
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        cfg = dataclasses.replace(cfg, n_kv_heads=4, n_heads=4)  # 4 % 4 == 0
        shape = ShapeConfig("d", 64, 4, "decode")
        ss = make_serve_steps(cfg, shape, mesh, dtype=jnp.float32,
                              cache_policy="auto")
        spec = ss.cache_sharding["layers"]["k"].spec
        # (L, B, S, KV, hd): head dim sharded, seq dim not.
        assert spec[2] is None and spec[3] == "model", spec
        print("head-shard ok", spec)
    """)


def test_auto_policy_seq_shards_when_heads_dont_divide():
    _run("""
        import dataclasses
        from repro.configs import get_model_config
        from repro.configs.base import ShapeConfig
        from repro.launch.trainer import make_serve_steps

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        cfg = dataclasses.replace(cfg, n_kv_heads=2, n_heads=4)  # 2 % 4 != 0
        shape = ShapeConfig("d", 64, 4, "decode")
        ss = make_serve_steps(cfg, shape, mesh, dtype=jnp.float32,
                              cache_policy="auto")
        spec = ss.cache_sharding["layers"]["k"].spec
        assert spec[2] == "model" and spec[3] is None, spec
        print("seq-shard ok", spec)
    """)


def test_auto_policy_decode_step_runs_and_matches_baseline():
    """Auto vs baseline placement must produce identical logits."""
    _run("""
        import dataclasses
        import numpy as np
        from repro.configs import get_model_config
        from repro.configs.base import ShapeConfig
        from repro.launch.trainer import make_serve_steps
        from repro.launch.specs import make_batch

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        cfg = dataclasses.replace(cfg, n_kv_heads=2, n_heads=4)
        shape = ShapeConfig("d", 64, 4, "decode")

        outs = {}
        for policy in ("baseline", "auto"):
            rng = np.random.default_rng(0)   # identical prompt per policy
            ss = make_serve_steps(cfg, shape, mesh, dtype=jnp.float32,
                                  cache_policy=policy, max_len_extra=4)
            params = ss.model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
            prompt = make_batch(cfg, shape, rng, kind="train")
            prompt.pop("labels", None)
            logits, cache = ss.prefill(params, prompt)
            step = {"tokens": jnp.ones((4, 1), jnp.int32)}
            logits, cache = ss.decode(params, cache, step)
            outs[policy] = np.asarray(logits)
        np.testing.assert_allclose(outs["auto"], outs["baseline"],
                                   rtol=5e-4, atol=5e-4)
        print("auto == baseline logits")
    """)

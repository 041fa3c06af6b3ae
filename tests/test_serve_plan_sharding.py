"""Acceptance: the lowered serving program realizes exactly the decode
plan's choices -- the KV head sharding of the cache layout and the
page-aligned capacity -- for both branches of the mesh-level decision
(subprocess with an 8-device host platform, like test_serve_policy)."""

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


def test_plan_kv_shard_realized_in_cache_sharding():
    """Memory pressure at the mesh level (tiny forced HBM) makes the decode
    plan shard KV heads over the full model axis; the lowered cache layout
    must match, and a decode step must run."""
    _run("""
        import dataclasses
        import numpy as np
        from repro.configs import get_model_config
        from repro.configs.base import ShapeConfig
        from repro.hw.tpu import chip_spec
        from repro.launch.specs import make_batch
        from repro.serve import make_serve_steps, plan_decode

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        cfg = dataclasses.replace(cfg, n_kv_heads=4, n_heads=4)
        shape = ShapeConfig("d", 64, 4, "decode")

        # Tiny HBM: the mesh search must demand np > 1 -> kv_shard = axis.
        # (The reduced model's KV is ~150 KiB per data shard at np=1 plus a
        # ~100 KiB replicated reserve; 160 KiB only fits at np=4.)
        small = chip_spec(hbm_bytes=160 << 10)
        hp = plan_decode(cfg, mesh, max_len=72, batch=4, dtype_bytes=4,
                         spec=small)
        ici = hp.level("ICI")
        assert ici.np_raw > 1, ici
        assert hp.kv_shard() == 4, hp.kv_shard()

        ss = make_serve_steps(cfg, shape, mesh, dtype=jnp.float32,
                              max_len_extra=8, decode_plan=hp)
        spec = ss.cache_sharding["layers"]["k"].spec
        # (L, B, S, KV, hd): the plan's head sharding, no seq fallback.
        assert spec[3] == "model" and spec[2] is None, spec

        # And it runs: prefill + one decode step under the plan layout.
        rng = np.random.default_rng(0)
        params = ss.model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        prompt = make_batch(cfg, shape, rng, kind="train")
        prompt.pop("labels", None)
        logits, cache = ss.prefill(params, prompt)
        logits, cache = ss.decode(
            params, cache, {"tokens": jnp.ones((4, 1), jnp.int32)})
        assert np.isfinite(np.asarray(logits)).all()
        print("sharded ok", spec)
    """)


def test_plan_replicated_kv_when_memory_fits():
    """With room to spare the decode plan keeps np = 1: the cache stays
    unsharded over heads AND the legacy auto seq fallback is disabled
    (the plan does not model it)."""
    _run("""
        import dataclasses
        from repro.configs import get_model_config
        from repro.configs.base import ShapeConfig
        from repro.serve import make_serve_steps, plan_decode

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_model_config("llama3.2-1b").reduced()
        cfg = dataclasses.replace(cfg, n_kv_heads=2, n_heads=4)
        shape = ShapeConfig("d", 64, 4, "decode")
        hp = plan_decode(cfg, mesh, max_len=72, batch=4, dtype_bytes=4)
        assert hp.kv_shard() == 1, hp.kv_shard()
        ss = make_serve_steps(cfg, shape, mesh, dtype=jnp.float32,
                              max_len_extra=8, decode_plan=hp)
        spec = ss.cache_sharding["layers"]["k"].spec
        assert spec[2] is None and spec[3] is None, spec
        print("replicated ok", spec)
    """)


def test_plan_page_matches_engine_capacity():
    """The page level of the decode tree IS the engine's allocation granule
    (single process, host devices)."""
    _run("""
        import numpy as np
        from repro.configs import get_model_config
        from repro.hw.tpu import chip_spec
        from repro.launch.mesh import make_host_mesh
        from repro.serve import ServeEngine, ServePolicy

        cfg = get_model_config("llama3.2-1b").reduced()
        small = chip_spec(vmem_bytes=800 << 10, vmem_reserved_bytes=0)
        engine = ServeEngine(cfg, make_host_mesh(),
                             policy=ServePolicy(max_new_tokens=12,
                                                max_len=64),
                             spec=small)
        page = engine.plan.page_plan()
        assert page is not None and engine.page.page_tokens == \
            page["page_tokens"]
        outs = engine.generate(
            [np.random.default_rng(0).integers(0, 256, 9, dtype=np.int32)])
        assert len(outs[0]) == 12
        caps = engine.metrics["capacities"]
        assert caps and all(c % page["page_tokens"] == 0 for c in caps)
        print("page ok", page["page_tokens"], caps)
    """)

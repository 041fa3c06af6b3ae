"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers a kernel for one chip of a described
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode accepts -- a block the tiling cannot hold, a kernel
over its VMEM limit.  The paged-attention cases compile under the VMEM
footprint the planner estimated for the page (``page_vmem_bytes``), so
they also check that the estimate bounds what the compiler allocates.

The topology is described inside a fixture (never at import time): only
one process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, SingleDeviceSharding

from repro.configs import get_model_config
from repro.core.plan import leaf_matmul_plan
from repro.hw.tpu import chip_spec
from repro.kernels.matmul_cc import matmul_cc
from repro.kernels.paged_attention import (
    paged_attention,
    paged_prefill_attention,
)
from repro.serve.engine import plan_decode
from repro.serve.pages import pool_head_dim

SLOTS = 8
MAX_LEN = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 -- any backend refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch,dtype", [
    ("stablelm-1.6b", jnp.float32),      # MHA, the engine's default dtype
    ("stablelm-1.6b", jnp.bfloat16),
    ("llama3.2-1b", jnp.bfloat16),       # GQA: 8 KV heads, groups of 4
])
def test_paged_attention_compiles_at_planned_page(one_chip, arch, dtype):
    cfg = get_model_config(arch)
    plan = plan_decode(cfg, AbstractMesh((1, 1), ("data", "model")),
                       max_len=MAX_LEN, batch=SLOTS,
                       dtype_bytes=jnp.dtype(dtype).itemsize,
                       spec=chip_spec("tpu_v5e"))
    page = plan.page_plan()
    pt = page["page_tokens"]
    assert page["vmem_bytes"] <= page["vmem_limit_bytes"]
    n_pages = -(-MAX_LEN // pt)
    pool = (1 + SLOTS * n_pages, pt, cfg.n_kv_heads, pool_head_dim(cfg.head_dim))

    def step(q, k, v, table, lengths):
        return paged_attention(q, k, v, table, lengths, page_tokens=pt,
                               interpret=False,
                               vmem_limit_bytes=page["vmem_bytes"])

    text = _compile_text(step, one_chip,
                         ((SLOTS, cfg.n_heads, cfg.head_dim), dtype),
                         (pool, dtype), (pool, dtype),
                         ((SLOTS, n_pages), jnp.int32), ((SLOTS,), jnp.int32))
    # The kernel is the Mosaic custom call, and keeps its name beside it.
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all("%paged_attention" in ln for ln in kernels)


@pytest.mark.parametrize("arch,max_len,slots", [
    ("stablelm-1.6b", 4096, 16),         # MHA, head dim 64 padded to 128
    ("deepseek-coder-33b", 16384, 10),   # GQA 56/8 at 128, a 1176-token page
])
def test_paged_prefill_compiles_at_planned_tile(one_chip, arch, max_len,
                                                slots):
    """The chunk kernel compiles for a full chunk at the planner's
    ``prefill_block_q``, within the VMEM the planner counted for it
    (``prefill_page_vmem_bytes``)."""
    cfg = get_model_config(arch)
    plan = plan_decode(cfg, AbstractMesh((1, 1), ("data", "model")),
                       max_len=max_len, batch=slots, dtype_bytes=2,
                       spec=chip_spec("tpu_v5e"))
    page = plan.page_plan()
    pt, bq = page["page_tokens"], page["prefill_block_q"]
    assert bq % 8 == 0 and 8 <= bq <= pt
    assert page["prefill_vmem_bytes"] <= page["vmem_limit_bytes"]
    n_pages = -(-max_len // pt)
    pool = (1 + n_pages, pt, cfg.n_kv_heads, pool_head_dim(cfg.head_dim))

    def step(q, k, v, table_row, pos0):
        return paged_prefill_attention(
            q, k, v, table_row, pos0, page_tokens=pt, block_q=bq,
            interpret=False, vmem_limit_bytes=page["prefill_vmem_bytes"])

    text = _compile_text(step, one_chip,
                         ((pt, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
                         (pool, jnp.bfloat16), (pool, jnp.bfloat16),
                         ((n_pages,), jnp.int32), ((), jnp.int32))
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all("%paged_prefill_attention" in ln
                           for ln in kernels)


def test_matmul_cc_compiles_at_planned_tile(one_chip):
    cfg = get_model_config("stablelm-1.6b")
    m, k, n = 2048, cfg.d_model, cfg.d_ff        # one prefill chunk's FFN up
    plan = leaf_matmul_plan(m, k, n, dtype_bytes=2)

    def step(a, b):
        return matmul_cc(a, b, plan=plan, interpret=False)

    text = _compile_text(step, one_chip, ((m, k), jnp.bfloat16),
                         ((k, n), jnp.bfloat16))
    assert "tpu_custom_call" in text

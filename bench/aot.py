"""Compile a cell's decode step and full prefill chunk for a described
TPU v5e, without a chip, and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload deepseek-coder-stage-repo

The engine's programs are built as a run builds them (the plan, the pool
geometry and the slot count of the cell), lowered for one chip of a
described ``v5e:2x2`` topology with shapes only, and compiled by the TPU
compiler; ``memory_analysis()`` then gives each program's HBM use.  Off
a TPU the program would take its interpret-mode branch, so
``jax.default_backend`` reads "tpu" while the programs are traced.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent

#: ``bytes_limit`` of one v5e chip as its runtime reports it.
V5E_BYTES_LIMIT = 16_909_336_064


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from bench import spec, weights
    from repro.hw.tpu import chip_spec
    from repro.serve import ServeEngine, ServePolicy
    from repro.serve.pages import init_paged_cache, pool_page_bytes
    from repro.serve.steps import make_paged_steps

    cell = spec.load_cell(args.workload)
    fam, m = cell.family, cell.model
    cfg = fam.model_config(m, cell.config["repro_arch"])
    slots = int(cell.cell["max_slots"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    chip = chip_spec("tpu_v5e", hbm_bytes=V5E_BYTES_LIMIT)
    dtype = getattr(jnp, m.dtype)
    eng = ServeEngine(cfg, mesh, policy=ServePolicy(
        max_slots=slots, max_len=m.max_len, batching="paged"),
        dtype=dtype, params={}, spec=chip)
    page = eng.page.page_tokens
    pps = int(eng.plan.page_table()["pages_per_slot"])
    budget_pages = eng.scheduler.budget_bytes // pool_page_bytes(cfg, eng.page)
    pages_total = 1 + min(budget_pages, slots * pps)
    chunk = eng.plan.chunk_tokens() or page
    print(f"{args.workload}: page={page} chunk={chunk} slots={slots} "
          f"pages_per_slot={pps} pool_pages={pages_total - 1} "
          f"pool_page_bytes={pool_page_bytes(cfg, eng.page)}")
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, eng.model, slots, pages_total, page, pps, dtype))
    params = jax.eval_shape(lambda: weights.make_weights(fam, m, 0, dtype))
    i32 = jnp.int32
    chip_spec()         # the attached-chip lookup is cached: off a TPU, v5e
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        steps = make_paged_steps(cfg, mesh, cache, n_slots=slots,
                                 max_len=m.max_len, dtype=dtype,
                                 decode_plan=eng.plan)
        progs = {
            "decode": steps.decode.lower(
                params, cache,
                {"tokens": jax.ShapeDtypeStruct((slots, 1), i32)}),
            "prefill_chunk": steps.prefill_chunk.lower(
                params, cache, jax.ShapeDtypeStruct((1, chunk), i32),
                jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32)),
        }
        for name, lowered in progs.items():
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            kernel = "tpu_custom_call" in compiled.as_text()
            print(f"{name}: arguments={ma.argument_size_in_bytes} "
                  f"outputs={ma.output_size_in_bytes} "
                  f"aliased={ma.alias_size_in_bytes} "
                  f"temporaries={ma.temp_size_in_bytes} "
                  f"generated_code={ma.generated_code_size_in_bytes} "
                  f"tpu_custom_call={kernel}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

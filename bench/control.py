"""Readings that set a cell's correctness limit: the program's widest
logit gap and the fp8 control's, over many seeds in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--calls 1]
        [--fault state_unchanged --fault-seeds 4]

For each seed the weights are made anew and swapped into one engine (the
programs compile once), ``--calls`` calls of the cell's own size run
(the cell's ``2 * max_slots`` requests, every request run to its end),
the sample a run would compare is drawn, and two numbers are read over
it: the widest gap by which a served token's float32
reference logit lies below the reference's best (the program), and the
same for the token the fp8 control puts first at each position (the
control).  A limit lies between the largest program reading and the
smallest control reading.  With ``--fault`` the named fault of
``bench/faults.py`` is then planted, the engine built anew, and each of
``--fault-seeds`` read the same way: its ``program`` is the broken
program's reading, which has to lie above the limit.  Each seed prints
one JSON line; the last line sums them up.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--calls", type=int, default=1)
    ap.add_argument("--fault", choices=faults.NAMES)
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from bench import harness, reference, spec, traffic, weights
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("JAX found no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    fam, m = cell.family, cell.model
    cfg = fam.model_config(m, cell.config["repro_arch"])
    c = cell.cell
    slots = int(c["max_slots"])
    device = jax.devices()[0]
    dtype = getattr(jnp, m.dtype)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]

    def new_engine(seed):
        return harness.build_engine(
            cfg, m, slots, weights.make_weights(fam, m, seed, dtype, device),
            device)

    def readings(engine, seeds, fault=None):
        rows = []
        for seed in seeds:
            t0 = time.perf_counter()
            engine.params = None
            gc.collect()
            engine.params = weights.make_weights(fam, m, seed, dtype, device)
            stream = traffic.RequestStream(pairs, m.vocab, seed)
            served = []
            for call in range(args.calls):
                served += harness._call(engine, stream.next_batch(), call,
                                        time.perf_counter)
            sample = harness.pick_sample(served, slots,
                                         int(c["sample_tokens"]),
                                         int(c["sample_requests"]), seed)
            with jax.default_matmul_precision("highest"):
                prog, ctl = reference.widest_gap(
                    fam, engine.params, m,
                    [(r.prompt, r.tokens) for r in sample], control=True)
            row = {"seed": seed, "program": prog, "control": ctl,
                   "requests": len(sample),
                   "served_tokens": sum(len(r.tokens) for r in sample),
                   "seconds": time.perf_counter() - t0}
            if fault:
                row["fault"] = fault
            rows.append(row)
            print(json.dumps(row), flush=True)
        return rows

    engine = new_engine((seeds or fault_seeds)[0])
    chunk = engine.plan.chunk_tokens() or engine.page.page_tokens
    pairs = traffic.length_pairs(cell.mix, 2 * slots, chunk, m.max_len)
    rows = []
    if seeds:
        harness.warm_up(engine, pairs, chunk, slots, m.vocab)
        rows = readings(engine, seeds)
    fault_rows = []
    if args.fault:
        del engine
        gc.collect()
        faults.plant(args.fault, fam, setattr)
        engine = new_engine(fault_seeds[0])
        harness.warm_up(engine, pairs, chunk, slots, m.vocab)
        fault_rows = readings(engine, fault_seeds, args.fault)
    summary = {"workload": args.workload, "seeds": len(rows)}
    if rows:
        summary["program_max"] = max(r["program"] for r in rows)
        summary["control_min"] = min(r["control"] for r in rows)
    if fault_rows:
        summary[f"{args.fault}_min"] = min(r["program"]
                                           for r in fault_rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once, on the chip it finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --dry [--workload <name>]

A run makes its weights and requests from ``--seed``, warms every
program the cell's traffic uses (set-up), serves for ``--seconds``
seconds, checks a sample of what it served against the float32
reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each compared number beside its limit.  The same numbers end standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits nonzero and prints no result.

``--dry`` rehearses every cell (or the one named) on the CPU: the same
path at a reduced width and shortened lengths, with the Pallas kernel in
interpret mode.  It prints no metrics.

JAX's persistent compilation cache is kept in the checkout
(``repro.launch.compile_cache``), so only a cell's first run compiles.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _fail(msg: str, code: int = 2) -> int:
    _log(f"FAIL: {msg}")
    return code


def _check_lines(checks: dict) -> None:
    for name, c in checks.items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)


def dry(workload: str, seconds: float) -> int:
    """The CPU rehearsal of one cell or of every cell."""
    from bench import harness, spec

    names = ([workload] if workload else
             [w["name"] for w in spec.load_benchmark()["workloads"]])
    ok = True
    for name in names:
        cell = spec.load_cell(name)
        t0 = time.perf_counter()
        res = harness.run(cell, 1234, seconds, False, t_process=t0,
                          dry=True, log=_log)
        _log(f"dry {name}: correct={res.correct} attempted={res.attempted} "
             f"failed={res.failed} calls={res.log['calls']} "
             f"compiles_in_window={res.log['compiles_in_window']} "
             f"({time.perf_counter() - t0:.1f}s)")
        _check_lines(res.checks)
        ok &= res.correct
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true",
                    help="rehearse on the CPU at a reduced width")
    args = ap.parse_args(argv)
    if args.dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # The TPU runtime logs to /tmp/tpu_logs unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        from repro.launch.compile_cache import enable_compile_cache

        from bench import harness, spec
    except ImportError as e:
        return _fail(f"not a checkout of the repository ({e})")
    if args.dry:
        return dry(args.workload, args.seconds or 3.0)
    if not args.workload or args.seconds <= 0:
        return _fail("--workload and --seconds are required")
    cell = spec.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        return _fail(f"the cell needs {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    _log(f"compile cache: {enable_compile_cache()}")
    res = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, log=_log)
    _log(f"log {json.dumps(res.log, default=str)}")
    out = {"correct": res.correct, "attempted": res.attempted,
           "failed": res.failed, "metrics": res.metrics,
           "device": res.device}
    if res.breakdown is not None:
        out["breakdown"] = res.breakdown
    out["checks"] = {k: {n: (x if x == x and abs(x) != float("inf")
                             else None) for n, x in c.items()}
                     for k, c in res.checks.items()}
    _check_lines(res.checks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The fp8 control of a served run comes out not correct, and the run
itself correct, under the cell's limit (the CPU rehearsal's width).

On the chip the same readings are taken at the cell's own size by
``bench/control.py``; PERF.md gives them.
"""

import time

import jax
import pytest

from bench import harness, reference, spec, weights

SEED = 4242


@pytest.mark.parametrize(
    "name", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_control_fails_where_the_run_passes(name):
    cell = spec.load_cell(name)
    res = harness.run(cell, SEED, 1.0, False, t_process=time.perf_counter(),
                      dry=True, log=lambda _m: None)
    assert res.correct, res.checks
    m = cell.model.dry()
    w = weights.make_weights(cell.family, m, SEED, jax.numpy.bfloat16)
    with jax.default_matmul_precision("highest"):
        prog, ctl = reference.widest_gap(
            cell.family, w, m, [(r.prompt, r.tokens) for r in res.sample],
            control=True)
    limit = float(cell.cell["limit_max_logit_gap"])
    assert prog == res.checks["max_logit_gap"]["value"] <= limit
    assert ctl > limit, (prog, ctl, limit)

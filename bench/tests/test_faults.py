"""A run with the served path broken underneath comes out not correct.

Each case drives the whole harness of a cell on the CPU (``dry``: the
rehearsal's width and lengths, no look for a chip) with one fault of
``bench/faults.py`` planted: a token altered where the engine samples
it, and a decode step that hands back its K/V pool unchanged.  The sound
run comes out correct.  On the chip, ``bench/control.py --fault`` reads
the same faults at a cell's own size.
"""

import time

import pytest

from bench import faults, harness, spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _run(cell, seed=321):
    return harness.run(cell, seed, 2.0, False, t_process=time.perf_counter(),
                       dry=True, log=lambda _m: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(spec.load_cell(name))
    assert res.correct, res.checks
    assert res.log["compiles_in_window"] == 0


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", faults.NAMES)
def test_fault_is_not_correct(monkeypatch, fault, name):
    cell = spec.load_cell(name)
    faults.plant(fault, cell.family, monkeypatch.setattr)
    res = _run(cell)
    assert not res.correct, res.checks
    gap = res.checks["max_logit_gap"]
    assert gap["value"] > gap["limit"]

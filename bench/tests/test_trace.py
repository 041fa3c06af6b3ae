"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/testdata/record_trace.py``: three runs of a jitted
``decode_fn`` around the paged attention kernel)."""

from pathlib import Path

import pytest

from bench import harness, trace

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "paged_decode.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(TRACE), kernels=harness.KERNELS)


def test_programs_and_kernel_are_found(summary):
    runs, seconds = summary.programs["jit_decode_fn"]
    assert runs == 3 and seconds > 0
    assert summary.kernel_calls["jit_decode_fn"] == 3
    assert 0 < summary.kernel_s["jit_decode_fn"] < seconds


def test_busy_within_extent(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s <= summary.extent_s
    assert summary.top_ops and summary.idle_gaps


def test_union_merges_overlaps():
    import numpy as np

    iv = trace._union(np.array([0.0, 5.0, 2.0, 20.0]),
                      np.array([3.0, 8.0, 6.0, 21.0]))
    assert iv == [(0.0, 8.0), (20.0, 21.0)]


def test_program_name_drops_the_run_id():
    assert trace.program_name("jit_decode_fn(1234)") == "jit_decode_fn"

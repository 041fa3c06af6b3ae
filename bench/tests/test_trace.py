"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/testdata/record_trace.py``: three runs of a jitted
``decode_fn`` around the paged attention kernel), and the kernel
roofline readers, each of which reads one kernel by name."""

from pathlib import Path

import numpy as np
import pytest

from bench import harness, spec, trace

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "paged_decode.xplane.pb"

#: The recorded kernel, named after the jitted function around it (the
#: trace predates the kernel's own ``pallas_call`` name).
KERNEL = ("jit_decode_fn", "decode_fn")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(TRACE))


def test_programs_and_kernel_are_found(summary):
    runs, seconds = summary.programs["jit_decode_fn"]
    assert runs == 3 and seconds > 0
    assert summary.kernel_calls[KERNEL] == 3
    assert 0 < summary.kernel_s[KERNEL] < seconds


def test_kernel_keying_keeps_the_decode_kernel_time(summary):
    """Keyed by kernel, the recorded decode program's Mosaic seconds and
    runs are what the reduction keyed by program alone gave."""
    assert list(summary.kernel_s) == [KERNEL]
    assert summary.kernel_s[KERNEL] == 0.00016185300000000002
    assert summary.kernel_calls == {KERNEL: 3}


@pytest.mark.parametrize("hlo, name", [
    ('%paged_attention.9 = bf16[16,32,128]{2,1,0} custom-call(s32[16,4]{1,0}'
     ' %copy), custom_call_target="tpu_custom_call"', "paged_attention"),
    ('%paged_prefill_attention.11 = bf16[8,1024,128]{2,1,0} custom-call('
     '%p), custom_call_target="tpu_custom_call"', "paged_prefill_attention"),
    ('%decode_fn.1 = bf16[4,32,128]{2,1,0} custom-call(%copy), '
     'custom_call_target="tpu_custom_call"', "decode_fn"),
    ('%expert_ffn = bf16[8,128]{1,0} custom-call(%x), '
     'custom_call_target="tpu_custom_call"', "expert_ffn"),
])
def test_kernel_name_from_the_mosaic_op(hlo, name):
    assert trace.kernel_name(hlo) == name


def _context(kernel_s):
    conf, fam = spec.load_config("stablelm-1.6b")
    served = [harness.Served(0, 0.0, np.zeros(p, np.int32), o, i,
                             tokens=[1] * o)
              for i, (p, o) in enumerate([(300, 40), (128, 7)])]
    return harness.RunContext(
        family=fam, model=fam.desc(conf).dry(),
        peaks={"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11},
        dtype_bytes=2, chunk=128,
        trace=trace.TraceSummary(kernel_s=kernel_s), traced_requests=served,
        traced_seconds=1.0)


@pytest.mark.parametrize("metric, program, kernel", [
    ("paged_attn_decode_roofline", "jit_decode_fn", "paged_attention"),
    ("paged_attn_prefill_roofline", "jit_prefill_chunk_fn",
     "paged_prefill_attention"),
])
def test_second_kernel_of_a_program_is_not_counted(metric, program, kernel):
    """A second Mosaic kernel in the same program (an expert kernel, say)
    leaves the attention kernel's roofline as it reads alone; the other
    kernel alone, as after a rename of the attention kernel, raises."""
    read = harness._reader(metric).read
    alone = read(_context({(program, kernel): 2e-3}))
    beside = read(_context({(program, kernel): 2e-3,
                            (program, "expert_ffn"): 5e-3}))
    assert alone is not None and alone > 0
    assert beside == alone
    with pytest.raises(ValueError, match="expert_ffn"):
        read(_context({(program, "expert_ffn"): 5e-3}))


@pytest.mark.parametrize("metric", ["paged_attn_decode_roofline",
                                    "paged_attn_prefill_roofline"])
def test_program_without_kernels_reads_nothing(metric):
    """Where no Mosaic kernel ran (the CPU's interpret mode), the
    roofline reads nothing, and never 0."""
    assert harness._reader(metric).read(_context({})) is None


def test_busy_within_extent(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s <= summary.extent_s
    assert summary.top_ops and summary.idle_gaps


def test_union_merges_overlaps():
    iv = trace._union(np.array([0.0, 5.0, 2.0, 20.0]),
                      np.array([3.0, 8.0, 6.0, 21.0]))
    assert iv == [(0.0, 8.0), (20.0, 21.0)]


def test_program_name_drops_the_run_id():
    assert trace.program_name("jit_decode_fn(1234)") == "jit_decode_fn"


def test_merge_adds_kernel_time_by_kernel():
    a = trace.TraceSummary(kernel_s={KERNEL: 1.0, ("p", "k"): 2.0},
                           kernel_calls={KERNEL: 1, ("p", "k"): 2})
    b = trace.TraceSummary(kernel_s={KERNEL: 0.5}, kernel_calls={KERNEL: 3})
    m = trace.merge([a, b])
    assert m.kernel_s == {KERNEL: 1.5, ("p", "k"): 2.0}
    assert m.kernel_calls == {KERNEL: 4, ("p", "k"): 2}

"""The device idle put down to the serving loop's ``serve.*`` spans, and
the queue waits read from the engine's events (``bench/phases.py``).

A trace of a program that writes no spans -- the small trace recorded on
a TPU v5e that ``test_trace.py`` reads -- gets no attribution, and
``bench/trace.py`` reduces it as it always has: the values below are
pinned so that wiring the attribution into that reduction cannot move
the metrics it already gives."""

import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, phases, trace

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "paged_decode.xplane.pb"

PINNED = {
    "busy_s": 0.00016404400000000002,
    "extent_s": 0.0017653100000000002,
    "programs": {"jit_decode_fn": (3, 0.000164072)},
    "kernel_s": {("jit_decode_fn", "decode_fn"): 0.00016185300000000002},
    "kernel_calls": {("jit_decode_fn", "decode_fn"): 3},
    "top_ops": [("jit_decode_fn %decode_fn.1 [custom call]",
                 0.00016185300000000002),
                ("jit_decode_fn %copy", 1.3220000000000002e-06),
                ("jit_decode_fn %broadcast_multiply_fusion",
                 8.690000000000001e-07)],
    "idle_gaps": [("$profiler.py:101 start_trace", 0.0009174820000000001),
                  ("PjitFunction(decode_fn)", 0.00068378),
                  ("ReadSyncFlag", 4e-09)],
}


@pytest.mark.parametrize("key", sorted(PINNED))
def test_trace_without_spans_reduces_as_before(key):
    summary = trace.reduce(str(TRACE))
    assert getattr(summary, key) == PINNED[key]


def test_trace_without_spans_has_no_attribution():
    p = phases.reduce(str(TRACE))
    assert (p.ticks, p.tick_s, p.tick_idle, p.span_s) == (0, 0.0, {}, {})
    assert p.idle_outside_ticks_s == 0.0
    assert phases.host_idle_ms_per_tick(p) is None
    assert phases.report(p, None, 1.0) == {}


def test_readers_find_nothing_without_data():
    assert phases.host_idle_ms_per_tick(phases.Phases()) is None
    assert phases.queue_wait_p95_s(None) is None
    assert phases.queue_wait_p95_s([]) is None


def test_gap_split_over_nested_spans_by_overlap():
    spans = [("serve.tick", 0, 100), ("serve.decode_tick", 10, 60),
             ("serve.readback", 40, 60), ("serve.emit", 60, 80),
             ("serve.check", 200, 210)]          # a cut tick's child
    gaps = [(35, 45), (55, 65), (90, 95), (120, 130), (200, 205)]
    by, outside = phases.attribute_idle(gaps, spans)
    assert by == {"serve.decode_tick": 5.0, "serve.readback": 10.0,
                  "serve.emit": 5.0, "serve.tick": 5.0}
    assert outside == 15.0
    assert phases.attribute_idle(gaps, []) == ({}, 40.0)
    assert phases.attribute_idle([], spans) == ({}, 0.0)


def test_merge_sums_sessions():
    a = phases.Phases(n_devices=1, ticks=2, tick_s=0.1,
                      tick_idle={"serve.readback": 0.01},
                      idle_outside_ticks_s=0.002,
                      span_s={"serve.tick": [0.04, 0.06]})
    b = phases.Phases(n_devices=1, ticks=1, tick_s=0.05,
                      tick_idle={"serve.readback": 0.02, "serve.emit": 0.001},
                      span_s={"serve.tick": [0.05]})
    m = phases.merge([a, b])
    assert (m.n_devices, m.ticks, m.tick_s) == (1, 3, pytest.approx(0.15))
    assert m.tick_idle == {"serve.readback": pytest.approx(0.03),
                           "serve.emit": 0.001}
    assert m.idle_outside_ticks_s == 0.002
    assert m.span_s == {"serve.tick": [0.04, 0.06, 0.05]}
    assert phases.host_idle_ms_per_tick(m) == pytest.approx(1e3 * 0.031 / 3)
    out = phases.report(m, [1.0, 3.0], 8.0)
    assert out["tick_idle_coverage"] == pytest.approx(0.031 / 0.033)
    assert out["queue_share_of_ttft"] == 0.5
    assert out["queue_wait_p95_s"] == pytest.approx(2.9)


def test_tick_cut_by_a_session_restart_is_left_out(tmp_path):
    """The spans of ``repro.obs.Tracer`` reach the profiler's trace, and a
    tick open across a restart of the profiler lands in neither
    session."""
    import jax

    from repro.obs import Tracer

    tracer = Tracer()
    dirs = [tmp_path / "a", tmp_path / "b"]
    jax.profiler.start_trace(str(dirs[0]))
    for step in range(3):
        with tracer.span("tick", step=step):
            with tracer.span("emit"):
                pass
    with tracer.span("tick", step=3):
        jax.profiler.stop_trace()
        jax.profiler.start_trace(str(dirs[1]))
    with tracer.span("tick", step=4):
        pass
    jax.profiler.stop_trace()
    a, b = (phases.reduce(trace.find_xplane(str(d))) for d in dirs)
    assert (a.ticks, b.ticks) == (3, 1)
    assert len(a.span_s["serve.emit"]) == 3
    assert phases.merge([a, b]).ticks == 4


def test_queue_waits_count_every_admission():
    events = [
        {"name": "queue_wait", "ph": "X", "ts": 0.0, "dur": 10.0,
         "args": {"rid": 0}},
        {"name": "queue_wait", "ph": "X", "ts": 5.0, "dur": 20.0,
         "args": {"rid": 1}},
        {"name": "preempt", "ph": "i", "ts": 50.0, "args": {"rid": 0}},
        # Re-admission: the event runs from submission, the wait from
        # the preemption.
        {"name": "queue_wait", "ph": "X", "ts": 0.0, "dur": 80.0,
         "args": {"rid": 0}},
    ]
    waits = phases.queue_waits(events, 2)
    assert waits == [pytest.approx(40e-6), pytest.approx(20e-6)]
    assert phases.queue_waits(events, 3) is None


class _Engine:
    """What ``harness._call`` uses of an engine: each request waits in
    the queue, then one tick delivers its only token."""

    def __init__(self, capacity=65536):
        from repro.obs import Tracer

        self.tracer = Tracer(capacity=capacity)

    def generate(self, prompts, max_new_tokens, on_token):
        t0 = self.tracer.now()
        for rid in range(len(prompts)):
            self.tracer.complete("queue_wait", t0, t0 + 0.5 * rid,
                                 tid=rid + 1, args={"rid": rid})
            with self.tracer.span("tick", step=rid):
                on_token(rid, 7)
        return [[7] for _ in prompts]


def test_queue_waits_none_after_the_ring_dropped_events():
    eng = _Engine(capacity=4)
    mark = phases.ring_mark(eng)
    eng.generate([0], None, lambda i, t: None)
    assert phases.call_queue_waits(eng, mark, 1) == [0.0]
    mark = phases.ring_mark(eng)
    eng.generate([0] * 5, None, lambda i, t: None)
    assert phases.call_queue_waits(eng, mark, 5) is None
    assert phases.call_queue_waits(object(), None, 1) is None


def test_reading_sees_the_traced_call_and_each_session():
    """Inside ``_reading`` the harness's own traced call and session
    reduction hand their engine events and traces over; outside, the
    harness is as it was."""
    call0, reduce0 = harness._call, harness.Sessions.reduce
    eng = _Engine()
    batch = [(np.zeros(4, np.int32), 1) for _ in range(3)]
    found = {}
    with phases._reading(harness, found):
        harness._call(eng, batch, 0, time.perf_counter)     # untraced
        assert found == {}
        sessions = harness.Sessions(time.perf_counter)
        sessions.start()
        served = harness._call(eng, batch, 1, time.perf_counter,
                               sessions.tick)
        sessions.stop()
        sessions.reduce()
    assert (harness._call, harness.Sessions.reduce) == (call0, reduce0)
    assert found["waits"] == [0.0, pytest.approx(0.5), pytest.approx(1.0)]
    assert found["ttft_s"] == pytest.approx(
        sum(r.times[0] - r.call_start for r in served))
    assert found["phases"].ticks == 3
    assert found["e2e"]["output_tok_s"] > 0

"""The device's idle time put down to the phases of the serving loop,
and the queue's share of the time to first token, in one traced run.

    python3 -m bench.phases --workload <name> --seed <n> --seconds <s>

runs ``bench/run.py --trace 1`` as it is (its result line comes first)
and reads two things that run passes by:

- each profiler session's trace, before the harness removes it: the
  program's own ``serve.*`` spans (``repro.obs.Tracer.span`` writes each
  as a profiler annotation on the host) beside the device's operations;
- the traced call's ``queue_wait`` events and ``preempt`` instants, from
  the engine's event ring (``engine.tracer``).

Its last line is a JSON object:

- ``ticks``, ``tick_s``: the complete ``serve.tick`` spans and their
  seconds.  The profiler records a span only when it closes in the
  session it opened in, so a tick cut by a session restart (the harness
  restarts from ``on_token``, inside the ``emit`` phase) is left out;
- ``idle_by_phase_s``: the device idle between operations inside those
  ticks, each stretch of a gap put down to the innermost ``serve.*``
  span over it (its self time), by span name;
- ``idle_outside_ticks_s`` and ``tick_idle_coverage``, the share of the
  idle between operations that complete ticks hold;
- ``host_idle_ms_per_tick``: the idle inside ticks over their count;
- ``span_median_ms``: each span's median length;
- ``queue_wait_p95_s`` and ``queue_share_of_ttft``: each request's
  waits in the queue, summed over its admissions, their p95, and their
  sum over the summed TTFT of the traced call;
- ``traced_end_to_end``: the end-to-end metrics of the traced call,
  which the profiler slows (each session restart writes a trace).

A program that writes no ``serve.*`` spans gives no ticks and no idle by
phase; the ``queue_wait`` events are older than the spans.  None of this
is a metric of ``BENCHMARK.json`` yet: ``bench/harness.py`` would have to
pass the engine's events and ``bench/trace.py`` the spans to a reader.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import trace as trace_mod

SPAN_PREFIX = "serve."
TICK = "serve.tick"


@dataclass
class Phases:
    n_devices: int = 0
    ticks: int = 0
    tick_s: float = 0.0
    tick_idle: Dict[str, float] = field(default_factory=dict)
    idle_outside_ticks_s: float = 0.0
    span_s: Dict[str, List[float]] = field(default_factory=dict)


def attribute_idle(gaps: Sequence[Tuple[float, float]],
                   spans: Sequence[Tuple[str, float, float]]
                   ) -> Tuple[Dict[str, float], float]:
    """Split the idle ``gaps`` (sorted, disjoint ``(start, end)``) over
    well-nested ``spans`` (``(name, start, end)``): each stretch of a gap
    inside a ``serve.tick`` span goes to the innermost span over it.
    Returns the idle inside ticks by span name, and the idle outside
    every tick."""
    g = np.asarray(gaps, float).reshape(-1, 2)
    lens = g[:, 1] - g[:, 0]
    total = float(lens.sum())
    if not spans or not len(g):
        return {}, total
    names = [n for n, _, _ in spans]
    s = np.asarray([a for _, a, _ in spans], float)
    e = np.asarray([b for _, _, b in spans], float)
    bounds = np.unique(np.concatenate([s, e]))
    label = np.full(len(bounds) - 1, -1)
    in_tick = np.zeros(len(bounds) - 1, bool)
    # Longest first, so that an inner span overwrites its parents.
    for i in np.lexsort((s, -(e - s))):
        k0, k1 = np.searchsorted(bounds, (s[i], e[i]))
        label[k0:k1] = i
        if names[i] == TICK:
            in_tick[k0:k1] = True
    # Idle before each bound, then inside each segment between bounds.
    cum = np.concatenate([[0.0], np.cumsum(lens)])
    j = np.searchsorted(g[:, 0], bounds, side="right") - 1
    jj = np.maximum(j, 0)
    before = np.where(j >= 0,
                      cum[jj] + np.clip(bounds - g[jj, 0], 0, lens[jj]), 0.0)
    seg = np.diff(before)
    out: Dict[str, float] = defaultdict(float)
    for k in np.flatnonzero(in_tick & (seg > 0)):
        out[names[label[k]]] += float(seg[k])
    return dict(out), total - sum(out.values())


def reduce(path: str) -> Phases:
    """The ``serve.*`` spans of the trace at ``path``, and the device idle
    between operations split over them (averaged over the devices)."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    spans = [(n, a, b) for n, a, b in zip(*trace_mod._host_events(planes))
             if n.startswith(SPAN_PREFIX)]
    out = Phases()
    for n, a, b in spans:
        out.span_s.setdefault(n, []).append((b - a) * 1e-9)
    ticks = out.span_s.get(TICK, [])
    out.ticks, out.tick_s = len(ticks), float(sum(ticks))
    if not spans:
        return out
    by: Dict[str, float] = defaultdict(float)
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if trace_mod.OP_LINE not in lines:
            continue
        _, s, d = trace_mod._events(lines[trace_mod.OP_LINE])
        if not len(s):
            continue
        iv = trace_mod._union(s, s + d)
        gaps = [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)]
        inside, rest = attribute_idle(gaps, spans)
        for k, v in inside.items():
            by[k] += v * 1e-9
        out.idle_outside_ticks_s += rest * 1e-9
        out.n_devices += 1
    n = max(1, out.n_devices)
    out.tick_idle = {k: v / n for k, v in by.items()}
    out.idle_outside_ticks_s /= n
    return out


def merge(parts: Sequence[Phases]) -> Phases:
    """One summary of several back-to-back sessions: times add up."""
    out = Phases()
    by: Dict[str, float] = defaultdict(float)
    for p in parts:
        out.n_devices = max(out.n_devices, p.n_devices)
        out.ticks += p.ticks
        out.tick_s += p.tick_s
        out.idle_outside_ticks_s += p.idle_outside_ticks_s
        for k, v in p.tick_idle.items():
            by[k] += v
        for k, v in p.span_s.items():
            out.span_s.setdefault(k, []).extend(v)
    out.tick_idle = dict(by)
    return out


def host_idle_ms_per_tick(p: Phases) -> Optional[float]:
    """Device idle milliseconds inside the complete ticks, per tick."""
    if not p.ticks or not p.n_devices:
        return None
    return 1e3 * sum(p.tick_idle.values()) / p.ticks


def ring_mark(engine) -> Optional[Tuple[int, int]]:
    """Where the engine's event ring (``engine.tracer``) stands: its
    length and its drop count; None for an engine without one."""
    tracer = getattr(engine, "tracer", None)
    if tracer is None:
        return None
    return len(tracer.export_events()), tracer.dropped


def call_queue_waits(engine, mark: Optional[Tuple[int, int]],
                     n_requests: int) -> Optional[List[float]]:
    """The queue waits of the call made since ``mark`` (``ring_mark``);
    None where the ring dropped events since, as it does when full."""
    tracer = getattr(engine, "tracer", None)
    if tracer is None or mark is None or tracer.dropped != mark[1]:
        return None
    return queue_waits(tracer.export_events()[mark[0]:], n_requests)


def queue_waits(events, n_requests: int) -> Optional[List[float]]:
    """Each request's seconds in the paged scheduler's queue, summed
    over its admissions, from the engine's events: a ``queue_wait`` runs
    from the request's submission to an admission, so a re-admission
    waits from the latest ``preempt`` of the request before it.  None
    unless each of ``n_requests`` requests was admitted."""
    preempts: Dict[Any, List[float]] = defaultdict(list)
    for ev in events:
        if ev.get("name") == "preempt":
            preempts[(ev.get("args") or {}).get("rid")].append(ev["ts"])
    waits: Dict[Any, float] = defaultdict(float)
    for ev in events:
        rid = (ev.get("args") or {}).get("rid")
        if ev.get("name") != "queue_wait" or ev.get("ph") != "X" \
                or rid is None:
            continue
        end = ev["ts"] + ev["dur"]
        start = max([ev["ts"]] + [t for t in preempts[rid] if t <= end])
        waits[rid] += (end - start) * 1e-6
    if len(waits) != n_requests:
        return None
    return list(waits.values())


def queue_wait_p95_s(waits: Optional[List[float]]) -> Optional[float]:
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, float), 95))


def report(p: Optional[Phases], waits: Optional[List[float]],
           ttft_s: float) -> Dict[str, Any]:
    """The JSON object of a run: what was found, and nothing where
    nothing was."""
    out: Dict[str, Any] = {}
    if p is not None and p.ticks:
        inside = sum(p.tick_idle.values())
        idle = inside + p.idle_outside_ticks_s
        out.update(
            ticks=p.ticks, tick_s=p.tick_s,
            idle_by_phase_s=dict(sorted(p.tick_idle.items(),
                                        key=lambda kv: -kv[1])),
            idle_outside_ticks_s=p.idle_outside_ticks_s,
            tick_idle_coverage=inside / idle if idle > 0 else None,
            host_idle_ms_per_tick=host_idle_ms_per_tick(p),
            span_median_ms={k: 1e3 * float(np.median(v))
                            for k, v in sorted(p.span_s.items())})
    if waits is not None:
        out["queue_wait_p95_s"] = queue_wait_p95_s(waits)
        out["queue_wait_s"] = sum(waits)
        if ttft_s > 0:
            out["queue_share_of_ttft"] = sum(waits) / ttft_s
    return out


@contextmanager
def _reading(harness, found: Dict[str, Any]):
    """While inside, ``harness.Sessions.reduce`` reduces each session's
    trace here first, and ``harness._call`` marks the engine's event ring
    around the traced call (the one given ``between_steps``)."""
    reduce0, call0 = harness.Sessions.reduce, harness._call

    def sessions_reduce(self):
        found["phases"] = merge([reduce(trace_mod.find_xplane(d))
                                 for d in self.dirs])
        return reduce0(self)

    def call(engine, batch, call, t_now, between_steps=None):
        mark = ring_mark(engine) if between_steps is not None else None
        served = call0(engine, batch, call, t_now, between_steps)
        if between_steps is not None:
            found["waits"] = call_queue_waits(engine, mark, len(batch))
            found["ttft_s"] = sum(r.times[0] - r.call_start
                                  for r in served if r.times)
            found["e2e"] = harness.end_to_end(served, served[0].call_start)
        return served

    harness.Sessions.reduce, harness._call = sessions_reduce, call
    try:
        yield found
    finally:
        harness.Sessions.reduce, harness._call = reduce0, call0


def main() -> int:
    from bench import run

    # As ``run.main`` does before JAX starts: the TPU runtime logs to
    # /tmp/tpu_logs unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    sys.path[:0] = [str(run.ROOT / "src")]
    from bench import harness

    found: Dict[str, Any] = {}
    with _reading(harness, found):
        rc = run.main(sys.argv[1:] + ["--trace", "1"])
    if rc:
        return rc
    out = report(found.get("phases"), found.get("waits"),
                 found.get("ttft_s", 0.0))
    out["traced_end_to_end"] = found.get("e2e")
    for k, v in out.items():
        print(f"[phases] {k} {v}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

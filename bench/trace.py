"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

A device plane (``/device:TPU:<n>``) has a line of program executions
(``XLA Modules``: one event per run of a compiled program, named
``<program>(<id>)``) and a line of operations (``XLA Ops``).  From them:

- ``busy_s``: the union of the operation intervals, averaged over the
  devices that ran any;
- ``programs``: for each program name, its runs and their device seconds;
- ``kernel_s``, ``kernel_calls``: the device seconds and the runs of
  each Mosaic kernel (a ``tpu_custom_call`` operation), keyed by
  ``(program, kernel)``: the program whose run holds it, and the
  kernel's name (``kernel_name``), so that each kernel of a program is
  read apart;
- ``top_ops``: leaf operations (not a loop holding others) by total
  device seconds, named ``<program> <instruction>``;
- ``idle_gaps``: device idle time between operations, each of the
  longest gaps named by the innermost host event that covers most of it
  (the host's own trace events: Python frames, a jitted call's
  dispatch), summed by name;
- ``dropped``: trace buffers the profiler dropped; a trace that dropped
  any is incomplete.

An operation's name in the trace is its HLO instruction
(``%name = type opcode(operands), ...``); ``<instruction>`` is the part
before `` = ``, with ``[custom call]`` added for a Mosaic kernel.  A
Mosaic kernel's instruction is named after its ``pallas_call``'s
``name``: ``%paged_attention.9`` is the kernel ``paged_attention``.

All times are seconds.  Event times in one file share one clock.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\(\d+\)$")
_INSTR_SUFFIX = re.compile(r"(\.\d+)+$")
#: What marks a Mosaic (Pallas) kernel among the operations.
MOSAIC = 'custom_call_target="tpu_custom_call"'


@dataclass
class TraceSummary:
    busy_s: float = 0.0
    extent_s: float = 0.0
    n_devices: int = 0
    programs: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    kernel_s: Dict[Tuple[str, str], float] = field(default_factory=dict)
    kernel_calls: Dict[Tuple[str, str], int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    dropped: int = 0

    def kernel_seconds(self, program: str, kernel: str) -> float:
        """The device seconds of ``kernel`` inside ``program``'s runs; 0
        where the program ran no Mosaic kernel (the CPU's interpret
        mode).  A program that ran Mosaic kernels but none of that name
        raises: a renamed ``pallas_call`` would otherwise leave its
        roofline silent."""
        found = sorted(k for p, k in self.kernel_s if p == program)
        if found and kernel not in found:
            raise ValueError(f"no Mosaic kernel {kernel!r} in {program}; "
                             f"it ran {found}")
        return self.kernel_s.get((program, kernel), 0.0)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """``jit_decode_fn(12)`` -> ``jit_decode_fn``."""
    return _SUFFIX.sub("", event_name)


def _events(line) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, starts, durs = [], [], []
    for e in line.events:
        names.append(e.name)
        starts.append(e.start_ns)
        durs.append(e.duration_ns)
    return names, np.asarray(starts, float), np.asarray(durs, float)


def _union(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[float, float]]:
    order = np.argsort(starts, kind="stable")
    out: List[List[float]] = []
    for s, e in zip(starts[order], ends[order]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


def _host_events(planes) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, starts, ends = [], [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n, s, d = _events(line)
            names += n
            starts.append(s)
            ends.append(s + d)
    if not names:
        return [], np.zeros(0), np.zeros(0)
    return names, np.concatenate(starts), np.concatenate(ends)


def _label_gaps(gaps: Sequence[Tuple[float, float]], host,
                longest: int = 256) -> Dict[str, float]:
    """Idle seconds by the name of the innermost host event that covers
    at least half of each of the ``longest`` gaps (else the one that
    overlaps it most); the shorter gaps are summed apart."""
    names, hs, he = host
    out: Dict[str, float] = defaultdict(float)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    rest = sum(b - a for a, b in gaps[longest:])
    if rest:
        out[f"gaps shorter than the {longest} longest"] = rest * 1e-9
    for a, b in gaps[:longest]:
        label = "no host event"
        if len(names):
            ov = np.minimum(he, b) - np.maximum(hs, a)
            cover = np.flatnonzero(ov >= 0.5 * (b - a))
            if len(cover):
                i = int(cover[np.argmin((he - hs)[cover])])
                label = names[i]
            elif ov.max() > 0:
                label = names[int(np.argmax(ov))]
        out[label] += (b - a) * 1e-9
    return out


def op_label(program: str, hlo: str) -> str:
    """``<program> <instruction>`` for an operation's HLO text."""
    name = hlo.split(" = ", 1)[0]
    if "custom-call(" in hlo:
        name += " [custom call]"
    return f"{program} {name}"


def kernel_name(hlo: str) -> str:
    """The kernel of a Mosaic operation's HLO text: its instruction name
    without ``%`` and the ``.<n>`` the compiler numbers it with
    (``%paged_attention.9 = ...`` -> ``paged_attention``)."""
    return _INSTR_SUFFIX.sub("", hlo.split(" = ", 1)[0].lstrip("%"))


def reduce(path: str, top: int = 10) -> TraceSummary:
    """Reduce the trace at ``path``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = _host_events(planes)
    out = TraceSummary()
    progs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    kern: Dict[Tuple[str, str], float] = defaultdict(float)
    kcalls: Dict[Tuple[str, str], int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    busy, lo, hi = [], np.inf, -np.inf
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        out.dropped += int(dict(plane.stats).get("dropped_traces", 0) or 0)
        lines = {ln.name: ln for ln in plane.lines}
        if OP_LINE not in lines:
            continue
        on, os_, od = _events(lines[OP_LINE])
        if not on:
            continue
        mn, ms, md = (_events(lines[MODULE_LINE]) if MODULE_LINE in lines
                      else ([], np.zeros(0), np.zeros(0)))
        pnames = [program_name(n) for n in mn]
        for p, d in zip(pnames, md):
            progs[p][0] += 1
            progs[p][1] += d * 1e-9
        order = np.argsort(ms)
        ms_sorted, me_sorted = ms[order], (ms + md)[order]
        pn_sorted = [pnames[i] for i in order]
        j = np.searchsorted(ms_sorted, os_, side="right") - 1
        inside = (j >= 0) & (os_ < me_sorted[np.maximum(j, 0)])
        prog_of = [pn_sorted[k] if ok else "outside any program"
                   for k, ok in zip(j.tolist(), inside.tolist())]
        # An operation that holds the next one (a loop) is not a leaf.
        oo = np.argsort(os_, kind="stable")
        holds = np.zeros(len(on), bool)
        holds[oo[:-1]] = os_[oo[1:]] < (os_ + od)[oo[:-1]]
        for n, d, prog, h in zip(on, od, prog_of, holds.tolist()):
            if not h:
                ops[op_label(prog, n)] += d * 1e-9
            if MOSAIC in n:
                key = (prog, kernel_name(n))
                kern[key] += d * 1e-9
                kcalls[key] += 1
        iv = _union(os_, os_ + od)
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        lo, hi = min(lo, iv[0][0]), max(hi, iv[-1][1])
        gaps = [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)]
        for k, v in _label_gaps(gaps, host).items():
            gaps_by[k] += v
    out.n_devices = len(busy)
    if busy:
        out.busy_s = float(np.mean(busy))
        out.extent_s = float((hi - lo) * 1e-9)
    out.programs = {k: (int(v[0]), float(v[1])) for k, v in progs.items()}
    out.kernel_s = dict(kern)
    out.kernel_calls = dict(kcalls)
    out.top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps_n = max(1, out.n_devices)
    out.idle_gaps = sorted(((k, v / gaps_n) for k, v in gaps_by.items()),
                           key=lambda kv: -kv[1])[:top]
    return out


def merge(parts: Sequence[TraceSummary], top: int = 10) -> TraceSummary:
    """One summary of several traces of one run (back-to-back sessions):
    times and counts add up."""
    out = TraceSummary()
    progs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    sums = {"kernel_s": defaultdict(float), "kernel_calls": defaultdict(int),
            "top_ops": defaultdict(float), "idle_gaps": defaultdict(float)}
    for p in parts:
        out.busy_s += p.busy_s
        out.extent_s += p.extent_s
        out.n_devices = max(out.n_devices, p.n_devices)
        out.dropped += p.dropped
        for k, (n, sec) in p.programs.items():
            progs[k][0] += n
            progs[k][1] += sec
        for key in ("kernel_s", "kernel_calls"):
            for k, v in getattr(p, key).items():
                sums[key][k] += v
        for key in ("top_ops", "idle_gaps"):
            for k, v in getattr(p, key):
                sums[key][k] += v
    out.programs = {k: (int(v[0]), float(v[1])) for k, v in progs.items()}
    out.kernel_s = dict(sums["kernel_s"])
    out.kernel_calls = dict(sums["kernel_calls"])
    for key in ("top_ops", "idle_gaps"):
        setattr(out, key, sorted(sums[key].items(),
                                 key=lambda kv: -kv[1])[:top])
    return out


def describe(path: str, events: int = 6) -> str:
    """The planes, lines and first event names of a trace, for reading one
    by hand: ``python3 -m bench.trace <file.xplane.pb>``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            out.append(f"  line {line.name!r}: {len(evs)} events, "
                       f"{len(names)} names: {names[:events]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
    for k, v in vars(reduce(sys.argv[1])).items():
        print(k, v)

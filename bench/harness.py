"""One run of one cell: set-up, the measured window, the trace, the check.

Set-up makes the weights from the seed on the device in bf16
(``bench.weights``), builds the engine as ``repro-serve`` builds it
(paged, chunked prefill, prefix cache off, greedy) with those weights,
and warms every program the cell's traffic will run: the decode step at
the cell's slot count, the full prefill chunk, and one partial chunk for
each prompt length remainder the mix can give.

The window then calls ``ServeEngine.generate`` again and again, each
call with ``2 * max_slots`` requests from the stream, so slots are
backfilled inside a call, while less than ``--seconds`` have passed; the
last call runs to its end, and the window ends with its last token.
``on_token`` stamps every delivered token; a ``None`` (a recompute
preemption) discards that request's earlier tokens.  With ``trace`` the
first call of the window runs under the JAX profiler.

After the window the engine is freed and a sample of the finished
requests, drawn from the seed, is compared with the float32 reference
(``bench.reference``).
"""

from __future__ import annotations

import gc
import importlib.util
import math
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import flops, reference, spec, traffic, weights
from bench import trace as trace_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Backend compilations (or read-backs from the persistent cache),
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


@dataclass
class Served:
    """One request of the window."""

    call: int
    call_start: float
    prompt: np.ndarray
    max_new: int
    index: int                      # position in its call
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)


@dataclass
class RunContext:
    """What the per-layer readers (``bench/metrics``) read."""

    family: Any
    model: Any
    peaks: Dict[str, float]
    dtype_bytes: int
    chunk: int
    trace: Optional[trace_mod.TraceSummary] = None
    traced_requests: List[Served] = field(default_factory=list)
    traced_seconds: float = 0.0
    counters: Dict[str, float] = field(default_factory=dict)
    compiles_in_window: int = 0

    def traced_work(self):
        return flops.call_work(
            self.family, self.model, [(len(r.prompt), len(r.tokens))
                         for r in self.traced_requests],
            self.chunk, self.dtype_bytes)


def load_peaks(kind: str) -> Dict[str, float]:
    import json

    table = json.loads((spec.BENCH_DIR / "peaks.json").read_text())
    for entry in table["chips"]:
        if kind in entry["device_kind"]:
            return entry
    raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")


def build_engine(cfg, m, max_slots: int, params, device):
    import jax.numpy as jnp

    from repro.launch.mesh import make_device_mesh
    from repro.serve import SamplingConfig, ServeEngine, ServePolicy

    engine = ServeEngine(
        cfg, make_device_mesh(device),
        policy=ServePolicy(max_new_tokens=1, max_slots=max_slots,
                           max_len=m.max_len, batching="auto",
                           prefill="chunked", prefix_cache="off",
                           sampling=SamplingConfig()),
        dtype=getattr(jnp, m.dtype), params=params)
    if engine.batching != "paged":
        raise SystemExit(f"batching='auto' resolved to {engine.batching!r}")
    return engine


def warm_up(engine, pairs, chunk: int, max_slots: int, vocab: int) -> int:
    """One ``generate`` that runs every program the window will: a chunk
    of every length the pairs cut (the full chunk and each remainder
    ``prompt_len % chunk``) and the decode step, at the window's pool
    geometry.  A call's first chunk meets a freshly made pool, which JAX
    dispatches as a program of its own; every call of the window starts
    with the first pair's prompt, so the warm-up call does too.  The
    geometry does not depend on the call: every pair fits the plan's
    per-slot table (``max_len``).  Returns the number of chunk lengths."""
    lens = sorted({p % chunk for p, _ in pairs} - {0})
    if any(p >= chunk for p, _ in pairs):
        lens.append(chunk)
    reqs = [pairs[0][0]] + lens
    reqs += [lens[0]] * max(0, max_slots - len(reqs))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, k, dtype=np.int32) for k in reqs]
    engine.generate(prompts, max_new_tokens=2)
    return len(lens)


class Sessions:
    """Profiler sessions back to back over one call, each at most
    ``SESSION_S`` long, so that no session overflows the device's trace
    buffer.  ``tick`` runs between two engine steps (from ``on_token``,
    when the step's tokens have been read back and nothing runs on the
    device), so no device program straddles two sessions.  ``seconds``
    sums the sessions' host-clock lengths."""

    def __init__(self, t_now: Callable[[], float]):
        self.t_now = t_now
        self.dirs: List[str] = []
        self.seconds = 0.0
        self._t0: Optional[float] = None

    def start(self) -> None:
        import jax

        self.dirs.append(tempfile.mkdtemp(prefix="bench-trace-"))
        jax.profiler.start_trace(self.dirs[-1])
        self._t0 = self.t_now()

    def stop(self) -> None:
        import jax

        self.seconds += self.t_now() - self._t0
        jax.profiler.stop_trace()
        self._t0 = None

    def tick(self) -> None:
        if self.t_now() - self._t0 >= SESSION_S:
            self.stop()
            self.start()

    def reduce(self) -> trace_mod.TraceSummary:
        try:
            return trace_mod.merge([
                trace_mod.reduce(trace_mod.find_xplane(d))
                for d in self.dirs])
        finally:
            for d in self.dirs:
                shutil.rmtree(d, ignore_errors=True)


def _call(engine, batch, call: int, t_now: Callable[[], float],
          between_steps: Optional[Callable[[], None]] = None
          ) -> List[Served]:
    t0 = t_now()
    served = [Served(call, t0, p, o, i) for i, (p, o) in enumerate(batch)]

    def on_token(i: int, tok: Optional[int]) -> None:
        r = served[i]
        if tok is None:
            r.times.clear()
            r.tokens.clear()
        else:
            r.times.append(t_now())
            r.tokens.append(int(tok))
        if between_steps is not None:
            between_steps()

    outs = engine.generate([p for p, _ in batch],
                           max_new_tokens=[o for _, o in batch],
                           on_token=on_token)
    for r, out in zip(served, outs):
        if list(out) != r.tokens:
            raise RuntimeError(f"request {r.index} of call {call}: streamed "
                               f"tokens differ from the returned ones")
    return served


def _percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q)) if xs else math.nan


def end_to_end(served: List[Served], t0: float) -> Dict[str, float]:
    """The end-to-end metrics of the window's calls (every call begun
    within ``--seconds``, each run to its end).  Each is a host-clock
    time that spans many engine steps: the rate over all the calls'
    tokens and all their time; a first token, which waits for a prompt's
    prefill; a request's time per output token over its decode
    (``TPOT_SPAN_S`` at the least)."""
    last = max((r.times[-1] for r in served if r.times), default=t0)
    toks = sum(len(r.times) for r in served)
    ttft = [r.times[0] - r.call_start for r in served if r.times]
    tpot = [(r.times[-1] - r.times[0]) / (len(r.times) - 1)
            for r in served
            if len(r.times) > 1 and r.times[-1] - r.times[0] >= TPOT_SPAN_S]
    return {
        "output_tok_s": toks / (last - t0) if last > t0 else 0.0,
        "ttft_p95_s": _percentile(ttft, 95),
        "tpot_p95_ms": _percentile(tpot, 95) * 1e3,
        "n_ttft": len(ttft),
        "n_tpot": len(tpot),
    }


def pick_sample(served: List[Served], max_slots: int, want_tokens: int,
                max_requests: int, seed: int) -> List[Served]:
    """Finished requests to compare: the one served most tokens, the
    longest prompt, the longest backfilled request (second half of its
    call), then others drawn from the seed until ``want_tokens`` served
    tokens or ``max_requests``."""
    done = [r for r in served if len(r.tokens) == r.max_new]
    if not done:
        return []
    picks = [max(done, key=lambda r: len(r.tokens)),
             max(done, key=lambda r: len(r.prompt))]
    back = [r for r in done if r.index >= max_slots]
    if back:
        picks.append(max(back, key=lambda r: len(r.tokens)))
    out: List[Served] = []
    for r in picks:
        if all(r is not o for o in out):
            out.append(r)
    rest = [r for r in done if all(r is not o for o in out)]
    order = np.random.default_rng(seed).permutation(len(rest))
    for i in order:
        if (sum(len(r.tokens) for r in out) >= want_tokens
                or len(out) >= max_requests):
            break
        out.append(rest[int(i)])
    return out


def _reader(name: str):
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    modspec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod


def per_layer_metrics(ctx: RunContext, bench: dict
                      ) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of ``BENCHMARK.json``, each read by
    ``bench/metrics/<name>.py``; a reader that finds nothing returns None
    and the metric is left out."""
    out = {}
    for m in bench["per_layer"]:
        v = _reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


@dataclass
class RunResult:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, Dict[str, Any]]
    device: Dict[str, Any]
    checks: Dict[str, Dict[str, float]]
    breakdown: Optional[Dict[str, list]] = None
    log: Dict[str, Any] = field(default_factory=dict)
    sample: List[Served] = field(default_factory=list)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, dry: bool = False,
        log: Callable[[str], None] = print) -> RunResult:
    """One run of ``cell``.  ``dry`` shrinks the model and the lengths for
    the CPU rehearsal."""
    import jax
    import jax.numpy as jnp

    clock = CompileClock()
    device = jax.devices()[0]
    fam = cell.family
    m = cell.model.dry() if dry else cell.model
    cfg = fam.model_config(m, cell.config["repro_arch"])
    c = cell.cell
    max_slots = int(c["max_slots"])
    if dry:
        max_slots = min(max_slots, DRY_SLOTS)
    params = weights.make_weights(fam, m, seed, getattr(jnp, m.dtype),
                                  device)
    jax.block_until_ready(params)
    engine = build_engine(cfg, m, max_slots, params, device)
    page = engine.page.page_tokens
    chunk = engine.plan.chunk_tokens() or page
    scale = m.max_len / cell.model.max_len
    per_call = 2 * max_slots
    pairs = traffic.length_pairs(cell.mix, per_call, chunk, m.max_len, scale)
    n_buckets = warm_up(engine, pairs, chunk, max_slots, m.vocab)
    stream = traffic.RequestStream(pairs, m.vocab, seed)
    t_now = time.perf_counter
    log(f"cell={cell.name} page={page} chunk={chunk} slots={max_slots} "
        f"chunk_programs={n_buckets} pool_pages="
        f"{engine.metrics['pages_total']} compiles={clock.count} "
        f"({clock.seconds:.1f}s)")

    sessions = None
    traced: List[Served] = []
    traced_s = 0.0
    counters: Dict[str, float] = {}
    compiles0 = clock.count
    t0 = t_now()
    setup_s = t0 - t_process
    t1 = t0 + seconds
    served: List[Served] = []
    call = 0
    while t_now() < t1:
        batch = stream.next_batch()
        if trace and call == 0:
            sessions = Sessions(t_now)
            before = {k: engine.metrics[k] for k in
                      ("slot_steps", "active_slot_steps")}
            sessions.start()
            traced = _call(engine, batch, call, t_now, sessions.tick)
            sessions.stop()
            traced_s = sessions.seconds
            counters = {k: engine.metrics[k] - v for k, v in before.items()}
            served += traced
        else:
            served += _call(engine, batch, call, t_now)
        call += 1
    t_end = t_now()
    compiles = clock.count - compiles0
    stats = device.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    e2e = end_to_end(served, t0)
    log(f"window: {call} calls, {len(served)} requests, ran "
        f"{t_end - t0:.2f}s of {seconds}s, compiles inside {compiles}, "
        f"ttft samples {e2e['n_ttft']}, tpot samples {e2e['n_tpot']}")
    del engine
    gc.collect()

    failed = sum(1 for r in served if len(r.tokens) != r.max_new)
    checks: Dict[str, Dict[str, float]] = {}
    limit = float(c["limit_max_logit_gap"])
    sample = pick_sample(served, max_slots, int(c["sample_tokens"]),
                         int(c["sample_requests"]), seed)
    t_check = t_now()
    if sample:
        with jax.default_matmul_precision("highest"):
            gap, _ = reference.widest_gap(
                fam, params, m, [(r.prompt, r.tokens) for r in sample])
    else:
        gap = math.inf
    checks["max_logit_gap"] = {"value": gap, "limit": limit}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = bool(gap <= limit and failed == 0 and served)
    log(f"check: {len(sample)} requests, "
        f"{sum(len(r.tokens) for r in sample)} served tokens, prompts "
        f"{[len(r.prompt) for r in sample]}, {t_now() - t_check:.1f}s")

    kind = device.device_kind
    dev = {"platform": device.platform, "kind": kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = RunResult(correct, len(served), failed, {}, dev, checks,
                       log={"calls": call, "requests": len(served),
                            "compiles_in_window": compiles,
                            "n_ttft": e2e["n_ttft"], "n_tpot": e2e["n_tpot"]},
                       sample=sample)
    if dry:
        return result
    bench = spec.load_benchmark()
    if not trace:
        vals = dict(e2e, setup_s=setup_s)
        result.metrics = {e["name"]: {"value": float(vals[e["name"]]),
                                      "unit": e["unit"]}
                          for e in bench["end_to_end"]}
        return result
    t_reduce = t_now()
    summary = sessions.reduce()
    log(f"trace: {len(sessions.dirs)} sessions over {traced_s:.1f}s, "
        f"reduced in {t_now() - t_reduce:.1f}s")
    if summary.dropped:
        raise RuntimeError(f"the profiler dropped {summary.dropped} trace "
                           f"buffers: the device trace is incomplete")
    ctx = RunContext(family=fam, model=m, peaks=load_peaks(kind),
                     dtype_bytes=jnp.dtype(m.dtype).itemsize, chunk=chunk,
                     trace=summary, traced_requests=traced,
                     traced_seconds=traced_s, counters=counters,
                     compiles_in_window=compiles)
    result.metrics = per_layer_metrics(ctx, bench)
    dev["busy_s"] = summary.busy_s
    dev["window_s"] = traced_s
    result.breakdown = {"device_ops": [[n, s] for n, s in summary.top_ops],
                        "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result.log["programs"] = summary.programs
    result.log["kernel_s"] = {f"{p} {k}": s
                              for (p, k), s in summary.kernel_s.items()}
    return result


#: Longest profiler session (s); a second of the served path makes about
#: 10,000 device events.
SESSION_S = 5.0

#: Shortest decode span (s) a request's time per output token is read
#: over: the host clock is good to about half a millisecond.
TPOT_SPAN_S = 0.25

#: Decode slots in the CPU rehearsal.
DRY_SLOTS = 4

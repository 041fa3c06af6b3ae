"""The replica host: one worker (spawned process or thread) per
`ServeEngine`, driven by an instruction queue (DESIGN.md §12).

The orchestration shape follows Mithril's ``TorchParallel``: the front
side never touches the engine directly -- it enqueues ``(seq, op,
payload)`` instructions and a per-replica worker loop executes them
against ONE engine built lazily in the worker and cached for the
worker's lifetime (the expensive part -- mesh, params, jit caches --
is paid once per process, not per request).  Every reply is tagged with
the instruction's ``seq`` so one response queue can carry interleaved
token streams, results and errors; after each instruction the worker
pushes an unsolicited ``ReplicaStats`` tick (``seq == _TICK``) so the
router sees the replica's pool pressure without a round trip.

Two transports share the loop verbatim:

  * ``"proc"`` -- a ``multiprocessing`` *spawn* context worker with a
    ``ctx.Queue`` pair.  The factory must be picklable (``EngineSpec`` /
    ``StubSpec``); one JAX runtime per replica, on CPU hosts only: a TPU
    chip belongs to one process, and the front side already holds it.
  * ``"thread"`` -- a daemon thread with ``queue.Queue``s in-process.
    Same protocol, no pickling, and the live engine is reachable for
    LIVE telemetry (``Replica.stats`` reads ``engine.stats()`` directly,
    so a replica's free-page count moves WHILE a request is resident --
    what the ``free_pages`` routing policy keys on).  Tests, the
    single-host benchmark and TPU hosts (one replica per chip) use this.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field, fields, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: seq id of unsolicited telemetry pushes (never a real instruction).
_TICK = -1

TRANSPORTS = ("thread", "proc")


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------


@dataclass
class ReplicaStats:
    """One replica's telemetry tick -- the router's entire world view.

    ``free_pages``/``slots_free`` come from ``engine.stats()`` (the live
    pool when one exists); ``queued``/``active`` are FRONT-side facts
    (instructions enqueued but unfinished) filled in by ``Replica.stats``
    -- the worker cannot see its own backlog.  ``drained`` is a router
    verdict, stamped by ``ServeCluster.stats``."""

    replica: int = 0
    role: str = "serve"                 # | "prefill" | "decode"
    free_pages: int = 0
    used_pages: int = 0
    pages_total: int = 0
    slots_free: int = 0
    slots_total: int = 0
    page_tokens: int = 0
    prefix_nodes: int = 0
    prefix_pages: int = 0
    prefix_resident_bytes: int = 0
    queued: int = 0
    active: int = 0
    tokens: int = 0
    ticks: int = 0
    drained: bool = False
    #: Full registry snapshot (DESIGN.md §13): every counter/gauge plus
    #: flattened histogram quantiles, forwarded on the existing stats
    #: tick -- so the router (and ``GET /metrics``) reads the very gauge
    #: the replica's page pool writes, not a reconstruction.  Stub
    #: engines (no registry) leave it empty.
    metrics: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_engine(cls, engine, replica: int, role: str = "serve",
                    ticks: int = 0) -> "ReplicaStats":
        s = engine.stats()
        keep = {f.name for f in fields(cls)}
        snap: Dict[str, Any] = {}
        obs = getattr(engine, "obs", None)
        if obs is not None:
            try:
                snap = obs.snapshot()
            except Exception:                       # noqa: BLE001
                snap = {}
        return cls(replica=replica, role=role, ticks=ticks, metrics=snap,
                   **{k: v for k, v in s.items() if k in keep})


# ---------------------------------------------------------------------------
# Picklable engine factories (the spawn transport ships these, not engines)
# ---------------------------------------------------------------------------

#: Engines built in THIS process, keyed by (spec, replica): the spawn
#: worker builds its engine once and every later instruction reuses it;
#: the thread transport keys by replica so co-resident replicas get
#: INDEPENDENT pools (the whole point of the cluster).
_ENGINE_CACHE: Dict[Any, Any] = {}


@dataclass(frozen=True)
class EngineSpec:
    """A picklable ``ServeEngine`` recipe: everything the worker needs to
    rebuild the engine on its side of the spawn.  ``chip`` is a tuple of
    ``chip_spec`` override items (tests shrink VMEM with it).  Replica ``r``
    runs on a one-device mesh over device ``r`` (modulo the device count):
    co-resident replicas on a multi-chip host each hold one chip."""

    arch: str = "llama3.2-1b"
    reduced: bool = True
    max_new_tokens: int = 16
    max_slots: int = 1
    max_len: int = 256
    batching: str = "paged"
    prefill: str = "chunked"
    prefix_cache: str = "radix"
    kv_budget_bytes: Optional[int] = None
    seed: int = 0
    chip: Tuple[Tuple[str, Any], ...] = ()

    def __call__(self, replica: int = 0):
        key = (self, replica)
        eng = _ENGINE_CACHE.get(key)
        if eng is None:
            import jax

            from repro.configs.base import get_model_config
            from repro.hw.tpu import chip_spec
            from repro.launch.mesh import make_device_mesh
            from repro.serve.engine import ServeEngine, ServePolicy

            cfg = get_model_config(self.arch)
            if self.reduced:
                cfg = cfg.reduced()
            devices = jax.devices()
            eng = ServeEngine(
                cfg, make_device_mesh(devices[replica % len(devices)]),
                policy=ServePolicy(
                    max_new_tokens=self.max_new_tokens,
                    max_slots=self.max_slots, max_len=self.max_len,
                    batching=self.batching, prefill=self.prefill,
                    prefix_cache=self.prefix_cache,
                    kv_budget_bytes=self.kv_budget_bytes),
                seed=self.seed,
                spec=chip_spec(**dict(self.chip)),
                replica=replica)
            _ENGINE_CACHE[key] = eng
        return eng


class _StubEngine:
    """Deterministic engine double: token ``i`` of a prompt is
    ``(sum(prompt) + i) % 997``, with an optional per-token delay so
    tests can hold a replica busy.  Implements exactly the surface the
    worker loop drives (``generate``/``stats``/``export_pages``/
    ``import_pages``)."""

    def __init__(self, spec: "StubSpec", replica: int = 0):
        self.spec = spec
        self.replica = replica
        self._tokens = 0
        self._busy = 0

    def generate(self, prompts, max_new_tokens=16, on_token=None):
        max_new = max_new_tokens
        if isinstance(max_new, int):
            max_new = [max_new] * len(prompts)
        outs = []
        self._busy += 1
        try:
            for i, p in enumerate(prompts):
                base = int(sum(int(x) for x in np.asarray(p).reshape(-1)))
                toks = []
                for step in range(int(max_new[i])):
                    if self.spec.delay_s:
                        time.sleep(self.spec.delay_s)
                    t = (base + step) % 997
                    toks.append(t)
                    self._tokens += 1
                    if on_token is not None:
                        on_token(i, t)
                outs.append(toks)
        finally:
            self._busy -= 1
        return outs

    def stats(self) -> Dict[str, Any]:
        used = self._busy * self.spec.pages_per_request
        return {
            "batching": "paged",
            "free_pages": max(0, self.spec.pages_total - used),
            "used_pages": used,
            "pages_total": self.spec.pages_total,
            "slots_free": max(0, self.spec.slots_total - self._busy),
            "slots_total": self.spec.slots_total,
            "page_tokens": self.spec.page_tokens,
            "page_bytes": 0,
            "kv_shard": 1,
            "tokens": self._tokens,
            "decode_steps": self._tokens,
            "prefill_chunks": 0,
            "prefix_nodes": 0,
            "prefix_pages": 0,
            "prefix_resident_bytes": 0,
        }

    def export_pages(self, tokens):
        return None

    def import_pages(self, tokens, payloads, snaps=None, n_slots=1):
        return 0


@dataclass(frozen=True)
class StubSpec:
    """Picklable factory for ``_StubEngine`` (protocol / HTTP / router
    tests: no JAX, deterministic tokens, controllable latency)."""

    pages_total: int = 64
    slots_total: int = 4
    page_tokens: int = 8
    pages_per_request: int = 8
    delay_s: float = 0.0

    def __call__(self, replica: int = 0) -> _StubEngine:
        return _StubEngine(self, replica)


# ---------------------------------------------------------------------------
# The worker loop (both transports run THIS, verbatim)
# ---------------------------------------------------------------------------


def _serve_loop(recv: Callable[[], Any], send: Callable[[Any], None],
                factory, replica: int, role: str) -> None:
    """Drain ``(seq, op, payload)`` instructions against one lazily-built
    engine.  Ops: ``generate`` (streams ``(seq, "token", (i, tok))``
    before the final result), ``export`` / ``import`` (disaggregation
    page hooks), ``stats``, ``shutdown``.  Any exception becomes a
    ``(seq, "err", msg)`` reply -- the worker never dies on a bad
    request.  After every instruction one unsolicited ``(_TICK,
    "stats", ReplicaStats)`` tick is pushed."""
    engine = None
    ticks = 0
    while True:
        seq, op, payload = recv()
        if op == "shutdown":
            send((seq, "ok", None))
            return
        send((seq, "begin", None))
        try:
            if engine is None:
                engine = factory(replica)
            if op == "generate":
                prompts, max_new = payload

                def cb(i, tok, _seq=seq):
                    send((_seq, "token", (i, tok)))

                result = engine.generate(prompts, max_new_tokens=max_new,
                                         on_token=cb)
            elif op == "export":
                result = engine.export_pages(payload)
            elif op == "import":
                tokens, payloads, snaps = payload
                result = engine.import_pages(tokens, payloads, snaps=snaps)
            elif op == "stats":
                result = engine.stats()
            elif op == "trace":
                tracer = getattr(engine, "tracer", None)
                result = (tracer.chrome_events(payload)
                          if tracer is not None else [])
            else:
                raise ValueError(f"unknown op {op!r}")
            send((seq, "ok", result))
        except Exception as e:                      # noqa: BLE001
            send((seq, "err", f"{type(e).__name__}: {e}"))
        ticks += 1
        if engine is not None:
            try:
                send((_TICK, "stats",
                      ReplicaStats.from_engine(engine, replica, role,
                                               ticks=ticks)))
            except Exception:                       # noqa: BLE001
                pass


def _proc_main(inq, outq, factory, replica: int, role: str) -> None:
    _serve_loop(inq.get, outq.put, factory, replica, role)


# ---------------------------------------------------------------------------
# Front side
# ---------------------------------------------------------------------------


class _Call:
    """One in-flight instruction: a future plus its streaming hooks."""

    def __init__(self, seq: int, op: str, payload: Any,
                 on_token=None, on_done=None):
        self.seq = seq
        self.op = op
        self.payload = payload
        self.on_token = on_token
        self.on_done = on_done
        self.t_submit = time.monotonic()
        self.first_token_time: Optional[float] = None
        self.started = False
        self.result: Any = None
        self.err: Optional[str] = None
        self._ev = threading.Event()

    def done(self) -> bool:
        return self._ev.is_set()

    def wait(self, timeout: Optional[float] = 60.0):
        if not self._ev.wait(timeout):
            raise TimeoutError(f"call {self.op}#{self.seq} timed out")
        if self.err is not None:
            raise RuntimeError(self.err)
        return self.result

    def _finish(self, result=None, err=None) -> None:
        self.result = result
        self.err = err
        self._ev.set()
        if self.on_done is not None:
            try:
                self.on_done(self)
            except Exception:                       # noqa: BLE001
                pass


class Replica:
    """Front-side handle to one replica host.

    ``submit`` enqueues an instruction and returns a ``_Call``; a demux
    pump thread routes the shared response queue's messages back to their
    calls (token streams fire ``on_token(i, tok)`` as they arrive --
    ``tok is None`` is a stream reset after a recompute preemption).
    ``cancel_pending`` abandons instructions the worker has not BEGUN
    (drain/requeue): the worker may still execute them later, but their
    replies are discarded -- wasted compute, never wrong results."""

    def __init__(self, factory, replica: int = 0, role: str = "serve",
                 transport: str = "thread",
                 default_stats: Optional[ReplicaStats] = None):
        if transport not in TRANSPORTS:
            raise ValueError(f"unknown transport {transport!r}; "
                             f"one of {TRANSPORTS}")
        self.replica = replica
        self.role = role
        self.transport = transport
        self.engine = None              # thread transport: live telemetry
        self.last_stats: Optional[ReplicaStats] = None
        #: What a replica that has never served advertises -- the PLAN's
        #: pool geometry (whole pool free), so the ``free_pages`` policy
        #: spreads onto fresh replicas instead of starving them at the
        #: zero-telemetry default.
        self.default_stats = default_stats
        self._seq = 0
        self._calls: Dict[int, _Call] = {}
        self._lock = threading.Lock()
        self._closed = False
        if transport == "proc":
            import jax

            if jax.default_backend() == "tpu":
                raise ValueError(
                    "transport='proc' cannot serve on a TPU: this process "
                    "already holds the chip, so a spawned replica would "
                    "fail or hang reaching it; use transport='thread' (one "
                    "replica per device)")
        if transport == "thread":
            self._inq: Any = queue.Queue()
            self._outq: Any = queue.Queue()

            def _build(rep):
                eng = factory(rep)
                self.engine = eng
                return eng

            self._worker: Any = threading.Thread(
                target=_serve_loop,
                args=(self._inq.get, self._outq.put, _build, replica, role),
                name=f"replica-{replica}", daemon=True)
        else:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")
            self._inq = ctx.Queue()
            self._outq = ctx.Queue()
            self._worker = ctx.Process(
                target=_proc_main,
                args=(self._inq, self._outq, factory, replica, role),
                daemon=True)
        self._worker.start()
        self._pump = threading.Thread(target=self._demux,
                                      name=f"replica-{replica}-demux",
                                      daemon=True)
        self._pump.start()

    # ------------------------------------------------------------- demux
    def _demux(self) -> None:
        while True:
            try:
                msg = self._outq.get(timeout=0.1)
            except queue.Empty:
                if self._closed and not self._calls:
                    return
                continue
            except (EOFError, OSError):
                return
            seq, kind, payload = msg
            if seq == _TICK:
                self.last_stats = payload
                continue
            with self._lock:
                call = self._calls.get(seq)
            if call is None:
                continue                 # cancelled: discard the reply
            if kind == "begin":
                call.started = True
            elif kind == "token":
                i, tok = payload
                if tok is None:
                    call.first_token_time = None    # preempted: re-emits
                elif call.first_token_time is None:
                    call.first_token_time = time.monotonic()
                if call.on_token is not None:
                    try:
                        call.on_token(i, tok)
                    except Exception:               # noqa: BLE001
                        pass
            else:
                with self._lock:
                    self._calls.pop(seq, None)
                call._finish(result=payload if kind == "ok" else None,
                             err=payload if kind == "err" else None)

    # ----------------------------------------------------------- submits
    def submit(self, op: str, payload: Any, on_token=None,
               on_done=None) -> _Call:
        if self._closed:
            raise RuntimeError(f"replica {self.replica} is closed")
        with self._lock:
            seq = self._seq
            self._seq += 1
            call = _Call(seq, op, payload, on_token=on_token,
                         on_done=on_done)
            self._calls[seq] = call
        self._inq.put((seq, op, payload))
        return call

    def generate(self, prompts: Sequence[Any], max_new_tokens=16,
                 on_token=None, on_done=None) -> _Call:
        prompts = [np.asarray(p).tolist() if isinstance(p, np.ndarray)
                   else p for p in prompts]
        return self.submit("generate", (prompts, max_new_tokens),
                           on_token=on_token, on_done=on_done)

    # --------------------------------------------------------- telemetry
    def _load(self) -> Tuple[int, int]:
        with self._lock:
            gen = [c for c in self._calls.values() if c.op == "generate"]
        active = sum(1 for c in gen if c.started)
        return len(gen) - active, active

    def stats(self) -> ReplicaStats:
        """Latest telemetry, preferring the LIVE engine (thread
        transport) so mid-generate pool pressure is visible; the spawn
        transport sees the last tick.  ``queued``/``active`` always come
        from this side's books."""
        st = None
        if self.engine is not None:
            try:
                st = ReplicaStats.from_engine(self.engine, self.replica,
                                              self.role)
            except Exception:                       # noqa: BLE001
                st = None
        if st is None:
            base = self.last_stats or self.default_stats
            st = (replace(base) if base is not None
                  else ReplicaStats(replica=self.replica, role=self.role))
        st.replica = self.replica
        st.role = self.role
        st.queued, st.active = self._load()
        return st

    def trace(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """This replica's Chrome trace events (pid = replica id).  The
        thread transport reads the live tracer; the spawn transport
        round-trips a ``trace`` instruction.  Engines without a tracer
        (stubs) yield []."""
        if self.engine is not None:
            tracer = getattr(self.engine, "tracer", None)
            return tracer.chrome_events(last) if tracer is not None else []
        try:
            return self.submit("trace", last).wait() or []
        except Exception:                           # noqa: BLE001
            return []

    # -------------------------------------------------------------- drain
    def pending(self) -> List[_Call]:
        """Generate calls enqueued but not yet begun by the worker."""
        with self._lock:
            return [c for c in self._calls.values()
                    if c.op == "generate" and not c.started
                    and not c.done()]

    def cancel_pending(self) -> List[_Call]:
        """Abandon every not-yet-begun generate call (drain/requeue).
        Returns the abandoned calls so the router can resubmit their
        payloads elsewhere; late replies from this replica are ignored."""
        cancelled = []
        with self._lock:
            for seq, call in list(self._calls.items()):
                if call.op == "generate" and not call.started \
                        and not call.done():
                    del self._calls[seq]
                    cancelled.append(call)
        return cancelled

    def close(self, timeout: float = 10.0) -> None:
        if self._closed:
            return
        try:
            self.submit("shutdown", None)
        except RuntimeError:
            pass
        self._closed = True
        self._worker.join(timeout)
        if self.transport == "proc" and self._worker.is_alive():
            self._worker.terminate()

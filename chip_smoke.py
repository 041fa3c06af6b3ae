"""Serve stablelm-1.6b at its published widths on a TPU and check the tokens.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four one-chip replicas vs one engine

One chip: the engine is built as ``repro.launch.serve`` builds it
(``build_engine``: float32, ``batching="auto"``, chunked prefill) with
weights drawn from ``--seed``, and it serves four requests of 512-1536
prompt tokens and 32 new tokens each under ``max_len=2048``.  It passes
when ``auto`` resolved to the paged engine, the compiled decode step
holds the Pallas kernel (``tpu_custom_call``), every request got its 32
tokens, and each request's first greedy token is the argmax of
``Model.forward`` over the same prompt (or within 1e-3 relative of it).

``--chips 4``: four thread-transport replicas, each on its own chip,
behind the ``free_pages`` router serve eight requests; their greedy
tokens must equal one engine's on chip 0, and each replica's weights and
page pool must sit on a device of its own.

Both matmul paths run at ``highest`` precision, so the comparisons test
paging, chunking and the kernel rather than bf16 matmul passes.  The
last line of standard output is one JSON object naming the device; it is
printed only when every check passed.  Without a TPU, or outside a
checkout of this repository, the script exits nonzero and prints none.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ARCH = "stablelm-1.6b"
MAX_LEN = 2048
NEW_TOKENS = 32
PROMPT_RANGE = (512, 1536)


class SmokeFailure(RuntimeError):
    """A check failed: the script exits nonzero without a result line."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def prompt_lengths(page_tokens: int, n: int) -> list:
    """``n`` prompt lengths in ``PROMPT_RANGE``, whole multiples of the
    planned page where any fit (one prefill chunk bucket), cycling."""
    lo, hi = PROMPT_RANGE
    fits = [k * page_tokens for k in range(1, hi // page_tokens + 1)
            if k * page_tokens >= lo]
    fits = fits or [lo, hi]
    return [fits[i % len(fits)] for i in range(n)]


class CompileClock:
    """Seconds the backend spent compiling (or reading back from the
    persistent cache), from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def decode_step_text(engine) -> str:
    """The optimized HLO of the engine's compiled paged decode step."""
    import jax
    import jax.numpy as jnp

    from repro.serve.pages import init_paged_cache

    (key, steps), = engine._paged_steps_cache.items()
    n_slots, pages_total, pages_per_slot, page_tokens, enc_max = key
    cache = jax.eval_shape(lambda: init_paged_cache(
        engine.cfg, engine.model, n_slots, pages_total, page_tokens,
        pages_per_slot, engine.dtype, enc_len=enc_max))
    batch = {"tokens": jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)}
    return steps.decode.lower(engine.params, cache, batch).compile().as_text()


def first_token_check(engine, prompts, outs) -> None:
    """Each request's first engine token against ``Model.forward`` on the
    same prompt (one jit bucket: prompts padded at the end, which causal
    attention never looks at)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    width = max(len(p) for p in prompts)
    fwd = jax.jit(lambda params, toks: engine.model.forward(
        params, {"tokens": toks}, dtype=jnp.float32)[0][0])
    for i, (p, out) in enumerate(zip(prompts, outs)):
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(p)] = p
        logits = np.asarray(fwd(engine.params, jnp.asarray(toks))[len(p) - 1])
        best = int(np.argmax(logits))
        tok = int(out[0])
        top, got = float(logits[best]), float(logits[tok])
        rel = abs(top - got) / max(abs(top), 1e-30)
        log(f"first-token check request {i}: engine={tok} reference={best} "
            f"rel_gap={rel:.3e}")
        check(tok == best or rel <= 1e-3,
              f"request {i}: first token {tok} is not the reference argmax "
              f"{best} (logit gap {rel:.3e} relative)")


def one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_model_config
    from repro.launch.serve import build_engine

    clock = CompileClock()
    cfg = get_model_config(ARCH)
    t0 = time.perf_counter()
    engine = build_engine(cfg, n_new=NEW_TOKENS, batch=4, max_len=MAX_LEN,
                          batching="auto", prefill="chunked", seed=seed)
    jax.block_until_ready(engine.params)
    setup_s = time.perf_counter() - t0
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.params))
    log(f"arch={ARCH} dtype={np.dtype(engine.dtype).name} params={n_params} "
        f"(config count {cfg.param_count()})")
    log(f"batching={engine.batching}")
    check(engine.batching == "paged",
          f"batching='auto' resolved to {engine.batching!r}, not 'paged'")
    page = engine.plan.page_plan()
    ptab = engine.plan.page_table()
    log(f"plan: page_tokens={page['page_tokens']} "
        f"pages_total={ptab['pages_total']} "
        f"pages_per_slot={ptab['pages_per_slot']} "
        f"page_vmem_bytes={page['vmem_bytes']} "
        f"vmem_limit_bytes={page['vmem_limit_bytes']}")

    rng = np.random.default_rng(seed)
    lengths = prompt_lengths(page["page_tokens"], 4)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in lengths]
    log(f"prompt lengths {lengths}, {NEW_TOKENS} new tokens each")
    t1 = time.perf_counter()
    outs = engine.generate(prompts)
    serve_s = time.perf_counter() - t1
    n_tok = sum(len(o) for o in outs)
    check(all(len(o) == NEW_TOKENS for o in outs),
          f"token counts {[len(o) for o in outs]}, wanted {NEW_TOKENS} each")
    m = engine.metrics
    log(f"served: tokens={n_tok} prefill_chunks={m['prefill_chunks']} "
        f"decode_steps={m['decode_steps']} "
        f"pool_pages={m['pages_total']} (plan bound {ptab['pages_total']})")
    log(f"seconds: setup={setup_s:.2f} first_generate={serve_s:.2f} "
        f"(compiles inside: {clock.count}, {clock.seconds:.2f}s) "
        f"tok_per_s_incl_compile={n_tok / serve_s:.1f}")

    text = decode_step_text(engine)
    has_kernel = "tpu_custom_call" in text
    log(f"decode step contains tpu_custom_call: {has_kernel}")
    check(has_kernel, "the compiled decode step holds no tpu_custom_call: "
                      "the paged kernel did not lower to Mosaic")
    first_token_check(engine, prompts, outs)
    log(f"compile seconds total={clock.seconds:.2f} over {clock.count} "
        f"compiles")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.cluster import EngineSpec, ServeCluster
    from repro.configs import get_model_config
    from repro.launch.mesh import make_device_mesh
    from repro.serve import ServeEngine, ServePolicy
    from repro.serve.engine import plan_decode

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX sees "
                             f"{len(devices)}")
    clock = CompileClock()
    cfg = get_model_config(ARCH)
    spec = EngineSpec(arch=ARCH, reduced=False, max_new_tokens=NEW_TOKENS,
                      max_slots=1, max_len=MAX_LEN, seed=seed)
    policy = ServePolicy(max_new_tokens=NEW_TOKENS, max_slots=1,
                         max_len=MAX_LEN, batching=spec.batching,
                         prefill=spec.prefill,
                         prefix_cache=spec.prefix_cache)
    plan = plan_decode(cfg, make_device_mesh(devices[0]), max_len=MAX_LEN,
                       dtype_bytes=4, cluster=4)
    check(plan.replicas() == 4, f"plan stands up {plan.replicas()} "
                                f"replicas, wanted 4")
    page_tokens = plan.page_plan()["page_tokens"]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32).tolist()
               for n in prompt_lengths(page_tokens, 8)]

    t0 = time.perf_counter()
    ref = ServeEngine(cfg, make_device_mesh(devices[0]), policy=policy,
                      seed=seed)
    want = ref.generate(prompts)
    log(f"reference engine on {devices[0]}: {sum(map(len, want))} tokens "
        f"in {time.perf_counter() - t0:.2f}s")
    del ref

    t1 = time.perf_counter()
    front = ServeCluster.from_plan(plan, spec, transport="thread",
                                   policy="free_pages")
    try:
        got = front.generate(prompts, NEW_TOKENS)
        log(f"fleet: {sum(map(len, got))} tokens in "
            f"{time.perf_counter() - t1:.2f}s (compiles so far: "
            f"{clock.count}, {clock.seconds:.2f}s)")
        homes = {}
        for rep in front.replicas:
            eng = rep.engine
            check(eng is not None, f"replica {rep.replica} never served")
            param_devs = {d for a in jax.tree.leaves(eng.params)
                          for d in a.devices()}
            pool = eng._paged_session.cache["pool"]
            pool_devs = {d for a in jax.tree.leaves(pool) for d in a.devices()}
            log(f"replica {rep.replica}: params on {sorted(map(str, param_devs))}"
                f" pool on {sorted(map(str, pool_devs))} "
                f"served {eng.metrics['tokens']} tokens")
            check(len(param_devs) == 1 and param_devs == pool_devs,
                  f"replica {rep.replica} spans {param_devs | pool_devs}")
            homes[rep.replica] = next(iter(param_devs))
        check(len(set(homes.values())) == 4,
              f"replicas share devices: {homes}")
    finally:
        front.close()
    same = [w == g for w, g in zip(want, got)]
    log(f"token-identical per request: {same}")
    check(all(same), "the fleet's tokens differ from the single engine's")
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use(chip 0)={stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica fleet phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the prompts")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] FAIL: this is not a checkout of the repository "
              f"({e})", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAIL: JAX found no TPU (platform "
              f"{dev.platform!r}); this check runs on the chip only",
              file=sys.stderr)
        return 1
    # Set for the process, not as a context: the fleet's replicas serve
    # from threads of their own, and a context is thread-local.
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

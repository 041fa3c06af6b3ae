"""Device milliseconds per run of the jitted prefill-chunk step
(``jit_prefill_chunk_fn`` in the trace's ``XLA Modules`` line), full and
partial chunks together."""

PROGRAM = "jit_prefill_chunk_fn"


def read(ctx):
    n, s = ctx.trace.programs.get(PROGRAM, (0, 0.0))
    return 1e3 * s / n if n else None

"""Device milliseconds per run of the jitted paged decode step
(``jit_decode_fn`` in the trace's ``XLA Modules`` line)."""

PROGRAM = "jit_decode_fn"


def read(ctx):
    n, s = ctx.trace.programs.get(PROGRAM, (0, 0.0))
    return 1e3 * s / n if n else None

"""Backend compilations inside the measured window (JAX's
``backend_compile_duration`` monitoring events; a read-back from the
persistent cache counts too).  Every shape is warmed in set-up, so this
reads 0; anything else puts compilation into the latencies."""


def read(ctx):
    return ctx.compiles_in_window

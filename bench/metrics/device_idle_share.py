"""Share of the traced call's wall time in which no operation ran on the
device, in percent: one minus the union of the trace's operation
intervals over the call's host-clock seconds."""


def read(ctx):
    if ctx.traced_seconds <= 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.traced_seconds)

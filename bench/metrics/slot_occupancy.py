"""Share of decode-slot steps that carried a live request, over the
traced call: the paged scheduler's ``active_slot_steps / slot_steps``
counters, in percent.  Empty and prefilling slots ride through each
decode step without producing a token."""


def read(ctx):
    steps = ctx.counters.get("slot_steps", 0)
    if not steps:
        return None
    return 100.0 * ctx.counters["active_slot_steps"] / steps

"""The whole traced call's share of the chip's bf16 peak, in percent: the
model operations its requests needed (every prompt token and every
decode row through the layers' projections and causal attention, the
output head where a token's logits are used; ``bench/flops.py``) over
the call's host-clock seconds times the peak."""


def read(ctx):
    pre, dec = ctx.traced_work()
    flops = pre.model_flops + dec.model_flops
    if ctx.traced_seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (ctx.traced_seconds * ctx.peaks["bf16_flops"])

"""The paged attention kernel's share of its roofline inside prefill
chunks, in percent: the least time the chip needs for the traced call's
chunks (attention operations of every row over peak FLOP/s, or each
chunk's K/V context read once over HBM bandwidth, whichever is larger;
``bench/flops.py``) over the kernel's device seconds inside
``jit_prefill_chunk_fn`` runs."""

PROGRAM = "jit_prefill_chunk_fn"


def read(ctx):
    kernel_s = ctx.trace.kernel_s.get(PROGRAM, 0.0)
    work, _ = ctx.traced_work()
    if kernel_s <= 0 or not work.rows:
        return None
    least = max(work.attn_flops / ctx.peaks["bf16_flops"],
                work.attn_bytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s

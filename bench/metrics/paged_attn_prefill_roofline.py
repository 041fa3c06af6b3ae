"""The paged attention kernel's share of its roofline inside prefill
chunks, in percent: the least time the chip needs for the traced call's
chunks (attention operations of every row over peak FLOP/s, or each
chunk's K/V context read once over HBM bandwidth, whichever is larger;
the family's ``prefill_chunk``) over the device seconds of the Mosaic
kernel ``paged_prefill_attention`` inside ``jit_prefill_chunk_fn`` runs.
Another kernel of the same program is not counted; a program that runs
kernels but not this one raises."""

PROGRAM = "jit_prefill_chunk_fn"
KERNEL = "paged_prefill_attention"


def read(ctx):
    kernel_s = ctx.trace.kernel_seconds(PROGRAM, KERNEL)
    work, _ = ctx.traced_work()
    if kernel_s <= 0 or not work.rows:
        return None
    least = max(work.attn_flops / ctx.peaks["bf16_flops"],
                work.attn_bytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s

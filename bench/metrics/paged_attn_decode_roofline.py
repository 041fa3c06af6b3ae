"""The paged attention kernel's share of its roofline inside decode steps,
in percent: the least time the chip needs for the decode rows of the
traced call (the larger of their attention operations over peak FLOP/s
and the K/V of each row's real context, read once, over HBM bandwidth;
the family's ``decode_row``) over the device seconds of the Mosaic kernel
``paged_attention`` inside ``jit_decode_fn`` runs.  Another kernel of
the same program is not counted; a program that runs kernels but not
this one raises."""

PROGRAM = "jit_decode_fn"
KERNEL = "paged_attention"


def read(ctx):
    kernel_s = ctx.trace.kernel_seconds(PROGRAM, KERNEL)
    _, work = ctx.traced_work()
    if kernel_s <= 0 or not work.rows:
        return None
    least = max(work.attn_flops / ctx.peaks["bf16_flops"],
                work.attn_bytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s

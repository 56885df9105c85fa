"""Share of its roofline that the ``chol_gram`` kernel reached: the least
time the chip could take for each wave's rank-n update of the real samples
(no L·Lᵀ rebuild; bench.work) over the summed device time of the kernel's
events."""
from bench import trace_reduce, work

# the kernel's instruction name in a TPU trace (read from a chip trace)
MATCH = ("chol_gram_pallas",)


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = trace_reduce.op_seconds(ctx.trace, MATCH)
    if kernel_s <= 0:
        return None
    flops = sum(work.stats_flops(u.samples, ctx.d, ctx.C) for u in ctx.units)
    nbytes = sum(work.rank_update_bytes(u.samples, ctx.d, ctx.C) for u in ctx.units)
    least = max(flops / ctx.peak.bf16_flops, nbytes / ctx.peak.hbm_bytes_per_s)
    return 100.0 * least / kernel_s

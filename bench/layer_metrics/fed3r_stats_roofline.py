"""Share of its roofline that the ``fed3r_stats`` kernel reached: the least
time the chip could take for the statistics of the real samples
(max of least FLOPs over peak FLOP/s and least bytes over peak bytes/s,
bench.work) over the summed device time of the kernel's events."""
from bench import trace_reduce, work

# the kernel's instruction name in a TPU trace (read from a chip trace)
MATCH = ("fed3r_stats_pallas",)


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = trace_reduce.op_seconds(ctx.trace, MATCH)
    if kernel_s <= 0:
        return None
    flops = sum(work.stats_flops(u.samples, ctx.d, ctx.C) for u in ctx.units)
    nbytes = sum(work.stats_bytes(u.samples, ctx.d, ctx.C) for u in ctx.units)
    least = max(flops / ctx.peak.bf16_flops, nbytes / ctx.peak.hbm_bytes_per_s)
    return 100.0 * least / kernel_s

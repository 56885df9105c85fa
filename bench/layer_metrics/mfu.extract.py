"""The least work of the window's passes over a backbone (the forward over
the real tokens, the backbone module's ``flops``, plus the statistics of
the real samples and each pass's solve, bench.work) per second of the
traced window, over the chips' published bf16 peak."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    flops = sum(u.flops for u in ctx.units)
    return 100.0 * flops / ctx.trace_window_s / (ctx.chips * ctx.peak.bf16_flops)

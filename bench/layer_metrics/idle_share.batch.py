"""Share of the traced window in which no op ran on the device, mean over
the chips (device trace)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)

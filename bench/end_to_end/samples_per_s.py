"""Real client samples folded in the window over the window's wall time.

Padding rows do not count.  A unit counts once its answer is ready: a
batch pass after its solve, a stream wave once its refreshed W is ready."""


def read(ctx):
    return sum(u.samples for u in ctx.units) / ctx.window_s

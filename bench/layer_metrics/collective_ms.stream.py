"""Device time of the statistics' all-reduce between the chips, per wave:
the summed time of the all-reduce events inside the traced window, mean
over the devices, over the window's waves (device trace)."""
from bench import trace_reduce

# The all-reduce's instruction name in a four-chip TPU v5e trace of the psum
# stream path, read from bench/tests/data/stream4.tiny.xplane.pb.gz (one
# ``%all-reduce.N`` a wave on each chip); an asynchronous all-reduce's
# ``-start``/``-done`` halves share the stem's prefix.
PREFIX = "all-reduce"


def read(ctx):
    if ctx.trace is None or ctx.trace.n_devices < 2 or not ctx.units:
        return None
    lo, hi = trace_reduce.window(ctx.trace)
    ns = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in ctx.trace.ops
             if trace_reduce.op_stem(e).startswith(PREFIX) and e.end_ns > lo and e.start_ns < hi)
    if ns <= 0:
        return None
    return 1e-6 * ns / ctx.trace.n_devices / len(ctx.units)

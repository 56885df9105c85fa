"""Share of the traced window in which no op ran on the device while the
host was inside the program's ``absorb`` span, mean over the chips: the
part of ``idle_share.stream`` that the engine's own host path (placement,
launch) holds the device idle for (device trace, program spans).

The profiler can draw a v5e's ops a millisecond or two off the host's
events: one stream trace put every wave's program before its own launch.
So each device's ops are first shifted by the least amount that makes the
trace causal (:func:`device_shift`)."""
from bench import program_spans, trace_reduce

# a wave's device work is one burst of ops across gaps shorter than this
BURST_GAP_NS = 100_000.0


def _overlap_ns(a, b) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_shift(trace, spans, busy) -> float:
    """What to add to a device's op times so that each wave's device work
    (its longest bursts of ``busy``, one a wave, in order) starts after
    the wave's ``absorb/launch`` starts and ends before its ``block`` ends:
    0 where the trace is causal as drawn or the bursts do not match the
    waves, else the least such shift."""
    launches = [s for s in spans if s[0] == "absorb/launch"]
    blocks = [p for p in trace.phases if p.name == "block"]
    bursts = []
    for a, b in busy:
        if bursts and a - bursts[-1][1] < BURST_GAP_NS:
            bursts[-1][1] = max(bursts[-1][1], b)
        else:
            bursts.append([a, b])
    if not launches or len(blocks) != len(launches) or len(bursts) < len(launches):
        return 0.0
    work = sorted(sorted(bursts, key=lambda w: w[0] - w[1])[:len(launches)])
    lo = max(s[1] - w[0] for s, w in zip(launches, work))
    hi = min(p.end_ns - w[1] for p, w in zip(blocks, work))
    return min(max(0.0, lo), hi) if lo <= hi else 0.0


def read(ctx):
    spans = program_spans.spans(ctx.trace)
    if not spans or not ctx.trace.ops:
        return None
    lo, hi = trace_reduce.window(ctx.trace)
    host = trace_reduce.union((max(a, lo), min(b, hi)) for s, a, b, _ in spans
                              if s == "absorb" and min(b, hi) > max(a, lo))
    if not host:
        return None
    held = sum(b - a for a, b in host)
    idle = []
    for d in sorted({e.device for e in ctx.trace.ops}):
        busy = trace_reduce.union((e.start_ns, e.end_ns) for e in ctx.trace.ops
                                  if e.device == d)
        x = device_shift(ctx.trace, spans, busy)
        idle.append(held - _overlap_ns(host, [(a + x, b + x) for a, b in busy]))
    return 100.0 * sum(idle) / len(idle) / (hi - lo)

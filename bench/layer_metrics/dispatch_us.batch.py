"""Host time per ``AccumulationEngine.accumulate`` call, from the
benchmark's span around the un-blocked call (host clock)."""


def read(ctx):
    spans = ctx.spans.get("round") or []
    return 1e6 * sum(spans) / len(spans) if spans else None

"""Share of the token positions the backbone's forward computed in the
window that were padding: 100·(1 − real/computed), from the program's
``extract_tokens{kind=real|computed}`` counters, which
``AccumulationEngine.accumulate`` adds for each call over token inputs
(``ctx.telemetry``, reset as the window opens).  Row padding (the packed
capacity) and length padding (to the longest sequence) both count."""


def _count(snapshot, kind):
    for c in snapshot.get("counters", []):
        if c["name"] == "extract_tokens" and c["labels"].get("kind") == kind:
            return c["value"]
    return None


def read(ctx):
    if ctx.telemetry is None:
        return None
    real, computed = _count(ctx.telemetry, "real"), _count(ctx.telemetry, "computed")
    if not computed or real is None:
        return None
    return 100.0 * (1.0 - real / computed)

"""The program's own spans of a traced window, on the trace's clock.

While a profiler trace runs, ``repro.federated.telemetry`` keeps each
finished span in its registry's ``span_log`` as ``(stage, start_ns,
end_ns, cpu_ns)``, its start on the host clock the profiler stamps its
events with.  The trace file holds its events relative to the start of its
session, so the two differ by one offset.

The drivers open one benchmark phase around each engine fold call: a
``round`` around ``accumulate``, a ``wave`` around ``absorb`` (then a
``block`` inside the wave).  The window's calls are the log's last as many
fold spans as the trace has such phases, the i-th inside the i-th phase:
the log keeps only traced spans, and the trace ends with the window.  No
span starts before its phase opens, and none ends after its phase closes
or after a phase nested in it opens: the offset lies between the two
bounds this gives, and the midpoint is taken.  It is off by at most half
their distance (``offset_bounds``), a few microseconds on either side.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from bench import trace_reduce

# benchmark phase → the engine call it holds (bench/drivers)
FOLD_PHASES = {"round": "accumulate", "wave": "absorb"}


def _span_log():
    from repro.federated.telemetry import get_telemetry

    return getattr(get_telemetry(), "span_log", None)  # None: a program without it


def _pairs(trace: trace_reduce.Trace, log) -> Optional[List[Tuple[int, tuple]]]:
    """Each fold phase's index in ``trace.phases`` with its span record."""
    at = [i for i, p in enumerate(trace.phases) if p.name in FOLD_PHASES]
    calls = [r for r in log if r[0] in FOLD_PHASES.values()][-len(at):] if at else []
    if not at or len(calls) < len(at):
        return None
    pairs = list(zip(at, calls))
    if any(FOLD_PHASES[trace.phases[i].name] != r[0] for i, r in pairs):
        return None
    return pairs


def _closes(phases, i: int, after: float) -> float:
    """When phase ``i`` closes, or a phase nested in it opens at or after
    ``after``, whichever comes first."""
    bound, j = phases[i].end_ns, i + 1
    while j < len(phases) and phases[j].start_ns <= phases[i].end_ns:
        if phases[j].start_ns >= after:
            bound = min(bound, phases[j].start_ns)
        j += 1
    return bound


def offset_bounds(trace: trace_reduce.Trace, pairs) -> Optional[Tuple[int, int]]:
    """The least and the greatest offset from the log's clock to the trace's
    that keep every fold span inside its phase; ``None`` where there is
    none, so the log does not match the trace's phases."""
    phases = trace.phases
    lo = max(int(phases[i].start_ns) - r[1] for i, r in pairs)
    hi = min(int(_closes(phases, i, r[2] + lo)) - r[2] for i, r in pairs)
    return (lo, hi) if hi >= lo else None


def spans(trace: Optional[trace_reduce.Trace]) -> Optional[List[tuple]]:
    """The log's spans of the traced window as ``(stage, start_ns, end_ns,
    cpu_ns)``, start and end on the trace's clock; ``None`` where there is
    no trace, the program keeps no span log, or its log does not match the
    trace's fold phases."""
    log = None if trace is None else _span_log()
    pairs = None if log is None else _pairs(trace, log)
    bounds = None if pairs is None else offset_bounds(trace, pairs)
    if bounds is None:
        return None
    offset = (bounds[0] + bounds[1]) // 2
    lo, hi = trace_reduce.window(trace)
    return [(r[0], float(r[1] + offset), float(r[2] + offset), r[3]) for r in log
            if lo <= r[1] + offset <= hi]


def fold_spans(trace: Optional[trace_reduce.Trace]) -> Optional[List[tuple]]:
    """The window's fold calls (``accumulate``, ``absorb``) among
    :func:`spans`."""
    found = spans(trace)
    return None if found is None else [s for s in found if s[0] in FOLD_PHASES.values()]

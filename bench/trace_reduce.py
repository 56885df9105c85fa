"""From a profiler trace (``.xplane.pb``) to device intervals, events per
op and host annotations, on one clock.

Device planes are ``/device:<platform>:<n>``; their op events are on the
``XLA Ops`` line.  Host annotations are the benchmark's own
``jax.profiler.TraceAnnotation`` phases on the host plane's threads.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
PHASES = ("window", "pass", "round", "solve", "block", "wave")


class OpEvent(NamedTuple):
    device: int
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


class Trace(NamedTuple):
    ops: List[OpEvent]
    phases: List[Span]
    n_devices: int


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _device_index(plane_name: str) -> Optional[int]:
    if not plane_name.startswith("/device:"):
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: List[OpEvent] = []
    phases: List[Span] = []
    devices = set()
    for plane in data.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.add(dev)
                for ev in line.events:
                    ops.append(OpEvent(dev, ev.name, float(ev.start_ns),
                                       float(ev.duration_ns), dict(ev.stats)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PHASES:
                        start = float(ev.start_ns)
                        phases.append(Span(ev.name, start, start + float(ev.duration_ns)))
    return Trace(ops=ops, phases=sorted(phases, key=lambda s: s.start_ns),
                 n_devices=len(devices))


def window(trace: Trace) -> Tuple[float, float]:
    """The traced window: the benchmark's ``window`` phase."""
    spans = [s for s in trace.phases if s.name == "window"]
    if not spans:
        raise ValueError("the trace holds no 'window' phase")
    return spans[0].start_ns, spans[-1].end_ns


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def busy_ns(trace: Trace, device: int, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which some op ran on ``device``."""
    spans = union(_clip(((e.start_ns, e.end_ns) for e in trace.ops if e.device == device),
                        lo, hi))
    return sum(b - a for a, b in spans)


def mean_busy_s(trace: Trace) -> float:
    lo, hi = window(trace)
    devices = sorted({e.device for e in trace.ops})
    if not devices:
        return 0.0
    return sum(busy_ns(trace, d, lo, hi) for d in devices) / len(devices) / 1e9


def op_stem(e: OpEvent) -> str:
    """The HLO instruction's name without its ``%`` and numeric suffix.  A
    TPU trace names each op event by its instruction's text, so
    ``%fed3r_stats_pallas.6 = (...) custom-call(...)`` gives
    ``fed3r_stats_pallas`` and ``%copy-done = ...`` gives ``copy-done``."""
    head = e.name.split(" ", 1)[0].lstrip("%")
    stem, dot, tail = head.rpartition(".")
    return stem if dot and tail.isdigit() else head


def op_seconds(trace: Trace, stems: Sequence[str], device: Optional[int] = None) -> float:
    """Summed device time, inside the window, of the ops whose instruction
    name (:func:`op_stem`) is one of ``stems``.  An op that only takes such
    an op's output as an operand does not count."""
    lo, hi = window(trace)
    want = set(stems)
    total = 0.0
    for e in trace.ops:
        if device is not None and e.device != device:
            continue
        if op_stem(e) in want:
            total += sum(b - a for a, b in _clip([(e.start_ns, e.end_ns)], lo, hi))
    return total / 1e9


def op_label(e: OpEvent) -> str:
    """A stable, readable name for an op: its instruction name without the
    numeric suffix (``fusion.12`` → ``fusion``) unless the trace names it
    better."""
    for key in ("tf_op", "long_name"):
        v = e.stats.get(key)
        if isinstance(v, str) and v:
            return v.split(" = ")[0][:120]
    return op_stem(e)


def top_ops(trace: Trace, k: int = 10, device: int = 0) -> List[List]:
    lo, hi = window(trace)
    acc: Dict[str, float] = {}
    for e in trace.ops:
        if e.device != device:
            continue
        t = sum(b - a for a, b in _clip([(e.start_ns, e.end_ns)], lo, hi))
        if t > 0:
            label = op_label(e)
            acc[label] = acc.get(label, 0.0) + t / 1e9
    return [[n, s] for n, s in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10, device: int = 0) -> List[List]:
    """The ``k`` longest idle gaps on ``device`` inside the window, each
    labelled by the innermost benchmark phase the host was in at its middle."""
    lo, hi = window(trace)
    busy = union(_clip(((e.start_ns, e.end_ns) for e in trace.ops if e.device == device),
                       lo, hi))
    gaps, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (a + b)
        inner = [s for s in trace.phases if s.start_ns <= mid <= s.end_ns]
        label = min(inner, key=lambda s: s.end_ns - s.start_ns).name if inner else "outside"
        out.append([label, (b - a) / 1e9])
    return out

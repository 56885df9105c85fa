"""Engine paths a traffic file can name under ``driver``: ``bench.drivers.<name>``.

A driver packs and places its cell's traffic in set-up, runs one unit of
work per :meth:`step` (blocked until its answer is ready), keeps the
answers that the reference checks, and counts the work of each unit.
"""
from __future__ import annotations

from typing import NamedTuple, Optional


class Unit(NamedTuple):
    samples: int  # real client samples folded by this unit
    flops: float  # least work of the unit (bench.work)
    latency_s: Optional[float]  # the unit's own timed section, where it has one

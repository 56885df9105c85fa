"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error."""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float  # FLOP/s per chip, dense bf16
    hbm_bytes_per_s: float  # bytes/s per chip
    hbm_bytes: float  # bytes of HBM per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

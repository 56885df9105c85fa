"""The least work each stage must do, from the shapes of the real samples.

No padding, half of the symmetric A, no L·Lᵀ rebuild: a change to padding,
tiling or algorithm can then never push a share of the roofline above 100%.
Counts are FLOPs (two per multiply-add) and bytes of fp32 data.
"""
from __future__ import annotations

F32 = 4


def stats_flops(n: int, d: int, C: int) -> float:
    """A = ZᵀZ (its d(d+1)/2 distinct entries) and b = ZᵀY over n samples
    (Fed3R App. E: ½·n·d(d+1) + n·d·C multiply-adds)."""
    return 2.0 * (0.5 * n * d * (d + 1) + n * d * C)


def stats_bytes(n: int, d: int, C: int) -> float:
    """Read each sample's features and label once; write half of A and b."""
    return float(F32 * (n * d + n + d * (d + 1) // 2 + d * C))


def solve_flops(d: int, C: int) -> float:
    """Cholesky of the d×d system and two triangular solves against C
    columns: d³/3 + 2·d²·C multiply-adds."""
    return 2.0 * (d**3 / 3.0 + 2.0 * d * d * C)


def rank_update_bytes(n: int, d: int, C: int) -> float:
    """Read the wave's samples and the factor's triangle; write the updated
    triangle and the wave's class sums."""
    return float(F32 * (n * d + n + d * (d + 1) + d * C))

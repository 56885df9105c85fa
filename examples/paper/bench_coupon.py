"""Paper Table 7 / App. I: Batch Coupon Collector — rounds to sample a given
fraction of distinct clients with replacement (1000 trials, as the paper)."""
from __future__ import annotations

import numpy as np

from common import emit

SETTINGS = [  # (dataset, K, kappa) — paper Table 7 rows
    ("landmarks", 1262, 10),
    ("landmarks", 1262, 20),
    ("landmarks", 1262, 50),
    ("inaturalist", 9275, 10),
    ("cifar100", 100, 10),
]
FRACTIONS = (0.25, 0.50, 0.75, 1.00)
TRIALS = 200  # paper uses 1000; CPU-budgeted


def simulate(K: int, kappa: int, rng: np.random.Generator) -> dict:
    seen = np.zeros(K, bool)
    hits = {}
    r = 0
    while not seen.all():
        r += 1
        seen[rng.choice(K, size=kappa, replace=False)] = True
        frac = seen.mean()
        for f in FRACTIONS:
            if f not in hits and frac >= f:
                hits[f] = r
    return hits


def main() -> list:
    rows = []
    rng = np.random.default_rng(0)
    for name, K, kappa in SETTINGS:
        all_hits = [simulate(K, kappa, rng) for _ in range(TRIALS)]
        parts = []
        for f in FRACTIONS:
            vals = np.asarray([h[f] for h in all_hits])
            parts.append(f"p{int(f*100)}={vals.mean():.0f}±{vals.std():.0f}")
        emit(f"table7_{name}_K{K}_k{kappa}", " ".join(parts))
        rows.append((name, K, kappa, parts))
    return rows


if __name__ == "__main__":
    main()

"""Paper Fig. 1 / Fig. 9: FED3R(-RF) invariance to the federated split.

Four different partitions of the same dataset (different client counts and
heterogeneity levels) must converge to numerically identical accuracy —
and equal the centralized RR solution.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from common import emit, f3_cfg, fed_cfg, landmarks_like
from repro.core import fed3r
from repro.federated import run_fed3r

SPLITS = [(200, 0.0), (100, 0.0), (400, 0.0), (200, 100.0)]


def main() -> list:
    fed, test = landmarks_like()
    rows = []

    # centralized reference
    cen = fed3r.solve(
        fed3r.client_stats(jnp.asarray(fed.features), jnp.asarray(fed.labels),
                           fed.n_classes),
        0.01,
    )
    acc_cen = float(fed3r.accuracy(cen, test.features, test.labels))

    for use_rf in (False, True):
        accs = []
        for n_cl, alpha in SPLITS:
            fed_s = fed.repartition(np.random.default_rng(n_cl), n_cl, alpha)
            f3 = f3_cfg(n_random_features=1024 if use_rf else 0, rff_sigma=50.0)
            _, _, hist = run_fed3r(
                fed_s, test.features, test.labels, f3,
                fed_cfg(n_clients=n_cl, n_rounds=1000), eval_every=10_000,
            )
            accs.append(hist.accuracy[-1])
        name = "fig1_invariance_" + ("fed3r_rf" if use_rf else "fed3r")
        spread = max(accs) - min(accs)
        derived = (
            f"acc={accs[0]:.4f} spread={spread:.2e}"
            + ("" if use_rf else f" centralized={acc_cen:.4f} gap={abs(accs[0]-acc_cen):.2e}")
        )
        emit(name, derived)
        rows.append((name, accs, spread))
    return rows


if __name__ == "__main__":
    main()

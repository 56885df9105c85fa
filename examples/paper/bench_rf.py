"""Paper Fig. 8: random-feature count sweep vs the exact-KRR ceiling."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from common import RF_LAMBDA, RF_SIGMA, emit, landmarks_like
from repro.core import fed3r
from repro.core.random_features import rbf_kernel, rff_init, rff_map

SIGMA = RF_SIGMA
LAM = RF_LAMBDA


def krr_exact(f_tr, y_tr, f_te, C):
    """Exact kernel ridge regression on a subset (the paper's upper bound)."""
    K = rbf_kernel(f_tr, f_tr, SIGMA)
    Y = jax.nn.one_hot(y_tr, C)
    alpha = jnp.linalg.solve(K + LAM * jnp.eye(K.shape[0]), Y)
    K_te = rbf_kernel(f_te, f_tr, SIGMA)
    return jnp.argmax(K_te @ alpha, axis=-1)


def main() -> list:
    fed, test = landmarks_like(nonlinear=True)
    C = fed.n_classes
    sub = 3000  # KRR is O(n²) memory: subset ceiling, as in the paper App. F
    f_tr = jnp.asarray(fed.features[:sub])
    y_tr = jnp.asarray(fed.labels[:sub])
    f_te = jnp.asarray(np.asarray(test.features))
    rows = []

    pred = krr_exact(f_tr, y_tr, f_te, C)
    krr_acc = float(jnp.mean((pred == test.labels).astype(jnp.float32)))
    emit("fig8_krr_exact_subset", f"acc={krr_acc:.4f} n={sub}")

    accs = []
    for D_rf in (128, 512, 2048, 8192):
        p = rff_init(jax.random.PRNGKey(0), f_tr.shape[1], D_rf, SIGMA)
        W = fed3r.solve(
            fed3r.client_stats(rff_map(p, f_tr), y_tr, C), LAM
        )
        acc = float(fed3r.accuracy(W, rff_map(p, f_te), test.labels))
        accs.append(acc)
        emit(f"fig8_rr_rf_{D_rf}", f"acc={acc:.4f} gap_to_krr={krr_acc-acc:+.4f}")
        rows.append((D_rf, acc))
    # monotone improvement toward the KRR ceiling
    emit("fig8_monotonicity",
         f"improving={bool(accs[0] <= accs[-1])} final_gap={krr_acc-accs[-1]:+.4f}")
    return rows


if __name__ == "__main__":
    main()

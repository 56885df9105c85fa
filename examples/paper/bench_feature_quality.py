"""Paper Table 3: feature-extractor quality measured with the RR probe.

After each FT strategy, re-fit RR on the (fine-tuned) feature map and compare
softmax accuracy vs RR-probe accuracy.  The paper's finding: FED3R-initialized
FT (esp. FT-FEAT) yields more linearly-separable features.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from common import emit, f3_cfg, fed_cfg, landmarks_like
from repro.core import fed3r
from repro.federated import run_fed3r_ft

ROUNDS = 60


def main() -> list:
    fed, test = landmarks_like()
    C = fed.n_classes
    rows = []
    for strategy, use_init in [("full", False), ("full", True), ("feat", True)]:
        cfg = fed_cfg(algorithm="fedavg", n_rounds=ROUNDS)
        params, info = run_fed3r_ft(
            fed, test.features, test.labels, f3_cfg(), cfg,
            strategy=strategy, use_fed3r_init=use_init, eval_every=ROUNDS,
        )
        softmax_acc = info["ft_history"].accuracy[-1]
        # RR probe on the fine-tuned feature map h = x·M
        M = np.asarray(params["M"])
        tr_h = jnp.asarray(fed.features @ M)
        te_h = jnp.asarray(np.asarray(test.features) @ M)
        W = fed3r.solve(fed3r.client_stats(tr_h, jnp.asarray(fed.labels), C), 0.01)
        rr_acc = float(fed3r.accuracy(W, te_h, test.labels))
        tag = f"table3_{strategy}_{'fed3r' if use_init else 'rand'}_init"
        emit(tag, f"softmax={softmax_acc:.4f} rr_probe={rr_acc:.4f}")
        rows.append((tag, softmax_acc, rr_acc))
    return rows


if __name__ == "__main__":
    main()

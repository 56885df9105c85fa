"""Paper Table 1 / Table 6: FED3R family vs FedNCM final accuracy."""
from __future__ import annotations

from common import RF_LAMBDA, RF_SIGMA, emit, f3_cfg, fed_cfg, landmarks_like
from repro.federated import run_fed3r, run_fedncm


def main() -> list:
    fed, test = landmarks_like(nonlinear=True)  # NCM's weakness shows off-linear
    rows = []
    results = {}
    for name, rf in [("fed3r", 0), ("fed3r_rf_1k", 1024), ("fed3r_rf_4k", 4096)]:
        f3 = f3_cfg(n_random_features=rf, rff_sigma=RF_SIGMA,
                    ridge_lambda=RF_LAMBDA if rf else 0.01)
        _, _, h = run_fed3r(fed, test.features, test.labels, f3,
                            fed_cfg(n_rounds=1000), eval_every=10_000)
        results[name] = h.accuracy[-1]
        emit(f"table1_{name}", f"final={h.accuracy[-1]:.4f}")
        rows.append((name, h.accuracy[-1]))

    _, hn = run_fedncm(fed, test.features, test.labels, fed_cfg())
    results["fedncm"] = hn.accuracy[-1]
    emit("table1_fedncm", f"final={hn.accuracy[-1]:.4f}")
    rows.append(("fedncm", hn.accuracy[-1]))

    margin = results["fed3r_rf_4k"] - results["fedncm"]
    emit("table1_rf_vs_ncm_margin", f"margin={margin:.4f}")
    return rows


if __name__ == "__main__":
    main()

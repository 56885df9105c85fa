"""Paper Table 2 / Fig. 4/5/11: FED3R+FT strategies vs no-FED3R-init FT.

Grid: {FedAvg, FedAvgM} × {FT, FT-LP, FT-FEAT} × {FED3R init, random init}.
The paper's headline orderings to reproduce directionally:
  * FED3R init ≥ random init at equal budget;
  * FT-FEAT (classifier fixed) is the most stable under heterogeneity.
"""
from __future__ import annotations

from common import emit, f3_cfg, fed_cfg, landmarks_like
from repro.federated import run_fed3r_ft

ROUNDS = 60


def main() -> list:
    fed, test = landmarks_like()
    rows = []
    for alg, smom in [("fedavg", 0.0), ("fedavgm", 0.9)]:
        for strategy in ("full", "lp", "feat"):
            for use_init in (True, False):
                if strategy == "feat" and not use_init:
                    # paper reports FT-FEAT only with the FED3R classifier
                    continue
                cfg = fed_cfg(algorithm=alg, n_rounds=ROUNDS,
                              server_momentum=smom)
                _, info = run_fed3r_ft(
                    fed, test.features, test.labels, f3_cfg(), cfg,
                    strategy=strategy, use_fed3r_init=use_init,
                    eval_every=10,
                )
                h = info["ft_history"]
                tag = (
                    f"table2_{alg}_ft{strategy}_"
                    + ("fed3r_init" if use_init else "rand_init")
                )
                extra = (
                    f" fed3r_rounds={info['fed3r_rounds']}"
                    f" temp={info.get('temperature', '-')}"
                    if use_init else ""
                )
                emit(tag, f"final={h.accuracy[-1]:.4f}{extra}")
                rows.append((tag, h.accuracy[-1]))
    return rows


if __name__ == "__main__":
    main()

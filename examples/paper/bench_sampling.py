"""Paper Fig. 3: participation rates and sampling with/without replacement.

FED3R's final accuracy is invariant to the sampling rate by construction;
with-replacement sampling merely delays full coverage (worst case analysed
by the Batch Coupon Collector, see bench_coupon).
"""
from __future__ import annotations

from common import emit, f3_cfg, fed_cfg, landmarks_like
from repro.federated import run_fed3r


def main() -> list:
    fed, test = landmarks_like()
    rows = []
    finals = []
    for per_round in (5, 10, 20):
        for repl in (False, True):
            cfg = fed_cfg(clients_per_round=per_round, n_rounds=400,
                          sample_with_replacement=repl)
            _, _, h = run_fed3r(fed, test.features, test.labels, f3_cfg(),
                                cfg, eval_every=5)
            tag = f"fig3_fed3r_{per_round}clr_{'with' if repl else 'wo'}_repl"
            rounds_done = h.rounds[-1]
            emit(tag,
                 f"final={h.accuracy[-1]:.4f} rounds={rounds_done} "
                 f"clients_seen={h.clients_seen[-1]}")
            rows.append((tag, h.accuracy[-1], rounds_done))
            if not repl:
                finals.append(h.accuracy[-1])
    # invariance to the participation rate (paper §4.3)
    emit("fig3_rate_invariance",
         f"spread={max(finals)-min(finals):.2e}")
    return rows


if __name__ == "__main__":
    main()

"""Shared fixtures of the paper-table scripts: the synthetic "Landmarks-like"
federation.

Paper datasets (Landmarks/iNaturalist + ImageNet MobileNetV2) are not
available offline; every script runs on a controlled synthetic federation
whose *exact* claims (invariance, equivalence, round counts, cost ratios)
are checkable analytically, and whose accuracy-shaped comparisons reproduce
the paper's orderings directionally.  Scale is CPU-budgeted.
"""
from __future__ import annotations

from repro.configs.base import Fed3RConfig, FederatedConfig
from repro.data import make_federated_features

# script-wide synthetic federation scale (calibrated so FED3R lands
# mid-accuracy — nothing saturates — and the RBF-RF variant has headroom)
N, D, C, K = 16_000, 64, 50, 200
NOISE = 6.0
ALPHA = 0.0  # one-class-per-client: the paper's most heterogeneous split
CLIENTS_PER_ROUND = 10

# nonlinear (quadratic-boundary) federation for the RF/NCM scripts
NL_D, NL_C = 24, 10
RF_SIGMA = 15.0  # RBF bandwidth matched to the nonlinear feature scale
RF_LAMBDA = 1.0


def landmarks_like(nonlinear: bool = False, seed: int = 0):
    if nonlinear:
        return make_federated_features(
            seed=seed, n=N, d=NL_D, n_classes=NL_C, n_clients=K, alpha=ALPHA,
            nonlinear=True, noise=0.05,
        )
    return make_federated_features(
        seed=seed, n=N, d=D, n_classes=C, n_clients=K, alpha=ALPHA, noise=NOISE,
    )


def fed_cfg(**kw) -> FederatedConfig:
    base = dict(
        n_clients=K, clients_per_round=CLIENTS_PER_ROUND, n_rounds=60,
        local_epochs=1, local_batch_size=32, client_lr=0.05,
        client_weight_decay=4e-5, server_lr=1.0, algorithm="fedavg", seed=0,
    )
    base.update(kw)
    return FederatedConfig(**base)


def f3_cfg(**kw) -> Fed3RConfig:
    base = dict(ridge_lambda=0.01, n_classes=C)
    base.update(kw)
    return Fed3RConfig(**base)


def emit(name: str, derived: str) -> None:
    """One ``name,derived`` CSV row: what the table or figure reports."""
    print(f"{name},{derived}")

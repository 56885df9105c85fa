"""Federated runtime: algorithms, sampling, FED3R drivers, cost meters."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import Fed3RConfig, FederatedConfig
from repro.core import fed3r
from repro.data import make_federated_features
from repro.data.partition import dirichlet_partition, quantity_skew_sizes
from repro.federated import costs, run_fed3r, run_fed3r_ft, run_fedncm
from repro.federated.sampling import ClientSampler
from repro.federated.simulator import linear_head_task, run_federated

N_CLIENTS, C, D = 20, 6, 32


@pytest.fixture(scope="module")
def fed_data():
    return make_federated_features(
        seed=0, n=1500, d=D, n_classes=C, n_clients=N_CLIENTS, alpha=0.0, noise=1.5
    )


def _fc(**kw):
    base = dict(
        n_clients=N_CLIENTS, clients_per_round=5, n_rounds=20, local_epochs=1,
        local_batch_size=16, client_lr=0.1, algorithm="fedavg", seed=0,
    )
    base.update(kw)
    return FederatedConfig(**base)


def test_fed3r_converges_in_k_over_kappa_rounds(fed_data):
    """Paper §4.3: exactly ⌈K/κ⌉ rounds to the final solution."""
    fed, test = fed_data
    f3 = Fed3RConfig(n_classes=C)
    W, stats, hist = run_fed3r(fed, test.features, test.labels, f3, _fc(), eval_every=1)
    assert hist.rounds[-1] == -(-N_CLIENTS // 5)  # ⌈20/5⌉ = 4
    assert hist.clients_seen[-1] == N_CLIENTS
    # and the solution equals the centralized one
    cen = fed3r.solve(
        fed3r.client_stats(jnp.asarray(fed.features), jnp.asarray(fed.labels), C),
        f3.ridge_lambda,
    )
    np.testing.assert_allclose(np.asarray(W), np.asarray(cen), rtol=1e-4, atol=1e-4)


def test_fed3r_split_invariance_via_driver(fed_data):
    """Fig. 1: different federated splits converge to identical accuracy."""
    fed, test = fed_data
    f3 = Fed3RConfig(n_classes=C)
    accs = []
    for n_cl, alpha in [(10, 0.0), (40, 0.0), (20, 100.0)]:
        fed2 = fed.repartition(np.random.default_rng(7), n_cl, alpha)
        W, _, h = run_fed3r(
            fed2, test.features, test.labels, f3,
            _fc(n_clients=n_cl), eval_every=1000,
        )
        accs.append(h.accuracy[-1])
    assert max(accs) - min(accs) < 1e-6


def test_fed3r_resampled_client_sends_exactly_once(fed_data):
    """Regression for the seen-once dedup (formerly two identical branches):
    with-replacement sampling re-draws clients, but each client's statistics
    enter the sum exactly once — stats equal the centralized pass and ``n``
    counts every sample once."""
    fed, test = fed_data
    f3 = Fed3RConfig(n_classes=C)
    cfg = _fc(sample_with_replacement=True, n_rounds=60)
    W, stats, hist = run_fed3r(fed, test.features, test.labels, f3, cfg)
    assert hist.clients_seen[-1] == N_CLIENTS  # coupon collector finished
    cen = fed3r.client_stats(jnp.asarray(fed.features), jnp.asarray(fed.labels), C)
    # a lost or doubled client moves entries by O(max|A|); fp32 summation
    # order across the two folds moves them by O(eps·max|A|)
    A_cen = np.asarray(cen.A)
    np.testing.assert_allclose(np.asarray(stats.A), A_cen,
                               rtol=1e-4, atol=1e-6 * np.abs(A_cen).max())
    assert float(stats.n) == len(fed.labels)


def test_fed3r_beats_fedncm(fed_data):
    fed, test = fed_data
    f3 = Fed3RConfig(n_classes=C)
    W, _, h3 = run_fed3r(fed, test.features, test.labels, f3, _fc())
    _, hn = run_fedncm(fed, test.features, test.labels, _fc())
    assert h3.accuracy[-1] >= hn.accuracy[-1] - 0.02


@pytest.mark.parametrize("algorithm", ["fedavg", "fedavgm", "fedprox", "scaffold"])
def test_gradient_fl_learns(fed_data, algorithm):
    fed, test = fed_data
    task = linear_head_task(D, C, test.features, test.labels)
    cfg = _fc(algorithm=algorithm, n_rounds=15,
              server_momentum=0.9 if algorithm == "fedavgm" else 0.0)
    params, hist = run_federated(task, fed, cfg, eval_every=5)
    assert hist.accuracy[-1] > 1.5 / C  # clearly better than chance


@pytest.mark.parametrize("algorithm", ["fedadam", "fedyogi"])
def test_adaptive_server_optimizers_learn(fed_data, algorithm):
    """FedAdam / FedYogi (Reddi et al. 2021) as FT-phase server optimizers."""
    fed, test = fed_data
    task = linear_head_task(D, C, test.features, test.labels)
    cfg = _fc(algorithm=algorithm, n_rounds=15, server_lr=0.01)
    params, hist = run_federated(task, fed, cfg, eval_every=5)
    assert hist.accuracy[-1] > 1.5 / C


def test_ft_feat_keeps_classifier_fixed(fed_data):
    fed, test = fed_data
    f3 = Fed3RConfig(n_classes=C, ft_strategy="feat")
    params, info = run_fed3r_ft(
        fed, test.features, test.labels, f3, _fc(n_rounds=5), strategy="feat",
    )
    # classifier must equal the calibrated FED3R init exactly (frozen)
    hist1 = info["fed3r_history"]
    assert hist1.accuracy[-1] > 0
    W_init_norm = float(jnp.linalg.norm(params["W"]))
    assert W_init_norm > 0  # present
    grid = (3.0, 1.0, 0.3, 0.1, 0.03, 0.01)
    assert min(abs(info["temperature"] - t) for t in grid) < 1e-5


def test_sampler_without_replacement_covers_all():
    s = ClientSampler(17, 5, replacement=False, seed=0)
    seen = set()
    for _ in range(s.rounds_to_full_coverage()):
        seen.update(int(c) for c in s.sample())
    assert len(seen) == 17


def test_sampler_with_replacement_coupon_collector():
    s = ClientSampler(50, 10, replacement=True, seed=0)
    rounds = 0
    while s.coverage < 1.0 and rounds < 500:
        s.sample()
        rounds += 1
    assert rounds > 50 / 10  # strictly more rounds than ⌈K/κ⌉


# ---------------------------------------------------------------------------
# cost meters (paper App. D/E)
# ---------------------------------------------------------------------------


def test_cost_formulas_match_paper_structure():
    cm = costs.CostModel(b=2.22e6, d=1280, C=2028)
    assert cm.comm_per_client("fedavg")["up"] == cm.b + cm.d * cm.C
    assert cm.comm_per_client("scaffold")["up"] == 2 * (cm.b + cm.d * cm.C)
    assert cm.comm_per_client("fedavg-lp")["up"] == cm.d * cm.C
    assert cm.comm_per_client("fed3r")["up"] == cm.d**2 + cm.d * cm.C
    assert cm.comm_per_client("fed3r")["down"] == 0.0
    # computation: FedAvg = 3·E·n_k·F_M (App. E)
    assert cm.comp_per_client("fedavg", 100) == 3 * cm.E * 100 * cm.F_M
    fed3r_comp = cm.comp_per_client("fed3r", 100)
    assert fed3r_comp == 100 * (cm.F_phi + 0.5 * cm.d * (cm.d + 1) + cm.d * cm.C)


def test_fed3r_two_orders_of_magnitude_cheaper():
    """§5.2: at paper scale, FED3R total compute ≪ gradient FL compute."""
    cm = costs.INATURALIST
    # gradient FL: 5000 rounds (paper's iNaturalist budget)
    grad = cm.comp_per_client("fedavg", 13.0) * 5000 * 10 / 9275
    f3 = cm.comp_per_client("fed3r", 13.0)  # each client works exactly once
    assert grad / f3 > 25  # orders-of-magnitude regime


# ---------------------------------------------------------------------------
# partitioners
# ---------------------------------------------------------------------------


def test_dirichlet_alpha0_single_class_per_client():
    rng = np.random.default_rng(0)
    labels = np.repeat(np.arange(10), 100)
    parts = dirichlet_partition(rng, labels, 20, alpha=0.0)
    for p in parts:
        assert len(np.unique(labels[p])) == 1
    assert sum(len(p) for p in parts) == len(labels)


def test_dirichlet_alpha_large_is_roughly_uniform():
    rng = np.random.default_rng(0)
    labels = np.repeat(np.arange(5), 200)
    parts = dirichlet_partition(rng, labels, 10, alpha=1000.0)
    for p in parts:
        counts = np.bincount(labels[p], minlength=5)
        assert counts.min() > 0  # every class present


def test_quantity_skew_sizes_sum():
    rng = np.random.default_rng(0)
    sizes = quantity_skew_sizes(rng, 1000, 30, sigma=1.5)
    assert sizes.sum() == 1000
    assert sizes.min() >= 1

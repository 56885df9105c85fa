"""FED3R core: the paper's exact claims, tested exactly.

Section 4.3 properties:
  * immunity to statistical heterogeneity == invariance to the data split;
  * invariance to client sampling order;
  * federated solution == centralized solution;
plus solve correctness against the normal equations and the class-norm step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import calibration, fed3r, ncm
from repro.data.synthetic import make_feature_dataset

D, C, N = 24, 7, 400


@pytest.fixture(scope="module")
def data():
    ds = make_feature_dataset(jax.random.PRNGKey(0), N, D, C, noise=1.0)
    return np.asarray(ds.features), np.asarray(ds.labels)


def _centralized(feats, labels, lam=0.01):
    stats = fed3r.client_stats(jnp.asarray(feats), jnp.asarray(labels), C)
    return fed3r.solve(stats, lam)


def test_solve_matches_normal_equations(data):
    feats, labels = data
    lam = 0.37
    stats = fed3r.client_stats(jnp.asarray(feats), jnp.asarray(labels), C)
    W = fed3r.solve(stats, lam, normalize=False)
    Z = feats.astype(np.float64)
    Y = np.eye(C)[labels]
    W_np = np.linalg.solve(Z.T @ Z + lam * np.eye(D), Z.T @ Y)
    np.testing.assert_allclose(np.asarray(W), W_np, rtol=2e-4, atol=2e-4)


def test_split_invariance(data):
    """Eq. (5)/(6): any partition of D gives the same A, b, W*."""
    feats, labels = data
    W_cen = _centralized(feats, labels)
    rng = np.random.default_rng(1)
    for trial in range(3):
        order = rng.permutation(N)
        cuts = np.sort(rng.choice(np.arange(1, N), size=5, replace=False))
        parts = np.split(order, cuts)
        stats = [
            fed3r.client_stats(jnp.asarray(feats[p]), jnp.asarray(labels[p]), C)
            for p in parts
        ]
        W_fed = fed3r.solve(fed3r.merge(*stats), 0.01)
        np.testing.assert_allclose(np.asarray(W_fed), np.asarray(W_cen),
                                   rtol=1e-4, atol=1e-4)


def test_sampling_order_invariance(data):
    feats, labels = data
    parts = np.array_split(np.arange(N), 8)
    stats = [
        fed3r.client_stats(jnp.asarray(feats[p]), jnp.asarray(labels[p]), C)
        for p in parts
    ]
    W1 = fed3r.solve(fed3r.merge(*stats), 0.01)
    W2 = fed3r.solve(fed3r.merge(*stats[::-1]), 0.01)
    rng = np.random.default_rng(2)
    shuffled = [stats[i] for i in rng.permutation(len(stats))]
    W3 = fed3r.solve(fed3r.merge(*shuffled), 0.01)
    np.testing.assert_allclose(np.asarray(W1), np.asarray(W2), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(W1), np.asarray(W3), rtol=1e-5, atol=1e-5)


def test_masked_client_stats_exact(data):
    """Padding masks keep the statistics exact (clients-per-shard batching)."""
    feats, labels = data
    z = jnp.asarray(feats[:64])
    y = jnp.asarray(labels[:64])
    full = fed3r.client_stats(z[:40], y[:40], C)
    mask = jnp.arange(64) < 40
    padded = fed3r.client_stats(z, y, C, mask=mask)
    np.testing.assert_allclose(np.asarray(full.A), np.asarray(padded.A), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(full.b), np.asarray(padded.b), rtol=1e-6)
    assert float(padded.n) == 40.0


def test_class_normalization():
    W = jnp.asarray(np.random.default_rng(0).normal(size=(D, C)))
    stats = fed3r.Fed3RStats(
        A=jnp.eye(D), b=W, n=jnp.asarray(1.0)
    )
    Wn = fed3r.solve(stats, 0.0 + 1e-9, normalize=True)
    norms = jnp.linalg.norm(Wn, axis=0)
    np.testing.assert_allclose(np.asarray(norms), np.ones(C), rtol=1e-5)


def test_accuracy_perfect_on_separable():
    ds = make_feature_dataset(jax.random.PRNGKey(3), 500, 16, 5,
                              noise=0.1, class_scale=5.0)
    stats = fed3r.client_stats(ds.features, ds.labels, 5)
    W = fed3r.solve(stats, 0.01)
    assert float(fed3r.accuracy(W, ds.features, ds.labels)) > 0.99


def test_ncm_stats_and_solve(data):
    feats, labels = data
    stats = ncm.client_stats(jnp.asarray(feats), jnp.asarray(labels), C)
    parts = np.array_split(np.arange(N), 5)
    merged = ncm.merge(*[
        ncm.client_stats(jnp.asarray(feats[p]), jnp.asarray(labels[p]), C)
        for p in parts
    ])
    np.testing.assert_allclose(np.asarray(stats.sums), np.asarray(merged.sums),
                               rtol=1e-5, atol=1e-5)
    W = ncm.solve(stats)
    assert W.shape == (D, C)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(W, axis=0)), np.ones(C), rtol=1e-5
    )


def test_temperature_calibration_prefers_sharp():
    """RR scores are small-scale; the best temperature should be < 1."""
    ds = make_feature_dataset(jax.random.PRNGKey(4), 600, 32, 10,
                              noise=0.5, class_scale=4.0)
    stats = fed3r.client_stats(ds.features, ds.labels, 10)
    W = fed3r.solve(stats, 0.01)
    scores = fed3r.predict(W, ds.features)
    temp, ces = calibration.calibrate_temperature(scores, ds.labels)
    assert float(temp) < 1.0
    assert ces.shape[0] == len(calibration.DEFAULT_TEMPERATURES)

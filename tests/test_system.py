"""End-to-end system behaviour: the paper's pipeline on a real backbone.

FED3R with a transformer feature extractor φ (reduced config), exercising
the full statistics → aggregation → solve → FT-init path, plus the
distributed-runtime statistics step on a host mesh (psum aggregation
equivalence — the datacenter code path at test scale).
"""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import make_batch
from repro.configs import get_config
from repro.core import calibration, fed3r
from repro.data.synthetic import make_token_dataset
from repro.launch.steps import make_fed3r_stats_step
from repro.models import build_model


def test_fed3r_on_transformer_features(rng):
    """Statistics over a real backbone's pooled features → working classifier."""
    cfg = get_config("fed3r-mnv2-proxy-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(rng)
    C = 8
    ds = make_token_dataset(jax.random.PRNGKey(1), 256, 16, cfg.vocab_size, C)

    extract = jax.jit(lambda b: model.extract_features(params, b))
    # split "clients" = batches; aggregate statistics exactly
    stats = fed3r.init_stats(cfg.d_feat, C)
    for s in range(0, 256, 64):
        feats = extract({"tokens": ds.tokens[s : s + 64]})
        stats = fed3r.merge(
            stats, fed3r.client_stats(feats, ds.labels[s : s + 64], C)
        )
    W = fed3r.solve(stats, 0.01)

    # centralized equivalence
    feats_all = extract({"tokens": ds.tokens})
    W_cen = fed3r.solve(fed3r.client_stats(feats_all, ds.labels, C), 0.01)
    np.testing.assert_allclose(np.asarray(W), np.asarray(W_cen), rtol=1e-3, atol=1e-3)

    # the class-prefix token makes features informative → above chance
    acc = float(fed3r.accuracy(W, feats_all, ds.labels))
    assert acc > 2.0 / C, acc

    # calibrated softmax init is finite
    temp, _ = calibration.calibrate_temperature(
        fed3r.predict(W, feats_all), ds.labels
    )
    W_init = calibration.fold_temperature(W, temp)
    assert bool(jnp.all(jnp.isfinite(W_init)))


def test_fed3r_stats_step_matches_simulator_path(rng):
    """launch.steps.make_fed3r_stats_step == core path (same batch)."""
    cfg = get_config("qwen2-7b-smoke").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(rng)
    C = 5
    batch = make_batch(cfg, rng, 4, 16, with_labels=False)
    batch["class_labels"] = jax.random.randint(jax.random.fold_in(rng, 3), (4,), 0, C)

    step = jax.jit(make_fed3r_stats_step(cfg, C))
    stats0 = fed3r.init_stats(cfg.d_feat, C)
    stats1 = step(params, stats0, batch)

    feats = model.extract_features(params, batch)
    ref = fed3r.client_stats(feats, batch["class_labels"], C)
    np.testing.assert_allclose(np.asarray(stats1.A), np.asarray(ref.A),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(stats1.b), np.asarray(ref.b),
                               rtol=1e-4, atol=1e-4)
    assert float(stats1.n) == 4.0


def test_fed3r_psum_aggregation_on_host_mesh(rng):
    """The datacenter aggregation (psum over data) == simulator merge."""
    from repro.core.fed3r import aggregate_mesh
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    d, C, n = 8, 3, 4 * n_dev
    feats = jax.random.normal(rng, (n, d))
    labels = jax.random.randint(jax.random.fold_in(rng, 1), (n,), 0, C)

    def local_stats(f, l):
        s = fed3r.client_stats(f, l, C)
        return aggregate_mesh(s, ("data",))

    agg = jax.shard_map(
        local_stats, mesh=mesh,
        in_specs=(P("data", None), P("data")),
        out_specs=P(),
    )(feats, labels)
    ref = fed3r.client_stats(feats, labels, C)
    np.testing.assert_allclose(np.asarray(agg.A), np.asarray(ref.A),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(agg.b), np.asarray(ref.b),
                               rtol=1e-5, atol=1e-5)

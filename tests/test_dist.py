"""Distributed execution layer (repro.federated.dist) coverage.

The layer's contract:
  * ``make_host_mesh`` raises ``ValueError`` (not a stripped assert) on
    indivisible factorizations, and builds the 3-axis ("pod", "data",
    "model") layout on simulated host devices;
  * ``DistConfig`` owns the merge|psum validation and axis resolution the
    engines used to triplicate;
  * ``two_stage_psum`` (one psum per axis, innermost first) equals the flat
    all-reduce;
  * all FOUR engines route their psum backend through the dist layer: with
    ``DistConfig(mesh=...)`` each host call is ONE shard_map dispatch whose
    results match the single-device ``merge`` backend — bitwise for A/b (and
    the factored L/W downstream) on grid-quantized features where fp32
    sums are exact, ≤ 1e-5 for solved classifiers in general;
  * shard-count invariance: the same packed arrays give the same A, b, L, W
    at data-parallel 1 and data-parallel N;
  * the packers' ``mesh``/``num_shards`` padding adds only fully-masked
    blocks — exact no-ops that leave every engine's output bit-identical.

Most sharded tests need ≥ 4 simulated devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the multi-device
CI job sets this); on 1 device they skip, while the mesh-mode plumbing
tests still run (a 1-device mesh is a valid degenerate case).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fed3r
from repro.data.pipeline import (
    pack_arrival_waves,
    pack_client_shards,
    pack_cohort_batches,
    pack_personal_cohort,
)
from repro.federated.algorithms import make_algorithm
from repro.federated.dist import DistConfig, DistContext, two_stage_psum
from repro.federated.engine import AccumulationEngine, EngineConfig
from repro.federated.personalization import (
    PersonalizationEngine,
    PersonalizeConfig,
)
from repro.federated.round_engine import RoundConfig, RoundEngine
from repro.federated.streaming_engine import StreamConfig, StreamingEngine
from repro.launch.mesh import (
    data_axes,
    data_parallel_size,
    make_host_mesh,
)

D, C = 16, 5
LAM = 0.1

N_DEV = len(jax.devices())
needs4 = pytest.mark.skipif(
    N_DEV < 4,
    reason="needs >=4 simulated devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _grid_clients(seed, sizes, d=D, n_classes=C):
    """Clients whose features live on a 1/8 grid in [-2, 2]: all Gram
    products land on a 1/64 grid and every partial sum stays far below
    2^24/64, so fp32 accumulation is EXACT — any summation order (scan
    fold, psum tree, two-stage hierarchy) produces bit-identical A/b."""
    rng = np.random.default_rng(seed)
    return [
        (
            (rng.integers(-16, 17, size=(n, d)) / 8.0).astype(np.float32),
            rng.integers(0, n_classes, size=n).astype(np.int32),
        )
        for n in sizes
    ]


def _submesh(dp: int) -> jax.sharding.Mesh:
    """A (data=dp, model=1) mesh over the first dp local devices."""
    devs = np.asarray(jax.devices()[:dp]).reshape(dp, 1)
    return jax.sharding.Mesh(devs, ("data", "model"))


def _psum_cfg(mesh, **kw) -> DistConfig:
    return DistConfig(aggregation="psum", mesh=mesh, donate=False, **kw)


# ---------------------------------------------------------------------------
# host meshes
# ---------------------------------------------------------------------------


def test_make_host_mesh_raises_on_indivisible():
    with pytest.raises(ValueError):
        make_host_mesh(model_parallel=N_DEV + 1)
    with pytest.raises(ValueError):
        make_host_mesh(model_parallel=0)
    with pytest.raises(ValueError):
        make_host_mesh(pods=0)
    with pytest.raises(ValueError):
        make_host_mesh(pods=N_DEV + 1)


def test_make_host_mesh_axis_layouts():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert data_axes(mesh) == ("data",)
    assert data_parallel_size(mesh) == N_DEV


@needs4
def test_make_host_mesh_pod_variant_is_three_axis():
    mesh = make_host_mesh(pods=2)
    assert mesh.axis_names == ("pod", "data", "model")
    assert data_axes(mesh) == ("pod", "data")
    assert mesh.devices.shape == (2, N_DEV // 2, 1)
    assert data_parallel_size(mesh) == N_DEV


# ---------------------------------------------------------------------------
# DistConfig / DistContext
# ---------------------------------------------------------------------------


def test_dist_config_validation():
    with pytest.raises(ValueError):
        DistConfig(aggregation="allgather")
    with pytest.raises(ValueError):
        DistConfig(aggregation="psum")  # no axes, no mesh
    with pytest.raises(ValueError):
        DistConfig(aggregation="merge", mesh=make_host_mesh())  # merge is local
    with pytest.raises(ValueError):
        DistConfig(
            aggregation="psum", mesh=make_host_mesh(), mesh_axes=("nonexistent",)
        )
    # explicit axes without a mesh: the external-shard_map contract
    cfg = DistConfig(aggregation="psum", mesh_axes=("data",))
    assert cfg.axis_names == ("data",)
    assert cfg.data_shards == 1


def test_dist_config_resolves_axes_from_mesh():
    mesh = make_host_mesh()
    cfg = DistConfig(aggregation="psum", mesh=mesh)
    assert cfg.axis_names == ("data",)
    assert cfg.data_shards == N_DEV


def test_dist_context_merge_all_reduce_is_identity():
    ctx = DistContext(DistConfig())
    tree = {"a": jnp.ones((3,))}
    assert ctx.all_reduce(tree) is tree
    ctx.dispatch()
    ctx.dispatch()
    assert ctx.dispatches == 2


@needs4
def test_two_stage_psum_equals_flat_psum_on_pod_mesh():
    mesh = make_host_mesh(pods=2)
    dp = data_parallel_size(mesh)
    x = jnp.asarray(
        (np.random.default_rng(0).integers(-16, 17, size=(dp, 8)) / 8.0
         ).astype(np.float32)
    )

    def two_stage(v):
        return two_stage_psum(v, ("pod", "data"))

    def flat(v):
        return jax.lax.psum(v, ("pod", "data"))

    spec = P(("pod", "data"))
    a = jax.shard_map(two_stage, mesh=mesh, in_specs=spec, out_specs=P())(x)
    b = jax.shard_map(flat, mesh=mesh, in_specs=spec, out_specs=P())(x)
    # exact grid values: any reduction order is bit-identical
    assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(
        np.asarray(a).reshape(-1), np.asarray(x).sum(0)
    )


# ---------------------------------------------------------------------------
# packer dp-padding: fully-masked blocks are exact no-ops
# ---------------------------------------------------------------------------


def test_pack_client_shards_dp_padding_is_bitwise_noop():
    clients = _grid_clients(0, [5, 9, 2, 7, 3])
    plain = pack_client_shards(clients, 2, max_n=16)
    padded = pack_client_shards(clients, 2, max_n=16, num_shards=4)
    assert padded.n_shards % 4 == 0
    assert padded.n_clients == plain.n_clients
    eng = AccumulationEngine(EngineConfig(n_classes=C))
    a = eng.accumulate(eng.init(D), plain)
    b = eng.accumulate(eng.init(D), padded)
    assert np.array_equal(np.asarray(a.stats.A), np.asarray(b.stats.A))
    assert np.array_equal(np.asarray(a.stats.b), np.asarray(b.stats.b))
    assert np.array_equal(np.asarray(a.class_counts), np.asarray(b.class_counts))


def test_pack_arrival_waves_dp_padding_is_bitwise_noop():
    waves = [_grid_clients(t, [6] * (1 + t % 3)) for t in range(4)]
    plain = pack_arrival_waves(waves)
    padded = pack_arrival_waves(waves, num_shards=4)
    assert padded.clients_per_wave % 4 == 0
    eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    sa, _ = eng.absorb(eng.init(D), plain)
    sb, _ = eng.absorb(eng.init(D), padded)
    assert np.array_equal(np.asarray(sa.L), np.asarray(sb.L))
    assert np.array_equal(np.asarray(sa.W), np.asarray(sb.W))


def test_pack_cohort_batches_dp_padding_is_noop():
    clients = _grid_clients(1, [20, 12, 17])
    plain = pack_cohort_batches(clients, 8, 3)
    padded = pack_cohort_batches(clients, 8, 3, num_shards=4)
    assert padded.cohort % 4 == 0 and padded.n_clients == 3
    params0 = {"W": jnp.zeros((D, C), jnp.float32)}
    freeze = jax.tree.map(lambda _: 1.0, params0)

    def loss(params, batch):
        logits = batch["x"] @ params["W"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, batch["y"][:, None].astype(jnp.int32), axis=-1
        )[:, 0]
        return lse - picked

    rc = RoundConfig(algo=make_algorithm("fedavg"), client_lr=0.1,
                     n_total_clients=3)
    eng = RoundEngine(rc, loss, freeze)
    sa = eng.step(eng.init(params0), plain)
    sb = eng.step(eng.init(params0), padded)
    np.testing.assert_allclose(
        np.asarray(sa.params["W"]), np.asarray(sb.params["W"]),
        rtol=0, atol=1e-7,
    )


def test_pack_personal_cohort_dp_padding_is_noop():
    clients = _grid_clients(2, [12, 9, 15])
    plain = pack_personal_cohort(clients, holdout_frac=0.25)
    padded = pack_personal_cohort(clients, holdout_frac=0.25, num_shards=4)
    assert padded.cohort % 4 == 0 and padded.n_clients == 3
    fac = _factored_state(clients)
    eng = PersonalizationEngine(PersonalizeConfig(n_classes=C))
    ha = eng.solve_heads(fac, plain)
    hb = eng.solve_heads(fac, padded)
    real = np.asarray(padded.client_ids) >= 0
    assert np.array_equal(np.asarray(ha.alpha), np.asarray(hb.alpha)[real])
    np.testing.assert_allclose(
        np.asarray(ha.W), np.asarray(hb.W)[real], rtol=0, atol=1e-6
    )


def _factored_state(clients) -> fed3r.Fed3RFactored:
    fac = fed3r.init_factored(D, C, LAM)
    return fed3r.factored_update(
        fac,
        jnp.asarray(np.concatenate([x for x, _ in clients])),
        jnp.asarray(np.concatenate([y for _, y in clients])),
    )


# ---------------------------------------------------------------------------
# four-engine psum == merge on the sharded host mesh (ONE dispatch each)
# ---------------------------------------------------------------------------


@needs4
def test_accumulation_engine_sharded_matches_merge_bitwise():
    mesh = make_host_mesh()
    clients = _grid_clients(3, [9, 3, 14, 6, 1, 11, 8, 4])
    packed = pack_client_shards(clients, 2, max_n=16, mesh=mesh)

    merge_eng = AccumulationEngine(EngineConfig(n_classes=C))
    ref = merge_eng.accumulate(merge_eng.init(D), packed)

    eng = AccumulationEngine(EngineConfig(n_classes=C, dist=_psum_cfg(mesh)))
    acc = eng.accumulate(eng.init(D), packed)
    assert eng.dispatches == 1  # the whole sharded fold is ONE dispatch
    # exact grid features: the psum tree cannot change a bit of A or b
    assert np.array_equal(np.asarray(ref.stats.A), np.asarray(acc.stats.A))
    assert np.array_equal(np.asarray(ref.stats.b), np.asarray(acc.stats.b))
    assert np.array_equal(
        np.asarray(ref.class_counts), np.asarray(acc.class_counts)
    )
    # and the solved classifier agrees within fp32 solve tolerance
    W_ref = fed3r.solve(ref.stats, LAM)
    W_got = fed3r.solve(acc.stats, LAM)
    np.testing.assert_allclose(
        np.asarray(W_ref), np.asarray(W_got), rtol=0, atol=1e-5
    )


@needs4
def test_streaming_engine_sharded_matches_merge_bitwise():
    mesh = make_host_mesh()
    waves = [_grid_clients(10 + t, [8] * (2 + t % 2)) for t in range(5)]
    packed = pack_arrival_waves(waves, mesh=mesh)

    merge_eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    ref, _ = merge_eng.absorb(merge_eng.init(D), packed)

    eng = StreamingEngine(
        StreamConfig(n_classes=C, ridge_lambda=LAM, dist=_psum_cfg(mesh))
    )
    got, trace = eng.absorb(eng.init(D), packed)
    assert eng.dispatches == 1
    # exact per-wave Grams ⇒ identical refactorizations ⇒ bitwise L and W
    assert np.array_equal(np.asarray(ref.L), np.asarray(got.L))
    assert np.array_equal(np.asarray(ref.W), np.asarray(got.W))
    assert float(got.n) == float(ref.n)
    assert np.asarray(trace.refreshed).all()


@needs4
def test_round_engine_sharded_matches_merge():
    mesh = make_host_mesh()
    clients = _grid_clients(4, [24, 18, 30, 12])
    cohort = pack_cohort_batches(clients, 8, 3, mesh=mesh)
    params0 = {"W": jnp.zeros((D, C), jnp.float32)}
    freeze = jax.tree.map(lambda _: 1.0, params0)

    def loss(params, batch):
        logits = batch["x"] @ params["W"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, batch["y"][:, None].astype(jnp.int32), axis=-1
        )[:, 0]
        return lse - picked

    def rc(dist):
        return RoundConfig(algo=make_algorithm("fedavg"), client_lr=0.1,
                           n_total_clients=4, dist=dist)

    merge_eng = RoundEngine(rc(DistConfig()), loss, freeze)
    ref = merge_eng.step(merge_eng.init(params0), cohort)

    eng = RoundEngine(rc(_psum_cfg(mesh)), loss, freeze)
    got = eng.step(eng.init(params0), cohort)
    assert eng.dispatches == 1
    np.testing.assert_allclose(
        np.asarray(ref.params["W"]), np.asarray(got.params["W"]),
        rtol=1e-5, atol=1e-6,
    )


@needs4
def test_personalization_engine_sharded_matches_merge():
    mesh = make_host_mesh()
    # strongly label-skewed tenants so the α sweep's score gaps dwarf any
    # batched-solve ulp differences between local cohort widths
    rng = np.random.default_rng(5)
    clients = []
    for k in range(8):
        n = 12
        feats = (rng.integers(-16, 17, size=(n, D)) / 8.0).astype(np.float32)
        labels = np.full((n,), k % C, dtype=np.int32)
        clients.append((feats, labels))
    packed = pack_personal_cohort(clients, mesh=mesh)
    fac = _factored_state(clients)

    merge_eng = PersonalizationEngine(PersonalizeConfig(n_classes=C))
    ref = merge_eng.solve_heads(fac, packed)

    eng = PersonalizationEngine(
        PersonalizeConfig(n_classes=C, dist=_psum_cfg(mesh))
    )
    got = eng.solve_heads(fac, packed)
    assert eng.dispatches == 1
    assert np.array_equal(np.asarray(ref.alpha), np.asarray(got.alpha))
    np.testing.assert_allclose(
        np.asarray(ref.W), np.asarray(got.W), rtol=0, atol=1e-5
    )
    # fixed-α path too (the serving cache re-solve shape)
    at_ref = merge_eng.solve_at(fac, packed, ref.alpha)
    at_got = eng.solve_at(fac, packed, ref.alpha)
    np.testing.assert_allclose(
        np.asarray(at_ref.W), np.asarray(at_got.W), rtol=0, atol=1e-5
    )


# ---------------------------------------------------------------------------
# shard-count invariance: data-parallel 1 vs 4 on the SAME packed arrays
# ---------------------------------------------------------------------------


@needs4
def test_shard_count_invariance_stats_and_stream():
    clients = _grid_clients(6, [7, 13, 5, 9, 11, 3, 8, 6])
    packed = pack_client_shards(clients, 2, max_n=16, num_shards=4)
    waves = [_grid_clients(20 + t, [8] * 4) for t in range(3)]
    arrivals = pack_arrival_waves(waves, num_shards=4)

    results = {}
    for dp in (1, 4):
        mesh = _submesh(dp)
        eng = AccumulationEngine(EngineConfig(n_classes=C, dist=_psum_cfg(mesh)))
        acc = eng.accumulate(eng.init(D), packed)
        s_eng = StreamingEngine(
            StreamConfig(n_classes=C, ridge_lambda=LAM, dist=_psum_cfg(mesh))
        )
        st, _ = s_eng.absorb(s_eng.init(D), arrivals)
        results[dp] = (acc, st)

    a1, s1 = results[1]
    a4, s4 = results[4]
    # same A, b, L, W at data-parallel 1 vs 4 — bitwise on the exact grid
    assert np.array_equal(np.asarray(a1.stats.A), np.asarray(a4.stats.A))
    assert np.array_equal(np.asarray(a1.stats.b), np.asarray(a4.stats.b))
    assert np.array_equal(np.asarray(s1.L), np.asarray(s4.L))
    assert np.array_equal(np.asarray(s1.W), np.asarray(s4.W))
    W1 = fed3r.solve(a1.stats, LAM)
    W4 = fed3r.solve(a4.stats, LAM)
    np.testing.assert_allclose(np.asarray(W1), np.asarray(W4), rtol=0, atol=1e-5)


@needs4
def test_streaming_sharded_on_pod_mesh():
    """The 3-axis ("pod", "data", "model") host mesh end to end: the wave
    statistics reduce intra-pod then cross-pod and still match merge."""
    mesh = make_host_mesh(pods=2)
    waves = [_grid_clients(30 + t, [8] * 4) for t in range(3)]
    packed = pack_arrival_waves(waves, mesh=mesh)

    merge_eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    ref, _ = merge_eng.absorb(merge_eng.init(D), packed)

    eng = StreamingEngine(
        StreamConfig(n_classes=C, ridge_lambda=LAM, dist=_psum_cfg(mesh))
    )
    got, _ = eng.absorb(eng.init(D), packed)
    assert eng.dispatches == 1
    assert np.array_equal(np.asarray(ref.L), np.asarray(got.L))
    assert np.array_equal(np.asarray(ref.W), np.asarray(got.W))


def test_place_puts_batches_straight_into_the_data_sharding():
    """Mesh mode hands each device only its shard of a packed batch,
    instead of staging the whole array on device 0 first."""
    mesh = make_host_mesh()
    dp = data_parallel_size(mesh)
    ctx = DistContext(DistConfig(aggregation="psum", mesh=mesh))
    x = np.arange(3 * 2 * dp * 5, dtype=np.float32).reshape(3, 2 * dp, 5)
    placed = ctx.place(x, axis=1)
    assert placed.sharding == NamedSharding(mesh, P(None, "data"))
    assert {s.data.shape for s in placed.addressable_shards} == {(3, 2, 5)}
    np.testing.assert_array_equal(np.asarray(placed), x)
    merged = DistContext(DistConfig()).place(x)
    assert merged.sharding.is_fully_replicated

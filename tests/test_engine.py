"""Accumulation-engine coverage: parity, kernel dispatch, exact invariance.

The engine's contract (federated/engine.py):
  * packed scan accumulation == naive per-client loop, exactly (same math);
  * the Pallas kernel path (interpret mode on CPU) matches the XLA path
    under odd shapes, padding, and dtypes;
  * A and b are BIT-identical under client reordering and re-sharding
    (canonical packing + strict left fold);
  * idempotent re-send semantics in the drivers (regression for the
    collapsed seen-once branches).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r, ncm
from repro.core.random_features import rff_init, rff_map
from repro.data.pipeline import pack_client_shards
from repro.federated.engine import (
    AccumulationEngine,
    EngineConfig,
    aggregate,
    shard_stats,
    to_ncm_stats,
)

D, C = 16, 5


def _make_clients(rng, sizes, d=D, n_classes=C):
    out = []
    for i, n in enumerate(sizes):
        r = np.random.default_rng(rng + i)
        out.append((
            r.normal(size=(n, d)).astype(np.float32),
            r.integers(0, n_classes, size=n).astype(np.int32),
        ))
    return out


def _naive(clients, n_classes=C, d=D):
    stats = fed3r.init_stats(d, n_classes)
    for f, y in clients:
        stats = fed3r.merge(
            stats, fed3r.client_stats(jnp.asarray(f), jnp.asarray(y), n_classes)
        )
    return stats


# ---------------------------------------------------------------------------
# packer
# ---------------------------------------------------------------------------


def test_packer_shapes_masks_and_ids():
    clients = _make_clients(0, [5, 9, 2])
    p = pack_client_shards(clients, 2, round_to=4)
    assert p.inputs.shape == (2, 2, 12, D)  # 9 → 12 (round_to), 3 → 4 slots
    assert p.n_clients == 3
    assert p.n_samples == 16
    assert (p.client_ids.reshape(-1)[:3] == np.arange(3)).all()
    assert p.client_ids.reshape(-1)[3] == -1
    # mask rows agree with client sizes, padding rows are fully zero
    sizes = p.mask.reshape(-1, p.inputs.shape[2]).sum(1)
    assert sorted(sizes.tolist()) == [0.0, 2.0, 5.0, 9.0]


def test_packer_canonical_order_is_input_order_invariant():
    clients = _make_clients(1, [4, 7, 3, 6])
    ids = [11, 3, 7, 5]
    p1 = pack_client_shards(clients, 2, client_ids=ids)
    perm = [2, 0, 3, 1]
    p2 = pack_client_shards(
        [clients[i] for i in perm], 2, client_ids=[ids[i] for i in perm]
    )
    for a, b in zip(p1, p2):
        assert np.array_equal(a, b)


def test_packer_counts_stats_rows():
    """``stats_rows``: real rows, slots × capacity, and the rows the
    statistics kernel computes (each client rounded up to its row block)."""
    from repro.federated.telemetry import Telemetry, set_telemetry
    from repro.kernels.fed3r_stats import BK

    sizes = [5, BK + 1, 2 * BK, 1]
    prev = set_telemetry(Telemetry())
    try:
        pack_client_shards(_make_clients(3, sizes), 3, round_to=8)
        t = set_telemetry(prev)
    except BaseException:
        set_telemetry(prev)
        raise
    rows = {k: t.counter("stats_rows", kind=k).value
            for k in ("real", "capacity", "kernel")}
    assert rows == {
        "real": 3 * BK + 7,
        "capacity": 2 * 3 * 2 * BK,  # 2 shards of 3 slots, 2·BK rows each
        "kernel": BK + 2 * BK + 2 * BK + BK,
    }


def test_packer_rejects_oversized_client():
    clients = _make_clients(2, [4, 9])
    with pytest.raises(ValueError):
        pack_client_shards(clients, 2, max_n=8)


# ---------------------------------------------------------------------------
# engine vs naive loop — exact parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sizes", [[8], [5, 9, 2], [1, 17, 4, 4, 30]])
def test_engine_matches_naive_loop(sizes):
    clients = _make_clients(3, sizes)
    eng = AccumulationEngine(EngineConfig(n_classes=C))
    acc = eng.accumulate(eng.init(D), pack_client_shards(clients, 2))
    ref = _naive(clients)
    np.testing.assert_allclose(np.asarray(acc.stats.A), np.asarray(ref.A),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc.stats.b), np.asarray(ref.b),
                               rtol=1e-6, atol=1e-6)
    assert float(acc.stats.n) == float(ref.n) == sum(sizes)


def test_engine_class_counts_give_ncm():
    clients = _make_clients(4, [6, 11, 3])
    eng = AccumulationEngine(EngineConfig(n_classes=C))
    acc = eng.accumulate(eng.init(D), pack_client_shards(clients, 2))
    ref = ncm.init_stats(D, C)
    for f, y in clients:
        ref = ncm.merge(ref, ncm.client_stats(jnp.asarray(f), jnp.asarray(y), C))
    got = to_ncm_stats(acc)
    np.testing.assert_allclose(np.asarray(got.sums), np.asarray(ref.sums),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.counts), np.asarray(ref.counts))


def test_engine_rff_fusion_matches_host_map():
    clients = _make_clients(5, [7, 12])
    params = rff_init(jax.random.PRNGKey(0), D, 32, sigma=3.0)
    eng = AccumulationEngine(EngineConfig(n_classes=C), rff_params=params)
    acc = eng.accumulate(eng.init(32), pack_client_shards(clients, 2))
    mapped = [(np.asarray(rff_map(params, jnp.asarray(f))), y) for f, y in clients]
    ref = _naive(mapped, d=32)
    np.testing.assert_allclose(np.asarray(acc.stats.A), np.asarray(ref.A),
                               rtol=1e-5, atol=1e-5)


def test_engine_feature_fn_runs_inside_scan():
    clients = _make_clients(6, [5, 8, 2])
    scale = {"w": jnp.asarray(2.5, jnp.float32)}
    eng = AccumulationEngine(
        EngineConfig(n_classes=C), feature_fn=lambda p, x: x * p["w"]
    )
    acc = eng.accumulate(eng.init(D), pack_client_shards(clients, 2), scale)
    ref = _naive([(f * 2.5, y) for f, y in clients])
    np.testing.assert_allclose(np.asarray(acc.stats.A), np.asarray(ref.A),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# exact invariance: reordering + re-sharding
# ---------------------------------------------------------------------------


def test_engine_bit_identical_under_client_permutation():
    clients = _make_clients(7, [9, 3, 14, 6, 1, 11])
    eng = AccumulationEngine(EngineConfig(n_classes=C))
    a1 = eng.accumulate(eng.init(D), pack_client_shards(clients, 3))
    perm = [4, 0, 5, 2, 1, 3]
    a2 = eng.accumulate(
        eng.init(D),
        pack_client_shards(
            [clients[i] for i in perm], 3, client_ids=perm
        ),
    )
    assert np.array_equal(np.asarray(a1.stats.A), np.asarray(a2.stats.A))
    assert np.array_equal(np.asarray(a1.stats.b), np.asarray(a2.stats.b))


@pytest.mark.parametrize("cps", [1, 2, 3, 6])
def test_engine_bit_identical_under_resharding(cps):
    """Strict left fold in canonical order ⇒ shard boundaries are invisible."""
    clients = _make_clients(8, [9, 3, 14, 6, 1, 11])
    ref_eng = AccumulationEngine(EngineConfig(n_classes=C))
    # fixed max_n so per-client block shapes are identical across shardings
    ref = ref_eng.accumulate(
        ref_eng.init(D), pack_client_shards(clients, 2, max_n=16)
    )
    eng = AccumulationEngine(EngineConfig(n_classes=C))
    got = eng.accumulate(eng.init(D), pack_client_shards(clients, cps, max_n=16))
    assert np.array_equal(np.asarray(ref.stats.A), np.asarray(got.stats.A))
    assert np.array_equal(np.asarray(ref.stats.b), np.asarray(got.stats.b))


KERNEL_SIZES = [150, 3, 260, 9, 1, 128]  # across several row blocks


@pytest.mark.parametrize("layout", [
    dict(max_n=600),  # padded further: more dead row blocks
    dict(perm=[4, 0, 5, 2, 1, 3]),
    dict(cps=1), dict(cps=3), dict(cps=6),
], ids=["capacity", "permutation", "cps1", "cps3", "cps6"])
def test_engine_kernel_path_bit_identical(layout):
    """On the kernel path a client's statistics depend on its real rows
    only: the fixed row block makes A and b the same bits at any capacity,
    and the left fold keeps them so under reordering and re-sharding."""
    clients = _make_clients(10, KERNEL_SIZES)
    eng = AccumulationEngine(EngineConfig(n_classes=C, use_kernel=True))
    ref = eng.accumulate(eng.init(D), pack_client_shards(clients, 2))
    perm = layout.get("perm", list(range(len(clients))))
    packed = pack_client_shards(
        [clients[i] for i in perm], layout.get("cps", 2), client_ids=perm,
        max_n=layout.get("max_n"),
    )
    got = eng.accumulate(eng.init(D), packed)
    assert np.array_equal(np.asarray(ref.stats.A), np.asarray(got.stats.A))
    assert np.array_equal(np.asarray(ref.stats.b), np.asarray(got.stats.b))


# ---------------------------------------------------------------------------
# kernel path (Pallas, interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,C_", [(30, 24, 3), (129, 65, 7), (64, 16, 11)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shard_stats_kernel_matches_reference(n, d, C_, dtype, rng):
    feats = jax.random.normal(rng, (n, d), dtype)
    labels = jax.random.randint(jax.random.fold_in(rng, 1), (n,), 0, C_)
    mask = (jax.random.uniform(jax.random.fold_in(rng, 2), (n,)) > 0.3).astype(
        jnp.float32
    )
    ker = shard_stats(feats, labels, C_, mask, use_kernel=True)
    ref = shard_stats(feats, labels, C_, mask, use_kernel=False)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(ker.A), np.asarray(ref.A),
                               rtol=tol, atol=tol * n)
    np.testing.assert_allclose(np.asarray(ker.b), np.asarray(ref.b),
                               rtol=tol, atol=tol * n)
    assert ker.A.dtype == jnp.float32
    np.testing.assert_allclose(float(ker.n), float(ref.n))


def test_engine_kernel_path_matches_xla_path():
    clients = _make_clients(9, [5, 13, 7])
    packed = pack_client_shards(clients, 2)
    xla = AccumulationEngine(EngineConfig(n_classes=C, use_kernel=False))
    ker = AccumulationEngine(EngineConfig(n_classes=C, use_kernel=True))
    a1 = xla.accumulate(xla.init(D), packed)
    a2 = ker.accumulate(ker.init(D), packed)
    np.testing.assert_allclose(np.asarray(a1.stats.A), np.asarray(a2.stats.A),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(a1.stats.b), np.asarray(a2.stats.b),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# aggregation backends
# ---------------------------------------------------------------------------


def test_aggregate_merge_is_identity_and_psum_validates():
    s = fed3r.init_stats(4, 3)
    assert aggregate(s, "merge") is s
    with pytest.raises(ValueError):
        aggregate(s, "psum")  # psum without axes is a bug, not a no-op
    with pytest.raises(ValueError):
        aggregate(s, "allgather")


def test_psum_backend_matches_merge_on_host_mesh(rng):
    from jax.sharding import PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    n = 4 * n_dev
    feats = jax.random.normal(rng, (n, D))
    labels = jax.random.randint(jax.random.fold_in(rng, 1), (n,), 0, C)

    def local(f, l):
        return aggregate(shard_stats(f, l, C, use_kernel=False), "psum", ("data",))

    agg = jax.shard_map(local, mesh=mesh, in_specs=(P("data", None), P("data")),
                    out_specs=P())(feats, labels)
    ref = fed3r.client_stats(feats, labels, C)
    np.testing.assert_allclose(np.asarray(agg.A), np.asarray(ref.A),
                               rtol=1e-5, atol=1e-5)


def test_engine_counts_one_dispatch_per_accumulate():
    clients = _make_clients(10, [4] * 12)
    eng = AccumulationEngine(EngineConfig(n_classes=C))
    acc = eng.init(D)
    acc = eng.accumulate(acc, pack_client_shards(clients[:6], 3))
    acc = eng.accumulate(acc, pack_client_shards(clients[6:], 3, client_ids=range(6, 12)))
    assert eng.dispatches == 2  # 12 clients, 2 dispatches (was 12 in the loop)
    assert float(acc.stats.n) == 48.0

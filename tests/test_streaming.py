"""Streaming arrival engine: parity, stability, invariance, policy, purity.

The engine's contract (federated/streaming_engine.py):
  * T waves fold in ONE jitted dispatch, and the factored-form final W
    matches the batch ``solve`` in fp32 at λ ≤ 1e-2, where a subtractive
    update of a carried inverse would cancel;
  * the packed timeline (and hence the folded state and final W) is
    BIT-identical under permutation of a wave's concurrent arrivals
    (canonical within-wave packing);
  * ``"psum"`` aggregation inside shard_map == the local ``"merge"`` fold;
  * the arrival hot path performs NO host transfers after warmup
    (regression guard for the per-arrival host loop it replaced);
  * the refresh policy: ``refresh_every=k`` re-solves W on every k-th
    wave only, with the staleness metric counting waves/samples since;
  * the core factored update (``factored_update``/``factored_solution``)
    matches the batch solve.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r
from repro.data.pipeline import PackedArrivals, pack_arrival_waves
from repro.federated.arrivals import (
    dominant_labels,
    pack_schedule,
    poisson_schedule,
    skewed_schedule,
    trace_schedule,
)
from repro.federated.streaming_engine import (
    StreamConfig,
    StreamingEngine,
    batch_equivalent,
)
from repro.kernels import chol_gram
from repro.kernels.chol_update import live_row_blocks
from repro.kernels.fed3r_stats import BK
from repro.kernels.ref import chol_gram_ref

D, C = 24, 6


def _make_stream(seed, n_waves, lo=8, hi=40, max_clients=3, d=D, n_classes=C):
    rng = np.random.default_rng(seed)
    waves = []
    for _ in range(n_waves):
        wave = []
        for _ in range(int(rng.integers(0, max_clients + 1))):
            n = int(rng.integers(lo, hi))
            wave.append((
                rng.normal(size=(n, d)).astype(np.float32),
                rng.integers(0, n_classes, size=n).astype(np.int32),
            ))
        waves.append(wave)
    if all(not w for w in waves):
        waves[0].append((
            rng.normal(size=(lo, d)).astype(np.float32),
            rng.integers(0, n_classes, size=lo).astype(np.int32),
        ))
    return waves


def _cfg(**kw):
    base = dict(n_classes=C, ridge_lambda=1e-2)
    base.update(kw)
    return StreamConfig(**base)


# ---------------------------------------------------------------------------
# packer
# ---------------------------------------------------------------------------


def test_arrival_packer_shapes_masks_and_clock():
    waves = _make_stream(0, 6)
    p = pack_arrival_waves(waves)
    widths = [len(w) for w in waves]
    sizes = [len(y) for w in waves for _, y in w]
    assert p.n_waves == 6
    assert p.clients_per_wave == max(widths)
    assert p.inputs.shape[2] % 8 == 0 and p.inputs.shape[2] >= max(sizes)
    assert p.n_clients == sum(widths)
    assert p.n_samples == sum(sizes)
    # empty waves / empty slots are all-padding: -1 ids, zero mask
    for t, w in enumerate(waves):
        assert (p.client_ids[t] >= 0).sum() == len(w)
        assert p.mask[t][p.client_ids[t] < 0].sum() == 0.0


def test_arrival_packer_canonical_within_wave():
    waves = _make_stream(1, 4, max_clients=4)
    ids = []
    nxt = 0
    for w in waves:
        ids.append(list(range(nxt, nxt + len(w))))
        nxt += len(w)
    p1 = pack_arrival_waves(waves, client_ids=ids)
    rng = np.random.default_rng(2)
    shuffled, sh_ids = [], []
    for w, wi in zip(waves, ids):
        perm = rng.permutation(len(w))
        shuffled.append([w[i] for i in perm])
        sh_ids.append([wi[i] for i in perm])
    p2 = pack_arrival_waves(shuffled, client_ids=sh_ids)
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(a, b)


def test_arrival_packer_counts_gram_rows():
    """``gram_rows``: real rows, waves × slots × capacity, and the rows of
    the live BK-row blocks of each wave's flat design."""
    from repro.federated.telemetry import Telemetry, set_telemetry

    rng = np.random.default_rng(0)

    def client(n):
        return rng.normal(size=(n, D)).astype(np.float32), np.zeros(n, np.int32)

    # capacity 192: wave 0's slots start at rows 0 and 192, wave 1's at 0
    waves = [[client(5), client(BK + 1)], [client(192)], []]
    prev = set_telemetry(Telemetry())
    try:
        pack_arrival_waves(waves, round_to=64)
        t = set_telemetry(prev)
    except BaseException:
        set_telemetry(prev)
        raise
    rows = {k: t.counter("gram_rows", kind=k).value
            for k in ("real", "packed", "kernel")}
    assert rows == {
        "real": 5 + BK + 1 + 192,
        "packed": 3 * 2 * 192,
        # wave 0: blocks 0 (rows 0-4), 1 and 2 (rows 192-320); wave 1:
        # blocks 0 and 1; wave 2: none
        "kernel": (3 + 2) * BK,
    }


def test_arrival_packer_validates():
    waves = _make_stream(3, 3, max_clients=2)
    with pytest.raises(ValueError):
        pack_arrival_waves([])
    with pytest.raises(ValueError):
        pack_arrival_waves(waves, clients_per_wave=1)
    with pytest.raises(ValueError):
        pack_arrival_waves(waves, max_n=2)
    with pytest.raises(ValueError):
        pack_arrival_waves([[], []])  # no clients in any wave


# ---------------------------------------------------------------------------
# chol_gram kernel (Pallas, interpret mode on CPU)
# ---------------------------------------------------------------------------


def _factor(key, d):
    A = jax.random.normal(key, (d, d), jnp.float32)
    return jnp.linalg.cholesky(A @ A.T + jnp.eye(d))


def _wave_design(key, d, sizes, N, C_, dtype):
    """A packed wave's flat design (P·N rows): slot k's first sizes[k] rows
    are real, the rest of its N rows masked to zero."""
    k2, k3 = jax.random.split(key)
    mask = (np.arange(N)[None, :] < np.asarray(sizes)[:, None]).reshape(-1)
    m = jnp.asarray(mask, jnp.float32)[:, None]
    Z = jax.random.normal(k2, (mask.size, d), jnp.float32) * m
    Y = jax.nn.one_hot(jax.random.randint(k3, (mask.size,), 0, C_), C_) * m
    return Z.astype(dtype), Y.astype(dtype), jnp.asarray(mask, jnp.float32)


@pytest.mark.parametrize("d,n,C_", [
    (16, 30, 3), (65, 129, 7), (24, 7, 5),
    # (slot sizes, N): padded, empty and full slots; N = 3·64 or 5·64 (an
    # odd multiple of 64), so 128-row blocks straddle two slots; d = 300
    # (not a lane multiple, two row tiles), d + C = 307 and 356 (not one)
    (300, ((5, 0, 192, 70), 192), 7),
    (256, ((130, 0, 320), 320), 100),
    (128, ((64, 1, 0, 64), 64), 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chol_gram_kernel_matches_oracle(d, n, C_, dtype, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    L = _factor(k1, d)
    if isinstance(n, int):  # every row real
        Z = jax.random.normal(k2, (n, d), dtype)
        Y = jax.nn.one_hot(jax.random.randint(k3, (n,), 0, C_), C_, dtype=dtype)
        G, B = chol_gram(L, Z, Y)
    else:  # a packed wave: the kernel sweeps its live row blocks
        sizes, N = n
        Z, Y, mask = _wave_design(k2, d, sizes, N, C_, dtype)
        G, B = chol_gram(L, Z, Y, *live_row_blocks(mask))
        Z, Y, n = Z[mask > 0], Y[mask > 0], sum(sizes)
    Gr, Br = chol_gram_ref(L, Z, Y)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=tol, atol=tol * n)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Br), rtol=tol, atol=tol * n)
    assert G.dtype == jnp.float32


def test_chol_gram_kernel_never_reads_dead_row_blocks(rng):
    """NaN in every 128-row block that holds no real row: the sample sweep
    never reads those blocks, so G and B still match the real rows."""
    k1, k2 = jax.random.split(rng)
    d, C_, N = 136, 9, 192
    sizes = (3, 0, 0, 0, 150, 0, 0, 0, 0, 64)  # live blocks 0, 6, 7, 13
    Z, Y, mask = _wave_design(k2, d, sizes, N, C_, jnp.float32)
    blocks, count = live_row_blocks(mask)
    dead = np.ones(mask.size // BK, bool)
    dead[np.asarray(blocks)[: int(count[0])]] = False
    assert np.flatnonzero(~dead).tolist() == [0, 6, 7, 13]
    rows = jnp.asarray(np.repeat(dead, BK))[:, None]
    Zn, Yn = jnp.where(rows, jnp.nan, Z), jnp.where(rows, jnp.nan, Y)
    L = _factor(k1, d)
    G, B = chol_gram(L, Zn, Yn, blocks, count)
    Gr, Br = chol_gram_ref(L, Z[mask > 0], Y[mask > 0])
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Br), rtol=1e-4, atol=1e-2)


def test_chol_gram_kernel_ignores_factor_upper_triangle(rng):
    """Garbage above L's diagonal gives the G of its ``tril``: L is read as
    lower-triangular, which is what lets the factor sweep stop at each row
    tile's last row."""
    k1, k2, k3 = jax.random.split(rng, 3)
    d = 300  # two row tiles of 256
    L = _factor(k1, d)
    junk = jnp.triu(jax.random.normal(k3, (d, d)) * 1e3, 1)
    Z, Y, mask = _wave_design(k2, d, (40, 7), 64, 5, jnp.float32)
    live = live_row_blocks(mask)
    G, B = chol_gram(L + junk, Z, Y, *live)
    G0, B0 = chol_gram(L, Z, Y, *live)
    np.testing.assert_array_equal(np.asarray(G), np.asarray(G0))
    np.testing.assert_array_equal(np.asarray(B), np.asarray(B0))
    Gr, _ = chol_gram_ref(L, Z, Y)
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=1e-4, atol=1e-2)


def test_chol_gram_kernel_handles_empty_arrival_batch():
    """Regression: a 0-row Z must degrade to the pure refactorization."""
    L = jnp.linalg.cholesky(2.0 * jnp.eye(16))
    G, B = chol_gram(L, jnp.zeros((0, 16)), jnp.zeros((0, 4)))
    np.testing.assert_allclose(np.asarray(G), 2.0 * np.eye(16), atol=1e-6)
    assert not np.asarray(B).any()


def test_engine_kernel_path_matches_xla_path():
    # slots padded to 192 rows, so most of each wave's row blocks are dead
    packed = pack_arrival_waves(_make_stream(4, 5), round_to=64, max_n=192)
    _, count = live_row_blocks(jnp.asarray(packed.mask[0].reshape(-1)))
    assert 0 < int(count[0]) < packed.mask[0].size // BK
    xla = StreamingEngine(_cfg(use_kernel=False))
    ker = StreamingEngine(_cfg(use_kernel=True))
    s1, _ = xla.absorb(xla.init(D), packed)
    s2, _ = ker.absorb(ker.init(D), packed)
    np.testing.assert_allclose(np.asarray(s1.W), np.asarray(s2.W),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1.L), np.asarray(s2.L),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# parity with the batch solve at small λ (fp32)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lam", [1e-2, 1e-3])
def test_streaming_matches_batch_solve_at_small_lambda(lam):
    waves = _make_stream(5, 16, lo=40, hi=80, max_clients=3)
    packed = pack_arrival_waves(waves)
    cfg = _cfg(ridge_lambda=lam)
    eng = StreamingEngine(cfg)
    state, _ = eng.absorb(eng.init(D), packed)
    W_batch, stats = batch_equivalent(packed, cfg)
    assert eng.dispatches == 1  # the whole T-wave stream in one dispatch
    err = float(jnp.max(jnp.abs(state.W - W_batch)))
    assert err <= 1e-4, f"factored engine drifted: {err:.2e}"
    assert float(state.n) == float(stats.n) == packed.n_samples


def test_streaming_is_chunk_invariant():
    """Absorbing the stream in segments == absorbing it in one dispatch."""
    packed = pack_arrival_waves(_make_stream(7, 9))
    eng = StreamingEngine(_cfg())
    whole, _ = eng.absorb(eng.init(D), packed)
    state = eng.init(D)
    for lo in (0, 3, 6):
        state, _ = eng.absorb(state, packed.slice_waves(lo, lo + 3))
    assert int(state.wave) == int(whole.wave) == 9
    np.testing.assert_array_equal(np.asarray(whole.L), np.asarray(state.L))
    np.testing.assert_array_equal(np.asarray(whole.W), np.asarray(state.W))


# ---------------------------------------------------------------------------
# arrival-order bit-invariance of the final W
# ---------------------------------------------------------------------------


def test_final_w_bit_invariant_under_concurrent_arrival_permutation():
    waves = _make_stream(8, 6, max_clients=4)
    ids = []
    nxt = 0
    for w in waves:
        ids.append(list(range(nxt, nxt + len(w))))
        nxt += len(w)
    rng = np.random.default_rng(9)
    shuffled, sh_ids = [], []
    for w, wi in zip(waves, ids):
        perm = rng.permutation(len(w))
        shuffled.append([w[i] for i in perm])
        sh_ids.append([wi[i] for i in perm])
    eng = StreamingEngine(_cfg())
    s1, _ = eng.absorb(eng.init(D), pack_arrival_waves(waves, client_ids=ids))
    s2, _ = eng.absorb(
        eng.init(D), pack_arrival_waves(shuffled, client_ids=sh_ids)
    )
    # canonical within-wave packing ⇒ bit-identical state and served W
    np.testing.assert_array_equal(np.asarray(s1.L), np.asarray(s2.L))
    np.testing.assert_array_equal(np.asarray(s1.b), np.asarray(s2.b))
    np.testing.assert_array_equal(np.asarray(s1.W), np.asarray(s2.W))


# ---------------------------------------------------------------------------
# refresh policy + staleness metric
# ---------------------------------------------------------------------------


def test_refresh_policy_and_staleness_trace():
    packed = pack_arrival_waves(_make_stream(10, 8, max_clients=2))
    eng = StreamingEngine(_cfg(refresh_every=3))
    state, trace = eng.absorb(eng.init(D), packed)
    np.testing.assert_array_equal(
        np.asarray(trace.refreshed),
        np.array([False, False, True] * 2 + [False, False]),
    )
    np.testing.assert_array_equal(
        np.asarray(trace.stale_waves), np.array([1, 2, 0, 1, 2, 0, 1, 2])
    )
    # samples-staleness re-accumulates between refreshes
    per_wave = packed.mask.sum(axis=(1, 2))
    assert float(trace.stale_samples[1]) == pytest.approx(per_wave[:2].sum())
    assert float(trace.stale_samples[2]) == 0.0
    # the served W is the wave-6 solve, NOT the final statistics' solve
    W_at_6, _ = batch_equivalent(
        PackedArrivals(*[a[:6] for a in packed]), _cfg()
    )
    np.testing.assert_allclose(np.asarray(state.W), np.asarray(W_at_6),
                               rtol=1e-5, atol=1e-5)
    refreshed = eng.refresh(state)
    W_final, _ = batch_equivalent(packed, _cfg())
    np.testing.assert_allclose(np.asarray(refreshed.W), np.asarray(W_final),
                               rtol=1e-5, atol=1e-5)
    assert int(refreshed.stale_waves) == 0


def test_refresh_on_arrival_never_stale():
    packed = pack_arrival_waves(_make_stream(11, 5))
    eng = StreamingEngine(_cfg(refresh_every=1))
    _, trace = eng.absorb(eng.init(D), packed)
    assert np.asarray(trace.refreshed).all()
    assert not np.asarray(trace.stale_waves).any()
    assert not np.asarray(trace.stale_samples).any()


def test_stream_config_validation():
    from repro.federated.dist import DistConfig

    with pytest.raises(ValueError):
        StreamingEngine(_cfg(refresh_every=0))
    with pytest.raises(ValueError):
        DistConfig(aggregation="psum")  # no axes, no mesh
    with pytest.raises(ValueError):
        DistConfig(aggregation="allgather")


# ---------------------------------------------------------------------------
# mesh mode: psum backend == merge backend
# ---------------------------------------------------------------------------


def test_streaming_psum_matches_merge_on_host_mesh():
    """The dist-layer mesh path (shard_map owned by DistContext) == merge."""
    from repro.federated.dist import DistConfig
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    waves = _make_stream(12, 4, max_clients=4)
    packed = pack_arrival_waves(waves, mesh=mesh)  # wave width padded to dp

    merge_eng = StreamingEngine(_cfg())
    ref, _ = merge_eng.absorb(merge_eng.init(D), packed)

    psum_eng = StreamingEngine(
        _cfg(dist=DistConfig(aggregation="psum", mesh=mesh, donate=False))
    )
    got, _ = psum_eng.absorb(psum_eng.init(D), packed)
    assert psum_eng.dispatches == 1  # the shard_map program is ONE dispatch
    np.testing.assert_allclose(np.asarray(ref.W), np.asarray(got.W),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.L), np.asarray(got.L),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# hot path is transfer-free (regression: per-arrival host loop)
# ---------------------------------------------------------------------------


def test_absorb_hot_path_makes_no_host_transfers():
    packed = pack_arrival_waves(_make_stream(13, 4))
    dev = PackedArrivals(*[jnp.asarray(a) for a in packed])
    eng = StreamingEngine(_cfg())
    state, _ = eng.absorb(eng.init(D), dev)  # warm the trace
    # steady-state arrivals: everything already on device ⇒ zero transfers
    with jax.transfer_guard("disallow"):
        state, _ = eng.absorb(state, dev)
        state, _ = eng.absorb(state, dev)
    assert int(state.wave) == 12


# ---------------------------------------------------------------------------
# factored core state
# ---------------------------------------------------------------------------


def test_factored_update_matches_batch_and_solution_routes():
    rng = np.random.default_rng(14)
    xs = rng.normal(size=(3, 50, D)).astype(np.float32)
    ys = rng.integers(0, C, size=(3, 50)).astype(np.int32)
    st = fed3r.init_factored(D, C, 1e-2)
    stats = fed3r.init_stats(D, C)
    for x, y in zip(xs, ys):
        st = fed3r.factored_update(st, jnp.asarray(x), jnp.asarray(y))
        stats = fed3r.merge(stats, fed3r.client_stats(jnp.asarray(x), jnp.asarray(y), C))
    W_batch = fed3r.solve(stats, 1e-2)
    np.testing.assert_allclose(np.asarray(fed3r.factored_solution(st)),
                               np.asarray(W_batch), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# arrival schedules
# ---------------------------------------------------------------------------


def test_poisson_schedule_each_client_arrives_once():
    sched = poisson_schedule(40, 12, rate=3.0, seed=0)
    flat = [k for wave in sched for k in wave]
    assert sorted(flat) == list(range(40))  # drain ⇒ exact partition
    assert len(sched) == 12
    sched2 = poisson_schedule(40, 12, rate=3.0, seed=0)
    assert sched == sched2  # seeded determinism
    undrained = poisson_schedule(40, 3, rate=1.0, seed=0, drain=False)
    assert len({k for w in undrained for k in w}) < 40


def test_trace_schedule_replays_arrival_log():
    sched = trace_schedule([2, 0, 2, 5])
    assert sched == [[1], [], [0, 2], [], [], [3]]
    assert len(trace_schedule([1, 0], n_waves=4)) == 4
    with pytest.raises(ValueError):
        trace_schedule([3], n_waves=2)


def test_skewed_schedule_orders_by_dominant_label():
    dom = np.array([3, 0, 3, 1, 0, 2, 1, 2])
    strict = skewed_schedule(dom, 4, skew=1.0, seed=0)
    seen = [int(dom[k]) for wave in strict for k in wave]
    assert seen == sorted(seen)  # skew=1 ⇒ label-sorted arrivals
    flat = sorted(k for wave in strict for k in wave)
    assert flat == list(range(8))
    iid = skewed_schedule(dom, 4, skew=0.0, seed=0)
    assert sorted(k for w in iid for k in w) == list(range(8))


def test_pack_schedule_roundtrips_dataset(fed_stream_data):
    fed = fed_stream_data
    sched = skewed_schedule(dominant_labels(fed), 5, skew=1.0, seed=0)
    packed = pack_schedule(fed, sched)
    assert packed.n_waves == 5
    assert packed.n_clients == fed.n_clients
    assert packed.n_samples == int(fed.client_sizes().sum())
    eng = StreamingEngine(_cfg(n_classes=fed.n_classes))
    state, _ = eng.absorb(eng.init(fed.features.shape[-1]), packed)
    stats = fed3r.init_stats(fed.features.shape[-1], fed.n_classes)
    for k in range(fed.n_clients):
        cd = fed.client(k)
        stats = fed3r.merge(stats, fed3r.client_stats(
            jnp.asarray(cd.features), jnp.asarray(cd.labels), fed.n_classes
        ))
    np.testing.assert_allclose(np.asarray(state.W),
                               np.asarray(fed3r.solve(stats, 1e-2)),
                               rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def fed_stream_data():
    from repro.data import make_federated_features

    fed, _ = make_federated_features(
        seed=0, n=800, d=D, n_classes=C, n_clients=10, alpha=0.5, noise=1.5
    )
    return fed

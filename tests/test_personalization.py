"""Personalization engine: parity, α=0 bitwise, invariance, serving, secure-agg.

The engine's contract (federated/personalization.py):
  * K personalized heads solve in ONE jitted dispatch, matching the
    per-client re-solve loop (K+1 dispatches) to fp32 tolerance at the
    same α_k;
  * an α of exactly 0 reproduces the global ``factored_solution``
    BITWISE — engine, core API, and padded cohort slots alike;
  * the packed cohort (and hence the batched head solve) is BIT-identical
    under permutation of the request order (canonical packing);
  * the grid-over-heads Pallas kernel matches its pure-jnp oracle;
  * α selection happens inside the dispatch via the held-out ridge score;
  * secure aggregation composes: masked per-client uploads still sum to
    the unmasked cohort statistics, so the global base state — and every
    head derived from it — is unchanged;
  * the serving layer's LRU head cache evicts by recency, dirty-marks on
    stream advance, and answers per-tenant vs global by data availability.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r
from repro.data.pipeline import pack_personal_cohort
from repro.federated.personalization import (
    PersonalizationEngine,
    PersonalizeConfig,
    ReferencePersonalizedLoop,
    cohort_stats,
)
from repro.federated.secure_agg import mask_statistics, secure_aggregate
from repro.kernels import batched_chol_gram
from repro.kernels.ref import batched_chol_gram_ref
from repro.launch.serve_heads import HeadCache

D, C, LAM = 24, 6, 1e-2


def _make_clients(seed, K, lo=20, hi=60, d=D, n_classes=C):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(K):
        n = int(rng.integers(lo, hi))
        out.append((
            rng.normal(size=(n, d)).astype(np.float32),
            rng.integers(0, n_classes, size=n).astype(np.int32),
        ))
    return out


def _state_from(packed, lam=LAM):
    stats = cohort_stats(packed, C)
    L = jnp.linalg.cholesky(stats.A + lam * jnp.eye(D, dtype=jnp.float32))
    return fed3r.Fed3RFactored(L=L, b=stats.b)


def _cfg(**kw):
    base = dict(n_classes=C, alpha_grid=(0.0, 0.5, 1.0, 2.0))
    base.update(kw)
    return PersonalizeConfig(**base)


# ---------------------------------------------------------------------------
# packer
# ---------------------------------------------------------------------------


def test_personal_cohort_packer_shapes_masks_holdout():
    clients = _make_clients(0, 5)
    p = pack_personal_cohort(clients, cohort_size=8, holdout_frac=0.25)
    sizes = [len(y) for _, y in clients]
    assert p.cohort == 8
    assert p.n_clients == 5
    assert p.n_samples == sum(sizes)
    assert p.inputs.shape[1] % 8 == 0 and p.inputs.shape[1] >= max(sizes)
    # empty slots: -1 ids, all-zero masks
    assert (p.client_ids == -1).sum() == 3
    assert p.mask[p.client_ids == -1].sum() == 0.0
    # holdout ⊆ mask, roughly the requested fraction, never sample 0
    assert np.all(p.holdout <= p.mask)
    assert p.holdout[:, 0].sum() == 0.0
    for k in range(5):
        n_k = sizes[k] if p.client_ids[k] == k else int(p.mask[k].sum())
        got = int(p.holdout[k].sum())
        assert got == len(np.arange(3, n_k, 4))


def test_personal_cohort_packer_validates():
    clients = _make_clients(1, 3)
    with pytest.raises(ValueError):
        pack_personal_cohort(clients, cohort_size=2)
    with pytest.raises(ValueError):
        pack_personal_cohort(clients, holdout_frac=1.0)
    with pytest.raises(ValueError):
        pack_personal_cohort(clients, max_n=2)
    with pytest.raises(ValueError):
        pack_personal_cohort([])


def test_personal_cohort_packer_canonical_order():
    clients = _make_clients(2, 6)
    ids = list(range(6))
    p1 = pack_personal_cohort(clients, client_ids=ids)
    perm = [3, 0, 5, 1, 4, 2]
    p2 = pack_personal_cohort(
        [clients[i] for i in perm], client_ids=[ids[i] for i in perm]
    )
    for a, b in zip(p1, p2):
        np.testing.assert_array_equal(a, b)


def test_tiny_client_keeps_a_train_sample():
    clients = [(np.ones((1, D), np.float32), np.zeros((1,), np.int32))]
    p = pack_personal_cohort(clients, holdout_frac=0.5)
    assert p.holdout.sum() == 0.0  # n_k < 2: never hold out the only sample
    # n_k >= 2 but below the stride still holds out exactly ONE sample
    # (its last), so small tenants are swept rather than pinned to grid[0]
    clients = [(np.ones((3, D), np.float32), np.zeros((3,), np.int32))]
    p = pack_personal_cohort(clients, holdout_frac=0.25)  # stride 4 > 3
    assert p.holdout[0].sum() == 1.0
    assert p.holdout[0, 2] == 1.0 and p.holdout[0, 0] == 0.0


# ---------------------------------------------------------------------------
# batched grid-over-heads kernel (Pallas, interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K,n,d,C_", [(3, 30, 16, 3), (2, 129, 65, 7), (4, 7, 24, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_chol_gram_kernel_matches_oracle(K, n, d, C_, dtype, rng):
    k1, k2, k3 = jax.random.split(rng, 3)
    A = jax.random.normal(k1, (d, d), jnp.float32)
    L = jnp.linalg.cholesky(A @ A.T + jnp.eye(d))
    Z = jax.random.normal(k2, (K, n, d), dtype)
    Y = jax.nn.one_hot(jax.random.randint(k3, (K, n), 0, C_), C_, dtype=dtype)
    G, B = batched_chol_gram(L, Z, Y)
    Gr, Br = batched_chol_gram_ref(L, Z, Y)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(G), np.asarray(Gr), rtol=tol, atol=tol * n)
    np.testing.assert_allclose(np.asarray(B), np.asarray(Br), rtol=tol, atol=tol * n)
    assert G.shape == (K, d, d) and B.shape == (K, d, C_)
    assert G.dtype == jnp.float32


def test_batched_chol_gram_kernel_handles_empty_cohort_batch():
    L = jnp.linalg.cholesky(2.0 * jnp.eye(16))
    G, B = batched_chol_gram(L, jnp.zeros((3, 0, 16)), jnp.zeros((3, 0, 4)))
    np.testing.assert_allclose(
        np.asarray(G), np.broadcast_to(2.0 * np.eye(16), (3, 16, 16)), atol=1e-6
    )
    assert not np.asarray(B).any()


def test_engine_kernel_path_matches_xla_path():
    packed = pack_personal_cohort(_make_clients(3, 6))
    state = _state_from(packed)
    xla = PersonalizationEngine(_cfg(use_kernel=False))
    ker = PersonalizationEngine(_cfg(use_kernel=True))
    h1 = xla.solve_heads(state, packed)
    h2 = ker.solve_heads(state, packed)
    np.testing.assert_array_equal(np.asarray(h1.alpha), np.asarray(h2.alpha))
    np.testing.assert_allclose(np.asarray(h1.W), np.asarray(h2.W),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# α = 0 ⇒ the global factored_solution, bitwise
# ---------------------------------------------------------------------------


def test_alpha_zero_engine_is_factored_solution_bitwise():
    packed = pack_personal_cohort(_make_clients(4, 5), cohort_size=8)
    state = _state_from(packed)
    eng = PersonalizationEngine(_cfg(alpha_grid=(0.0,)))
    heads = eng.solve_heads(state, packed)
    W_g = np.asarray(fed3r.factored_solution(state))
    assert eng.dispatches == 1
    # every head — real AND padded slots — is exactly the global solve
    for k in range(packed.cohort):
        np.testing.assert_array_equal(np.asarray(heads.W[k]), W_g)


def test_alpha_zero_core_api_is_factored_solution_bitwise():
    packed = pack_personal_cohort(_make_clients(5, 3))
    state = _state_from(packed)
    cs = fed3r.client_stats(
        jnp.asarray(packed.inputs[1]), jnp.asarray(packed.labels[1]), C,
        jnp.asarray(packed.mask[1]),
    )
    W0 = fed3r.personalized_solution(state, cs, 0.0)
    np.testing.assert_array_equal(
        np.asarray(W0), np.asarray(fed3r.factored_solution(state))
    )
    # and with α > 0 it visibly moves off the global head
    W1 = fed3r.personalized_solution(state, cs, 4.0)
    assert float(jnp.max(jnp.abs(W1 - W0))) > 1e-4


def test_alpha_zero_rows_of_mixed_cohort_are_bitwise_global():
    packed = pack_personal_cohort(_make_clients(6, 6))
    state = _state_from(packed)
    eng = PersonalizationEngine(_cfg())
    alphas = jnp.asarray([0.0, 2.0, 0.0, 1.0, 0.0, 0.5])
    heads = eng.solve_at(state, packed, alphas)
    assert eng.dispatches == 1  # the fixed-α batched solve is one dispatch
    W_g = np.asarray(fed3r.factored_solution(state))
    for k, a in enumerate(np.asarray(alphas)):
        if a == 0.0:
            np.testing.assert_array_equal(np.asarray(heads.W[k]), W_g)
        else:
            assert float(np.max(np.abs(np.asarray(heads.W[k]) - W_g))) > 1e-5


def test_batched_personalized_solution_matches_per_client():
    packed = pack_personal_cohort(_make_clients(7, 4))
    state = _state_from(packed)
    A_k, b_k = [], []
    for k in range(4):
        cs = fed3r.client_stats(
            jnp.asarray(packed.inputs[k]), jnp.asarray(packed.labels[k]), C,
            jnp.asarray(packed.mask[k]),
        )
        A_k.append(cs.A)
        b_k.append(cs.b)
    alphas = jnp.asarray([0.0, 1.0, 2.0, 0.5])
    W = fed3r.batched_personalized_solution(
        state, jnp.stack(A_k), jnp.stack(b_k), alphas
    )
    for k in range(4):
        cs = fed3r.Fed3RStats(A=A_k[k], b=b_k[k], n=jnp.zeros(()))
        np.testing.assert_allclose(
            np.asarray(W[k]),
            np.asarray(fed3r.personalized_solution(state, cs, alphas[k])),
            rtol=1e-5, atol=1e-6,
        )


# ---------------------------------------------------------------------------
# engine vs per-client reference loop (dispatch shape + parity)
# ---------------------------------------------------------------------------


def test_engine_matches_reference_loop_at_same_alphas():
    packed = pack_personal_cohort(_make_clients(8, 8, lo=30, hi=70))
    state = _state_from(packed)
    cfg = _cfg()
    eng = PersonalizationEngine(cfg)
    heads = eng.solve_heads(state, packed)
    ref = ReferencePersonalizedLoop(cfg)
    _, W_ref = ref.solve_at(state, packed, np.asarray(heads.alpha))
    assert eng.dispatches == 1
    assert ref.dispatches == packed.cohort + 1  # K re-solves + the global head
    err = float(jnp.max(jnp.abs(heads.W - W_ref)))
    assert err <= 1e-5, f"engine drifted from per-client re-solves: {err:.2e}"


def test_cohort_permutation_bit_invariance_of_batched_solve():
    clients = _make_clients(9, 7)
    ids = list(range(7))
    perm = [4, 1, 6, 0, 2, 5, 3]
    state = _state_from(pack_personal_cohort(clients, client_ids=ids))
    eng = PersonalizationEngine(_cfg())
    h1 = eng.solve_heads(state, pack_personal_cohort(clients, client_ids=ids))
    h2 = eng.solve_heads(state, pack_personal_cohort(
        [clients[i] for i in perm], client_ids=[ids[i] for i in perm]
    ))
    np.testing.assert_array_equal(np.asarray(h1.client_ids), np.asarray(h2.client_ids))
    np.testing.assert_array_equal(np.asarray(h1.alpha), np.asarray(h2.alpha))
    np.testing.assert_array_equal(np.asarray(h1.W), np.asarray(h2.W))


def test_alpha_selection_minimizes_heldout_error():
    """The default sweep must pick the grid argmin of the held-out 0/1
    error of the SERVED (normalized) candidate head — verified against a
    by-hand sweep outside the engine."""
    clients = _make_clients(10, 5, lo=40, hi=80)
    packed = pack_personal_cohort(clients, holdout_frac=0.25)
    state = _state_from(packed)
    grid = (0.0, 0.5, 1.0, 2.0, 4.0)
    eng = PersonalizationEngine(_cfg(alpha_grid=grid, selection="error"))
    heads = eng.solve_heads(state, packed)
    for k in range(packed.cohort):
        m = packed.mask[k]
        ho = packed.holdout[k]
        tr = m * (1.0 - ho)
        z_tr, y_tr, _ = fed3r.masked_design(
            jnp.asarray(packed.inputs[k]), jnp.asarray(packed.labels[k]), C,
            jnp.asarray(tr),
        )
        z_ho, _, _ = fed3r.masked_design(
            jnp.asarray(packed.inputs[k]), jnp.asarray(packed.labels[k]), C,
            jnp.asarray(ho),
        )
        errs = []
        for a in grid:
            G = state.L @ state.L.T + a * (z_tr.T @ z_tr)
            W = jax.scipy.linalg.cho_solve(
                (jnp.linalg.cholesky(G), True), state.b + a * (z_tr.T @ y_tr)
            )
            W = W / jnp.maximum(jnp.linalg.norm(W, axis=0, keepdims=True), 1e-12)
            pick = jnp.argmax(z_ho @ W, axis=-1)
            errs.append(float(jnp.sum(
                jnp.asarray(ho) * (pick != jnp.asarray(packed.labels[k]))
            )))
        assert float(heads.alpha[k]) == grid[int(np.argmin(errs))]
        assert float(heads.score[k]) == pytest.approx(min(errs))


def test_alpha_selection_minimizes_heldout_sse():
    """selection="sse" picks the grid argmin of the raw held-out ridge
    residual — verified against a by-hand sweep outside the engine."""
    clients = _make_clients(10, 5, lo=40, hi=80)
    packed = pack_personal_cohort(clients, holdout_frac=0.25)
    state = _state_from(packed)
    grid = (0.0, 0.5, 1.0, 2.0, 4.0)
    eng = PersonalizationEngine(_cfg(alpha_grid=grid, selection="sse"))
    heads = eng.solve_heads(state, packed)
    for k in range(packed.cohort):
        m = packed.mask[k]
        ho = packed.holdout[k]
        tr = m * (1.0 - ho)
        z_tr, y_tr, _ = fed3r.masked_design(
            jnp.asarray(packed.inputs[k]), jnp.asarray(packed.labels[k]), C,
            jnp.asarray(tr),
        )
        z_ho, y_ho, _ = fed3r.masked_design(
            jnp.asarray(packed.inputs[k]), jnp.asarray(packed.labels[k]), C,
            jnp.asarray(ho),
        )
        scores = []
        for a in grid:
            G = state.L @ state.L.T + a * (z_tr.T @ z_tr)
            W = jax.scipy.linalg.cho_solve(
                (jnp.linalg.cholesky(G), True), state.b + a * (z_tr.T @ y_tr)
            )
            scores.append(float(jnp.sum((z_ho @ W - y_ho) ** 2)))
        assert float(heads.alpha[k]) == grid[int(np.argmin(scores))]
        assert float(heads.score[k]) == pytest.approx(min(scores), rel=1e-4)


def test_config_validation():
    with pytest.raises(ValueError):
        PersonalizeConfig(n_classes=C, alpha_grid=())
    with pytest.raises(ValueError):
        PersonalizeConfig(n_classes=C, alpha_grid=(0.0, -1.0))
    with pytest.raises(ValueError):
        PersonalizeConfig(n_classes=C, selection="accuracy")


def test_personalization_recovers_tenant_concept_drift():
    """Tenants whose label concepts DISAGREE with the federation (per-tenant
    label swaps — user-specific tastes) must get large-α personalized heads
    that beat the global average-of-concepts head on their own data, while
    aligned tenants may keep α = 0 (the bitwise global head)."""
    from repro.data.pipeline import make_federated_features

    fed, _ = make_federated_features(
        seed=11, n=4000, d=D, n_classes=C, n_clients=10, alpha=0.3, noise=2.0
    )
    clients, eval_xy = [], []
    for k in range(fed.n_clients):
        cd = fed.client(k)
        labels = np.asarray(cd.labels)
        if k % 2 == 1:  # every other tenant swaps two class labels
            rng = np.random.default_rng((11, k))
            i, j = rng.choice(C, size=2, replace=False)
            perm = np.arange(C)
            perm[[i, j]] = perm[[j, i]]
            labels = perm[labels]
        half = max(cd.n // 2, 1)
        clients.append((cd.features[:half], labels[:half]))
        eval_xy.append((cd.features[half:], labels[half:]))
    packed = pack_personal_cohort(clients, client_ids=list(range(fed.n_clients)))
    stats = cohort_stats(packed, C)
    L = jnp.linalg.cholesky(stats.A + LAM * jnp.eye(D, dtype=jnp.float32))
    state = fed3r.Fed3RFactored(L=L, b=stats.b)
    eng = PersonalizationEngine(_cfg(alpha_grid=(0.0, 1.0, 4.0, 16.0, 64.0)))
    heads = eng.solve_heads(state, packed)
    W_g = fed3r.factored_solution(state)
    acc_p, acc_g = [], []
    for k, (x, y) in enumerate(eval_xy):
        if len(y) == 0:
            continue
        x, y = jnp.asarray(x), jnp.asarray(np.asarray(y))
        acc_p.append(float(fed3r.accuracy(heads.W[k], x, y)))
        acc_g.append(float(fed3r.accuracy(W_g, x, y)))
    assert np.mean(acc_p) > np.mean(acc_g) + 0.05


# ---------------------------------------------------------------------------
# secure aggregation interop: masked per-client uploads, unmasked cohort sum
# ---------------------------------------------------------------------------


def test_masked_client_stats_sum_to_unmasked_cohort():
    clients = _make_clients(12, 5)
    packed = pack_personal_cohort(clients, client_ids=list(range(5)))
    per_client = [
        fed3r.client_stats(
            jnp.asarray(packed.inputs[k]), jnp.asarray(packed.labels[k]), C,
            jnp.asarray(packed.mask[k]),
        )
        for k in range(5)
    ]
    cohort = list(range(5))
    masked = [
        mask_statistics(s, k, cohort, seed=7) for k, s in enumerate(per_client)
    ]
    # individual uploads are actually masked...
    assert float(jnp.max(jnp.abs(masked[0].A - per_client[0].A))) > 1.0
    # ...but the server's sum is the exact unmasked cohort statistics
    agg = secure_aggregate(masked)
    plain = cohort_stats(packed, C)
    np.testing.assert_allclose(np.asarray(agg.A), np.asarray(plain.A),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(agg.b), np.asarray(plain.b),
                               rtol=1e-5, atol=1e-3)
    # and the personalized heads built on the secure-agg base agree
    lam_eye = LAM * jnp.eye(D, dtype=jnp.float32)
    st_plain = fed3r.Fed3RFactored(
        L=jnp.linalg.cholesky(plain.A + lam_eye), b=plain.b
    )
    st_agg = fed3r.Fed3RFactored(
        L=jnp.linalg.cholesky(agg.A + lam_eye), b=agg.b
    )
    eng = PersonalizationEngine(_cfg())
    alphas = jnp.ones((packed.cohort,))
    h1 = eng.solve_at(st_plain, packed, alphas)
    h2 = eng.solve_at(st_agg, packed, alphas)
    np.testing.assert_allclose(np.asarray(h1.W), np.asarray(h2.W),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# serving layer: LRU head cache + per-tenant vs global query modes
# ---------------------------------------------------------------------------


def test_head_cache_lru_eviction_and_counters():
    cache = HeadCache(capacity=2)
    W = jnp.zeros((D, C))
    assert cache.get(1) is None  # miss
    cache.put(1, W)
    cache.put(2, W)
    assert cache.get(1) is not None  # hit refreshes recency of 1
    cache.put(3, W)  # evicts 2 (LRU), not 1
    assert cache.get(2) is None
    assert cache.get(1) is not None and cache.get(3) is not None
    assert cache.lru_evictions == 1
    assert cache.hits == 3 and cache.misses == 2


def test_head_cache_dirty_marking_on_stream_advance():
    cache = HeadCache(capacity=4)
    cache.put(1, jnp.zeros((D, C)))
    assert cache.get(1) is not None
    cache.advance()  # the global state moved: every cached head is stale
    assert cache.get(1) is None
    assert cache.stale_evictions == 1
    cache.put(1, jnp.ones((D, C)))  # re-solved against the new version
    assert cache.get(1) is not None


def test_head_server_batched_query_modes_and_single_dispatch():
    from repro.data.pipeline import make_federated_features
    from repro.federated.streaming_engine import StreamConfig, StreamingEngine
    from repro.federated.arrivals import pack_schedule, poisson_schedule
    from repro.launch.serve_heads import HeadServer

    fed, _ = make_federated_features(
        seed=13, n=900, d=D, n_classes=C, n_clients=8, alpha=0.3, noise=2.0
    )
    server = HeadServer(
        StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM)),
        PersonalizationEngine(_cfg()),
        fed,
        cache_capacity=4,
        cohort_round_to=4,
    )
    server.init(D)
    packed = pack_schedule(fed, poisson_schedule(fed.n_clients, 4, 3.0, seed=0))
    server.absorb(packed)
    assert server.cache.version == 1

    # burst: 3 known tenants (one repeated) + 1 unknown tenant id
    cids = [0, 3, 0, 999]
    xs = np.stack([fed.client(0).features[0], fed.client(3).features[0],
                   fed.client(0).features[1], fed.client(3).features[1]])
    scores, rep = server.query(cids, xs)
    assert scores.shape == (4, C)
    assert rep["modes"] == ["per-tenant", "per-tenant", "per-tenant", "global"]
    assert rep["solved_now"] == 2  # tenants {0, 3}, ONE batched dispatch
    assert server.pers.dispatches == 1
    # second burst on the same tenants: pure cache hits, no new dispatch
    _, rep2 = server.query(cids, xs)
    assert rep2["solved_now"] == 0
    assert server.pers.dispatches == 1
    # the stream advances ⇒ cached heads dirty ⇒ the next burst re-solves
    server.absorb(packed)
    _, rep3 = server.query(cids, xs)
    assert rep3["solved_now"] == 2
    assert server.pers.dispatches == 2
    assert server.cache.stale_evictions >= 2

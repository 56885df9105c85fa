"""Asynchronous round engine: chaos parity, staleness, dropout, secure agg.

The engine's contract (federated/async_engine.py):
  * merge-on-arrival is bitwise-equivalent to the synchronous barrier for
    exact-once delivery, under every chaos fault type (drop-with-
    retransmit, duplication, reordering, transient delay) — statistics
    sums are order-invariant (paper §4.3) and the slot/retire design
    keeps the fp32 operand sequence identical;
  * uploads landing after the staleness window retire are rejected
    ("stale"), duplicates are deduped without re-folding;
  * ClientHealth demotes persistent stragglers after ``demote_after``
    blown deadlines and re-admits them after ``cooldown`` rounds;
  * secure mode: masked mod-2³² integer slots with orphan-mask recovery —
    the retired W with 1..K-1 dropped clients is BITWISE the W of a
    survivor-only cohort with unmasked payloads (same shared scales);
  * the retire fold is the same algebra as the streaming engine's
    ``absorb_stats`` round-granular entry.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r
from repro.federated import secure_agg
from repro.federated.arrivals import (
    ChaosSpec,
    UploadEvent,
    chaos_timeline,
    latency_profile,
    timeline_from_json,
    timeline_to_json,
)
from repro.data.pipeline import make_federated_features
from repro.federated.async_engine import (
    AsyncConfig,
    AsyncRoundEngine,
    ClientHealth,
    client_payloads,
    run_adaptive_rounds,
    run_chaos_timeline,
)
from repro.federated.compress import WireFormat, cohort_quantize_int8
from repro.federated.costs import CostModel
from repro.federated.dist import shard_cohort
from repro.federated.streaming_engine import StreamConfig, StreamingEngine

D, C = 16, 4
N_CLIENTS = 10
COHORT = 4
LAMBDA = 1e-2


def _payloads(seed=0, n_clients=N_CLIENTS, d=D, lo=20, hi=40):
    rng = np.random.default_rng(seed)
    out = {}
    for k in range(n_clients):
        n = int(rng.integers(lo, hi))
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = rng.integers(0, C, size=n).astype(np.int32)
        out[k] = fed3r.client_stats(jnp.asarray(x), jnp.asarray(y), C)
    return out


def _cohorts(n_rounds, seed=0, n_clients=N_CLIENTS, k=COHORT):
    return [
        sorted(
            np.random.default_rng((seed, r))
            .choice(n_clients, size=k, replace=False)
            .tolist()
        )
        for r in range(n_rounds)
    ]


def _engine(synchronous=False, **kw):
    kw.setdefault("staleness_rounds", 3)
    kw.setdefault("early_close", False)
    kw.setdefault("demote_after", 10_000)
    return AsyncRoundEngine(AsyncConfig(
        n_classes=C, ridge_lambda=LAMBDA, cohort=COHORT,
        deadline=1.0, synchronous=synchronous, **kw,
    ))


FAULTS = {
    "drop": ChaosSpec(drop=0.5, rto=0.1, max_attempts=6, seed=3),
    "duplicate": ChaosSpec(duplicate=0.6, seed=3),
    "reorder": ChaosSpec(reorder=0.9, rto=0.2, seed=3),
    "delay": ChaosSpec(delay=0.5, delay_factor=2.0, seed=3),
    "all": ChaosSpec(drop=0.3, duplicate=0.3, reorder=0.5, delay=0.2,
                     delay_factor=2.0, rto=0.1, max_attempts=6, seed=3),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_chaos_parity_bitwise_per_fault_type(fault):
    payloads = _payloads()
    cohorts = _cohorts(5)
    latency = latency_profile(N_CLIENTS, 0.2, straggler_factor=3.0,
                              base=0.3, jitter=0.5, seed=1)
    events = chaos_timeline(cohorts, latency, FAULTS[fault])

    def pf(c, r):
        return payloads[c]

    ea = _engine(synchronous=False)
    sa, ra = run_chaos_timeline(ea, ea.init(D), cohorts, events, pf)
    es = _engine(synchronous=True)
    ss, _ = run_chaos_timeline(es, es.init(D), cohorts, events, pf)

    assert ra["dropped_uploads"] == 0, "chaos tail escaped the staleness window"
    np.testing.assert_array_equal(np.asarray(sa.W), np.asarray(ss.W))
    np.testing.assert_array_equal(np.asarray(sa.L), np.asarray(ss.L))
    if fault in ("duplicate", "all"):
        assert ra["duplicates"] > 0  # dedup actually exercised


# Seeded chaos schedules: 4 fault profiles x 2 seeds.  Each profile stresses
# one fault mode; rto/max_attempts bound the retransmit tail so every upload
# lands inside the staleness window (exact-once delivery, the precondition
# of the parity claim).
CHAOS_D, CHAOS_C = 32, 8
CHAOS_N_CLIENTS = 16
CHAOS_COHORT = 6
CHAOS_N_ROUNDS = 6
CHAOS_DEADLINE = 1.0
CHAOS_STALENESS = 4
CHAOS_PROFILES = {
    "drop_heavy": ChaosSpec(drop=0.5, duplicate=0.0, reorder=0.0, delay=0.0,
                            rto=0.1, max_attempts=6),
    "duplicate_heavy": ChaosSpec(drop=0.1, duplicate=0.6, reorder=0.1, delay=0.0,
                                 rto=0.1, max_attempts=4),
    "reorder_heavy": ChaosSpec(drop=0.1, duplicate=0.1, reorder=0.8, delay=0.0,
                               rto=0.1, max_attempts=4),
    "delay_heavy": ChaosSpec(drop=0.1, duplicate=0.1, reorder=0.2, delay=0.4,
                             delay_factor=2.0, rto=0.1, max_attempts=4),
}


@pytest.fixture(scope="module")
def chaos_payloads():
    fed, _ = make_federated_features(
        seed=7, n=1200, d=CHAOS_D, n_classes=CHAOS_C,
        n_clients=CHAOS_N_CLIENTS, alpha=0.3, noise=2.0,
    )
    return client_payloads(fed, CHAOS_C)


def _chaos_engine(synchronous):
    return AsyncRoundEngine(AsyncConfig(
        n_classes=CHAOS_C, ridge_lambda=LAMBDA, cohort=CHAOS_COHORT,
        deadline=CHAOS_DEADLINE, staleness_rounds=CHAOS_STALENESS,
        synchronous=synchronous, early_close=False, demote_after=10_000,
    ))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("profile", sorted(CHAOS_PROFILES))
def test_chaos_schedule_async_bitwise_equals_barrier(profile, seed, chaos_payloads):
    """One seeded fault schedule replayed through the async engine and the
    synchronous barrier: W bitwise equal, no upload dropped.  On failure
    the message carries the schedule as JSON (``timeline_from_json``
    replays it)."""
    latency = latency_profile(
        CHAOS_N_CLIENTS, 0.2, straggler_factor=4.0, base=0.3, jitter=0.5, seed=seed
    )
    cohorts = [
        sorted(
            np.random.default_rng((seed, r, 0xC0407))
            .choice(CHAOS_N_CLIENTS, size=CHAOS_COHORT, replace=False)
            .tolist()
        )
        for r in range(CHAOS_N_ROUNDS)
    ]
    spec = ChaosSpec(**{**CHAOS_PROFILES[profile].__dict__, "seed": seed})
    events = chaos_timeline(cohorts, latency, spec)
    schedule = timeline_to_json(cohorts, latency, spec, events)

    def pf(c, r):
        return chaos_payloads[c]

    ea = _chaos_engine(synchronous=False)
    sa, ra = run_chaos_timeline(ea, ea.init(CHAOS_D), cohorts, events, pf)
    es = _chaos_engine(synchronous=True)
    ss, _ = run_chaos_timeline(es, es.init(CHAOS_D), cohorts, events, pf)

    assert ra["dropped_uploads"] == 0, (
        f"{ra['dropped_uploads']} uploads fell outside the staleness window; "
        f"schedule: {schedule}"
    )
    assert np.array_equal(np.asarray(sa.W), np.asarray(ss.W)), (
        f"W diverged, max abs diff "
        f"{np.abs(np.asarray(sa.W) - np.asarray(ss.W)).max():.3e}; "
        f"schedule: {schedule}"
    )


def test_stale_upload_rejected_and_never_folds():
    payloads = _payloads()
    eng = _engine(staleness_rounds=0)
    state = eng.init(D)
    eng.begin_round(0, [0, 1], 0.0)
    state, s = eng.deliver(state, UploadEvent(0.1, 0, 0, 0), payloads[0])
    assert s == "folded"
    state = eng.close_round(state, 0, now=1.0)  # staleness 0: retires at once
    W_before = np.asarray(state.W)
    state, s = eng.deliver(state, UploadEvent(1.5, 0, 1, 0), payloads[1])
    assert s == "stale"
    assert eng.stale_rejected == 1
    np.testing.assert_array_equal(np.asarray(state.W), W_before)


def test_duplicate_deduped_state_unchanged():
    payloads = _payloads()
    eng = _engine()
    state = eng.init(D)
    eng.begin_round(0, [0, 1], 0.0)
    state, _ = eng.deliver(state, UploadEvent(0.1, 0, 0, 0), payloads[0])
    snap = np.asarray(state.A_slots)
    state, s = eng.deliver(state, UploadEvent(0.2, 0, 0, 1), payloads[0])
    assert s == "duplicate"
    assert eng.duplicates == 1
    np.testing.assert_array_equal(np.asarray(state.A_slots), snap)


def test_late_fold_inside_staleness_window_counts():
    payloads = _payloads()
    eng = _engine(staleness_rounds=2)
    state = eng.init(D)
    eng.begin_round(0, [0, 1], 0.0)
    state, _ = eng.deliver(state, UploadEvent(0.1, 0, 0, 0), payloads[0])
    state = eng.close_round(state, 0, now=1.0)
    state, s = eng.deliver(state, UploadEvent(1.5, 0, 1, 0), payloads[1])
    assert s == "late"
    assert eng.late_folds == 1
    state = eng.drain(state)
    # both uploads made it into the retired sums
    assert float(state.n) == pytest.approx(
        float(payloads[0].n) + float(payloads[1].n)
    )


def test_client_health_demotes_and_readmits():
    h = ClientHealth(demote_after=2, cooldown=3)
    h.missed(7, 0)
    assert h.is_eligible(7, 1)
    h.missed(7, 1)
    assert 7 in h.demoted
    assert not h.is_eligible(7, 2)
    assert not h.is_eligible(7, 3)
    assert h.is_eligible(7, 4)  # cooldown elapsed: probation
    h.on_time(7)
    assert 7 not in h.demoted
    assert h.is_eligible(7, 5)


def test_adaptive_rounds_demote_persistent_straggler():
    payloads = _payloads()
    latency = latency_profile(N_CLIENTS, 0.0, base=0.2, jitter=0.2, seed=2)
    latency[3] = 50.0  # client 3 never makes any deadline
    eng = AsyncRoundEngine(AsyncConfig(
        n_classes=C, ridge_lambda=LAMBDA, cohort=N_CLIENTS,
        deadline=1.0, staleness_rounds=2, demote_after=2, cooldown=100,
    ))
    _, rep = run_adaptive_rounds(
        eng, eng.init(D), N_CLIENTS, N_CLIENTS, 8, latency,
        ChaosSpec(seed=0), lambda c, r: payloads[c], seed=5,
    )
    assert 3 in rep["demoted"]
    # once demoted, client 3 stops being sampled
    demoted_from = next(
        r for r, cohort in enumerate(rep["cohorts"]) if 3 not in cohort
    )
    for cohort in rep["cohorts"][demoted_from:]:
        assert 3 not in cohort


def test_adaptive_dropout_closes_steady_rounds_before_deadline():
    """20% stragglers 8x slower under drop/duplicate/reorder chaos: once
    health demotion has moved the persistent stragglers out of the
    cohorts, the second half of the rounds completes before the deadline."""
    n_clients, cohort, n_rounds, deadline = 24, 10, 8, 1.0
    payloads = _payloads(n_clients=n_clients)
    latency = latency_profile(n_clients, 0.2, straggler_factor=8.0, base=0.3,
                              jitter=0.5, seed=2)
    spec = ChaosSpec(drop=0.2, duplicate=0.1, reorder=0.3, rto=0.1,
                     max_attempts=4, seed=3)
    eng = AsyncRoundEngine(AsyncConfig(
        n_classes=C, ridge_lambda=LAMBDA, cohort=cohort, deadline=deadline,
        staleness_rounds=3, demote_after=2, cooldown=2 * n_rounds,
    ))
    _, rep = run_adaptive_rounds(
        eng, eng.init(D), n_clients, cohort, n_rounds, latency, spec,
        lambda c, r: payloads[c], seed=4,
    )
    assert rep["demoted"]
    tail = float(np.mean(rep["completion"][n_rounds // 2:]))
    assert tail < deadline, f"steady-state rounds closed at {tail:.3f}s"


def test_live_classifier_tracks_open_rounds():
    payloads = _payloads()
    eng = _engine(staleness_rounds=2)
    state = eng.init(D)
    eng.begin_round(0, [0, 1], 0.0)
    state, _ = eng.deliver(state, UploadEvent(0.1, 0, 0, 0), payloads[0])
    state, _ = eng.deliver(state, UploadEvent(0.2, 0, 1, 0), payloads[1])
    live = np.asarray(eng.live_classifier(state))
    # the open round has not retired; the carried classifier is still empty
    assert not np.array_equal(live, np.asarray(state.W))
    state = eng.drain(state)
    np.testing.assert_allclose(live, np.asarray(state.W), rtol=1e-5, atol=1e-6)


def test_retire_matches_streaming_absorb_stats():
    payloads = _payloads()
    cohort = [0, 1, 2, 3]
    eng = _engine(staleness_rounds=0)
    state = eng.init(D)
    eng.begin_round(0, cohort, 0.0)
    for i, c in enumerate(cohort):
        state, _ = eng.deliver(state, UploadEvent(0.1 * i, 0, c, 0), payloads[c])
    state = eng.close_round(state, 0, now=1.0)

    se = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAMBDA))
    ss = se.init(D)
    S_A = jnp.sum(jnp.stack([payloads[c].A for c in cohort]), axis=0)
    S_b = jnp.sum(jnp.stack([payloads[c].b for c in cohort]), axis=0)
    S_n = jnp.sum(jnp.stack([payloads[c].n for c in cohort]), axis=0)
    ss = se.absorb_stats(ss, S_A, S_b, S_n)

    np.testing.assert_allclose(
        np.asarray(state.W), np.asarray(ss.W), rtol=1e-6, atol=1e-7
    )
    assert float(state.n) == pytest.approx(float(ss.n))


# ---------------------------------------------------------------------------
# Secure aggregation under dropout
# ---------------------------------------------------------------------------


def _secure_round(cohort, payloads_masked, scales, deliver_clients, seed=0):
    eng = AsyncRoundEngine(AsyncConfig(
        n_classes=C, ridge_lambda=LAMBDA, cohort=len(cohort), deadline=1.0,
        staleness_rounds=0, secure=True, secure_seed=seed,
    ))
    state = eng.init(D)
    eng.begin_round(0, cohort, 0.0, scales=scales)
    for i, c in enumerate(deliver_clients):
        state, s = eng.deliver(
            state, UploadEvent(0.1 * i, 0, c, 0), payloads_masked[c]
        )
        assert s == "folded"
    state = eng.close_round(state, 0, now=1.0)
    return eng, state


@pytest.mark.parametrize("n_drop", [1, 2, 3])
def test_secure_dropout_recovery_bitwise(n_drop):
    """Masked round with 1..K-1 dropped clients == survivor-only round with
    UNMASKED payloads and the same shared scales, bit for bit."""
    stats = _payloads(seed=4)
    cohort = [0, 1, 2, 3]
    q, sA, sb = cohort_quantize_int8([stats[c] for c in cohort])
    dropped = cohort[:n_drop]
    survivors = cohort[n_drop:]
    seed = 11

    masked = {
        c: secure_agg.mask_quantized_payload(q[i], c, cohort, seed)
        for i, c in enumerate(cohort)
    }
    _, s_drop = _secure_round(cohort, masked, (sA, sb), survivors, seed=seed)

    unmasked = {c: q[cohort.index(c)] for c in survivors}
    _, s_base = _secure_round(survivors, unmasked, (sA, sb), survivors, seed=seed)

    np.testing.assert_array_equal(np.asarray(s_drop.W), np.asarray(s_base.W))
    np.testing.assert_array_equal(np.asarray(s_drop.L), np.asarray(s_base.L))


def test_secure_live_classifier_serves_last_retired_w():
    stats = _payloads(seed=4)
    cohort = [0, 1]
    q, sA, sb = cohort_quantize_int8([stats[c] for c in cohort])
    masked = {
        c: secure_agg.mask_quantized_payload(q[i], c, cohort, 0)
        for i, c in enumerate(cohort)
    }
    eng, state = _secure_round(cohort, masked, (sA, sb), cohort)
    # open slots are masked garbage by design; live serving returns state.W
    np.testing.assert_array_equal(
        np.asarray(eng.live_classifier(state)), np.asarray(state.W)
    )


def test_recover_survivor_sum_quantized_host_bitwise():
    stats = _payloads(seed=6)
    cohort = [0, 1, 2, 3, 4]
    q, _, _ = cohort_quantize_int8([stats[c] for c in cohort])
    survivors, dropped = cohort[:3], cohort[3:]
    seed = 9
    masked_sum = secure_agg.secure_aggregate_quantized([
        secure_agg.mask_quantized_payload(q[i], c, cohort, seed)
        for i, c in enumerate(cohort) if c in survivors
    ])
    rec = secure_agg.recover_survivor_sum_quantized(
        masked_sum, survivors, dropped, seed
    )
    plain = secure_agg.secure_aggregate_quantized(
        [q[cohort.index(c)] for c in survivors]
    )
    np.testing.assert_array_equal(np.asarray(rec.qA), np.asarray(plain.qA))
    np.testing.assert_array_equal(np.asarray(rec.qb), np.asarray(plain.qb))


def test_recover_survivor_sum_float_tolerance():
    stats = _payloads(seed=6)
    cohort = [0, 1, 2]
    survivors, dropped = cohort[:2], cohort[2:]
    seed = 9
    masked = [
        secure_agg.mask_statistics(stats[c], c, cohort, seed) for c in survivors
    ]
    rec = secure_agg.recover_survivor_sum(
        secure_agg.secure_aggregate(masked), survivors, dropped, seed
    )
    plain_A = sum(np.asarray(stats[c].A) for c in survivors)
    np.testing.assert_allclose(np.asarray(rec.A), plain_A, rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# Control-plane errors, serialization, satellites
# ---------------------------------------------------------------------------


def test_begin_round_contiguity_and_overflow():
    eng = _engine(staleness_rounds=1)  # ring of 2
    eng.init(D)
    with pytest.raises(ValueError, match="contiguously"):
        eng.begin_round(1, [0], 0.0)
    eng.begin_round(0, [0], 0.0)
    eng.begin_round(1, [1], 1.0)
    with pytest.raises(RuntimeError, match="ring overflow"):
        eng.begin_round(2, [2], 2.0)
    with pytest.raises(ValueError, match="duplicate"):
        _engine().begin_round(0, [3, 3], 0.0)


def test_deliver_unknown_round_or_client_raises():
    payloads = _payloads()
    eng = _engine()
    state = eng.init(D)
    with pytest.raises(ValueError, match="before begin_round"):
        eng.deliver(state, UploadEvent(0.1, 0, 0, 0), payloads[0])
    eng.begin_round(0, [0, 1], 0.0)
    with pytest.raises(ValueError, match="cohort"):
        eng.deliver(state, UploadEvent(0.1, 0, 9, 0), payloads[9])


def test_timeline_json_roundtrip():
    cohorts = _cohorts(3)
    latency = latency_profile(N_CLIENTS, 0.2, seed=0)
    spec = ChaosSpec(drop=0.3, duplicate=0.2, reorder=0.4, seed=7)
    events = chaos_timeline(cohorts, latency, spec)
    sched = timeline_from_json(timeline_to_json(cohorts, latency, spec, events))
    assert sched["spec"] == spec
    assert sched["cohorts"] == [list(c) for c in cohorts]
    np.testing.assert_allclose(sched["latency"], latency)
    assert sched["events"] == list(events)


def test_straggler_tail_pricing():
    cm = CostModel(b=2.22e6, d=D, C=C)
    out = cm.straggler_tail(16, 0.2, straggler_factor=8.0, base_s=0.3,
                            deadline_s=1.0)
    assert 0.0 < out["p_straggler_round"] <= 1.0
    assert out["async_round_s"] <= out["sync_round_s"]
    assert out["speedup"] >= 1.5  # 20% stragglers 8x slower
    flat = cm.straggler_tail(16, 0.0, straggler_factor=8.0, base_s=0.3)
    assert flat["speedup"] == pytest.approx(1.0)


def test_shard_cohort_partitions_round_robin():
    cohort = [9, 2, 5, 7, 1]
    parts = [shard_cohort(cohort, s, 3) for s in range(3)]
    joined = sorted(c for p in parts for c in p)
    assert joined == sorted(cohort)
    assert all(len(set(p)) == len(p) for p in parts)
    with pytest.raises(ValueError):
        shard_cohort(cohort, 3, 3)


def test_config_validation():
    with pytest.raises(ValueError, match="cohort"):
        AsyncConfig(n_classes=C, ridge_lambda=LAMBDA, cohort=0)
    with pytest.raises(ValueError, match="deadline"):
        AsyncConfig(n_classes=C, ridge_lambda=LAMBDA, cohort=1, deadline=0.0)
    with pytest.raises(ValueError, match="secure"):
        AsyncConfig(n_classes=C, ridge_lambda=LAMBDA, cohort=1, secure=True,
                    wire=WireFormat(kind="int8"))

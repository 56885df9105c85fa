"""Continuous-batching slot-serving engine: parity, eviction, admission.

The serving acceptance surface (ISSUE 6):

* strict-mode slot serving matches the synchronous ``serve_heads`` path —
  BITWISE on global-mode queries, <= 1e-5 on personalized ones (same
  cohort packing, same in-dispatch alpha sweep);
* the slot table evicts coldest-first, readmits evicted tenants with a
  fresh solve, and never evicts a slot protected by an in-flight query;
* admission control sheds at enqueue beyond ``queue_depth`` and sheds
  queued requests past ``deadline_ticks``, with every request accounted;
* each stage costs ONE dispatch per tick regardless of batch composition;
* version-segmented invalidation re-solves ONLY tenants whose own
  statistics arrived (both in the :class:`HeadCache` policy and the slot
  engine), where the strict policy re-solves the whole working set.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import make_federated_features
from repro.federated.arrivals import pack_schedule, poisson_schedule, zipf_traffic
from repro.federated.personalization import (
    PersonalizationEngine,
    PersonalizeConfig,
)
from repro.federated.slots import SlotTable, TenantUniverse
from repro.federated.streaming_engine import StreamConfig, StreamingEngine
from repro.launch.serve_heads import HeadCache, HeadServer
from repro.launch.serving_engine import ServingConfig, ServingEngine

D, C, LAM = 16, 5, 1e-2
ALPHA_GRID = (0.0, 0.5, 1.0, 2.0, 4.0)


def _fed(seed=1, n_clients=8):
    fed, _ = make_federated_features(
        seed=seed, n=600, d=D, n_classes=C, n_clients=n_clients,
        alpha=0.3, noise=2.0,
    )
    return fed


def _packed(fed, seed=0, waves=4):
    return pack_schedule(fed, poisson_schedule(fed.n_clients, waves, 3.0, seed=seed))


def _engine(fed, **kw):
    cfg = dict(
        n_classes=C, ridge_lambda=LAM, n_slots=6, solve_bucket=4,
        serve_bucket=8, alpha_grid=ALPHA_GRID,
    )
    cfg.update(kw)
    eng = ServingEngine(ServingConfig(**cfg), fed)
    eng.init(D)
    return eng


def _lru(fed, capacity=4, invalidation="strict"):
    srv = HeadServer(
        StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM)),
        PersonalizationEngine(PersonalizeConfig(
            n_classes=C, alpha_grid=ALPHA_GRID,
        )),
        fed,
        cache_capacity=capacity,
        cohort_round_to=4,
        invalidation=invalidation,
    )
    srv.init(D)
    return srv


def _burst(fed, cids):
    return np.stack([
        fed.client(c % fed.n_clients).features[i] for i, c in enumerate(cids)
    ])


# ---------------------------------------------------------------------------
# slot-table state
# ---------------------------------------------------------------------------


def test_slot_table_rejects_degenerate_sizes():
    with pytest.raises(ValueError):
        SlotTable(1, D, C)  # no room for a tenant next to the pinned slot


def test_slot_table_free_first_then_coldest_eviction():
    t = SlotTable(5, D, C)
    # fill the three tenant slots behind the pinned global slot
    s = t.take_slots(3)
    assert s == [1, 2, 3] and t.evictions == 0
    t.assign(s, [10, 11, 12], [0, 0, 0], global_version=1, tick=1)
    assert len(t) == 3 and t.slot_of(11) == 2
    # one free slot left; ask for two: free slot 4 first, then the coldest
    t.touch([1], [5], tick=3)  # tenant 10 is hot and recent
    t.touch([2], [1], tick=2)  # tenant 11 lukewarm
    got = t.take_slots(2)  # slot 3 (tenant 12, never served) is coldest
    assert got == [4, 3]
    assert t.evictions == 1 and t.slot_of(12) is None
    assert t.slot_of(10) == 1  # the hot tenant survived


def test_slot_table_protected_slots_survive_saturation():
    t = SlotTable(4, D, C)
    s = t.take_slots(3)
    t.assign(s, [7, 8, 9], [0, 0, 0], global_version=1, tick=1)
    got = t.take_slots(3, protect=[1, 2])  # only slot 3 is evictable
    assert got == [3]
    assert t.slot_of(7) == 1 and t.slot_of(8) == 2


def test_tenant_universe_aliases_base_clients():
    fed = _fed()
    uni = TenantUniverse(fed, 1_000_000)
    assert uni.n_clients == 1_000_000
    k = 777_777
    base = fed.client(k % fed.n_clients)
    np.testing.assert_array_equal(uni.client(k).features, base.features)
    assert int(uni.client_sizes().max()) == int(fed.client_sizes().max())
    with pytest.raises(ValueError):
        TenantUniverse(fed, fed.n_clients - 1)


# ---------------------------------------------------------------------------
# version-segmented invalidation (cache policy + partial re-personalization)
# ---------------------------------------------------------------------------


def test_head_cache_segmented_invalidates_only_touched_tenants():
    cache = HeadCache(capacity=4, segmented=True)
    W = jnp.zeros((D, C))
    cache.put(1, W)
    cache.put(2, W)
    cache.advance(touched=[1])  # only tenant 1's own statistics moved
    assert cache.get(1) is None  # stale: its stats version advanced
    assert cache.get(2) is not None  # untouched resident survives
    assert cache.stale_evictions == 1
    # unknown arrival set degrades to whole-cache invalidation
    cache.put(1, W)
    cache.advance(touched=None)
    assert cache.get(1) is None and cache.get(2) is None


def test_head_cache_strict_still_sweeps_everything():
    cache = HeadCache(capacity=4, segmented=False)
    cache.put(1, jnp.zeros((D, C)))
    cache.put(2, jnp.zeros((D, C)))
    cache.advance(touched=[1])  # strict ignores the touched set
    assert cache.get(1) is None and cache.get(2) is None


def test_head_server_partial_repersonalization():
    fed = _fed()
    srv = _lru(fed, capacity=8, invalidation="segmented")
    packed = _packed(fed)
    srv.absorb(packed)
    cids = [0, 1, 2, 3]
    xs = _burst(fed, cids)
    _, rep = srv.query(cids, xs)
    assert rep["solved_now"] == 4
    # an absorb whose arrivals touch ONLY client 2
    wave = pack_schedule(fed, [[2]])
    srv.absorb(wave)
    _, rep2 = srv.query(cids, xs)
    assert rep2["solved_now"] == 1  # partial re-personalization: just 2
    assert srv.cache.stale_evictions == 1
    # the strict server re-solves the whole working set on the same event
    strict = _lru(fed, capacity=8, invalidation="strict")
    strict.absorb(packed)
    strict.query(cids, xs)
    strict.absorb(wave)
    _, rep3 = strict.query(cids, xs)
    assert rep3["solved_now"] == 4


def test_slot_engine_segmented_resolves_only_touched_tenants():
    fed = _fed()
    eng = _engine(fed, invalidation="segmented")
    strict = _engine(fed, invalidation="strict")
    packed = _packed(fed)
    cids = [0, 1, 2, 3]
    xs = _burst(fed, cids)
    for e in (eng, strict):
        e.absorb(packed)
        _, rep = e.query(cids, xs)
        assert rep["solved_now"] == 4
        e.absorb(pack_schedule(fed, [[2]]))  # touches only client 2
    _, rep_seg = eng.query(cids, xs)
    _, rep_strict = strict.query(cids, xs)
    assert rep_seg["solved_now"] == 1
    assert rep_strict["solved_now"] == 4


# ---------------------------------------------------------------------------
# answer parity with the synchronous server
# ---------------------------------------------------------------------------


def test_slot_engine_matches_synchronous_server():
    fed = _fed()
    eng = _engine(fed, invalidation="strict")
    srv = _lru(fed, capacity=5)
    packed = _packed(fed)
    eng.absorb(packed)
    srv.absorb(packed)
    cids = [0, 3, 0, 999]  # repeat + an unknown tenant
    xs = _burst(fed, cids)
    for _ in range(2):  # second burst exercises the hit path on both
        s1, r1 = eng.query(cids, xs)
        s2, r2 = srv.query(cids, xs)
        assert r1["modes"] == r2["modes"] == [
            "per-tenant", "per-tenant", "per-tenant", "global",
        ]
        # personalized rows: same cohort packing + same alpha sweep => bitwise
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # and the engine's served global head IS the synchronous classifier
    assert np.array_equal(np.asarray(eng.classifier()),
                          np.asarray(srv.stream.classifier(srv.state)))


def test_slot_engine_personalized_parity_after_stream_advance():
    fed = _fed()
    eng = _engine(fed, invalidation="strict")
    srv = _lru(fed, capacity=5)
    cids = [1, 4, 6]
    xs = _burst(fed, cids)
    for seed in (0, 1):  # absorb -> query -> absorb -> query
        packed = _packed(fed, seed=seed, waves=2)
        eng.absorb(packed)
        srv.absorb(packed)
        s1, _ = eng.query(cids, xs)
        s2, _ = srv.query(cids, xs)
        err = float(np.max(np.abs(np.asarray(s1) - np.asarray(s2))))
        assert err <= 1e-5


# ---------------------------------------------------------------------------
# slot lifecycle: eviction / readmission round-trip
# ---------------------------------------------------------------------------


def test_slot_engine_eviction_readmission_roundtrip():
    fed = _fed()
    eng = _engine(fed, n_slots=3)  # 2 tenant slots only
    eng.absorb(_packed(fed))
    xs0 = _burst(fed, [0])
    s_first, rep = eng.query([0], xs0)
    assert rep["solved_now"] == 1 and eng.table.slot_of(0) is not None
    # flood with other tenants until tenant 0 is evicted
    _, rep2 = eng.query([1, 2], _burst(fed, [1, 2]))
    assert eng.table.slot_of(0) is None  # evicted (coldest of the three)
    assert eng.table.evictions >= 1
    # readmission: a fresh solve into a reclaimed slot, same answer (the
    # stream state never moved, so the re-solved head is bitwise the same)
    s_again, rep3 = eng.query([0], xs0)
    assert rep3["solved_now"] == 1
    assert eng.table.slot_of(0) is not None
    np.testing.assert_array_equal(np.asarray(s_first), np.asarray(s_again))


def test_slot_engine_overflow_serves_global_and_reports():
    fed = _fed()
    eng = _engine(fed, n_slots=3)  # 2 tenant slots vs 4 distinct tenants
    eng.absorb(_packed(fed))
    cids = [0, 1, 2, 3]
    scores, rep = eng.query(cids, _burst(fed, cids))
    assert rep["slot_overflow"] == 2
    assert rep["modes"].count("per-tenant") == 2
    assert rep["modes"].count("global") == 2
    assert scores.shape == (4, C)
    # the overflowed queries were answered with the pinned global head
    g = [i for i, m in enumerate(rep["modes"]) if m == "global"]
    W_g = eng.classifier()
    expect = np.asarray(_burst(fed, cids))[g] @ np.asarray(W_g)
    np.testing.assert_allclose(np.asarray(scores)[g], expect, rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch budget + admission control
# ---------------------------------------------------------------------------


def test_slot_engine_one_dispatch_per_stage():
    fed = _fed()
    eng = _engine(fed)
    eng.absorb(_packed(fed))
    assert eng.absorb_dispatches == 1
    cids = [0, 1, 2, 0, 1, 3]
    eng.query(cids, _burst(fed, cids))
    assert eng.solve_dispatches == 1  # whole miss cohort in one dispatch
    assert eng.serve_dispatches == 1  # whole burst in one dispatch
    # all-hit burst: no solve work at all, still one serve dispatch
    eng.query(cids, _burst(fed, cids))
    assert eng.solve_dispatches == 1
    assert eng.serve_dispatches == 2


@pytest.mark.parametrize("n_tenants", [1_000, 1_000_000])
def test_slot_engine_serve_dispatches_independent_of_tenant_universe(n_tenants):
    """Zipf-skewed traffic over a tenant universe folded onto the
    federation, an arrival absorbed before each burst, two bursts drained
    per tick: the serve stage costs exactly one dispatch per tick at every
    universe size."""
    fed = _fed()
    universe = TenantUniverse(fed, n_tenants)
    eng = _engine(universe, serve_bucket=16)
    packed = _packed(fed, waves=6)
    burst, n_bursts = 8, 6
    trace = zipf_traffic(n_tenants, burst * n_bursts, exponent=1.6, seed=7)
    for bidx in range(n_bursts):
        eng.absorb(packed.slice_waves(bidx, bidx + 1))
        cids = trace[bidx * burst:(bidx + 1) * burst]
        xs = np.stack([universe.client(c).features[0] for c in cids])
        eng.enqueue(cids, xs)
        if bidx % 2 == 1:
            eng.tick()
    assert eng.ticks == n_bursts // 2
    assert eng.serve_dispatches == eng.ticks


def test_slot_engine_queue_overflow_sheds_at_enqueue():
    fed = _fed()
    eng = _engine(fed, queue_depth=4)
    eng.absorb(_packed(fed))
    cids = [0, 1, 2, 3, 4, 5]
    admitted, shed = eng.enqueue(cids, _burst(fed, cids))
    assert (admitted, shed) == (4, 2)
    assert eng.shed_overflow == 2
    scores, rep = eng.tick()
    assert rep["queries"] == 4 and scores.shape == (4, C)
    with pytest.raises(RuntimeError):  # query() refuses silently-shed bursts
        eng.query(cids, _burst(fed, cids))


def test_slot_engine_deadline_sheds_stale_requests():
    fed = _fed()
    eng = _engine(fed, queue_depth=64, max_batch=2, deadline_ticks=1)
    eng.absorb(_packed(fed))
    cids = [0, 1, 2, 3, 4, 5]
    admitted, shed = eng.enqueue(cids, _burst(fed, cids))
    assert (admitted, shed) == (6, 0)
    served = 0
    sheds = 0
    while eng.queue:
        _, rep = eng.tick()
        served += rep["queries"]
        sheds += rep["shed"]
    # tick 1 serves 2 (waited 1), tick 2 serves 2 (waited 2 > 1? no: the
    # deadline compares full ticks waited; admitted at tick 0, popped at
    # tick 2 => waited 2 > 1 => shed)
    assert served + sheds == 6
    assert sheds == eng.shed_deadline > 0
    assert eng.ticks >= 2


def test_slot_engine_latency_accounting_covers_every_served_request():
    fed = _fed()
    eng = _engine(fed, max_batch=3)
    eng.absorb(_packed(fed))
    cids = [0, 1, 2, 3, 4]
    eng.enqueue(cids, _burst(fed, cids))
    reports = []
    while eng.queue:
        _, rep = eng.tick()
        reports.append(rep)
    assert [r["queries"] for r in reports] == [3, 2]
    for rep in reports:
        assert len(rep["latency_s"]) == rep["queries"]
        assert all(t >= 0.0 for t in rep["latency_s"])
    # in-flight batching across tenants: the first tick mixed 3 tenants
    assert reports[0]["tenants"] == [0, 1, 2]
    assert reports[1]["tenants"] == [3, 4]

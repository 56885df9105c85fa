"""Unified telemetry layer (repro.federated.telemetry) coverage.

The layer's contract:
  * every engine's ``dispatches`` back-compat property reads/writes the
    SAME cell as the registry's ``engine_dispatches_total`` counter —
    bitwise equal, including through the ``dispatches = 0`` reset idiom;
  * log-bucketed histograms report p50/p99 within one bucket of the raw
    sample order statistic at any scale;
  * disabled mode is a structural no-op (shared null span, empty ring)
    while counters keep counting — the dispatch contract is functional;
  * the flight recorder is a bounded ring: memory is capped, drops are
    counted, sequence numbers stay monotone;
  * snapshot (JSON), Prometheus text, and the event JSONL all round-trip
    through their parsers;
  * telemetry adds ZERO device dispatches: the module never holds jax,
    and an engine's dispatch count is identical under an enabled and a
    disabled registry.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r
from repro.data.pipeline import (
    pack_arrival_waves,
    pack_client_shards,
    pack_cohort_batches,
    pack_personal_cohort,
)
from repro.federated.algorithms import make_algorithm
from repro.federated.async_engine import AsyncConfig, AsyncRoundEngine
from repro.federated.arrivals import UploadEvent
from repro.federated.engine import AccumulationEngine, EngineConfig
from repro.federated.personalization import (
    PersonalizationEngine,
    PersonalizeConfig,
)
from repro.federated.round_engine import RoundConfig, RoundEngine
from repro.federated.streaming_engine import StreamConfig, StreamingEngine
from repro.federated.telemetry import (
    Histogram,
    Telemetry,
    dispatch_summary,
    events_from_jsonl,
    parse_prometheus,
    set_telemetry,
)

D, C = 16, 5
LAM = 0.1


@pytest.fixture
def registry():
    """A fresh injected global registry, restored after the test."""
    t = Telemetry()
    prev = set_telemetry(t)
    yield t
    set_telemetry(prev)


def _clients(seed, sizes, d=D, n_classes=C):
    rng = np.random.default_rng(seed)
    return [
        (
            rng.normal(size=(n, d)).astype(np.float32),
            rng.integers(0, n_classes, size=n).astype(np.int32),
        )
        for n in sizes
    ]


# ---------------------------------------------------------------------------
# histograms: quantile accuracy and edge buckets
# ---------------------------------------------------------------------------


def test_histogram_p50_p99_within_one_bucket_of_raw():
    rng = np.random.default_rng(0)
    samples = np.exp(rng.normal(-7.0, 1.5, size=20_000))  # latency-shaped
    h = Histogram("lat", {})
    for s in samples:
        h.observe(float(s))
    for q, est in ((0.50, h.p50), (0.99, h.p99), (0.999, h.p999)):
        raw = float(np.quantile(samples, q))
        assert abs(Histogram.bucket_of(est) - Histogram.bucket_of(raw)) <= 1, (
            f"q={q}: estimate {est:.3e} vs raw {raw:.3e}"
        )
    assert h.count == len(samples)
    np.testing.assert_allclose(h.sum, samples.sum(), rtol=1e-6)
    assert h.min <= samples.min() and h.max >= samples.max()


def test_histogram_zero_and_negative_land_in_zero_bucket():
    h = Histogram("lat", {})
    h.observe(0.0)
    h.observe(-1.0)
    h.observe(1.0)
    assert h.zero_count == 2 and h.count == 3
    assert h.quantile(0.5) == 0.0  # zero bucket dominates the median


# ---------------------------------------------------------------------------
# exposition round-trips
# ---------------------------------------------------------------------------


def _populated() -> Telemetry:
    t = Telemetry(ring=128)
    t.counter("engine_dispatches_total", engine="accumulation", inst="0").inc(7)
    t.counter("wire_bytes_sent_total", kind="int8", inst="1").inc(4096)
    t.gauge("wire_compression_ratio", kind="int8", inst="1").set(3.98)
    h = t.histogram("span_seconds", stage="solve", engine="serving")
    for v in (1e-4, 2e-4, 5e-3, 0.0):
        h.observe(v)
    t.event("client_demoted", client=3, round=2)
    t.event("request_shed", reason="overflow", tenant=17)
    return t


def test_snapshot_json_roundtrip_identity():
    snap = _populated().snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_prometheus_roundtrip():
    t = _populated()
    parsed = parse_prometheus(t.prometheus())
    snap = t.snapshot()
    for c in snap["counters"] + snap["gauges"]:
        key = tuple(sorted((k, str(v)) for k, v in c["labels"].items()))
        assert parsed[(c["name"], key)] == pytest.approx(c["value"])
    for h in snap["histograms"]:
        key = tuple(sorted((k, str(v)) for k, v in h["labels"].items()))
        assert parsed[(h["name"] + "_count", key)] == h["count"]
        assert parsed[(h["name"] + "_sum", key)] == pytest.approx(h["sum"])


def test_events_jsonl_roundtrip():
    t = _populated()
    back = events_from_jsonl(t.events_jsonl())
    assert back == list(t.events)
    assert [ev["kind"] for ev in back] == ["client_demoted", "request_shed"]


# ---------------------------------------------------------------------------
# flight recorder: bounded memory
# ---------------------------------------------------------------------------


def test_event_ring_is_bounded_and_counts_drops():
    t = Telemetry(ring=64)
    for i in range(10_000):
        t.event("tick", i=i)
    assert len(t.events) == 64
    assert t.events_dropped == 10_000 - 64
    seqs = [ev["seq"] for ev in t.events]
    assert seqs == list(range(10_000 - 63, 10_001))  # newest 64, monotone


# ---------------------------------------------------------------------------
# disabled mode: structural no-op, counters still functional
# ---------------------------------------------------------------------------


def test_disabled_mode_is_noop_but_counters_count():
    t = Telemetry(enabled=False)
    assert t.span("a") is t.span("b", x=1)  # one shared null span
    with t.span("a"):
        pass
    t.event("client_demoted", client=0)
    assert len(t.events) == 0
    assert t.snapshot()["histograms"] == []  # no span histogram created
    c = t.counter("engine_dispatches_total", engine="e", inst="0")
    c.inc()
    assert c.value == 1  # the dispatch contract survives disabling


def test_disabled_mode_overhead_regression():
    t = Telemetry(enabled=False)
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with t.span("stage", engine="x"):
            pass
        t.event("tick")
    wall = time.perf_counter() - t0
    # generous absolute bound: ~3µs/iteration budget on a shared CI box
    assert wall < 0.3 * (n / 100_000) * 10, f"disabled-mode loop took {wall:.3f}s"


# ---------------------------------------------------------------------------
# spans: nesting paths
# ---------------------------------------------------------------------------


def test_span_paths_nest():
    t = Telemetry()
    with t.span("retire", engine="async"):
        with t.span("fold", engine="async"):
            pass
    for name in ("span_seconds", "span_cpu_seconds"):
        stages = {
            h["labels"]["stage"]
            for h in t.snapshot()["histograms"]
            if h["name"] == name
        }
        assert stages == {"retire", "retire/fold"}, name
    assert len(t.span_log) == 0  # the log keeps traced spans only


def test_span_records_cpu_time_within_its_wall_time():
    t = Telemetry()
    with t.span("spin"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.05:
            pass
    with t.span("sleep"):
        time.sleep(0.05)
    # one tick of the thread CPU clock: what CPU may exceed wall by
    tick = time.get_clock_info("thread_time").resolution
    for stage in ("spin", "sleep"):
        wall = t.histogram("span_seconds", stage=stage)
        cpu = t.histogram("span_cpu_seconds", stage=stage)
        assert wall.count == cpu.count == 1
        assert cpu.sum <= wall.sum + tick, stage
    # the sleeping thread waited; the spinning one ran (for as long as a
    # busy machine let it)
    sleep_cpu = t.histogram("span_cpu_seconds", stage="sleep").sum
    assert sleep_cpu < 0.5 * t.histogram("span_seconds", stage="sleep").sum
    assert t.histogram("span_cpu_seconds", stage="spin").sum > sleep_cpu
    # the exit observes into the cells the registry exposes, reset included
    t.reset()
    with t.span("spin"):
        pass
    assert t.histogram("span_cpu_seconds", stage="spin").count == 1


def _trace_events(directory):
    from jax.profiler import ProfileData

    (path,) = directory.glob("**/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [
        (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns), dict(ev.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
    ]


def test_span_reaches_the_profiler_trace_and_the_span_log(tmp_path):
    t, off = Telemetry(), Telemetry(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("retire", engine="async"):
            with t.span("fold", engine="async"):
                time.sleep(0.002)
            with off.span("hidden"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = {name: (a, b, stats) for name, a, b, stats in _trace_events(tmp_path)
              if name.startswith("fed3r:")}
    assert set(events) == {"fed3r:retire", "fed3r:retire/fold"}  # none for "hidden"
    (pa, pb, pstats), (ca, cb, _) = events["fed3r:retire"], events["fed3r:retire/fold"]
    assert pa <= ca < cb <= pb  # the child lies inside its parent
    assert cb - ca >= 2e6
    assert pstats.get("engine") == "async"  # labels ride as metadata
    # the log holds the traced spans, innermost first, on the host clock,
    # each inside its own annotation
    assert [r[0] for r in t.span_log] == ["retire/fold", "retire"]
    (_, cs, ce, ccpu), (_, ps, pe, _) = t.span_log
    assert ps <= cs < ce <= pe and 0 <= ccpu <= ce - cs
    assert 2e6 <= ce - cs <= cb - ca and pe - ps <= pb - pa
    assert len(off.span_log) == 0


# ---------------------------------------------------------------------------
# engine dispatch counters == legacy property, across all four engines
# ---------------------------------------------------------------------------


def _round_engine():
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
    return RoundEngine(rc, loss, freeze), params0


def test_all_four_engines_dispatch_counter_equals_legacy(registry):
    clients = _clients(0, [8, 6, 7])

    eng = AccumulationEngine(EngineConfig(n_classes=C))
    st = eng.accumulate(eng.init(D), pack_client_shards(clients, 2, max_n=8))
    st = eng.accumulate(st, pack_client_shards(clients, 2, max_n=8))
    assert eng.dist.dispatches == 2

    s_eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    waves = pack_arrival_waves([_clients(t, [6]) for t in range(3)])
    s_eng.absorb(s_eng.init(D), waves)
    assert s_eng.dist.dispatches == 1

    r_eng, params0 = _round_engine()
    r_eng.step(r_eng.init(params0), pack_cohort_batches(clients, 4, 3))
    assert r_eng.dist.dispatches == 1

    p_eng = PersonalizationEngine(PersonalizeConfig(n_classes=C))
    fac = fed3r.init_factored(D, C, LAM)
    fac = fed3r.factored_update(
        fac,
        jnp.asarray(np.concatenate([x for x, _ in clients])),
        jnp.asarray(np.concatenate([y for _, y in clients])),
    )
    p_eng.solve_heads(fac, pack_personal_cohort(clients, holdout_frac=0.25))
    assert p_eng.dist.dispatches == 1

    # the legacy property and the registry read the SAME cell
    assert dispatch_summary(registry.snapshot()) == {
        "accumulation": 2, "streaming": 1, "rounds": 1, "personalization": 1,
    }

    # the reset idiom writes through to the registry
    eng.dist.dispatches = 0
    assert eng.dist.dispatches == 0
    assert dispatch_summary(registry.snapshot())["accumulation"] == 0

    # per-stage spans landed for every engine
    engines = {
        h["labels"]["engine"]
        for h in registry.snapshot()["histograms"]
        if h["name"] == "span_seconds"
    }
    assert {"accumulation", "streaming", "rounds", "personalization"} <= engines


@pytest.mark.parametrize("kind", ["accumulation", "streaming"])
def test_engine_spans_split_placement_from_launch(registry, kind):
    if kind == "accumulation":
        eng = AccumulationEngine(EngineConfig(n_classes=C))
        call = "accumulate"
        out = eng.accumulate(eng.init(D), pack_client_shards(_clients(1, [8, 6]), 2, max_n=8))
    else:
        eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
        call = "absorb"
        out, _ = eng.absorb(eng.init(D), pack_arrival_waves([_clients(2, [6])]))
    jax.block_until_ready(out)
    assert eng.dist.dispatches == 1  # three spans, one dispatch
    for name in ("span_seconds", "span_cpu_seconds"):
        counts = {
            h["labels"]["stage"]: h["count"]
            for h in registry.snapshot()["histograms"]
            if h["name"] == name and h["labels"].get("engine") == kind
        }
        assert counts == {"init": 1, call: 1, f"{call}/place": 1, f"{call}/launch": 1}
    wall = {s: registry.histogram("span_seconds", stage=s, engine=kind).sum
            for s in (call, f"{call}/place", f"{call}/launch")}
    assert wall[f"{call}/place"] + wall[f"{call}/launch"] <= wall[call]


# ---------------------------------------------------------------------------
# zero device dispatches: telemetry never touches jax on a metric path
# ---------------------------------------------------------------------------


def test_telemetry_module_holds_no_jax():
    import repro.federated.telemetry as T

    assert not any(
        getattr(v, "__name__", "").startswith("jax") for v in vars(T).values()
    ), "telemetry module must not import jax at module level"


def test_dispatch_count_identical_enabled_vs_disabled():
    clients = _clients(3, [8, 6])
    counts = {}
    for enabled in (True, False):
        t = Telemetry(enabled=enabled)
        prev = set_telemetry(t)
        try:
            eng = AccumulationEngine(EngineConfig(n_classes=C))
            st = eng.accumulate(
                eng.init(D), pack_client_shards(clients, 2, max_n=8)
            )
            jax.block_until_ready(st.stats.A)
            counts[enabled] = eng.dist.dispatches
        finally:
            set_telemetry(prev)
    assert counts[True] == counts[False] == 1


# ---------------------------------------------------------------------------
# flight-recorder events from the async engine's health/staleness paths
# ---------------------------------------------------------------------------


def _async_engine(**kw):
    kw.setdefault("staleness_rounds", 0)
    kw.setdefault("early_close", False)
    kw.setdefault("demote_after", 1)
    kw.setdefault("cooldown", 1)
    return AsyncRoundEngine(AsyncConfig(
        n_classes=C, ridge_lambda=LAM, cohort=2, deadline=1.0, **kw,
    ))


def _stats(seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(12, D)).astype(np.float32)
    y = rng.integers(0, C, size=12).astype(np.int32)
    return fed3r.client_stats(jnp.asarray(x), jnp.asarray(y), C)


def test_async_engine_emits_health_and_staleness_events(registry):
    eng = _async_engine()
    state = eng.init(D)
    eng.begin_round(0, [0, 1], 0.0)
    state, s = eng.deliver(state, UploadEvent(0.1, 0, 0, 0), _stats(0))
    assert s == "folded"
    state = eng.close_round(state, 0, now=1.0)  # client 1 missed → demoted
    state, s = eng.deliver(state, UploadEvent(1.5, 0, 1, 0), _stats(1))
    assert s == "stale"
    eng.begin_round(1, [0, 1], 2.0)  # past probation: readmitted on arrival
    state, s = eng.deliver(state, UploadEvent(2.1, 1, 1, 0), _stats(1))
    assert s == "folded"
    kinds = [ev["kind"] for ev in registry.events]
    assert "client_demoted" in kinds
    assert "staleness_drop" in kinds
    assert "client_readmitted" in kinds

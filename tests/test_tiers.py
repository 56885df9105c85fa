"""Tests for the hierarchical N-tier aggregation trees.

The tree's contract is the paper's §4.3 order-invariance made executable:
on the engines' grid-exact statistics, an all-fp32 tree of ANY shape is a
pure reassociation of the flat sum — bitwise equal — while lossy tiers
quantize exactly once per boundary, so the tree result matches a manual
per-boundary roundtrip bit for bit.  Mesh-routed trees must emit the same
program as the two-stage psum; host trees drive the
:class:`~repro.federated.tiers.TieredAbsorber` whose overlapped and
blocking paths must also agree bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r
from repro.federated import compress
from repro.federated.compress import WireFormat
from repro.federated.costs import CostModel
from repro.federated.dist import DistConfig
from repro.federated.engine import AccumulationEngine, EngineConfig, shard_stats
from repro.federated.streaming_engine import StreamConfig, StreamingEngine
from repro.federated.telemetry import Telemetry
from repro.federated.tiers import (
    TIER_WIRE_KINDS,
    AggregationTree,
    TierSpec,
    TieredAbsorber,
    mesh_tree,
    two_stage_tree,
)
from repro.launch.mesh import make_host_mesh, make_tier_host_mesh

D, C, LAM = 16, 5, 0.1

N_DEV = len(jax.devices())
needs4 = pytest.mark.skipif(
    N_DEV < 4,
    reason="needs >=4 simulated devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)
needs8 = pytest.mark.skipif(
    N_DEV < 8,
    reason="needs 8 simulated devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _grid(rng, shape):
    """Features on a 1/8 grid in [-2, 2]: fp32 partial Gram sums are EXACT
    at this scale, so any reduction order is bitwise identical."""
    return (rng.integers(-16, 17, size=shape) / 8.0).astype(np.float32)


def _leaf_payloads(k, n=12, seed=0):
    rng = np.random.default_rng(seed)
    return [
        fed3r.client_stats(
            jnp.asarray(_grid(rng, (n, D))),
            jnp.asarray(rng.integers(0, C, size=n).astype(np.int32)),
            C,
        )
        for _ in range(k)
    ]


def _flat_sum(payloads):
    return jax.tree.map(lambda *xs: sum(xs[1:], xs[0]), *payloads)


def _bitwise(a, b):
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------


def test_tierspec_validation():
    with pytest.raises(ValueError):
        TierSpec("edge", fan_in=0)
    with pytest.raises(ValueError):
        TierSpec("edge", fan_in=2, staleness=-1)
    with pytest.raises(ValueError):
        TierSpec("edge", fan_in=2, bandwidth=0.0)
    # sketch is a client-uplink format, not a tier-boundary format
    with pytest.raises(ValueError):
        TierSpec("edge", fan_in=2, wire=WireFormat(kind="sketch"))
    for kind in TIER_WIRE_KINDS:
        TierSpec("edge", fan_in=2, wire=WireFormat(kind=kind))


def test_tree_validation():
    with pytest.raises(ValueError):
        AggregationTree(())
    with pytest.raises(ValueError):  # duplicate tier names
        AggregationTree((TierSpec("a", fan_in=2), TierSpec("a", fan_in=2)))
    with pytest.raises(ValueError):  # duplicate mesh axes
        AggregationTree((
            TierSpec("a", fan_in=2, axis="data"),
            TierSpec("b", fan_in=2, axis="data"),
        ))
    tree = AggregationTree((
        TierSpec("edge", fan_in=3),
        TierSpec("region", fan_in=2),
        TierSpec("cloud", fan_in=2),
    ))
    assert tree.leaves == 12
    assert tree.lossy_wire is None
    with pytest.raises(ValueError):  # wrong leaf count
        tree.reduce(_leaf_payloads(5))


def test_two_stage_tree_matches_reduce_order():
    tree = two_stage_tree(("pod", "data"))
    # leaf tier on the INNERMOST axis — the two-stage psum order
    assert tree.axes == ("data", "pod")
    with pytest.raises(ValueError):
        two_stage_tree(())
    tree.validate_mesh_axes(("pod", "data"))
    with pytest.raises(ValueError):
        tree.validate_mesh_axes(("data", "pod"))


def test_lossy_wire_is_topmost_non_fp32():
    tree = AggregationTree((
        TierSpec("edge", fan_in=2, wire=WireFormat(kind="int8")),
        TierSpec("cloud", fan_in=2),
    ))
    assert tree.lossy_wire is not None and tree.lossy_wire.kind == "int8"
    assert AggregationTree((TierSpec("edge", fan_in=2),)).lossy_wire is None


# ---------------------------------------------------------------------------
# fp32 trees are exact reassociations (bitwise)
# ---------------------------------------------------------------------------


def test_tree_reduce_bitwise_equals_flat_sum():
    payloads = _leaf_payloads(12)
    tree = AggregationTree((
        TierSpec("edge", fan_in=3),
        TierSpec("region", fan_in=2),
        TierSpec("cloud", fan_in=2),
    ))
    assert _bitwise(tree.reduce(payloads), _flat_sum(payloads))


def test_single_tier_tree_is_flat_fold():
    payloads = _leaf_payloads(6, seed=3)
    tree = AggregationTree((TierSpec("edge", fan_in=6),))
    assert _bitwise(tree.reduce(payloads), _flat_sum(payloads))


def test_fully_masked_leaves_are_exact_noops():
    rng = np.random.default_rng(7)
    x = _grid(rng, (8, D))
    y = rng.integers(0, C, size=8).astype(np.int32)
    real = shard_stats(jnp.asarray(x), jnp.asarray(y), C)
    pad = shard_stats(
        jnp.asarray(x), jnp.asarray(y), C, jnp.zeros(8, jnp.float32)
    )
    tree = AggregationTree((TierSpec("e", fan_in=2), TierSpec("c", fan_in=2)))
    out = tree.reduce([real, pad, pad, pad])
    assert _bitwise(out, real)


def test_int8_tier_quantizes_exactly_once_per_boundary():
    """A lossy tier must match the manual per-boundary fused
    dequantize-accumulate bit for bit (no double quantization)."""
    payloads = _leaf_payloads(4, seed=5)
    wire = WireFormat(kind="int8")
    tree = AggregationTree((
        TierSpec("edge", fan_in=2),  # exact lower fold
        TierSpec("cloud", fan_in=2, wire=wire),
    ))
    got = tree.reduce(payloads)

    def pairsum(a, b):
        return jax.tree.map(lambda x, y: x + y, a, b)

    mids = [pairsum(payloads[0], payloads[1]), pairsum(payloads[2], payloads[3])]

    def cross(acc, child):  # one roundtrip per 2-D matrix per boundary
        A = compress.matrix_roundtrip_add(acc.A, child.A, wire)
        b = compress.matrix_roundtrip_add(acc.b, child.b, wire)
        return child._replace(A=A, b=b, n=acc.n + child.n)

    zero = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), mids[0])
    want = cross(cross(zero, mids[0]), mids[1])
    # n is a scalar sidecar: stays exact fp32, never quantized
    assert _bitwise((got.A, got.b, got.n), (want.A, want.b, mids[0].n + mids[1].n))


# ---- property: any fan-in assignment, any leaf order, still the flat sum ---

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _PAYLOADS = _leaf_payloads(16, seed=11)

    @st.composite
    def tree_shapes(draw):
        fans = draw(
            st.lists(st.integers(1, 4), min_size=1, max_size=3).filter(
                lambda f: np.prod(f) <= 16
            )
        )
        leaves = int(np.prod(fans))
        order = draw(st.permutations(list(range(leaves))))
        return fans, order

    @settings(max_examples=25, deadline=None)
    @given(tree_shapes())
    def test_property_any_tree_any_order_bitwise(shape):
        fans, order = shape
        tree = AggregationTree(
            tuple(TierSpec(f"t{i}", fan_in=k) for i, k in enumerate(fans))
        )
        chosen = [_PAYLOADS[i] for i in order]
        assert _bitwise(tree.reduce(chosen), _flat_sum(chosen))


# ---------------------------------------------------------------------------
# mesh-routed trees (DistConfig(tree=...))
# ---------------------------------------------------------------------------


def test_dist_tree_requires_psum_backend():
    tree = AggregationTree((TierSpec("data", fan_in=1, axis="data"),))
    with pytest.raises(ValueError):
        DistConfig(aggregation="merge", tree=tree)


def test_dist_tree_axes_must_match_mesh():
    mesh = make_host_mesh()
    bad = AggregationTree((TierSpec("edge", fan_in=1, axis="edge"),))
    with pytest.raises(ValueError):
        DistConfig(aggregation="psum", mesh=mesh, donate=False, tree=bad)


def test_mesh_tree_routes_engine_bitwise_single_host():
    """The degenerate 1-axis mesh tree runs at any device count and must
    route the accumulation engine bitwise onto the merge result."""
    from repro.data.pipeline import pack_client_shards

    mesh = make_tier_host_mesh((N_DEV,))
    tree = mesh_tree(mesh)
    assert tree.axes == ("edge",)
    rng = np.random.default_rng(0)
    clients = [
        (_grid(rng, (8, D)), rng.integers(0, C, size=8).astype(np.int32))
        for _ in range(2 * N_DEV)
    ]
    packed = pack_client_shards(clients, 2, mesh=mesh)
    eng = AccumulationEngine(EngineConfig(
        n_classes=C,
        dist=DistConfig(aggregation="psum", mesh=mesh, donate=False, tree=tree),
    ))
    eng.accumulate(eng.init(D), packed)  # warm the trace
    eng.dispatches = 0
    acc = eng.accumulate(eng.init(D), packed)
    ref_eng = AccumulationEngine(EngineConfig(n_classes=C))
    ref = ref_eng.accumulate(ref_eng.init(D), packed)
    assert _bitwise((acc.stats.A, acc.stats.b), (ref.stats.A, ref.stats.b))
    assert eng.dispatches == 1  # the one-dispatch contract survives routing


@needs4
def test_mesh_tree_two_tier_bitwise_vs_two_stage():
    """On a real multi-axis tier mesh the fp32 tree must emit the SAME
    result as the un-routed two-stage psum AND the merge backend."""
    from repro.data.pipeline import pack_arrival_waves

    mesh = make_tier_host_mesh((2, N_DEV // 2))
    tree = mesh_tree(mesh)
    rng = np.random.default_rng(1)
    waves = [
        [
            (_grid(rng, (8, D)), rng.integers(0, C, size=8).astype(np.int32))
            for _ in range(N_DEV)
        ]
        for _ in range(2)
    ]
    arrivals = pack_arrival_waves(waves, mesh=mesh)
    outs = {}
    for name, dist in (
        ("tree", DistConfig(aggregation="psum", mesh=mesh, donate=False, tree=tree)),
        ("flat", DistConfig(aggregation="psum", mesh=mesh, donate=False)),
        ("merge", None),
    ):
        cfg = dict(n_classes=C, ridge_lambda=LAM)
        eng = StreamingEngine(
            StreamConfig(**cfg) if dist is None else StreamConfig(**cfg, dist=dist)
        )
        state, _ = eng.absorb(eng.init(D), arrivals)
        outs[name] = np.asarray(state.W)
    assert np.array_equal(outs["tree"], outs["flat"])
    assert np.array_equal(outs["tree"], outs["merge"])


def _linear_loss(params, batch):
    logits = batch["x"] @ params["W"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, batch["y"][:, None].astype(jnp.int32), axis=-1
    )[:, 0]
    return lse - picked


@needs8
@pytest.mark.parametrize("shape", [(8,), (2, 4), (2, 2, 2)])
def test_mesh_tree_bitwise_at_every_tier_shape(shape):
    """1-, 2- and 3-tier meshes over the same 8 devices: the fp32 tree, the
    un-routed two-stage psum and the merge backend give bitwise equal A/b
    (statistics engine) and W (streaming, rounds, personalization), each
    routed call in ONE dispatch."""
    from repro.data.pipeline import (
        pack_arrival_waves,
        pack_client_shards,
        pack_cohort_batches,
        pack_personal_cohort,
    )
    from repro.federated.algorithms import make_algorithm
    from repro.federated.personalization import (
        PersonalizationEngine,
        PersonalizeConfig,
    )
    from repro.federated.round_engine import RoundConfig, RoundEngine

    mesh = make_tier_host_mesh(shape)
    dists = {
        "tree": DistConfig(aggregation="psum", mesh=mesh, donate=False,
                           tree=mesh_tree(mesh)),
        "flat": DistConfig(aggregation="psum", mesh=mesh, donate=False),
    }

    def clients(seed, k, n=16):
        rng = np.random.default_rng(seed)
        return [
            (_grid(rng, (n, D)), rng.integers(0, C, size=n).astype(np.int32))
            for _ in range(k)
        ]

    packed = pack_client_shards(clients(1, 4 * N_DEV), 2, mesh=mesh)
    arrivals = pack_arrival_waves(
        [clients(10 + w, N_DEV) for w in range(3)], mesh=mesh
    )
    cohort = pack_cohort_batches(clients(20, N_DEV), 8, 2, mesh=mesh)
    tenants = clients(30, N_DEV)
    pcohort = pack_personal_cohort(tenants, mesh=mesh)
    fac = fed3r.factored_update(
        fed3r.init_factored(D, C, LAM),
        jnp.asarray(np.concatenate([x for x, _ in tenants])),
        jnp.asarray(np.concatenate([y for _, y in tenants])),
    )
    params0 = {"W": jnp.zeros((D, C), jnp.float32)}
    freeze = jax.tree.map(lambda _: 1.0, params0)

    out = {}
    for name, dist in (*dists.items(), ("merge", DistConfig())):
        eng = AccumulationEngine(EngineConfig(n_classes=C, dist=dist))
        acc = eng.accumulate(eng.init(D), packed)
        s_eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM, dist=dist))
        st, _ = s_eng.absorb(s_eng.init(D), arrivals)
        r_eng = RoundEngine(
            RoundConfig(algo=make_algorithm("fedavg"), client_lr=0.1,
                        n_total_clients=N_DEV, dist=dist),
            _linear_loss, freeze,
        )
        rs = r_eng.step(r_eng.init(params0), cohort)
        p_eng = PersonalizationEngine(PersonalizeConfig(n_classes=C, dist=dist))
        heads = p_eng.solve_heads(fac, pcohort)
        out[name] = (acc.stats.A, acc.stats.b, st.W, rs.params["W"], heads.W)
        if name in dists:
            for e in (eng, s_eng, r_eng, p_eng):
                assert e.dispatches == 1, (name, type(e).__name__)
    assert _bitwise(out["tree"], out["flat"])
    # the round and personalization solves are compared to the two-stage
    # psum only: their local cohort widths differ from the merge backend's
    assert _bitwise(out["tree"][:3], out["merge"][:3])


@needs4
def test_async_engine_dist_mesh_tree_bitwise():
    """The async ring's retire folds route through the dist-owned mesh
    (slots sharded over the data axes) with and without a tree, bitwise
    equal to the merge backend; K must divide over the shards."""
    from repro.federated.arrivals import UploadEvent
    from repro.federated.async_engine import AsyncConfig, AsyncRoundEngine

    mesh = make_tier_host_mesh((2, N_DEV // 2))
    tree = mesh_tree(mesh)
    K = N_DEV
    payloads = {}
    rng = np.random.default_rng(2)
    for c in range(K):
        x = _grid(rng, (8, D))
        y = rng.integers(0, C, size=8).astype(np.int32)
        payloads[c] = fed3r.client_stats(jnp.asarray(x), jnp.asarray(y), C)

    def run(dist):
        cfg = dict(n_classes=C, ridge_lambda=LAM, cohort=K)
        eng = AsyncRoundEngine(
            AsyncConfig(**cfg) if dist is None else AsyncConfig(**cfg, dist=dist)
        )
        st = eng.init(D)
        eng.begin_round(0, list(range(K)), 0.0)
        for i, c in enumerate(np.random.default_rng(3).permutation(K)):
            st, s = eng.deliver(st, UploadEvent(0.1 * i, 0, int(c), 0), payloads[int(c)])
            assert s == "folded"
        st = eng.close_round(st, 0, now=1.0)
        return np.asarray(eng.drain(st).W)

    ref = run(None)
    dist_tree = DistConfig(aggregation="psum", mesh=mesh, donate=False, tree=tree)
    assert np.array_equal(run(dist_tree), ref)

    with pytest.raises(ValueError):  # K=3 slots do not shard over the axes
        AsyncRoundEngine(AsyncConfig(
            n_classes=C, ridge_lambda=LAM, cohort=3, dist=dist_tree
        ))


# ---------------------------------------------------------------------------
# TieredAbsorber (host tiers)
# ---------------------------------------------------------------------------

_HOST_TREE = AggregationTree((
    TierSpec("edge", fan_in=2),
    TierSpec("cloud", fan_in=2, staleness=1),
))


def _segments(s, leaves, n=8, seed=0):
    rng = np.random.default_rng(seed)
    return [
        (
            _grid(rng, (leaves, n, D)),
            rng.integers(0, C, size=(leaves, n)).astype(np.int32),
            np.ones((leaves, n), np.float32),
        )
        for _ in range(s)
    ]


def _run_absorber(tree, segs, *, overlap, telemetry=None, cost_model=None):
    eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    ab = eng.tiered_absorber(
        tree, overlap=overlap, telemetry=telemetry, cost_model=cost_model
    )
    before = ab.dist.dispatches
    for f, l, m in segs:
        ab.absorb_segment(f, l, m)
    state = ab.drain()
    return state, ab.dist.dispatches - before


def test_absorber_blocking_overlap_flat_bitwise():
    segs = _segments(4, _HOST_TREE.leaves)
    st_b, disp_b = _run_absorber(_HOST_TREE, segs, overlap=False)
    st_o, disp_o = _run_absorber(_HOST_TREE, segs, overlap=True)
    assert np.array_equal(np.asarray(st_b.W), np.asarray(st_o.W))
    assert disp_b == len(segs)  # one fused dispatch per segment
    assert disp_o == 2 * len(segs)  # lower + upper per segment

    eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    st = eng.init(D)
    for f, l, m in segs:
        s = shard_stats(
            jnp.asarray(f).reshape(-1, D), jnp.asarray(l).reshape(-1), C,
            jnp.asarray(m).reshape(-1),
        )
        st = eng.absorb_stats(st, s.A, s.b, s.n)
    assert np.array_equal(np.asarray(st.W), np.asarray(st_o.W))


@pytest.mark.parametrize("fan_ins", [(8,), (4, 2), (2, 2, 2)])
def test_absorber_blocking_one_fused_dispatch_at_every_tier_count(fan_ins):
    """1-, 2- and 3-tier host trees over 8 leaves: the blocking absorber
    costs one fused dispatch per segment, and its W is bitwise the flat
    ``absorb_stats`` of each segment's sum."""
    tree = AggregationTree(tuple(
        TierSpec(f"t{i}", fan_in=k) for i, k in enumerate(fan_ins)
    ))
    segs = _segments(3, tree.leaves, seed=8)
    st_b, disp_b = _run_absorber(tree, segs, overlap=False)
    assert disp_b == len(segs)

    eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    st = eng.init(D)
    for f, l, m in segs:
        s = shard_stats(
            jnp.asarray(f).reshape(-1, D), jnp.asarray(l).reshape(-1), C,
            jnp.asarray(m).reshape(-1),
        )
        st = eng.absorb_stats(st, s.A, s.b, s.n)
    assert np.array_equal(np.asarray(st.W), np.asarray(st_b.W))


def test_absorber_int8_tier_paths_agree_bitwise():
    tree = AggregationTree((
        TierSpec("edge", fan_in=2),
        TierSpec("cloud", fan_in=2, wire=WireFormat(kind="int8"), staleness=2),
    ))
    segs = _segments(3, tree.leaves, seed=4)
    st_b, _ = _run_absorber(tree, segs, overlap=False)
    st_o, _ = _run_absorber(tree, segs, overlap=True)
    assert np.array_equal(np.asarray(st_b.W), np.asarray(st_o.W))


def test_absorber_staleness_budget_and_gauges():
    tel = Telemetry()
    segs = _segments(4, _HOST_TREE.leaves, seed=2)
    _run_absorber(_HOST_TREE, segs, overlap=True, telemetry=tel)
    snap = tel.snapshot()
    # ring depth 1: every segment after the first forces the oldest flush
    stale = [e for e in snap["events"] if e["kind"] == "tier_staleness_exceeded"]
    assert len(stale) == len(segs) - 1
    eff = {g["name"]: g["value"] for g in snap["gauges"]}
    assert eff["tier_overlap_efficiency"] == 1.0  # no absorb-path syncs

    tel2 = Telemetry()
    _run_absorber(_HOST_TREE, segs, overlap=False, telemetry=tel2)
    eff2 = {g["name"]: g["value"] for g in tel2.snapshot()["gauges"]}
    assert eff2["tier_overlap_efficiency"] == 0.0  # one sync per segment


def test_absorber_cost_model_drift_gauge():
    tel = Telemetry()
    cm = CostModel(b=1e6, d=D, C=C)
    segs = _segments(3, _HOST_TREE.leaves, seed=6)
    _run_absorber(_HOST_TREE, segs, overlap=False, telemetry=tel, cost_model=cm)
    drift = {g["name"]: g["value"] for g in tel.snapshot()["gauges"]}[
        "tier_cost_model_drift"
    ]
    assert 0.5 <= drift <= 2.0


def test_absorber_validation():
    eng = StreamingEngine(StreamConfig(n_classes=C, ridge_lambda=LAM))
    with pytest.raises(ValueError):  # mesh tiers route through DistConfig
        TieredAbsorber(
            eng, AggregationTree((TierSpec("data", fan_in=1, axis="data"),))
        )
    with pytest.raises(ValueError):  # overlap needs a staleness budget
        TieredAbsorber(
            eng, AggregationTree((TierSpec("edge", fan_in=2),)), overlap=True
        )
    psum_eng = StreamingEngine(StreamConfig(
        n_classes=C, ridge_lambda=LAM,
        dist=DistConfig(aggregation="psum", mesh=make_host_mesh(), donate=False),
    ))
    with pytest.raises(ValueError):  # absorber owns the topology
        TieredAbsorber(psum_eng, _HOST_TREE, overlap=False)
    wired = StreamingEngine(StreamConfig(
        n_classes=C, ridge_lambda=LAM, wire=WireFormat(kind="int8")
    ))
    with pytest.raises(ValueError):  # compression lives on the tiers
        TieredAbsorber(wired, _HOST_TREE, overlap=False)
    ab = eng.tiered_absorber(_HOST_TREE, overlap=False)
    f, l, m = _segments(1, _HOST_TREE.leaves + 1)[0]
    with pytest.raises(ValueError):  # segment width != tree.leaves
        ab.absorb_segment(f, l, m)


def test_obs_report_renders_tier_tree():
    from repro.launch.obs_report import render

    tel = Telemetry()
    segs = _segments(2, _HOST_TREE.leaves, seed=8)
    _run_absorber(_HOST_TREE, segs, overlap=True, telemetry=tel)
    report = render(tel.snapshot())
    assert "aggregation tree (leaf tier first):" in report
    assert "edge" in report and "cloud" in report


def test_merge_snapshot_carries_tier_counters():
    tel = Telemetry()
    segs = _segments(2, _HOST_TREE.leaves, seed=9)
    _run_absorber(_HOST_TREE, segs, overlap=False, telemetry=tel)
    parent = Telemetry()
    parent.merge_snapshot(tel.snapshot())
    parent.merge_snapshot(tel.snapshot())  # counters ADD across workers
    merged = {
        (c["name"], tuple(sorted(c["labels"].items()))): c["value"]
        for c in parent.snapshot()["counters"]
    }
    for c in tel.snapshot()["counters"]:
        key = (c["name"], tuple(sorted(c["labels"].items())))
        assert merged[key] == 2 * c["value"]


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------


def test_tiered_allreduce_two_fp32_tiers_match_two_stage():
    cm = CostModel(b=1e6, d=128, C=32)
    dp, pods = 16, 4
    tree = AggregationTree((
        TierSpec("data", fan_in=dp, bandwidth=50e9),
        TierSpec("pod", fan_in=pods, bandwidth=12.5e9),
    ))
    tiered = cm.tiered_allreduce(tree.as_cost_tiers())
    two = cm.two_stage_allreduce(dp, pods)
    assert tiered["leaves"] == dp * pods
    assert tiered["total_s"] == pytest.approx(two["ici_s"] + two["dcn_s"])
    assert tiered["flat_allreduce_s"] == pytest.approx(two["flat_allreduce_s"])


def test_tiered_allreduce_single_leaf_is_free():
    cm = CostModel(b=1e6, d=64, C=16)
    priced = cm.tiered_allreduce(
        AggregationTree((TierSpec("edge", fan_in=1),)).as_cost_tiers()
    )
    assert priced["leaves"] == 1
    assert priced["total_s"] == 0.0
    assert priced["flat_allreduce_s"] == 0.0


def test_tiered_allreduce_lossy_tier_shrinks_bytes():
    cm = CostModel(b=1e6, d=128, C=32)

    def total(wire):
        tree = AggregationTree((
            TierSpec("edge", fan_in=4),
            TierSpec("cloud", fan_in=4, wire=WireFormat(kind=wire),
                     bandwidth=1.25e9),
        ))
        return cm.tiered_allreduce(tree.as_cost_tiers())["total_s"]

    assert total("int8") < total("fp32")

"""Compressed statistics uplink: kernels, formats, error feedback, interop.

Covers the wire-format contract end to end:
* quantize/dequantize Pallas kernels vs the ref.py oracles (exact int8
  agreement — both sides round half-to-even under the same jit);
* fp32 format bitwise-identical to the uncompressed engines;
* error feedback telescoping (EF strictly beats no-EF over repeated
  rounds, and the compressed solve stays near the exact one);
* client-permutation invariance under every format (canonical fold order);
* fp8 → int8 fallback warning when the backend lacks float8;
* secure aggregation over integer payloads (mod-2³² masks cancel
  bit-exactly);
* the PSD-guarded Cholesky that keeps compressed streaming finite on
  rank-deficient waves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fed3r
from repro.core.fed3r import Fed3RStats
from repro.data.pipeline import pack_arrival_waves, pack_client_shards
from repro.federated import compress, secure_agg
from repro.federated.compress import EFState, UplinkCompressor, WireFormat
from repro.federated.costs import CostModel, stats_wire_bytes
from repro.federated.engine import AccumulationEngine, EngineConfig
from repro.federated.streaming_engine import StreamConfig, StreamingEngine
from repro.kernels import dequant_accumulate, quantize_tiles
from repro.kernels.ref import dequant_acc_ref, quantize_tiles_ref

D, C = 48, 7


def _clients(rng, K=6, d=D, n_classes=C, lo=5, hi=20):
    """Synthetic client shards: clustered features → separable classes."""
    out = {}
    centers = rng.normal(size=(n_classes, d)).astype(np.float32) * 3.0
    for k in range(K):
        n = int(rng.integers(lo, hi))
        y = rng.integers(0, n_classes, size=n).astype(np.int32)
        x = centers[y] + rng.normal(size=(n, d)).astype(np.float32)
        out[k] = (x, y)
    return out


def _client_stats(x, y, n_classes=C):
    z, yh, n = fed3r.masked_design(
        jnp.asarray(x), jnp.asarray(y), n_classes, None
    )
    return Fed3RStats(A=z.T @ z, b=z.T @ yh, n=n)


def _run_engine(packed, fmt, n_classes=C, d=D):
    eng = AccumulationEngine(
        EngineConfig(n_classes=n_classes, use_kernel=False, wire=fmt)
    )
    acc = eng.accumulate(eng.init(d), packed)
    return eng, acc


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,tile", [
    ((128, 128), 128),  # exactly one tile
    ((256, 128), 64),   # aligned multi-tile
    ((200, 150), 64),   # ragged both dims
    ((33, 190), 128),   # smaller than one tile in M
])
def test_quantize_kernel_matches_oracle_exactly(shape, tile, rng):
    x = 10.0 * jax.random.normal(rng, shape, jnp.float32)
    q, s = quantize_tiles(x, tile=tile)
    # jit the oracle too: XLA folds the divide-by-qmax identically, making
    # the comparison exact rather than 1-ulp
    qr, sr = jax.jit(quantize_tiles_ref, static_argnames=("tile",))(x, tile=tile)
    assert q.dtype == jnp.int8 and q.shape == shape
    assert s.shape == (-(-shape[0] // tile), -(-shape[1] // tile))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


@pytest.mark.parametrize("shape,tile", [((200, 150), 64), ((128, 128), 128)])
def test_dequant_accumulate_matches_oracle_exactly(shape, tile, rng):
    x = jax.random.normal(rng, shape, jnp.float32)
    acc = jax.random.normal(jax.random.fold_in(rng, 1), shape, jnp.float32)
    q, s = quantize_tiles(x, tile=tile)
    out = dequant_accumulate(acc, q, s, tile=tile)
    ref = jax.jit(dequant_acc_ref, static_argnames=("tile",))(acc, q, s, tile=tile)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # and the roundtrip is a faithful int8 reconstruction of x
    err = np.max(np.abs(np.asarray(out - acc - x)))
    assert err <= np.max(np.abs(np.asarray(x))) / 127.0


def test_quantize_zero_tile_scale_is_one(rng):
    x = jnp.zeros((64, 64), jnp.float32)
    q, s = quantize_tiles(x, tile=32)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(s), 1.0)


# ---------------------------------------------------------------------------
# WireFormat / roundtrip algebra
# ---------------------------------------------------------------------------


def test_wireformat_validation():
    with pytest.raises(ValueError):
        WireFormat(kind="int4")
    with pytest.raises(ValueError):
        WireFormat(tile=0)
    with pytest.raises(ValueError):
        WireFormat(rank=0)


def test_wire_bytes_ratio():
    fp32 = stats_wire_bytes(64, 50, "fp32")
    int8 = stats_wire_bytes(64, 50, "int8")
    assert fp32 / int8 >= 3.9
    # sketch beats int8 when r ≪ d/4 and C ≪ d
    assert stats_wire_bytes(1280, 10, "sketch", rank=64) < stats_wire_bytes(
        1280, 10, "int8"
    )
    with pytest.raises(ValueError):
        stats_wire_bytes(64, 50, "bf16")


def test_cost_model_wire_pricing():
    cm = CostModel(b=2.22e6, d=1280, C=100)
    assert cm.compressed_stats_bytes("fp32") == cm.tenant_stats_bytes(1)
    assert cm.wire_compression_ratio("int8") >= 3.9
    # fp32 default reproduces the pre-compression two_stage_allreduce
    base = cm.two_stage_allreduce(8, n_pods=2)
    assert cm.two_stage_allreduce(8, n_pods=2, wire="fp32") == base
    int8 = cm.two_stage_allreduce(8, n_pods=2, wire="int8")
    assert int8["payload_bytes"] < base["payload_bytes"]
    assert int8["total_s"] < base["total_s"]


def test_fp32_roundtrip_is_bitwise_identity(rng):
    A = jax.random.normal(rng, (D, D))
    b = jax.random.normal(jax.random.fold_in(rng, 1), (D, C))
    Ah, bh = compress.wire_roundtrip(A, b, WireFormat(), use_kernel=False)
    assert Ah is A and bh is b


def test_roundtrip_add_matches_unfused(rng):
    A = jax.random.normal(rng, (D, D))
    b = jax.random.normal(jax.random.fold_in(rng, 1), (D, C))
    accA = jax.random.normal(jax.random.fold_in(rng, 2), (D, D))
    accb = jax.random.normal(jax.random.fold_in(rng, 3), (D, C))
    fmt = WireFormat(kind="int8", tile=16)
    fa, fb = compress.roundtrip_add(accA, accb, A, b, fmt, use_kernel=False)
    Ah, bh = compress.wire_roundtrip(A, b, fmt, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(accA + Ah))
    np.testing.assert_array_equal(np.asarray(fb), np.asarray(accb + bh))


def test_sketch_exact_on_low_rank(rng):
    r = 8
    Z = jax.random.normal(rng, (r, D))
    A = Z.T @ Z  # rank-r PSD by construction
    Ah = compress.unsketch(compress.sketch_psd(A, r))
    np.testing.assert_allclose(np.asarray(Ah), np.asarray(A), atol=1e-4)


def test_fp8_fallback_warns(monkeypatch):
    monkeypatch.setattr(compress, "fp8_supported", lambda: False)
    compress._warn_fp8_fallback.cache_clear()
    with pytest.warns(RuntimeWarning, match="falling back to int8"):
        resolved = WireFormat(kind="fp8").resolved()
    assert resolved.kind == "int8"
    assert resolved.tile == WireFormat(kind="fp8").tile


def test_fp8_fallback_warns_once_per_process(monkeypatch, recwarn):
    """Regression: every engine construction used to re-emit the fallback
    warning; it must fire exactly once per process no matter how many
    WireFormats resolve (the backend's fp8 support cannot change)."""
    monkeypatch.setattr(compress, "fp8_supported", lambda: False)
    compress._warn_fp8_fallback.cache_clear()
    try:
        for _ in range(5):
            assert WireFormat(kind="fp8").resolved().kind == "int8"
        # engines resolve at construction too — still no second warning
        StreamingEngine(StreamConfig(
            n_classes=C, ridge_lambda=1e-2, wire=WireFormat(kind="fp8"),
        ))
        fallback = [
            w for w in recwarn.list
            if issubclass(w.category, RuntimeWarning)
            and "falling back to int8" in str(w.message)
        ]
        assert len(fallback) == 1
    finally:
        compress._warn_fp8_fallback.cache_clear()


@pytest.mark.skipif(not compress.fp8_supported(), reason="backend lacks fp8")
def test_fp8_roundtrip_accuracy(rng):
    A = jax.random.normal(rng, (D, D))
    b = jax.random.normal(jax.random.fold_in(rng, 1), (D, C))
    Ah, bh = compress.wire_roundtrip(
        A, b, WireFormat(kind="fp8", tile=16), use_kernel=False
    )
    # e4m3 carries a 3-bit mantissa: relative error ≤ 2⁻⁴ elementwise
    assert np.max(np.abs(np.asarray(Ah - A))) <= np.max(np.abs(np.asarray(A))) / 8


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------


def test_error_feedback_telescopes(rng):
    """Over R rounds the EF aggregate error stays O(1) quantization step;
    the deterministic no-EF error accumulates and must be strictly worse."""
    npr = np.random.default_rng(0)
    clients = _clients(npr, K=4)
    R = 10

    def total_err(error_feedback):
        fmt = WireFormat(kind="int8", tile=16, error_feedback=error_feedback)
        up = UplinkCompressor(fmt, use_kernel=False)
        tot = fed3r.init_stats(D, C)
        exact = fed3r.init_stats(D, C)
        for _ in range(R):
            for k, (x, y) in clients.items():
                s = _client_stats(x, y)
                tot = fed3r.merge(tot, up.upload(k, s))
                exact = fed3r.merge(exact, s)
        return float(jnp.max(jnp.abs(tot.A - exact.A))), tot, exact

    e_ef, tot_ef, exact = total_err(True)
    e_no, _, _ = total_err(False)
    assert e_ef < e_no, f"EF ({e_ef}) must beat no-EF ({e_no})"
    assert e_no / max(e_ef, 1e-12) > 2.0  # telescoping, not luck
    # the compressed solve classifies the synthetic eval like the exact one
    W_ef = fed3r.solve(tot_ef, 1e-1)
    W_exact = fed3r.solve(exact, 1e-1)
    xs = jnp.asarray(np.concatenate([x for x, _ in clients.values()]))
    p_ef = jnp.argmax(fed3r.predict(W_ef, xs), axis=1)
    p_exact = jnp.argmax(fed3r.predict(W_exact, xs), axis=1)
    assert float(jnp.mean(p_ef == p_exact)) >= 0.995


def test_ef_fp32_is_exact_passthrough(rng):
    A = jax.random.normal(rng, (D, D))
    b = jax.random.normal(jax.random.fold_in(rng, 1), (D, C))
    ef = compress.ef_init(D, C)
    Ah, bh, ef2 = compress.compress_stats_ef(A, b, ef, WireFormat())
    assert Ah is A and bh is b and ef2 is ef


def test_uplink_compressor_accounting():
    npr = np.random.default_rng(1)
    clients = _clients(npr, K=3)
    up = UplinkCompressor(WireFormat(kind="int8", tile=16), use_kernel=False)
    for k, (x, y) in clients.items():
        up.upload(k, _client_stats(x, y))
    assert up.uploads == 3
    assert up.compression_ratio >= 3.5
    assert up.bytes_sent < up.bytes_fp32


def test_ef_state_isolated_per_client():
    npr = np.random.default_rng(2)
    clients = _clients(npr, K=2)
    up = UplinkCompressor(WireFormat(kind="int8", tile=16), use_kernel=False)
    for k, (x, y) in clients.items():
        up.upload(k, _client_stats(x, y))
    e0, e1 = up._residuals[0], up._residuals[1]
    assert isinstance(e0, EFState)
    assert not np.array_equal(np.asarray(e0.eA), np.asarray(e1.eA))


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------


def test_engine_fp32_wire_bitwise_identical():
    npr = np.random.default_rng(3)
    packed = pack_client_shards(_clients(npr), clients_per_shard=3)
    _, acc_default = _run_engine(packed, WireFormat())
    eng = AccumulationEngine(EngineConfig(n_classes=C, use_kernel=False))
    acc_plain = eng.accumulate(eng.init(D), packed)
    np.testing.assert_array_equal(
        np.asarray(acc_default.stats.A), np.asarray(acc_plain.stats.A)
    )
    np.testing.assert_array_equal(
        np.asarray(acc_default.stats.b), np.asarray(acc_plain.stats.b)
    )


@pytest.mark.parametrize("fmt", [
    WireFormat(kind="int8", tile=16),
    WireFormat(kind="sketch", rank=32),
])
def test_engine_compressed_one_dispatch_and_close(fmt):
    npr = np.random.default_rng(4)
    clients = _clients(npr, K=8, lo=10, hi=30)
    packed = pack_client_shards(clients, clients_per_shard=4)
    eng32, acc32 = _run_engine(packed, WireFormat())
    engc, accc = _run_engine(packed, fmt)
    assert eng32.dispatches == engc.dispatches == 1
    W32 = fed3r.solve(acc32.stats, 1e-1)
    Wc = fed3r.solve(accc.stats, 1e-1)
    # the classifiers agree on the separable synthetic eval
    xs = np.concatenate([x for x, _ in clients.values()])
    ys = np.concatenate([y for _, y in clients.values()])
    p32 = np.argmax(np.asarray(fed3r.predict(W32, jnp.asarray(xs))), axis=1)
    pc = np.argmax(np.asarray(fed3r.predict(Wc, jnp.asarray(xs))), axis=1)
    acc_32 = float(np.mean(p32 == ys))
    acc_c = float(np.mean(pc == ys))
    assert abs(acc_32 - acc_c) <= 0.005


def test_engine_int8_accuracy_gap_on_unsaturated_eval():
    """Every wire format keeps the one-dispatch contract, and the int8 wire
    moves the served classifier's accuracy by at most 0.5 points against
    the fp32 engine, on a noisy 50-class federation whose accuracy sits
    mid-range (a saturated eval would pass any format)."""
    d, n_classes = 64, 50
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(n_classes, d)).astype(np.float32) * 2.0

    def draw(n):
        y = rng.integers(0, n_classes, size=n).astype(np.int32)
        return centers[y] + 7.0 * rng.normal(size=(n, d)).astype(np.float32), y

    clients = {k: draw(int(rng.integers(40, 120))) for k in range(24)}
    eval_x, eval_y = draw(4000)
    packed = pack_client_shards(clients, clients_per_shard=6)
    acc = {}
    for kind in ("fp32", "int8", "fp8", "sketch"):
        eng, out = _run_engine(packed, WireFormat(kind=kind, tile=32, rank=48),
                               n_classes=n_classes, d=d)
        assert eng.dispatches == 1, kind
        W = fed3r.solve(out.stats, 0.1)
        pred = np.argmax(np.asarray(fed3r.predict(W, jnp.asarray(eval_x))), axis=1)
        acc[kind] = float(np.mean(pred == eval_y))
    assert 0.2 < acc["fp32"] < 0.8, acc
    assert abs(acc["int8"] - acc["fp32"]) <= 0.005, acc


@pytest.mark.parametrize("kind,kw", [
    ("fp32", {}),
    ("int8", {"tile": 16}),
    ("sketch", {"rank": 32}),
])
def test_engine_client_permutation_invariant(kind, kw):
    """Canonical fold order makes A bitwise invariant to client relabeling
    of the SAME shard contents under every wire format."""
    npr = np.random.default_rng(5)
    clients = _clients(npr)
    perm = {k: clients[k] for k in reversed(sorted(clients))}
    fmt = WireFormat(kind=kind, **kw)
    _, acc_a = _run_engine(pack_client_shards(clients, clients_per_shard=3), fmt)
    _, acc_b = _run_engine(pack_client_shards(perm, clients_per_shard=3), fmt)
    np.testing.assert_array_equal(
        np.asarray(acc_a.stats.A), np.asarray(acc_b.stats.A)
    )
    np.testing.assert_array_equal(
        np.asarray(acc_a.stats.b), np.asarray(acc_b.stats.b)
    )


# ---------------------------------------------------------------------------
# Streaming engine under compression
# ---------------------------------------------------------------------------


def _waves(npr, T=5, P=2, d=D, n_classes=C):
    centers = npr.normal(size=(n_classes, d)).astype(np.float32) * 3.0
    waves = []
    for _ in range(T):
        wave = []
        for _ in range(P):
            n = int(npr.integers(4, 12))
            y = npr.integers(0, n_classes, size=n).astype(np.int32)
            wave.append((centers[y] + npr.normal(size=(n, d)).astype(np.float32), y))
        waves.append(wave)
    return pack_arrival_waves(waves)


def _run_stream(packed, fmt):
    eng = StreamingEngine(
        StreamConfig(n_classes=C, ridge_lambda=1e-2, use_kernel=False, wire=fmt)
    )
    state, trace = eng.absorb(eng.init(D), packed)
    return eng, state


def test_streaming_fp32_wire_bitwise_identical():
    packed = _waves(np.random.default_rng(6))
    _, s_wire = _run_stream(packed, WireFormat())
    eng = StreamingEngine(
        StreamConfig(n_classes=C, ridge_lambda=1e-2, use_kernel=False)
    )
    s_plain, _ = eng.absorb(eng.init(D), packed)
    np.testing.assert_array_equal(np.asarray(s_wire.L), np.asarray(s_plain.L))
    np.testing.assert_array_equal(np.asarray(s_wire.W), np.asarray(s_plain.W))


@pytest.mark.parametrize("fmt", [
    WireFormat(kind="int8", tile=16),
    WireFormat(kind="sketch", rank=40),
])
def test_streaming_compressed_finite_one_dispatch(fmt):
    """Rank-deficient early waves make the quantized Gram indefinite; the
    PSD-guarded Cholesky must keep the whole stream finite at 1 dispatch."""
    packed = _waves(np.random.default_rng(7))
    eng32, s32 = _run_stream(packed, WireFormat())
    engc, sc = _run_stream(packed, fmt)
    assert eng32.dispatches == engc.dispatches == 1
    assert bool(jnp.all(jnp.isfinite(sc.L)))
    assert bool(jnp.all(jnp.isfinite(sc.W)))
    rel = float(jnp.max(jnp.abs(sc.W - s32.W)) / jnp.max(jnp.abs(s32.W)))
    assert rel < 0.5  # lossy but sane


def test_psd_cholesky_repairs_indefinite(rng):
    """A Gram pushed indefinite by quantization-scale noise factors finite,
    while a clean PD matrix passes through bit-identically."""
    G_pd = jnp.eye(16) * 2.0
    bound = jnp.asarray(0.5, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(compress.psd_cholesky(G_pd, bound)),
        np.asarray(jnp.linalg.cholesky(G_pd)),
    )
    noise = jax.random.normal(rng, (16, 16)) * 0.1
    G_bad = jnp.eye(16) * 1e-4 + (noise + noise.T) / 2.0
    assert bool(jnp.any(jnp.isnan(jnp.linalg.cholesky(G_bad))))
    L = compress.psd_cholesky(G_bad, jnp.asarray(1.0, jnp.float32))
    assert bool(jnp.all(jnp.isfinite(L)))


def test_quant_spectral_bound_kinds(rng):
    S = jax.random.normal(rng, (32, 32))
    assert float(compress.quant_spectral_bound(S, WireFormat())) == 0.0
    assert float(compress.quant_spectral_bound(S, WireFormat(kind="sketch"))) == 0.0
    b8 = compress.quant_spectral_bound(S, WireFormat(kind="int8"))
    assert float(b8) > 0.0


# ---------------------------------------------------------------------------
# Secure aggregation over integer payloads
# ---------------------------------------------------------------------------


def test_secure_agg_quantized_masks_cancel_exactly():
    npr = np.random.default_rng(8)
    clients = _clients(npr, K=4)
    cohort = sorted(clients)
    stats = [_client_stats(*clients[k]) for k in cohort]
    payloads, sA, sb = compress.cohort_quantize_int8(stats, tile=16)
    masked = [
        secure_agg.mask_quantized_payload(p, k, cohort, seed=11)
        for k, p in zip(cohort, payloads)
    ]
    # each masked upload is NOT the plain payload (the privacy property)
    for m, p in zip(masked, payloads):
        assert not np.array_equal(np.asarray(m.qA), np.asarray(p.qA))
    agg_masked = secure_agg.secure_aggregate_quantized(masked)
    agg_plain = secure_agg.secure_aggregate_quantized(payloads)
    np.testing.assert_array_equal(
        np.asarray(agg_masked.qA), np.asarray(agg_plain.qA)
    )
    np.testing.assert_array_equal(
        np.asarray(agg_masked.qb), np.asarray(agg_plain.qb)
    )
    # the masked integer sum dequantizes to the true cohort aggregate
    A_sum, b_sum = compress.dequantize_int_sum(agg_masked, sA, sb, tile=16)
    exact = stats[0]
    for s in stats[1:]:
        exact = fed3r.merge(exact, s)
    relA = float(jnp.max(jnp.abs(A_sum - exact.A)) / jnp.max(jnp.abs(exact.A)))
    relb = float(jnp.max(jnp.abs(b_sum - exact.b)) / jnp.max(jnp.abs(exact.b)))
    assert relA < 0.02 and relb < 0.02


def test_secure_agg_quantized_rejects_float_payloads(rng):
    bad = compress.IntPayload(
        qA=jax.random.normal(rng, (8, 8)),
        qb=jax.random.normal(rng, (8, 2)),
    )
    with pytest.raises(TypeError):
        secure_agg.mask_quantized_payload(bad, 0, [0, 1], seed=0)


def test_float_masking_still_works():
    """The pre-existing float path is untouched by the integer additions."""
    npr = np.random.default_rng(9)
    clients = _clients(npr, K=3)
    cohort = sorted(clients)
    stats = [_client_stats(*clients[k]) for k in cohort]
    masked = [
        secure_agg.mask_statistics(s, k, cohort, seed=5)
        for k, s in zip(cohort, stats)
    ]
    agg = secure_agg.secure_aggregate(masked)
    exact = stats[0]
    for s in stats[1:]:
        exact = fed3r.merge(exact, s)
    np.testing.assert_allclose(
        np.asarray(agg.A), np.asarray(exact.A), rtol=1e-3, atol=1e-2
    )

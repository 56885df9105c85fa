#!/usr/bin/env python3
"""Run the Fed3R main path once on a TPU chip and check what comes out.

    python chip_smoke.py               # one chip: phase A, then phase B
    python chip_smoke.py --four-chips  # four chips: the psum mesh path only

Phase A drives the trainer entry point, ``repro.launch.train.run``, at the
full width of ``fed3r-mnv2-proxy`` (d_model=1280, 6 layers, 97.1M
parameters, random weights from a seed): the Fed3R statistics pass with
the backbone inside the engine scan and the ``fed3r_stats`` kernel per
client, the solve and temperature calibration, then FedAvg fine-tuning
rounds on the round engine.

Phase B drives the closed form at the paper's Landmarks shape, d=1280 and
C=2028, on synthetic features from a seed: ``AccumulationEngine`` with the
statistics kernel, ``fed3r.solve``, ``StreamingEngine`` with ``chol_gram``,
and a ``ServingEngine`` absorb plus one tick (``batched_chol_gram``, then
the serve gather and matmul).  Each result is compared on the same chip
with the XLA path (``use_kernel=False``) run under
``jax.default_matmul_precision("highest")``, the fp32 reference.

``--four-chips`` runs the accumulation and streaming engines in ``psum``
mode over a mesh of four chips against ``merge`` on one chip, on features
that live on a grid (every Gram sum is then exact in fp32).

Every check raises once its phase has printed its numbers; the JSON line
that ends the output is printed only when every phase passed.  ``main()``
refuses to run on anything but a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import fed3r  # noqa: E402
from repro.data.pipeline import (  # noqa: E402
    make_federated_features,
    pack_arrival_waves,
    pack_client_shards,
)
from repro.federated import compress  # noqa: E402
from repro.federated.arrivals import pack_schedule, poisson_schedule  # noqa: E402
from repro.federated.dist import DistConfig  # noqa: E402
from repro.federated.engine import AccumulationEngine, EngineConfig  # noqa: E402
from repro.federated.streaming_engine import (  # noqa: E402
    StreamConfig,
    StreamingEngine,
    batch_equivalent,
)
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import data_parallel_size, make_host_mesh  # noqa: E402
from repro.launch.serving_engine import ServingConfig, ServingEngine  # noqa: E402

D_FEAT = 1280  # MobileNetV2 feature width (paper Table 4)
N_CLASSES = 2028  # Landmarks-Users-160k classes (paper Table 4)
PROXY_ARCH = "fed3r-mnv2-proxy"

# Largest max|kernel - reference| / max|reference| accepted.  The kernels
# contract fp32 operands at fp32 and the reference is XLA at "highest", so
# the two differ by summation order only: about sqrt(n)·eps on the
# statistics (n ≤ a few thousand rows per fold), and at most the
# conditioning of A + λI (≈ 10-40 on these features) times that on
# anything solved from them.
TOL_STATS = 1e-5  # A, b
TOL_SOLVED = 1e-4  # W, served heads and scores
# Claims the CPU test suite makes, reported here and not gated: the
# streaming engine's factored W against a batch re-solve of the same data
# (tests and bench_streaming hold it to ~1e-6 at small d).
CPU_CLAIM_FACTORED = 1e-6


def _max_abs(got, ref) -> tuple:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError("non-finite values in the result")
    err = float(np.max(np.abs(got - ref)))
    return err, float(np.max(np.abs(ref)))


def _compare(tag: str, label: str, got, ref, tol: float, failures: list) -> float:
    """Print the max-abs error of ``got`` against ``ref``; record a failure
    when it is above ``tol`` of the reference's scale."""
    err, scale = _max_abs(got, ref)
    rel = err / scale if scale > 0 else err
    ok = rel <= tol
    print(f"[{tag}] {label}: max|Δ| {err!r} (max|ref| {scale!r}, "
          f"relative {rel!r}, tolerance {tol!r}) {'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(label)
    return err


def _kernels_compiled(tag: str, programs: dict, require: bool) -> None:
    """Each compiled program must hold its Pallas kernel as a
    ``tpu_custom_call``: neither interpreted nor replaced by XLA GEMMs."""
    missing = []
    for name, lowered in programs.items():
        has = "tpu_custom_call" in lowered.compile().as_text()
        print(f"[{tag}] {name} program holds a compiled Pallas kernel: {has}")
        if not has:
            missing.append(name)
    if require and missing:
        raise AssertionError(f"no compiled Pallas kernel in: {missing}")


def _finish(tag: str, failures: list) -> None:
    if failures:
        raise AssertionError(f"[{tag}] outside tolerance: {failures}")


# ---------------------------------------------------------------------------
# phase A: the trainer
# ---------------------------------------------------------------------------


def phase_trainer(
    arch: str = PROXY_ARCH,
    *,
    n_classes: int = 16,
    n_clients: int = 40,
    clients_per_round: int = 8,
    rounds: int = 3,
    n_samples: int = 2048,
    seq_len: int = 32,
    local_batch_size: int = 64,
) -> dict:
    """Fed3R statistics → solve → calibration → FedAvg rounds, through
    ``repro.launch.train.run``.  ``clients_per_round=8`` is the cohort whose
    round program the v5e compiler puts at 12.8 GB of the chip's 16."""
    t0 = time.perf_counter()
    log = train.run(
        arch, n_classes=n_classes, n_clients=n_clients,
        clients_per_round=clients_per_round, rounds=rounds, seq_len=seq_len,
        n_samples=n_samples, local_batch_size=local_batch_size,
    )
    wall = time.perf_counter() - t0
    mem = log["ft_step_memory"]
    print(f"[A] {arch}: {rounds} FedAvg rounds of {clients_per_round} clients, "
          f"cold wall time {wall!r} s (includes compilation)")
    print(f"[A] Fed3R accuracy {log['fed3r_acc']!r}, fine-tuned accuracy "
          f"{log['ft_acc'][-1]!r} after round {log['rounds'][-1]}")
    print(f"[A] round program bytes (compiler): argument "
          f"{mem.argument_size_in_bytes}, output {mem.output_size_in_bytes}, "
          f"alias {mem.alias_size_in_bytes}, temp {mem.temp_size_in_bytes}")
    chance = 1.0 / n_classes
    accs = [log["fed3r_acc"], *log["ft_acc"]]
    if not all(np.isfinite(a) and 0.0 <= a <= 1.0 for a in accs):
        raise AssertionError(f"[A] accuracies out of range: {accs}")
    if log["rounds"][-1] != rounds:
        raise AssertionError(f"[A] ran {log['rounds'][-1]} of {rounds} rounds")
    if log["fed3r_acc"] <= chance:
        raise AssertionError(
            f"[A] Fed3R accuracy {log['fed3r_acc']} is not above chance {chance}"
        )
    return {"wall_s": wall, "fed3r_acc": log["fed3r_acc"], "ft_acc": log["ft_acc"][-1]}


# ---------------------------------------------------------------------------
# phase B: the closed form at the Landmarks shape
# ---------------------------------------------------------------------------


def phase_closed_form(
    *,
    d: int = D_FEAT,
    n_classes: int = N_CLASSES,
    n_samples: int = 10240,
    n_clients: int = 32,
    clients_per_shard: int = 4,
    n_waves: int = 4,
    n_slots: int = 16,
    n_tenants: int = 8,
    queries_per_tenant: int = 4,
    ridge_lambda: float = 1e-2,
    seed: int = 0,
    require_kernels: bool = False,
) -> dict:
    """Kernel paths against the fp32 XLA reference, on one device."""
    t0 = time.perf_counter()
    fed, _ = make_federated_features(
        seed=seed, n=n_samples, d=d, n_classes=n_classes,
        n_clients=n_clients, alpha=0.3, noise=2.0,
    )
    clients = [
        (fed.client(k).features, fed.client(k).labels) for k in range(n_clients)
    ]
    packed = pack_client_shards(clients, clients_per_shard)
    arrivals = pack_schedule(
        fed, poisson_schedule(n_clients, n_waves, n_clients / n_waves, seed=seed)
    )
    tenants = [k % n_clients for k in range(n_tenants) for _ in range(queries_per_tenant)]
    queries = np.stack([
        fed.client(t).features[i] for i, t in enumerate(tenants)
    ]).astype(np.float32)
    failures: list = []
    out = {}

    def accumulate(use_kernel, clients_per_shard=clients_per_shard):
        eng = AccumulationEngine(EngineConfig(n_classes=n_classes, use_kernel=use_kernel))
        p = pack_client_shards(clients, clients_per_shard)
        return eng, eng.accumulate(eng.init(d), p)

    def stream(use_kernel):
        eng = StreamingEngine(StreamConfig(
            n_classes=n_classes, ridge_lambda=ridge_lambda, use_kernel=use_kernel,
        ))
        state, _ = eng.absorb(eng.init(d), arrivals)
        return eng, state

    def serve(use_kernel):
        eng = ServingEngine(ServingConfig(
            n_classes=n_classes, ridge_lambda=ridge_lambda, n_slots=n_slots,
            solve_bucket=n_tenants, serve_bucket=len(tenants),
            alpha_grid=(1.0,), use_kernel=use_kernel,
        ), fed)
        eng.init(d)
        eng.absorb(arrivals)
        scores, report = eng.query(tenants, queries)
        if report["per_tenant"] != len(tenants):
            raise AssertionError(f"[B] served {report['per_tenant']} per-tenant "
                                 f"answers of {len(tenants)}")
        return eng, scores

    kern, acc_k = accumulate(True)
    W_k = fed3r.solve(acc_k.stats, ridge_lambda)
    s_eng, s_k = stream(True)
    srv, scores_k = serve(True)
    with jax.default_matmul_precision("highest"):
        _, acc_x = accumulate(False)
        W_x = fed3r.solve(acc_x.stats, ridge_lambda)
        _, s_x = stream(False)
        srv_x, scores_x = serve(False)
        W_batch, _ = batch_equivalent(arrivals, s_eng.cfg)
    jax.block_until_ready((W_k, W_x, s_k.W, s_x.W, scores_k, scores_x))
    wall = time.perf_counter() - t0
    print(f"[B] d={d} C={n_classes}: {n_clients} clients, {packed.inputs.shape[0]} "
          f"shards, {n_waves} waves, {len(tenants)} queries over {n_tenants} "
          f"tenants; cold wall time {wall!r} s (includes compilation)")

    if float(acc_k.stats.n) != float(acc_x.stats.n) or float(acc_k.stats.n) != len(fed.labels):
        failures.append("n")
    _compare("B", "statistics A", acc_k.stats.A, acc_x.stats.A, TOL_STATS, failures)
    _compare("B", "statistics b", acc_k.stats.b, acc_x.stats.b, TOL_STATS, failures)
    _compare("B", "solve W", W_k, W_x, TOL_SOLVED, failures)
    _compare("B", "streaming served W", s_k.W, s_x.W, TOL_SOLVED, failures)
    _compare("B", "serving head table", srv.table.heads, srv_x.table.heads,
             TOL_SOLVED, failures)
    out["scores_err"] = _compare("B", "serving scores", scores_k, scores_x,
                                 TOL_SOLVED, failures)

    # claims of the CPU suite, reported as measured here
    _, acc_k8 = accumulate(True, clients_per_shard=2 * clients_per_shard)
    same = bool(
        np.array_equal(np.asarray(acc_k.stats.A), np.asarray(acc_k8.stats.A))
        and np.array_equal(np.asarray(acc_k.stats.b), np.asarray(acc_k8.stats.b))
    )
    print(f"[B] claim: kernel A, b bitwise equal under re-sharding "
          f"({clients_per_shard} vs {2 * clients_per_shard} clients per shard): {same}")
    err, _ = _max_abs(s_k.W, W_batch)
    print(f"[B] claim: streaming W vs batch re-solve max|Δ| {err!r} "
          f"(CPU suite: <= {CPU_CLAIM_FACTORED!r}): {err <= CPU_CLAIM_FACTORED}")
    print(f"[B] fp8 wire on {jax.default_backend()}: "
          f"{'native' if compress.fp8_supported() else 'falls back to int8'}")

    _kernels_compiled("B", {
        "statistics": kern.lower(kern.init(d), packed),
        "streaming": s_eng.lower(s_eng.init(d), arrivals),
        "serving solve": srv.lower_solve(sorted(set(tenants))),
    }, require_kernels)
    _finish("B", failures)
    out["wall_s"] = wall
    return out


# ---------------------------------------------------------------------------
# four chips: the psum mesh path against merge on one chip
# ---------------------------------------------------------------------------


def _grid_clients(seed: int, n_clients: int, n: int, d: int, n_classes: int):
    """Features on a 1/8 grid in [-2, 2]: every product lands on a 1/64
    grid and every partial sum stays below 2^24/64, so fp32 sums of them
    are exact in any order."""
    rng = np.random.default_rng(seed)
    return [
        ((rng.integers(-16, 17, size=(n, d)) / 8.0).astype(np.float32),
         rng.integers(0, n_classes, size=n).astype(np.int32))
        for _ in range(n_clients)
    ]


def phase_mesh(
    *,
    d: int = D_FEAT,
    n_classes: int = N_CLASSES,
    n_clients: int = 32,
    client_n: int = 256,
    clients_per_shard: int = 2,
    clients_per_wave: int = 8,
    ridge_lambda: float = 1e-2,
    seed: int = 0,
    require_kernels: bool = False,
) -> dict:
    """``DistConfig(aggregation="psum", mesh=make_host_mesh())`` over every
    local device, against the ``merge`` engines on the first device."""
    t0 = time.perf_counter()
    mesh = make_host_mesh()
    n_dev = data_parallel_size(mesh)
    psum = DistConfig(aggregation="psum", mesh=mesh)
    clients = _grid_clients(seed, n_clients, client_n, d, n_classes)
    packed = pack_client_shards(clients, clients_per_shard, mesh=mesh)
    waves = [clients[i:i + clients_per_wave]
             for i in range(0, n_clients, clients_per_wave)]
    arrivals = pack_arrival_waves(waves, mesh=mesh)
    failures: list = []

    acc_eng = AccumulationEngine(EngineConfig(n_classes=n_classes, dist=psum))
    placed = acc_eng.dist.place(packed.inputs)
    rows = {s.data.shape[0] for s in placed.addressable_shards}
    devices = {s.device for s in placed.addressable_shards}
    print(f"[mesh] {n_dev} devices; packed shards {packed.inputs.shape[0]} placed "
          f"as {sorted(rows)} per device on {len(devices)} devices "
          f"({placed.sharding.spec})")
    if len(devices) != n_dev or rows != {packed.inputs.shape[0] // n_dev}:
        raise AssertionError("[mesh] inputs are not split over the data axis")

    acc_p = acc_eng.accumulate(acc_eng.init(d), packed)
    merge = AccumulationEngine(EngineConfig(n_classes=n_classes))
    acc_m = merge.accumulate(merge.init(d), packed)
    W_p = fed3r.solve(acc_p.stats, ridge_lambda)
    W_m = fed3r.solve(acc_m.stats, ridge_lambda)

    st_eng = StreamingEngine(StreamConfig(
        n_classes=n_classes, ridge_lambda=ridge_lambda, dist=psum,
    ))
    st_p, _ = st_eng.absorb(st_eng.init(d), arrivals)
    st_merge = StreamingEngine(StreamConfig(n_classes=n_classes, ridge_lambda=ridge_lambda))
    st_m, _ = st_merge.absorb(st_merge.init(d), arrivals)
    jax.block_until_ready((W_p, W_m, st_p.W, st_m.W))
    wall = time.perf_counter() - t0
    print(f"[mesh] d={d} C={n_classes}: {n_clients} grid clients of {client_n}, "
          f"cold wall time {wall!r} s (includes compilation)")

    _compare("mesh", "psum vs merge A", acc_p.stats.A, acc_m.stats.A, TOL_STATS, failures)
    _compare("mesh", "psum vs merge b", acc_p.stats.b, acc_m.stats.b, TOL_STATS, failures)
    _compare("mesh", "psum vs merge W", W_p, W_m, TOL_SOLVED, failures)
    _compare("mesh", "streaming psum vs merge W", st_p.W, st_m.W, TOL_SOLVED, failures)
    for label, a, b in [
        ("A", acc_p.stats.A, acc_m.stats.A),
        ("b", acc_p.stats.b, acc_m.stats.b),
        ("W", W_p, W_m),
        ("streaming L", st_p.L, st_m.L),
        ("streaming W", st_p.W, st_m.W),
    ]:
        print(f"[mesh] claim: {label} bitwise equal on grid features: "
              f"{bool(np.array_equal(np.asarray(a), np.asarray(b)))}")

    for name, lowered in {
        "statistics psum": acc_eng.lower(acc_eng.init(d), packed),
        "streaming psum": st_eng.lower(st_eng.init(d), arrivals),
    }.items():
        text = lowered.compile().as_text()
        print(f"[mesh] {name} program: all-reduce {'all-reduce' in text}, "
              f"compiled Pallas kernel {'tpu_custom_call' in text}")
        if require_kernels and "tpu_custom_call" not in text:
            raise AssertionError(f"[mesh] no compiled Pallas kernel in {name}")
    _finish("mesh", failures)
    return {"wall_s": wall}


# ---------------------------------------------------------------------------


def _print_peak_memory(tag: str) -> None:
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        print(f"[{tag}] {dev}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the psum mesh path over four chips, against merge on one",
    )
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
          f"device count {len(devices)}; compile cache {cache}")
    if args.four_chips:
        phase_mesh(require_kernels=True)
        _print_peak_memory("mesh")
    else:
        phase_trainer()
        _print_peak_memory("A")
        phase_closed_form(require_kernels=True)
        _print_peak_memory("B")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Paper App. D/E + Fig. 2 mid/right: communication & computation meters.

Reproduces the paper's cost accounting at PAPER scale (MobileNetV2 d=1280,
Landmarks C=2028 / iNaturalist C=1203, FP32) — these are exact analytic
quantities, so the reproduction is exact, not directional.

The tail section meters the model against REALITY: actual quantized-array
bytes vs ``stats_wire_bytes`` and XLA ``cost_analysis`` FLOPs vs the
analytic solve/serve counts.  Each delta lands as a ``cost_model_drift`` telemetry gauge and
prints a WARNING line when measured/model leaves [0.5, 2.0]x — the early
tripwire for the cost model silently drifting away from the code it prices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np

from common import emit
from repro.federated.compress import sketch_psd
from repro.federated.costs import INATURALIST, LANDMARKS, CostModel
from repro.federated.telemetry import get_telemetry
from repro.kernels.ref import quantize_tiles_ref

ALGS = ("fedavg", "fedavgm", "scaffold", "fedavg-lp", "scaffold-lp",
        "fed3r", "fed3r-rf", "fed3r-personalized", "personalized-ft")


def _drift(name: str, measured: float, model: float,
           warn_low: bool = True, note: str = "") -> None:
    """One measured-vs-CostModel meter: gauge + WARNING outside [0.5, 2.0]x.

    ``warn_low=False`` silences the under-count direction for meters where
    the measurement is a known lower bound (XLA ``cost_analysis`` omits
    custom-call FLOPs, so library Cholesky/triangular solves read low).
    """
    ratio = measured / model if model else float("inf")
    get_telemetry().gauge("cost_model_drift", meter=name).set(ratio)
    flag = ""
    if ratio > 2.0 or (warn_low and ratio < 0.5):
        flag = " WARNING_gt2x_drift"
        print(f"# WARNING drift_{name}: measured/model = {ratio:.3f}x "
              f"(outside [0.5, 2.0])", flush=True)
    extra = f" note={note}" if note else ""
    emit(f"drift_{name}",
         f"measured={measured:.4e} model={model:.4e} "
         f"ratio={ratio:.3f}x{flag}{extra}")


def _xla_flops(fn, *xs) -> float | None:
    """FLOPs XLA attributes to the compiled fn, or None when unavailable."""
    try:
        c = jax.jit(fn).lower(*xs).compile().cost_analysis()
        if isinstance(c, list):
            c = c[0]
        f = c.get("flops")
        return None if f is None else float(f)
    except Exception:  # noqa: BLE001 — cost_analysis is backend-best-effort
        return None


def measured_vs_model() -> None:
    """Meter the CostModel against real arrays and XLA."""
    d, C, tile, rank, q = 256, 64, 128, 16, 1024
    cm = CostModel(b=0.0, d=d, C=C)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((2 * d, d)).astype(np.float32)
    A = jnp.asarray(X.T @ X)  # a real PSD second moment
    b = jnp.asarray(rng.standard_normal((d, C)).astype(np.float32))

    # wire bytes: the bytes that actually cross the uplink per format
    _drift("wire_fp32_bytes", A.nbytes + b.nbytes,
           cm.compressed_stats_bytes("fp32", tile=tile, rank=rank))
    qa, sa = quantize_tiles_ref(A, tile=tile)
    qb, sb = quantize_tiles_ref(b, tile=tile)
    _drift("wire_int8_bytes", qa.nbytes + sa.nbytes + qb.nbytes + sb.nbytes,
           cm.compressed_stats_bytes("int8", tile=tile, rank=rank))
    Z = sketch_psd(A, rank)
    _drift("wire_sketch_bytes", Z.nbytes + b.nbytes,
           cm.compressed_stats_bytes("sketch", tile=tile, rank=rank))

    # serve/solve FLOPs: what XLA prices the compiled stages at
    xs = jnp.ones((q, d), jnp.float32)
    W = jnp.ones((d, C), jnp.float32)
    f_serve = _xla_flops(lambda x, w: x @ w, xs, W)
    if f_serve is not None:
        _drift("serve_flops", f_serve, cm.serve_flops(q))
    f_solve = _xla_flops(
        lambda a, rhs: jsl.cho_solve(jsl.cho_factor(a, lower=True), rhs),
        A + d * jnp.eye(d), b,
    )
    if f_solve is not None:
        _drift("solve_flops", f_solve, d**3 / 3.0 + 2.0 * d * d * C,
               warn_low=False, note="xla_omits_custom_call_flops")


def main() -> list:
    rows = []
    for ds_name, cm0, K, n_k in (
        ("landmarks", LANDMARKS, 1262, 119.9),
        ("inaturalist", INATURALIST, 9275, 13.0),
    ):
        cm = cm0.__class__(**{**cm0.__dict__, "D": 10_000})
        for alg in ALGS:
            comm = cm.comm_per_client(alg)
            comp = cm.comp_per_client(alg, n_k)
            emit(
                f"appD_{ds_name}_{alg}",
                f"down_params={comm['down']:.3e} up_params={comm['up']:.3e} "
                f"comp_flops_per_round={comp:.3e}",
            )
            rows.append((ds_name, alg, comm, comp))

        # headline ratio (paper §5.2: up to two orders of magnitude)
        rounds_fed3r = -(-K // 10)  # ⌈K/κ⌉
        grad_total = cm.comp_per_client("fedavg", n_k) * 3000 * 10 / K
        f3_total = cm.comp_per_client("fed3r", n_k)
        emit(
            f"appE_{ds_name}_compute_ratio",
            f"fedavg_vs_fed3r_x={grad_total / f3_total:.1f} "
            f"fed3r_rounds_to_exact={rounds_fed3r}",
        )
        comm_grad = (cm.comm_per_client("fedavg")["up"] * 2) * 4  # up+down
        comm_f3 = cm.comm_per_client("fed3r")["up"] * 4
        emit(
            f"appD_{ds_name}_comm_per_client_ratio",
            f"fedavg_roundtrip_bytes={comm_grad:.3e} fed3r_once_bytes={comm_f3:.3e} "
            f"note=fed3r_pays_once_gradFL_pays_every_visit",
        )

        # multi-tenant personalized serving at planet scale (1M tenants):
        # head-cache + retained-stats memory, and the wire cost of the
        # closed form vs a full-model push per tenant
        M_TENANTS = 1_000_000
        emit(
            f"personalize_{ds_name}_serving_memory",
            f"head_cache_gb_per_1M={cm.head_cache_bytes(M_TENANTS) / 1e9:.2f} "
            f"tenant_stats_gb_per_1M={cm.tenant_stats_bytes(M_TENANTS) / 1e9:.2f}",
        )
        emit(
            f"personalize_{ds_name}_wire_ratio",
            f"ft_roundtrip_vs_onetime_stats_upload_x="
            f"{cm.personalization_vs_model_push_ratio():.2f} "
            f"note=lower_bound__closed_form_marginal_upload_is_zero_"
            f"and_ft_repays_per_refresh",
        )

        # continuous-batching slot serving: the fixed slot table vs a
        # full 1M-tenant head store, per-tick solve-vs-serve FLOPs, and
        # the serve stage's memory-bound QPS roofline
        S_SLOTS = 4096
        roof = cm.serving_qps_roofline()
        emit(
            f"serving_{ds_name}_slot_table",
            f"slot_table_mb_at_{S_SLOTS}_slots="
            f"{cm.slot_table_bytes(S_SLOTS) / 1e6:.1f} "
            f"full_1M_head_store_gb={cm.head_cache_bytes(M_TENANTS) / 1e9:.2f} "
            f"solve_tick_gflops_64_misses={cm.slot_solve_flops(64, n_k) / 1e9:.2f} "
            f"serve_tick_mflops_4096_queries={cm.serve_flops(4096) / 1e6:.2f}",
        )
        emit(
            f"serving_{ds_name}_qps_roofline",
            f"bound={roof['bound']} qps={roof['qps']:.3e} "
            f"bytes_per_query={roof['bytes_per_query']:.0f} "
            f"compute_qps={roof['compute_bound_qps']:.3e} "
            f"memory_qps={roof['memory_bound_qps']:.3e}",
        )

        # two-stage statistics all-reduce on the production meshes
        # (repro.federated.dist): intra-pod ICI stage vs cross-pod DCN
        # stage for the d² payload, vs the flat single-stage all-reduce
        for mesh_name, dp, pods in (("pod_16x16", 16, 1), ("multipod_2x16x16", 16, 2)):
            ar = cm.two_stage_allreduce(dp, pods)
            emit(
                f"dist_{ds_name}_allreduce_{mesh_name}",
                f"total_us={ar['total_s'] * 1e6:.1f} "
                f"payload_mb={ar['payload_bytes'] / 1e6:.1f} "
                f"ici_bytes_per_chip={ar['ici_bytes_per_chip']:.3e} "
                f"dcn_bytes_per_pod={ar['dcn_bytes_per_pod']:.3e} "
                f"ici_us={ar['ici_s'] * 1e6:.1f} dcn_us={ar['dcn_s'] * 1e6:.1f} "
                f"flat_us={ar['flat_allreduce_s'] * 1e6:.1f}",
            )

        # compressed statistics wire formats (repro.federated.compress):
        # per-upload bytes at paper scale, the retained-stats figure per 1M
        # tenants, and the two-stage all-reduce re-priced under int8 tiles
        for kind in ("fp32", "int8", "fp8", "sketch"):
            emit(
                f"compress_{ds_name}_wire_{kind}",
                f"upload_mb={cm.compressed_stats_bytes(kind) / 1e6:.2f} "
                f"ratio_vs_fp32={cm.wire_compression_ratio(kind):.2f}x "
                f"tenant_stats_gb_per_1M="
                f"{cm.compressed_stats_bytes(kind, M_TENANTS) / 1e9:.2f}",
            )
        ar8 = cm.two_stage_allreduce(16, 2, wire="int8")
        emit(
            f"compress_{ds_name}_allreduce_int8_multipod",
            f"total_us={ar8['total_s'] * 1e6:.1f} "
            f"payload_mb={ar8['payload_bytes'] / 1e6:.1f} "
            f"ici_us={ar8['ici_s'] * 1e6:.1f} dcn_us={ar8['dcn_s'] * 1e6:.1f}",
        )
    measured_vs_model()
    return rows


if __name__ == "__main__":
    main()

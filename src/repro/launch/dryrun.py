import os
_N_DEV = os.environ.get("REPRO_DRYRUN_DEVICES", "512")
# appended AFTER any inherited flags: XLA's duplicate-flag parsing is
# last-wins, so this is what makes the forced count override e.g. a CI
# job-level --xla_force_host_platform_device_count
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_N_DEV}"
).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) combination.

The lines above MUST run before any jax import (jax locks the device
count on first init) — which is why this module must only ever be executed
as a script/module entry point, never imported by tests.  The simulated
host-device count defaults to the full multi-pod mesh (512) and can be
overridden with ``REPRO_DRYRUN_DEVICES=N`` for smaller scale-out dry runs.

Per combination, TWO kinds of compile:

1. **Full model, scan-over-layers** — the deployment program.  Proves the
   sharding lowers and fits: ``memory_analysis()`` (per-device bytes) is
   recorded; this is the §Dry-run pass/fail artifact.
2. **Unrolled depth-1 / depth-2 variants** — exact per-layer roofline terms
   by the delta method (XLA's ``cost_analysis`` counts a while-loop body
   once, so the scanned program's numbers can't be used directly):

       total(L) = cost(L1) + (units − 1) · (cost(L2) − cost(L1))

   flops/bytes from ``cost_analysis`` (verified per-device on this backend),
   collective wire bytes parsed from the partitioned HLO.

Sharding/dtype policies (see sharding/specs.py for the fallback chains):
  * train:   fp32 params, FSDP ("data"-axis) sharding, microbatched grads;
  * prefill/decode: bf16 params; FSDP only if bf16 params > 8 GB per chip
    under 16-way tensor parallelism alone.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  python -m repro.launch.dryrun --all --multi-pod --skip-roofline
  python -m repro.launch.dryrun --arch qwen2-7b --shape prefill_32k --kind fed3r
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import fed3r
from repro.launch import hlo_analysis, steps
from repro.launch.flops import model_flops, param_breakdown
from repro.launch.mesh import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    data_axes,
    make_production_mesh,
    n_chips,
)
from repro.launch.shapes import abstract_params, input_specs, variant_for
from repro.models import model as model_lib
from repro.sharding.specs import batch_specs, cache_specs, param_specs, stats_specs

FED3R_N_CLASSES = 2028  # Landmarks-scale classifier head (paper Table 4)
FSDP_INFERENCE_THRESHOLD = 8e9  # bytes of bf16 params per chip under TP-only
FSDP_TRAIN_THRESHOLD = 12e9  # bytes of fp32 params+grads per chip under TP-only
MICROBATCH_ACT_BUDGET = 4e9  # target per-device activation bytes (train)
HBM_PER_CHIP = 16e9  # v5e


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _bf16_params(params_abs):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, jnp.bfloat16 if jnp.issubdtype(l.dtype, jnp.floating) else l.dtype
        ),
        params_abs,
    )


def _depth_variants(cfg: ModelConfig) -> Tuple[ModelConfig, ModelConfig, int]:
    """(depth-1 cfg, depth-2 cfg, number of extrapolation units)."""
    if cfg.arch_type == "hybrid":
        p = len(cfg.block_pattern)
        rem = cfg.n_layers % p
        return (
            cfg.replace(n_layers=p + rem, scan_layers=False),
            cfg.replace(n_layers=2 * p + rem, scan_layers=False),
            cfg.n_superblocks,
        )
    if cfg.arch_type == "audio":
        return (
            cfg.replace(n_layers=1, n_encoder_layers=1, scan_layers=False),
            cfg.replace(n_layers=2, n_encoder_layers=2, scan_layers=False),
            cfg.n_layers,
        )
    return (
        cfg.replace(n_layers=1, scan_layers=False),
        cfg.replace(n_layers=2, scan_layers=False),
        cfg.n_layers,
    )


_ACT_FACTOR = {"dense": 6, "vlm": 6, "audio": 6, "moe": 12, "ssm": 14, "hybrid": 8}


def _pick_microbatches(cfg: ModelConfig, shape: ShapeConfig, da_size: int) -> int:
    if shape.kind != "train":
        return 1
    b_pd = max(shape.global_batch // da_size, 1)
    tokens_pd = shape.global_batch * shape.seq_len / da_size
    n_l = cfg.n_layers + cfg.n_encoder_layers
    act = n_l * tokens_pd * cfg.d_model * 2 * _ACT_FACTOR.get(cfg.arch_type, 6)
    m = 1
    while act / m > MICROBATCH_ACT_BUDGET and m < b_pd:
        m *= 2
    while b_pd % m != 0:
        m //= 2
    return max(m, 1)


def _build_jit(cfg, kind, shape, mesh, ax_sizes, da, *, num_microbatches=1):
    """Returns (jitted, abstract_args)."""
    is_train = kind == "train"
    params_abs = abstract_params(cfg)
    if not is_train:
        params_abs = _bf16_params(params_abs)
        tp_bytes = sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(params_abs)
        ) / ax_sizes["model"]
        fsdp = tp_bytes > FSDP_INFERENCE_THRESHOLD
    else:
        # FSDP (params over "data" too) only when fp32 params + grad
        # accumulator exceed the TP-only budget — pure data-parallel grad
        # all-reduce is far cheaper than per-microbatch weight gathers.
        tp_bytes = sum(
            l.size * 4 for l in jax.tree.leaves(params_abs)
        ) / ax_sizes["model"]
        fsdp = 2 * tp_bytes > FSDP_TRAIN_THRESHOLD
    fsdp_axis = ("pod", "data") if "pod" in ax_sizes else "data"
    p_shard = _ns(
        mesh, param_specs(cfg, params_abs, ax_sizes, fsdp=fsdp, fsdp_axis=fsdp_axis)
    )
    specs = input_specs(cfg, shape)

    if kind == "train":
        fn = steps.make_train_step(
            cfg, lr=1e-2, num_microbatches=num_microbatches,
            param_specs=param_specs(
                cfg, params_abs, ax_sizes, fsdp=fsdp, fsdp_axis=fsdp_axis
            ),
        )
        b_shard = _ns(mesh, batch_specs(cfg, specs["batch"], da, ax_sizes))
        jitted = jax.jit(
            fn,
            in_shardings=(p_shard, b_shard),
            out_shardings=(p_shard, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )
        return jitted, (params_abs, specs["batch"]), fsdp
    if kind == "prefill":
        cap = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
        fn = steps.make_prefill_step(cfg, cache_capacity=cap)
        b_shard = _ns(mesh, batch_specs(cfg, specs["batch"], da, ax_sizes))
        cache_abs = jax.eval_shape(
            lambda: model_lib.make_cache(cfg, shape.global_batch, cap)
        )
        c_shard = _ns(mesh, cache_specs(cfg, cache_abs, da, ax_sizes))
        logits_shard = NamedSharding(
            mesh, P(da if shape.global_batch % _da_size(ax_sizes, da) == 0 else None,
                    "model" if cfg.vocab_size % ax_sizes["model"] == 0 else None)
        )
        jitted = jax.jit(
            fn,
            in_shardings=(p_shard, b_shard),
            out_shardings=(logits_shard, c_shard),
        )
        return jitted, (params_abs, specs["batch"]), fsdp
    if kind == "decode":
        fn = steps.make_decode_step(cfg)
        c_shard = _ns(mesh, cache_specs(cfg, specs["cache"], da, ax_sizes))
        bdiv = shape.global_batch % _da_size(ax_sizes, da) == 0
        tok_shard = NamedSharding(mesh, P(da if bdiv else None, None))
        pos_shard = NamedSharding(mesh, P())
        logits_shard = NamedSharding(
            mesh, P(da if bdiv else None,
                    "model" if cfg.vocab_size % ax_sizes["model"] == 0 else None)
        )
        jitted = jax.jit(
            fn,
            in_shardings=(p_shard, c_shard, tok_shard, pos_shard),
            out_shardings=(logits_shard, c_shard),
            donate_argnums=(1,),
        )
        return jitted, (params_abs, specs["cache"], specs["token"], specs["pos"]), fsdp
    if kind == "fed3r":
        fn = steps.make_fed3r_stats_step(cfg, FED3R_N_CLASSES)
        pre = input_specs(cfg, dataclasses.replace(shape, kind="prefill"))
        batch = dict(pre["batch"])
        batch["class_labels"] = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        b_shard = _ns(mesh, batch_specs(cfg, batch, da, ax_sizes))
        s_abs = jax.eval_shape(lambda: fed3r.init_stats(cfg.d_feat, FED3R_N_CLASSES))
        s_shard = _ns(mesh, stats_specs(cfg.d_feat, ax_sizes))
        jitted = jax.jit(
            fn,
            in_shardings=(p_shard, s_shard, b_shard),
            out_shardings=s_shard,
            donate_argnums=(1,),
        )
        return jitted, (params_abs, s_abs, batch), fsdp
    raise ValueError(kind)


def _da_size(ax_sizes, da) -> int:
    s = 1
    for a in da:
        s *= ax_sizes[a]
    return s


def _compile_and_cost(cfg, kind, shape, mesh, ax_sizes, da, num_microbatches):
    """Compile one unrolled variant; return (flops_pd, bytes_pd, CollectiveStats)."""
    jitted, args, _ = _build_jit(
        cfg, kind, shape, mesh, ax_sizes, da, num_microbatches=num_microbatches
    )
    compiled = jitted.lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    coll = hlo_analysis.collective_stats(compiled.as_text())
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)), coll


def lower_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    kind_override: Optional[str] = None,
    mesh=None,
    skip_roofline: bool = False,
) -> Dict[str, Any]:
    """Lower + compile one combination; return the §Dry-run record."""
    t0 = time.time()
    cfg0 = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    cfg = variant_for(cfg0, shape)
    kind = kind_override or shape.kind
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind,
        "status": "skipped" if cfg is None else "pending",
    }
    if cfg is None:
        rec["skip_reason"] = "long_500k n/a for full-attn enc-dec (see DESIGN.md)"
        return rec
    if cfg.sliding_window and shape.name == "long_500k":
        rec["variant"] = f"sliding_window={cfg.sliding_window}"

    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    jax.set_mesh(mesh)  # ambient mesh: enables model-internal sharding hints
    da = data_axes(mesh)
    ax_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    chips = n_chips(mesh)

    M = _pick_microbatches(cfg, shape, _da_size(ax_sizes, da))
    rec["num_microbatches"] = M
    rec["remat_block_size"] = cfg.remat_block_size

    # ---- 1) full-model compile: the deployment program ----------------------
    jitted, args, fsdp = _build_jit(
        cfg, kind, shape, mesh, ax_sizes, da, num_microbatches=M
    )
    rec["fsdp"] = bool(fsdp)
    lowered = jitted.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, f, None)
            if v is not None:
                rec[f] = int(v)
        per_dev = (
            rec.get("argument_size_in_bytes", 0)
            + rec.get("output_size_in_bytes", 0)
            + rec.get("temp_size_in_bytes", 0)
            - rec.get("alias_size_in_bytes", 0)
        )
        rec["per_device_bytes"] = per_dev
        rec["per_device_gb"] = round(per_dev / 1e9, 2)
        rec["fits_hbm"] = bool(per_dev <= HBM_PER_CHIP)

    census = hlo_analysis.collective_stats(compiled.as_text())
    rec["scanned_hlo_collectives"] = {k: int(v) for k, v in census.counts.items()}
    del compiled, lowered  # free compile memory

    # ---- 2) delta-method roofline (unrolled depth variants) -----------------
    if not skip_roofline:
        cfg1, cfg2, units = _depth_variants(cfg)
        f1, b1, c1 = _compile_and_cost(cfg1, kind, shape, mesh, ax_sizes, da, M)
        f2, b2, c2 = _compile_and_cost(cfg2, kind, shape, mesh, ax_sizes, da, M)
        dflops, dbytes = f2 - f1, b2 - b1
        dcoll = c2.minus(c1)
        # the microbatch loop body is also counted once by cost_analysis —
        # scale to the deployed M (epilogue overcount is negligible)
        flops_pd = (f1 + (units - 1) * dflops) * M
        bytes_pd = (b1 + (units - 1) * dbytes) * M
        coll = c1.plus_scaled(dcoll, units - 1).scaled(M)

        rt = hlo_analysis.roofline_terms(
            flops_pd, bytes_pd, coll.total_wire_bytes, chips,
            peak_flops=PEAK_FLOPS_BF16, hbm_bw=HBM_BW, ici_bw=ICI_BW,
        )
        rec["hlo_flops_global"] = rt.hlo_flops_global
        rec["hlo_bytes_global"] = rt.hlo_bytes_global
        rec["collective_wire_bytes_per_chip"] = coll.total_wire_bytes
        rec["collectives"] = {k: int(v) for k, v in coll.counts.items()}
        rec["collective_wire_by_kind"] = {k: float(v) for k, v in coll.wire_bytes.items()}
        rec["roofline"] = {
            "compute_s": rt.compute_s,
            "memory_s": rt.memory_s,
            "collective_s": rt.collective_s,
            "dominant": rt.dominant,
        }
        params_abs = abstract_params(cfg)
        mf = model_flops(cfg, shape, params_abs)
        rec["model_flops"] = mf
        rec["useful_flops_ratio"] = (
            mf / rt.hlo_flops_global if rt.hlo_flops_global else None
        )
        rec["params"] = param_breakdown(cfg, params_abs)

    rec["status"] = "ok"
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ASSIGNED_ARCHS + ["all"], default="all")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES) + ["all"], default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--kind", default=None, choices=[None, "fed3r"],
                    help="override the step kind (fed3r = statistics pass)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--all", action="store_true", help="arch=all shape=all")
    ap.add_argument("--skip-roofline", action="store_true",
                    help="only the full compile (multi-pod pass)")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or args.arch == "all") else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape == "all") else [args.shape]

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    print(f"mesh: {mesh}", flush=True)
    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            try:
                rec = lower_one(arch, shape, multi_pod=args.multi_pod,
                                kind_override=args.kind, mesh=mesh,
                                skip_roofline=args.skip_roofline)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {
                    "arch": arch, "shape": shape,
                    "mesh": "2x16x16" if args.multi_pod else "16x16",
                    "kind": args.kind or INPUT_SHAPES[shape].kind,
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
            status = rec["status"]
            n_ok += status == "ok"
            n_fail += status == "error"
            n_skip += status == "skipped"
            msg = f"[{status:7s}] {arch:24s} {shape:12s} {rec['mesh']:8s}"
            if status == "ok":
                msg += (
                    f" compile={rec['compile_s']:7.1f}s"
                    f" mem={rec.get('per_device_gb', -1):7.2f}GB"
                    f" fits={rec.get('fits_hbm')}"
                )
                if "roofline" in rec:
                    r = rec["roofline"]
                    msg += (
                        f" compute={r['compute_s']*1e3:9.3f}ms"
                        f" memory={r['memory_s']*1e3:9.3f}ms"
                        f" coll={r['collective_s']*1e3:9.3f}ms"
                        f" dom={r['dominant']}"
                    )
            elif status == "error":
                msg += f" {rec['error'][:140]}"
            print(msg, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    print(f"done: ok={n_ok} failed={n_fail} skipped={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

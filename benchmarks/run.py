"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping to paper artifacts:

  bench_invariance       Fig. 1 / Fig. 9   split-invariance & centralized eq.
  bench_vs_baselines     Fig. 2 / Fig. 10  FED3R vs FedAvg(M)/Scaffold-LP
  bench_sampling         Fig. 3            participation rates ± replacement
  bench_ncm              Table 1 / Table 6 FED3R family vs FedNCM
  bench_ft               Table 2 / Fig. 4/5/11  FT / FT-LP / FT-FEAT grid
  bench_feature_quality  Table 3           RR probe of fine-tuned features
  bench_rf               Fig. 8            RF sweep vs exact-KRR ceiling
  bench_costs            App. D/E          exact cost meters @ paper scale
  bench_coupon           Table 7 / App. I  batch coupon collector
  bench_kernels          (kernels)         Pallas-vs-oracle + XLA timing
  bench_engine           (engine)          packed scan vs per-client loop
  bench_rounds           (round engine)    packed FL round vs per-client loop
  bench_streaming        (streaming)       packed arrival scan vs Woodbury loop
  bench_personalize      (personalization) batched per-tenant heads vs re-solve loop
  bench_serving          (slot serving)    continuous-batching slots vs synchronous LRU
  bench_scaleout         (dist layer)      weak scaling of the one-dispatch engines
  bench_compress         (wire formats)    accuracy-vs-bytes of compressed uploads
  bench_async            (async engine)    merge-on-arrival vs sync barrier @ stragglers
  roofline               §Roofline         dry-run roofline table

Modules listed in ``JSON_OUT`` additionally persist their result dict as a
``BENCH_<name>.json`` next to the invocation — the perf trajectory record
that ``benchmarks/check_regression.py`` gates CI against (baselines live
in ``benchmarks/baselines/``).  Each JSON_OUT module runs under a fresh
``Telemetry`` registry whose snapshot is persisted alongside as
``telemetry_<name>.json`` (a CI artifact); the per-engine dispatch totals
from that snapshot are folded into the BENCH dict under ``telemetry``.

Usage: PYTHONPATH=src:. python benchmarks/run.py [--smoke] [names ...]
"""
from __future__ import annotations

import argparse
import inspect
import json
import time
import traceback

from repro.federated.telemetry import Telemetry, dispatch_summary, set_telemetry
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "bench_costs",
    "bench_coupon",
    "bench_kernels",
    "bench_engine",
    "bench_rounds",
    "bench_streaming",
    "bench_personalize",
    "bench_serving",
    "bench_scaleout",
    "bench_compress",
    "bench_async",
    "bench_tiers",
    "bench_invariance",
    "bench_ncm",
    "bench_rf",
    "bench_sampling",
    "bench_vs_baselines",
    "bench_ft",
    "bench_feature_quality",
    "roofline",
]

# result dicts persisted as BENCH_<suffix>.json (perf trajectory record)
JSON_OUT = {
    "bench_engine": "engine",
    "bench_rounds": "rounds",
    "bench_streaming": "streaming",
    "bench_personalize": "personalize",
    "bench_serving": "serving",
    "bench_scaleout": "scaleout",
    "bench_compress": "compress",
    "bench_async": "async",
    "bench_tiers": "tiers",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*", help="subset of benchmark modules")
    ap.add_argument("--smoke", action="store_true",
                    help="small configs (CI budget) where supported")
    args = ap.parse_args()
    enable_compile_cache()
    only = args.names or None
    print("name,us_per_call,derived")
    failures = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        telemetry = None
        if name in JSON_OUT:
            # fresh registry per bench: the snapshot is that bench's own
            # dispatch/span record, unpolluted by earlier modules
            telemetry = Telemetry()
            set_telemetry(telemetry)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            kwargs = {}
            if args.smoke and "smoke" in inspect.signature(mod.main).parameters:
                kwargs["smoke"] = True
            result = mod.main(**kwargs)
            if name in JSON_OUT and isinstance(result, dict):
                snap = telemetry.snapshot()
                result["telemetry"] = {"dispatches": dispatch_summary(snap)}
                with open(f"BENCH_{JSON_OUT[name]}.json", "w") as f:
                    json.dump(result, f, indent=2, default=float)
                with open(f"telemetry_{JSON_OUT[name]}.json", "w") as f:
                    json.dump(snap, f, indent=2, default=float)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            failures.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"failed benchmarks: {failures}")


if __name__ == "__main__":
    main()

"""Run one benchmark cell once: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the deployment;
* ``bench/traffic/<traffic>.json``: parameters for the generator and the
  engine path (``driver`` names ``bench/drivers/<driver>.py``);
* ``bench/limits/<workload>.json``: the limit of each number compared;
* ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``:
  a ``read(ctx)`` that returns the metric's value, or ``None`` where the
  run holds nothing to read.  ``ctx.telemetry`` is the program's registry
  (``snapshot()``) as the window left it: it is reset as the window opens.

A configuration that runs a backbone holds a ``backbone`` object:
``reference`` names ``bench/backbones/<reference>.py``, ``model`` holds its
published widths and the cut as plain data, and ``inputs`` is ``{"kind":
"tokens", "vocab": V, "seq_len": {"median": .., "sigma": .., "max": ..}}``
(``bench.generator``).  ``feature_dim`` stays the pooled width.  The
module is the backbone's plain reference and imports nothing of the
program; ``setup`` loads it once, and the federation carries it as
``backbone_module`` to the reference and the driver:

* ``ROWS``: samples per block of the reference's forward, so that a block
  fits on the chip once the program's state is freed;
* ``init(model, seed) -> dict[str, array]``: float32 weights from a 32-bit
  seed, best made on the device in one jitted call; the generator keeps
  them on the host as numpy;
* ``features(weights, tokens, lengths, model, precision) -> (rows, d)``:
  the forward over one block of ``ROWS`` rows, pooled over each row's real
  tokens, traceable under ``jax.jit``, its products at ``precision``:
  "highest" for the reference, "high" for the control
  (``bench.reference.matmul`` gives both).  A module whose configuration
  states less than fp32 takes "high" to mean its own control, the step
  below the precision it states (int8 or fp8 for bf16);
* ``flops(model, lengths)`` and ``bytes(model, lengths)``: the least work
  of that forward over the real tokens; a driver adds ``flops`` into its
  units' work, and ``bytes`` is there for a roofline reader of the forward.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"  # JAX's persistent compilation cache
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class MissingMetric(RuntimeError):
    """A traced run read nothing for a per-layer metric that lists its cell."""


# ---- finding things by name ------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _json(BENCH_DIR / "configs" / f"{name}.json")


def load_traffic(name: str) -> dict:
    return _json(BENCH_DIR / "traffic" / f"{name}.json")


def load_limits(workload: str) -> Dict[str, float]:
    return _json(BENCH_DIR / "limits" / f"{workload}.json")


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}").Driver


def load_backbone(name: str):
    """The plain reference module ``bench/backbones/<name>.py``."""
    return _module("backbones", name)


def load_reader(kind: str, name: str) -> Callable:
    """``read`` of ``bench/<kind>/<name>.py``."""
    return _module(kind, name).read


def cell(spec: dict, workload: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(spec: dict, workload: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in moved else [])]


# ---- the run -----------------------------------------------------------------


def _devices(chips: int, require_accelerator: bool):
    import jax

    devices = jax.devices()
    if require_accelerator and devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def _mesh(chips: int, aggregation: str):
    """The host mesh of a ``psum`` cell; ``merge`` cells run on one chip."""
    if aggregation == "merge":
        if chips != 1:
            raise ValueError("a merge cell runs on one chip")
        return None
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    if mesh.devices.size != chips:
        raise NoAccelerator(f"the cell needs {chips} chips, the host mesh holds "
                            f"{mesh.devices.size}")
    return mesh


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def _same(a: Dict, b: Dict) -> bool:
    import jax.numpy as jnp

    return all(bool(jnp.array_equal(a[k], b[k])) for k in a)


def check_answers(answers: Dict[int, List[Dict]], fed, ridge_lambda: float,
                  groups) -> List[Dict]:
    """The readings of every kept answer against the reference.

    ``groups`` are the client ids the program folds as one unit, in its
    order; ``answers`` maps a group index to the answers due after it.  The
    first answer at a point is compared on the host; a later one that is
    bitwise equal to it reads the same, and one that is not is compared on
    the host too."""
    from bench import reference

    refs = reference.statistics(fed, ridge_lambda, groups, sorted(answers))
    readings = []
    for t, got_list in answers.items():
        first = None
        for got in got_list:
            if first is not None and _same(got, got_list[0]):
                readings.append(first)
                continue
            r = reference.compare({k: np.asarray(v) for k, v in got.items()}, refs[t],
                                  ridge_lambda)
            readings.append(r)
            if first is None:
                first = r
    return readings


def setup(workload: str, seed: int, *, spec: Optional[dict] = None,
          config: Optional[dict] = None, on_chip: bool = True):
    """Devices, compile cache, generated federation and a driver that has run
    one warm-up pass.  ``on_chip=False`` (tests) accepts the CPU and leaves
    the persistent compile cache off.  On the chip the cache is
    ``<checkout>/.jax_cache``, whatever the environment names."""
    import jax

    from bench import generator

    spec = load_spec() if spec is None else spec
    w = cell(spec, workload)
    config = load_config(w["config"]) if config is None else config
    traffic = load_traffic(w["traffic"])
    devices = _devices(w["chips"], on_chip)
    if on_chip:
        # a fixed directory in the checkout, unbounded: a cap would evict one
        # cell's programs while another cell runs
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seed = int(seed) % 2**64
    t0 = time.perf_counter()
    backbone = config.get("backbone")
    module = None if backbone is None else load_backbone(backbone["reference"])
    fed = generator.make_federation(config, seed, traffic.get("warm_rounds", 0), module)
    t1 = time.perf_counter()
    mesh = _mesh(w["chips"], traffic["aggregation"])
    drv = load_driver(traffic["driver"])(config, traffic, fed, mesh=mesh, seed=seed)
    t2 = time.perf_counter()
    for _ in range(drv.units_per_pass):  # warm-up: every shape of the cell
        drv.step()
    drv.reset()
    gc.collect()  # what compiling left behind goes before any window
    t3 = time.perf_counter()
    timings = {"generate_s": t1 - t0, "pack_place_s": t2 - t1, "warm_up_s": t3 - t2}
    return SimpleNamespace(spec=spec, cell=w, config=config, traffic=traffic,
                           devices=devices, fed=fed, driver=drv, timings=timings)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             spec: Optional[dict] = None, config: Optional[dict] = None,
             limits: Optional[Dict[str, float]] = None, on_chip: bool = True,
             t_process: Optional[float] = None, log=print) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import peaks, reference
    from repro.federated.telemetry import get_telemetry

    t_process = time.perf_counter() if t_process is None else t_process
    limits = load_limits(workload) if limits is None else limits
    run = setup(workload, seed, spec=spec, config=config, on_chip=on_chip)
    drv, devices, w = run.driver, run.devices, run.cell
    kind = devices[0].device_kind
    peak = peaks.peak_for(kind) if on_chip else None
    log(f"[bench] {workload}: platform {devices[0].platform}, device_kind {kind!r}, "
        f"{len(devices)} device(s); set-up {run.timings}", file=sys.stderr)

    compiles: List[str] = []
    listening = [False]

    def on_compile(event: str, _secs: float, **kw) -> None:
        if listening[0] and event in COMPILE_EVENTS:
            compiles.append(f"{event} {kw.get('fun_name', '')}")

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    units = []
    # the readers count the window's work alone, not set-up's or warm-up's
    get_telemetry().reset()
    # the objects that live on are not walked again by the collector inside
    # the window: a run that compiled and one that loaded from the cache then
    # time the same work
    gc.freeze()
    listening[0] = True
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    with TraceAnnotation("window"):
        while True:
            units.append(drv.step())
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    listening[0] = False
    telemetry = get_telemetry().snapshot()
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if trace:
        jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices)

    # the program's state goes before the reference runs
    answers = drv.answers()
    spans = {"round": list(getattr(drv, "round_spans_s", []))}
    drv.free()
    gc.unfreeze()
    gc.collect()
    lam = run.config["assumed"]["ridge_lambda"]
    t_ref = time.perf_counter()
    readings = check_answers(answers, run.fed, lam, drv.groups)
    reference_s = time.perf_counter() - t_ref
    failed = sum(any(r[k] > limits[k] for k in r) for r in readings)
    compared = reference.worst(readings) if readings else {}
    compared["window_compiles"] = float(len(compiles))
    missing = [k for k in compared if k not in limits]
    if missing:
        raise KeyError(f"no limit for {missing} in bench/limits/{workload}.json")
    correct = bool(readings) and failed == 0 and all(
        compared[k] <= limits[k] for k in compared)

    ctx = SimpleNamespace(
        workload=w, config=run.config, traffic=run.traffic, unit=drv.unit, units=units,
        window_s=window_s, setup_s=setup_s, spans=spans, chips=w["chips"], peak=peak,
        d=run.fed.feature_dim, C=run.fed.n_classes, trace=None, busy_s=None,
        trace_window_s=None, telemetry=telemetry,
    )
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(units), "failed": failed}
    breakdown = None
    if trace:
        from bench import trace_reduce

        tr = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = trace_reduce.window(tr)
        ctx.trace, ctx.trace_window_s = tr, (hi - lo) / 1e9
        ctx.busy_s = trace_reduce.mean_busy_s(tr)
        device.update(busy_s=ctx.busy_s, window_s=ctx.trace_window_s)
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.idle_gaps(tr)}
    metrics, unread = {}, []
    for m in metrics_for(run.spec, workload, trace):
        value = load_reader("layer_metrics" if trace else "end_to_end", m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif workload in m.get("workloads", ()):
            unread.append(m["name"])
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": limits[k]} for k, v in compared.items()}

    log(f"[bench] window {window_s!r} s: {len(units)} units of one {drv.unit}, "
        f"{sum(u.samples for u in units)} samples; memory_peak_bytes {memory_peak}",
        file=sys.stderr)
    lat = [u.latency_s for u in units if u.latency_s is not None]
    if lat:
        log(f"[bench] {drv.unit} latency: count {len(lat)}, median "
            f"{1e3 * float(np.median(lat))!r} ms", file=sys.stderr)
    if compiles:
        log(f"[bench] compiled inside the window: {compiles[:5]}", file=sys.stderr)
    log(f"[bench] {len(readings)} answers checked against the reference, {failed} failed; "
        f"reference_s {reference_s!r}", file=sys.stderr)
    for k, v in compared.items():
        log(f"[bench] compared {k} {v!r} limit {limits[k]!r} "
            f"{'ok' if v <= limits[k] else 'FAIL'}", file=sys.stderr)
    if unread:
        # a renamed kernel or a reader that no longer matches the trace must
        # not drop its metric in silence
        log(f"[bench] result without the unread metrics: {json.dumps(result)}",
            file=sys.stderr)
        raise MissingMetric(f"the trace held nothing for {unread}, which list "
                            f"{workload!r} in BENCHMARK.json")
    return result


def main(argv=None, t_process: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          t_process=t_process)
    except NoAccelerator as e:
        print(f"[bench] {e}; nothing was run", file=sys.stderr)
        return 3
    except MissingMetric as e:
        print(f"[bench] {e}; no result", file=sys.stderr)
        return 4
    print(json.dumps(result))
    return 0

#!/usr/bin/env python3
"""The readings that the limits of ``bench/limits/<workload>.json`` are set
from, on many seeds in one process.

    python3 bench/readings.py --workload <name> --seeds 1 2 3 ... [--control]

For each seed: set-up as a run does, one whole pass through the cell's
driver, and the numbers that a run compares, worst over the pass's
answers (the lower readings).  With ``--control``, also the control: the
reference put in the program's place at the same answer points, each
stage one precision step below what the configuration states (the upper
readings).  The statistics contract fp32 at "highest", so the control's
take three bf16 products for each fp32 one (``bf16x3``); the solve and
the factor are plain fp32, so the control's run on bf16-rounded
operands.  ``bf16``, one bf16 pass for the statistics, is read beside it.
The benchmark's own runs do not run this.  One JSON line per seed.
"""
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402

from bench import harness, reference  # noqa: E402


def _bf16(x: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16), np.float64)


def control_readings(fed, answers, ridge_lambda: float, how: str, groups):
    """The control in the program's place, read against the reference at the
    answer points of ``answers``: statistics at contraction ``how``, the
    solve and the factor on bf16-rounded operands."""
    points = sorted(answers)
    refs = reference.statistics(fed, ridge_lambda, groups, points)
    ctrl = reference.statistics(fed, ridge_lambda, groups, points, how=how)
    out = []
    for t in points:
        keys = answers[t][0].keys()
        c = ctrl[t]
        reg = _bf16(c.A + ridge_lambda * np.eye(c.A.shape[0]))
        W = reference.solve(reg, _bf16(c.b), 0.0)
        got = {"W": W, "n": c.n}
        if "b" in keys:
            got["b"] = c.b
        if "A" in keys:
            got["A"] = c.A
        if "L" in keys:
            got["L"] = np.linalg.cholesky(reg)
        if "counts" in keys:
            got["counts"] = c.counts
        out.append(reference.compare(got, refs[t], ridge_lambda))
    return reference.worst(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        try:
            run = harness.setup(args.workload, seed)
        except harness.NoAccelerator as e:
            print(f"[readings] {e}; nothing was run", file=sys.stderr)
            return 3
        drv = run.driver
        for _ in range(drv.units_per_pass):
            drv.step()
        answers = drv.answers()
        drv.free()
        gc.collect()
        lam = run.config["assumed"]["ridge_lambda"]
        line = {"workload": args.workload, "seed": seed, "timings": run.timings,
                "program": reference.worst(
                    harness.check_answers(answers, run.fed, lam, drv.groups))}
        if args.control:
            for how in ("bf16x3", "bf16"):
                line[f"control_{how}"] = control_readings(run.fed, answers, lam, how,
                                                         drv.groups)
        print(json.dumps(line), flush=True)
        del run, drv, answers
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

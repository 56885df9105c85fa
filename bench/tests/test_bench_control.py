"""The control must come out not correct where the program comes out correct.

The control is the reference put in the program's place one precision step
below the configuration's fp32 at "highest": three bf16 products for each
fp32 product (``bf16x3``, what ``Precision.HIGH`` does on a TPU).  On the
chip it runs at each cell's own size through ``bench/readings.py
--control``; here at a tiny size, against the cells' own limits.
"""
import importlib.util

import pytest

from bench import harness, reference

_spec = importlib.util.spec_from_file_location("bench_readings",
                                               harness.BENCH_DIR / "readings.py")
readings = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(readings)


@pytest.mark.parametrize("workload", ["landmarks-batch", "landmarks-stream-warm", "inat-batch"])
def test_control_fails_where_the_program_passes(tiny, workload):
    run = harness.setup(workload, 2**31 + 21, config=tiny, on_chip=False)
    drv = run.driver
    for _ in range(drv.units_per_pass):
        drv.step()
    answers = drv.answers()
    lam = tiny["assumed"]["ridge_lambda"]
    limits = harness.load_limits(workload)
    program = reference.worst(harness.check_answers(answers, run.fed, lam, drv.groups))
    control = readings.control_readings(run.fed, answers, lam, "bf16x3", drv.groups)
    assert all(program[k] <= limits[k] for k in program), program
    assert any(control[k] > limits[k] for k in control), control

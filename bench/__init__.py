"""On-chip benchmark of Fed3R-JAX: one cell per run, driven by BENCHMARK.json.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell and prints its result as the last line of standard output.
Everything the yardstick needs lives here: the traffic generator, the
reference, the peaks table, the work counts and the trace reduction.  The
program under test is imported only for its engines and packers.
"""

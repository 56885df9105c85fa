"""The psum stream path that a four-chip cell would run (``aggregation:
psum`` in its traffic file), on four virtual CPU devices in a child
process: sound, it reads correct; with the exchange between chips left
out, it does not."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(harness.__file__).resolve().parents[1]

CHILD = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/bench/tests"]
    from bench import harness
    from conftest import TINY
    from repro.federated import dist
    if sys.argv[2] == "exchange_left_out":
        dist.DistContext.all_reduce = lambda self, tree, wire_fn=None: tree
    spec = harness.load_spec()
    spec["workloads"].append({"name": "psum-4", "config": "landmarks-users-160k",
                              "traffic": "waves-stream-psum", "chips": 4, "why": "test"})
    traffic = {"driver": "stream", "aggregation": "psum", "refresh_every": 1,
               "round_to": 64, "checked_waves": 3, "warm_rounds": 8}
    harness.load_traffic = lambda name: traffic
    r = harness.run_cell("psum-4", 2**31 + 3, 0.2, False, spec=spec, config=TINY,
                         limits=harness.load_limits("landmarks-stream-warm"), on_chip=False,
                         log=lambda *a, **k: None)
    print(json.dumps({"correct": r["correct"], "count": r["device"]["count"]}))
""")


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_psum_stream_on_four_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD, str(ROOT), str(fault)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["count"] == 4
    assert r["correct"] is (fault is None)

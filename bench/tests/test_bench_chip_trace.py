"""The trace readers on traces recorded on a TPU v5e: one traced run of each
engine path (``bench/drivers``) at the tiny test size (``conftest.TINY``),
kept gzipped in ``bench/tests/data``: ``landmarks-batch``, and the stream
path with a first wave of 8 rounds.  The kernels must be found under the
names the readers match, and each reader must give a share in (0, 100]."""
import gzip
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import generator, harness, peaks, trace_reduce, work
from bench.drivers import Unit

from .conftest import TINY

DATA = Path(__file__).resolve().parent / "data"
DRIVERS = {
    # driver: (reader of its kernel's roofline, the other driver's kernel)
    "batch": ("fed3r_stats_roofline", "chol_gram_roofline"),
    "stream": ("chol_gram_roofline", "fed3r_stats_roofline"),
}


def _reader(name: str):
    path = harness.BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.layer_metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load(driver: str, tmp_path) -> trace_reduce.Trace:
    raw = gzip.decompress((DATA / f"{driver}.tiny.xplane.pb.gz").read_bytes())
    path = tmp_path / f"{driver}.xplane.pb"
    path.write_bytes(raw)
    return trace_reduce.load(str(path))


def _least_unit_samples(driver: str) -> int:
    if driver == "batch":
        return TINY["n_samples"]
    a = TINY["assumed"]
    sizes = generator.client_sizes(TINY["n_samples"], TINY["n_clients"],
                                   a["client_size_sigma"], a["plan_seed"])
    plan = generator.round_plan(TINY["n_clients"], a["clients_per_round"], a["plan_seed"])
    return int(min(sizes[r].sum() for r in plan))


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_recorded_chip_trace_names_the_kernels(driver, tmp_path):
    tr = _load(driver, tmp_path)
    lo, hi = trace_reduce.window(tr)
    assert tr.n_devices == 1 and hi > lo
    busy = trace_reduce.mean_busy_s(tr)
    assert 0 < busy <= (hi - lo) / 1e9
    own, other = (_reader(n) for n in DRIVERS[driver])
    kernel_s = trace_reduce.op_seconds(tr, own.MATCH)
    assert 0 < kernel_s <= busy
    assert trace_reduce.op_seconds(tr, other.MATCH) == 0.0
    assert trace_reduce.top_ops(tr) and trace_reduce.idle_gaps(tr)
    # the reader's share for less work than the window held (at least one
    # unit ran: a whole pass, or a wave of at least the smallest round):
    # above 0, and never above the roofline
    d, C, n = TINY["feature_dim"], TINY["n_classes"], _least_unit_samples(driver)
    ctx = SimpleNamespace(trace=tr, d=d, C=C, peak=peaks.peak_for("TPU v5 lite"),
                          units=[Unit(n, work.stats_flops(n, d, C), None)])
    share = own.read(ctx)
    assert share is not None and 0 < share <= 100

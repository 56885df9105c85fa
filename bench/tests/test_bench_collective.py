"""``collective_ms.stream`` on a trace recorded on a TPU v5e 2x2 host: the
psum stream path (``waves-stream-warm-psum``) at the tiny test size
(``conftest.TINY``), kept gzipped in ``bench/tests/data``.  The reader finds
the all-reduce under the name it matches, one a wave on each chip, and
reads nothing on one chip."""
import gzip
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, trace_reduce
from bench.drivers import Unit

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("stream4") / "stream4.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / "stream4.tiny.xplane.pb.gz").read_bytes()))
    return trace_reduce.load(str(path))


def _reader():
    path = harness.BENCH_DIR / "layer_metrics" / "collective_ms.stream.py"
    spec = importlib.util.spec_from_file_location("collective_ms_stream", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_recorded_four_chip_trace_names_the_all_reduce(trace):
    reader = _reader()
    assert trace.n_devices == 4
    waves = sum(p.name == "wave" for p in trace.phases)
    lo, hi = trace_reduce.window(trace)
    events = [e for e in trace.ops if trace_reduce.op_stem(e).startswith(reader.PREFIX)
              and lo <= e.start_ns < hi]
    assert waves > 10 and len(events) == 4 * waves  # one a wave on each chip
    ctx = SimpleNamespace(trace=trace, units=[Unit(1, 0.0, None)] * waves)
    ms = reader.read(ctx)
    per_device = trace_reduce.op_seconds(trace, ("all-reduce",)) / 4
    assert ms == pytest.approx(1e3 * per_device / waves)
    assert 0 < ms < 1e3 * (hi - lo) / 1e9 / waves  # inside a wave's share of the window


def test_reader_reads_nothing_on_one_chip(trace):
    one = trace._replace(ops=[e for e in trace.ops if e.device == 0], n_devices=1)
    assert _reader().read(SimpleNamespace(trace=one, units=[Unit(1, 0.0, None)])) is None

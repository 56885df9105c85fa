"""The program's own spans in the benchmark: the registry's span log put on
a trace's clock (``bench.program_spans``), the readers that use it on
hand-made traces, a CPU-traced tiny run of each driver, and a traced tiny
run of each driver recorded on a TPU v5e."""
import gzip
import importlib.util
import json
import statistics
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from bench import harness, program_spans
from bench import trace_reduce as tr
from repro.federated.telemetry import Telemetry, set_telemetry

DATA = Path(__file__).resolve().parent / "data"
# the span log's clock: the trace's, plus an offset the readers must find
SHIFT = 1_792_000_000_000_000_000


@pytest.fixture
def registry():
    t = Telemetry()
    prev = set_telemetry(t)
    yield t
    set_telemetry(prev)


def _reader(name):
    return harness.load_reader("layer_metrics", name)


def _reader_module(name):
    path = harness.BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.layer_metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ctx(ops, phases):
    trace = tr.Trace(
        ops=[tr.OpEvent(dev, "%fusion.1 = f32[8]{0} fusion()", float(a), float(b - a), {})
             for dev, a, b in ops],
        phases=sorted((tr.Span(n, float(a), float(b)) for n, a, b in phases),
                      key=lambda s: s.start_ns),
        n_devices=len({dev for dev, _, _ in ops}),
    )
    lo, hi = tr.window(trace)
    return SimpleNamespace(trace=trace, busy_s=tr.mean_busy_s(trace) if ops else 0.0,
                           trace_window_s=(hi - lo) / 1e9)


STREAM_PHASES = [("window", 0, 1000), ("wave", 100, 400), ("block", 300, 400),
                 ("wave", 500, 900), ("block", 750, 900)]
STREAM_OPS = [(0, 0, 100), (0, 150, 300), (0, 400, 600), (0, 700, 1000), (1, 0, 1000)]
# absorb on the trace's clock [105, 295] and [510, 740], 50 and 70 µs of CPU
STREAM_LOG = [("init", SHIFT + 20, SHIFT + 90, 60_000),
              ("absorb/place", SHIFT + 110, SHIFT + 200, 10_000),
              ("absorb", SHIFT + 105, SHIFT + 295, 50_000),
              ("absorb", SHIFT + 510, SHIFT + 740, 70_000)]


def test_span_log_lands_on_the_trace_clock(registry):
    registry.span_log.extend(STREAM_LOG)
    ctx = _ctx(STREAM_OPS, STREAM_PHASES)
    # no span before its wave opens (≥ -5), none after its block opens (≤ +5)
    pairs = program_spans._pairs(ctx.trace, registry.span_log)
    assert program_spans.offset_bounds(ctx.trace, pairs) == (-SHIFT - 5, -SHIFT + 5)
    assert program_spans.fold_spans(ctx.trace) == [
        ("absorb", 105.0, 295.0, 50_000), ("absorb", 510.0, 740.0, 70_000)]


def test_readers_on_a_hand_made_stream_trace(registry):
    registry.span_log.extend(STREAM_LOG)
    ctx = _ctx(STREAM_OPS, STREAM_PHASES)
    assert _reader("api_cpu_us")(ctx) == pytest.approx(60.0)
    # device 0 idles 45 ns inside the first absorb and 100 inside the
    # second; device 1 never: the mean over the chips of 1,000 ns
    share = _reader("api_idle_share.stream")(ctx)
    assert share == pytest.approx(100 * (145 + 0) / 2 / 1000)
    assert 0 < share <= _reader("idle_share.stream")(ctx)


def test_readers_on_a_hand_made_batch_trace(registry):
    registry.span_log.extend([("accumulate", SHIFT + 7, SHIFT + 95, 30_000),
                              ("accumulate", SHIFT + 207, SHIFT + 290, 50_000)])
    ctx = _ctx([(0, 0, 300)], [("window", 0, 300), ("pass", 0, 300),
                               ("round", 5, 100), ("round", 205, 295)])
    assert _reader("api_cpu_us")(ctx) == pytest.approx(40.0)
    assert _reader("api_idle_share.stream")(ctx) is None  # no absorb in a batch cell


@pytest.mark.parametrize("early_us", [0, 40, 400])
def test_idle_share_puts_the_device_back_on_the_host_clock(registry, early_us):
    """A trace that draws the device's ops early, before their own launch,
    reads as if drawn right: they are shifted by the least amount that makes
    each wave's burst start after its launch and end before its block."""
    us = 1000  # this test's times are in µs: bursts are told apart by 100 µs gaps
    log = [("absorb/launch", 200, 290), ("absorb", 105, 295),
           ("absorb/launch", 600, 700), ("absorb", 510, 740)]
    registry.span_log.extend((n, SHIFT + a * us, SHIFT + b * us, 0) for n, a, b in log)
    # each wave's program runs 250..380 and 650..880 on the host's clock
    ops = [(0, (250 - early_us) * us, (380 - early_us) * us),
           (0, (650 - early_us) * us, (880 - early_us) * us)]
    phases = [("window", 0, 1000), ("wave", 100, 400), ("block", 300, 400),
              ("wave", 500, 900), ("block", 750, 900)]
    ctx = _ctx(ops, [(n, a * us, b * us) for n, a, b in phases])
    busy = tr.union((e.start_ns, e.end_ns) for e in ctx.trace.ops)
    shift = _reader_module("api_idle_share.stream").device_shift(
        ctx.trace, program_spans.spans(ctx.trace), busy)
    # causal from a shift of early - 50 on (the first burst then starts at
    # its launch), up to early + 20 (the second then ends with its block)
    assert shift == pytest.approx(max(0, early_us - 50) * us)
    # absorb [105, 295] idles until its wave's burst starts, [510, 740] until
    # the second's: 40 µs early is causal as drawn, so it stays
    idle = {0: 145 + 140, 40: 105 + 100, 400: 95 + 90}[early_us]
    assert _reader("api_idle_share.stream")(ctx) == pytest.approx(100 * idle / 1000)


def test_idle_share_on_a_misaligned_chip_trace(registry):
    """Reduced from a full-size stream window recorded on a TPU v5e whose
    trace drew every wave's program 1.06–1.96 ms before the host's own
    events allow (its program started before its launch)."""
    fx = json.loads(gzip.decompress((DATA / "stream.misaligned.json.gz").read_bytes()))
    registry.span_log.extend(tuple(r) for r in fx["span_log"])
    ctx = _ctx([(0, a, b) for a, b in fx["busy_ns"]], fx["phases"])
    spans = program_spans.spans(ctx.trace)
    busy = tr.union((e.start_ns, e.end_ns) for e in ctx.trace.ops)
    shift = _reader_module("api_idle_share.stream").device_shift(ctx.trace, spans, busy)
    assert 1.0e6 < shift < 1.1e6
    share = _reader("api_idle_share.stream")(ctx)
    assert 7 < share < 8.5  # as drawn, 0.41%
    assert share <= _reader("idle_share.stream")(ctx)


@pytest.mark.parametrize("case", [
    "no trace", "no span log in the program", "empty log", "fewer spans than waves",
    "a span that fits no wave",
])
def test_readers_read_nothing_without_matching_spans(registry, case):
    ctx = _ctx(STREAM_OPS, STREAM_PHASES)
    log = list(STREAM_LOG)
    if case == "no trace":
        registry.span_log.extend(log)
        ctx.trace = None
    elif case == "no span log in the program":
        set_telemetry(SimpleNamespace())  # a program without span_log
    elif case == "fewer spans than waves":
        registry.span_log.extend(log[:-1])
    elif case == "a span that fits no wave":
        log[-1] = ("absorb", SHIFT + 510, SHIFT + 1510, 70_000)
        registry.span_log.extend(log)
    assert program_spans.fold_spans(ctx.trace) is None
    assert _reader("api_cpu_us")(ctx) is None
    assert _reader("api_idle_share.stream")(ctx) is None


@pytest.mark.parametrize("workload", ["landmarks-batch", "landmarks-stream-warm"])
def test_cpu_traced_tiny_run_reads_the_program_spans(tiny, workload):
    """The CPU trace holds no device ops, so the idle share reads nothing
    here; the traces recorded on the chip (below) read it."""
    spec = harness.load_spec()
    spec["per_layer"] = [m for m in spec["per_layer"] if m["source"] == "program_span"
                         or m["name"] == "api_idle_share.stream"]
    r = harness.run_cell(workload, 2**31 + 9, 0.3, True, spec=spec, config=tiny,
                         on_chip=False, log=lambda *a, **k: None)
    assert r["correct"] is True
    assert r["metrics"]["api_cpu_us"]["value"] > 0
    assert "api_idle_share.stream" not in r["metrics"]


# a span's log record against the trace's own annotation of it: the offset
# is off by at most half the width of its bounds (24–36 µs on these traces)
ALIGNED_NS = 20_000.0
# the v5e host's thread CPU clock steps by 10 ms (every recorded cpu_ns is
# 0 or 10,000,000), so one span's CPU time can exceed its wall time by that
TICK_NS = 10_000_000


def _recorded(driver, tmp_path):
    """A traced tiny run recorded on a TPU v5e with the program's spans:
    its trace, and its registry's span log."""
    raw = gzip.decompress((DATA / f"{driver}.spans.tiny.xplane.pb.gz").read_bytes())
    path = tmp_path / f"{driver}.xplane.pb"
    path.write_bytes(raw)
    log = [tuple(r) for r in json.loads((DATA / f"{driver}.spans.tiny.json").read_text())]
    return str(path), log


@pytest.mark.parametrize("driver", ["batch", "stream"])
def test_recorded_chip_trace_with_program_spans(registry, driver, tmp_path):
    path, log = _recorded(driver, tmp_path)
    registry.span_log.extend(log)
    trace = tr.load(path)
    lo, hi = tr.window(trace)
    ctx = SimpleNamespace(trace=trace, busy_s=tr.mean_busy_s(trace),
                          trace_window_s=(hi - lo) / 1e9)
    spans = program_spans.fold_spans(trace)
    call = "accumulate" if driver == "batch" else "absorb"
    # each aligned fold span against the trace's own annotation of that call
    own = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                 for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name == f"fed3r:{call}")
    assert spans and len(own) == len(spans)
    # the log's record lies inside the annotation, on a clock one offset away
    for (_, a, b, cpu), (ta, tb) in zip(spans, own):
        assert ta - ALIGNED_NS < a < b < tb + ALIGNED_NS
        assert 0 <= cpu <= b - a + TICK_NS
    assert statistics.median(a - ta for (_, a, _, _), (ta, _) in zip(spans, own)) < ALIGNED_NS
    cpu_us = _reader("api_cpu_us")(ctx)
    assert cpu_us == pytest.approx(1e-3 * sum(s[3] for s in spans) / len(spans))
    share = _reader("api_idle_share.stream")(ctx)
    if driver == "batch":
        assert share is None
    else:
        assert 0 < share <= _reader("idle_share.stream")(ctx)

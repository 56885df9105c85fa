"""The reduction from a profiler trace to device intervals, op times and
host phases, on hand-made traces."""
import pytest

from bench import trace_reduce as tr


def _trace(ops, phases, n_devices=1):
    return tr.Trace(
        ops=[tr.OpEvent(dev, name, float(s), float(d), stats) for dev, name, s, d, stats in ops],
        phases=[tr.Span(n, float(a), float(b)) for n, a, b in phases],
        n_devices=n_devices,
    )


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert tr.union([]) == []


def test_busy_idle_and_op_time_inside_the_window():
    t = _trace(
        ops=[
            (0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 10, {}),
            (0, "%chol_gram_pallas.3 = (f32[8,8]{1,0}) custom-call(f32[8,8]{1,0} %p)",
             20, 30, {}),
            # overlaps the kernel, and takes its output as an operand
            (0, "%slice_add_fusion.2 = f32[8]{0} fusion(f32[8]{0} %chol_gram_pallas.3)",
             40, 5, {}),
            (0, "%cholesky.7 = f32[8,8]{1,0} cholesky(f32[8,8]{1,0} %p)", 70, 20, {}),
            (1, "%all-reduce = f32[8]{0} all-reduce(f32[8]{0} %p)", 10, 80, {}),
        ],
        phases=[("window", 5, 100), ("wave", 5, 60), ("block", 50, 60), ("solve", 60, 92)],
        n_devices=2,
    )
    assert tr.window(t) == (5.0, 100.0)
    assert tr.busy_ns(t, 0, 5, 100) == 5 + 30 + 20
    assert tr.busy_ns(t, 1, 5, 100) == 80
    assert tr.mean_busy_s(t) == pytest.approx((55 + 80) / 2 / 1e9)
    assert [tr.op_stem(e) for e in t.ops] == [
        "fusion", "chol_gram_pallas", "slice_add_fusion", "cholesky", "all-reduce"]
    assert tr.op_seconds(t, ["chol_gram_pallas"]) == pytest.approx(30e-9)
    assert tr.op_seconds(t, ["chol_gram"]) == 0.0  # whole names only
    assert tr.op_seconds(t, ["cholesky"]) == pytest.approx(20e-9)
    assert tr.op_seconds(t, ["all-reduce"], device=0) == 0.0
    assert tr.op_seconds(t, ["all-reduce"], device=1) == pytest.approx(80e-9)
    gaps = tr.idle_gaps(t, device=0)
    # 10→20 in "wave", 50→70 spans block then solve (middle 60: both; the
    # shorter phase wins), 90→100 in "window" only
    assert gaps == [["block", pytest.approx(20e-9)], ["wave", pytest.approx(10e-9)],
                    ["window", pytest.approx(10e-9)]]
    top = dict((n, s) for n, s in tr.top_ops(t, device=0))
    assert top["chol_gram_pallas"] == pytest.approx(30e-9)
    assert top["fusion"] == pytest.approx(5e-9)  # half of it before the window
    assert top["slice_add_fusion"] == pytest.approx(5e-9)


def test_window_must_be_in_the_trace():
    with pytest.raises(ValueError, match="window"):
        tr.window(_trace([], [("wave", 0, 1)]))

"""The harness on the CPU at a tiny size: discovery by name, the generator,
the reference comparison, the result line, and the refusal of a CPU."""
import hashlib
import json
import shutil

import numpy as np
import pytest

from bench import generator, harness, peaks, reference, work

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def test_generator_is_deterministic_under_seed(tiny):
    a = generator.make_federation(tiny, 2**31 + 5)
    b = generator.make_federation(tiny, 2**31 + 5)
    c = generator.make_federation(tiny, 7)
    assert np.array_equal(a.features, b.features)
    assert np.array_equal(a.labels, b.labels)
    assert all(np.array_equal(x, y) for x, y in zip(a.rounds, b.rounds))
    assert not np.array_equal(a.features, c.features)
    # every seed folds the same rounds, in another order: same shapes
    assert np.array_equal(a.offsets, c.offsets)
    key = lambda fed: sorted(tuple(r) for r in fed.rounds)  # noqa: E731
    assert key(a) == key(c)
    assert a.n_samples == tiny["n_samples"]
    assert a.labels.min() >= 0 and a.labels.max() < tiny["n_classes"]


# sha256 of make_federation(TINY, seed, warm_rounds) as the generator drew it
# before a configuration could name a backbone: a configuration without one
# must draw the same arrays, bit for bit
GAUSSIAN_DIGESTS = {
    (7, 0): "f36042eac32443f22d0da3d9a709905c7dd136cd46e9dada420e99d2b16957a8",
    (7, 8): "15217aeb8ab427f6650a4018956da7772b74ce43094794729b649f88f31458c4",
    (2**31 + 5, 0): "62943a45076b4c01c0f12562870c9d2f36a83240cbbce1ea0dde468544ba9028",
    (2**31 + 5, 8): "b3aee8a57ce84573e43f555e7947ced9afaea992da450dc5567badbaef06b7c3",
}


def _digest(fed) -> str:
    h = hashlib.sha256()
    for a in (fed.features, fed.labels, fed.offsets, *fed.rounds):
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    h.update(str(fed.n_classes).encode())
    return h.hexdigest()


@pytest.mark.parametrize("seed, warm", sorted(GAUSSIAN_DIGESTS))
def test_gaussian_federation_is_pinned(tiny, seed, warm):
    fed = generator.make_federation(tiny, seed, warm)
    assert _digest(fed) == GAUSSIAN_DIGESTS[seed, warm]
    assert fed.feature_dim == tiny["feature_dim"] and fed.inputs is fed.features
    assert fed.tokens is None and fed.lengths is None and fed.weights is None
    assert fed.backbone is None and fed.backbone_module is None


def test_reference_rejects_statistics_of_bf16_features(tiny):
    fed = generator.make_federation(tiny, 11)
    lam = tiny["assumed"]["ridge_lambda"]
    clients = [[k] for r in fed.rounds for k in r]
    last = len(clients) - 1
    ref = reference.statistics(fed, lam, clients, [last])[last]
    low = reference.statistics(fed, lam, clients, [last], how="bf16")[last]
    assert ref.n == fed.n_samples
    got = {"A": low.A, "b": low.b, "W": low.W, "n": low.n, "counts": low.counts}
    readings = reference.compare(got, ref, lam)
    for workload in ("landmarks-batch", "inat-batch", "landmarks-stream-warm"):
        limits = harness.load_limits(workload)
        # b on its own: W's normalized columns hide a per-class scale
        assert readings["b_rel"] > limits["b_rel"], (workload, readings)
    for workload in ("landmarks-batch", "inat-batch"):
        limits = harness.load_limits(workload)
        assert readings["A_rel"] > limits["A_rel"], (workload, readings)
    # and the reference read against itself is exact
    same = {"A": ref.A, "b": ref.b, "W": ref.W, "n": ref.n, "counts": ref.counts}
    assert all(v == 0.0 for v in reference.compare(same, ref, lam).values())


def test_result_line_has_only_the_contract_keys(tiny):
    r = harness.run_cell("landmarks-batch", 3, 0.2, False, config=tiny,
                         limits=harness.load_limits("landmarks-batch"), on_chip=False,
                         log=lambda *a, **k: None)
    assert list(r) == CONTRACT_KEYS  # ``compared`` comes last
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    assert all(set(v) == {"value", "limit"} for v in r["compared"].values())
    json.dumps(r)


def test_measurement_path_refuses_a_cpu(capsys):
    rc = harness.main(["--workload", "landmarks-batch", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no accelerator" in err


def test_new_files_are_found_by_name(tiny, tmp_path, monkeypatch):
    """A configuration, a traffic mix, an end-to-end metric and a per-layer
    metric added as new files, with entries in the spec, run with no edit to
    any file that is there."""
    for sub in ("configs", "traffic", "limits", "end_to_end", "layer_metrics"):
        shutil.copytree(harness.BENCH_DIR / sub, tmp_path / sub)
    (tmp_path / "configs" / "tiny-users.json").write_text(json.dumps(tiny))
    traffic = json.loads((tmp_path / "traffic" / "rounds-batch.json").read_text())
    traffic["round_to"] = 32
    (tmp_path / "traffic" / "rounds-batch-32.json").write_text(json.dumps(traffic))
    limits = json.loads((tmp_path / "limits" / "landmarks-batch.json").read_text())
    (tmp_path / "limits" / "tiny-batch.json").write_text(json.dumps(limits))
    (tmp_path / "end_to_end" / "passes_per_s.py").write_text(
        "def read(ctx):\n    return len(ctx.units) / ctx.window_s\n")
    (tmp_path / "layer_metrics" / "samples_per_pass.py").write_text(
        "def read(ctx):\n    return float(ctx.units[0].samples)\n")
    spec = harness.load_spec()
    spec["configs"].append({"name": "tiny-users", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny-users.json", "why": "test"})
    spec["workloads"].append({"name": "tiny-batch", "config": "tiny-users",
                              "traffic": "rounds-batch-32", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "passes_per_s", "unit": "1/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["tiny-batch"]})
    spec["per_layer"].append({"name": "samples_per_pass", "unit": "samples",
                              "better": "higher", "source": "program_counter",
                              "layer": "engine host API", "moves": "passes_per_s",
                              "workloads": ["tiny-batch"]})
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)
    quiet = dict(on_chip=False, log=lambda *a, **k: None)
    r = harness.run_cell("tiny-batch", 5, 0.2, False, spec=spec, **quiet)
    assert r["correct"] is True
    assert {"passes_per_s", "samples_per_s", "setup_s"} == set(r["metrics"])
    t = harness.run_cell("tiny-batch", 5, 0.2, True, spec=spec, **quiet)
    assert t["metrics"]["samples_per_pass"]["value"] == tiny["n_samples"]


def test_work_counts_by_hand():
    # n=2 samples, d=3, C=4: A has 6 distinct entries, each 2 multiply-adds;
    # b has 12 entries, each 2 multiply-adds
    assert work.stats_flops(2, 3, 4) == 2 * (0.5 * 2 * 3 * 4 + 2 * 3 * 4) == 72
    assert work.stats_bytes(2, 3, 4) == 4 * (6 + 2 + 6 + 12)
    # Cholesky of 3: 9 multiply-adds; two solves against 4 columns: 72
    assert work.solve_flops(3, 4) == 2 * (9 + 72)
    assert work.rank_update_bytes(2, 3, 4) == 4 * (6 + 2 + 12 + 12)


def test_peaks_table_is_keyed_by_device_kind():
    v5e = peaks.peak_for("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v9 imaginary")


def test_traced_run_fails_loudly_where_a_listed_metric_reads_nothing(tiny, tmp_path,
                                                                     monkeypatch):
    """A per-layer metric that lists the cell and finds nothing in the trace
    (a renamed kernel, say) stops the run rather than leaving the line."""
    shutil.copytree(harness.BENCH_DIR / "layer_metrics", tmp_path / "layer_metrics")
    for sub in ("configs", "traffic", "limits", "end_to_end"):
        shutil.copytree(harness.BENCH_DIR / sub, tmp_path / sub)
    (tmp_path / "layer_metrics" / "renamed_roofline.py").write_text(
        "def read(ctx):\n    return None\n")
    spec = harness.load_spec()
    spec["per_layer"] = [{"name": "renamed_roofline", "unit": "%", "better": "higher",
                          "source": "device_trace", "layer": "kernels",
                          "moves": "samples_per_s", "workloads": ["landmarks-batch"]}]
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)
    with pytest.raises(harness.MissingMetric, match="renamed_roofline"):
        harness.run_cell("landmarks-batch", 5, 0.2, True, spec=spec, config=tiny,
                         on_chip=False, log=lambda *a, **k: None)


COUNTING_DRIVER = """
from bench.drivers.batch import Driver as Batch
from repro.federated.telemetry import get_telemetry


class Driver(Batch):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        get_telemetry().counter("bench_test_setup").inc()

    def step(self):
        get_telemetry().counter("bench_test_units").inc()
        return super().step()
"""


def test_readers_get_the_windows_telemetry(tiny, tmp_path, monkeypatch, drivers_from):
    """The registry is reset as the window opens: a counter bumped in set-up
    and warm-up reads 0 in ``ctx.telemetry``, one bumped in each of the
    window's units reads their number."""
    for sub in ("configs", "limits", "end_to_end"):
        shutil.copytree(harness.BENCH_DIR / sub, tmp_path / sub)
    (tmp_path / "drivers").mkdir()
    (tmp_path / "drivers" / "counting.py").write_text(COUNTING_DRIVER)
    drivers_from(tmp_path / "drivers", "counting")
    (tmp_path / "traffic").mkdir()
    traffic = json.loads((harness.BENCH_DIR / "traffic" / "rounds-batch.json").read_text())
    (tmp_path / "traffic" / "rounds-counting.json").write_text(
        json.dumps({**traffic, "driver": "counting"}))
    for name in ("setup", "units"):
        (tmp_path / "end_to_end" / f"test_{name}.py").write_text(
            "def read(ctx):\n"
            f"    return [c['value'] for c in ctx.telemetry['counters']\n"
            f"            if c['name'] == 'bench_test_{name}'][0]\n")
    spec = harness.load_spec()
    spec["workloads"].append({"name": "counting-batch", "config": "landmarks-users-160k",
                              "traffic": "rounds-counting", "chips": 1, "why": "test"})
    spec["end_to_end"] = [{"name": f"test_{n}", "unit": "1", "better": "higher",
                           "bound": 0.05, "source": "host_clock"} for n in ("setup", "units")]
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)
    r = harness.run_cell("counting-batch", 5, 0.3, False, spec=spec, config=tiny,
                         limits=harness.load_limits("landmarks-batch"), on_chip=False,
                         log=lambda *a, **k: None)
    assert r["correct"] is True
    assert r["metrics"]["test_setup"]["value"] == 0
    assert r["metrics"]["test_units"]["value"] == r["attempted"] >= 1

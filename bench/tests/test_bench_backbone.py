"""A configuration that names a backbone, on the CPU at a toy size: token
inputs and weights from the seed, the reference's own forward, and whole
runs through ``AccumulationEngine(feature_fn=...)`` that read correct when
sound and not correct when a weight or the precision is off.

Every file of the toy cell is new and found by name: the module
``backbones/toy.py``, the configuration, the traffic and the limits under a
monkeypatched ``BENCH_DIR``, the driver as ``bench.drivers.toy_batch``."""
import importlib.util
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import generator, harness, reference
from repro.federated.engine import AccumulationEngine

DATA = Path(__file__).resolve().parent / "data"
MAX_LEN = 32
TOY = {
    "name": "toy-tokens",
    "feature_dim": 64,
    "n_classes": 40,
    "n_clients": 37,
    "n_samples": 600,
    "assumed": {
        "client_size_sigma": 1.0,
        "plan_seed": 5,
        "label_dirichlet_alpha": 0.1,
        "clients_per_round": 10,
        "ridge_lambda": 0.01,
    },
    "backbone": {
        "reference": "toy",
        "model": {"vocab": 512, "hidden": 64, "ffn": 256, "eps": 1e-6},
        "inputs": {"kind": "tokens", "vocab": 512,
                   "seq_len": {"median": 12, "sigma": 0.6, "max": MAX_LEN}},
    },
}
TRAFFIC = {"driver": "toy_batch", "aggregation": "merge", "clients_per_shard": 10,
           "round_to": 8}
QUIET = dict(on_chip=False, log=lambda *a, **k: None)
SEED = 2**31 + 17


@pytest.fixture
def toy_dir(tmp_path, monkeypatch, drivers_from):
    """A benchmark directory holding today's files and the toy cell's new ones;
    returns the spec with the toy cell in it."""
    for sub in ("configs", "traffic", "limits", "end_to_end", "layer_metrics"):
        shutil.copytree(harness.BENCH_DIR / sub, tmp_path / sub)
    (tmp_path / "backbones").mkdir()
    shutil.copy(DATA / "toy_backbone.py", tmp_path / "backbones" / "toy.py")
    (tmp_path / "drivers").mkdir()
    shutil.copy(DATA / "toy_batch.py", tmp_path / "drivers" / "toy_batch.py")
    drivers_from(tmp_path / "drivers", "toy_batch")
    (tmp_path / "configs" / "toy-tokens.json").write_text(json.dumps(TOY))
    (tmp_path / "traffic" / "toy-rounds.json").write_text(json.dumps(TRAFFIC))
    shutil.copy(tmp_path / "limits" / "landmarks-batch.json",
                tmp_path / "limits" / "toy-batch.json")
    monkeypatch.setattr(harness, "BENCH_DIR", tmp_path)
    spec = harness.load_spec()
    spec["configs"].append({"name": "toy-tokens", "source": "test", "reduced": [],
                            "file": "bench/configs/toy-tokens.json", "why": "test"})
    spec["workloads"].append({"name": "toy-batch", "config": "toy-tokens",
                              "traffic": "toy-rounds", "chips": 1, "why": "test"})
    return spec


def _toy():
    return json.loads(json.dumps(TOY))


def _fed(seed):
    """The toy federation of ``seed``, its module loaded as ``setup`` loads it."""
    return generator.make_federation(_toy(), seed, module=harness.load_backbone("toy"))


def test_lengths_are_the_plans_and_tokens_end_with_them(toy_dir):
    a = _fed(7)
    b = _fed(SEED)
    assert np.array_equal(a.lengths, b.lengths) and np.array_equal(a.offsets, b.offsets)
    assert a.lengths.min() >= 1 and a.lengths.max() <= MAX_LEN
    assert len(np.unique(a.lengths)) > 5  # lengths vary
    for fed in (a, b):
        assert fed.tokens.shape == (TOY["n_samples"], MAX_LEN)
        assert fed.tokens.dtype == np.int32
        real = np.arange(MAX_LEN)[None, :] < fed.lengths[:, None]
        assert np.all(fed.tokens[real] >= 1) and np.all(fed.tokens[real] < 512)
        assert np.all(fed.tokens[~real] == 0)


def test_tokens_and_weights_follow_the_seed(toy_dir):
    a = _fed(SEED)
    b = _fed(SEED)
    c = _fed(SEED + 1)
    assert np.array_equal(a.tokens, b.tokens) and np.array_equal(a.labels, b.labels)
    assert not np.array_equal(a.tokens, c.tokens)
    assert sorted(a.weights) == ["embed", "norm", "w_down", "w_up"]
    for k in a.weights:
        # kept on the host: the window's device memory holds the program's copy alone
        assert isinstance(a.weights[k], np.ndarray) and a.weights[k].dtype == np.float32
        assert np.array_equal(a.weights[k], b.weights[k])
        assert not np.array_equal(a.weights[k], c.weights[k])


def test_classes_shift_the_token_draw(toy_dir):
    """Each class's most frequent token is its own, so b and W carry signal."""
    fed = _fed(SEED)
    real = fed.tokens != 0
    top = {}
    for c in np.unique(fed.labels):
        rows = fed.labels == c
        if rows.sum() >= 20:
            top[c] = np.bincount(fed.tokens[rows][real[rows]]).argmax()
    assert len(top) >= 5 and len(set(top.values())) == len(top)


def test_features_are_never_materialized(toy_dir):
    fed = _fed(SEED)
    assert fed.features is None and fed.feature_dim == TOY["feature_dim"]
    assert fed.inputs is fed.tokens
    x, y = fed.client(3)
    assert x.shape == (fed.offsets[4] - fed.offsets[3], MAX_LEN) and len(y) == len(x)
    # the reference computes the features itself, in blocks that the last
    # one pads, and pools each row over its own tokens alone
    module = fed.backbone_module
    rows = np.arange(fed.n_samples)[::-1][:150]
    got = reference.backbone_features(fed, rows)
    model = TOY["backbone"]["model"]
    want = np.asarray(module.features(fed.weights, fed.tokens[rows], fed.lengths[rows], model))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    one = np.asarray(module.features(fed.weights, fed.tokens[rows[:1], :fed.lengths[rows[0]]],
                                     fed.lengths[rows[:1]], model))
    np.testing.assert_allclose(got[:1], one, rtol=2e-6, atol=2e-6)


def test_toy_work_by_hand():
    module = _load_toy()
    model = {"vocab": 512, "hidden": 4, "ffn": 8, "eps": 1e-6}
    # 5 real tokens, each through two 4×8 products: 5 · 2 · 32 multiply-adds
    assert module.flops(model, np.array([2, 3])) == 2 * 5 * 2 * 32
    # weights 2·32 + 4, each token's id and row (5 · 5), each sample's 4 features
    assert module.bytes(model, np.array([2, 3])) == 4 * (64 + 4 + 25 + 8)


def _load_toy():
    spec = importlib.util.spec_from_file_location("toy_backbone", DATA / "toy_backbone.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _run(spec, seconds=0.2):
    return harness.run_cell("toy-batch", SEED, seconds, False, spec=spec, **QUIET)


def test_backbone_cell_reads_correct(toy_dir):
    r = _run(toy_dir)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}
    assert set(r["compared"]) == set(harness.load_limits("toy-batch"))
    # the driver's units count the forward's work beside the statistics'
    run = harness.setup("toy-batch", SEED, spec=toy_dir, on_chip=False)
    extraction = _load_toy().flops(TOY["backbone"]["model"], run.fed.lengths)
    assert run.driver.flops > extraction > 0


def test_backbone_cell_reads_a_scaled_weight(toy_dir, monkeypatch):
    real = AccumulationEngine.accumulate

    def scaled(self, acc, packed, params=None):
        return real(self, acc, packed, {**params, "w_up": params["w_up"] * (1 + 1e-3)})
    monkeypatch.setattr(AccumulationEngine, "accumulate", scaled)
    r = _run(toy_dir)
    assert r["correct"] is False, r["compared"]


def test_backbone_cell_reads_bf16_features(toy_dir):
    path = harness.BENCH_DIR / "traffic" / "toy-rounds.json"
    path.write_text(json.dumps({**TRAFFIC, "dtype": "bfloat16"}))
    r = _run(toy_dir)
    assert r["correct"] is False, r["compared"]


def test_control_runs_on_the_backbone_config(toy_dir, monkeypatch):
    """``bench/readings.py --control``: the whole reference one step below,
    the forward's products at "high" and the fold at ``bf16x3``."""
    spec = importlib.util.spec_from_file_location("bench_readings",
                                                  Path(reference.__file__).parent / "readings.py")
    readings = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readings)
    run = harness.setup("toy-batch", SEED, spec=toy_dir, on_chip=False)
    module = run.fed.backbone_module
    real, seen = module.features, []

    def features(*args, precision, **kwargs):
        seen.append(precision)
        return real(*args, precision=precision, **kwargs)
    monkeypatch.setattr(module, "features", features)
    drv = run.driver
    drv.step()
    answers = drv.answers()
    lam = TOY["assumed"]["ridge_lambda"]
    limits = harness.load_limits("toy-batch")
    program = reference.worst(harness.check_answers(answers, run.fed, lam, drv.groups))
    low = readings.control_readings(run.fed, answers, lam, "bf16x3", drv.groups)
    assert seen == ["highest", "highest", "high"]
    assert set(low) == set(program)
    assert all(program[k] <= limits[k] for k in program), program
    assert any(low[k] > limits[k] for k in low), low

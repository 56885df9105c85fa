"""The Nemotron-H cell on the CPU at tiny widths: its configuration file
holds the published config, the program's forward through
``AccumulationEngine(feature_fn=...)`` in the ``batch_nemotron_h`` driver
reads correct against the plain reference, and reads not correct with one
expert's weight off by 1e-3; the control runs through ``readings.py``."""
import importlib.util
import json
from functools import partial
from pathlib import Path

import pytest

from bench import harness, reference
from repro.federated.engine import AccumulationEngine

CELL = "newsgroups-nemotron-batch"
CATALOG_KEYS = (  # the published config.json's keys, as the catalog copies them
    "attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim", "hidden_size",
    "hybrid_override_pattern", "intermediate_size", "layer_norm_epsilon",
    "mamba_head_dim", "mamba_hidden_act", "mamba_num_heads", "mamba_proj_bias",
    "max_position_embeddings", "mlp_bias", "mlp_hidden_act", "model_type",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size", "n_group", "n_groups",
    "n_routed_experts", "n_shared_experts", "norm_eps", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_logits_to_keep", "partial_rotary_factor",
    "rescale_prenorm_residual", "residual_in_fp32", "rope_theta", "routed_scaling_factor",
    "sliding_window", "ssm_state_size", "tie_word_embeddings", "time_step_floor",
    "time_step_max", "time_step_min", "topk_group", "use_bias", "use_conv_bias",
    "use_mamba_kernels", "vocab_size",
)
TINY_WIDTHS = {  # every width cut, the pattern, router and top-k kept
    "hidden_size": 64, "vocab_size": 512, "mamba_num_heads": 4, "mamba_head_dim": 16,
    "n_groups": 2, "ssm_state_size": 16, "chunk_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "router_outputs": 16,
    "n_routed_experts": 8, "held_expert_offset": 4,
}
SEED = 2**31 + 29
QUIET = dict(on_chip=False, log=lambda *a, **k: None)


def _published():
    return harness.load_config("newsgroups-nemotron3-nano")


def _tiny():
    config = json.loads(json.dumps(_published()))
    config.update(feature_dim=64, n_classes=6, n_clients=10, n_samples=90)
    config["backbone"]["model"].update(TINY_WIDTHS)
    config["backbone"]["inputs"] = {"kind": "tokens", "vocab": 512,
                                    "seq_len": {"median": 20, "sigma": 0.5, "max": 48}}
    return config


# the bf16 program at these widths reads A_rel 6.4e-3-8.0e-3 and the fp8 control
# 3.9e-2-7.6e-2; at float32 the program reads within rounding (1.7e-7)
LIMITS = {"A_rel": 2e-2, "b_rel": 2e-2, "W_rel": 0.3, "n_diff": 0.0, "counts_diff": 0.0,
          "window_compiles": 0.0}
FP32_LIMITS = dict(LIMITS, A_rel=2e-4, b_rel=2e-4, W_rel=2e-2)


def test_config_holds_the_published_widths_and_states_the_cut():
    config = _published()
    model = config["backbone"]["model"]
    spec = harness.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    cut = config["reduced_from"]
    assert sorted(entry["reduced"]) == sorted(cut)
    for key in CATALOG_KEYS:
        assert key in config, key
        assert model[key] == config[key], key  # the module reads the same numbers
    assert (config["num_hidden_layers"], cut["num_hidden_layers"]) == (7, 52)
    assert (config["n_routed_experts"], cut["n_routed_experts"]) == (16, 128)
    assert (config["n_clients"], config["n_samples"]) == (10, 1131)
    assert config["hybrid_override_pattern"][:7] == "MEMEM*E"
    assert model["router_outputs"] == 128 and model["num_experts_per_tok"] == 6
    assert config["feature_dim"] == config["hidden_size"] == 2688
    assert config["backbone"]["inputs"]["vocab"] == config["vocab_size"]


@pytest.fixture
def tiny_spec():
    return harness.load_spec()


def _run(spec, seconds=0.1):
    return harness.run_cell(CELL, SEED, seconds, False, spec=spec, config=_tiny(),
                            limits=LIMITS, **QUIET)


def test_nemotron_cell_reads_correct(tiny_spec):
    r = _run(tiny_spec)
    assert r["correct"] is True, r["compared"]
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}
    # bf16 against the float32 reference: near, not equal
    assert 0 < r["compared"]["A_rel"]["value"] < LIMITS["A_rel"]


def _float32(monkeypatch):
    """The cell's traffic with the weights and activations in float32."""
    traffic = dict(harness.load_traffic("rounds-extract"), dtype="float32")
    monkeypatch.setattr(harness, "load_traffic", lambda name: traffic)


def test_nemotron_cell_reads_a_scaled_expert(tiny_spec, monkeypatch):
    """One held expert's up weight ×(1 + 1e-3) reads not correct.  At float32:
    in bf16 the scale is below the format's step and rounds away."""
    real = AccumulationEngine.accumulate

    def scaled(self, acc, packed, params=None):
        layers = list(params["layers"])
        moe = layers[1]["moe"]
        layers[1] = {**layers[1], "moe": {**moe, "w_up": moe["w_up"].at[3].multiply(1 + 1e-3)}}
        return real(self, acc, packed, {**params, "layers": layers})
    monkeypatch.setattr(AccumulationEngine, "accumulate", scaled)
    _float32(monkeypatch)
    r = harness.run_cell(CELL, SEED, 0.1, False, spec=tiny_spec, config=_tiny(),
                         limits=FP32_LIMITS, **QUIET)
    assert r["correct"] is False, r["compared"]



def test_nemotron_cell_at_float32_reads_within_rounding(tiny_spec, monkeypatch):
    """The same path at float32 reads the reference within rounding: what
    the bf16 cell reads beyond it is the precision, not the algorithm."""
    _float32(monkeypatch)
    r = harness.run_cell(CELL, SEED, 0.1, False, spec=tiny_spec, config=_tiny(),
                         limits=FP32_LIMITS, **QUIET)
    assert r["correct"] is True, r["compared"]
    assert r["compared"]["A_rel"]["value"] < 1e-6


def test_control_runs_through_readings(tiny_spec, monkeypatch, capsys):
    """``bench/readings.py --control``: the reference one step below in the
    program's place, its forward's products in fp8, reads above the limits
    that the bf16 program reads within."""
    spec = importlib.util.spec_from_file_location(
        "bench_readings", Path(reference.__file__).parent / "readings.py")
    readings = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readings)
    tiny = _tiny()
    monkeypatch.setattr(harness, "load_config", lambda name: tiny)
    monkeypatch.setattr(harness, "load_spec", lambda: tiny_spec)
    monkeypatch.setattr(harness, "setup", partial(harness.setup, on_chip=False))  # the CPU
    assert readings.main(["--workload", CELL, "--seeds", str(SEED), "--control"]) == 0
    (line,) = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    program, control = line["program"], line["control_bf16x3"]
    assert all(program[k] <= LIMITS[k] for k in program), program
    for k in ("A_rel", "b_rel"):
        assert control[k] > 3 * program[k], (k, program, control)

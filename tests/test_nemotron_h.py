"""Nemotron-H on the CPU at its smoke size, on seeded random weights: the
program's forward against the plain reference (``bench/backbones/
nemotron_h.py``), the held-share MoE layer (shares add up, nothing is
dropped), the chunked SSD against the sequential recurrence at several
groups, masked pooling, and the ``extract_tokens`` counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.backbones import nemotron_h as ref
from bench.drivers.batch_nemotron_h import program_config, program_params
from repro.configs import get_config
from repro.data.pipeline import pack_client_shards
from repro.federated.engine import AccumulationEngine, EngineConfig
from repro.federated.telemetry import Telemetry, set_telemetry, get_telemetry
from repro.models import build_model, model as model_lib
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod

SMOKE = "nemotron3-nano-30b-a3b-smoke"
# the smoke config in the published config.json's names (the reference's input)
MODEL = {
    "hidden_size": 64, "num_hidden_layers": 7,
    "hybrid_override_pattern": get_config(SMOKE).mixer_pattern, "vocab_size": 512,
    "mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
    "chunk_size": 16, "conv_kernel": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "router_outputs": 8, "n_routed_experts": 8,
    "held_expert_offset": 0, "num_experts_per_tok": 2, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
    "norm_eps": 1e-5, "tie_word_embeddings": False,
}
F32 = dict(dtype="float32")


def _weights(model=MODEL, seed=3):
    return {k: np.asarray(v) for k, v in ref.init(model, seed).items()}


def _tokens(rng, lengths, width):
    out = np.zeros((len(lengths), width), np.int32)
    for i, n in enumerate(lengths):
        out[i, :n] = rng.integers(1, MODEL["vocab_size"], n)
    return out


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def test_bench_model_is_the_smoke_config():
    cfg = program_config(MODEL, "bfloat16")
    smoke = get_config(SMOKE)
    assert cfg == smoke.replace(name=cfg.name, source=cfg.source)


def test_forward_matches_the_reference_over_padded_lengths():
    """Pooled features of the program's float32 forward (chunked SSD,
    held-share grouped MoE, mask-pooled, in row blocks) against the plain
    reference's (sequential recurrence, dense gated experts) at "highest":
    within 1e-5 relative, for rows of different lengths padded to 48."""
    cfg = program_config(MODEL, "float32")
    weights = _weights()
    lengths = np.array([48, 1, 17, 33, 5, 40, 16, 29])
    tokens = _tokens(np.random.default_rng(0), lengths, 48)
    params = program_params(weights, cfg, jnp.float32)
    got = jax.jit(model_lib.token_feature_fn(cfg))(params, tokens)
    want = ref.features(weights, tokens, lengths, MODEL, "highest")
    assert _rel(got, want) < 1e-5


def _moe_cfg(held):
    return get_config(SMOKE).replace(experts_held=held, **F32)


def _moe_params(rng, cfg, n=8):
    p = moe_mod.held_moe_init(rng, cfg.replace(experts_held=(0, n)))
    return {**p, "router_bias": 0.3 * jax.random.normal(jax.random.PRNGKey(9), (n,))}


def _dense_moe(cfg, p, x):
    """Every routed expert over every token, weighted by its gate (zero where
    not chosen), plus the shared expert: the uncut layer."""
    xf = x.reshape(-1, x.shape[-1])
    idx, w = moe_mod.route_sigmoid(cfg, p, xf)
    gates = jnp.zeros((xf.shape[0], cfg.n_experts)).at[
        jnp.arange(xf.shape[0])[:, None], idx].add(w)
    h = jnp.square(jax.nn.relu(jnp.einsum("td,edf->etf", xf, p["w_up"])))
    y = jnp.einsum("etf,efd,te->td", h, p["w_down"], gates)
    shared = jnp.square(jax.nn.relu(xf @ p["shared"]["w_up"])) @ p["shared"]["w_down"]
    return (y + shared).reshape(x.shape)


def test_expert_shares_add_up_to_the_uncut_layer():
    """At 8 experts, the layer holding {0-3} and the layer holding {4-7},
    with the shared expert (which every chip computes) counted once, add
    up to the layer that holds all 8."""
    cfg = _moe_cfg((0, 8))
    p = _moe_params(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 40, cfg.d_model))
    whole, _ = moe_mod.held_moe_apply(cfg, p, x)
    parts = []
    for lo in (0, 4):
        share = {**p, "w_up": p["w_up"][lo:lo + 4], "w_down": p["w_down"][lo:lo + 4]}
        parts.append(moe_mod.held_moe_apply(_moe_cfg((lo, 4)), share, x)[0])
    shared = moe_mod.mlp_apply(cfg, p["shared"], x.reshape(-1, cfg.d_model)).reshape(x.shape)
    np.testing.assert_allclose(parts[0] + parts[1] - shared, whole, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(whole, _dense_moe(cfg, p, x), rtol=1e-5, atol=1e-5)


def test_held_moe_drops_nothing():
    """Every token routed to expert 5 (its correction bias dwarfs the
    scores): 600 pairs to one expert, three tiles of the grouped loop, and
    the layer still equals the dense one; the capacity layer would drop."""
    cfg = _moe_cfg((0, 8))
    p = _moe_params(jax.random.PRNGKey(4), cfg)
    p = {**p, "router_bias": p["router_bias"].at[5].set(100.0)}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 300, cfg.d_model))
    idx, _ = moe_mod.route_sigmoid(cfg, p, x.reshape(-1, cfg.d_model))
    assert bool(jnp.all(jnp.any(idx == 5, axis=1)))
    assert 600 > 2 * moe_mod.HELD_TILE
    got, _ = jax.jit(lambda p, x: moe_mod.held_moe_apply(cfg, p, x))(p, x)
    np.testing.assert_allclose(got, _dense_moe(cfg, p, x), rtol=1e-5, atol=1e-5)


def test_padding_takes_no_routed_expert():
    """Positions the mask marks as padding take the shared expert alone;
    the real ones read as without a mask."""
    cfg = _moe_cfg((0, 8))
    p = _moe_params(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, cfg.d_model))
    real = jnp.arange(40)[None, :] < jnp.array([[25], [3]])
    got, _ = moe_mod.held_moe_apply(cfg, p, x, real)
    whole, _ = moe_mod.held_moe_apply(cfg, p, x)
    shared = moe_mod.mlp_apply(cfg, p["shared"], x)
    np.testing.assert_allclose(got, jnp.where(real[..., None], whole, shared),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_chunked_ssd_matches_the_sequential_recurrence(groups):
    """The Mamba-2 mixer's chunked SSD (4 chunks of 16) against the
    reference's one-step-a-token recurrence, heads sharing B and C by group."""
    model = dict(MODEL, n_groups=groups)
    cfg = program_config(model, "float32")
    weights = _weights(model, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg.d_model))
    params = program_params(weights, cfg, jnp.float32)["layers"][0]["mamba"]
    got, _ = ssm_mod.ssm_apply(cfg, params, x)
    want = ref._mamba(weights, "0.", x, model, "highest")
    assert _rel(got, want) < 1e-5


def _pooled_mean(cfg, params, batch):
    """The pooling as it was before per-token masks: every position."""
    out = model_lib.forward(cfg, params, batch, mode="train", return_logits=False)
    return jnp.mean(out.hidden.astype(jnp.float32), axis=1)


def test_masked_padding_leaves_features_unchanged():
    """A causal model (mamba2-1.3b smoke, float32): appending padding
    positions that the mask marks leaves each row's features as they were,
    and a row of padding alone gives zeros."""
    cfg = get_config("mamba2-1.3b-smoke").replace(**F32)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 32), 1, cfg.vocab_size)
    alone = m.extract_features(params, {"tokens": tokens, "mask": tokens != 0})
    padded = jnp.concatenate([tokens, jnp.zeros((3, 32), jnp.int32)], axis=1)
    padded = jnp.concatenate([padded, jnp.zeros((1, 64), jnp.int32)])
    got = m.extract_features(params, {"tokens": padded, "mask": padded != 0})
    np.testing.assert_allclose(got[:3], alone, rtol=1e-5, atol=1e-6)
    assert not bool(jnp.any(got[3]))
    np.testing.assert_allclose(alone, _pooled_mean(cfg, params, {"tokens": tokens}),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name", ["mamba2-1.3b-smoke", "qwen2-7b-smoke"])
def test_unmasked_features_are_bit_identical(name):
    """Without a per-token mask (none, or the statistics step's per-sample
    one) extract_features pools every position, bit for bit as before."""
    cfg = get_config(name)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    want = _pooled_mean(cfg, params, {"tokens": tokens})
    assert np.array_equal(m.extract_features(params, {"tokens": tokens}), want)
    per_sample = {"tokens": tokens, "mask": jnp.array([1.0, 0.0])}
    assert np.array_equal(m.extract_features(params, per_sample), want)


def test_feature_fn_runs_in_row_blocks(monkeypatch):
    """The rows go through the forward in blocks sized from the shapes;
    the result does not depend on the block size."""
    cfg = program_config(MODEL, "float32")
    params = program_params(_weights(), cfg, jnp.float32)
    lengths = np.array([30, 7, 48, 12, 1, 22, 39])
    tokens = _tokens(np.random.default_rng(2), lengths, 48)
    whole = jax.jit(model_lib.token_feature_fn(cfg))(params, tokens)
    monkeypatch.setattr(model_lib, "ACTIVATION_BYTES",
                        3 * model_lib.ACTIVATION_BYTES // model_lib.feature_block_rows(cfg, 48))
    assert model_lib.feature_block_rows(cfg, 48) == 3
    blocked = jax.jit(model_lib.token_feature_fn(cfg))(params, tokens)
    np.testing.assert_allclose(blocked, whole, rtol=1e-6, atol=1e-6)


def test_extract_token_counters():
    """pack_client_shards records a token packing's real tokens and the
    positions computed; accumulate adds them when it runs a forward."""
    previous = get_telemetry()
    t = Telemetry()
    set_telemetry(t)
    try:
        rng = np.random.default_rng(0)
        lens = [np.array([5, 9, 3]), np.array([16, 2])]
        clients = [(_tokens(rng, n, 16), np.zeros(len(n), np.int32)) for n in lens]
        packed = pack_client_shards(clients, 1, round_to=4)
        assert packed.extract_tokens == (35, 2 * 4 * 16)
        features = pack_client_shards(
            [(np.ones((3, 8), np.float32), np.zeros(3, np.int32))], 1)
        assert features.extract_tokens is None

        def counts():
            return {c["labels"]["kind"]: c["value"] for c in t.snapshot()["counters"]
                    if c["name"] == "extract_tokens"}

        def feature_fn(params, toks):
            return jnp.ones((toks.shape[0], 4)) * jnp.sum(toks != 0, axis=1, keepdims=True)

        eng = AccumulationEngine(EngineConfig(n_classes=2), feature_fn=feature_fn)
        acc = eng.accumulate(eng.init(4), packed)
        acc = eng.accumulate(acc, packed)
        assert counts() == {"real": 70, "computed": 256}
        plain = AccumulationEngine(EngineConfig(n_classes=2))
        plain.accumulate(plain.init(8), features)
        assert counts() == {"real": 70, "computed": 256}
    finally:
        set_telemetry(previous)

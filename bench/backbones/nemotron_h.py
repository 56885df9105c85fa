"""Plain reference of Nemotron-H (``model_type`` nemotron_h), the backbone of
NVIDIA-Nemotron-3-Nano-30B-A3B: float32 ``jax.numpy``, every product through
``bench.reference.matmul``.  Imports nothing of the program.

The forward, as ``modeling_nemotron_h.py`` describes it: token embedding;
each block ``x + mixer(RMSNorm(x))`` (eps ``layer_norm_epsilon``), its mixer
given by ``hybrid_override_pattern`` (the first ``num_hidden_layers``
characters); ``norm_f`` (eps ``norm_eps``); the mean over each sample's
real tokens.

* ``M``, Mamba-2: in_proj to z ‖ xBC ‖ dt; depthwise causal conv of width
  ``conv_kernel`` with bias over xBC, then SiLU; dt = softplus(dt +
  dt_bias), A = −exp(A_log).  The state runs as the sequential recurrence,
  h_t = exp(dt·A)·h_{t−1} + dt·x_t ⊗ B_t, y_t = C_t·h_t + D·x_t, one
  ``lax.scan`` step a token (not the chunked dual form the program runs);
  head h reads group h // (heads/groups).  Then RMSNorm(y·silu(z)) per group
  of mamba_num_heads·mamba_head_dim / n_groups channels, and out_proj.
* ``E``, MoE: sigmoid of x·router in fp32; top-``num_experts_per_tok`` on
  score + ``e_score_correction_bias``; the chosen scores normalized to sum
  to one and scaled by ``routed_scaling_factor``.  The routed experts this
  chip holds (``n_routed_experts`` of ``router_outputs``, from
  ``held_expert_offset``) run dense, each weighted by its gate (zero where
  the token did not choose it): down(relu(up x)²).  Plus the shared expert
  of the same form.
* ``*``, attention: ``num_attention_heads`` queries over
  ``num_key_value_heads`` key/value heads of ``head_dim``, causal, scale
  head_dim^-½, no bias.  No rotary embedding: the published module applies
  none (its config's ``rope_theta`` is not read).

Precision: "highest" is the reference.  The configuration states bf16, so
"high" is its control one step below: every weight product in fp8 (e4m3),
each operand scaled by its own row's (activations) or column's (weights)
largest magnitude, the router (stated fp32) at bf16x3; the rest stays
float32.

The forward sorts a block's rows by length and runs them in groups of
``GROUP`` rows, each truncated to the multiple of ``BUCKET`` tokens that
holds its longest row: padding past a row's end changes none of its
positions, and features are per sample.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import matmul

ROWS = 128  # samples per block of the reference's forward
GROUP = 16  # rows run together, sorted by length
BUCKET = 128  # a group's tokens are truncated to a multiple of this


def _widths(model: dict) -> dict:
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    return dict(H=H, P=P, G=G, N=N, inner=H * P, conv=H * P + 2 * G * N)


def kinds(model: dict) -> str:
    return model["hybrid_override_pattern"][: model["num_hidden_layers"]]


def init(model: dict, seed: int) -> dict:
    """float32 weights from ``seed``, made on the device in one call.  The
    router's correction bias is seeded non-zero, so that it moves the
    experts chosen."""
    d, V = model["hidden_size"], model["vocab_size"]
    w = _widths(model)
    E, n, f = model["router_outputs"], model["n_routed_experts"], model["moe_intermediate_size"]
    fs = model["moe_shared_expert_intermediate_size"]
    hq = model["num_attention_heads"] * model["head_dim"]
    hkv = model["num_key_value_heads"] * model["head_dim"]
    K = model["conv_kernel"]

    def normal(key, shape, fan_in=1):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    def scale(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 2 + 12 * model["num_hidden_layers"]))
        out = {"embed": normal(next(keys), (V, d)), "norm_f": scale(next(keys), (d,))}
        for i, kind in enumerate(kinds(model)):
            p = f"{i}."
            out[p + "norm"] = scale(next(keys), (d,))
            if kind == "M":
                dt = jnp.exp(jax.random.uniform(next(keys), (w["H"],), jnp.float32,
                                                np.log(1e-3), np.log(1e-1)))
                out.update({
                    p + "in_proj": normal(next(keys), (d, 2 * w["inner"] + 2 * w["G"] * w["N"]
                                                       + w["H"]), d),
                    p + "conv_w": normal(next(keys), (K, w["conv"]), K),
                    p + "conv_b": 0.1 * normal(next(keys), (w["conv"],)),
                    p + "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus⁻¹(dt)
                    p + "A_log": jnp.log(jax.random.uniform(next(keys), (w["H"],),
                                                            jnp.float32, 1.0, 16.0)),
                    p + "D": scale(next(keys), (w["H"],)),
                    p + "gate_norm": scale(next(keys), (w["inner"],)),
                    p + "out_proj": normal(next(keys), (w["inner"], d), w["inner"]),
                })
            elif kind == "E":
                out.update({
                    p + "router": normal(next(keys), (d, E), d),
                    p + "router_bias": 0.1 * normal(next(keys), (E,)),
                    p + "up": normal(next(keys), (n, d, f), d),
                    p + "down": normal(next(keys), (n, f, d), f),
                    p + "shared_up": normal(next(keys), (d, fs), d),
                    p + "shared_down": normal(next(keys), (fs, d), fs),
                })
            else:
                out.update({
                    p + "wq": normal(next(keys), (d, hq), d),
                    p + "wk": normal(next(keys), (d, hkv), d),
                    p + "wv": normal(next(keys), (d, hkv), d),
                    p + "wo": normal(next(keys), (hq, d), hq),
                })
        return out

    return make(jax.random.key(seed))


# ---- products -----------------------------------------------------------------


FP8_MAX = 448.0  # the largest float8_e4m3fn


def _fp8(a, axis):
    """a rounded to float8 e4m3, scaled so that its largest magnitude along
    ``axis`` is the format's largest."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision):
    """x @ w of a weight product: float32 at "highest"; the control's fp8."""
    if precision == "high":
        return matmul(_fp8(x, -1), _fp8(w, 0), "highest")
    return matmul(x, w, precision)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# ---- mixers ---------------------------------------------------------------------


def _mamba(wt, p, x, model, precision):
    B, L, _ = x.shape
    w = _widths(model)
    H, P, G, N, inner = w["H"], w["P"], w["G"], w["N"], w["inner"]
    zxbcdt = _mm(x, wt[p + "in_proj"], precision)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + w["conv"]],
                  zxbcdt[..., inner + w["conv"]:])
    K = wt[p + "conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + L] * wt[p + "conv_w"][i] for i in range(K)) + wt[p + "conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :inner].reshape(B, L, H, P)
    Bm = jnp.repeat(xbc[..., inner:inner + G * N].reshape(B, L, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., inner + G * N:].reshape(B, L, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + wt[p + "dt_bias"])  # (B, L, H)
    A = -jnp.exp(wt[p + "A_log"])

    def step(h, inp):  # h: (B, H, P, N)
        x_t, b_t, c_t, dt_t = inp
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    seq = tuple(jnp.moveaxis(a, 1, 0) for a in (xs, Bm, Cm, dt))
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32), seq)
    y = jnp.moveaxis(ys, 0, 1) + wt[p + "D"][:, None] * xs  # (B, L, H, P)
    y = y.reshape(B, L, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(B, L, G, inner // G), 1.0, model["layer_norm_epsilon"])
    y = y.reshape(B, L, inner) * wt[p + "gate_norm"]
    return _mm(y, wt[p + "out_proj"], precision)


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def _moe(wt, p, x, model, precision):
    B, L, d = x.shape
    xf = x.reshape(B * L, d)
    scores = jax.nn.sigmoid(matmul(xf, wt[p + "router"], precision))
    _, idx = jax.lax.top_k(scores + wt[p + "router_bias"], model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if model["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    chosen = chosen * model["routed_scaling_factor"]
    held = model["held_expert_offset"] + jnp.arange(model["n_routed_experts"])
    gates = jnp.sum(jnp.where(idx[:, :, None] == held, chosen[:, :, None], 0.0), axis=1)

    def expert(y, e):
        up, down, g = e
        return y + g[:, None] * _mm(_relu2(_mm(xf, up, precision)), down, precision), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xf),
                        (wt[p + "up"], wt[p + "down"], gates.T))
    y = y + _mm(_relu2(_mm(xf, wt[p + "shared_up"], precision)), wt[p + "shared_down"],
                precision)
    return y.reshape(B, L, d)


def _attention(wt, p, x, model, precision):
    B, L, _ = x.shape
    Hq, Hkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    q = _mm(x, wt[p + "wq"], precision).reshape(B, L, Hkv, Hq // Hkv, hd)
    k = _mm(x, wt[p + "wk"], precision).reshape(B, L, Hkv, hd)
    v = _mm(x, wt[p + "wv"], precision).reshape(B, L, Hkv, hd)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k, precision=hi) * hd ** -0.5
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgqs,bskh->bqkgh", jax.nn.softmax(s, axis=-1), v, precision=hi)
    return _mm(o.reshape(B, L, Hq * hd), wt[p + "wo"], precision)


MIXERS = {"M": _mamba, "E": _moe, "*": _attention}


def _pooled(weights, tokens, lengths, model, precision, length):
    """(rows, d) features of rows no longer than ``length`` tokens."""
    tokens = tokens[:, :length]
    x = jnp.take(weights["embed"], tokens, axis=0)
    for i, kind in enumerate(kinds(model)):
        p = f"{i}."
        h = _rms(x, weights[p + "norm"], model["layer_norm_epsilon"])
        x = x + MIXERS[kind](weights, p, h, model, precision)
    x = _rms(x, weights["norm_f"], model["norm_eps"])
    real = jnp.arange(length)[None, :] < lengths[:, None]
    return jnp.sum(jnp.where(real[..., None], x, 0.0), axis=1) / lengths[:, None]


def features(weights: dict, tokens, lengths, model: dict, precision: str = "highest"):
    """(rows, d) float32: the forward over ``tokens`` (rows, max), averaged
    over each row's first ``lengths`` positions."""
    n, width = tokens.shape
    group = min(GROUP, n)
    rows = -(-n // group) * group
    tokens = jnp.pad(tokens, ((0, rows - n), (0, 0)))
    lengths = jnp.pad(lengths, (0, rows - n), constant_values=1)
    order = jnp.argsort(lengths)
    buckets = [min(b, width) for b in range(BUCKET, width + BUCKET, BUCKET)]
    branches = [partial(_pooled, model=model, precision=precision, length=b) for b in buckets]

    def run(take):
        lens = lengths[take]
        which = jnp.searchsorted(jnp.asarray(buckets), jnp.max(lens))
        return jax.lax.switch(which, branches, weights, tokens[take], lens)

    feats = jax.lax.map(run, order.reshape(rows // group, group)).reshape(rows, -1)
    return jnp.zeros_like(feats).at[order].set(feats)[:n]


# ---- work -----------------------------------------------------------------------


def _token_params(model: dict) -> dict:
    """Weights each real token multiplies by, per mixer kind, and the state
    work of a Mamba token."""
    d = model["hidden_size"]
    w = _widths(model)
    f, fs = model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]
    hq = model["num_attention_heads"] * model["head_dim"]
    hkv = model["num_key_value_heads"] * model["head_dim"]
    held_pairs = (model["num_experts_per_tok"] * model["n_routed_experts"]
                  / model["router_outputs"])
    return {
        "M": d * (2 * w["inner"] + 2 * w["G"] * w["N"] + w["H"]) + w["inner"] * d
        + 2 * w["H"] * w["P"] * w["N"] + model["conv_kernel"] * w["conv"],
        "E": d * model["router_outputs"] + held_pairs * 2 * d * f + 2 * d * fs,
        "*": d * (hq + 2 * hkv) + hq * d,
    }


def flops(model: dict, lengths) -> float:
    """The forward's least work over the real tokens (2 FLOPs a multiply-add):
    every weight product at the active parameters (routed experts at
    top-k·held/router_outputs a token), Mamba's state update and read, the
    causal attention's scores and values."""
    per = _token_params(model)
    lengths = np.asarray(lengths, np.float64)
    tokens = float(np.sum(lengths))
    macs = sum(per[k] for k in kinds(model)) * tokens
    hq = model["num_attention_heads"] * model["head_dim"]
    macs += kinds(model).count("*") * 2 * hq * float(np.sum(lengths * (lengths + 1) / 2))
    return 2.0 * macs


def bytes(model: dict, lengths) -> float:  # noqa: A001 - the module's contract names it
    """Read every weight once in bf16 (the stated precision) and each real
    token's id and bf16 embedding row; write each sample's fp32 features."""
    d = model["hidden_size"]
    n_weights = sum(int(np.prod(s.shape)) for s in jax.eval_shape(
        lambda: init(model, 0)).values()) - model["vocab_size"] * d
    tokens = float(np.sum(lengths))
    return 2.0 * n_weights + tokens * (4 + 2 * d) + 4.0 * len(lengths) * d

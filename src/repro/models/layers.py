"""Common neural-net layers: norms, MLPs, embeddings, rotary (+M-RoPE), conv.

Conventions
-----------
* activations: ``(batch, seq, d_model)`` in ``cfg.dtype`` (bf16 by default);
* parameters: fp32, cast to compute dtype at use;
* every layer is a pair of functions ``<layer>_init(rng, cfg, ...) -> params``
  and ``<layer>_apply(params, x, ...) -> y`` over plain dict pytrees — no
  framework objects, so the whole stack pjit/shard_maps transparently.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(rng, shape, in_axis: int = 0, dtype=jnp.float32):
    """Fan-in truncated-normal initializer (maxtext-style)."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return std * jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype)


def embed_init(rng, shape, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_init(cfg: ModelConfig, d: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """RMSNorm or LayerNorm computed in fp32, returned in input dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        eps = 1e-5 if cfg.norm_eps is None else cfg.norm_eps
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        eps = 1e-6 if cfg.norm_eps is None else cfg.norm_eps
        y = x * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    """Gated (swiglu/geglu) or plain (gelu, relu2) MLP parameters."""
    d, f = cfg.d_model, d_ff or cfg.d_ff
    r = jax.random.split(rng, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(r[0], (d, f)),
            "w_up": dense_init(r[1], (d, f)),
            "w_down": dense_init(r[2], (f, d)),
        }
    if cfg.mlp_type == "relu2":
        return {"w_up": dense_init(r[0], (d, f)), "w_down": dense_init(r[1], (f, d))}
    return {
        "w_up": dense_init(r[0], (d, f)),
        "b_up": jnp.zeros((f,), jnp.float32),
        "w_down": dense_init(r[1], (f, d)),
        "b_down": jnp.zeros((cfg.d_model,), jnp.float32),
    }


def relu2(x: jax.Array) -> jax.Array:
    """relu(x)², the non-gated activation of Nemotron-H's experts."""
    return jnp.square(jax.nn.relu(x))


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        g = act(x @ p["w_gate"].astype(dt))
        u = x @ p["w_up"].astype(dt)
        return (g * u) @ p["w_down"].astype(dt)
    if cfg.mlp_type == "relu2":
        return relu2(x @ p["w_up"].astype(dt)) @ p["w_down"].astype(dt)
    h = jax.nn.gelu(x @ p["w_up"].astype(dt) + p["b_up"].astype(dt))
    return h @ p["w_down"].astype(dt) + p["b_down"].astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the rotary half-dims: (head_dim//2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> jax.Array:
    """positions (..., S) -> angles (..., S, head_dim//2), fp32."""
    inv = rope_frequencies(head_dim, theta)
    return positions.astype(jnp.float32)[..., None] * inv


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate pairs. x: (B, S, H, hd); angles: (B, S, hd//2) or (S, hd//2)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[..., None, :]  # (B, S, 1, hd//2)
    sin = jnp.sin(angles)[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def mrope_angles(
    positions_3d: jax.Array, head_dim: int, theta: float, sections: Tuple[int, ...]
) -> jax.Array:
    """M-RoPE (Qwen2-VL): three position streams share the rotary dims.

    positions_3d: (3, B, S) — temporal / height / width position ids.
    sections: how many of the head_dim//2 rotary dims each stream owns,
    e.g. (16, 24, 24) for head_dim=128.

    Returns angles (B, S, head_dim//2).
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    inv = rope_frequencies(head_dim, theta)  # (hd//2,)
    # angles per stream: (3, B, S, hd//2)
    ang = positions_3d.astype(jnp.float32)[..., None] * inv
    pieces = []
    start = 0
    for i, sec in enumerate(sections):
        pieces.append(ang[i, ..., start : start + sec])
        start += sec
    return jnp.concatenate(pieces, axis=-1)


def sinusoidal_positions(n_pos: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings (n_pos, d), fp32."""
    half = d // 2
    log_timescale = math.log(10_000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    scaled = jnp.arange(n_pos, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


# ---------------------------------------------------------------------------
# causal depthwise conv (mamba2 / RG-LRU temporal conv)
# ---------------------------------------------------------------------------


def causal_conv1d_init(rng, channels: int, width: int) -> dict:
    return {
        "kernel": dense_init(rng, (width, channels), in_axis=0),
        "bias": jnp.zeros((channels,), jnp.float32),
    }


def causal_conv1d_apply(p: dict, x: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, C) -> (B, S, C)."""
    width = p["kernel"].shape[0]
    dt = x.dtype
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    ker = p["kernel"].astype(dt)
    out = jnp.zeros_like(x)
    for i in range(width):  # width is small (4): unrolled taps
        out = out + pad[:, i : i + x.shape[1], :] * ker[i]
    return out + p["bias"].astype(dt)


def causal_conv1d_step(p: dict, conv_state: jax.Array, x_t: jax.Array):
    """Single decode step. conv_state: (B, width-1, C); x_t: (B, C)."""
    width = p["kernel"].shape[0]
    dt = x_t.dtype
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B, W, C)
    ker = p["kernel"].astype(dt)
    y = jnp.einsum("bwc,wc->bc", window, ker) + p["bias"].astype(dt)
    new_state = window[:, 1:, :] if width > 1 else conv_state
    return new_state, y


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def embedding_init(rng, cfg: ModelConfig) -> dict:
    p = {"embedding": embed_init(rng, (cfg.vocab_size, cfg.d_model))}
    return p


def embed_apply(p: dict, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(dtype)


def unembed_apply(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Project hidden states to vocab logits (tied or separate head).

    The table is padded to ``cfg.padded_vocab`` for even sharding; padded
    columns are masked to −inf so softmax/CE semantics are unchanged.
    """
    if cfg.tie_embeddings:
        w = params["embed"]["embedding"].astype(x.dtype)
        logits = x @ w.T
    else:
        logits = x @ params["lm_head"]["kernel"].astype(x.dtype)
    if cfg.attn_logit_softcap:  # reuse as final-logit softcap when configured
        cap = cfg.attn_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    if cfg.padded_vocab > cfg.vocab_size:
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(col < cfg.vocab_size, logits, jnp.asarray(-1e30, logits.dtype))
    return logits

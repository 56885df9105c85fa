"""Mamba2 mixer — SSD (state-space duality) chunked scan + O(1) decode.

Follows the Mamba2 paper (arXiv:2405.21060) "fully recurrent <-> quadratic
dual" chunked algorithm:

  * within a chunk of length Q, the output is an attention-like quadratic
    form  Y_intra = (C Bᵀ ∘ L) (Δ·X)  with L the decay-weighted causal mask;
  * across chunks a tiny recurrence carries the (H, P, N) state
    h_{c+1} = (Π decay) h_c + states_c, run with ``jax.lax.scan``;
  * decode is a rank-1 state update per token — the sub-quadratic path that
    makes the long_500k shape feasible for this architecture.

TPU adaptation: the intra-chunk term is MXU-shaped matmuls over (Q, Q) and
(Q, N)/(Q, P) tiles (Q = cfg.ssm_chunk = 256, N = 128, P = 64 — all
128-friendly); the inter-chunk scan carries only B·H·P·N floats.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    causal_conv1d_apply,
    causal_conv1d_init,
    causal_conv1d_step,
    dense_init,
)
from repro.sharding.hints import hint


def ssm_init(rng, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_inner = cfg.d_inner
    H = cfg.ssm_nheads
    N = cfg.ssm_state
    g = cfg.ssm_ngroups
    conv_ch = d_inner + 2 * g * N
    d_in_proj = 2 * d_inner + 2 * g * N + H
    r = jax.random.split(rng, 4)
    return {
        "in_proj": dense_init(r[0], (d, d_in_proj)),
        "conv": causal_conv1d_init(r[1], conv_ch, cfg.ssm_conv),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), jnp.float32),
        "out_proj": dense_init(r[3], (d_inner, d)),
    }


def _segsum(a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j < k <= i} a[..., k].

    a: (..., Q) -> (..., Q, Q) lower-triangular (−inf above diagonal).
    """
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # cs_i - cs_j
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)  inputs already weighted by Δ
    a: jax.Array,  # (B, S, H)     log-decay per step (Δ·A, negative)
    Bm: jax.Array,  # (B, S, H, N)
    Cm: jax.Array,  # (B, S, H, N)
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y: (B,S,H,P), final_state: (B,H,P,N))."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    nc = S // Q

    # reshape to chunks: (B, nc, Q, ...); heads stay tensor-parallel
    xc = hint(x.reshape(B, nc, Q, H, P), "batch", None, None, "model", None)
    ac = hint(a.reshape(B, nc, Q, H).transpose(0, 1, 3, 2), "batch", None, "model", None)
    Bc = hint(Bm.reshape(B, nc, Q, H, N), "batch", None, None, "model", None)
    Cc = hint(Cm.reshape(B, nc, Q, H, N), "batch", None, None, "model", None)

    a_cum = jnp.cumsum(ac, axis=-1)  # (B, nc, H, Q)

    # ---- intra-chunk (quadratic, attention-like) --------------------------
    L = hint(jnp.exp(_segsum(ac)), "batch", None, "model", None, None)
    scores = hint(
        jnp.einsum("bclhn,bcshn->bchls", Cc, Bc), "batch", None, "model", None, None
    )
    y_diag = hint(
        jnp.einsum("bchls,bchls,bcshp->bclhp", scores, L, xc),
        "batch", None, None, "model", None,
    )

    # ---- per-chunk states (fp32 carry for numerical stability) -------------
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)  # (B, nc, H, Q)
    states = hint(
        jnp.einsum(
            "bchl,bclhn,bclhp->bchpn",
            decay_states,
            Bc.astype(jnp.float32),
            xc.astype(jnp.float32),
        ),
        "batch", None, "model", None, None,
    )

    # ---- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(a_cum[..., -1])  # (B, nc, H)
    h0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((B, H, P, N), jnp.float32)
    )

    def step(h, inp):
        dec, st = inp  # (B, H), (B, H, P, N)
        h_new = h * dec[..., None, None] + st
        return h_new, h  # emit the state *entering* the chunk

    final_state, prev_states = jax.lax.scan(
        step, h0, (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4))
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B, nc, H, P, N)

    # ---- contribution of carried state to each position --------------------
    state_decay = jnp.exp(a_cum)  # (B, nc, H, Q)
    prev_states = hint(prev_states, "batch", None, "model", None, None)
    y_off = jnp.einsum(
        "bclhn,bchpn,bchl->bclhp", Cc.astype(jnp.float32), prev_states, state_decay
    ).astype(x.dtype)

    y = (y_diag.astype(x.dtype) + y_off).reshape(B, S, H, P)
    return y, final_state


def _split_zxbcdt(cfg: ModelConfig, zxbcdt: jax.Array):
    d_inner = cfg.d_inner
    g, N, H = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : 2 * d_inner + 2 * g * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * g * N :]
    return z, xBC, dt


def _split_xbc(cfg: ModelConfig, xBC: jax.Array):
    d_inner = cfg.d_inner
    g, N = cfg.ssm_ngroups, cfg.ssm_state
    x = xBC[..., :d_inner]
    Bm = xBC[..., d_inner : d_inner + g * N]
    Cm = xBC[..., d_inner + g * N :]
    return x, Bm, Cm


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   groups: int = 1, eps: float = 1e-6) -> jax.Array:
    """RMSNorm of y·silu(z), over all of d_inner or per group of
    d_inner/groups channels (Nemotron-H)."""
    dt = y.dtype
    y = (y * jax.nn.silu(z)).astype(jnp.float32)
    if groups == 1:
        ms = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
        return (y * jax.lax.rsqrt(ms + eps) * scale).astype(dt)
    yg = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    ms = jnp.mean(jnp.square(yg), axis=-1, keepdims=True)
    return ((yg * jax.lax.rsqrt(ms + eps)).reshape(y.shape) * scale).astype(dt)


def _norm_args(cfg: ModelConfig) -> dict:
    return {"groups": cfg.ssm_ngroups if cfg.ssm_group_norm else 1,
            "eps": 1e-6 if cfg.norm_eps is None else cfg.norm_eps}


def ssm_apply(
    cfg: ModelConfig,
    p: dict,
    u: jax.Array,
    *,
    build_cache: bool = False,
) -> Tuple[jax.Array, Optional[dict]]:
    """Sequence-mode Mamba2 mixer. u: (B, S, d)."""
    B, S, _ = u.shape
    H, P, N, g = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_ngroups
    dt_ = u.dtype

    zxbcdt = hint(u @ p["in_proj"].astype(dt_), "batch", None, "model")
    z, xBC_raw, dtr = _split_zxbcdt(cfg, zxbcdt)
    xBC = jax.nn.silu(causal_conv1d_apply(p["conv"], xBC_raw))
    x, Bm, Cm = _split_xbc(cfg, xBC)

    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])  # (B, S, H)
    A = -jnp.exp(p["A_log"])  # (H,)

    xh = x.reshape(B, S, H, P)
    Bh = jnp.repeat(Bm.reshape(B, S, g, N), H // g, axis=2)
    Ch = jnp.repeat(Cm.reshape(B, S, g, N), H // g, axis=2)

    y, final_state = ssd_chunked(
        xh * dt[..., None].astype(dt_), (dt * A).astype(jnp.float32), Bh, Ch,
        cfg.ssm_chunk,
    )
    y = y + xh * p["D"][None, None, :, None].astype(dt_)
    y = y.reshape(B, S, H * P)
    y = _gated_rmsnorm(y, z, p["norm_scale"], **_norm_args(cfg))
    out = y @ p["out_proj"].astype(dt_)

    cache = None
    if build_cache:
        w = cfg.ssm_conv
        tail = xBC_raw[:, max(0, S - (w - 1)) :, :]
        pad = jnp.zeros((B, (w - 1) - tail.shape[1], tail.shape[-1]), dt_)
        cache = {
            "state": final_state.astype(jnp.float32),
            "conv": jnp.concatenate([pad, tail], axis=1),
        }
    return out, cache


def ssm_decode_step(
    cfg: ModelConfig, p: dict, u_t: jax.Array, cache: dict
) -> Tuple[jax.Array, dict]:
    """One-token recurrent update. u_t: (B, 1, d); O(B·H·P·N) work."""
    B = u_t.shape[0]
    H, P, N, g = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_ngroups
    dt_ = u_t.dtype

    zxbcdt = (u_t[:, 0, :] @ p["in_proj"].astype(dt_))  # (B, dproj)
    z, xBC, dtr = _split_zxbcdt(cfg, zxbcdt)
    conv_state, xBC = causal_conv1d_step(p["conv"], cache["conv"], xBC)
    xBC = jax.nn.silu(xBC)
    x, Bm, Cm = _split_xbc(cfg, xBC)

    dt = jax.nn.softplus(dtr.astype(jnp.float32) + p["dt_bias"])  # (B, H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)  # (B, H)

    xh = x.reshape(B, H, P).astype(jnp.float32)
    Bh = jnp.repeat(Bm.reshape(B, g, N), H // g, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(Cm.reshape(B, g, N), H // g, axis=1).astype(jnp.float32)

    state = cache["state"] * dA[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt, xh, Bh
    )
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + xh * p["D"][None, :, None]
    y = y.reshape(B, H * P).astype(dt_)
    y = _gated_rmsnorm(y, z, p["norm_scale"], **_norm_args(cfg))
    out = (y @ p["out_proj"].astype(dt_))[:, None, :]
    return out, {"state": state, "conv": conv_state}

"""Model facade: init / forward / prefill / decode / extract_features.

``build_model(cfg)`` returns a :class:`Model` of pure functions over plain
pytrees — the single entry point used by the launcher, the federated runtime,
the FED3R driver and the tests.

Batch dict contract (see launch/shapes.py for the ShapeDtypeStruct specs):
  * ``tokens``        (B, S) int32 — always present (decode: (B, 1))
  * ``labels``        (B, S) int32 — train mode (next-token targets)
  * ``patch_embeds``  (B, n_patches, d) — vlm only (stub vision frontend)
  * ``audio_frames``  (B, n_frames, d) — audio only (stub conv frontend)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.models.layers import (
    embed_apply,
    mrope_angles,
    norm_apply,
    norm_init,
    rope_angles,
    sinusoidal_positions,
    unembed_apply,
)
from repro.models.transformer import ForwardOut
from repro.sharding.hints import hint


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, rng: jax.Array) -> dict:
    r = jax.random.split(rng, 8)
    params: Dict[str, Any] = {
        "embed": embed_init_params(cfg, r[0]),
        "final_norm": norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": 0.02 * jax.random.normal(r[1], (cfg.d_model, cfg.padded_vocab))
        }

    if cfg.arch_type in ("dense", "moe", "vlm"):
        params["layers"] = tfm.stacked_block_init(r[2], cfg, "attn", cfg.n_layers)
    elif cfg.arch_type == "nemotron_h":
        params["layers"] = tfm.mixer_stack_init(r[2], cfg)
    elif cfg.arch_type == "ssm":
        params["layers"] = tfm.stacked_block_init(r[2], cfg, "ssm", cfg.n_layers)
    elif cfg.arch_type == "hybrid":
        params["layers"] = tfm.hybrid_init(r[2], cfg)
    elif cfg.arch_type == "audio":
        params["enc_layers"] = tfm.stacked_block_init(r[2], cfg, "enc", cfg.n_encoder_layers)
        params["enc_norm"] = norm_init(cfg)
        params["dec_layers"] = tfm.stacked_block_init(r[3], cfg, "dec", cfg.n_layers)
        params["dec_pos"] = {
            "embedding": 0.02 * jax.random.normal(r[4], (cfg.n_positions, cfg.d_model))
        }
    else:
        raise ValueError(cfg.arch_type)
    return params


def embed_init_params(cfg: ModelConfig, rng) -> dict:
    return {"embedding": 0.02 * jax.random.normal(rng, (cfg.padded_vocab, cfg.d_model))}


# ---------------------------------------------------------------------------
# position streams
# ---------------------------------------------------------------------------


def vlm_positions_3d(cfg: ModelConfig, seq_idx: jax.Array) -> jax.Array:
    """Map flat sequence indices to Qwen2-VL (t, h, w) M-RoPE positions.

    Image tokens occupy seq indices [0, n_patches) on a g×g grid with t=0;
    text tokens at index i ≥ n_patches get all three streams equal to
    ``g + (i − n_patches)`` (text positions continue after the spatial extent).
    """
    g = int(round(cfg.n_patches ** 0.5))
    is_img = seq_idx < cfg.n_patches
    t = jnp.where(is_img, 0, g + (seq_idx - cfg.n_patches))
    h = jnp.where(is_img, seq_idx // g, g + (seq_idx - cfg.n_patches))
    w = jnp.where(is_img, seq_idx % g, g + (seq_idx - cfg.n_patches))
    return jnp.stack([t, h, w], axis=0)  # (3, S)


def _angles_for(cfg: ModelConfig, seq_idx: jax.Array) -> Optional[jax.Array]:
    """Rotary angles for a run of sequence indices. seq_idx: (S,) int32."""
    if cfg.arch_type == "ssm" or cfg.arch_type == "audio" or not cfg.use_rope:
        return None
    if cfg.arch_type == "vlm":
        pos3 = vlm_positions_3d(cfg, seq_idx)
        return mrope_angles(pos3, cfg.hd, cfg.rope_theta, cfg.mrope_sections)
    return rope_angles(seq_idx, cfg.hd, cfg.rope_theta)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: dict,
    batch: Dict[str, jax.Array],
    *,
    mode: str = "train",
    cache: Optional[Any] = None,
    decode_pos: Optional[jax.Array] = None,
    cache_capacity: Optional[int] = None,
    return_logits: bool = True,
) -> ForwardOut:
    dtype = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]
    B, S_tok = tokens.shape

    if cfg.arch_type == "audio":
        return _forward_encdec(
            cfg, params, batch, mode=mode, cache=cache, decode_pos=decode_pos,
            cache_capacity=cache_capacity, return_logits=return_logits,
        )

    x = embed_apply(params["embed"], tokens, dtype)
    if cfg.arch_type == "hybrid":
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)  # gemma-style scaling

    if cfg.arch_type == "vlm" and mode != "decode":
        patches = batch["patch_embeds"].astype(dtype)
        x = jnp.concatenate([patches, x], axis=1)
    # Sequence parallelism (Korthikanti et al., opt-in per config): the
    # residual stream is seq-sharded over the TP axis, shrinking per-layer
    # remat saves by the TP degree.  No-op at decode (S=1).
    if mode != "decode" and cfg.sequence_parallel:
        x = hint(x, "batch", "model", None)
    else:
        x = hint(x, "batch", None, None)
    S = x.shape[1]

    if mode == "decode":
        assert decode_pos is not None
        seq_idx = decode_pos[None].astype(jnp.int32)
    else:
        seq_idx = jnp.arange(S, dtype=jnp.int32)
    angles = _angles_for(cfg, seq_idx)

    window = cfg.sliding_window
    capacity = cache_capacity
    if capacity is not None and window is not None:
        capacity = min(capacity, window)

    if cfg.arch_type == "nemotron_h":
        if mode != "train":
            raise NotImplementedError("nemotron_h runs whole sequences only (no cache)")
        real = _token_mask(batch)
        h, new_cache, aux = tfm.apply_mixer_stack(cfg, params["layers"], x, real), None, 0.0
    elif cfg.arch_type == "hybrid":
        h, new_cache, aux = tfm.apply_hybrid(
            cfg, params["layers"], x, angles=angles, mode=mode, cache=cache,
            decode_pos=decode_pos,
            cache_capacity=min(capacity, cfg.local_window) if capacity else None,
        )
    else:
        kind = "ssm" if cfg.arch_type == "ssm" else "attn"
        h, new_cache, aux = tfm.apply_stack(
            cfg, kind, params["layers"], x, angles=angles, window=window,
            mode=mode, cache=cache, decode_pos=decode_pos, cache_capacity=capacity,
        )

    h = norm_apply(cfg, params["final_norm"], h)
    logits = None
    if return_logits:
        logits = hint(unembed_apply(cfg, params, h), "batch", None, "model")
    return ForwardOut(h, logits, new_cache, aux)


def _forward_encdec(
    cfg: ModelConfig,
    params: dict,
    batch: Dict[str, jax.Array],
    *,
    mode: str,
    cache,
    decode_pos,
    cache_capacity,
    return_logits: bool,
) -> ForwardOut:
    dtype = jnp.dtype(cfg.dtype)
    tokens = batch["tokens"]
    B = tokens.shape[0]

    enc_states = None
    if mode != "decode":
        frames = batch["audio_frames"].astype(dtype)
        F = frames.shape[1]
        enc_x = frames + sinusoidal_positions(F, cfg.d_model).astype(dtype)
        enc_x, _, _ = tfm.apply_stack(cfg, "enc", params["enc_layers"], enc_x, mode="train")
        enc_states = norm_apply(cfg, params["enc_norm"], enc_x)

    x = embed_apply(params["embed"], tokens, dtype)
    if mode == "decode":
        pos_emb = jnp.take(params["dec_pos"]["embedding"], decode_pos[None], axis=0)
    else:
        S = tokens.shape[1]
        pos_emb = params["dec_pos"]["embedding"][:S]
    x = x + pos_emb.astype(dtype)

    h, new_cache, aux = tfm.apply_stack(
        cfg, "dec", params["dec_layers"], x, mode=mode, cache=cache,
        decode_pos=decode_pos, cache_capacity=cache_capacity,
        enc_states=enc_states,
    )
    h = norm_apply(cfg, params["final_norm"], h)
    logits = None
    if return_logits:
        logits = hint(unembed_apply(cfg, params, h), "batch", None, "model")
    return ForwardOut(h, logits, new_cache, aux)


# ---------------------------------------------------------------------------
# caches (also used by launch/shapes.py under jax.eval_shape — no allocation)
# ---------------------------------------------------------------------------


def make_cache(cfg: ModelConfig, batch: int, capacity: int) -> Any:
    dtype = jnp.dtype(cfg.dtype)
    if cfg.sliding_window is not None:
        capacity = min(capacity, cfg.sliding_window)
    if cfg.arch_type in ("dense", "moe", "vlm"):
        return tfm.stacked_attn_cache(cfg, cfg.n_layers, batch, capacity, dtype)
    if cfg.arch_type == "ssm":
        return tfm.stacked_ssm_cache(cfg, cfg.n_layers, batch, dtype)
    if cfg.arch_type == "hybrid":
        return tfm.hybrid_cache(cfg, batch, min(capacity, cfg.local_window), dtype)
    if cfg.arch_type == "audio":
        self_c = tfm.stacked_attn_cache(cfg, cfg.n_layers, batch, capacity, dtype)
        F = cfg.n_audio_frames
        KV, hd = cfg.n_kv_heads, cfg.hd
        cross = (
            jnp.zeros((cfg.n_layers, batch, F, KV, hd), dtype),
            jnp.zeros((cfg.n_layers, batch, F, KV, hd), dtype),
        )
        return {"self": self_c, "cross": cross}
    raise ValueError(cfg.arch_type)


# ---------------------------------------------------------------------------
# losses & features
# ---------------------------------------------------------------------------


def lm_loss(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array]) -> jax.Array:
    """Mean next-token cross-entropy (+ MoE aux). fp32 log-softmax."""
    out = forward(cfg, params, batch, mode="train")
    logits = out.logits.astype(jnp.float32)
    labels = batch["labels"]
    if cfg.arch_type == "vlm":  # logits cover [patches|text]; labels cover text
        logits = logits[:, cfg.n_patches :, :]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - picked)
    return ce + cfg.router_aux_coef * out.aux_loss


def _token_mask(batch: Dict[str, jax.Array]) -> Optional[jax.Array]:
    """A per-token ``mask`` (B, S) of the batch as booleans, or None: a
    per-sample mask (B,), as the statistics step's packed batches carry,
    marks no tokens."""
    mask = batch.get("mask")
    return mask != 0 if mask is not None and mask.ndim == 2 else None


def extract_features(
    cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array]
) -> jax.Array:
    """φ(x): pooled final hidden state, (B, d_feat) fp32 — the FED3R feature map.

    With a per-token ``mask`` (B, S) in the batch (nonzero on real tokens)
    the pooling covers the real positions alone: the mean over them, or the
    last of them; a row without one gives zeros.  Without one every
    position counts (a per-sample ``mask`` (B,), as the statistics step's
    packed batches carry, is not a token mask)."""
    out = forward(cfg, params, batch, mode="train", return_logits=False)
    h = out.hidden.astype(jnp.float32)
    if cfg.arch_type == "vlm":  # pool text positions only
        h = h[:, cfg.n_patches :, :]
    real = _token_mask(batch)
    if real is not None:
        if cfg.feature_pooling == "last":
            pos = jnp.arange(h.shape[1], dtype=jnp.int32)
            last = jnp.max(jnp.where(real, pos, -1), axis=1)
            picked = jnp.take_along_axis(h, jnp.maximum(last, 0)[:, None, None], axis=1)
            return jnp.where(last[:, None] >= 0, picked[:, 0, :], 0.0)
        count = jnp.maximum(jnp.sum(real, axis=1, keepdims=True), 1).astype(jnp.float32)
        return jnp.sum(jnp.where(real[..., None], h, 0.0), axis=1) / count
    if cfg.feature_pooling == "last":
        return h[:, -1, :]
    return jnp.mean(h, axis=1)


# Activation bytes one block of a token forward may take (of a v5e's 16 GB,
# beside the weights and the statistics).
ACTIVATION_BYTES = 2 << 30


def feature_block_rows(cfg: ModelConfig, seq_len: int) -> int:
    """Rows a block of :func:`token_feature_fn` holds: the widest per-token
    activation of a layer (attention's scores over the sequence, an MLP's or
    expert's hidden, Mamba's in-projection and SSD chunk products) at about
    three live copies of four bytes, over ``ACTIVATION_BYTES``."""
    widths = [cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.n_heads * seq_len]
    if cfg.ssm_state:
        widths += [2 * cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state,
                   cfg.ssm_nheads * min(cfg.ssm_chunk, seq_len)]
    return max(1, ACTIVATION_BYTES // (12 * seq_len * max(widths)))


def token_feature_fn(cfg: ModelConfig):
    """``feature_fn(params, tokens) -> (n, d_feat)`` fp32 for
    ``AccumulationEngine``: pooled features of packed token rows, id 0
    being padding (real ids are 1..V−1), so a row of padding gives zeros.
    The rows run through the forward in blocks of at most
    :func:`feature_block_rows`, one after another, so that a client's
    activations fit whatever its rows."""

    def fn(params, tokens: jax.Array) -> jax.Array:
        n, S = tokens.shape
        n_blocks = -(-n // feature_block_rows(cfg, S))
        rows = -(-n // n_blocks)
        padded = jnp.pad(tokens, ((0, n_blocks * rows - n), (0, 0)))

        def block(t):
            return extract_features(cfg, params, {"tokens": t, "mask": t != 0})

        feats = jax.lax.map(block, padded.reshape(n_blocks, rows, S))
        return feats.reshape(n_blocks * rows, -1)[:n]

    return fn


def prefill(
    cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array], cache_capacity: int
) -> Tuple[jax.Array, Any]:
    out = forward(
        cfg, params, batch, mode="prefill", cache_capacity=cache_capacity,
        return_logits=False,  # unembed only the last position (B·V, not B·S·V)
    )
    logits = unembed_apply(cfg, params, out.hidden[:, -1:, :])
    return hint(logits, "batch", None, "model")[:, 0, :], out.cache


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: Any,
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # scalar int32 — absolute position of this token
) -> Tuple[jax.Array, Any]:
    out = forward(
        cfg, params, {"tokens": token}, mode="decode", cache=cache, decode_pos=pos
    )
    return out.logits[:, 0, :], out.cache


# ---------------------------------------------------------------------------
# facade
# ---------------------------------------------------------------------------


class Model:
    """Bound pure-function bundle for one architecture config."""

    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg
        self.init = functools.partial(init_params, cfg)
        self.forward = functools.partial(forward, cfg)
        self.loss = functools.partial(lm_loss, cfg)
        self.extract_features = functools.partial(extract_features, cfg)
        self.prefill = functools.partial(prefill, cfg)
        self.decode_step = functools.partial(decode_step, cfg)
        self.make_cache = functools.partial(make_cache, cfg)

    def param_count(self, params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)

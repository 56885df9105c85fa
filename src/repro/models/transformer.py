"""Backbone stacks: blocks, scan-over-layers, caches, and forward modes.

All six architecture families reduce to three stack shapes:

* **decoder-only homogeneous** (dense / moe / vlm / ssm) — a single
  ``jax.lax.scan`` over stacked layer parameters;
* **hybrid** (RecurrentGemma) — a scan over homogeneous *super-blocks*
  (one (rec, rec, attn) pattern repetition each) plus an unrolled remainder;
* **encoder-decoder** (Whisper) — two scans plus per-layer cross-attention;
* **single-mixer** (Nemotron-H) — ``cfg.mixers``, one mixer a block
  (``x + mixer(norm(x))``: M Mamba-2, E held-share MoE, * attention),
  unrolled; each mixer runs under ``jax.named_scope`` ("mamba", "moe",
  "attention"), so its ops carry the name in their metadata.

Modes: ``train`` (causal, no cache), ``prefill`` (build KV/state caches),
``decode`` (one token, consume+update caches).  Remat (``jax.checkpoint``)
wraps the scan body in train mode.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import attn_apply, attn_init, cross_attn_apply
from repro.models.layers import (
    mlp_apply,
    mlp_init,
    norm_apply,
    norm_init,
)
from repro.sharding.hints import hint


class ForwardOut(NamedTuple):
    hidden: jax.Array  # (B, S, d) post-final-norm hidden states
    logits: Optional[jax.Array]
    cache: Optional[Any]
    aux_loss: jax.Array  # MoE load-balance scalar (0 for non-MoE)


# ---------------------------------------------------------------------------
# block init
# ---------------------------------------------------------------------------


def block_init(rng, cfg: ModelConfig, kind: str) -> dict:
    r = jax.random.split(rng, 8)
    if kind == "attn":
        p = {"norm1": norm_init(cfg), "attn": attn_init(r[0], cfg)}
        if cfg.arch_type == "moe":
            p["moe"] = moe_mod.moe_init(r[1], cfg)
        else:
            p["mlp"] = mlp_init(r[1], cfg)
        if not cfg.parallel_block:
            p["norm2"] = norm_init(cfg)
        return p
    if kind == "ssm":
        return {"norm1": norm_init(cfg), "ssm": ssm_mod.ssm_init(r[0], cfg)}
    if kind == "rec":
        return {
            "norm1": norm_init(cfg),
            "rec": rglru_mod.rglru_init(r[0], cfg),
            "norm2": norm_init(cfg),
            "mlp": mlp_init(r[1], cfg),
        }
    if kind == "enc":
        return {
            "norm1": norm_init(cfg),
            "attn": attn_init(r[0], cfg),
            "norm2": norm_init(cfg),
            "mlp": mlp_init(r[1], cfg),
        }
    if kind == "dec":
        return {
            "norm1": norm_init(cfg),
            "self_attn": attn_init(r[0], cfg),
            "norm2": norm_init(cfg),
            "cross_attn": attn_init(r[1], cfg, cross=True),
            "norm3": norm_init(cfg),
            "mlp": mlp_init(r[2], cfg),
        }
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# block apply
# ---------------------------------------------------------------------------


def _ffn(cfg: ModelConfig, p: dict, h: jax.Array) -> Tuple[jax.Array, jax.Array]:
    if "moe" in p:
        return moe_mod.moe_apply(cfg, p["moe"], h)
    return mlp_apply(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)


def block_apply(
    cfg: ModelConfig,
    kind: str,
    p: dict,
    x: jax.Array,
    *,
    angles: Optional[jax.Array],
    window: Optional[int],
    mode: str,
    cache: Optional[dict] = None,
    decode_pos: Optional[jax.Array] = None,
    cache_capacity: Optional[int] = None,
    enc_states: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Any], jax.Array]:
    """Apply one block. Returns (x', new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    build = mode == "prefill"

    if kind == "attn":
        h = norm_apply(cfg, p["norm1"], x)
        a, new_cache = attn_apply(
            cfg, p["attn"], h, angles=angles, window=window,
            cache=cache, decode_pos=decode_pos,
            build_cache=build, cache_capacity=cache_capacity,
        )
        if cfg.parallel_block:
            f, aux = _ffn(cfg, p, h)
            return x + a + f, new_cache, aux
        x = x + a
        h = norm_apply(cfg, p["norm2"], x)
        f, aux = _ffn(cfg, p, h)
        return x + f, new_cache, aux

    if kind == "ssm":
        h = norm_apply(cfg, p["norm1"], x)
        if mode == "decode":
            y, new_cache = ssm_mod.ssm_decode_step(cfg, p["ssm"], h, cache)
        else:
            y, new_cache = ssm_mod.ssm_apply(cfg, p["ssm"], h, build_cache=build)
        return x + y, new_cache, aux

    if kind == "rec":
        h = norm_apply(cfg, p["norm1"], x)
        if mode == "decode":
            y, new_cache = rglru_mod.rglru_decode_step(cfg, p["rec"], h, cache)
        else:
            y, new_cache = rglru_mod.rglru_apply(cfg, p["rec"], h, build_cache=build)
        x = x + y
        h = norm_apply(cfg, p["norm2"], x)
        return x + mlp_apply(cfg, p["mlp"], h), new_cache, aux

    if kind == "enc":
        h = norm_apply(cfg, p["norm1"], x)
        a, _ = attn_apply(cfg, p["attn"], h, angles=None, bidirectional=True)
        x = x + a
        h = norm_apply(cfg, p["norm2"], x)
        return x + mlp_apply(cfg, p["mlp"], h), None, aux

    if kind == "dec":
        # cache = {"self": attn ring cache, "cross": (k, v)} per layer
        self_cache = cache["self"] if cache is not None else None
        cross_kv = cache["cross"] if cache is not None and mode == "decode" else None
        h = norm_apply(cfg, p["norm1"], x)
        a, new_self = attn_apply(
            cfg, p["self_attn"], h, angles=None,
            cache=self_cache if mode == "decode" else None,
            decode_pos=decode_pos, build_cache=build,
            cache_capacity=cache_capacity,
        )
        x = x + a
        h = norm_apply(cfg, p["norm2"], x)
        c, new_cross = cross_attn_apply(
            cfg, p["cross_attn"], h, enc_kv=cross_kv, enc_states=enc_states
        )
        x = x + c
        h = norm_apply(cfg, p["norm3"], x)
        x = x + mlp_apply(cfg, p["mlp"], h)
        new_cache = None
        if mode in ("prefill", "decode"):
            new_cache = {"self": new_self, "cross": new_cross}
        return x, new_cache, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stacked parameter / cache construction
# ---------------------------------------------------------------------------


def stacked_block_init(rng, cfg: ModelConfig, kind: str, n: int) -> dict:
    keys = jax.random.split(rng, n)
    return jax.vmap(lambda k: block_init(k, cfg, kind))(keys)


def stacked_attn_cache(cfg: ModelConfig, n: int, batch: int, cap: int, dtype) -> dict:
    one = attn_mod.init_cache(cfg, batch, cap, dtype)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)


def stacked_ssm_cache(cfg: ModelConfig, n: int, batch: int, dtype) -> dict:
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    conv_ch = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return {
        "state": jnp.zeros((n, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((n, batch, cfg.ssm_conv - 1, conv_ch), dtype),
    }


def stacked_rec_cache(cfg: ModelConfig, n: int, batch: int, dtype) -> dict:
    return {
        "h": jnp.zeros((n, batch, cfg.lru_width), jnp.float32),
        "conv": jnp.zeros((n, batch, 3, cfg.lru_width), dtype),
    }


# ---------------------------------------------------------------------------
# homogeneous stack application (dense / moe / vlm / ssm, and whisper stacks)
# ---------------------------------------------------------------------------


def apply_stack(
    cfg: ModelConfig,
    kind: str,
    stacked: dict,
    x: jax.Array,
    *,
    angles=None,
    window=None,
    mode="train",
    cache=None,
    decode_pos=None,
    cache_capacity=None,
    enc_states=None,
):
    """Scan one homogeneous stack. Returns (x, stacked_new_cache, aux)."""

    def body(carry, xs):
        h, aux = carry
        if cache is not None:
            p, c = xs
        else:
            p, c = xs, None
        h, new_c, a = block_apply(
            cfg, kind, p, h, angles=angles, window=window, mode=mode,
            cache=c, decode_pos=decode_pos, cache_capacity=cache_capacity,
            enc_states=enc_states,
        )
        if mode != "decode" and cfg.sequence_parallel:
            # keep the residual stream (the per-layer remat save) seq-sharded
            h = hint(h, "batch", "model", None)
        return (h, aux + a), new_c

    bs = cfg.remat_block_size
    use_block_remat = (
        cfg.remat and mode == "train" and cfg.scan_layers and bs > 1
        and cache is None
    )

    if cfg.remat and mode == "train" and not use_block_remat:
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (stacked, cache) if cache is not None else stacked
    if use_block_remat:
        n = jax.tree.leaves(stacked)[0].shape[0]
        assert n % bs == 0, (n, bs)
        blocked = jax.tree.map(
            lambda a: a.reshape((n // bs, bs) + a.shape[1:]), stacked
        )

        def block_body(carry, ps):
            return jax.lax.scan(body, carry, ps)[0], None

        block_body = jax.checkpoint(block_body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(
            block_body, (x, jnp.zeros((), jnp.float32)), blocked
        )
        return x, None, aux
    if cfg.scan_layers:
        (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    else:
        n = jax.tree.leaves(stacked)[0].shape[0]
        aux = jnp.zeros((), jnp.float32)
        caches_out = []
        for i in range(n):
            xs_i = jax.tree.map(lambda a: a[i], xs)
            (x, aux), c = body((x, aux), xs_i)
            caches_out.append(c)
        new_caches = (
            jax.tree.map(lambda *ls: jnp.stack(ls), *caches_out)
            if caches_out and caches_out[0] is not None
            else None
        )
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# hybrid stack (RecurrentGemma): scan over super-blocks + unrolled remainder
# ---------------------------------------------------------------------------


def hybrid_init(rng, cfg: ModelConfig) -> dict:
    pat = cfg.block_pattern
    nb = cfg.n_superblocks
    rem = cfg.pattern_for(cfg.n_layers)[nb * len(pat) :]
    r = jax.random.split(rng, len(pat) + len(rem) + 1)
    kind_of = {"rec": "rec", "attn": "attn"}
    super_p = {
        f"b{i}_{k}": stacked_block_init(r[i], cfg, kind_of[k], nb)
        for i, k in enumerate(pat)
    }
    rem_p = {
        f"rem{i}_{k}": block_init(r[len(pat) + i], cfg, kind_of[k])
        for i, k in enumerate(rem)
    }
    return {"super": super_p, "rem": rem_p}


def hybrid_cache(cfg: ModelConfig, batch: int, cap: int, dtype) -> dict:
    pat = cfg.block_pattern
    nb = cfg.n_superblocks
    rem = cfg.pattern_for(cfg.n_layers)[nb * len(pat) :]

    def one(kind, n=None):
        if kind == "rec":
            return (
                stacked_rec_cache(cfg, n, batch, dtype)
                if n
                else jax.tree.map(lambda a: a[0], stacked_rec_cache(cfg, 1, batch, dtype))
            )
        return (
            stacked_attn_cache(cfg, n, batch, cap, dtype)
            if n
            else jax.tree.map(lambda a: a[0], stacked_attn_cache(cfg, 1, batch, cap, dtype))
        )

    return {
        "super": {f"b{i}_{k}": one(k, nb) for i, k in enumerate(pat)},
        "rem": {f"rem{i}_{k}": one(k) for i, k in enumerate(rem)},
    }


def apply_hybrid(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    *,
    angles,
    mode,
    cache=None,
    decode_pos=None,
    cache_capacity=None,
):
    pat = cfg.block_pattern
    kind_of = {"rec": "rec", "attn": "attn"}
    aux_total = jnp.zeros((), jnp.float32)

    def superblock(carry, xs):
        h, aux = carry
        new_caches = {}
        for i, k in enumerate(pat):
            key = f"b{i}_{k}"
            p = xs[0][key] if cache is not None else xs[key]
            c = xs[1][key] if cache is not None else None
            h, nc, a = block_apply(
                cfg, kind_of[k], p, h, angles=angles,
                window=cfg.local_window if k == "attn" else None,
                mode=mode, cache=c, decode_pos=decode_pos,
                cache_capacity=cache_capacity,
            )
            new_caches[key] = nc
            aux = aux + a
        if mode == "train":
            new_caches = None
        return (h, aux), new_caches

    body = superblock
    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (params["super"], cache["super"]) if cache is not None else params["super"]
    if cfg.scan_layers:
        (x, aux_total), new_super = jax.lax.scan(body, (x, aux_total), xs)
    else:
        nb = cfg.n_superblocks
        outs = []
        for i in range(nb):
            xs_i = jax.tree.map(lambda a: a[i], xs)
            (x, aux_total), c_i = body((x, aux_total), xs_i)
            outs.append(c_i)
        new_super = (
            jax.tree.map(lambda *ls: jnp.stack(ls), *outs)
            if outs and outs[0] is not None
            else None
        )

    new_rem = {}
    nb = cfg.n_superblocks
    rem = cfg.pattern_for(cfg.n_layers)[nb * len(pat) :]
    for i, k in enumerate(rem):
        key = f"rem{i}_{k}"
        c = cache["rem"][key] if cache is not None else None
        x, nc, a = block_apply(
            cfg, kind_of[k], params["rem"][key], x, angles=angles,
            window=cfg.local_window if k == "attn" else None,
            mode=mode, cache=c, decode_pos=decode_pos,
            cache_capacity=cache_capacity,
        )
        new_rem[key] = nc
        aux_total = aux_total + a

    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"super": new_super, "rem": new_rem}
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# single-mixer stack (Nemotron-H): one mixer a block, unrolled
# ---------------------------------------------------------------------------

MIXER_SCOPE = {"M": "mamba", "E": "moe", "*": "attention"}


def mixer_block_init(rng, cfg: ModelConfig, kind: str) -> dict:
    if kind == "M":
        mixer = ssm_mod.ssm_init(rng, cfg)
    elif kind == "E":
        mixer = moe_mod.held_moe_init(rng, cfg)
    else:
        mixer = attn_init(rng, cfg)
    return {"norm": norm_init(cfg), MIXER_SCOPE[kind]: mixer}


def mixer_stack_init(rng, cfg: ModelConfig) -> list:
    keys = jax.random.split(rng, cfg.n_layers)
    return [mixer_block_init(r, cfg, kind) for r, kind in zip(keys, cfg.mixers)]


def apply_mixer_stack(cfg: ModelConfig, layers: list, x: jax.Array,
                      real: Optional[jax.Array] = None) -> jax.Array:
    """Causal forward of the single-mixer stack over whole sequences;
    ``real`` (B, S) marks the real tokens for the MoE's routed experts."""
    for kind, p in zip(cfg.mixers, layers):
        h = norm_apply(cfg, p["norm"], x)
        name = MIXER_SCOPE[kind]
        with jax.named_scope(name):
            if kind == "M":
                y, _ = ssm_mod.ssm_apply(cfg, p[name], h)
            elif kind == "E":
                y, _ = moe_mod.held_moe_apply(cfg, p[name], h, real)
            else:
                y, _ = attn_apply(cfg, p[name], h)
        x = x + y
    return x

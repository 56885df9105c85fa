"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, shared experts.

Faithful to the two assigned MoE families:
  * DeepSeekMoE 16B  — 64 fine-grained routed experts, top-6, 2 shared experts
    (arXiv:2401.06066).
  * Llama-4 Scout    — 16 experts, top-1, 1 shared expert.

Implementation: Gshard-style capacity dispatch via scatter-add into an
``(E, C, d)`` expert buffer (the token-permutation formulation — memory
O(T·k·capacity_factor·d), never O(T·E)).  On the production mesh the expert
dim E is sharded over the "model" axis (expert parallelism); GSPMD lowers the
dispatch/combine scatters into all-to-all-style collectives.

Shared experts are algebraically fused into a single wide gated MLP: the sum
of S swiglu experts equals one swiglu MLP with the gate/up matrices
concatenated on the hidden axis and the down matrices stacked — exact, not an
approximation.

Held-share layer (``held_moe_*``; Nemotron-H, ``cfg.experts_held``): under
expert parallelism a chip holds ``count`` of the router's ``n_experts``
experts, from ``offset``.  Every token is routed over all of them and no
pair is dropped; the pairs that go to a held expert are sorted by expert
and run as a grouped product, one ``HELD_TILE``-row tile at a time in a
loop whose trip count is the tiles those pairs fill, so the held experts'
work scales with the held pairs (padded to tiles), not with T·k.  Each
tile's rows are gathered, weighted and scatter-added back.  The absent
experts' part is left out: it is the other chips' share of the result.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init, relu2
from repro.sharding.hints import hint, mesh_axis_size


def moe_init(rng, cfg: ModelConfig) -> dict:
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    r = jax.random.split(rng, 5)
    p = {
        "router": dense_init(r[0], (d, E)),
        "w_gate": dense_init(r[1], (E, d, f), in_axis=1),
        "w_up": dense_init(r[2], (E, d, f), in_axis=1),
        "w_down": dense_init(r[3], (E, f, d), in_axis=1),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = mlp_init(r[4], cfg, d_ff=cfg.n_shared_experts * cfg.d_expert)
    return p


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = int(math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, ((cap + 7) // 8) * 8)  # pad to a lane-friendly multiple


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_load_balance_loss)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d)

    # ---- routing (fp32 for numerics) -------------------------------------
    logits = (xf @ p["router"].astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, k)  # (T, k)

    # ---- load-balance auxiliary loss (Switch/Gshard form) ------------------
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    dispatch_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=1), axis=0
    ) / k
    aux = E * jnp.sum(me * dispatch_frac)

    # ---- capacity positions (GROUP-LOCAL, Gshard-style) ---------------------
    # Positions are computed with a cumsum *within* data-shard-aligned token
    # groups, never across shards: a cross-shard cumsum forces the SPMD
    # partitioner into a pathological dense lowering (measured: 95% of all
    # HLO FLOPs at prefill_32k — see EXPERIMENTS.md §Perf H1).  Each group
    # owns C/G capacity slots per expert; dropping becomes group-local,
    # which is the standard Gshard/Switch semantics.
    # Groups align with the INNERMOST data axis only — never the DCN "pod"
    # axis: a (pod,data)-wide group sharding makes the partitioner emit
    # cross-pod reshards (measured 47.6 GB/chip at 2×16×16; pinned to
    # "data": 9.8 GB — EXPERIMENTS.md §Perf H1/known-items).
    G = max(mesh_axis_size("data"), 1)
    while T % G != 0:  # tiny batches in tests: fall back to fewer groups
        G //= 2
    G = max(G, 1)
    Tg = T // G
    C = _capacity(cfg, T)
    Cg = max(8, -(-C // G))

    idx_g = top_idx.reshape(G, Tg * k)  # (G, Tg*k) routing per group
    onehot = jax.nn.one_hot(idx_g, E, dtype=jnp.int32)  # (G, Tg*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot  # group-local prefix sums
    pos_g = jnp.sum(pos_in_e * onehot, axis=-1)  # (G, Tg*k)
    keep_g = (pos_g < Cg).astype(x.dtype)
    pos_g = jnp.minimum(pos_g, Cg - 1)

    # ---- dispatch: BATCHED scatter over the group dim ------------------------
    # The scatter is vmapped over G with G sharded on the data axes, so its
    # locality is structural (each shard scatters only its own group) — GSPMD
    # cannot prove locality of value-dependent flat indices, and the unbatched
    # formulations lower to a full-buffer all-reduce (15.6 GB/layer wire) or
    # dense masked updates (95% of HLO FLOPs).  See EXPERIMENTS.md §Perf H1.
    dt = x.dtype
    x_g = hint(jnp.repeat(xf, k, axis=0).reshape(G, Tg * k, d), "batch", None, None)

    def scatter_group(xg, ig, pg, kg):
        bufg = jnp.zeros((E, Cg, d), dt)
        return bufg.at[ig, pg].add(xg * kg[:, None])

    buf = jax.vmap(scatter_group)(x_g, idx_g, pos_g, keep_g)  # (G, E, Cg, d)
    buf = hint(buf, "batch", "model", None, None)

    # ---- expert FFN (2-D parallel: groups over data × experts over model) ---
    g = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt)))
    u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    h = hint(
        jnp.einsum("gecf,efd->gecd", g * u, p["w_down"].astype(dt)),
        "batch", "model", None, None,
    )

    # ---- combine: batched gather back to tokens ------------------------------
    y_rep = jax.vmap(lambda hg, ig, pg: hg[ig, pg])(h, idx_g, pos_g)
    y_rep = hint(y_rep * keep_g[..., None], "batch", None, None)  # (G, Tg*k, d)
    w = top_p.reshape(G, Tg * k).astype(dt)[..., None]
    y = jnp.sum((y_rep * w).reshape(T, k, d), axis=1)

    if "shared" in p:
        y = y + mlp_apply(cfg.replace(mlp_type="swiglu"), p["shared"], xf)

    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# held-share layer: no capacity, a share of the experts (Nemotron-H)
# ---------------------------------------------------------------------------

HELD_TILE = 256  # rows of one held expert's product in the grouped loop


def held_moe_init(rng, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_expert
    n = cfg.experts_held[1]
    r = jax.random.split(rng, 4)
    return {
        "router": dense_init(r[0], (d, cfg.n_experts)),
        "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_up": dense_init(r[1], (n, d, f), in_axis=1),
        "w_down": dense_init(r[2], (n, f, d), in_axis=1),
        "shared": mlp_init(r[3], cfg, d_ff=cfg.d_ff),
    }


def route_sigmoid(cfg: ModelConfig, p: dict, xf: jax.Array):
    """(T, d) -> expert ids (T, k) and weights (T, k) fp32: sigmoid scores
    at fp32 "highest", top-k on score + correction bias, the chosen scores
    normalized to sum to one and scaled by ``cfg.routed_scaling``."""
    logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32), cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling


def held_experts(cfg: ModelConfig, p: dict, xf: jax.Array, idx: jax.Array,
                 w: jax.Array, real: Optional[jax.Array] = None) -> jax.Array:
    """Σ over each token's routed pairs to a held expert of weight ·
    down(relu(up x)²), (T, d) fp32; zero for the tokens ``real`` (T,) marks
    as padding.  The pairs are sorted by held expert; a loop runs one tile
    of one expert's pairs per step, as many steps as the held pairs fill
    tiles."""
    T, d = xf.shape
    offset, n = cfg.experts_held
    k = idx.shape[1]
    local = idx.reshape(-1) - offset
    held = (local >= 0) & (local < n)
    if real is not None:
        held &= jnp.repeat(real, k)
    key = jnp.where(held, local, n)  # n: not held here, or padding
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=n + 1)[:n]
    starts = jnp.cumsum(counts) - counts
    tiles = (counts + HELD_TILE - 1) // HELD_TILE
    tile_end = jnp.cumsum(tiles)
    tail = jnp.zeros((HELD_TILE,), jnp.int32)
    token = jnp.concatenate([(order // k).astype(jnp.int32), tail])
    gate = jnp.concatenate([w.reshape(-1)[order], tail.astype(w.dtype)])
    lane = jnp.arange(HELD_TILE)

    def tile(j, y):
        e = jnp.searchsorted(tile_end, j, side="right")
        first = (j - (tile_end[e] - tiles[e])) * HELD_TILE  # e's pairs before it
        rows = jax.lax.dynamic_slice(token, (starts[e] + first,), (HELD_TILE,))
        g = jax.lax.dynamic_slice(gate, (starts[e] + first,), (HELD_TILE,))
        rows = jnp.where(first + lane < counts[e], rows, T)  # T: no row
        xt = xf.at[rows].get(mode="fill", fill_value=0)
        h = relu2(xt @ p["w_up"][e].astype(xf.dtype))
        out = jnp.dot(h, p["w_down"][e].astype(xf.dtype),
                      preferred_element_type=jnp.float32)
        return y.at[rows].add(out * g[:, None], mode="drop")

    return jax.lax.fori_loop(0, tile_end[-1], tile, jnp.zeros((T, d), jnp.float32))


def held_moe_apply(cfg: ModelConfig, p: dict, x: jax.Array,
                   real: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (this chip's share of the MoE output, 0): its held
    experts' part plus the shared expert.  ``real`` (B, S) marks the real
    tokens: padding positions (past a causal row's end, where nothing real
    reads them) take no routed expert, so they neither cost held-expert
    work nor pile onto the experts their one padding id routes to.  No
    load-balance loss: the sigmoid router balances through its correction
    bias."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    idx, w = route_sigmoid(cfg, p, xf)
    flat = None if real is None else real.reshape(B * S)
    y = held_experts(cfg, p, xf, idx, w, flat) + mlp_apply(cfg, p["shared"], xf)
    return y.astype(x.dtype).reshape(B, S, d), jnp.zeros((), jnp.float32)

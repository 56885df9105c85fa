"""Public jit'd wrappers for the Pallas kernels.

Each wrapper dispatches: TPU → compiled Pallas kernel; anything else →
interpret mode (the kernel body executed on CPU — used for validation in
this container) — the pure-jnp oracles live in ref.py.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels.chol_update import batched_chol_gram_pallas, chol_gram_pallas
from repro.kernels.fed3r_stats import fed3r_stats_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant import dequant_acc_pallas, quantize_tiles_pallas
from repro.kernels.rff import rff_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fed3r_stats(
    Z: jax.Array, Y: jax.Array, rows: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """Fused FED3R statistics (A, b) = (ZᵀZ, ZᵀY) over the first ``rows``
    rows (all by default; the rest must be zero)."""
    return fed3r_stats_pallas(Z, Y, rows, interpret=_interpret())


def chol_gram(
    L: jax.Array,
    Z: jax.Array,
    Y: jax.Array,
    blocks: Optional[jax.Array] = None,
    count: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused rank-n Cholesky-Gram update (G, B) = (L Lᵀ + ZᵀZ, ZᵀY) over
    the live row blocks (``live_row_blocks`` of the mask; all by default)."""
    return chol_gram_pallas(L, Z, Y, blocks, count, interpret=_interpret())


def batched_chol_gram(
    L: jax.Array, Z: jax.Array, Y: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Grid-over-heads Gram updates (G_k, B_k) = (L Lᵀ + Z_kᵀZ_k, Z_kᵀY_k)."""
    return batched_chol_gram_pallas(L, Z, Y, interpret=_interpret())


def quantize_tiles(x: jax.Array, *, tile: int = 128) -> Tuple[jax.Array, jax.Array]:
    """Per-tile absmax int8 quantization (q, scales) of the wire payload."""
    return quantize_tiles_pallas(x, tile=tile, interpret=_interpret())


def dequant_accumulate(
    acc: jax.Array, q: jax.Array, scales: jax.Array, *, tile: int = 128
) -> jax.Array:
    """Fused dequantize-accumulate acc + q·s (no dense HBM intermediate)."""
    return dequant_acc_pallas(acc, q, scales, tile=tile, interpret=_interpret())


def rff_transform(Z: jax.Array, omega: jax.Array, beta: jax.Array) -> jax.Array:
    """Fused random-features map √(2/D)·cos(ZΩ + β)."""
    return rff_pallas(Z, omega, beta, interpret=_interpret())


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True, window: Optional[int] = None,
) -> jax.Array:
    """Online-softmax GQA attention (prefill)."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, interpret=_interpret()
    )

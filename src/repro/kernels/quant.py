"""Pallas kernels: tile-wise int8 (de)quantization of the statistics uplink.

The compressed-uplink layer (:mod:`repro.federated.compress`) ships every
(A_k, b_k) statistics upload as symmetric per-tile absmax int8 instead of
dense fp32.  Two kernels cover the hot path on both ends of the wire:

* :func:`quantize_tiles_pallas` — the CLIENT side: one grid pass over
  (tile × tile) blocks; each block computes its own absmax scale
  s = max|x| / 127 in VMEM and writes the packed int8 payload plus the
  (M/tile, N/tile) fp32 scale grid.  Per-TILE scales (not per-tensor) keep
  the quantization error local: one hot diagonal block of A_k does not
  wash out the resolution of every other block.
* :func:`dequant_acc_pallas` — the AGGREGATOR side: the fused
  dequantize-accumulate acc ← acc + q·s.  Each grid step loads the fp32
  accumulator tile, the int8 payload tile, and its scalar scale, and
  writes the updated accumulator directly — the dense fp32 dequantized
  intermediate is never materialized in HBM (contrast the XLA reference,
  which expands q·s to a full (d, d) array before the add).  This is the
  merge-side primitive of every compressed engine fold: the server's A
  accumulator advances one compressed client payload at a time.

Rounding is round-half-to-even (``jnp.round``), matching the jnp oracles
in :mod:`repro.kernels.ref` BITWISE — kernel-vs-oracle parity tests compare
the int8 payloads exactly, not approximately.  All-zero tiles take scale 1
so q = 0 and dequantization is exact.  Shapes pad up to tile multiples
(zero padding quantizes to zero exactly); fp8 wire formats share the same
tiling algebra through the pure-jnp path in ``repro.federated.compress``
(the MXU has no fp8 VPU story worth a separate kernel body — the payload
byte count is identical to int8).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # absmax granularity: one fp32 scale per (TILE, TILE) block
INT8_QMAX = 127.0  # symmetric int8 range (−127 … 127; −128 unused)
# The per-tile scale grid lives whole in SMEM and each grid step indexes
# its own scalar: Mosaic refuses a (1, 1) VMEM block of the (Mt, Nt) grid,
# whose last two block dims must be multiples of (8, 128) or the full dims.
_SCALES_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _quantize_kernel(x_ref, q_ref, s_ref):
    """One (i, j) tile: absmax scale + packed int8 payload.

    x_ref: (T, T) fp32 input tile
    q_ref: (T, T) int8 quantized output tile
    s_ref: (Mt, Nt) fp32 scale grid, whole in SMEM; this step writes [i, j]
    """
    x = x_ref[...]
    absmax = jnp.max(jnp.abs(x))
    scale = jnp.where(absmax > 0.0, absmax / INT8_QMAX, 1.0)
    s_ref[pl.program_id(0), pl.program_id(1)] = scale
    q = jnp.clip(jnp.round(x / scale), -INT8_QMAX, INT8_QMAX)
    q_ref[...] = q.astype(jnp.int8)


def _dequant_acc_kernel(acc_ref, q_ref, s_ref, out_ref):
    """One (i, j) tile of the fused accumulate out = acc + q·s.

    acc_ref: (T, T) fp32 accumulator tile
    q_ref:   (T, T) int8 payload tile
    s_ref:   (Mt, Nt) fp32 scale grid, whole in SMEM; this step reads [i, j]
    out_ref: (T, T) fp32 updated accumulator tile
    """
    scale = s_ref[pl.program_id(0), pl.program_id(1)]
    out_ref[...] = acc_ref[...] + q_ref[...].astype(jnp.float32) * scale


def _pad_to(a: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-a.shape[0]) % m0
    p1 = (-a.shape[1]) % m1
    return jnp.pad(a, ((0, p0), (0, p1))) if (p0 or p1) else a


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def quantize_tiles_pallas(
    x: jax.Array, *, tile: int = TILE, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Per-tile absmax int8 quantization of x (M, N).

    Returns ``(q, scales)``: q (M, N) int8 and scales
    (⌈M/tile⌉, ⌈N/tile⌉) fp32 — together the wire payload (1 byte/element
    + one fp32 per tile).  Zero padding up to tile multiples quantizes to
    zero exactly and never moves a tile's absmax.
    """
    M, N = x.shape
    xp = _pad_to(x.astype(jnp.float32), tile, tile)
    Mt, Nt = xp.shape[0] // tile, xp.shape[1] // tile
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=(Mt, Nt),
        in_specs=[pl.BlockSpec((tile, tile), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
            _SCALES_SPEC,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, jnp.int8),
            jax.ShapeDtypeStruct((Mt, Nt), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return q[:M, :N], s


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def dequant_acc_pallas(
    acc: jax.Array,
    q: jax.Array,
    scales: jax.Array,
    *,
    tile: int = TILE,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequantize-accumulate acc + q·s (M, N) fp32.

    The aggregator-side merge primitive: the int8 payload lands directly
    in the fp32 accumulator, one tile at a time — no dense dequantized
    intermediate in HBM.  ``scales`` is the (⌈M/tile⌉, ⌈N/tile⌉) grid from
    :func:`quantize_tiles_pallas`.
    """
    M, N = acc.shape
    accp = _pad_to(acc.astype(jnp.float32), tile, tile)
    qp = _pad_to(q, tile, tile)
    out = pl.pallas_call(
        _dequant_acc_kernel,
        grid=(accp.shape[0] // tile, accp.shape[1] // tile),
        in_specs=[
            pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
            pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
            _SCALES_SPEC,
        ],
        out_specs=pl.BlockSpec((tile, tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(accp.shape, jnp.float32),
        interpret=interpret,
    )(accp, qp, scales)
    return out[:M, :N]

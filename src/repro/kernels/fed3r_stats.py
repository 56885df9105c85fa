"""Pallas kernel: fused FED3R statistics A = ZᵀZ, b = ZᵀY.

The paper's client-side hot spot (App. E charges ½·n·d(d+1) + n·d·C FLOPs
for it).  Key insight for the fused form: stacking the one-hot targets next
to the features, W = [Z | Y] ∈ R^{n×(d+C)}, turns both statistics into ONE
blocked GEMM  M = Zᵀ W, with A = M[:, :d] and b = M[:, d:].

TPU adaptation (vs. the paper's cuBLAS call on A100):
  * grid (d/bm, (d+C)/bn, n/BK): each (i, j) output tile stays resident
    in VMEM across the k-sweep and accumulates in fp32 — A is up to
    12288² fp32 (576 MB), so tiles must stream.
  * The work follows the client's real rows.  A client's block is padded
    to the round's capacity, often five to forty times its samples; the
    caller passes the live row extent (last real row + 1) as a
    scalar-prefetch operand, and the row blocks past it are skipped: their
    index maps clamp to the last live block (no DMA) and the MXU update
    sits under ``pl.when``.  BK is one fixed short row block, so a client's
    statistics are the same bits however far it was padded.
  * Output tiles grow with the short row block (up to TILE_M × TILE_N) so
    that each step's MXU work covers the step's fixed cost and its two
    block reads.  fp32 operands contract at fp32 (``Precision.HIGHEST``,
    several MXU passes): Mosaic's default contracts them in ONE bf16 pass,
    which rounds every feature to 8 mantissa bits — the engines hand this
    kernel fp32 designs and solve a ridge system on the result.  bf16
    operands take the native single pass.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BK = 128  # samples per accumulation step: the MXU's contraction depth (a
# shorter block costs an output tile the same MXU passes, in more grid steps)
TILE_M = 256  # most rows of an output tile (d dim)
TILE_N = 4096  # most cols of an output tile (d+C dim)
LANE = 128


def contract_rows(a: jax.Array, b: jax.Array) -> jax.Array:
    """aᵀb over the shared leading (row) dim, into fp32 on the MXU.

    fp32 operands contract at fp32; bf16 operands in the native pass."""
    fp32 = jnp.float32 in (a.dtype, b.dtype)
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if fp32 else None,
        preferred_element_type=jnp.float32,
    )


def fit_tile(width: int, cap: int) -> Tuple[int, int]:
    """(tile, padded width): the fewest equal 128-multiple tiles of at most
    ``cap`` that cover ``width``."""
    lanes = -(-width // LANE)
    n_tiles = -(-lanes * LANE // cap)
    tile = -(-lanes // n_tiles) * LANE
    return tile, n_tiles * tile


def _live_blocks(rows_ref) -> jax.Array:
    return (rows_ref[0] + BK - 1) // BK


def _stats_kernel(rows_ref, z_ref, w_ref, out_ref):
    """One (i, j) output tile; grid axis 2 sweeps the n (sample) dim.

    rows_ref: (1,) int32 live row extent (scalar prefetch, SMEM)
    z_ref:   (BK, bm) block of Z        (samples × features)
    w_ref:   (BK, bn) block of W=[Z|Y]  (samples × features+classes)
    out_ref: (bm, bn) fp32 output tile, the accumulator across k
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k < _live_blocks(rows_ref))
    def _update():
        out_ref[...] += contract_rows(z_ref[...], w_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fed3r_stats_pallas(
    Z: jax.Array,
    Y: jax.Array,
    rows: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Compute (A, b) = (ZᵀZ, ZᵀY). Z: (n, d); Y: (n, C). fp32 outputs.

    ``rows`` (int32 scalar, default n) is the live row extent: rows at and
    past it must be zero (a masked design's padding is), and the row
    blocks that hold only such rows are skipped.  Shapes are padded up to
    tile multiples (zero rows/cols are exact: they contribute nothing to
    either statistic).
    """
    n, d = Z.shape
    C = Y.shape[1]
    bm, dp = fit_tile(d, TILE_M)
    bn, ep = fit_tile(d + C, TILE_N)
    np_ = max(-(-n // BK), 1) * BK
    Zp = jnp.pad(Z, ((0, np_ - n), (0, dp - d)))
    Wp = jnp.pad(
        jnp.concatenate([Z, Y.astype(Z.dtype)], axis=1),  # (n, d+C)
        ((0, np_ - n), (0, ep - d - C)),
    )
    rows = jnp.full((1,), n if rows is None else rows, jnp.int32)

    def live(k, rows_ref):  # dead blocks keep the last live one: no DMA
        return jnp.minimum(k, jnp.maximum(_live_blocks(rows_ref) - 1, 0))

    out = pl.pallas_call(
        _stats_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(dp // bm, ep // bn, np_ // BK),
            in_specs=[
                pl.BlockSpec((BK, bm), lambda i, j, k, r: (live(k, r), i)),
                pl.BlockSpec((BK, bn), lambda i, j, k, r: (live(k, r), j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, r: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((dp, ep), jnp.float32),
        interpret=interpret,
    )(rows, Zp, Wp)

    M = out[:d, :]
    return M[:, :d], M[:, d : d + C]

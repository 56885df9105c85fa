"""Pallas kernels: fused rank-n Cholesky-Gram updates G = L Lᵀ + ZᵀZ, B = ZᵀY.

The streaming arrival engine's hot spot (repro.federated.streaming_engine):
every arrival wave refactors the carried Cholesky factor of A + λI through
the Gram reconstruction G = L Lᵀ + ZᵀZ and accumulates the class sums
B = ZᵀY.  Both right-hand contributions are contractions over a "row"
dimension — d rows of Lᵀ for the reconstruction, n sample rows of Z and Y
for the rank-n arrival update — so the whole update is ONE blocked GEMM
whose k-sweep walks the Lᵀ rows first and the sample rows second, into
output tiles resident in VMEM.  No (d+n, d+C) stacked operand is ever
materialized in HBM (contrast the XLA reference, which concatenates).

The single-head kernel (:func:`chol_gram_pallas`) multiplies only blocks
that can hold a nonzero:

  * grid (d/bm, kL + kZ): an output tile is bm ≤ TILE_M rows of G and of
    B at their full widths (d and C), the two outputs of one pallas_call,
    so Z and Y are read as they are, never stacked;
  * the factor sweep contracts Lᵀ row blocks into G's columns alone, and
    only the blocks at or above the tile's last row: Lᵀ is upper-
    triangular, so a row tile needs no factor row past its own;
  * the sample sweep walks the live BK-row blocks of the wave (those that
    hold a real row), passed as a scalar-prefetch operand in ascending
    order: a wave packs every client to its largest one, and the blocks
    of padding alone are skipped.  Steps past the live count sit under
    ``pl.when`` and their index maps hold the last block (no DMA).

The BATCHED variant (:func:`batched_chol_gram_pallas`) is the
personalization engine's hot spot (repro.federated.personalization): one
grid-over-heads pallas_call computes K per-tenant Gram updates
G_k = L Lᵀ + Z_kᵀZ_k, B_k = Z_kᵀY_k against ONE shared global factor L.
Grid (K, d/BM, (d+C)/BN, kL + kZ): phase one (k < kL) contracts
Lᵀ·[Lᵀ | 0], phase two Z_kᵀ·[Z_k | Y_k]; each phase has its own block size
(BKL for the factor sweep, BKZ for the sample sweep) and clamped index
maps keep the off-phase operand block loads in range.  The head index is
the leading (outermost) grid axis, so the k-sweep of each head runs to
completion in its private VMEM accumulator before the grid advances to
the next head; the shared Lᵀ blocks are re-walked per head (they
index-map independently of the head axis).  Per-head scaling α_k Z_kᵀZ_k
is folded in by pre-scaling Z_k ← √α_k·Z_k outside the kernel (both Gram
contributions are bilinear in Z), keeping the kernel body scale-free.

MXU-shaped tiles with fp32 accumulation and fp32 contraction of fp32
operands, as in kernels/fed3r_stats.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fed3r_stats import BK, TILE_M, contract_rows, fit_tile

VMEM_DEFAULT = 16 * 2**20  # Mosaic's scoped-VMEM limit unless raised

# tiles of the batched kernel
BM = 128  # rows of the output tile (d dim)
BN = 128  # cols of the output tile (d+C dim)
BKL = 128  # Lᵀ rows per accumulation step (factor sweep, ≤ d typically)
BKZ = 512  # samples per accumulation step (arrival sweep)


def live_row_blocks(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(blocks, count): the ascending indices of the BK-row blocks of a
    masked design that hold a real row (``mask > 0``), padded with zeros
    to one entry a block, and how many there are."""
    n = mask.shape[0]
    n_blocks = max(-(-n // BK), 1)
    live = jnp.pad(mask > 0, (0, n_blocks * BK - n)).reshape(n_blocks, BK).any(1)
    (blocks,) = jnp.nonzero(live, size=n_blocks, fill_value=0)
    return blocks.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32).reshape(1)


def _factor_blocks(i, bm: int):
    """Lᵀ row blocks that reach output row tile ``i``: those at or above
    its last row (Lᵀ is upper-triangular)."""
    return (i + 1) * (bm // BK)


def _chol_gram_kernel(blocks_ref, count_ref, lt_ref, ltw_ref, z_ref, zw_ref,
                      y_ref, g_ref, b_ref, *, bm: int):
    """One row tile i; grid axis 1 sweeps Lᵀ rows, then the live sample rows.

    blocks_ref: (n_blocks,) int32 live row blocks, ascending (SMEM)
    count_ref: (1,) int32 how many of them are live (SMEM)
    lt_ref:  (BK, bm) block of Lᵀ  (factor rows × the tile's features)
    ltw_ref: (BK, dp) block of Lᵀ  (factor rows × features)
    z_ref:   (BK, bm) block of Z   (samples × the tile's features)
    zw_ref:  (BK, dp) block of Z   (samples × features)
    y_ref:   (BK, C)  block of Y   (samples × classes)
    g_ref:   (bm, dp) fp32 G tile, the accumulator across k
    b_ref:   (bm, C)  fp32 B tile, the accumulator across k
    """
    k = pl.program_id(1)
    n_l = _factor_blocks(pl.program_id(0), bm)

    @pl.when(k == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    @pl.when(k < n_l)
    def _factor_phase():
        g_ref[...] += contract_rows(lt_ref[...], ltw_ref[...])

    @pl.when((k >= n_l) & (k < n_l + count_ref[0]))
    def _arrival_phase():
        z = z_ref[...]
        g_ref[...] += contract_rows(z, zw_ref[...])
        b_ref[...] += contract_rows(z, y_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_gram_pallas(
    L: jax.Array,
    Z: jax.Array,
    Y: jax.Array,
    blocks: Optional[jax.Array] = None,
    count: Optional[jax.Array] = None,
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Compute (G, B) = (L Lᵀ + ZᵀZ, ZᵀY).  L: (d, d); Z: (n, d); Y: (n, C).

    fp32 outputs.  L is read as lower-triangular: its strict upper triangle
    is never used.  ``blocks``/``count`` (:func:`live_row_blocks` of the
    design's mask) name the BK-row blocks of Z and Y that hold a real row;
    every other row must be zero (a masked design's padding is), and the
    blocks of such rows alone are skipped.  Without them every row is live.
    Rows are padded up to BK, and d up to a lane multiple, only where they
    are not (zero rows/cols contribute nothing to either Gram, so padding
    is exact).
    """
    n, d = Z.shape
    C = Y.shape[1]
    bm, dp = fit_tile(d, TILE_M)
    np_ = max(-(-n // BK), 1) * BK
    if blocks is None:
        blocks = jnp.arange(np_ // BK, dtype=jnp.int32)
        count = jnp.full((1,), -(-n // BK), jnp.int32)
    Lt = jnp.tril(L).T.astype(jnp.float32)  # contract over factor ROWS
    if dp != d:
        Lt = jnp.pad(Lt, ((0, dp - d), (0, dp - d)))
    if (np_, dp) != (n, d):
        Z = jnp.pad(Z, ((0, np_ - n), (0, dp - d)))
    Y = Y.astype(Z.dtype)
    if np_ != n:
        Y = jnp.pad(Y, ((0, np_ - n), (0, 0)))
    n_k = dp // BK + np_ // BK
    # double-buffered blocks and output tiles, and one step's products: wide
    # d or C outgrow the default limit, so ask for what the tiles need
    vmem = 4 * (2 * (bm * (dp + C) + BK * (2 * bm + 2 * dp + C)) + bm * (dp + C))
    params = None
    if vmem > VMEM_DEFAULT:
        params = pltpu.CompilerParams(vmem_limit_bytes=vmem + vmem // 4)

    def factor(i, k):  # past the tile's last factor row: hold it, no DMA
        return jnp.minimum(k, _factor_blocks(i, bm) - 1)

    def sample(i, k, blocks_ref, count_ref):  # ditto past the live count
        j = k - _factor_blocks(i, bm)
        return blocks_ref[jnp.clip(j, 0, jnp.maximum(count_ref[0] - 1, 0))]

    G, B = pl.pallas_call(
        functools.partial(_chol_gram_kernel, bm=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(dp // bm, n_k),
            in_specs=[
                pl.BlockSpec((BK, bm), lambda i, k, *r: (factor(i, k), i)),
                pl.BlockSpec((BK, dp), lambda i, k, *r: (factor(i, k), 0)),
                pl.BlockSpec((BK, bm), lambda i, k, *r: (sample(i, k, *r), i)),
                pl.BlockSpec((BK, dp), lambda i, k, *r: (sample(i, k, *r), 0)),
                pl.BlockSpec((BK, C), lambda i, k, *r: (sample(i, k, *r), 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, dp), lambda i, k, *r: (i, 0)),
                pl.BlockSpec((bm, C), lambda i, k, *r: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, C), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(blocks, count, Lt, Lt, Z, Z, Y)

    if dp != d:
        G, B = G[:d, :d], B[:d]
    return G, B


def _batched_chol_gram_kernel(
    lt_ref, ltw_ref, z_ref, zw_ref, out_ref, acc_ref, *, n_k_l: int, n_k: int
):
    """One (h, i, j) output tile; grid axis 3 sweeps Lᵀ rows, then head h's
    sample rows.  The factor operands are shared (their index maps drop
    ``h``) while the sample operands and the output carry a size-1 head
    block.

    lt_ref:  (BKL, BM)    block of Lᵀ            (factor rows × features)
    ltw_ref: (BKL, BN)    block of [Lᵀ | 0]      (factor rows × features+classes)
    z_ref:   (1, BKZ, BM) block of Z_h           (head × samples × features)
    zw_ref:  (1, BKZ, BN) block of [Z_h | Y_h]   (head × samples × feats+classes)
    out_ref: (1, BM, BN)  fp32 output tile of head h
    acc_ref: (BM, BN)     fp32 VMEM scratch accumulator
    """
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < n_k_l)
    def _factor_phase():
        acc_ref[...] += contract_rows(lt_ref[...], ltw_ref[...])

    @pl.when(k >= n_k_l)
    def _arrival_phase():
        acc_ref[...] += contract_rows(z_ref[0], zw_ref[0])

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[0] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_chol_gram_pallas(
    L: jax.Array, Z: jax.Array, Y: jax.Array, *, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Batched (G_k, B_k) = (L Lᵀ + Z_kᵀZ_k, Z_kᵀY_k) over K heads.

    L: (d, d) shared global factor; Z: (K, n, d); Y: (K, n, C).  Returns
    G: (K, d, d), B: (K, d, C), both fp32.  Shapes are padded up to tile
    multiples — zero rows/cols contribute nothing to either Gram, so
    padding is exact.  Per-head α_k scaling is the caller's pre-scaling
    Z_k ← √α_k·Z_k, Y_k ← √α_k·Y_k.
    """
    d = L.shape[0]
    K, n, _ = Z.shape
    C = Y.shape[2]
    if n == 0:
        # an empty cohort batch still needs one (all-zero, hence exact)
        # sample block so the z-phase BlockSpecs have rows to load
        Z = jnp.zeros((K, 1, d), Z.dtype)
        Y = jnp.zeros((K, 1, C), Y.dtype)
    Lt = L.T.astype(jnp.float32)
    LtW = jnp.concatenate([Lt, jnp.zeros((d, C), jnp.float32)], axis=1)
    ZW = jnp.concatenate([Z, Y.astype(Z.dtype)], axis=2)  # (K, n, d+C)

    def pad2(a, m0, m1):
        p0 = (-a.shape[0]) % m0
        p1 = (-a.shape[1]) % m1
        return jnp.pad(a, ((0, p0), (0, p1))) if (p0 or p1) else a

    def pad3(a, m1, m2):
        p1 = (-a.shape[1]) % m1
        p2 = (-a.shape[2]) % m2
        return jnp.pad(a, ((0, 0), (0, p1), (0, p2))) if (p1 or p2) else a

    Ltp = pad2(Lt, BKL, BM)
    LtWp = pad2(LtW, BKL, BN)
    Zp = pad3(Z, BKZ, BM)
    ZWp = pad3(ZW, BKZ, BN)
    dp = Ltp.shape[1]
    ep = LtWp.shape[1]
    n_k_l = Ltp.shape[0] // BKL
    n_k_z = Zp.shape[1] // BKZ
    n_k = n_k_l + n_k_z

    def clamp_l(k):
        return jnp.minimum(k, n_k_l - 1)

    def clamp_z(k):
        return jnp.clip(k - n_k_l, 0, n_k_z - 1)

    out = pl.pallas_call(
        functools.partial(_batched_chol_gram_kernel, n_k_l=n_k_l, n_k=n_k),
        grid=(K, dp // BM, ep // BN, n_k),
        in_specs=[
            pl.BlockSpec((BKL, BM), lambda h, i, j, k: (clamp_l(k), i)),
            pl.BlockSpec((BKL, BN), lambda h, i, j, k: (clamp_l(k), j)),
            pl.BlockSpec((1, BKZ, BM), lambda h, i, j, k: (h, clamp_z(k), i)),
            pl.BlockSpec((1, BKZ, BN), lambda h, i, j, k: (h, clamp_z(k), j)),
        ],
        out_specs=pl.BlockSpec((1, BM, BN), lambda h, i, j, k: (h, i, j)),
        out_shape=jax.ShapeDtypeStruct((K, dp, ep), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
    )(Ltp, LtWp, Zp, ZWp)

    M = out[:, :d, :]
    return M[:, :, :d], M[:, :, d : d + C]

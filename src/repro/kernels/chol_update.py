"""Pallas kernels: fused rank-n Cholesky-Gram updates G = L Lᵀ + ZᵀZ, B = ZᵀY.

The streaming arrival engine's hot spot (repro.federated.streaming_engine):
every arrival wave refactors the carried Cholesky factor of A + λI through
the Gram reconstruction G = L Lᵀ + ZᵀZ and accumulates the class sums
B = ZᵀY.  Both right-hand contributions are contractions over a "row"
dimension — d rows of Lᵀ for the reconstruction, n sample rows of [Z | Y]
for the rank-n arrival update — so the whole update is ONE blocked GEMM
whose k-sweep walks the Lᵀ rows first and the sample rows second, into a
single fp32 accumulator tile resident in VMEM.  No (d+n, d+C) stacked
operand is ever materialized in HBM (contrast the XLA reference, which
concatenates).

Grid (d/bm, (d+C)/bn, kL + kZ): phase one (k < kL) contracts
Lᵀ·[Lᵀ | 0], phase two contracts Zᵀ·[Z | Y]; each phase has its own block
size (BKL for the d-row factor sweep, BKZ for the sample sweep) and
clamped index maps keep the off-phase operand block loads in range.
MXU-shaped tiles with fp32 accumulation and fp32 contraction of fp32
operands, as in kernels/fed3r_stats.py.

The BATCHED variant (:func:`batched_chol_gram_pallas`) is the
personalization engine's hot spot (repro.federated.personalization): one
grid-over-heads pallas_call computes K per-tenant Gram updates
G_k = L Lᵀ + Z_kᵀZ_k, B_k = Z_kᵀY_k against ONE shared global factor L.
The head index is the leading (outermost) grid axis, so the k-sweep of
each head runs to completion in its private VMEM accumulator before the
grid advances to the next head; the shared Lᵀ blocks are re-walked per
head (they index-map independently of the head axis).  Per-head scaling
α_k Z_kᵀZ_k is folded in by pre-scaling Z_k ← √α_k·Z_k outside the kernel
(both Gram contributions are bilinear in Z), keeping the kernel body
scale-free.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fed3r_stats import contract_rows

BM = 128  # rows of the output tile (d dim)
BN = 128  # cols of the output tile (d+C dim)
BKL = 128  # Lᵀ rows per accumulation step (factor sweep, ≤ d typically)
BKZ = 512  # samples per accumulation step (arrival sweep)


def _chol_gram_kernel(
    lt_ref, ltw_ref, z_ref, zw_ref, out_ref, acc_ref, *, n_k_l: int, n_k: int
):
    """One (i, j) output tile; grid axis 2 sweeps Lᵀ rows, then sample rows.

    lt_ref:  (BKL, BM) block of Lᵀ          (factor rows × features)
    ltw_ref: (BKL, BN) block of [Lᵀ | 0]    (factor rows × features+classes)
    z_ref:   (BKZ, BM) block of Z           (samples × features)
    zw_ref:  (BKZ, BN) block of [Z | Y]     (samples × features+classes)
    out_ref: (BM, BN) fp32 output tile
    acc_ref: (BM, BN) fp32 VMEM scratch accumulator
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < n_k_l)
    def _factor_phase():
        acc_ref[...] += contract_rows(lt_ref[...], ltw_ref[...])

    @pl.when(k >= n_k_l)
    def _arrival_phase():
        acc_ref[...] += contract_rows(z_ref[...], zw_ref[...])

    @pl.when(k == n_k - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_gram_pallas(
    L: jax.Array, Z: jax.Array, Y: jax.Array, *, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Compute (G, B) = (L Lᵀ + ZᵀZ, ZᵀY).  L: (d, d); Z: (n, d); Y: (n, C).

    fp32 outputs.  Shapes are padded up to tile multiples — zero rows/cols
    contribute nothing to either Gram, so padding is exact.
    """
    d = L.shape[0]
    C = Y.shape[1]
    if Z.shape[0] == 0:
        # an empty arrival batch still needs one (all-zero, hence exact)
        # sample block so the z-phase BlockSpecs have rows to load
        Z = jnp.zeros((1, d), Z.dtype)
        Y = jnp.zeros((1, C), Y.dtype)
    Lt = L.T.astype(jnp.float32)  # contract over factor ROWS, like samples
    LtW = jnp.concatenate(
        [Lt, jnp.zeros((d, C), jnp.float32)], axis=1
    )  # (d, d+C): the factor sweep adds nothing to the B columns
    ZW = jnp.concatenate([Z, Y.astype(Z.dtype)], axis=1)  # (n, d+C)

    def pad_to(a, m0, m1):
        p0 = (-a.shape[0]) % m0
        p1 = (-a.shape[1]) % m1
        return jnp.pad(a, ((0, p0), (0, p1))) if (p0 or p1) else a

    Ltp = pad_to(Lt, BKL, BM)
    LtWp = pad_to(LtW, BKL, BN)
    Zp = pad_to(Z, BKZ, BM)
    ZWp = pad_to(ZW, BKZ, BN)
    dp = Ltp.shape[1]
    ep = LtWp.shape[1]
    n_k_l = Ltp.shape[0] // BKL
    n_k_z = Zp.shape[0] // BKZ
    n_k = n_k_l + n_k_z

    def clamp_l(k):
        return jnp.minimum(k, n_k_l - 1)

    def clamp_z(k):
        return jnp.clip(k - n_k_l, 0, n_k_z - 1)

    out = pl.pallas_call(
        functools.partial(_chol_gram_kernel, n_k_l=n_k_l, n_k=n_k),
        grid=(dp // BM, ep // BN, n_k),
        in_specs=[
            pl.BlockSpec((BKL, BM), lambda i, j, k: (clamp_l(k), i)),
            pl.BlockSpec((BKL, BN), lambda i, j, k: (clamp_l(k), j)),
            pl.BlockSpec((BKZ, BM), lambda i, j, k: (clamp_z(k), i)),
            pl.BlockSpec((BKZ, BN), lambda i, j, k: (clamp_z(k), j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, ep), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
    )(Ltp, LtWp, Zp, ZWp)

    M = out[:d, :]
    return M[:, :d], M[:, d : d + C]


def _batched_chol_gram_kernel(
    lt_ref, ltw_ref, z_ref, zw_ref, out_ref, acc_ref, *, n_k_l: int, n_k: int
):
    """One (h, i, j) output tile; grid axis 3 sweeps Lᵀ rows, then head h's
    sample rows.  Identical algebra to :func:`_chol_gram_kernel`, plus the
    leading head axis: the factor operands are shared (their index maps drop
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

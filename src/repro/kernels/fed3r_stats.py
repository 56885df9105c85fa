"""Pallas kernel: fused FED3R statistics A = ZᵀZ, b = ZᵀY.

The paper's client-side hot spot (App. E charges ½·n·d(d+1) + n·d·C FLOPs
for it).  Key insight for the fused form: stacking the one-hot targets next
to the features, W = [Z | Y] ∈ R^{n×(d+C)}, turns both statistics into ONE
blocked GEMM  M = Zᵀ W, with A = M[:, :d] and b = M[:, d:].

TPU adaptation (vs. the paper's cuBLAS call on A100):
  * grid (d/bm, (d+C)/bn, n/bk): each (i, j) owns one fp32 accumulator tile
    resident in VMEM scratch across the k-sweep — A is up to 12288² fp32
    (576 MB), so tiles must stream; HBM sees each Z tile once per j-pass.
  * MXU-shaped tiles (128×512×128) with an fp32 accumulator.  fp32
    operands contract at fp32 (``Precision.HIGHEST``, several MXU passes):
    Mosaic's default contracts them in ONE bf16 pass, which rounds every
    feature to 8 mantissa bits — the engines hand this kernel fp32
    designs and solve a ridge system on the result.  bf16 operands take
    the native single pass.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM = 128  # rows of the output tile (d dim)
BN = 128  # cols of the output tile (d+C dim)
BK = 512  # samples per accumulation step


def contract_rows(a: jax.Array, b: jax.Array) -> jax.Array:
    """aᵀb over the shared leading (row) dim, into fp32 on the MXU.

    fp32 operands contract at fp32; bf16 operands in the native pass."""
    fp32 = jnp.float32 in (a.dtype, b.dtype)
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if fp32 else None,
        preferred_element_type=jnp.float32,
    )


def _stats_kernel(zt_ref, w_ref, out_ref, acc_ref, *, n_k_steps: int):
    """One (i, j) output tile; grid axis 2 sweeps the n (sample) dim.

    zt_ref: (BK, BM) block of Z        (samples × features)
    w_ref:  (BK, BN) block of W=[Z|Y]  (samples × features+classes)
    out_ref: (BM, BN) fp32 output tile
    acc_ref: (BM, BN) fp32 VMEM scratch accumulator
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contract_rows(zt_ref[...], w_ref[...])

    @pl.when(k == n_k_steps - 1)
    def _done():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fed3r_stats_pallas(
    Z: jax.Array, Y: jax.Array, *, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """Compute (A, b) = (ZᵀZ, ZᵀY). Z: (n, d); Y: (n, C). fp32 outputs.

    Shapes are padded up to tile multiples (zero rows/cols are exact:
    they contribute nothing to either statistic).
    """
    n, d = Z.shape
    C = Y.shape[1]
    W = jnp.concatenate([Z, Y.astype(Z.dtype)], axis=1)  # (n, d+C)

    def pad_to(a, m0, m1):
        p0 = (-a.shape[0]) % m0
        p1 = (-a.shape[1]) % m1
        return jnp.pad(a, ((0, p0), (0, p1))) if (p0 or p1) else a

    Zp = pad_to(Z, BK, BM)
    Wp = pad_to(W, BK, BN)
    np_, dp = Zp.shape
    ep = Wp.shape[1]
    n_k = np_ // BK

    out = pl.pallas_call(
        functools.partial(_stats_kernel, n_k_steps=n_k),
        grid=(dp // BM, ep // BN, n_k),
        in_specs=[
            pl.BlockSpec((BK, BM), lambda i, j, k: (k, i)),
            pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((dp, ep), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret,
    )(Zp, Wp)

    M = out[:d, :]
    return M[:, :d], M[:, d : d + C]

"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles.

Per the kernel contract: each kernel is swept over shapes (including
non-tile-aligned ones that exercise padding) and dtypes, asserting allclose
against the pure-jnp oracle.  Kernels run in interpret mode on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fed3r_stats, flash_attention, rff_transform
from repro.kernels import ref


@pytest.mark.parametrize("n,d,C", [(64, 32, 5), (300, 200, 37), (513, 129, 10), (1024, 256, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed3r_stats_kernel(n, d, C, dtype, rng):
    Z = jax.random.normal(rng, (n, d), dtype)
    Y = jax.nn.one_hot(jax.random.randint(rng, (n,), 0, C), C)
    A, b = fed3r_stats(Z, Y)
    Ar, br = ref.fed3r_stats_ref(Z, Y)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(A), np.asarray(Ar), rtol=tol, atol=tol * n)
    np.testing.assert_allclose(np.asarray(b), np.asarray(br), rtol=tol, atol=tol * n)
    assert A.dtype == jnp.float32  # fp32 accumulation regardless of input


def _extent_case(case, n, bk):
    """(live-row mask, extent) of one case; the extent is the last live
    row plus one."""
    live = np.zeros(n, bool)
    if case == "first_block":
        live[: bk // 3] = True
    elif case == "not_multiple":
        live[: 2 * bk - 5] = True
    elif case == "full":
        live[:] = True
    elif case == "holes":
        live[[0, 3, bk - 1, bk + 7, 2 * bk + 1]] = True
    idx = np.flatnonzero(live)
    return live, int(idx[-1]) + 1 if idx.size else 0


@pytest.mark.parametrize(
    "case", ["empty", "first_block", "not_multiple", "full", "holes"]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed3r_stats_kernel_live_extent(case, dtype, rng):
    """Only rows below the extent count; the row blocks wholly past it are
    never read (NaN there would poison the tile) and an extent of 0 gives
    exact zeros."""
    from repro.kernels.fed3r_stats import BK

    n, d, C = 3 * BK + 8, 40, 7  # the last block is partly padding
    live, rows = _extent_case(case, n, BK)
    Z = np.asarray(jax.random.normal(rng, (n, d), dtype), np.float64)
    Y = np.eye(C)[np.asarray(jax.random.randint(rng, (n,), 0, C))]
    Z[~live], Y[~live] = 0.0, 0.0  # a masked design's padding
    dead = -(-rows // BK) * BK
    Z[dead:], Y[dead:] = np.nan, np.nan
    A, b = fed3r_stats(jnp.asarray(Z, dtype), jnp.asarray(Y, dtype), jnp.int32(rows))
    Zr, Yr = Z[:rows], Y[:rows]  # float64 reference over the live rows
    np.testing.assert_allclose(np.asarray(A), Zr.T @ Zr, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(b), Zr.T @ Yr, rtol=1e-5, atol=1e-4)
    if rows == 0:
        assert not np.any(np.asarray(A)) and not np.any(np.asarray(b))


@pytest.mark.parametrize("n,d,D", [(64, 32, 64), (200, 100, 257), (130, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rff_kernel(n, d, D, dtype, rng):
    Z = jax.random.normal(rng, (n, d), dtype)
    om = jax.random.normal(jax.random.fold_in(rng, 1), (d, D), jnp.float32) / 3.0
    be = jax.random.uniform(jax.random.fold_in(rng, 2), (D,), maxval=2 * np.pi)
    R = rff_transform(Z, om, be)
    Rr = ref.rff_ref(Z, om, be)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(R), np.asarray(Rr), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 2, 2, 32),   # MHA
    (2, 256, 4, 2, 64),   # GQA
    (1, 384, 8, 1, 16),   # MQA, 3 tiles
])
@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(B, S, H, KV, hd, window, dtype, rng):
    q = jax.random.normal(rng, (B, S, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, KV, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, KV, hd), dtype)
    o = flash_attention(q, k, v, causal=True, window=window)
    orf = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(orf, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_matches_model_attention(rng):
    """Kernel vs the framework's XLA attention path (same contract)."""
    from repro.models.attention import multihead_attention

    B, S, H, KV, hd = 2, 256, 4, 2, 32
    q = jax.random.normal(rng, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, KV, hd))
    pos = jnp.arange(S, dtype=jnp.int32)
    xla_out = multihead_attention(q, k, v, pos, pos)
    ker_out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(xla_out), np.asarray(ker_out), rtol=2e-4, atol=2e-4
    )


def test_fed3r_stats_kernel_feeds_solver(rng):
    """End-to-end: kernel statistics → ridge solve → same classifier."""
    from repro.core import fed3r as f3

    Z = jax.random.normal(rng, (256, 64))
    labels = jax.random.randint(jax.random.fold_in(rng, 1), (256,), 0, 10)
    Y = jax.nn.one_hot(labels, 10)
    A, b = fed3r_stats(Z, Y)
    W_kernel = f3.solve(f3.Fed3RStats(A=A, b=b, n=jnp.asarray(256.0)), 0.01)
    W_ref = f3.solve(f3.client_stats(Z, labels, 10), 0.01)
    np.testing.assert_allclose(np.asarray(W_kernel), np.asarray(W_ref),
                               rtol=1e-3, atol=1e-3)

"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program.  It takes the generator's
unpadded features (for a backbone configuration, its own forward pass over
the generator's tokens, ``backbone_features``), in the pass's arrival
order, and folds them as the closed form's sums are defined in fp32: each
client's (or each wave's) statistics over its own rows, in blocks of
``BLOCK`` rows with plain ``jax.numpy`` products at ``"highest"``, added one
after another into the running sums in arrival order (clients of a round or
wave in id order).  The program folds in that order too, so the two share
the rounding of the long running sums and differ by the products and short
sums alone.  The solve runs on the host in float64.

``how`` names the contraction: ``"fp32"`` is the reference itself;
``"bf16x3"`` is the control, the same computation one precision step
below (three bf16 products for each fp32 product, as ``Precision.HIGH``
does on a TPU, written out so that it means the same on every backend);
``"bf16"`` is one bf16 pass over bf16 features.  A backbone's forward
runs its products at the matching precision (``PRECISION``): "highest"
for the reference, "high" (three bf16 products, ``matmul``) for the
control.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

from bench.generator import Federation

BLOCK = 128  # rows per reference block
# a fold's contraction → the precision of a backbone's products
PRECISION = {"fp32": "highest", "bf16x3": "high", "bf16": "bfloat16"}
_HOW = {v: k for k, v in PRECISION.items()}
ROWS_T = (((0,), (0,)), ((), ()))  # aᵀb over the rows


def _dot(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _split_bf16(x: jax.Array):
    """x = hi + lo with hi the top 16 bits of each fp32 (exact in bf16) and
    lo the rest, rounded to bf16.  Cut by bits, not by a round trip through
    bf16, which a compiler that allows excess precision (XLA on a TPU) may
    drop, leaving lo = 0 and one bf16 pass in place of three."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _product(a: jax.Array, b: jax.Array, dims, how: str) -> jax.Array:
    if how == "fp32":
        return _dot(a, b, dims, jax.lax.Precision.HIGHEST)
    if how == "bf16x3":
        a_hi, a_lo = _split_bf16(a)
        b_hi, b_lo = _split_bf16(b)
        return _dot(a_hi, b_hi, dims) + (_dot(a_hi, b_lo, dims) + _dot(a_lo, b_hi, dims))
    if how == "bf16":
        return _dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims)
    raise ValueError(f"unknown contraction {how!r}")


def contract(a: jax.Array, b: jax.Array, how: str) -> jax.Array:
    """aᵀb over the rows, into fp32."""
    return _product(a, b, ROWS_T, how)


def matmul(x: jax.Array, w: jax.Array, precision: str = "highest") -> jax.Array:
    """x @ w over x's last axis and w's first, into fp32, at ``precision``:
    "highest" (fp32), "high" (three bf16 products, written out as
    ``contract``'s ``bf16x3``, so it means the same on every backend) or
    "bfloat16" (one pass).  For a backbone module's products."""
    return _product(x, w, (((x.ndim - 1,), (0,)), ((), ())), _HOW[precision])


def backbone_features(fed: Federation, rows: np.ndarray, how: str = "fp32") -> np.ndarray:
    """The backbone module's pooled features of the samples ``rows``: one
    jitted forward over blocks of the module's ``ROWS`` rows, its products
    at the precision that matches ``how``."""
    module = fed.backbone_module
    step = module.ROWS
    forward = jax.jit(partial(module.features, model=fed.backbone["model"],
                              precision=PRECISION[how]))
    weights = jax.device_put(fed.weights)
    blocks = []
    for lo in range(0, len(rows), step):
        take = rows[lo:lo + step]
        tokens = np.zeros((step, fed.tokens.shape[1]), np.int32)
        lengths = np.ones(step, np.int32)  # rows past the end: one token of id 0
        tokens[:len(take)], lengths[:len(take)] = fed.tokens[take], fed.lengths[take]
        blocks.append(forward(weights, tokens, lengths))
    return np.concatenate([np.asarray(b) for b in blocks])[:len(rows)]


@partial(jax.jit, static_argnames=("n_classes", "how"))
def _fold(acc, feats, labels, start, stop, n_classes: int, how: str):
    """acc + (ZᵀZ, ZᵀY) over rows [start, stop), in blocks of BLOCK rows."""
    d = feats.shape[1]

    def block(i, sums):
        lo = start + i * BLOCK
        live = (lo + jnp.arange(BLOCK)) < stop
        z = jax.lax.dynamic_slice(feats, (lo, 0), (BLOCK, d)) * live[:, None]
        y = jax.lax.dynamic_slice(labels, (lo,), (BLOCK,))
        onehot = ((y[:, None] == jnp.arange(n_classes)[None, :]) & live[:, None])
        onehot = onehot.astype(jnp.float32)
        return sums[0] + contract(z, z, how), sums[1] + contract(z, onehot, how)

    zero = (jnp.zeros((d, d), jnp.float32), jnp.zeros((d, n_classes), jnp.float32))
    A, b = jax.lax.fori_loop(0, (stop - start + BLOCK - 1) // BLOCK, block, zero)
    return acc[0] + A, acc[1] + b


class Snapshot(NamedTuple):
    """The statistics and the solved classifier after some rounds, float64."""

    A: np.ndarray
    b: np.ndarray
    n: int
    counts: np.ndarray
    W: np.ndarray


def solve(A: np.ndarray, b: np.ndarray, ridge_lambda: float) -> np.ndarray:
    """W = (A + λI)⁻¹ b in float64, each class column normalized."""
    A = np.asarray(A, np.float64)
    reg = A + ridge_lambda * np.eye(A.shape[0])
    W = scipy.linalg.cho_solve(scipy.linalg.cho_factor(reg, lower=True), np.asarray(b, np.float64))
    return W / np.maximum(np.linalg.norm(W, axis=0, keepdims=True), 1e-12)


def statistics(fed: Federation, ridge_lambda: float, groups: Sequence[np.ndarray],
               after: Sequence[int], how: str = "fp32") -> Dict[int, Snapshot]:
    """Snapshots of the statistics after each group index in ``after``.
    ``groups`` are the client ids the program adds into its running sums
    as one unit, in its order: one client each (the batch engine's fold),
    or one wave each (the streaming engine's)."""
    want = set(int(t) for t in after)
    last = max(want)
    d, C = fed.feature_dim, fed.n_classes
    rows = [np.concatenate([np.arange(fed.offsets[k], fed.offsets[k + 1]) for k in g])
            for g in groups[:last + 1]]
    edges = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    rows = np.concatenate(rows)
    feats = np.zeros((len(rows) + BLOCK, d), np.float32)
    feats[:len(rows)] = (fed.features[rows] if fed.backbone is None
                         else backbone_features(fed, rows, how))
    labels = np.zeros(len(rows) + BLOCK, np.int32)
    labels[:len(rows)] = fed.labels[rows]
    feats, labels = jax.device_put(feats), jax.device_put(labels)
    acc = (jnp.zeros((d, d), jnp.float32), jnp.zeros((d, C), jnp.float32))
    out: Dict[int, Snapshot] = {}
    for g in range(last + 1):
        acc = _fold(acc, feats, labels, np.int32(edges[g]), np.int32(edges[g + 1]), C, how)
        if g in want:
            n = int(edges[g + 1])
            A, b = (np.asarray(x, np.float64) for x in acc)
            out[g] = Snapshot(A=A, b=b, n=n,
                              counts=np.bincount(fed.labels[rows[:n]], minlength=C),
                              W=solve(A, b, ridge_lambda))
    return out


def rel_err(got, ref) -> float:
    """max|got − ref| / max|ref| in float64; inf if ``got`` is not finite or
    not of the reference's shape."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    scale = float(np.max(np.abs(ref)))
    return float(np.max(np.abs(got - ref))) / (scale if scale > 0 else 1.0)


def compare(got: Dict[str, np.ndarray], ref: Snapshot, ridge_lambda: float) -> Dict[str, float]:
    """The numbers compared for one answer.  ``got`` holds what the program
    produced: ``A`` or the factor ``L``, the class sums ``b``, ``W``, ``n``
    and optionally ``counts``.  b is compared as the raw sums: W's columns
    are normalized, so a per-class scale error in b shows only here."""
    out = {}
    if "A" in got:
        out["A_rel"] = rel_err(got["A"], ref.A)
    if "b" in got:
        out["b_rel"] = rel_err(got["b"], ref.b)
    if "L" in got:
        L = np.tril(np.asarray(got["L"], np.float64))
        target = ref.A + ridge_lambda * np.eye(ref.A.shape[0])
        out["LLt_rel"] = rel_err(L @ L.T, target)
    out["W_rel"] = rel_err(got["W"], ref.W)
    out["n_diff"] = abs(float(got["n"]) - ref.n)
    if "counts" in got:
        counts = np.asarray(got["counts"], np.float64)
        out["counts_diff"] = float(np.max(np.abs(counts - ref.counts)))
    return out


def worst(readings: List[Dict[str, float]]) -> Dict[str, float]:
    """The largest reading of each number over several answers."""
    keys = readings[0].keys() if readings else ()
    return {k: max(r[k] for r in readings) for k in keys}

"""FED3R — Federated Recursive Ridge Regression (paper §4).

The paper's contribution, as a composable JAX module.  Everything here is a
pure function over a tiny ``Fed3RStats`` pytree so the same code runs:

* in the **simulator** (python round loop, ``merge`` = server aggregation),
* in the **distributed runtime** (``aggregate_mesh`` = ``psum`` over the
  ("pod", "data") mesh axes — the paper's client→server aggregation mapped
  onto an all-reduce; exactness of the sum *is* the paper's immunity claim),
* in **streaming/online** mode (``Fed3RFactored`` — the recursive
  least-squares formulation of Eq. (3) kept in Cholesky-factored form;
  the subtractive Sherman–Morrison–Woodbury path ``woodbury_update`` is
  retained as a deprecated compat path),
* in **multi-tenant personalized** mode (``personalized_solution`` /
  ``batched_personalized_solution`` — per-client heads
  W_k = (A + α_k·A_k + λI)⁻¹(b + α_k·b_k) as rank-n updates of the shared
  factored state; the batched engine with α selection is
  :mod:`repro.federated.personalization`).

Statistics (Eq. 5/6):
    A = Σ_k Σ_{(x,y)∈D_k} φ(x)φ(x)ᵀ          (d×d, fp32)
    b = Σ_k Σ_{(x,y)∈D_k} φ(x) e_yᵀ           (d×C, fp32)
Solve (Eq. 4):  W* = (A + λI)⁻¹ b, then per-class column normalization.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

# The closed form is fp32 algebra.  On a TPU, XLA's default contracts fp32
# operands in one bf16 pass (8 mantissa bits), so every Gram, statistics
# and score product of the algebra asks for fp32; on CPU this is a no-op.
FP32 = jax.lax.Precision.HIGHEST


def gram(a: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    """aᵀb (aᵀa without ``b``) over the leading dim, contracted at fp32."""
    return jnp.matmul(a.T, a if b is None else b, precision=FP32)


class Fed3RStats(NamedTuple):
    """Sufficient statistics of the ridge-regression classifier."""

    A: jax.Array  # (d, d) fp32 feature second moment
    b: jax.Array  # (d, C) fp32 class-conditional feature sums
    n: jax.Array  # () fp32 sample count (diagnostics / NCM reuse)


def init_stats(d: int, n_classes: int) -> Fed3RStats:
    return Fed3RStats(
        A=jnp.zeros((d, d), jnp.float32),
        b=jnp.zeros((d, n_classes), jnp.float32),
        n=jnp.zeros((), jnp.float32),
    )


def masked_design(
    features: jax.Array,  # (n, d) — φ(x), any float dtype
    labels: jax.Array,  # (n,) int32
    n_classes: int,
    mask: Optional[jax.Array] = None,  # (n,) 1.0 = real sample, 0.0 = padding
) -> tuple:
    """Masked fp32 design matrices (Z, Y) and exact sample count n.

    The single source of truth for the masking semantics of Eq. 5/6:
    every statistics backend (XLA GEMMs here, the Pallas kernel in
    repro.federated.engine) consumes these so padded rows contribute
    exactly nothing to A, b, or n.
    """
    z = features.astype(jnp.float32)
    y = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)
    if mask is not None:
        m = mask.astype(jnp.float32)[:, None]
        z = z * m
        y = y * m
        n = jnp.sum(m)
    else:
        n = jnp.asarray(float(features.shape[0]), jnp.float32)
    return z, y, n


def client_stats(
    features: jax.Array,  # (n, d) — φ(x), any float dtype
    labels: jax.Array,  # (n,) int32
    n_classes: int,
    mask: Optional[jax.Array] = None,  # (n,) 1.0 = real sample, 0.0 = padding
) -> Fed3RStats:
    """Local statistics A_k, b_k of one client (Algorithm 1, client side).

    ``mask`` lets several clients share one padded batch (clients-per-shard
    batching in the distributed runtime) while keeping the sums exact.
    """
    z, y, n = masked_design(features, labels, n_classes, mask)
    return Fed3RStats(A=gram(z), b=gram(z, y), n=n)


def merge(*stats: Fed3RStats) -> Fed3RStats:
    """Server aggregation: associative+commutative sum of client statistics.

    Invariance to the client split and sampling order (paper §4.3) is the
    reassociation freedom of this sum.
    """
    return Fed3RStats(
        A=sum(s.A for s in stats),
        b=sum(s.b for s in stats),
        n=sum(s.n for s in stats),
    )


def aggregate_mesh(stats: Fed3RStats, axis_names: Sequence[str]) -> Fed3RStats:
    """Distributed aggregation: psum over mesh axes (inside shard_map)."""
    return jax.tree.map(lambda a: jax.lax.psum(a, tuple(axis_names)), stats)


def normalize_columns(W: jax.Array, axis: int = 0) -> jax.Array:
    """Per-class column normalization W_c ← W_c / max(‖W_c‖, 1e-12).

    The single definition every solve path shares (batched callers pass the
    feature axis of their layout) — the α=0 bitwise-parity contract of the
    personalization engine depends on all sites computing exactly this.
    """
    norms = jnp.linalg.norm(W, axis=axis, keepdims=True)
    return W / jnp.maximum(norms, 1e-12)


def solve(
    stats: Fed3RStats,
    ridge_lambda: float,
    normalize: bool = True,
) -> jax.Array:
    """Closed-form classifier W* = (A + λI)⁻¹ b (Eq. 4) via Cholesky.

    A + λI ≻ 0 for λ > 0, so the Cholesky factorization always exists.
    Optional per-class column normalization (paper, after Eq. 6):
    W*_c ← W*_c / ‖W*_c‖.
    """
    d = stats.A.shape[0]
    A_reg = stats.A + ridge_lambda * jnp.eye(d, dtype=jnp.float32)
    L = jax.scipy.linalg.cho_factor(A_reg, lower=True)
    W = jax.scipy.linalg.cho_solve(L, stats.b)
    if normalize:
        W = normalize_columns(W)
    return W


def predict(W: jax.Array, features: jax.Array) -> jax.Array:
    """One-vs-rest scores f(x) = Wᵀφ(x): (n, C)."""
    return features.astype(jnp.float32) @ W


def accuracy(W: jax.Array, features: jax.Array, labels: jax.Array) -> jax.Array:
    return jnp.mean((jnp.argmax(predict(W, features), axis=-1) == labels).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Recursive (online) formulation — factored rank-n updates
# ---------------------------------------------------------------------------


class Fed3RFactored(NamedTuple):
    """Online RR state in Cholesky-factored form: L Lᵀ = A + λI.

    The numerically stable recursive-least-squares formulation of Eq. (3):
    every arrival performs the ADDITIVE rank-n update L ← chol(L Lᵀ + ZᵀZ)
    (no subtraction, hence no fp32 cancellation — contrast ``Fed3ROnline``),
    and the solution W = (A + λI)⁻¹ b is two triangular solves against L.
    This is the state carried by the streaming arrival engine
    (:mod:`repro.federated.streaming_engine`) and the shared base every
    personalized head is a rank-n update away from
    (:func:`personalized_solution`, :mod:`repro.federated.personalization`).

    Fields:
      L: (d, d) fp32 lower-triangular Cholesky factor of A + λI, where
         A = Σ ZᵀZ is the global feature second moment over everything
         absorbed so far and λ is the ridge coefficient baked in at
         :func:`init_factored` time (L = √λ·I before any data).  Only the
         lower triangle is meaningful; consumers must pass ``lower=True``
         to the triangular solves.
      b: (d, C) fp32 class-conditional feature sums Σ ZᵀY (Y one-hot),
         the right-hand side of the closed-form solve.  Unlike L it is a
         plain running sum, so it merges/psums exactly like
         :class:`Fed3RStats` and composes with secure aggregation.
    """

    L: jax.Array  # (d, d) fp32 lower Cholesky factor of A + λI
    b: jax.Array  # (d, C)


def init_factored(d: int, n_classes: int, ridge_lambda: float) -> Fed3RFactored:
    return Fed3RFactored(
        L=jnp.sqrt(jnp.float32(ridge_lambda)) * jnp.eye(d, dtype=jnp.float32),
        b=jnp.zeros((d, n_classes), jnp.float32),
    )


def factored_update(
    state: Fed3RFactored,
    features: jax.Array,  # (n, d)
    labels: jax.Array,  # (n,) int32
    mask: Optional[jax.Array] = None,  # (n,) 1.0 real / 0.0 padding
) -> Fed3RFactored:
    """Stable rank-n update with a new arrival batch Z (n, d):

    L ← chol(L Lᵀ + ZᵀZ),  b ← b + ZᵀY.

    Both Gram contributions are PSD and the ridge floor λI ⪯ L Lᵀ keeps the
    refactorization positive definite, so the update is additions-only —
    exact in the same sense as the batch statistics path.  The fused Pallas
    form of the two GEMMs lives in :func:`repro.kernels.chol_gram`.
    """
    z, y, _ = masked_design(features, labels, state.b.shape[1], mask)
    G = gram(state.L.T) + gram(z)
    return Fed3RFactored(L=jnp.linalg.cholesky(G), b=state.b + gram(z, y))


def factored_solution(state: Fed3RFactored, normalize: bool = True) -> jax.Array:
    """W = (A + λI)⁻¹ b by two triangular solves against the carried factor."""
    W = jax.scipy.linalg.cho_solve((state.L, True), state.b)
    if normalize:
        W = normalize_columns(W)
    return W


# ---------------------------------------------------------------------------
# Personalized heads — per-client closed forms over the shared factored state
# ---------------------------------------------------------------------------


def personalized_solution(
    state: Fed3RFactored,
    client: Fed3RStats,
    alpha: Union[float, jax.Array],
    normalize: bool = True,
) -> jax.Array:
    """Per-client closed-form head W_k = (A + α·A_k + λI)⁻¹ (b + α·b_k).

    The personalization closed form over the shared factored state: client
    k's own statistics (A_k, b_k) are re-weighted by α ≥ 0 on top of the
    global sums, so the head interpolates from the heterogeneity-immune
    global classifier (α = 0) toward a local-emphasis one.  Cost: one d×d
    Cholesky refactorization G = L Lᵀ + α·A_k plus two triangular solves —
    no gradient step, no retraining, and the upload is the (A_k, b_k) the
    client already sent.

    α = 0 reproduces :func:`factored_solution` BITWISE: the carried factor
    L and right-hand side b are selected unchanged (not recomputed through
    chol(L Lᵀ) / b + 0, whose roundings could differ), so the downstream
    solves see identical operands.

    The batched form over a packed cohort — K heads in one dispatch, with
    per-client α selection — is
    :class:`repro.federated.personalization.PersonalizationEngine`.
    """
    a = jnp.asarray(alpha, jnp.float32)
    L_pers = jnp.linalg.cholesky(gram(state.L.T) + a * client.A)
    L_use = jnp.where(a == 0.0, state.L, L_pers)
    rhs = jnp.where(a == 0.0, state.b, state.b + a * client.b)
    W = jax.scipy.linalg.cho_solve((L_use, True), rhs)
    if normalize:
        W = normalize_columns(W)
    return W


def batched_personalized_solution(
    state: Fed3RFactored,
    A_k: jax.Array,  # (K, d, d) per-client second moments
    b_k: jax.Array,  # (K, d, C) per-client class-conditional sums
    alphas: jax.Array,  # (K,) per-client interpolation weights
    normalize: bool = True,
) -> jax.Array:
    """K personalized heads (K, d, C) in one vmapped batch of solves.

    Semantics per head follow :func:`personalized_solution`: α = 0 rows
    select the global (L, b) operands unchanged, but the solve itself is
    BATCHED, and XLA's batched triangular solve may lower differently from
    the unbatched one — so α = 0 here agrees with ``factored_solution`` to
    the last ulp of the solver, NOT bitwise.  When the exact-bitwise α = 0
    fallback matters (serving), use the engine
    (:class:`repro.federated.personalization.PersonalizationEngine`),
    which substitutes an unbatched global solve for those rows.  The
    global ``state`` is broadcast, so the Gram reconstructions, Cholesky
    refactorizations, and triangular solves all batch into single XLA ops.
    """
    return jax.vmap(
        lambda A, b, a: personalized_solution(
            state, Fed3RStats(A=A, b=b, n=jnp.zeros((), jnp.float32)), a, normalize
        )
    )(A_k, b_k, jnp.asarray(alphas, jnp.float32))


# ---------------------------------------------------------------------------
# Deprecated: subtractive Sherman–Morrison–Woodbury compat path
# ---------------------------------------------------------------------------


class Fed3ROnline(NamedTuple):
    """DEPRECATED online RR state carrying A⁻¹ directly.

    With λ ≪ tr(A)/d the initial A⁻¹ = I/λ is orders of magnitude larger
    than the converged inverse, so the subtractive Woodbury update suffers
    catastrophic cancellation in fp32 (observed ~1e-2 max-abs error on W at
    λ = 1e-2 where :class:`Fed3RFactored` stays ≤ 1e-6).  Kept only as a
    compat path; use ``init_factored``/``factored_update`` instead.
    """

    Ainv: jax.Array  # (d, d) fp32 — (A + λI)⁻¹
    b: jax.Array  # (d, C)


# fp32 cancellation becomes visible once 1/λ dwarfs the converged inverse;
# below this λ the legacy path is known-bad even at modest sample counts
_SMALL_LAMBDA = 0.1


def _warn_legacy_woodbury(ridge_lambda: Optional[float] = None) -> None:
    hazard = (
        " At small ridge_lambda the subtractive update CANCELS"
        " catastrophically in fp32 — expect a visibly wrong W."
        if ridge_lambda is not None and ridge_lambda < _SMALL_LAMBDA
        else ""
    )
    warnings.warn(
        "Fed3ROnline/woodbury_update is deprecated: the subtractive Woodbury"
        " update is numerically unstable in fp32. Use the factored state"
        " (init_factored/factored_update/factored_solution) or the streaming"
        " engine (repro.federated.streaming_engine)." + hazard,
        DeprecationWarning,
        stacklevel=3,
    )


def init_online(d: int, n_classes: int, ridge_lambda: float) -> Fed3ROnline:
    _warn_legacy_woodbury(ridge_lambda)
    return Fed3ROnline(
        Ainv=jnp.eye(d, dtype=jnp.float32) / ridge_lambda,
        b=jnp.zeros((d, n_classes), jnp.float32),
    )


def woodbury_update(state: Fed3ROnline, features: jax.Array, labels: jax.Array) -> Fed3ROnline:
    """DEPRECATED rank-n update with a new client's batch Z (n, d):

    (A + ZᵀZ)⁻¹ = A⁻¹ − A⁻¹Zᵀ (I + Z A⁻¹ Zᵀ)⁻¹ Z A⁻¹

    The subtraction is the fp32 hazard; prefer :func:`factored_update`.
    """
    Z = features.astype(jnp.float32)
    n = Z.shape[0]
    C = state.b.shape[1]
    AiZt = state.Ainv @ Z.T  # (d, n)
    K = jnp.eye(n, dtype=jnp.float32) + Z @ AiZt  # (n, n)
    L = jax.scipy.linalg.cho_factor(K, lower=True)
    Ainv = state.Ainv - AiZt @ jax.scipy.linalg.cho_solve(L, AiZt.T)
    b = state.b + Z.T @ jax.nn.one_hot(labels, C, dtype=jnp.float32)
    return Fed3ROnline(Ainv=Ainv, b=b)


def online_solution(
    state: Union[Fed3RFactored, Fed3ROnline], normalize: bool = True
) -> jax.Array:
    """Solution of either online state; routes through the factored path.

    Given a :class:`Fed3RFactored` this IS :func:`factored_solution` (two
    triangular solves).  The legacy :class:`Fed3ROnline` branch is kept for
    compatibility and warns: its W inherits the accumulated cancellation
    error of the carried A⁻¹.
    """
    if isinstance(state, Fed3RFactored):
        return factored_solution(state, normalize)
    _warn_legacy_woodbury()
    W = state.Ainv @ state.b
    if normalize:
        W = normalize_columns(W)
    return W

"""Multi-tenant personalization engine — batched closed-form per-client heads.

The fourth engine of the family (batch statistics → rounds → streaming →
personalization): the global ridge head is immune to heterogeneity
precisely because it ignores per-client structure, but cross-device
serving wants PER-USER heads.  The closed form makes them nearly free —

    W_k = (A + α_k·A_k + λI)⁻¹ (b + α_k·b_k)

is a rank-n_k Cholesky update away from the shared factored state
(:class:`repro.core.fed3r.Fed3RFactored` carries L with L Lᵀ = A + λI), so
K personalized heads solve in ONE jitted dispatch instead of K re-solves:

* the cohort arrives as a :class:`repro.data.pipeline.PackedPersonalCohort`
  (padded ``(K, max_n, ...)`` arrays with masks + a per-client holdout
  split, canonical id order — bit-invariant to request order);
* the rank-n updates G_k = L Lᵀ + α_k·Z_kᵀZ_k batch through the
  grid-over-heads Pallas kernel (:func:`repro.kernels.batched_chol_gram`)
  on TPU and batched XLA GEMMs elsewhere, with α_k folded in by √α_k
  pre-scaling; the K refactorizations and 2K triangular solves are
  vmapped/batched XLA linalg;
* per-client α_k is selected INSIDE the same dispatch by a closed-form
  held-out score swept over a static α grid (vmap over grid × clients):
  each candidate head is solved from the client's train split and scored
  on its holdout split — 0/1 error of the served head by default, or the
  raw ridge residual — then the winning α_k refits on the client's full
  data;
* α = 0 reproduces the global :func:`repro.core.fed3r.factored_solution`
  BITWISE — the global factor L and rhs b are selected unchanged rather
  than recomputed, so a degenerate tenant (no data, or α grid pinned to 0)
  serves exactly the global classifier.

:class:`ReferencePersonalizedLoop` preserves the per-client shape — one
jitted global solve plus one jitted re-solve per client (K+1 dispatches
for a K-head cohort) — as the dispatch baseline and the parity oracle
(``benchmarks/bench_personalize.py``).  The multi-tenant serving layer
(LRU head cache over a live arrival stream) is
:mod:`repro.launch.serve_heads`.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fed3r
from repro.core.fed3r import FP32, Fed3RFactored, Fed3RStats
from repro.data.pipeline import PackedPersonalCohort
from repro.federated.dist import (
    DistConfig,
    DistContext,
    DistDispatchMixin,
    resolve_use_kernel,
)
from repro.kernels import batched_chol_gram as batched_chol_gram_kernel
from repro.sharding.specs import replicated


@dataclass(frozen=True)
class PersonalizeConfig:
    """Static personalization-engine configuration (trace-time constants).

    ``alpha_grid`` is the candidate set the held-out sweep selects from;
    clients whose holdout split is empty (single-sample clients, or
    ``holdout_frac=0`` at pack time) fall back to ``alpha_grid[0]``, so
    put the conservative default (typically ``0.0`` = global head) first.

    ``dist`` is the shared distributed-execution config: with
    ``DistConfig(aggregation="psum", mesh=...)`` the dist layer shards the
    cohort axis over the mesh's data axes — each device solves only its
    K/N heads against the replicated (L, b) and the solved heads are
    gathered back (the cohort reduction is a gather, not a psum, since
    heads are per-tenant); pack with ``pack_personal_cohort(...,
    mesh=mesh)`` so the cohort divides.
    """

    n_classes: int
    alpha_grid: Tuple[float, ...] = (0.0, 0.25, 1.0, 4.0)
    normalize: bool = True  # per-class column normalization of served heads
    selection: str = "error"  # α score: "error" (0/1 held-out) | "sse" (ridge)
    use_kernel: Optional[bool] = None  # None → auto (Pallas on TPU, XLA else)
    dist: DistConfig = field(default_factory=DistConfig)  # mesh scale-out

    def __post_init__(self):
        if not self.alpha_grid:
            raise ValueError("alpha_grid must be non-empty")
        if any(a < 0.0 for a in self.alpha_grid):
            raise ValueError(f"alpha_grid must be >= 0, got {self.alpha_grid}")
        if self.selection not in ("error", "sse"):
            raise ValueError(f"unknown selection score: {self.selection!r}")


class PersonalizedHeads(NamedTuple):
    """The batched solve's output: K per-tenant heads + selection trace."""

    W: jax.Array  # (K, d, C) personalized classifiers (cohort order)
    alpha: jax.Array  # (K,) selected per-client interpolation weight
    score: jax.Array  # (K,) held-out ridge score at the selected α (0 if no sweep)
    client_ids: jax.Array  # (K,) int32 tenant ids, -1 = padded slot


class PersonalizationEngine(DistDispatchMixin):
    """K personalized heads over a shared factored state in ONE dispatch.

    ``solve_heads`` sweeps the α grid per client and refits; ``solve_at``
    skips the sweep and solves at caller-provided α_k (e.g. cached
    per-tenant values, or the reference-parity path).  Both are single
    jitted dispatches over the whole cohort.
    """

    def __init__(self, cfg: PersonalizeConfig):
        self.cfg = cfg
        self.dist = DistContext(cfg.dist, engine="personalization")
        # mesh mode: replicate the shared factored state, shard the cohort
        # axis of the packed client arrays, gather the per-tenant outputs
        # back along the same axis (no reduction: heads are per-client)
        sharded = self.dist.data_spec()
        common = (replicated(), replicated(), sharded, sharded, sharded, sharded)
        self._solve = self.dist.jit(
            self._heads_impl,
            in_specs=common,
            out_specs=(sharded, sharded, sharded),
            donate=False,  # (L, b) outlive the dispatch; nothing is carried
        )
        self._solve_at = self.dist.jit(
            self._heads_at_impl,
            in_specs=common,
            out_specs=sharded,
            donate=False,
        )

    # ---- pure core --------------------------------------------------------

    def _use_kernel(self) -> bool:
        return resolve_use_kernel(self.cfg.use_kernel)

    def _design(self, x, y, m):
        """Masked per-client designs: (K, N, d) features, (K, N, C) targets."""
        z = x.astype(jnp.float32) * m[..., None]
        yh = jax.nn.one_hot(y, self.cfg.n_classes, dtype=jnp.float32)
        return z, yh * m[..., None]

    def _batched_solve(self, L_use, rhs):
        """2K triangular solves, optionally normalized — the head refresh."""
        W = jax.vmap(
            lambda Lx, rx: jax.scipy.linalg.cho_solve((Lx, True), rx)
        )(L_use, rhs)
        if self.cfg.normalize:
            W = fed3r.normalize_columns(W, axis=1)
        return W

    def _refit(self, L, b, z, yh, alphas):
        """Batched rank-n refit at the selected α_k over full client data.

        α_k folds into the Gram bilinearly via √α_k pre-scaling, so the
        fused kernel stays scale-free.  α_k = 0 rows select a global head
        computed by :func:`repro.core.fed3r.factored_solution`'s exact ops
        (ONE unbatched solve — XLA's batched triangular solve lowers
        differently and would break the bitwise guarantee).
        """
        s = jnp.sqrt(alphas)[:, None, None]
        zs = z * s
        ys = yh * s
        if self._use_kernel():
            G, B = batched_chol_gram_kernel(L, zs, ys)
        else:
            G = fed3r.gram(L.T) + jnp.einsum("knd,kne->kde", zs, zs, precision=FP32)
            B = jnp.einsum("knd,knc->kdc", zs, ys, precision=FP32)
        Lk = jnp.linalg.cholesky(G)
        Wp = self._batched_solve(Lk, b[None] + B)
        Wg = fed3r.factored_solution(
            Fed3RFactored(L=L, b=b), self.cfg.normalize
        )
        return jnp.where(alphas[:, None, None] == 0.0, Wg[None], Wp)

    def _sweep(self, L, b, z_tr, yh_tr, z_ho, yh_ho, y, ho):
        """Closed-form α selection: grid × clients, one batched solve each.

        Candidate heads are solved from the TRAIN split only and scored on
        the HOLDOUT split (masks are already folded into the designs, so
        padded/train rows contribute exactly nothing):

        * ``"error"`` (default) — held-out misclassification count of the
          candidate head AS SERVED (normalized per config).  Robust: the
          raw ridge residual rewards prediction-magnitude growth, which
          biases toward large α on heavily shrunk global solutions even
          where decisions degrade.  Ties pick the FIRST grid entry, so an
          ascending grid starting at 0 degrades to the global head.
        * ``"sse"`` — the raw held-out ridge residual Σ_ho ‖Wᵀφ(x) − e_y‖²
          (the literal ridge objective; useful when scores, not decisions,
          are served).
        """
        grid = jnp.asarray(self.cfg.alpha_grid, jnp.float32)  # (G,)
        S = jnp.einsum("knd,kne->kde", z_tr, z_tr, precision=FP32)  # (K, d, d)
        Bt = jnp.einsum("knd,knc->kdc", z_tr, yh_tr, precision=FP32)  # (K, d, C)
        g = grid[:, None, None, None]
        Lg = jnp.linalg.cholesky(fed3r.gram(L.T) + g * S[None])  # (G, K, d, d)
        rhs = b + g * Bt[None]  # (G, K, d, C)
        W = jax.vmap(
            jax.vmap(lambda Lx, rx: jax.scipy.linalg.cho_solve((Lx, True), rx))
        )(Lg, rhs)
        if self.cfg.selection == "error":
            if self.cfg.normalize:
                W = fed3r.normalize_columns(W, axis=2)
            pick = jnp.argmax(
                jnp.einsum("knd,gkdc->gknc", z_ho, W, precision=FP32), axis=-1
            )  # (G, K, N)
            score = jnp.sum(
                ho[None] * (pick != y[None]).astype(jnp.float32), axis=2
            )  # (G, K)
        else:
            resid = jnp.einsum("knd,gkdc->gknc", z_ho, W, precision=FP32) - yh_ho[None]
            score = jnp.sum(resid**2, axis=(2, 3))  # (G, K)
        idx = jnp.argmin(score, axis=0)  # (K,) ties → first grid entry
        return grid[idx], jnp.take_along_axis(score, idx[None, :], axis=0)[0]

    def _heads_impl(self, L, b, x, y, m, ho) -> Tuple[jax.Array, ...]:
        z, yh = self._design(x, y, m)
        if len(self.cfg.alpha_grid) == 1:  # no sweep: α is pinned
            K = y.shape[0]
            alphas = jnp.full((K,), self.cfg.alpha_grid[0], jnp.float32)
            score = jnp.zeros((K,), jnp.float32)
        else:
            tr = (1.0 - ho)[..., None]  # holdout ⊆ mask, so z·tr is the train design
            alphas, score = self._sweep(
                L, b, z * tr, yh * tr, z * ho[..., None], yh * ho[..., None],
                y, ho,
            )
        return self._refit(L, b, z, yh, alphas), alphas, score

    def _heads_at_impl(self, L, b, x, y, m, alphas) -> jax.Array:
        z, yh = self._design(x, y, m)
        return self._refit(L, b, z, yh, alphas)

    # ---- host API ---------------------------------------------------------

    def solve_heads(
        self, state: Fed3RFactored, packed: PackedPersonalCohort
    ) -> PersonalizedHeads:
        """Sweep α and solve K personalized heads in ONE jitted dispatch."""
        with self.dist.telemetry.span("solve_heads", engine="personalization"):
            return self._solve_heads(state, packed)

    def _solve_heads(
        self, state: Fed3RFactored, packed: PackedPersonalCohort
    ) -> PersonalizedHeads:
        self.dist.dispatch()
        W, alphas, score = self._solve(
            state.L,
            state.b,
            jnp.asarray(packed.inputs),
            jnp.asarray(packed.labels),
            jnp.asarray(packed.mask),
            jnp.asarray(packed.holdout),
        )
        return PersonalizedHeads(
            W=W, alpha=alphas, score=score,
            client_ids=jnp.asarray(packed.client_ids),
        )

    def solve_at(
        self,
        state: Fed3RFactored,
        packed: PackedPersonalCohort,
        alphas: jax.Array,  # (K,) per-client weights, no selection sweep
    ) -> PersonalizedHeads:
        """Solve K heads at fixed per-client α_k in ONE jitted dispatch."""
        with self.dist.telemetry.span("solve_at", engine="personalization"):
            return self._solve_at_host(state, packed, alphas)

    def _solve_at_host(
        self,
        state: Fed3RFactored,
        packed: PackedPersonalCohort,
        alphas: jax.Array,
    ) -> PersonalizedHeads:
        self.dist.dispatch()
        a = jnp.asarray(alphas, jnp.float32)
        W = self._solve_at(
            state.L,
            state.b,
            jnp.asarray(packed.inputs),
            jnp.asarray(packed.labels),
            jnp.asarray(packed.mask),
            a,
        )
        return PersonalizedHeads(
            W=W, alpha=a, score=jnp.zeros_like(a),
            client_ids=jnp.asarray(packed.client_ids),
        )


class ReferencePersonalizedLoop:
    """The per-client shape: K+1 jitted dispatches for a K-head cohort.

    One global ``factored_solution`` (what a non-personalized server would
    serve) plus one per-client re-solve each — client statistics and the
    d×d refactorization re-dispatched per tenant.  Kept as the dispatch
    baseline and the numerical parity oracle the batched engine is measured
    against (``benchmarks/bench_personalize.py``).
    """

    def __init__(self, cfg: PersonalizeConfig):
        self.cfg = cfg
        self.dispatches = 0

        def one(L, b, x, y, m, a):
            stats = fed3r.client_stats(x, y, cfg.n_classes, m)
            return fed3r.personalized_solution(
                Fed3RFactored(L=L, b=b), stats, a, cfg.normalize
            )

        self._global = jax.jit(
            lambda L, b: fed3r.factored_solution(
                Fed3RFactored(L=L, b=b), cfg.normalize
            )
        )
        self._one = jax.jit(one)

    def solve_at(
        self,
        state: Fed3RFactored,
        packed: PackedPersonalCohort,
        alphas: jax.Array,  # (K,)
    ) -> Tuple[jax.Array, jax.Array]:
        """Returns (global W, stacked per-client heads (K, d, C))."""
        W_g = self._global(state.L, state.b)
        self.dispatches += 1
        heads = []
        for k in range(packed.cohort):
            heads.append(
                self._one(
                    state.L,
                    state.b,
                    jnp.asarray(packed.inputs[k]),
                    jnp.asarray(packed.labels[k]),
                    jnp.asarray(packed.mask[k]),
                    jnp.asarray(alphas[k], jnp.float32),
                )
            )
            self.dispatches += 1
        return W_g, jnp.stack(heads)


def cohort_stats(packed: PackedPersonalCohort, n_classes: int) -> Fed3RStats:
    """Fold the whole cohort's masked statistics — the secure-agg oracle.

    The sum of per-client (A_k, b_k, n_k) over the packed cohort: what the
    server's aggregate must equal whether uploads are masked (secure
    aggregation) or not, and a convenient parity anchor for tests.
    """
    K, N = packed.labels.shape
    feats = jnp.asarray(packed.inputs).reshape((K * N,) + packed.inputs.shape[2:])
    return fed3r.client_stats(
        feats,
        jnp.asarray(packed.labels).reshape(-1),
        n_classes,
        jnp.asarray(packed.mask).reshape(-1),
    )

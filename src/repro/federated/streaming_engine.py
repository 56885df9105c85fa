"""Streaming FED3R arrival engine — batched stable Woodbury + live serving.

The third engine of the triptych (batch statistics → rounds → streaming):
the paper's recursive-least-squares formulation (Eq. 3) and its §6 future
work — clients arriving over time with new data — promoted from a
per-arrival Python loop over the fp32-hazardous subtractive
``woodbury_update`` to a first-class arrival-driven runtime:

* the timeline arrives as a :class:`repro.data.pipeline.PackedArrivals`
  (padded ``(n_waves, clients_per_wave, max_n, ...)`` arrays with masks);
* ALL T waves fold through ONE jitted ``lax.scan`` with donated state —
  1 dispatch for the whole stream instead of the loop's T
  (``benchmarks/bench_streaming.py``);
* the carried state is the numerically stable FACTORED form
  (:class:`repro.core.fed3r.Fed3RFactored` semantics): the lower Cholesky
  factor L of A + λI, advanced per wave by the additive rank-n update
  L ← chol(L Lᵀ + ZᵀZ) — no subtraction, no fp32 cancellation — with the
  served classifier refreshed by two triangular solves;
* the rank-n update GEMMs dispatch to the fused Pallas kernel
  (:func:`repro.kernels.chol_gram`) on TPU and XLA GEMMs elsewhere,
  mirroring the statistics engine's backend split;
* live serving is a refresh POLICY inside the scan: ``refresh_every=1``
  is refresh-on-arrival, ``k > 1`` refreshes every k-th wave and the
  :class:`WaveTrace` reports the staleness metric (waves and samples
  absorbed since the served W was last solved) per wave;
* mesh mode (:mod:`repro.federated.dist`) mirrors ``engine.aggregate``:
  ``"merge"`` folds the whole wave locally; ``"psum"`` all-reduces each
  wave's rank-n statistics over the data axes (two stages on a pod mesh:
  intra-pod ICI, then cross-pod DCN) before the replicated
  refactorization.  With ``DistConfig(mesh=...)`` the dist layer owns the
  shard_map: the wave-WIDTH axis (concurrent arrivals) is split over the
  data axes — the wave axis itself is the scanned arrival clock — so pack
  with ``pack_arrival_waves(..., mesh=mesh)``.  Unlike the batch engine,
  the per-wave psum is inherently on the critical path (wave t+1's factor
  needs the reduced wave-t Gram); ``refresh_every`` bounds the solve cost.

Compressed uplink (:mod:`repro.federated.compress`): with
``StreamConfig(wire=WireFormat(kind="int8" | "fp8" | "sketch"))`` each
wave's rank-n statistics (S, Δb) cross the wire compressed — quantized
client-side, landed in the carried Gram through the fused dequantize-
accumulate (merge), or roundtripped per device partial before the psum —
still one dispatch per timeline; ``"fp32"`` keeps the scan bitwise
identical to today.

Exactness: each wave's clients are canonically packed (sorted by id), so
the folded state — and the final W — is bitwise invariant to the
presentation order of concurrent arrivals; across waves the stream order
IS the semantics.  :class:`ReferenceArrivalLoop` preserves the seed-era
per-arrival shape (one jitted subtractive Woodbury dispatch per wave) as
the dispatch baseline and the numerical foil.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fed3r
from repro.core.fed3r import Fed3RFactored
from repro.core.random_features import RFFParams, rff_map
from repro.data.pipeline import PackedArrivals
from repro.federated import compress
from repro.federated.compress import WireFormat
from repro.federated.dist import (
    DistConfig,
    DistContext,
    DistDispatchMixin,
    resolve_use_kernel,
)
from repro.kernels import chol_gram as chol_gram_kernel
from repro.kernels import fed3r_stats as fed3r_stats_kernel
from repro.sharding.hints import hint
from repro.sharding.specs import replicated


@dataclass(frozen=True)
class StreamConfig:
    """Static streaming-engine configuration (all trace-time constants)."""

    n_classes: int
    ridge_lambda: float
    refresh_every: int = 1  # 1 = refresh-on-arrival; k > 1 = every k-th wave
    normalize: bool = True  # per-class column normalization of the served W
    use_kernel: Optional[bool] = None  # None → auto (Pallas on TPU, XLA else)
    dist: DistConfig = field(default_factory=DistConfig)  # backend/mesh/donate
    # statistics wire format (repro.federated.compress): each wave's rank-n
    # (S, Δb) upload crosses the wire compressed before it touches the
    # carried factor; "fp32" keeps the scan bitwise identical to today
    wire: WireFormat = field(default_factory=WireFormat)


class StreamState(NamedTuple):
    """Donated scan carry: factored statistics + the live-served classifier."""

    L: jax.Array  # (d, d) fp32 lower Cholesky factor of A + λI
    b: jax.Array  # (d, C) fp32 class-conditional feature sums
    n: jax.Array  # () fp32 samples absorbed
    W: jax.Array  # (d, C) fp32 currently SERVED classifier
    wave: jax.Array  # () int32 waves absorbed (the arrival clock)
    stale_waves: jax.Array  # () int32 waves since W was last solved
    stale_samples: jax.Array  # () fp32 samples absorbed since W was last solved

    @property
    def factored(self) -> Fed3RFactored:
        """The core factored-state view (for factored_solution etc.)."""
        return Fed3RFactored(L=self.L, b=self.b)


class WaveTrace(NamedTuple):
    """Per-wave scan outputs, stacked over the absorbed timeline."""

    n_seen: jax.Array  # (T,) fp32 cumulative samples after each wave
    refreshed: jax.Array  # (T,) bool — did this wave re-solve W?
    stale_waves: jax.Array  # (T,) int32 staleness of the served W, in waves
    stale_samples: jax.Array  # (T,) fp32 staleness of the served W, in samples


class StreamingEngine(DistDispatchMixin):
    """One-dispatch streaming FED3R over packed arrival timelines.

    ``feature_fn(params, flat_inputs) -> (n, d)`` maps each wave's packed
    raw inputs (flattened to ``(clients_per_wave·max_n, ...)``) to φ
    features inside the scan; ``None`` means inputs already are features.
    ``rff_params`` fuses the FED3R-RF map the same way, mirroring
    :class:`repro.federated.engine.AccumulationEngine`.
    """

    def __init__(
        self,
        cfg: StreamConfig,
        *,
        feature_fn: Optional[Callable[[Any, jax.Array], jax.Array]] = None,
        rff_params: Optional[RFFParams] = None,
    ):
        if cfg.refresh_every < 1:
            raise ValueError(f"refresh_every must be >= 1, got {cfg.refresh_every}")
        self.cfg = cfg
        self.feature_fn = feature_fn
        self.rff_params = rff_params
        self.wire = cfg.wire.resolved()  # fp8 → int8 fallback off-TPU
        self.dist = DistContext(cfg.dist, engine="streaming")
        # a lossy tier in a routed aggregation tree quantizes the reduced
        # Gram exactly like a lossy engine wire — same PSD guard applies
        self._tree_wire = cfg.dist.lossy_tier_wire
        # mesh mode: shard the wave-WIDTH axis (dim 1; dim 0 is the scanned
        # arrival clock) over the data axes; state/params replicated
        sharded = self.dist.data_spec(axis=1)
        self._absorb = self.dist.jit(
            self.absorb_scan,
            in_specs=(replicated(), sharded, sharded, sharded, replicated()),
            out_specs=(replicated(), replicated()),
        )
        self._refresh = jax.jit(self._refresh_impl)
        # absorb_stats rejects dist-owned meshes (pre-reduced inputs would
        # broadcast-then-psum); plain jit keeps mesh-mode construction valid
        self._absorb_stats = jax.jit(self._absorb_stats_impl)

    def init(self, d: int) -> StreamState:
        with self.dist.telemetry.span("init", engine="streaming"):
            fac = fed3r.init_factored(d, self.cfg.n_classes, self.cfg.ridge_lambda)
            return StreamState(
                L=fac.L,
                b=fac.b,
                n=jnp.zeros((), jnp.float32),
                W=jnp.zeros((d, self.cfg.n_classes), jnp.float32),
                wave=jnp.zeros((), jnp.int32),
                stale_waves=jnp.zeros((), jnp.int32),
                stale_samples=jnp.zeros((), jnp.float32),
            )

    # ---- pure core (also usable directly inside shard_map) ----------------

    def _use_kernel(self) -> bool:
        return resolve_use_kernel(self.cfg.use_kernel)

    def _wire_fn(self):
        """The dist layer's compressed-payload hook (None under fp32)."""
        if self.wire.kind == "fp32":
            return None

        def roundtrip(tree):
            S, dB, nw = tree
            S, dB = compress.wire_roundtrip(S, dB, self.wire, self.cfg.use_kernel)
            return (S, dB, nw)

        return roundtrip

    def _solve(self, L: jax.Array, b: jax.Array) -> jax.Array:
        """Two triangular solves against the carried factor (the refresh)."""
        return fed3r.factored_solution(
            Fed3RFactored(L=L, b=b), self.cfg.normalize
        )

    def _wave_body(self, state: StreamState, wave, params: Any) -> Tuple[StreamState, Any]:
        x, y, m = wave  # (P, N, ...), (P, N), (P, N)
        flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
        # constrain the wave batch over the ambient mesh's data axes so
        # feature extraction data-parallelizes; exact no-op otherwise
        flat = hint(flat, "batch")
        feats = flat if self.feature_fn is None else self.feature_fn(params, flat)
        if self.rff_params is not None:
            feats = rff_map(self.rff_params, feats)
        z, yh, nw = fed3r.masked_design(
            feats, y.reshape(-1), self.cfg.n_classes, m.reshape(-1)
        )

        if self.cfg.dist.aggregation == "psum":
            # local rank-n statistics, all-reduced (two stages on a pod
            # mesh) before the replicated refactorization — the fused G
            # kernel would double-count L Lᵀ.  A compressed wire format
            # rides the dist hook: each device's partial (S, Δb) crosses
            # the ICI/DCN wire compressed, dequantized at the boundary.
            if self._use_kernel():
                S, dB = fed3r_stats_kernel(z, yh)
            else:
                S, dB = fed3r.gram(z), fed3r.gram(z, yh)
            S_local = S
            S, dB, nw = self.dist.all_reduce((S, dB, nw), wire_fn=self._wire_fn())
            G = fed3r.gram(state.L.T) + S
            b = state.b + dB
        elif self.wire.kind != "fp32":
            # compressed uplink, merge backend: the wave's rank-n upload
            # (S, Δb) quantizes client-side and lands in the carried Gram /
            # class sums through the fused dequantize-accumulate — the
            # fused G kernel is bypassed because the wire sits between the
            # sample GEMMs and the factor reconstruction
            if self._use_kernel():
                S, dB = fed3r_stats_kernel(z, yh)
            else:
                S, dB = fed3r.gram(z), fed3r.gram(z, yh)
            G, b = compress.roundtrip_add(
                fed3r.gram(state.L.T), state.b, S, dB, self.wire, self.cfg.use_kernel
            )
            S_local = S
        elif self._use_kernel():
            G, dB = chol_gram_kernel(state.L, z, yh)
            b = state.b + dB
            S_local = None
        else:
            G = fed3r.gram(state.L.T) + fed3r.gram(z)
            dB = fed3r.gram(z, yh)
            b = state.b + dB
            S_local = None

        lossy = self.wire if self.wire.kind in ("int8", "fp8") else self._tree_wire
        if lossy is not None and S_local is not None:
            # quantization noise (engine wire OR a lossy tree tier) can push
            # the smallest eigenvalues of the received Ŝ negative on
            # rank-deficient waves (early stream, few samples ≪ d); factor
            # with data-dependent jitter — a ridge of a few quantization
            # steps, applied only when the plain Cholesky actually produced
            # NaN
            L = compress.psd_cholesky(
                G, compress.quant_spectral_bound(S_local, lossy)
            )
        else:
            L = jnp.linalg.cholesky(G)
        n = state.n + nw
        t = state.wave + 1

        refresh = (t % self.cfg.refresh_every) == 0
        W = jax.lax.cond(
            refresh, lambda: self._solve(L, b), lambda: state.W
        )
        stale_w = jnp.where(refresh, 0, state.stale_waves + 1).astype(jnp.int32)
        stale_n = jnp.where(refresh, 0.0, state.stale_samples + nw)
        out = (n, refresh, stale_w, stale_n)
        return StreamState(
            L=L, b=b, n=n, W=W, wave=t, stale_waves=stale_w, stale_samples=stale_n
        ), out

    def absorb_scan(
        self,
        state: StreamState,
        inputs: jax.Array,  # (T, P, N, ...)
        labels: jax.Array,  # (T, P, N)
        mask: jax.Array,  # (T, P, N)
        params: Any = None,  # feature_fn parameters (backbone weights)
    ) -> Tuple[StreamState, WaveTrace]:
        """Fold a whole arrival timeline — the jitted one-dispatch core."""

        def body(carry, wave):
            return self._wave_body(carry, wave, params)

        state, outs = jax.lax.scan(body, state, (inputs, labels, mask))
        return state, WaveTrace(*outs)

    def _absorb_stats_impl(
        self, state: StreamState, A: jax.Array, b: jax.Array, n: jax.Array
    ) -> StreamState:
        """Fold ALREADY-REDUCED statistics (ΣA_k, Σb_k, Σn_k) of one round.

        The round-level entry the asynchronous engine's retire shares
        (:meth:`repro.federated.async_engine.AsyncRoundEngine.retire_fold`):
        same all-reduce placement, same Gram reconstruction, same solve —
        under the ``merge`` backend and fp32 wire the two fold chains are
        BITWISE identical, which is what lets the async engine's drained W
        be cross-checked against a streaming replay of its retire sums.
        Always refreshes W (a retire is a serving point, not a wave).
        """
        S_A, S_b, S_n = self.dist.all_reduce((A, b, n), wire_fn=self._wire_fn())
        G = fed3r.gram(state.L.T) + S_A
        lossy = self.wire if self.wire.kind in ("int8", "fp8") else self._tree_wire
        if lossy is not None:
            L = compress.psd_cholesky(
                G, compress.quant_spectral_bound(S_A, lossy)
            )
        else:
            L = jnp.linalg.cholesky(G)
        b_new = state.b + S_b
        return StreamState(
            L=L,
            b=b_new,
            n=state.n + S_n,
            W=self._solve(L, b_new),
            wave=state.wave + 1,
            stale_waves=jnp.zeros((), jnp.int32),
            stale_samples=jnp.zeros((), jnp.float32),
        )

    def _refresh_impl(self, state: StreamState) -> StreamState:
        return state._replace(
            W=self._solve(state.L, state.b),
            stale_waves=jnp.zeros((), jnp.int32),
            stale_samples=jnp.zeros((), jnp.float32),
        )

    # ---- host API ---------------------------------------------------------

    def absorb(
        self, state: StreamState, packed: PackedArrivals, params: Any = None
    ) -> Tuple[StreamState, WaveTrace]:
        """Absorb T arrival waves in ONE jitted dispatch.

        Returns the advanced state (the served classifier is ``state.W``)
        and the per-wave :class:`WaveTrace`.
        """
        span = self.dist.telemetry.span
        with span("absorb", engine="streaming"):
            self.dist.dispatch()
            with span("place", engine="streaming"):
                args = self._args(packed)
            with span("launch", engine="streaming"):
                return self._absorb(state, *args, params)

    def lower(
        self, state: StreamState, packed: PackedArrivals, params: Any = None
    ) -> jax.stages.Lowered:
        """The absorb program for this timeline shape, lowered without
        running it (its compiled text shows which kernels it holds)."""
        return self._absorb.lower(state, *self._args(packed), params)

    def _args(self, packed: PackedArrivals):
        # the wave-width axis (dim 1) is the one mesh mode shards
        return tuple(
            self.dist.place(a, axis=1)
            for a in (packed.inputs, packed.labels, packed.mask)
        )

    def absorb_stats(
        self, state: StreamState, A: jax.Array, b: jax.Array, n: jax.Array
    ) -> StreamState:
        """Fold one round's pre-reduced (ΣA_k, Σb_k, Σn_k) in ONE dispatch.

        The integration point for round-granular producers (the async
        engine's retires, a batch statistics engine's cohort sums): no
        packing, no per-sample features — the statistics land directly in
        the carried factor and W refreshes.  Under ``psum`` the arguments
        are each shard's LOCAL partials and the call belongs inside an
        external shard_map over the pure ``_absorb_stats_impl`` core; a
        dist-owned mesh would broadcast-then-psum (overcounting), so it is
        rejected here.
        """
        if self.cfg.dist.mesh is not None:
            raise ValueError(
                "absorb_stats takes pre-reduced statistics; under a "
                "dist-owned mesh use absorb(), or shard_map the "
                "_absorb_stats_impl core over per-device partials"
            )
        with self.dist.telemetry.span("absorb_stats", engine="streaming"):
            self.dist.dispatch()
            return self._absorb_stats(
                state, jnp.asarray(A), jnp.asarray(b),
                jnp.asarray(n, dtype=jnp.float32),
            )

    def tiered_absorber(self, tree, **kwargs):
        """The N-tier fold entry point: an overlapped
        :class:`repro.federated.tiers.TieredAbsorber` pipeline over this
        engine (host-level tree; upper-tier reductions of segment t overlap
        the lower folds of segment t+1).  Lazy import — tiers builds on
        this module."""
        from repro.federated.tiers import TieredAbsorber

        return TieredAbsorber(self, tree, **kwargs)

    def refresh(self, state: StreamState) -> StreamState:
        """Force a classifier re-solve now (e.g. before a query burst)."""
        with self.dist.telemetry.span("refresh", engine="streaming"):
            self.dist.dispatch()
            return self._refresh(state)

    def classifier(self, state: StreamState) -> jax.Array:
        """The currently SERVED classifier (possibly stale, by policy)."""
        return state.W


class ReferenceArrivalLoop:
    """The seed-era per-arrival path: one jitted subtractive Woodbury
    dispatch per wave (T dispatches for a T-wave stream).

    Kept as the dispatch-count baseline the streaming engine is measured
    against and as the numerical foil: at small λ its carried A⁻¹ cancels
    catastrophically in fp32 (``benchmarks/bench_streaming.py`` reports the
    divergence).  Padding rows are zero in the packed arrays, hence exact
    no-ops in the Woodbury algebra too.
    """

    def __init__(self, cfg: StreamConfig):
        self.cfg = cfg
        self.dispatches = 0
        self._update = jax.jit(fed3r.woodbury_update)

    def init(self, d: int) -> fed3r.Fed3ROnline:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return fed3r.init_online(d, self.cfg.n_classes, self.cfg.ridge_lambda)

    def absorb(
        self, state: fed3r.Fed3ROnline, packed: PackedArrivals
    ) -> fed3r.Fed3ROnline:
        for t in range(packed.n_waves):
            x = packed.inputs[t]
            flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
            state = self._update(
                state, jnp.asarray(flat), jnp.asarray(packed.labels[t].reshape(-1))
            )
            self.dispatches += 1
        return state

    def classifier(self, state: fed3r.Fed3ROnline) -> jax.Array:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return fed3r.online_solution(state, self.cfg.normalize)


def batch_equivalent(
    packed: PackedArrivals, cfg: StreamConfig
) -> Tuple[jax.Array, fed3r.Fed3RStats]:
    """The batch re-solve over the whole timeline — the parity oracle.

    Folds every wave's masked statistics with the batch path
    (init_stats/merge/solve) and returns (W, stats); the streaming engine's
    final refreshed W must match this to fp32 tolerance.
    """
    T, P, N = packed.mask.shape
    feats = jnp.asarray(packed.inputs).reshape((T * P * N,) + packed.inputs.shape[3:])
    stats = fed3r.client_stats(
        feats,
        jnp.asarray(packed.labels).reshape(-1),
        cfg.n_classes,
        jnp.asarray(packed.mask).reshape(-1),
    )
    return fed3r.solve(stats, cfg.ridge_lambda, cfg.normalize), stats

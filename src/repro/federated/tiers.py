"""Hierarchical N-tier aggregation trees — edge → region → cloud.

Fed3R's statistics are ORDER-INVARIANT additive sums (paper §4.3): any
reduction topology yields the same A/b, so topology is a free performance
variable.  This module generalizes
:func:`repro.federated.dist.two_stage_psum` (one psum per mesh axis,
innermost first) into an arbitrary N-tier reduction tree where every tier
owns

* a BATCHING WINDOW — ``fan_in`` child payloads fold in ONE fixed order
  per tier, so with fp32 wires the final ``W`` stays bitwise equal to the
  flat psum on the engines' grid-exact statistics;
* a WIRE FORMAT — the payload crosses each boundary compressed
  (:mod:`repro.federated.compress`) and is dequantized exactly ONCE per
  boundary through the fused dequantize-accumulate path (int8 on the slow
  WAN tier, fp32 on ICI);
* a STALENESS BUDGET — how many segments the tier's upward reduction may
  trail the newest arrival, riding the PR-8 async ring semantics (the
  budget is the depth of the pending-reduction ring).

Two execution forms share one :class:`AggregationTree`:

* :meth:`AggregationTree.psum` — inside ``shard_map``: one psum per
  MESH-TIER axis, leaf tier first, each crossing optionally compressed.
  ``DistConfig(tree=...)`` routes every engine's
  :meth:`repro.federated.dist.DistContext.all_reduce` through it; with
  fp32 wires the emitted program is the two-stage psum generalized to N
  axes (bitwise identical at N ≤ 2 by construction).
* :meth:`AggregationTree.fold_stacked` / :class:`TieredAbsorber` — the
  host-tier form: stacked child payloads fold tier by tier inside ONE
  jitted program, and the absorber OVERLAPS the upper-tier (DCN/WAN)
  reduction + refactorization of segment t with the lower-tier fold and
  feature extraction of segment t+1 (double-buffered donated accumulators:
  the upper program donates the carried state while the next segment's
  lower program is already on the async dispatch stream).

Every tier crossing is metered through the unified telemetry registry —
``tier_wire_bytes_total{tier=...}`` / ``tier_batches_total{tier=...}``
counters, ``tier_lower``/``tier_upper`` spans, an overlap-efficiency gauge,
and flight-recorder events (``tier_batch_flushed``,
``tier_staleness_exceeded``, ``tier_wire_fallback``) that
``repro.launch.obs_report`` renders as the tree.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import fed3r
from repro.federated import compress
from repro.federated.compress import WireFormat
from repro.federated.costs import stats_wire_bytes
from repro.federated.dist import DistConfig, DistContext
from repro.federated.engine import shard_stats
from repro.federated.telemetry import Telemetry
from repro.launch.mesh import ICI_BW

# tier boundaries carry arbitrary statistics pytrees, so only the
# per-matrix formats are valid tier wires (sketch is a client-uplink
# format for PSD second moments, not a generic boundary format)
TIER_WIRE_KINDS = ("fp32", "int8", "fp8")


@dataclass(frozen=True)
class TierSpec:
    """One tier of the aggregation tree.

    ``fan_in`` is the tier's batching window: how many child payloads fold
    into one parent payload (for a mesh tier, the axis size).  ``wire`` is
    the format each child crosses this boundary in; ``bandwidth`` prices
    the crossing (``CostModel.tiered_allreduce``); ``staleness`` is the
    tier's pending-reduction budget in segments (only the TOP tier's
    budget drives the :class:`TieredAbsorber` pipeline depth); ``axis``
    names the mesh axis when the tier is a collective stage (``None`` for
    host-level tiers).
    """

    name: str
    fan_in: int
    wire: WireFormat = field(default_factory=WireFormat)
    bandwidth: float = ICI_BW
    staleness: int = 0
    axis: Optional[str] = None

    def __post_init__(self):
        if self.fan_in < 1:
            raise ValueError(f"tier {self.name!r}: fan_in must be >= 1, got {self.fan_in}")
        if self.staleness < 0:
            raise ValueError(
                f"tier {self.name!r}: staleness must be >= 0, got {self.staleness}"
            )
        if self.bandwidth <= 0:
            raise ValueError(
                f"tier {self.name!r}: bandwidth must be > 0, got {self.bandwidth}"
            )
        if self.wire.kind not in TIER_WIRE_KINDS:
            raise ValueError(
                f"tier {self.name!r}: wire kind {self.wire.kind!r} is not a "
                f"tier-boundary format (expected one of {TIER_WIRE_KINDS})"
            )


def _wire_leaf(x: Any) -> bool:
    """Leaves the tier wire applies to: ≥2-D float matrices (the d² Gram
    and d·C class-sum payloads).  Scalars and 1-D sidecars (sample counts,
    class counts) stay exact fp32 — the same convention as the engines'
    uplink compression."""
    return jnp.ndim(x) >= 2 and jnp.issubdtype(jnp.result_type(x), jnp.floating)


def _roundtrip_nd(x: jax.Array, fmt: WireFormat, use_kernel: Optional[bool]) -> jax.Array:
    """Per-matrix wire roundtrip, vmapped over any leading stack axes."""
    if x.ndim == 2:
        return compress.matrix_roundtrip(x, fmt, use_kernel)
    return jax.vmap(lambda m: _roundtrip_nd(m, fmt, use_kernel))(x)


def _roundtrip_add_nd(
    acc: jax.Array, x: jax.Array, fmt: WireFormat, use_kernel: Optional[bool]
) -> jax.Array:
    """Fused dequantize-accumulate, vmapped over any leading stack axes."""
    if x.ndim == 2:
        return compress.matrix_roundtrip_add(acc, x, fmt, use_kernel)
    return jax.vmap(lambda a, m: _roundtrip_add_nd(a, m, fmt, use_kernel))(acc, x)


@dataclass(frozen=True)
class AggregationTree:
    """An N-tier reduction tree, LEAF TIER FIRST (edge → region → cloud).

    ``leaves`` child payloads enter the first tier; each tier folds
    ``fan_in`` children per group, so tier i receives
    ``prod(fan_in[i:])`` payloads per reduction.  The fp32 tree is an
    exact reassociation of the flat sum — bitwise equal on the engines'
    grid-exact statistics for ANY fan-in assignment and tier permutation.
    """

    tiers: Tuple[TierSpec, ...]

    def __post_init__(self):
        object.__setattr__(self, "tiers", tuple(self.tiers))
        if not self.tiers:
            raise ValueError("an aggregation tree needs at least one tier")
        names = [t.name for t in self.tiers]
        if len(set(names)) != len(names):
            raise ValueError(f"tier names must be unique, got {names}")
        axes = [t.axis for t in self.tiers if t.axis is not None]
        if len(set(axes)) != len(axes):
            raise ValueError(f"mesh-tier axes must be unique, got {axes}")

    @property
    def leaves(self) -> int:
        n = 1
        for t in self.tiers:
            n *= t.fan_in
        return n

    @property
    def axes(self) -> Tuple[str, ...]:
        """Mesh axes of the collective tiers, leaf tier first."""
        return tuple(t.axis for t in self.tiers if t.axis is not None)

    @property
    def lossy_wire(self) -> Optional[WireFormat]:
        """The coarsest-boundary lossy wire (topmost non-fp32 tier), or
        ``None`` for an all-fp32 (bit-exact) tree.  Engines use it to pick
        the PSD-guarded Cholesky when a tree crossing quantizes."""
        for t in reversed(self.tiers):
            if t.wire.kind != "fp32":
                return t.wire
        return None

    def resolved(self) -> "AggregationTree":
        """Tier wires resolved for this backend (fp8 → int8 fallback)."""
        return AggregationTree(
            tuple(
                TierSpec(
                    name=t.name,
                    fan_in=t.fan_in,
                    wire=t.wire.resolved(),
                    bandwidth=t.bandwidth,
                    staleness=t.staleness,
                    axis=t.axis,
                )
                for t in self.tiers
            )
        )

    def validate_mesh_axes(self, axis_names: Sequence[str]) -> None:
        """A mesh-routed tree must cover the resolved reduce axes exactly,
        leaf tier on the INNERMOST axis — the same order
        :func:`repro.federated.dist.two_stage_psum` reduces in, which is
        what makes the fp32 tree program identical to the two-stage one."""
        want = tuple(reversed(tuple(axis_names)))
        if self.axes != want:
            raise ValueError(
                f"tree mesh axes {self.axes} must equal the reversed reduce "
                f"axes {want} (leaf tier innermost)"
            )

    # ---- collective form (inside shard_map) --------------------------------

    def psum(self, payload: Any, use_kernel: Optional[bool] = None) -> Any:
        """N-tier hierarchical all-reduce: per collective tier, LEAF FIRST,
        optionally wire-compress each device's partial (dequantized exactly
        once at the boundary), then psum over the tier's axis.  Host-level
        tiers (``axis=None``) are skipped — they fold via
        :meth:`fold_stacked`.  With fp32 wires this is exactly
        ``two_stage_psum`` generalized to N axes."""
        for tier in self.tiers:
            if tier.axis is None:
                continue
            if tier.wire.kind != "fp32":
                payload = jax.tree.map(
                    lambda x, t=tier: _roundtrip_nd(x, t.wire, use_kernel)
                    if _wire_leaf(x)
                    else x,
                    payload,
                )
            payload = jax.tree.map(
                partial(jax.lax.psum, axis_name=tier.axis), payload
            )
        return payload

    # ---- host-tier form (stacked fixed-order folds) ------------------------

    def fold_stacked(
        self,
        payload: Any,
        tiers: Optional[Sequence[TierSpec]] = None,
        use_kernel: Optional[bool] = None,
    ) -> Any:
        """Fold stacked child payloads tier by tier, one FIXED-ORDER fold
        per tier (groups of ``fan_in`` along the leading axis, children
        accumulated left to right).  Lossy tiers cross every child through
        the fused dequantize-accumulate; fp32 tiers are a strict left fold
        (an exact reassociation of the flat sum).  Returns the stacked
        parents of the last folded tier."""
        for tier in self.tiers if tiers is None else tuple(tiers):
            k = tier.fan_in

            def fold_leaf(x, tier=tier, k=k):
                if x.shape[0] % k:
                    raise ValueError(
                        f"tier {tier.name!r}: {x.shape[0]} stacked children "
                        f"do not group by fan_in={k}"
                    )
                g = x.reshape((x.shape[0] // k, k) + x.shape[1:])
                lossy = tier.wire.kind != "fp32" and _wire_leaf(g[:, 0])
                if lossy:
                    acc = jnp.zeros_like(g[:, 0], dtype=jnp.float32)
                    for i in range(k):
                        acc = _roundtrip_add_nd(acc, g[:, i], tier.wire, use_kernel)
                    return acc
                acc = g[:, 0]
                for i in range(1, k):
                    acc = acc + g[:, i]
                return acc

            payload = jax.tree.map(fold_leaf, payload)
        return payload

    def reduce(self, payloads: Sequence[Any], use_kernel: Optional[bool] = None) -> Any:
        """Reduce exactly ``leaves`` child payload pytrees through the full
        tree (host-level convenience over :meth:`fold_stacked`)."""
        payloads = list(payloads)
        if len(payloads) != self.leaves:
            raise ValueError(
                f"tree with fan-ins {tuple(t.fan_in for t in self.tiers)} "
                f"reduces {self.leaves} leaf payloads, got {len(payloads)}"
            )
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *payloads)
        folded = self.fold_stacked(stacked, use_kernel=use_kernel)
        return jax.tree.map(lambda x: x[0], folded)

    # ---- pricing ------------------------------------------------------------

    def as_cost_tiers(self) -> Tuple[dict, ...]:
        """The plain-data tier description ``CostModel.tiered_allreduce``
        prices (keeps :mod:`repro.federated.costs` jax-free)."""
        return tuple(
            {
                "name": t.name,
                "fan_in": t.fan_in,
                "wire": t.wire.kind,
                "bandwidth": t.bandwidth,
                "tile": t.wire.tile,
            }
            for t in self.tiers
        )


def two_stage_tree(axis_names: Sequence[str]) -> AggregationTree:
    """The fp32 tree equivalent of today's two-stage psum over
    ``axis_names`` (outermost first, as :class:`DistConfig` resolves them):
    routing ``DistConfig(tree=two_stage_tree(axes))`` is bitwise identical
    to routing without a tree."""
    names = tuple(axis_names)
    if not names:
        raise ValueError("two_stage_tree needs at least one mesh axis")
    return AggregationTree(
        tuple(TierSpec(name=ax, fan_in=1, axis=ax) for ax in reversed(names))
    )


def mesh_tree(
    mesh: jax.sharding.Mesh,
    wires: Optional[dict] = None,
    bandwidths: Optional[dict] = None,
) -> AggregationTree:
    """An N-tier tree over a tier mesh (:func:`repro.launch.mesh.
    make_tier_host_mesh`): one collective tier per batch-carrying axis,
    innermost (leaf/edge) first, fan-in = axis size.  ``wires`` /
    ``bandwidths`` map axis name → per-tier overrides."""
    from repro.launch.mesh import data_axes

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    wires = wires or {}
    bandwidths = bandwidths or {}
    tiers = []
    for ax in reversed(data_axes(mesh)):
        kwargs = {}
        if ax in wires:
            kwargs["wire"] = wires[ax]
        if ax in bandwidths:
            kwargs["bandwidth"] = bandwidths[ax]
        tiers.append(TierSpec(name=ax, fan_in=sizes[ax], axis=ax, **kwargs))
    return AggregationTree(tuple(tiers))


class TieredAbsorber:
    """Overlapped N-tier absorb pipeline over a streaming engine.

    Each SEGMENT is one batch of ``tree.leaves`` edge payload blocks —
    ``(leaves, N, ...)`` features/labels/mask.  The pipeline splits the
    work at the top-tier boundary into two jitted programs:

    * LOWER — feature extraction, per-leaf masked statistics, and every
      tier fold below the top (the fast intra-region legs);
    * UPPER — the top-tier (DCN/WAN) crossing, Gram refactorization and
      solve, donating the carried :class:`StreamState` (the double-buffered
      accumulator: while segment t's upper program runs, segment t+1's
      lower program is already on the dispatch stream).

    With ``overlap=True`` the upper reduction of segment t is issued AFTER
    the lower dispatch of segment t+1, so the slow top-tier leg overlaps
    the next segment's extraction; the top tier's ``staleness`` budget
    bounds how many segments the served classifier may trail (the PR-8
    ring semantics — exceeding the budget forces the oldest pending
    reduction and logs ``tier_staleness_exceeded``).  ``overlap=False``
    fuses both programs into ONE blocking dispatch per segment — the
    two-stage baseline generalized to N tiers, bitwise equal to the
    overlapped result and to ``engine.absorb_stats`` of the flat sum.
    """

    def __init__(
        self,
        engine: Any,  # StreamingEngine (duck-typed)
        tree: AggregationTree,
        *,
        overlap: bool = True,
        cost_model: Optional[Any] = None,
        telemetry: Optional[Telemetry] = None,
    ):
        if any(t.axis is not None for t in tree.tiers):
            raise ValueError(
                "TieredAbsorber folds host-level tiers; mesh tiers "
                "(axis=...) route through DistConfig(tree=...) instead"
            )
        if engine.cfg.dist.mesh is not None or engine.cfg.dist.aggregation != "merge":
            raise ValueError(
                "TieredAbsorber owns the reduction topology; give it a "
                "merge-backend engine without a dist-owned mesh"
            )
        if engine.wire.kind != "fp32":
            raise ValueError(
                "tier wires own the compression here; use an fp32 engine "
                "wire and put int8/fp8 on the tree's tiers"
            )
        self.engine = engine
        self.tree = tree.resolved()
        for before, after in zip(tree.tiers, self.tree.tiers):
            if before.wire.kind != after.wire.kind:
                tel = telemetry if telemetry is not None else engine.dist.telemetry
                tel.event(
                    "tier_wire_fallback",
                    tier=after.name,
                    requested=before.wire.kind,
                    using=after.wire.kind,
                )
        top = self.tree.tiers[-1]
        self.depth = top.staleness if overlap else 0
        if overlap and self.depth < 1:
            raise ValueError(
                "overlap needs a top-tier staleness budget >= 1 "
                "(the pending-reduction ring depth); got "
                f"staleness={top.staleness}"
            )
        self.dist = DistContext(
            DistConfig(),
            engine="tiers",
            telemetry=telemetry if telemetry is not None else engine.dist.telemetry,
        )
        self.telemetry = self.dist.telemetry
        self.cost_model = cost_model
        donate = (0,) if engine.cfg.dist.donate else ()
        self._lower_fn = jax.jit(self._lower_impl)
        self._upper_fn = jax.jit(self._upper_impl, donate_argnums=donate)
        self._blocking_fn = jax.jit(self._blocking_impl, donate_argnums=donate)
        self._pending: deque = deque()
        self._state = None
        self._segments = 0
        self._absorb_syncs = 0
        self._bytes_by_tier = {t.name: 0.0 for t in self.tree.tiers}

    # ---- jitted cores -------------------------------------------------------

    def _leaf_payload(self, feats, labels, mask, params):
        """Per-leaf masked statistics: feature extraction over the whole
        segment (the packed-flat idiom of the engines), then one vmapped
        fused stats GEMM per edge block."""
        eng = self.engine
        leaves = feats.shape[0]
        flat = feats.reshape((leaves * feats.shape[1],) + feats.shape[2:])
        if eng.feature_fn is not None:
            flat = eng.feature_fn(params, flat)
        if getattr(eng, "rff_params", None) is not None:
            from repro.core.random_features import rff_map

            flat = rff_map(eng.rff_params, flat)
        phi = flat.reshape((leaves, feats.shape[1], flat.shape[-1]))
        stats = jax.vmap(
            lambda x, y, m: shard_stats(
                x, y, eng.cfg.n_classes, m, use_kernel=eng.cfg.use_kernel
            )
        )(phi, labels, mask)
        return (stats.A, stats.b, stats.n.astype(jnp.float32))

    def _lower_impl(self, feats, labels, mask, params):
        payload = self._leaf_payload(feats, labels, mask, params)
        return self.tree.fold_stacked(
            payload, tiers=self.tree.tiers[:-1], use_kernel=self.engine.cfg.use_kernel
        )

    def _upper_impl(self, state, children):
        top = self.tree.tiers[-1]
        S, dB, nw = jax.tree.map(
            lambda x: x[0],
            self.tree.fold_stacked(
                children, tiers=(top,), use_kernel=self.engine.cfg.use_kernel
            ),
        )
        G = fed3r.gram(state.L.T) + S
        if top.wire.kind in ("int8", "fp8"):
            L = compress.psd_cholesky(G, compress.quant_spectral_bound(S, top.wire))
        else:
            L = jnp.linalg.cholesky(G)
        b = state.b + dB
        return state._replace(
            L=L,
            b=b,
            n=state.n + nw,
            W=self.engine._solve(L, b),
            wave=state.wave + 1,
            stale_waves=jnp.zeros((), jnp.int32),
            stale_samples=jnp.zeros((), jnp.float32),
        )

    def _blocking_impl(self, state, feats, labels, mask, params):
        return self._upper_impl(state, self._lower_impl(feats, labels, mask, params))

    # ---- host pipeline ------------------------------------------------------

    def reset(self, d: int) -> None:
        """(Re)initialize the carried state for feature dimension ``d``."""
        self._pending.clear()
        self._state = self.engine.init(d)
        self._segments = 0
        self._absorb_syncs = 0
        self._bytes_by_tier = {t.name: 0.0 for t in self.tree.tiers}

    def _account_tiers(self, tiers, entering: int) -> int:
        """Meter one segment's crossings for the given tiers: ``entering``
        payloads arrive at the first of them; each tier folds ``fan_in``
        children per batch.  Pure host-side integer math — zero jax."""
        d, C = self._state.L.shape[0], self.engine.cfg.n_classes
        level = {t.name: i for i, t in enumerate(self.tree.tiers)}
        for t in tiers:
            per_child = stats_wire_bytes(d, C, t.wire.kind, tile=t.wire.tile)
            nbytes = entering * per_child
            self._bytes_by_tier[t.name] += nbytes
            self.telemetry.counter(
                "tier_wire_bytes_total", tier=t.name, level=level[t.name],
                wire=t.wire.kind,
            ).inc(int(nbytes))
            self.telemetry.counter(
                "tier_batches_total", tier=t.name, level=level[t.name]
            ).inc(entering // t.fan_in)
            self.telemetry.event(
                "tier_batch_flushed",
                tier=t.name,
                children=entering,
                batches=entering // t.fan_in,
                wire=t.wire.kind,
            )
            entering //= t.fan_in
        return entering

    def _flush_one(self) -> None:
        children = self._pending.popleft()
        with self.telemetry.span("tier_upper", engine="tiers"):
            self.dist.dispatch()
            self._state = self._upper_fn(self._state, children)
        self._account_tiers((self.tree.tiers[-1],), self.tree.tiers[-1].fan_in)

    def absorb_segment(self, feats, labels, mask, params: Any = None) -> None:
        """Absorb one segment of ``tree.leaves`` edge blocks.

        Blocking mode (``overlap=False``): ONE fused dispatch, host-synced
        per segment.  Overlapped mode: the segment's LOWER program is
        dispatched immediately; its UPPER (top-tier) reduction is deferred
        onto the pending ring and issued once a newer segment is in flight
        (or at :meth:`drain`), never letting the ring exceed the top
        tier's staleness budget.
        """
        feats = jnp.asarray(feats)
        labels = jnp.asarray(labels)
        mask = jnp.asarray(mask)
        if feats.shape[0] != self.tree.leaves:
            raise ValueError(
                f"segment carries {feats.shape[0]} edge blocks; the tree "
                f"folds {self.tree.leaves}"
            )
        if self._state is None:
            if self.engine.feature_fn is not None:
                raise ValueError(
                    "feature_fn hides the feature dim; call reset(d) first"
                )
            self.reset(int(feats.shape[-1]))
        if self.depth == 0:
            with self.telemetry.span("tier_absorb", engine="tiers"):
                self.dist.dispatch()
                self._state = self._blocking_fn(
                    self._state, feats, labels, mask, params
                )
            jax.block_until_ready(self._state.W)
            self._absorb_syncs += 1
            self._segments += 1
            self._account_tiers(self.tree.tiers, self.tree.leaves)
            return
        with self.telemetry.span("tier_lower", engine="tiers"):
            self.dist.dispatch()
            children = self._lower_fn(feats, labels, mask, params)
        self._segments += 1
        self._account_tiers(self.tree.tiers[:-1], self.tree.leaves)
        self._pending.append(children)
        while len(self._pending) > self.depth:
            self.telemetry.event(
                "tier_staleness_exceeded",
                tier=self.tree.tiers[-1].name,
                pending=len(self._pending),
                budget=self.depth,
            )
            self._flush_one()

    def classifier(self):
        """The currently served W — trails the newest segment by at most
        the top tier's staleness budget."""
        if self._state is None:
            raise ValueError("no segments absorbed yet")
        return self._state.W

    def drain(self):
        """Retire every pending reduction, sync, and publish the gauges.

        ``tier_overlap_efficiency`` = 1 − host_syncs/segments over the
        absorb phase: 0.0 for the blocking path (one sync per segment),
        → 1.0 when every upper reduction overlapped a newer segment.
        With a ``cost_model``, ``tier_cost_model_drift`` compares metered
        tier bytes against ``CostModel.tiered_allreduce``'s prediction.
        """
        while self._pending:
            self._flush_one()
        jax.block_until_ready(self._state.W)
        if self._segments:
            eff = 1.0 - self._absorb_syncs / self._segments
            self.telemetry.gauge("tier_overlap_efficiency").set(eff)
        if self.cost_model is not None and self._segments:
            priced = self.cost_model.tiered_allreduce(self.tree.as_cost_tiers())
            model = priced["uplink_bytes_total"] * self._segments
            measured = sum(self._bytes_by_tier.values())
            if model > 0:
                self.telemetry.gauge("tier_cost_model_drift").set(measured / model)
        return self._state

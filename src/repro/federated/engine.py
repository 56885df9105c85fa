"""Unified client-shard accumulation engine for FED3R statistics.

Every consumer of Eq. 5/6 — the simulator drivers
(:mod:`repro.federated.fed3r_driver`), the gradient-FL simulator and the
datacenter path (:mod:`repro.launch.steps` / ``launch/train.py``) — funnels
through this module instead of rolling its own padding + per-client
dispatch loop:

* :func:`shard_stats` — the fused masked (A, b, n) contraction for one
  padded sample block, dispatching to the Pallas kernel
  (:func:`repro.kernels.fed3r_stats`) on TPU (interpret mode in tests) and
  the XLA reference GEMMs elsewhere.
* :func:`aggregate` — the two server-aggregation backends behind one
  interface: ``"merge"`` (simulator: the scan carry IS the merged sum) and
  ``"psum"`` (mesh: the dist layer's two-stage all-reduce over the data
  axes inside shard_map).
* :class:`AccumulationEngine` — packed accumulation over a
  :class:`repro.data.pipeline.PackedClients`: ONE jitted ``lax.scan`` over
  shards (donated accumulator buffers), an inner scan folding the clients of
  each shard in canonical id order.  K sampled clients cost
  ⌈K/clients_per_shard⌉ scan steps inside a single dispatch, vs the K jit
  dispatches of the naive per-client loop.

Scale-out (:mod:`repro.federated.dist`): with ``DistConfig(mesh=...)`` the
same core runs as ONE shard_map dispatch over the mesh — the shard axis is
split over the data axes (pack with ``pack_client_shards(..., mesh=mesh)``
so it divides), each device scans only its local shards, and the final
A/b/class-count statistics are all-reduced hierarchically (intra-pod ICI,
then cross-pod DCN).  The all-reduce is issued once, AFTER the scan, so
feature extraction — the expensive leg — never serializes against
per-shard collectives.

Compressed uplink (:mod:`repro.federated.compress`): with
``EngineConfig(wire=WireFormat(kind="int8" | "fp8" | "sketch"))`` every
client's (A_k, b_k) crosses the wire quantized/sketched and folds into the
fp32 accumulator through the fused dequantize-accumulate kernel — same one
dispatch, ~4× (int8/fp8) fewer uplink bytes; ``"fp32"`` (default) keeps
the fold bitwise identical to the uncompressed engine.

Exactness: per-client blocks have identical padded shapes, and the
client fold is a strict left fold in sorted-id order regardless of how
clients land in shards — so A and b are *bit-identical* under client
reordering AND re-sharding (different ``clients_per_shard``), the paper's
§4.3 invariance made exact rather than approximate.  On the kernel path a
client's statistics cover its live rows only (the extent read from its
mask, in fixed row blocks), so they are the same bits at any capacity.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import fed3r, ncm
from repro.core.fed3r import Fed3RStats
from repro.core.random_features import RFFParams, rff_map
from repro.data.pipeline import PackedClients
from repro.federated import compress
from repro.federated.compress import WireFormat
from repro.federated.dist import (
    DistConfig,
    DistContext,
    DistDispatchMixin,
    resolve_use_kernel,
    two_stage_psum,
    validate_backend,
)
from repro.kernels import fed3r_stats as fed3r_stats_kernel
from repro.sharding.hints import hint
from repro.sharding.specs import replicated


def _ab(
    z: jax.Array,
    y: jax.Array,
    use_kernel: Optional[bool],
    mask: Optional[jax.Array] = None,
):
    """The (A, b) GEMM backend over masked design matrices.

    The kernel computes only up to the block's live extent, its last real
    row plus one, read from ``mask``."""
    if resolve_use_kernel(use_kernel):
        rows = None
        if mask is not None:
            idx = jnp.arange(1, mask.shape[0] + 1, dtype=jnp.int32)
            rows = jnp.max(jnp.where(mask > 0, idx, 0))
        return fed3r_stats_kernel(z, y, rows)
    return fed3r.gram(z), fed3r.gram(z, y)


def shard_stats(
    features: jax.Array,  # (n, d) φ(x), any float dtype
    labels: jax.Array,  # (n,) int
    n_classes: int,
    mask: Optional[jax.Array] = None,  # (n,) 1.0 real / 0.0 padding
    *,
    use_kernel: Optional[bool] = None,
) -> Fed3RStats:
    """Fused masked statistics of one padded sample block (Eq. 5/6)."""
    z, y, n = fed3r.masked_design(features, labels, n_classes, mask)
    A, b = _ab(z, y, use_kernel, mask)
    return Fed3RStats(A=A, b=b, n=n)


def aggregate(
    stats: Fed3RStats,
    backend: str = "merge",
    axis_names: Sequence[str] = (),
) -> Fed3RStats:
    """Server-aggregation backends behind one interface.

    ``"merge"``: the associative Python/scan-level sum already produced the
    global statistics — identity.  ``"psum"``: the mesh path; the dist
    layer's two-stage all-reduce over ``axis_names`` (valid inside
    shard_map only; one psum per axis, innermost first).
    """
    validate_backend(backend, tuple(axis_names))
    if backend == "merge":
        return stats
    return two_stage_psum(stats, tuple(axis_names))


class EngineStats(NamedTuple):
    """Engine accumulator: ridge statistics + per-class sample counts.

    ``class_counts`` rides along for free (one masked one-hot column sum per
    client) and makes the NCM baseline a byproduct of the same pass:
    ``NCMStats(sums=stats.b.T, counts=class_counts)``.
    """

    stats: Fed3RStats
    class_counts: jax.Array  # (C,) fp32


def engine_init(d: int, n_classes: int) -> EngineStats:
    return EngineStats(
        stats=fed3r.init_stats(d, n_classes),
        class_counts=jnp.zeros((n_classes,), jnp.float32),
    )


def to_ncm_stats(acc: EngineStats) -> ncm.NCMStats:
    """The FedNCM view of the accumulated statistics (sums = bᵀ)."""
    return ncm.NCMStats(sums=acc.stats.b.T, counts=acc.class_counts)


@dataclass(frozen=True)
class EngineConfig:
    n_classes: int
    use_kernel: Optional[bool] = None  # None → auto (Pallas on TPU, XLA else)
    dist: DistConfig = field(default_factory=DistConfig)  # backend/mesh/donate
    # statistics wire format (repro.federated.compress): each client's
    # (A_k, b_k) crosses the uplink compressed and lands in the fp32
    # accumulator through the fused dequantize-accumulate; "fp32" keeps
    # the fold bitwise identical to the uncompressed engine
    wire: WireFormat = field(default_factory=WireFormat)


class AccumulationEngine(DistDispatchMixin):
    """Packed client-shard accumulation of FED3R statistics.

    ``feature_fn(params, flat_inputs) -> (n, d)`` maps the packed raw inputs
    of one shard (tokens, images, precomputed features — flattened to
    ``(clients_per_shard·max_n, ...)``) to φ features *inside* the scan, so
    backbone extraction batches over whole shards.  ``None`` means inputs
    already are features.  ``rff_params`` fuses the FED3R-RF map into the
    same scan.
    """

    def __init__(
        self,
        cfg: EngineConfig,
        *,
        feature_fn: Optional[Callable[[Any, jax.Array], jax.Array]] = None,
        rff_params: Optional[RFFParams] = None,
    ):
        self.cfg = cfg
        self.feature_fn = feature_fn
        self.rff_params = rff_params
        self.wire = cfg.wire.resolved()  # fp8 → int8 fallback off-TPU
        self.dist = DistContext(cfg.dist, engine="accumulation")
        self._tree_reduce_cache: dict = {}  # AggregationTree → jitted reduce
        # mesh mode: shard the leading (n_shards) axis of the packed arrays
        # over the data axes; accumulator/params replicated; all-reduced
        # output replicated
        sharded = self.dist.data_spec()
        self._accumulate = self.dist.jit(
            self._accumulate_impl,
            in_specs=(replicated(), sharded, sharded, sharded, replicated()),
            out_specs=replicated(),
        )

    def init(self, d: int) -> EngineStats:
        with self.dist.telemetry.span("init", engine="accumulation"):
            return engine_init(d, self.cfg.n_classes)

    # ---- jitted core ------------------------------------------------------

    def _client_fold(self, acc: EngineStats, block) -> Tuple[EngineStats, None]:
        """Fold one client's padded block into the accumulator.

        With a compressed wire format the client's (A_k, b_k) is the wire
        payload: it quantizes client-side and lands in the fp32 accumulator
        through the fused dequantize-accumulate — per client, inside the
        scan, still one dispatch for the whole selection.  The tiny exact
        sidecars (n, class counts) stay fp32 on the wire.
        """
        feats, labels, mask = block
        z, y, n = fed3r.masked_design(feats, labels, self.cfg.n_classes, mask)
        A, b = _ab(z, y, self.cfg.use_kernel, mask)
        if self.wire.kind == "fp32":
            stats = fed3r.merge(acc.stats, Fed3RStats(A=A, b=b, n=n))
        else:
            accA, accb = compress.roundtrip_add(
                acc.stats.A, acc.stats.b, A, b, self.wire, self.cfg.use_kernel
            )
            stats = Fed3RStats(A=accA, b=accb, n=acc.stats.n + n)
        return EngineStats(
            stats=stats,
            class_counts=acc.class_counts + jnp.sum(y, axis=0),
        ), None

    def _accumulate_impl(self, acc, inputs, labels, mask, params):
        def shard_body(carry, shard):
            x, y, m = shard  # (P, N, ...), (P, N), (P, N)
            flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
            # constrain the shard batch over the ambient mesh's data axes so
            # feature extraction (the expensive leg) data-parallelizes when a
            # mesh is set; exact no-op otherwise
            flat = hint(flat, "batch")
            feats = flat if self.feature_fn is None else self.feature_fn(params, flat)
            if self.rff_params is not None:
                feats = rff_map(self.rff_params, feats)
            feats = feats.reshape(x.shape[:2] + feats.shape[1:])
            carry, _ = jax.lax.scan(self._client_fold, carry, (feats, y, m))
            return carry, None

        acc, _ = jax.lax.scan(shard_body, acc, (inputs, labels, mask))
        # ONE all-reduce, after the scan: the whole accumulator (A, b, n AND
        # the class counts) so every field is globally correct in mesh mode.
        # Under a compressed wire format each device's LOCAL partial crosses
        # the ICI/DCN wire compressed too (the edge→cloud hop of the uplink).
        return self.dist.all_reduce(acc, wire_fn=self._wire_fn())

    def _wire_fn(self):
        """The dist layer's compressed-payload hook (None under fp32)."""
        if self.wire.kind == "fp32":
            return None

        def roundtrip(acc: EngineStats) -> EngineStats:
            A, b = compress.wire_roundtrip(
                acc.stats.A, acc.stats.b, self.wire, self.cfg.use_kernel
            )
            return acc._replace(stats=acc.stats._replace(A=A, b=b))

        return roundtrip

    # ---- host API ---------------------------------------------------------

    def accumulate(
        self, acc: EngineStats, packed: PackedClients, params: Any = None
    ) -> EngineStats:
        """Fold a packed client selection into the accumulator (one dispatch).

        With a ``feature_fn`` over token inputs the call adds the packing's
        ``extract_tokens{kind=real|computed}`` to the registry: the tokens
        the forward runs over that are real, and all it computes: what the
        ``feature_fn``'s ``positions(lengths, seq_len)`` gives for each
        shard's row lengths, or without one the packed capacity."""
        span = self.dist.telemetry.span
        if self.feature_fn is not None and packed.extract_tokens is not None:
            real, computed = packed.extract_tokens
            positions = getattr(self.feature_fn, "positions", None)
            if positions is not None:
                seq_len = packed.inputs.shape[-1]
                computed = sum(positions(lengths.reshape(-1), seq_len)
                               for lengths in packed.row_lengths)
            self.dist.telemetry.counter("extract_tokens", kind="real").inc(real)
            self.dist.telemetry.counter("extract_tokens", kind="computed").inc(computed)
        with span("accumulate", engine="accumulation"):
            self.dist.dispatch()
            with span("place", engine="accumulation"):
                args = self._args(packed)
            with span("launch", engine="accumulation"):
                return self._accumulate(acc, *args, params)

    def lower(
        self, acc: EngineStats, packed: PackedClients, params: Any = None
    ) -> jax.stages.Lowered:
        """The accumulation program for this packing, lowered without
        running it (its compiled text shows which kernels it holds)."""
        return self._accumulate.lower(acc, *self._args(packed), params)

    def _args(self, packed: PackedClients):
        return tuple(
            self.dist.place(a) for a in (packed.inputs, packed.labels, packed.mask)
        )

    def reduce_payloads(self, payloads, tree) -> EngineStats:
        """The host-side tiered fold entry point: reduce ``tree.leaves``
        pre-computed :class:`EngineStats` payloads (edge aggregators'
        round outputs) through an N-tier
        :class:`repro.federated.tiers.AggregationTree` in ONE dispatch —
        one fixed-order fold per tier, each boundary crossed in the tier's
        wire format.  With fp32 wires the result is bitwise equal to
        ``fed3r.merge``-folding the payloads flat."""
        fn = self._tree_reduce_cache.get(tree)
        if fn is None:
            use_kernel = resolve_use_kernel(self.cfg.use_kernel)
            fn = jax.jit(lambda ps: tree.reduce(ps, use_kernel=use_kernel))
            self._tree_reduce_cache[tree] = fn
        with self.dist.telemetry.span("reduce_payloads", engine="accumulation"):
            self.dist.dispatch()
            return fn(list(payloads))

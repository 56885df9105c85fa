"""Unified distributed execution layer shared by the four engines.

Before this module, every engine (batch statistics, rounds, streaming,
personalization) carried its own copy of the same plumbing: the
``use_kernel`` auto-resolution, the donation policy, the
``merge|psum`` aggregation validation, a host-side dispatch counter, and —
for mesh runs — an externally-applied ``shard_map`` the caller had to
assemble by hand.  This module owns all of it:

* :func:`resolve_use_kernel` — ONE definition of the Pallas-vs-XLA auto
  rule (compiled Pallas on TPU; XLA GEMMs elsewhere).
* :class:`DistConfig` — the shared distributed-execution configuration the
  per-engine ``aggregation``/``mesh_axes``/``donate`` fields migrated
  into.  ``mesh=None`` keeps today's behavior (plain jit; ``"psum"`` mode
  is then for cores wrapped in an *external* shard_map).  ``mesh=Mesh``
  makes the layer own the scale-out: the engine core is wrapped in
  ``shard_map`` over the mesh, its batch-carrying leading axis sharded
  over the data axes (everything but ``"model"`` — on the multi-pod
  production mesh that is ``("pod", "data")``).
* :class:`DistContext` — the per-engine handle: dispatch counting,
  :meth:`DistContext.all_reduce` (identity under ``"merge"``; the
  TWO-STAGE psum under ``"psum"``), and :meth:`DistContext.jit` which
  builds the ``jit(shard_map(core))`` program from PartitionSpecs.
* :func:`dist_jit` — the functional core of :meth:`DistContext.jit`.
* :func:`two_stage_psum` — the hierarchical all-reduce: one psum per mesh
  axis, INNERMOST FIRST, so on a ``("pod", "data")`` mesh the d² statistics
  reduce over the fast intra-pod ICI before the small cross-pod DCN stage
  touches the wire (the tiered device/edge/cloud aggregation of the
  heterogeneous-FL systems literature, as collectives).  The per-stage
  bytes/latency are costed by ``repro.federated.costs.CostModel``
  (``two_stage_allreduce(..., wire=...)`` re-prices the moving payload
  under the compressed statistics formats; the engines feed their wire
  roundtrip into :meth:`DistContext.all_reduce` via ``wire_fn`` so the
  reduced payload actually IS the compressed one).

The two-stage psum is the N=2 point of a general family:
``DistConfig(tree=AggregationTree(...))`` (:mod:`repro.federated.tiers`)
routes :meth:`DistContext.all_reduce` through an N-TIER reduction tree —
one collective tier per mesh axis, leaf (edge) tier innermost, each tier
carrying its own wire format priced at its own bandwidth
(``CostModel.tiered_allreduce``).  An all-fp32 tree emits exactly the
two-stage program, so tree routing is bitwise backward compatible; the
engine's ``wire_fn`` stays the LEAF-side hook and is applied before the
first tier crossing.


Scheduling note: the engines place their all-reduce *after* the shard
scan wherever the algebra allows (batch statistics, rounds), so feature
extraction — the expensive leg of the scan — never serializes against
per-step collectives and XLA's async collectives overlap the reduction
with the epilogue.  The streaming engine's per-wave psum is inherently on
the critical path (wave t+1's factor depends on the reduced wave-t Gram);
its ``refresh_every`` policy bounds the solve cost instead.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.federated.telemetry import Telemetry, get_telemetry
from repro.launch.mesh import data_axes, data_parallel_size
from repro.sharding.specs import data_parallel_spec


def resolve_use_kernel(use_kernel: Optional[bool]) -> bool:
    """Auto: compiled Pallas on TPU; XLA GEMMs elsewhere (interpret mode is
    for validation, not production CPU throughput)."""
    return jax.default_backend() == "tpu" if use_kernel is None else use_kernel


def validate_backend(aggregation: str, axis_names: Tuple[str, ...]) -> None:
    """The merge|psum validation every engine used to re-implement."""
    if aggregation not in ("merge", "psum"):
        raise ValueError(f"unknown aggregation backend: {aggregation!r}")
    if aggregation == "psum" and not axis_names:
        raise ValueError("psum aggregation needs at least one mesh axis")


def _shard_map(fn: Callable, mesh, in_specs, out_specs) -> Callable:
    """``jax.shard_map`` with replication checking off: engine outputs are
    made replicated by explicit psums, not by tracked rep-sets, and the
    Pallas kernels inside the cores have no rep rules."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_cohort(
    cohort: Tuple[int, ...], shard: int, n_shards: int
) -> Tuple[int, ...]:
    """Deterministic partition of a (possibly partial) cohort across shards.

    The psum-mode contract of the merge-on-arrival engine
    (:mod:`repro.federated.async_engine`): each shard scatters ONLY the
    uploads of the clients it owns — ``shard_cohort(cohort, i, n)`` for
    shard i — leaving every other slot an exact zero, and the retire
    all-reduce reassembles the full cohort sum.  Round-robin by sorted
    cohort position, so the partition is independent of arrival order,
    covers every client exactly once, and stays balanced even when the
    cohort is PARTIAL (fewer clients than slots: late joiners, demoted
    stragglers dropped by the health tracker).
    """
    if not 0 <= shard < n_shards:
        raise ValueError(f"shard {shard} out of range for {n_shards} shards")
    ordered = sorted(int(c) for c in cohort)
    return tuple(c for i, c in enumerate(ordered) if i % n_shards == shard)


def linear_shard_index(axis_names: Tuple[str, ...]):
    """The caller's linearized position over the given mesh axes (valid
    inside shard_map) — row-major in axis order, matching how a
    ``PartitionSpec`` with a tuple entry linearizes the axes.  The
    dist-owned async scatter uses it to find which slot block of the
    sharded ring this device owns."""
    idx = 0
    for ax in axis_names:
        idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return idx


def two_stage_psum(tree: Any, axis_names: Tuple[str, ...]) -> Any:
    """Hierarchical all-reduce: one psum per axis, innermost (last) first.

    On the multi-pod mesh ``axis_names=("pod", "data")`` this reduces over
    the intra-pod ICI ring first and ships only the already-reduced d²
    statistics across the DCN — the two stages XLA can also schedule as
    separate async collectives.  For a single axis it is exactly one psum
    (bit-identical to the pre-refactor engines).
    """
    for ax in reversed(tuple(axis_names)):
        tree = jax.tree.map(partial(jax.lax.psum, axis_name=ax), tree)
    return tree


def dist_jit(
    fn: Callable,
    *,
    mesh: Optional[jax.sharding.Mesh] = None,
    in_specs: Any = None,
    out_specs: Any = None,
    donate: Tuple[int, ...] = (),
) -> Callable:
    """The one jit entry point of the engines.

    ``mesh=None``: plain ``jax.jit`` (single-process; the scan carry IS the
    aggregation).  ``mesh=Mesh``: ``jax.jit(shard_map(fn, mesh, in_specs,
    out_specs))`` — the engine core runs as one SPMD program per device
    over its shard of the batch-carrying axis, still ONE host dispatch.
    ``donate`` is the argnums whose buffers the program may reuse.
    """
    if mesh is not None:
        fn = _shard_map(fn, mesh, in_specs, out_specs)
    return jax.jit(fn, donate_argnums=tuple(donate))


@dataclass(frozen=True)
class DistConfig:
    """Shared distributed-execution configuration of the four engines.

    ``aggregation``:
      * ``"merge"`` — single-process: the associative scan/Python-level sum
        already produced the global result; ``mesh`` must be ``None``.
      * ``"psum"`` — distributed: local partials are all-reduced over the
        data axes.  With ``mesh=None`` the engine core must be wrapped in
        an EXTERNAL shard_map over ``mesh_axes`` (the pre-refactor
        contract, kept for composability).  With ``mesh=Mesh`` the dist
        layer owns the shard_map and the engine's host API transparently
        scales out.

    ``mesh_axes`` names the reduce axes explicitly; empty with a ``mesh``
    defaults to every non-``"model"`` axis of the mesh (``("pod", "data")``
    on the multi-pod production mesh).  ``donate`` is the donate-the-state
    policy, the same on every backend.

    ``tree`` routes :meth:`DistContext.all_reduce` through an N-tier
    :class:`repro.federated.tiers.AggregationTree` instead of the
    two-stage psum: one collective tier per reduce axis, LEAF TIER
    INNERMOST (the tree's axes must equal the reversed resolved axes), so
    an all-fp32 tree emits the identical program and stays bitwise
    backward compatible, while per-tier wire formats compress the slow
    upper crossings.  Requires ``"psum"``.
    """

    aggregation: str = "merge"  # "merge" | "psum"
    mesh_axes: Tuple[str, ...] = ()  # reduce axes ("psum"); () + mesh → data axes
    mesh: Optional[jax.sharding.Mesh] = None  # shard_map mesh (dist-owned scale-out)
    donate: bool = True  # donate the carried state to the dispatch
    tree: Optional[Any] = None  # N-tier AggregationTree (repro.federated.tiers)

    def __post_init__(self):
        if self.aggregation not in ("merge", "psum"):
            raise ValueError(f"unknown aggregation backend: {self.aggregation!r}")
        if self.aggregation == "merge" and self.mesh is not None:
            raise ValueError(
                "mesh-mode execution all-reduces device partials: use "
                "aggregation='psum' (merge is the single-process backend)"
            )
        axes = self.mesh_axes or (
            data_axes(self.mesh) if self.mesh is not None else ()
        )
        if self.aggregation == "psum" and not axes:
            raise ValueError("psum aggregation needs at least one mesh axis")
        if self.mesh is not None:
            unknown = set(axes) - set(self.mesh.axis_names)
            if unknown:
                raise ValueError(
                    f"mesh_axes {sorted(unknown)} not in mesh axes "
                    f"{self.mesh.axis_names}"
                )
        if self.tree is not None:
            if self.aggregation != "psum":
                raise ValueError(
                    "an aggregation tree routes the psum backend; merge "
                    "has no collective to tier"
                )
            # duck-typed (tiers.py imports this module); the tree's
            # collective tiers must cover the reduce axes leaf-innermost
            self.tree.validate_mesh_axes(axes)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        """The resolved reduce axes (explicit, or the mesh's data axes)."""
        if self.mesh_axes:
            return tuple(self.mesh_axes)
        return data_axes(self.mesh) if self.mesh is not None else ()

    @property
    def data_shards(self) -> int:
        """Data-parallel way count of the owned mesh (1 without a mesh)."""
        return 1 if self.mesh is None else data_parallel_size(self.mesh)

    @property
    def lossy_tier_wire(self) -> Optional[Any]:
        """The routed tree's coarsest lossy tier wire (``None`` when the
        reduction is bit-exact) — engines consult it to pick the
        PSD-guarded Cholesky when a tree crossing quantizes."""
        return None if self.tree is None else self.tree.lossy_wire


class DistContext:
    """Per-engine handle on the distributed execution layer.

    Owns the host→device dispatch counter every engine used to carry —
    now homed in the unified telemetry registry
    (:mod:`repro.federated.telemetry`) as the labeled series
    ``engine_dispatches_total{engine=<name>, inst=<n>}``, one counter
    cell per context so N same-type engines stay independently
    resettable — plus the aggregation backend (:meth:`all_reduce`) and
    program construction (:meth:`jit`).  Engines keep their
    ``.dispatches`` attribute as a property proxying this counter
    (:class:`DistDispatchMixin`), so benchmarks keep working unchanged;
    the CI regression gate reads the SAME cells back out of the
    ``telemetry_*.json`` snapshots, so the two can't diverge.
    """

    def __init__(
        self,
        cfg: DistConfig,
        *,
        engine: str = "engine",
        telemetry: Optional[Telemetry] = None,
    ):
        self.cfg = cfg
        # registry captured at construction (process-global by default,
        # injectable for tests/benches); spans/events ride the same handle
        self.telemetry = get_telemetry() if telemetry is None else telemetry
        inst = self.telemetry.next_instance(f"dist:{engine}")
        self._dispatches = self.telemetry.counter(
            "engine_dispatches_total", engine=engine, inst=inst
        )

    @property
    def dispatches(self) -> int:
        """Host→device dispatch count (a telemetry counter cell)."""
        return int(self._dispatches.value)

    @dispatches.setter
    def dispatches(self, value: int) -> None:
        self._dispatches.set(int(value))

    def dispatch(self) -> None:
        """Record one host→device dispatch (call at each host-API entry).

        A plain integer add on a telemetry Counter — zero device work."""
        self._dispatches.inc()

    def all_reduce(self, tree: Any, wire_fn: Optional[Callable[[Any], Any]] = None) -> Any:
        """The server aggregation behind one interface: identity under
        ``"merge"`` (the local fold IS the global sum); the two-stage psum
        over the resolved axes under ``"psum"`` (valid inside shard_map).

        ``wire_fn`` is the compressed-uplink hook
        (:mod:`repro.federated.compress`): the engines pass their
        wire-format roundtrip so each device's LOCAL partial crosses the
        ICI/DCN wire in the configured format — compressed on the way out,
        dequantized ONCE at the aggregation boundary before the psum sums
        the received payloads.  ``None`` (and the ``"merge"`` backend,
        whose uplink compression happens per client inside the engine
        fold) keeps the reduce bit-exact fp32.

        With ``cfg.tree`` set, the reduction runs the N-tier aggregation
        tree instead — ``wire_fn`` stays the LEAF-side hook (applied
        before the first tier crossing), then each collective tier
        compresses + psums in leaf-first order.  All-fp32 trees emit the
        identical two-stage program."""
        if self.cfg.aggregation == "merge":
            return tree
        if wire_fn is not None:
            tree = wire_fn(tree)
        if self.cfg.tree is not None:
            return self.cfg.tree.psum(tree)
        return two_stage_psum(tree, self.cfg.axis_names)

    def data_spec(self, axis: int = 0):
        """The in/out PartitionSpec of a batch-carrying array: dim ``axis``
        sharded over the data axes in mesh mode, ``None`` (don't-care —
        :meth:`jit` ignores specs) without a mesh.  The one spec idiom
        every engine's program construction uses."""
        if self.cfg.mesh is None:
            return None
        return data_parallel_spec(self.cfg.axis_names, axis)

    def place(self, x: Any, axis: int = 0) -> jax.Array:
        """Put a host batch array on device for the program: in mesh mode
        straight into its data sharding along dim ``axis``, so each device
        receives only its own shard; otherwise onto the default device."""
        if self.cfg.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(
            x, NamedSharding(self.cfg.mesh, self.data_spec(axis))
        )

    def jit(
        self,
        fn: Callable,
        *,
        in_specs: Any = None,
        out_specs: Any = None,
        donate: Optional[bool] = None,
        donate_argnums_: Tuple[int, ...] = (0,),
    ) -> Callable:
        """Build the engine's one-dispatch program (see :func:`dist_jit`).

        ``in_specs``/``out_specs`` are only consulted in mesh mode; the
        donation default comes from the config (``donate=False`` opts a
        non-carrying engine out).
        """
        want = self.cfg.donate if donate is None else donate
        return dist_jit(
            fn,
            mesh=self.cfg.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            donate=donate_argnums_ if want else (),
        )


class DistDispatchMixin:
    """The engines' public ``.dispatches`` counter, proxied onto the owned
    :class:`DistContext` (``self.dist``) — which in turn homes it in the
    telemetry registry as ``engine_dispatches_total`` — kept settable
    because the benchmarks reset it between timed sections."""

    dist: DistContext

    @property
    def dispatches(self) -> int:
        """Host→device dispatch count (owned by the dist context)."""
        return self.dist.dispatches

    @dispatches.setter
    def dispatches(self, value: int) -> None:
        self.dist.dispatches = int(value)

"""Production and host meshes — the device topologies the engines run over.

Target: TPU v5e pods — 256 chips/pod arranged (data=16, model=16); the
multi-pod deployment adds a leading "pod" axis over DCN (2 pods = 512
chips).  The distributed execution layer (:mod:`repro.federated.dist`)
shards the engines' batch-carrying axes over :func:`data_axes` — every
axis but "model" — and all-reduces the d² statistics hierarchically:
intra-pod over ICI first, then cross-pod over DCN (the two stages are
costed separately by ``repro.federated.costs.CostModel``).

Beyond two stages, :func:`make_tier_host_mesh` builds N-axis TIER meshes
(edge → region → cloud) for the generalized aggregation trees of
:mod:`repro.federated.tiers`: one mesh axis per tier, innermost axis =
leaf tier, each tier priced at its own bandwidth (``ICI_BW`` / ``DCN_BW``
/ ``WAN_BW``) by ``CostModel.tiered_allreduce``.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
first jax device query, while smoke tests must keep seeing 1 device.  The
host meshes (``make_host_mesh``) build the same axis layouts over however
many (possibly simulated) local devices exist, so tests and the weak-
scaling bench (``benchmarks/bench_scaleout.py``) exercise the exact
production code paths.
"""
from __future__ import annotations

from typing import Tuple

import jax

# Hardware constants (TPU v5e) used by the roofline analysis and cost model.
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per-chip effective for ring collectives)
DCN_BW = 12.5e9  # bytes/s per pod boundary (~100 Gbps cross-pod effective)
WAN_BW = 1.25e9  # bytes/s cross-region (~10 Gbps effective over WAN)

# Per-tier bandwidth lookup for aggregation trees: edge folds ride ICI,
# region crossings ride DCN, cloud crossings ride the WAN.
TIER_BANDWIDTHS = {"ici": ICI_BW, "dcn": DCN_BW, "wan": WAN_BW}

# Default axis names for N-tier host meshes, outermost (slowest) first.
# The leaf tier keeps the name "edge"; a 1-tier mesh degenerates to it.
_TIER_AXIS_NAMES = ("cloud", "region", "edge")


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto: the compiler propagates
    shardings and :func:`repro.sharding.hints.hint` constrains them.  (JAX's
    default, Explicit, turns each hint into an assertion that the array is
    already sharded that way.)"""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, *, pods: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / local / dry runs).

    Mirrors the production axis layouts so host-device tests exercise the
    same code paths: ``pods=1`` builds ("data", "model"); ``pods>1`` adds
    the leading "pod" axis — ("pod", "data", "model") — over simulated
    host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    Raises ``ValueError`` (not a bare assert, which ``python -O`` strips)
    when the device count does not factor as pods × data × model_parallel.
    """
    n = len(jax.devices())
    if model_parallel < 1 or pods < 1:
        raise ValueError(
            f"model_parallel and pods must be >= 1, got {model_parallel}, {pods}"
        )
    if n % (model_parallel * pods) != 0:
        raise ValueError(
            f"{n} devices do not factor as pods={pods} × data × "
            f"model_parallel={model_parallel}"
        )
    data = n // (model_parallel * pods)
    if pods > 1:
        return _make_mesh((pods, data, model_parallel), ("pod", "data", "model"))
    return _make_mesh((data, model_parallel), ("data", "model"))


def make_tier_host_mesh(
    tier_shape: Tuple[int, ...],
    tier_names: Tuple[str, ...] = (),
    model_parallel: int = 1,
) -> jax.sharding.Mesh:
    """N-tier mesh over local devices: one axis per tier + "model".

    ``tier_shape`` lists tier sizes OUTERMOST FIRST (cloud → edge), so the
    trailing tier axis is the leaf/edge tier — the same outer-to-inner
    convention as ("pod", "data").  Default names for ≤3 tiers are drawn
    from ("cloud", "region", "edge") right-aligned; deeper trees must name
    their axes explicitly.  All tier axes are batch-carrying (returned by
    :func:`data_axes`), so the engines' packers and the aggregation trees
    of :mod:`repro.federated.tiers` see them uniformly.

    Raises ``ValueError`` when the device count does not factor as
    prod(tier_shape) × model_parallel, or when names/shape disagree.
    """
    if not tier_shape or any(s < 1 for s in tier_shape):
        raise ValueError(f"tier_shape must be non-empty positive ints, got {tier_shape}")
    if not tier_names:
        if len(tier_shape) > len(_TIER_AXIS_NAMES):
            raise ValueError(
                f"{len(tier_shape)} tiers need explicit tier_names "
                f"(defaults cover {len(_TIER_AXIS_NAMES)})"
            )
        tier_names = _TIER_AXIS_NAMES[len(_TIER_AXIS_NAMES) - len(tier_shape):]
    if len(tier_names) != len(tier_shape):
        raise ValueError(f"tier_names {tier_names} do not match tier_shape {tier_shape}")
    if "model" in tier_names:
        raise ValueError('"model" is reserved for the model-parallel axis')
    n = len(jax.devices())
    want = model_parallel
    for s in tier_shape:
        want *= s
    if n != want:
        raise ValueError(
            f"{n} devices do not factor as tiers {tier_shape} × "
            f"model_parallel={model_parallel}"
        )
    return _make_mesh(
        tuple(tier_shape) + (model_parallel,), tuple(tier_names) + ("model",)
    )


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes carrying the batch dimension (everything but "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def data_parallel_size(mesh: jax.sharding.Mesh) -> int:
    """Product of the batch-carrying axis sizes — the shard-count the
    packers pad the engines' leading axes to a multiple of."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in data_axes(mesh):
        n *= sizes[a]
    return n


def n_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size

"""Client-side data pipeline: per-client views, batching, padding, packing.

``FederatedDataset`` is the simulator's handle on a partitioned dataset:
one global array store + per-client index lists (zero-copy views).

Four packers turn ragged per-client data into fixed-shape device arrays:

* :func:`pack_client_batches` — ONE client padded to a global
  ``(epochs·n_batches, batch_size)`` grid; the gradient-FL local-update
  shape (per-client reference path).
* :func:`pack_cohort_batches` — a SAMPLED COHORT of clients stacked into
  ``(cohort, epochs·n_batches, batch_size, ...)`` arrays with masks; the
  shape :mod:`repro.federated.round_engine` vmaps one whole FL round over.
  Canonical id order + per-(seed, client) shuffling make the packed arrays
  bitwise invariant to the order the cohort was sampled in.
* :func:`pack_client_shards` — MANY clients padded into
  ``(n_shards, clients_per_shard, max_n, ...)`` with masks; the statistics
  shape consumed by :mod:`repro.federated.engine`'s scan accumulation.
  Packing is canonical (clients sorted by id) so downstream accumulation is
  bitwise invariant to the order clients were sampled in.
* :func:`pack_arrival_waves` — a TIMELINE of arrival waves padded into
  ``(n_waves, clients_per_wave, max_n, ...)`` with masks; the streaming
  shape :mod:`repro.federated.streaming_engine` scans over.  Clients are
  canonically sorted by id WITHIN each wave (arrival order across waves is
  the semantics of the stream and is preserved), so the packed arrays —
  and the engine's folded state — are bitwise invariant to the order a
  wave's concurrent arrivals were presented in.
* :func:`pack_personal_cohort` — a COHORT of tenants padded into
  ``(cohort, max_n, ...)`` with masks plus a per-client HOLDOUT split for
  closed-form α selection; the personalization shape
  :mod:`repro.federated.personalization` solves K per-tenant heads over in
  one batched dispatch.  Built on :func:`pack_client_shards` (same
  canonical-id-order / round_to / ``-1``-empty-slot conventions), so the
  packed cohort — and the batched head solve — is bitwise invariant to
  the order the tenants were requested in.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.partition import dirichlet_partition
from repro.data.synthetic import FeatureDataset, make_feature_dataset
from repro.kernels.fed3r_stats import BK
from repro.launch.mesh import data_parallel_size
from repro.models.model import row_lengths


def _data_parallel(
    mesh: Optional[jax.sharding.Mesh], num_shards: Optional[int]
) -> int:
    """The data-parallel way count a packed leading axis must divide.

    Every packer pads its sharded axis to a multiple of this with fully
    masked blocks (``client_ids == -1``, zero mask) so the dist layer
    (:mod:`repro.federated.dist`) can split it evenly over
    ``data_axes(mesh)``.  Masked blocks contribute exactly nothing to any
    statistic, so padding preserves canonical-order bit-invariance.
    """
    if num_shards is not None:
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        return int(num_shards)
    return 1 if mesh is None else data_parallel_size(mesh)


@dataclass
class ClientData:
    features: np.ndarray  # (n_k, d) or tokens (n_k, S)
    labels: np.ndarray  # (n_k,)

    @property
    def n(self) -> int:
        return len(self.labels)

    def batches(
        self, batch_size: int, rng: Optional[np.random.Generator] = None,
        epochs: int = 1,
    ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        for _ in range(epochs):
            order = (
                rng.permutation(self.n) if rng is not None else np.arange(self.n)
            )
            for s in range(0, self.n, batch_size):
                sel = order[s : s + batch_size]
                yield self.features[sel], self.labels[sel]


@dataclass
class FederatedDataset:
    features: np.ndarray
    labels: np.ndarray
    client_indices: List[np.ndarray]
    n_classes: int

    @property
    def n_clients(self) -> int:
        return len(self.client_indices)

    def client(self, k: int) -> ClientData:
        idx = self.client_indices[k]
        return ClientData(self.features[idx], self.labels[idx])

    def client_sizes(self) -> np.ndarray:
        return np.array([len(ix) for ix in self.client_indices])

    def repartition(self, rng: np.random.Generator, n_clients: int, alpha: float
                    ) -> "FederatedDataset":
        """Same underlying D, different federated split — the Fig. 1 probe."""
        parts = dirichlet_partition(rng, self.labels, n_clients, alpha)
        return FederatedDataset(self.features, self.labels, parts, self.n_classes)


class PackedClients(NamedTuple):
    """Clients packed into dense shard arrays for scan accumulation.

    ``inputs``/``labels``/``mask`` share the leading
    ``(n_shards, clients_per_shard, max_n)`` layout; ``mask`` is 1.0 on real
    samples, 0.0 on padding.  Empty client slots (shard-count padding) have
    ``client_ids == -1`` and an all-zero mask, so they contribute exactly
    nothing to any masked statistic.
    """

    inputs: np.ndarray  # (S, P, N, ...) features or tokens
    labels: np.ndarray  # (S, P, N) int32
    mask: np.ndarray  # (S, P, N) float32
    client_ids: np.ndarray  # (S, P) int32, -1 = empty slot
    # token inputs only: (real tokens, packed positions), the non-zero ids
    # of the real rows and slots × capacity × length; None for features
    extract_tokens: Optional[Tuple[int, int]] = None
    # token inputs only: (S, P, N) each row's length, one past its last
    # non-zero id (``repro.models.model.row_lengths``); None for features
    row_lengths: Optional[np.ndarray] = None

    @property
    def n_shards(self) -> int:
        return self.inputs.shape[0]

    @property
    def clients_per_shard(self) -> int:
        return self.inputs.shape[1]

    @property
    def n_clients(self) -> int:
        return int((self.client_ids >= 0).sum())

    @property
    def n_samples(self) -> int:
        return int(self.mask.sum())


def _count_stats_rows(sizes: Sequence[int], capacity_rows: int) -> None:
    """``stats_rows{kind}`` in the process-global registry: the real rows,
    the packed capacity (slots × capacity), and the rows the statistics
    kernel computes (each client's rows rounded up to its row block)."""
    from repro.federated.telemetry import get_telemetry  # repro.federated imports this module

    t = get_telemetry()
    t.counter("stats_rows", kind="real").inc(sum(sizes))
    t.counter("stats_rows", kind="capacity").inc(capacity_rows)
    t.counter("stats_rows", kind="kernel").inc(sum(-(-n // BK) * BK for n in sizes))


def _count_gram_rows(mask: np.ndarray) -> None:
    """``gram_rows{kind}`` in the process-global registry: the real rows of
    the packed waves, the packed rows (waves × slots × capacity), and the
    rows of the BK-row blocks of each wave's flat design that hold a real
    row, which the streaming Gram kernel sweeps."""
    from repro.federated.telemetry import get_telemetry  # repro.federated imports this module

    flat = mask.reshape(mask.shape[0], -1) > 0
    pad = -flat.shape[1] % BK
    live = np.pad(flat, ((0, 0), (0, pad))).reshape(flat.shape[0], -1, BK).any(2)
    t = get_telemetry()
    t.counter("gram_rows", kind="real").inc(int(flat.sum()))
    t.counter("gram_rows", kind="packed").inc(mask.size)
    t.counter("gram_rows", kind="kernel").inc(int(live.sum()) * BK)


def pack_client_shards(
    clients: Sequence[Tuple[np.ndarray, np.ndarray]],
    clients_per_shard: int,
    *,
    client_ids: Optional[Sequence[int]] = None,
    max_n: Optional[int] = None,
    round_to: int = 8,
    canonical_order: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    num_shards: Optional[int] = None,
) -> PackedClients:
    """Pack ``[(inputs_k, labels_k), ...]`` into :class:`PackedClients`.

    ``max_n`` (the per-client sample capacity) is rounded up to a multiple of
    ``round_to`` so repeated rounds with slightly different client sizes hit
    one jit trace.  Pass a dataset-global ``max_n`` to guarantee a single
    trace across all rounds.  With ``canonical_order`` the clients are sorted
    by id before packing, which makes the packed arrays — and therefore every
    deterministic accumulation over them — invariant to sampling order.

    ``mesh`` (or an explicit ``num_shards`` way count) pads the leading
    shard axis to a multiple of the mesh's data-parallel size with fully
    masked empty shards, so the dist layer can split the scan evenly over
    the data axes; the padding blocks are exact no-ops, preserving the
    bit-invariance guarantees.
    """
    if not clients:
        raise ValueError("pack_client_shards: empty client list")
    if clients_per_shard < 1:
        raise ValueError(f"clients_per_shard must be >= 1, got {clients_per_shard}")
    ids = np.arange(len(clients), dtype=np.int32) if client_ids is None else (
        np.asarray(client_ids, np.int32)
    )
    if len(ids) != len(clients):
        raise ValueError("client_ids length mismatch")
    order = np.argsort(ids, kind="stable") if canonical_order else np.arange(len(ids))

    sizes = [len(clients[i][1]) for i in order]
    need = max(max(sizes), 1) if max_n is None else max_n
    if max(sizes) > need:
        raise ValueError(f"client with {max(sizes)} samples exceeds max_n={need}")
    cap = -(-need // round_to) * round_to

    n_shards = -(-len(clients) // clients_per_shard)
    dp = _data_parallel(mesh, num_shards)
    n_shards = -(-n_shards // dp) * dp  # pad with fully-masked shards
    n_slots = n_shards * clients_per_shard
    x0 = np.asarray(clients[order[0]][0])
    inputs = np.zeros((n_slots, cap) + x0.shape[1:], x0.dtype)
    labels = np.zeros((n_slots, cap), np.int32)
    mask = np.zeros((n_slots, cap), np.float32)
    slot_ids = np.full((n_slots,), -1, np.int32)
    for slot, i in enumerate(order):
        x, y = clients[i]
        n_k = len(y)
        inputs[slot, :n_k] = x
        labels[slot, :n_k] = y
        mask[slot, :n_k] = 1.0
        slot_ids[slot] = ids[i]

    _count_stats_rows(sizes, n_slots * cap)

    def shard(a: np.ndarray) -> np.ndarray:
        return a.reshape((n_shards, clients_per_shard) + a.shape[1:])

    extract_tokens = lengths = None
    if np.issubdtype(inputs.dtype, np.integer) and inputs.ndim == 3:  # token rows
        extract_tokens = (int(np.count_nonzero(inputs)), int(inputs.size))
        lengths = shard(row_lengths(inputs).astype(np.int32))

    return PackedClients(
        inputs=shard(inputs), labels=shard(labels), mask=shard(mask),
        client_ids=slot_ids.reshape(n_shards, clients_per_shard),
        extract_tokens=extract_tokens, row_lengths=lengths,
    )


class PackedArrivals(NamedTuple):
    """Arrival waves packed into dense timeline arrays for scan streaming.

    ``inputs``/``labels``/``mask`` share the leading
    ``(n_waves, clients_per_wave, max_n)`` layout; ``mask`` is 1.0 on real
    samples, 0.0 on padding.  Empty client slots — wave-width padding, or
    whole waves with zero arrivals — have ``client_ids == -1`` and an
    all-zero mask, so they contribute exactly nothing to any masked
    statistic (a zero-arrival wave is an exact no-op that still advances
    the wave clock).
    """

    inputs: np.ndarray  # (T, P, N, ...) features or tokens
    labels: np.ndarray  # (T, P, N) int32
    mask: np.ndarray  # (T, P, N) float32
    client_ids: np.ndarray  # (T, P) int32, -1 = empty slot

    @property
    def n_waves(self) -> int:
        return self.inputs.shape[0]

    @property
    def clients_per_wave(self) -> int:
        return self.inputs.shape[1]

    @property
    def n_clients(self) -> int:
        return int((self.client_ids >= 0).sum())

    @property
    def n_samples(self) -> int:
        return int(self.mask.sum())

    def slice_waves(self, start: int, stop: int) -> "PackedArrivals":
        """A contiguous sub-stream (e.g. one serving segment) — zero-copy."""
        return PackedArrivals(
            inputs=self.inputs[start:stop],
            labels=self.labels[start:stop],
            mask=self.mask[start:stop],
            client_ids=self.client_ids[start:stop],
        )


def pack_arrival_waves(
    waves: Sequence[Sequence[Tuple[np.ndarray, np.ndarray]]],
    *,
    client_ids: Optional[Sequence[Sequence[int]]] = None,
    clients_per_wave: Optional[int] = None,
    max_n: Optional[int] = None,
    round_to: int = 8,
    canonical_order: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    num_shards: Optional[int] = None,
) -> PackedArrivals:
    """Pack a timeline ``[[(x_k, y_k), ...], ...]`` into :class:`PackedArrivals`.

    Wave ``t`` holds the clients that arrive at time-step ``t`` (possibly
    none).  All waves share one ``(clients_per_wave, max_n)`` grid — both
    default to the timeline maxima, ``max_n`` rounded up to a multiple of
    ``round_to`` — so the streaming engine scans a single fixed-shape array
    and the whole stream costs one jit trace.  ``client_ids`` assigns global
    ids per wave (default: arrival-order enumeration across the timeline).
    With ``canonical_order`` each wave's clients are sorted by id before
    packing, making the packed arrays bitwise invariant to the presentation
    order of concurrent arrivals.

    ``mesh`` (or ``num_shards``) pads ``clients_per_wave`` — the axis the
    dist layer shards, since the wave axis is the scanned arrival clock —
    to a multiple of the data-parallel size with fully masked slots (exact
    no-ops, bit-invariance preserved).
    """
    if not waves:
        raise ValueError("pack_arrival_waves: empty timeline")
    if client_ids is None:
        ids_per_wave: List[np.ndarray] = []
        nxt = 0
        for wave in waves:
            ids_per_wave.append(np.arange(nxt, nxt + len(wave), dtype=np.int32))
            nxt += len(wave)
    else:
        if len(client_ids) != len(waves):
            raise ValueError("client_ids timeline length mismatch")
        ids_per_wave = [np.asarray(ids, np.int32) for ids in client_ids]
        for wave, ids in zip(waves, ids_per_wave):
            if len(ids) != len(wave):
                raise ValueError("client_ids wave length mismatch")

    widths = [len(wave) for wave in waves]
    P = max(max(widths), 1) if clients_per_wave is None else clients_per_wave
    if max(widths) > P:
        raise ValueError(
            f"wave with {max(widths)} arrivals exceeds clients_per_wave={P}"
        )
    dp = _data_parallel(mesh, num_shards)
    P = -(-P // dp) * dp  # pad the sharded wave-width axis
    sizes = [len(y) for wave in waves for _, y in wave]
    need = max(sizes, default=1) if max_n is None else max_n
    if sizes and max(sizes) > need:
        raise ValueError(f"client with {max(sizes)} samples exceeds max_n={need}")
    cap = -(-max(need, 1) // round_to) * round_to

    x0 = None
    for wave in waves:
        if wave:
            x0 = np.asarray(wave[0][0])
            break
    if x0 is None:
        raise ValueError("pack_arrival_waves: no clients in any wave")

    T = len(waves)
    inputs = np.zeros((T, P, cap) + x0.shape[1:], x0.dtype)
    labels = np.zeros((T, P, cap), np.int32)
    mask = np.zeros((T, P, cap), np.float32)
    slot_ids = np.full((T, P), -1, np.int32)
    for t, (wave, ids) in enumerate(zip(waves, ids_per_wave)):
        order = (
            np.argsort(ids, kind="stable") if canonical_order
            else np.arange(len(ids))
        )
        for slot, i in enumerate(order):
            x, y = wave[i]
            n_k = len(y)
            inputs[t, slot, :n_k] = x
            labels[t, slot, :n_k] = y
            mask[t, slot, :n_k] = 1.0
            slot_ids[t, slot] = ids[i]
    _count_gram_rows(mask)
    return PackedArrivals(
        inputs=inputs, labels=labels, mask=mask, client_ids=slot_ids
    )


class PackedPersonalCohort(NamedTuple):
    """A tenant cohort packed for one batched personalized-head solve.

    ``inputs``/``labels``/``mask``/``holdout`` share the leading
    ``(cohort, max_n)`` layout; ``mask`` is 1.0 on real samples, 0.0 on
    padding, and ``holdout`` ⊆ ``mask`` marks the per-client validation
    samples the α sweep scores on (never the client's full data: index 0 of
    every client is always train).  Empty cohort slots (width padding) have
    ``client_ids == -1`` and all-zero masks, so their statistics vanish and
    their head degenerates to the global solution at any α.
    """

    inputs: np.ndarray  # (K, N, ...) features or tokens
    labels: np.ndarray  # (K, N) int32
    mask: np.ndarray  # (K, N) float32
    holdout: np.ndarray  # (K, N) float32, subset of mask (α-selection split)
    client_ids: np.ndarray  # (K,) int32, -1 = empty slot

    @property
    def cohort(self) -> int:
        return self.inputs.shape[0]

    @property
    def n_clients(self) -> int:
        return int((self.client_ids >= 0).sum())

    @property
    def n_samples(self) -> int:
        return int(self.mask.sum())

    @property
    def n_holdout(self) -> int:
        return int(self.holdout.sum())


def pack_personal_cohort(
    clients: Sequence[Tuple[np.ndarray, np.ndarray]],
    *,
    client_ids: Optional[Sequence[int]] = None,
    cohort_size: Optional[int] = None,
    max_n: Optional[int] = None,
    round_to: int = 8,
    holdout_frac: float = 0.25,
    canonical_order: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    num_shards: Optional[int] = None,
) -> PackedPersonalCohort:
    """Pack ``[(x_k, y_k), ...]`` into a :class:`PackedPersonalCohort`.

    Reuses :func:`pack_client_shards`'s padding conventions by construction
    (one shard of width ``cohort_size``): canonical id sort, ``round_to``
    sample-capacity rounding, ``-1``/zero-mask empty slots.  On top, every
    client with ≥ 2 samples gets a deterministic non-empty HOLDOUT split —
    every ``round(1/frac)``-th of its samples (its last sample if it has
    fewer than that), never index 0, so at least one sample remains on
    each side — which the personalization engine's α sweep scores against.
    Single-sample clients get no holdout (their sweep degenerates to
    ``alpha_grid[0]``).  The split is a pure function of the client's own
    sample order, never of cohort position, preserving bit-invariance to
    request order.

    ``mesh`` (or ``num_shards``) pads the cohort axis to a multiple of the
    data-parallel size with empty slots whose heads degenerate to the
    global solution — the dist layer shards the cohort over the data axes
    and gathers the solved heads back.
    """
    if not 0.0 <= holdout_frac < 1.0:
        raise ValueError(f"holdout_frac must be in [0, 1), got {holdout_frac}")
    K = len(clients) if cohort_size is None else cohort_size
    if K < len(clients):
        raise ValueError(f"cohort_size={K} < {len(clients)} clients")
    dp = _data_parallel(mesh, num_shards)
    K = -(-K // dp) * dp  # pad the sharded cohort axis
    shards = pack_client_shards(
        clients,
        clients_per_shard=K,
        client_ids=client_ids,
        max_n=max_n,
        round_to=round_to,
        canonical_order=canonical_order,
    )
    inputs = shards.inputs[0]
    labels = shards.labels[0]
    mask = shards.mask[0]
    ids = shards.client_ids[0]

    holdout = np.zeros_like(mask)
    if holdout_frac > 0.0:
        stride = max(int(round(1.0 / holdout_frac)), 2)
        for k in range(K):
            n_k = int(mask[k].sum())
            if n_k >= 2:
                idx = np.arange(stride - 1, n_k, stride)
                if len(idx) == 0:  # n_k < stride: still hold out ONE sample
                    idx = np.array([n_k - 1])
                holdout[k, idx] = 1.0
    return PackedPersonalCohort(
        inputs=inputs, labels=labels, mask=mask, holdout=holdout, client_ids=ids
    )


def pack_client_batches(
    x: np.ndarray, y: np.ndarray, batch_size: int, n_batches: int, epochs: int,
    rng: Optional[np.random.Generator] = None,
) -> Dict[str, np.ndarray]:
    """Pad one client's data to the global (epochs·n_batches, batch_size) grid.

    The gradient-FL local-update shape: every client fills the same padded
    grid (mask marks real samples) so one jitted ``local_update`` serves all
    clients without retracing.  Each epoch reshuffles with ``rng``.
    """
    total = n_batches * batch_size
    xs, ys, ms = [], [], []
    for _ in range(epochs):
        order = rng.permutation(len(y)) if rng is not None else np.arange(len(y))
        xe = np.zeros((total,) + x.shape[1:], x.dtype)
        ye = np.zeros((total,), y.dtype)
        me = np.zeros((total,), np.float32)
        k = min(len(y), total)
        xe[:k] = x[order[:k]]
        ye[:k] = y[order[:k]]
        me[:k] = 1.0
        xs.append(xe.reshape(n_batches, batch_size, *x.shape[1:]))
        ys.append(ye.reshape(n_batches, batch_size))
        ms.append(me.reshape(n_batches, batch_size))
    return {
        "x": np.concatenate(xs, 0),
        "y": np.concatenate(ys, 0),
        "mask": np.concatenate(ms, 0),
    }


class PackedCohort(NamedTuple):
    """A sampled cohort packed for one vmapped FL round.

    ``x``/``y``/``mask`` share the leading ``(cohort, n_steps, batch_size)``
    layout (``n_steps = epochs·n_batches``); ``mask`` is 1.0 on real samples,
    0.0 on padding.  Padded cohort slots have ``client_ids == -1`` and an
    all-zero mask, so their local update is an exact no-op with aggregation
    weight 0.
    """

    x: np.ndarray  # (K, n_steps, B, ...) features or tokens
    y: np.ndarray  # (K, n_steps, B) int32
    mask: np.ndarray  # (K, n_steps, B) float32
    client_ids: np.ndarray  # (K,) int32, -1 = padded slot

    @property
    def cohort(self) -> int:
        return self.x.shape[0]

    @property
    def n_clients(self) -> int:
        return int((self.client_ids >= 0).sum())

    @property
    def n_samples(self) -> int:
        return int(self.mask.sum())

    def batches(self) -> Dict[str, np.ndarray]:
        """The stacked batch dict the round engine's vmapped update eats."""
        return {"x": self.x, "y": self.y, "mask": self.mask}


def pack_cohort_batches(
    clients: Sequence[Tuple[np.ndarray, np.ndarray]],
    batch_size: int,
    n_batches: int,
    epochs: int = 1,
    *,
    client_ids: Optional[Sequence[int]] = None,
    seed: Optional[Sequence[int]] = None,
    cohort_size: Optional[int] = None,
    canonical_order: bool = True,
    mesh: Optional[jax.sharding.Mesh] = None,
    num_shards: Optional[int] = None,
) -> PackedCohort:
    """Stack ``[(x_k, y_k), ...]`` into a :class:`PackedCohort`.

    Each client is padded through :func:`pack_client_batches` onto the same
    ``(epochs·n_batches, batch_size)`` grid, then the cohort is stacked on a
    new leading axis — the dimension the round engine vmaps ``local_update``
    over.  With ``canonical_order`` clients are sorted by id, and each
    client's epoch shuffles draw from ``default_rng((*seed, client_id))`` —
    a pure function of (seed, id), never of cohort position — so the packed
    arrays (and therefore the whole aggregated round) are bitwise invariant
    to sampling order.  ``cohort_size`` pads the cohort with empty slots
    (``client_ids == -1``, zero mask) up to a fixed vmap width; ``mesh``
    (or ``num_shards``) additionally pads it to a multiple of the mesh's
    data-parallel size so the dist layer can shard the cohort axis evenly
    (padded slots have aggregation weight 0 — exact no-ops).
    """
    if not clients:
        raise ValueError("pack_cohort_batches: empty cohort")
    ids = np.arange(len(clients), dtype=np.int32) if client_ids is None else (
        np.asarray(client_ids, np.int32)
    )
    if len(ids) != len(clients):
        raise ValueError("client_ids length mismatch")
    K = len(clients) if cohort_size is None else cohort_size
    if K < len(clients):
        raise ValueError(f"cohort_size={K} < {len(clients)} clients")
    dp = _data_parallel(mesh, num_shards)
    K = -(-K // dp) * dp  # pad the sharded cohort axis
    order = np.argsort(ids, kind="stable") if canonical_order else np.arange(len(ids))

    n_steps = epochs * n_batches
    x0 = np.asarray(clients[order[0]][0])
    xs = np.zeros((K, n_steps, batch_size) + x0.shape[1:], x0.dtype)
    ys = np.zeros((K, n_steps, batch_size), np.int32)
    ms = np.zeros((K, n_steps, batch_size), np.float32)
    slot_ids = np.full((K,), -1, np.int32)
    for slot, i in enumerate(order):
        x, y = clients[i]
        rng = (
            np.random.default_rng(tuple(seed) + (int(ids[i]),))
            if seed is not None else None
        )
        b = pack_client_batches(
            np.asarray(x), np.asarray(y), batch_size, n_batches, epochs, rng
        )
        xs[slot], ys[slot], ms[slot] = b["x"], b["y"], b["mask"]
        slot_ids[slot] = ids[i]
    return PackedCohort(x=xs, y=ys, mask=ms, client_ids=slot_ids)


def make_federated_features(
    seed: int,
    n: int,
    d: int,
    n_classes: int,
    n_clients: int,
    alpha: float,
    *,
    nonlinear: bool = False,
    noise: float = 1.0,
    test_frac: float = 0.2,
) -> Tuple[FederatedDataset, FeatureDataset]:
    """Build a heterogeneous federated feature dataset + held-out test set."""
    ds = make_feature_dataset(
        jax.random.PRNGKey(seed), n, d, n_classes, nonlinear=nonlinear, noise=noise
    )
    feats = np.asarray(ds.features)
    labels = np.asarray(ds.labels)
    n_test = int(n * test_frac)
    test = FeatureDataset(
        features=jnp.asarray(feats[:n_test]),
        labels=jnp.asarray(labels[:n_test]),
        n_classes=n_classes,
    )
    tr_feats, tr_labels = feats[n_test:], labels[n_test:]
    rng = np.random.default_rng(seed + 1)
    parts = dirichlet_partition(rng, tr_labels, n_clients, alpha)
    fed = FederatedDataset(tr_feats, tr_labels, parts, n_classes)
    return fed, test

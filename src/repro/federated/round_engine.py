"""Batched cohort round engine for gradient FL — the Fed3R+FT hot path.

The gradient-FL sibling of :mod:`repro.federated.engine`: where the
statistics engine folds a packed client selection into (A, b) in one
dispatch, this module runs an ENTIRE FedAvg-family round — K sampled
clients' local updates, weighted delta aggregation, the server optimizer
step, and the Scaffold control-variate scatter — inside ONE jitted
``round_step`` with donated server state:

* the cohort arrives as a :class:`repro.data.pipeline.PackedCohort`
  (stacked ``(cohort, n_steps, batch, ...)`` arrays with masks);
* ``local_update`` (the pure form from
  :mod:`repro.federated.algorithms`) is vmapped over the cohort dim;
* aggregation weights stay on device end to end — no ``float()`` host
  syncs, no Python-list delta sums (the round hot path is
  transfer-free, see ``tests/test_round_engine.py``);
* the Scaffold variates live in one stacked ``(n_clients, ...)`` table
  inside :class:`repro.federated.algorithms.ServerState`: gather by
  cohort ids on the way in, one ``.at[ids].set`` scatter on the way out;
* mesh mode (:mod:`repro.federated.dist`): under GSPMD jit the cohort dim
  is constrained over the ambient mesh's data axes
  (:func:`repro.sharding.hints.hint`) and the weighted-delta contraction
  lowers to the hierarchical all-reduce that IS the server aggregation
  (``aggregation="merge"``); with ``DistConfig(mesh=...)`` the dist layer
  wraps ``round_step`` in shard_map — the cohort axis split over the data
  axes, the weighted deltas all-reduced in two stages (intra-pod ICI,
  then cross-pod DCN), the server step replicated — still ONE dispatch.

K clients/round therefore cost 1 dispatch instead of K+1
(``benchmarks/bench_rounds.py``); :class:`ReferenceLoop` preserves the
seed-era per-client shape as the parity/benchmark baseline.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.data.pipeline import PackedCohort
from repro.federated.algorithms import (
    FLAlgorithm,
    ServerState,
    make_local_update,
    scaffold_update,
    server_init,
    server_optimizer_step,
)
from repro.federated.dist import DistConfig, DistContext, DistDispatchMixin
from repro.sharding.hints import hint
from repro.sharding.specs import replicated


@dataclass(frozen=True)
class RoundConfig:
    """Static round-engine configuration (all trace-time constants)."""

    algo: FLAlgorithm
    client_lr: float
    server_lr: float = 1.0
    weight_decay: float = 0.0
    n_total_clients: int = 0  # sizes the Scaffold cvar table / 1/N update
    dist: DistConfig = field(default_factory=DistConfig)  # backend/mesh/donate


class RoundEngine(DistDispatchMixin):
    """One-dispatch federated rounds over packed cohorts.

    ``loss_fn(params, batch) -> (batch_size,)`` per-example losses;
    ``freeze`` is the 0/1 trainability mask pytree (FT / FT-LP / FT-FEAT).
    Both are closed over, so the jitted ``round_step`` is traced once per
    cohort shape and reused for every round.
    """

    def __init__(
        self,
        cfg: RoundConfig,
        loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
        freeze: Any,
    ):
        if cfg.dist.aggregation == "psum" and cfg.algo.uses_cvar:
            raise ValueError(
                "scaffold needs the global cohort for the cvar scatter; "
                "use aggregation='merge' (GSPMD) for mesh runs"
            )
        self.cfg = cfg
        self.freeze = freeze
        self._local = make_local_update(
            loss_fn, cfg.algo, lr=cfg.client_lr,
            weight_decay=cfg.weight_decay, jit=False,
        )
        self.dist = DistContext(cfg.dist, engine="rounds")
        # mesh mode: shard the cohort axis of the packed batches/ids over
        # the data axes; server state replicated in and (post all-reduce) out
        sharded = self.dist.data_spec()
        self._step = self.dist.jit(
            self.round_step,
            in_specs=(replicated(), sharded, sharded),
            out_specs=replicated(),
        )

    def init(self, params0: Any) -> ServerState:
        return server_init(
            self.cfg.algo, params0, n_clients=self.cfg.n_total_clients
        )

    # ---- pure core (also usable directly inside shard_map) ----------------

    def round_step(
        self,
        state: ServerState,
        batches: Dict[str, jax.Array],  # leaves (cohort, n_steps, B, ...)
        client_ids: jax.Array,  # (cohort,) int32, -1 = padded slot
    ) -> ServerState:
        """One full FL round as a pure ServerState transition."""
        algo = self.cfg.algo
        # constrain the cohort dim over the ambient mesh's data axes so the
        # vmapped local updates data-parallelize; exact no-op without a mesh
        batches = jax.tree.map(lambda a: hint(a, "batch"), batches)

        if algo.uses_cvar:
            safe = jnp.clip(client_ids, 0, self.cfg.n_total_clients - 1)
            c_client = jax.tree.map(lambda t: t[safe], state.cvars)
            res = jax.vmap(self._local, in_axes=(None, 0, None, None, 0))(
                state.params, batches, self.freeze, state.c_server, c_client
            )
        else:
            zeros = jax.tree.map(jnp.zeros_like, state.params)
            res = jax.vmap(self._local, in_axes=(None, 0, None, None, None))(
                state.params, batches, self.freeze, zeros, zeros
            )

        # weighted delta aggregation, entirely on device: padded cohort slots
        # have an all-zero mask, hence weight 0 and a zero delta
        w = res.n_samples  # (cohort,)
        weighted = jax.tree.map(
            lambda d: jnp.tensordot(w, d, axes=1), res.delta
        )
        wsum = jnp.sum(w)
        # identity under "merge"; the two-stage (ICI then DCN) all-reduce of
        # the local weighted deltas under "psum" — issued once, after the
        # vmapped local updates
        weighted, wsum = self.dist.all_reduce((weighted, wsum))
        wsum = jnp.maximum(wsum, 1.0)
        avg_delta = jax.tree.map(lambda d: d / wsum, weighted)

        state = server_optimizer_step(
            algo, state, avg_delta, server_lr=self.cfg.server_lr
        )

        if algo.uses_cvar:
            # padded slots produced new_c = c_k − c (not c_k): mask them out
            # of the 1/N sum; the scatter drops them via the safe-id trick
            valid = (client_ids >= 0).astype(jnp.float32)
            cvar_delta_sum = jax.tree.map(
                lambda new, old: jnp.tensordot(valid, new - old, axes=1),
                res.new_cvar, c_client,
            )
            state = scaffold_update(
                state, cvar_delta_sum, res.new_cvar, client_ids,
                n_total_clients=self.cfg.n_total_clients,
            )
        return state._replace(round=state.round + 1)

    # ---- host API ---------------------------------------------------------

    def _args(self, cohort: PackedCohort):
        batches = {k: jnp.asarray(v) for k, v in cohort.batches().items()}
        return batches, jnp.asarray(cohort.client_ids)

    def step(self, state: ServerState, cohort: PackedCohort) -> ServerState:
        """Run one round over a packed cohort (ONE jitted dispatch)."""
        with self.dist.telemetry.span("round_step", engine="rounds"):
            self.dist.dispatch()
            return self._step(state, *self._args(cohort))

    def lower(self, state: ServerState, cohort: PackedCohort) -> jax.stages.Lowered:
        """The round program for this cohort shape, lowered without running
        it: ``.compile().memory_analysis()`` is its device footprint."""
        return self._step.lower(state, *self._args(cohort))


class ReferenceLoop:
    """The seed-era per-client round: K jitted local updates + host-side
    Python aggregation + one server dispatch (K+1 dispatches/round).

    Kept as the parity oracle for the engine (same ``local_update`` math,
    same pure server transition) and as the benchmark baseline the
    dispatch-reduction claim is measured against.  Mirrors the old
    ``Server.aggregate`` shape, including the per-client ``float()`` host
    syncs the engine removes.
    """

    def __init__(
        self,
        cfg: RoundConfig,
        loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
        freeze: Any,
    ):
        self.cfg = cfg
        self.freeze = freeze
        self._local = make_local_update(
            loss_fn, cfg.algo, lr=cfg.client_lr,
            weight_decay=cfg.weight_decay, jit=True,
        )
        self._server = jax.jit(
            lambda st, avg: server_optimizer_step(
                cfg.algo, st, avg, server_lr=cfg.server_lr
            )
        )
        self.dispatches = 0

    def init(self, params0: Any) -> ServerState:
        return server_init(
            self.cfg.algo, params0, n_clients=self.cfg.n_total_clients
        )

    def step(self, state: ServerState, cohort: PackedCohort) -> ServerState:
        algo = self.cfg.algo
        zeros = jax.tree.map(jnp.zeros_like, state.params)
        results, ids, cvar_olds = [], [], []
        for slot in range(cohort.cohort):
            cid = int(cohort.client_ids[slot])
            if cid < 0:
                continue
            batches = {
                k: jnp.asarray(v[slot]) for k, v in cohort.batches().items()
            }
            c_client = (
                jax.tree.map(lambda t: t[cid], state.cvars)
                if algo.uses_cvar else zeros
            )
            c_server = state.c_server if algo.uses_cvar else zeros
            res = self._local(
                state.params, batches, self.freeze, c_server, c_client
            )
            self.dispatches += 1
            results.append(res)
            ids.append(cid)
            cvar_olds.append(c_client)

        # host-side aggregation (the shape the engine replaces)
        weights = [float(r.n_samples) for r in results]
        wsum = max(sum(weights), 1.0)
        avg = jax.tree.map(
            lambda *ds: sum(wk * d for wk, d in zip(weights, ds)) / wsum,
            *[r.delta for r in results],
        )
        state = self._server(state, avg)
        self.dispatches += 1

        if algo.uses_cvar:
            cvar_delta_sum = jax.tree.map(
                lambda *cs: sum(cs),
                *[
                    jax.tree.map(lambda n, o: n - o, r.new_cvar, old)
                    for r, old in zip(results, cvar_olds)
                ],
            )
            c_server = jax.tree.map(
                lambda c, d: c + d / self.cfg.n_total_clients,
                state.c_server, cvar_delta_sum,
            )
            cvars = state.cvars
            for cid, r in zip(ids, results):
                cvars = jax.tree.map(
                    lambda t, n, i=cid: t.at[i].set(n), cvars, r.new_cvar
                )
            state = state._replace(c_server=c_server, cvars=cvars)
        return state._replace(round=state.round + 1)

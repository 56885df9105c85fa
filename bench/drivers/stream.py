"""Streaming refresh: each round is one arrival wave through
``StreamingEngine.absorb`` with ``refresh_every=1``, in a closed loop: the
next wave is handed over once the refreshed W is ready.  One unit is one
wave; each pass over the federation starts from ``init``.  With
``warm_rounds`` in the traffic file, a pass's first wave holds the first
that many rounds together."""
from __future__ import annotations

import time
from typing import Dict, List

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import work
from bench.drivers import Unit
from repro.data.pipeline import pack_arrival_waves
from repro.federated.dist import DistConfig
from repro.federated.streaming_engine import StreamConfig, StreamingEngine


class Driver:
    unit = "wave"

    def __init__(self, config: dict, traffic: dict, fed, mesh=None, seed: int = 0):
        self.d, self.C = fed.feature_dim, fed.n_classes
        self.ridge_lambda = config["assumed"]["ridge_lambda"]
        dist = (DistConfig() if mesh is None
                else DistConfig(aggregation="psum", mesh=mesh))
        self.engine = StreamingEngine(StreamConfig(
            n_classes=self.C, ridge_lambda=self.ridge_lambda,
            refresh_every=traffic["refresh_every"], dist=dist,
        ))
        warm = traffic.get("warm_rounds", 0)
        # the engine adds one wave at a time into its running sums
        self.groups = ([np.sort(np.concatenate(fed.rounds[:warm]))] if warm else []) + [
            np.asarray(r) for r in fed.rounds[warm:]]
        self.n_waves = self.units_per_pass = len(self.groups)
        width = config["assumed"]["clients_per_round"]
        self.waves = []
        for ids in self.groups:
            p = pack_arrival_waves(
                [[fed.client(int(k)) for k in ids]], client_ids=[ids],
                clients_per_wave=max(width, len(ids)),
                round_to=traffic["round_to"], mesh=mesh,
            )
            self.waves.append(p._replace(
                inputs=self.engine.dist.place(p.inputs, axis=1),
                labels=self.engine.dist.place(p.labels, axis=1),
                mask=self.engine.dist.place(p.mask, axis=1),
            ))
        self.wave_samples = [int(sum(fed.offsets[k + 1] - fed.offsets[k] for k in ids))
                             for ids in self.groups]
        solve = work.solve_flops(self.d, self.C)
        self.wave_flops = [work.stats_flops(n, self.d, self.C) + solve
                           for n in self.wave_samples]
        rng = np.random.default_rng([seed, 7])
        k = min(traffic["checked_waves"], self.n_waves - 1)
        self.checked = set(rng.choice(self.n_waves - 1, size=k, replace=False).tolist())
        self.checked.add(self.n_waves - 1)
        self.t = 0
        self.state = None
        self.kept: Dict[int, List[Dict]] = {}

    def step(self) -> Unit:
        t = self.t
        if t == 0:
            self.state = self.engine.init(self.d)
        with TraceAnnotation("wave"):
            t0 = time.perf_counter()
            self.state, _ = self.engine.absorb(self.state, self.waves[t])
            with TraceAnnotation("block"):
                self.state.W.block_until_ready()
            latency = time.perf_counter() - t0
        if t in self.checked:
            s = self.state
            # the next absorb donates the state: keep copies
            self.kept.setdefault(t, []).append(
                {"L": jnp.copy(s.L), "b": jnp.copy(s.b), "W": jnp.copy(s.W),
                 "n": jnp.copy(s.n)}
            )
        self.t = (t + 1) % self.n_waves
        return Unit(self.wave_samples[t], self.wave_flops[t], latency)

    def reset(self) -> None:
        self.t = 0
        self.state = None
        self.kept = {}

    def answers(self) -> Dict[int, List[Dict]]:
        return dict(self.kept)

    def free(self) -> None:
        self.waves = []
        self.state = None

"""Batch statistics: every round through ``AccumulationEngine.accumulate``,
then ``fed3r.solve``.  One unit is one whole pass over the federation.

A driver for a backbone configuration subclasses this one: it sets
``feature_fn`` (the program's forward, which the engine runs inside its
scan over the packed tokens), puts the program's weights in ``params``,
and adds the forward's work (the backbone module's ``flops``) into
``flops``."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import work
from bench.drivers import Unit
from repro.core import fed3r
from repro.data.pipeline import PackedClients, pack_client_shards
from repro.federated.engine import AccumulationEngine, EngineConfig


class Driver:
    unit = "pass"
    feature_fn = None  # None: the inputs are the features

    def __init__(self, config: dict, traffic: dict, fed, mesh=None, seed: int = 0):
        if mesh is not None:
            raise ValueError("the batch driver runs on one chip")
        self.d, self.C = fed.feature_dim, fed.n_classes
        self.ridge_lambda = config["assumed"]["ridge_lambda"]
        self.n_rounds = len(fed.rounds)
        self.engine = AccumulationEngine(EngineConfig(n_classes=self.C),
                                         feature_fn=self.feature_fn)
        self.params = None  # the feature_fn's weights, on the device
        self.rounds: List[PackedClients] = []
        for r in range(self.n_rounds):
            p = pack_client_shards(
                fed.round_clients(r), traffic["clients_per_shard"],
                client_ids=fed.rounds[r], round_to=traffic["round_to"],
            )
            self.rounds.append(p._replace(
                inputs=jax.device_put(p.inputs), labels=jax.device_put(p.labels),
                mask=jax.device_put(p.mask),
            ))
        # the engine adds one client at a time into its running sums
        self.groups = [np.array([k]) for r in fed.rounds for k in r]
        self.units_per_pass = 1
        self.samples = fed.n_samples
        self.flops = (work.stats_flops(self.samples, self.d, self.C)
                      + work.solve_flops(self.d, self.C))
        self.round_spans_s: List[float] = []  # host time of each un-blocked accumulate
        self.kept: List[Dict] = []

    def step(self) -> Unit:
        with TraceAnnotation("pass"):
            acc = self.engine.init(self.d)
            for packed in self.rounds:
                with TraceAnnotation("round"):
                    t0 = time.perf_counter()
                    acc = self.engine.accumulate(acc, packed, self.params)
                    self.round_spans_s.append(time.perf_counter() - t0)
            with TraceAnnotation("solve"):
                W = fed3r.solve(acc.stats, self.ridge_lambda)
            with TraceAnnotation("block"):
                jax.block_until_ready((acc, W))
        self.kept.append({"A": acc.stats.A, "b": acc.stats.b, "n": acc.stats.n,
                          "counts": acc.class_counts, "W": W})
        return Unit(self.samples, self.flops, None)

    def reset(self) -> None:
        self.round_spans_s.clear()
        self.kept.clear()

    def answers(self) -> Dict[int, List[Dict]]:
        """Every pass's answer, due after the pass's last round."""
        return {len(self.groups) - 1: list(self.kept)}

    def free(self) -> None:
        self.rounds = []

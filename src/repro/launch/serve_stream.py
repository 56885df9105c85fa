"""Live-refresh classifier serving over a streaming FED3R arrival process.

The serving-side driver of the streaming engine
(:mod:`repro.federated.streaming_engine`): clients arrive over time
(Poisson or label-skewed schedule), the server folds each arrival SEGMENT
through one jitted dispatch, and between segments it answers queries with
the currently served classifier — which is as fresh as the refresh policy
paid for:

* ``--policy arrival``  refresh-on-arrival (``refresh_every=1``): every
  wave re-solves W by two triangular solves; queries never see stale
  weights;
* ``--policy every-k``  refresh every k-th wave (``--k``): cheaper
  refresh cadence, and the reported STALENESS metric (waves / samples
  absorbed since the last re-solve) quantifies what queries see.

``--engine slots`` routes the same loop through the continuous-batching
slot engine (:mod:`repro.launch.serving_engine`): absorbs go through its
absorb stage, query bursts are admitted to its queue and answered by the
one-dispatch serve stage against the pinned global slot (refreshed at
tick time whenever the stream advanced — the slot engine's solve stage
owns the refresh, so the ``--policy`` staleness knobs report the stream
state's lag while queries see a tick-fresh head).  ``--engine lru``
(default) is the legacy synchronous driver.  Same log/report shape either
way.

``--engine async`` serves over ASYNCHRONOUS merge-on-arrival rounds
(:mod:`repro.federated.async_engine`): per round a cohort (~``--rate``
clients, sampled from the health tracker's currently-eligible set) uploads
through a seeded chaos schedule (duplicates deduped, reordered and delayed
arrivals folding late under the staleness bound), rounds close at their
deadline instead of waiting for stragglers, and query bursts are answered
by the LIVE classifier — retired state plus every open partial cohort.
The staleness columns report open (unretired) rounds and the samples
sitting in their slots; the final report carries the chaos counters.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_stream --waves 24 --rate 4 \
      --policy every-k --k 4 --segment 6 --engine slots
  PYTHONPATH=src python -m repro.launch.serve_stream --waves 20 --rate 6 \
      --segment 5 --engine async
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import fed3r
from repro.data.pipeline import make_federated_features
from repro.federated.arrivals import (
    dominant_labels,
    pack_schedule,
    poisson_schedule,
    skewed_schedule,
)
from repro.federated.streaming_engine import StreamConfig, StreamingEngine
from repro.federated.telemetry import get_telemetry
from repro.launch.compile_cache import enable_compile_cache


def serve_stream(
    n_waves: int = 24,
    rate: float = 4.0,
    policy: str = "arrival",
    k: int = 4,
    segment: int = 6,
    skew: float = 0.0,
    n_clients: int = 64,
    d: int = 64,
    n_classes: int = 10,
    ridge_lambda: float = 1e-2,
    engine: str = "lru",
    seed: int = 0,
    verbose: bool = True,
) -> dict:
    """Run the arrival → absorb → query loop; returns the serving log.

    ``engine="lru"`` is the legacy synchronous driver; ``engine="slots"``
    rides the continuous-batching slot engine (absorb/serve stages, one
    dispatch each) behind the same log shape; ``engine="async"`` serves
    the live classifier of the merge-on-arrival round engine under a
    seeded chaos arrival schedule.
    """
    if engine not in ("lru", "slots", "async"):
        raise ValueError(f"unknown serving engine: {engine!r}")
    # noise calibrated so the served accuracy GROWS over the stream —
    # stale refreshes are then visible in the query-burst numbers
    fed, test = make_federated_features(
        seed=seed, n=8000, d=d, n_classes=n_classes, n_clients=n_clients,
        alpha=0.1, noise=7.0,
    )
    if engine == "async":
        return _serve_async(
            fed, jnp.asarray(test.features), jnp.asarray(test.labels),
            n_rounds=n_waves, rate=rate, segment=segment, d=d,
            n_classes=n_classes, ridge_lambda=ridge_lambda, seed=seed,
            verbose=verbose,
        )
    if skew > 0.0:
        schedule = skewed_schedule(
            dominant_labels(fed), n_waves, skew=skew, seed=seed
        )
    else:
        schedule = poisson_schedule(fed.n_clients, n_waves, rate, seed=seed)
    packed = pack_schedule(fed, schedule)

    refresh_every = 1 if policy == "arrival" else k
    test_x = jnp.asarray(test.features)
    test_y = jnp.asarray(test.labels)
    test_np = np.asarray(test.features)

    slot_server = None
    if engine == "slots":
        from repro.launch.serving_engine import ServingConfig, ServingEngine

        # global-only traffic: a tiny table (slot 0 + one spare) suffices,
        # and every query carries tenant -1 (no server-side data)
        slot_server = ServingEngine(
            ServingConfig(
                n_classes=n_classes, ridge_lambda=ridge_lambda, n_slots=2,
                queue_depth=max(4096, len(test_np)),
            ),
            fed,
        )
        slot_server.init(d)
        stream_engine = slot_server.stream
        state = slot_server.state
    else:
        stream_engine = StreamingEngine(StreamConfig(
            n_classes=n_classes, ridge_lambda=ridge_lambda,
            refresh_every=refresh_every,
        ))
        state = stream_engine.init(d)

    log: dict = {
        "wave": [], "clients_seen": [], "samples_seen": [],
        "stale_waves": [], "stale_samples": [], "acc_served": [],
        # this driver serves ONE global head to all tenants; per-tenant
        # heads (with their own cache staleness) are repro.launch.serve_heads
        "served_head": "global",
        "engine": engine,
    }
    seen = 0
    t0 = time.perf_counter()  # monotonic: wall clock steps under NTP
    if verbose:
        print(f"engine={engine} policy={policy} refresh_every={refresh_every} "
              f"waves={packed.n_waves} clients={packed.n_clients}")
        print("served head: GLOBAL (one W for all tenants; staleness below "
              "is refresh-policy lag — for per-tenant heads and their cache "
              "staleness see repro.launch.serve_heads)")
        print("wave | arrived | samples seen | stale (waves/samples) | acc(served W)")
    for lo in range(0, packed.n_waves, segment):
        chunk = packed.slice_waves(lo, min(lo + segment, packed.n_waves))
        if engine == "slots":
            slot_server.absorb(chunk)  # ONE dispatch per segment
            state = slot_server.state
            # the query burst: every test row admitted with tenant -1 →
            # served by the pinned global slot in ONE serve dispatch
            scores, _ = slot_server.query(
                np.full((len(test_np),), -1, np.int64), test_np
            )
            acc = float(jnp.mean(
                (jnp.argmax(scores, axis=-1) == test_y).astype(jnp.float32)
            ))
        else:
            state, trace = stream_engine.absorb(state, chunk)
            # a query burst against the served (possibly stale) classifier
            acc = float(fed3r.accuracy(
                stream_engine.classifier(state), test_x, test_y
            ))
        seen += chunk.n_clients
        log["wave"].append(int(state.wave))
        log["clients_seen"].append(seen)
        log["samples_seen"].append(float(state.n))
        log["stale_waves"].append(int(state.stale_waves))
        log["stale_samples"].append(float(state.stale_samples))
        log["acc_served"].append(acc)
        if verbose:
            print(f"{int(state.wave):4d} | {chunk.n_clients:7d} | "
                  f"{float(state.n):12.0f} | {int(state.stale_waves):5d} /"
                  f"{float(state.stale_samples):8.0f} | {acc:.4f}")
    if engine == "slots":
        state = slot_server.state
        acc = log["acc_served"][-1]  # slot ticks already serve a fresh head
        log["dispatches"] = (
            slot_server.absorb_dispatches + slot_server.solve_dispatches
            + slot_server.serve_dispatches
        )
        log["serve_dispatches"] = slot_server.serve_dispatches
        log["stage_s"] = dict(slot_server.stage_s)
    else:
        state = stream_engine.refresh(state)  # final sync before reporting
        acc = float(fed3r.accuracy(
            stream_engine.classifier(state), test_x, test_y
        ))
        log["dispatches"] = stream_engine.dispatches
    log["acc_final"] = acc
    log["wall_s"] = time.perf_counter() - t0
    get_telemetry().gauge(
        "driver_wall_seconds", driver="serve_stream", engine=engine
    ).set(log["wall_s"])
    if verbose:
        print(f"final sync: acc={acc:.4f}  "
              f"({log['dispatches']} dispatches for {packed.n_waves} waves, "
              f"{log['wall_s']:.2f}s)")
    return log


def _serve_async(
    fed, test_x, test_y, *, n_rounds, rate, segment, d, n_classes,
    ridge_lambda, seed, verbose,
) -> dict:
    """The ``--engine async`` loop: chaos-injected merge-on-arrival rounds
    with query bursts served from the LIVE classifier between segments."""
    import time as _time

    from repro.federated.arrivals import (
        ChaosSpec,
        chaos_round_events,
        latency_profile,
    )
    from repro.federated.async_engine import (
        AsyncConfig,
        AsyncRoundEngine,
        client_payloads,
    )

    t0 = _time.perf_counter()
    per_round = max(1, int(round(rate)))
    eng = AsyncRoundEngine(AsyncConfig(
        n_classes=n_classes, ridge_lambda=ridge_lambda, cohort=per_round,
        deadline=1.0, staleness_rounds=1,
    ))
    state = eng.init(d)
    payloads = client_payloads(fed, n_classes)
    latency = latency_profile(fed.n_clients, 0.2, seed=seed)
    spec = ChaosSpec(duplicate=0.05, reorder=0.2, delay=0.1, seed=seed)
    log: dict = {
        "wave": [], "clients_seen": [], "samples_seen": [],
        "stale_waves": [], "stale_samples": [], "acc_served": [],
        "served_head": "global", "engine": "async",
    }
    seen = 0
    if verbose:
        print(f"engine=async rounds={n_rounds} cohort~{per_round} "
              f"deadline={eng.cfg.deadline} staleness={eng.cfg.staleness_rounds}")
        print("round | arrived | samples retired | open (rounds/samples) | acc(live W)")
    for lo in range(0, n_rounds, segment):
        for r in range(lo, min(lo + segment, n_rounds)):
            eligible = [
                c for c in range(fed.n_clients) if eng.health.is_eligible(c, r)
            ]
            rng = np.random.default_rng((seed, r, 0xA51))
            take = min(per_round, len(eligible))
            cohort = sorted(
                int(eligible[i])
                for i in rng.choice(len(eligible), size=take, replace=False)
            )
            eng.begin_round(r, cohort, float(r))
            events = chaos_round_events(cohort, latency, spec, r)
            on_time = [e for e in events if e.t <= eng.cfg.deadline]
            late = [e for e in events if e.t > eng.cfg.deadline]
            for ev in sorted(on_time):
                state, _ = eng.deliver(state, ev, payloads[ev.client],
                                       now=float(r) + ev.t)
            state = eng.close_round(state, r, now=float(r) + eng.cfg.deadline)
            # stragglers past the deadline keep merging (staleness bound)
            for ev in sorted(late):
                state, _ = eng.deliver(state, ev, payloads[ev.client],
                                       now=float(r) + ev.t)
            seen += len(cohort)
        acc = float(fed3r.accuracy(eng.live_classifier(state), test_x, test_y))
        open_rounds = eng._next_begin - eng._next_retire
        open_samples = float(jnp.sum(state.n_slots))
        log["wave"].append(eng._next_begin)
        log["clients_seen"].append(seen)
        log["samples_seen"].append(float(state.n))
        log["stale_waves"].append(open_rounds)
        log["stale_samples"].append(open_samples)
        log["acc_served"].append(acc)
        if verbose:
            print(f"{eng._next_begin:5d} | {seen:7d} | {float(state.n):15.0f} | "
                  f"{open_rounds:5d} /{open_samples:8.0f} | {acc:.4f}")
    state = eng.drain(state)
    acc = float(fed3r.accuracy(eng.classifier(state), test_x, test_y))
    log["acc_final"] = acc
    log["dispatches"] = eng.dispatches
    log["chaos"] = eng.report()
    log["wall_s"] = _time.perf_counter() - t0
    get_telemetry().gauge(
        "driver_wall_seconds", driver="serve_stream", engine="async"
    ).set(log["wall_s"])
    if verbose:
        rep = log["chaos"]
        print(f"final drain: acc={acc:.4f}  ({eng.dispatches} dispatches; "
              f"folded={rep['folded']} late={rep['late_folds']} "
              f"dup={rep['duplicates']} stale={rep['stale_rejected']} "
              f"dropped={rep['dropped_uploads']}, {log['wall_s']:.2f}s)")
    return log


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--waves", type=int, default=24)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--policy", choices=("arrival", "every-k"), default="arrival")
    ap.add_argument("--k", type=int, default=4, help="refresh cadence (every-k)")
    ap.add_argument("--segment", type=int, default=6,
                    help="waves absorbed per dispatch between query bursts")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="label-skewed arrival order in [0, 1]")
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--ridge-lambda", type=float, default=1e-2)
    ap.add_argument("--engine", choices=("lru", "slots", "async"),
                    default="lru",
                    help="legacy synchronous driver, slot-serving engine, "
                         "or chaos-injected async merge-on-arrival rounds")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    serve_stream(
        n_waves=args.waves, rate=args.rate, policy=args.policy, k=args.k,
        segment=args.segment, skew=args.skew, n_clients=args.clients,
        d=args.d, n_classes=args.classes, ridge_lambda=args.ridge_lambda,
        engine=args.engine, seed=args.seed,
    )


if __name__ == "__main__":
    main()

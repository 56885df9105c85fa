"""Federated training driver on a jax mesh (the datacenter path).

Phase 1 (FED3R, Algorithm 1): statistics pass over packed client shards
through the accumulation engine — ONE jitted scan, backbone features
batched per shard.  Solve → temperature-calibrate → install the classifier.

Phase 2 (FED3R+FT, §4.4): federated fine-tuning through the batched cohort
round engine (:mod:`repro.federated.round_engine`) — the sampled cohort is
packed into stacked ``(cohort, n_steps, batch)`` token arrays and the WHOLE
round (vmapped local updates over the cohort dim, on-device weighted
aggregation, server optimizer step) runs as one jitted dispatch, with the
cohort dim sharded over the mesh's data axes (the weighted-delta
contraction lowers to the hierarchical all-reduce that IS the server
aggregation).  The full :class:`ServerState` — backbone+head params,
optimizer buffers, round index — checkpoints every eval; ``--resume``
continues from the latest snapshot and reproduces the uninterrupted run
(cohorts and shuffles are pure functions of the round index).

The trainer runs over the host mesh of whatever devices exist: reduced
configs on CPU, the full-width ``fed3r-mnv2-proxy`` on one TPU chip
(``chip_smoke.py`` drives it there).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch fed3r-mnv2-proxy-smoke \
      --rounds 30 --ft-strategy feat [--algorithm fedavg] [--resume]
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_checkpoint, load_pytree, save_pytree
from repro.configs import get_config
from repro.core import calibration, fed3r
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import make_token_dataset
from repro.data.pipeline import pack_client_shards, pack_cohort_batches
from repro.federated.algorithms import make_algorithm, server_state_from_tree
from repro.federated.engine import AccumulationEngine, EngineConfig
from repro.federated.round_engine import RoundConfig, RoundEngine
from repro.federated.sampling import sample_round
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_cls_per_example_loss
from repro.models import build_model

_FT_SEED = 3  # phase-2 sampling/shuffle seed (pure function of the round)


def run(
    arch: str,
    *,
    n_classes: int = 16,
    n_clients: int = 40,
    clients_per_round: int = 8,
    rounds: int = 30,
    seq_len: int = 32,
    n_samples: int = 2048,
    lr: float = 0.05,
    local_batch_size: int = 64,
    algorithm: str = "fedavg",
    ft_strategy: str = "feat",
    use_fed3r_init: bool = True,
    ckpt_dir: Optional[str] = None,
    resume: bool = False,
    verbose: bool = True,
) -> dict:
    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = make_host_mesh()
    jax.set_mesh(mesh)

    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    ds = make_token_dataset(jax.random.PRNGKey(1), n_samples, seq_len,
                            cfg.vocab_size, n_classes)
    parts = dirichlet_partition(
        np.random.default_rng(2), np.asarray(ds.labels), n_clients, alpha=0.0
    )
    n_test = n_samples // 5
    test_tokens, test_labels = ds.tokens[:n_test], ds.labels[:n_test]
    tokens_np, labels_np = np.asarray(ds.tokens), np.asarray(ds.labels)

    log = {"fed3r_acc": None, "ft_acc": [], "rounds": [], "ft_step_memory": None}

    # Resuming from a full-state snapshot makes phase 1 dead work: the
    # loaded ServerState overwrites whatever head it would produce.
    resume_path = latest_checkpoint(ckpt_dir) if (resume and ckpt_dir) else None

    # ---- phase 1: FED3R statistics pass -------------------------------------
    W_head = None
    if use_fed3r_init and resume_path is None:
        t0 = time.time()
        # Every client contributes exactly once.  The engine packs clients
        # into shards and folds them in ONE jitted scan (backbone feature
        # extraction batched per shard) — the datacenter-scale replacement
        # for the former per-client stats_step dispatch loop.
        engine = AccumulationEngine(
            EngineConfig(n_classes=n_classes),
            feature_fn=lambda p, toks: model.extract_features(
                p, {"tokens": toks}
            ),
        )
        packed = pack_client_shards(
            [(tokens_np[parts[k]], labels_np[parts[k]]) for k in range(n_clients)],
            clients_per_shard=clients_per_round,
        )
        acc = engine.accumulate(engine.init(cfg.d_feat), packed, params)
        stats = acc.stats
        W = fed3r.solve(stats, 0.01)
        feats_test = model.extract_features(params, {"tokens": test_tokens})
        acc = float(fed3r.accuracy(W, feats_test, test_labels))
        scores = fed3r.predict(W, model.extract_features(params, {"tokens": ds.tokens[n_test:n_test+512]}))
        temp, _ = calibration.calibrate_temperature(scores, ds.labels[n_test:n_test+512])
        W_head = calibration.fold_temperature(W, temp)
        log["fed3r_acc"] = acc
        if verbose:
            print(f"[fed3r] classifier in {n_clients} client visits "
                  f"({time.time()-t0:.1f}s)  acc={acc:.4f}  T={float(temp):.2f}")

    # ---- phase 2: federated fine-tuning on the cohort round engine ----------
    head = {"W": W_head if W_head is not None
            else 0.01 * jax.random.normal(rng, (cfg.d_feat, n_classes)),
            "b": jnp.zeros((n_classes,), jnp.float32)}
    full = {"backbone": params, "head": head}

    freeze = {
        "backbone": jax.tree.map(
            lambda _: 0.0 if ft_strategy == "lp" else 1.0, params
        ),
        "head": jax.tree.map(
            lambda _: 0.0 if ft_strategy == "feat" else 1.0, head
        ),
    }

    algo = make_algorithm(algorithm)
    round_engine = RoundEngine(
        RoundConfig(
            algo=algo, client_lr=lr, n_total_clients=n_clients,
        ),
        make_cls_per_example_loss(cfg),
        freeze,
    )
    if resume_path is not None:
        state = server_state_from_tree(load_pytree(resume_path))
        start_round = int(state.round)
        if verbose:
            print(f"[ft:{ft_strategy}] resuming from {resume_path} (round {start_round})")
    else:
        state = round_engine.init(full)
        start_round = 0

    @jax.jit
    def evaluate(p):
        feats = model.extract_features(p["backbone"], {"tokens": test_tokens})
        logits = feats @ p["head"]["W"] + p["head"]["b"]
        return jnp.mean((jnp.argmax(logits, -1) == test_labels).astype(jnp.float32))

    max_nk = max(len(parts[k]) for k in range(n_clients))
    n_batches = -(-max_nk // local_batch_size)
    for rnd in range(start_round, rounds):
        chosen = sample_round(n_clients, clients_per_round, rnd, seed=_FT_SEED)
        cohort = pack_cohort_batches(
            [(tokens_np[parts[int(k)]], labels_np[parts[int(k)]]) for k in chosen],
            local_batch_size, n_batches, client_ids=chosen, seed=(_FT_SEED, rnd),
        )
        if rnd == start_round:
            # the compiled round program's device footprint (argument,
            # output, temp bytes), read before its first run
            mem = round_engine.lower(state, cohort).compile().memory_analysis()
            log["ft_step_memory"] = mem
            if verbose:
                print(f"[ft:{ft_strategy}] round program of {clients_per_round} "
                      f"clients: argument {mem.argument_size_in_bytes} B, "
                      f"output {mem.output_size_in_bytes} B, alias "
                      f"{mem.alias_size_in_bytes} B, temp {mem.temp_size_in_bytes} B")
        state = round_engine.step(state, cohort)  # ONE dispatch per round
        if (rnd + 1) % 5 == 0 or rnd == rounds - 1:
            acc = float(evaluate(state.params))
            log["rounds"].append(rnd + 1)
            log["ft_acc"].append(acc)
            if verbose:
                print(f"[ft:{ft_strategy}] round {rnd+1:4d}  acc={acc:.4f}")
            if ckpt_dir:
                # round-resumable: the FULL server state, not just the head
                save_pytree(os.path.join(ckpt_dir, f"ckpt_{rnd+1}.npz"), state)
    return log


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="fed3r-mnv2-proxy-smoke")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=40)
    ap.add_argument("--per-round", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--local-batch", type=int, default=64)
    ap.add_argument("--algorithm", default="fedavg",
                    choices=["fedavg", "fedavgm", "fedprox", "scaffold",
                             "fedadam", "fedyogi"])
    ap.add_argument("--ft-strategy", default="feat", choices=["full", "lp", "feat"])
    ap.add_argument("--no-fed3r-init", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    run(
        args.arch, rounds=args.rounds, n_clients=args.clients,
        clients_per_round=args.per_round, seq_len=args.seq_len,
        local_batch_size=args.local_batch, algorithm=args.algorithm,
        ft_strategy=args.ft_strategy, use_fed3r_init=not args.no_fed3r_init,
        ckpt_dir=args.ckpt_dir, resume=args.resume,
    )


if __name__ == "__main__":
    main()

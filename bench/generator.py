"""The traffic generator: a federation deployment's clients, data and rounds.

One general generator for every cell.  A configuration file fixes the
deployment (clients, samples, classes, feature width, and under
``assumed`` the size skew, label skew, round size and ridge); a traffic
file says how the rounds are fed to the program.

What is fixed and what the seed draws:

* Client sizes and the membership of each round are the deployment's own:
  drawn once from ``assumed.plan_seed``, as a real user split's counts are
  fixed.  Every seed therefore folds the same multiset of round shapes and
  needs the same compiled programs.
* The seed draws the class centroids, each client's labels, the features,
  and the order in which the rounds arrive.  A traffic file may hold the
  plan's first ``warm_rounds`` rounds at the front (in a seeded order among
  themselves), so that a first wave merged from them has one shape.

Arithmetic copied from the program's generators so that a later change to
them cannot move the yardstick: lognormal client sizes from
``repro.data.partition.quantity_skew_sizes``; Gaussian class-conditional
features from ``repro.data.synthetic.make_feature_dataset``; Dirichlet
label skew in the per-client form of Hsu et al. (2019), which
``repro.data.partition.dirichlet_partition`` applies per class.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, NamedTuple, Tuple

import numpy as np

FEATURE_CHUNKS = 8  # the features are drawn in this many fixed row blocks


class Federation(NamedTuple):
    features: np.ndarray  # (n, d) float32, client k's rows at offsets[k]:offsets[k+1]
    labels: np.ndarray  # (n,) int32
    offsets: np.ndarray  # (n_clients + 1,) int64
    rounds: List[np.ndarray]  # client ids of each round, in arrival order
    n_classes: int

    @property
    def n_samples(self) -> int:
        return int(self.labels.shape[0])

    @property
    def feature_dim(self) -> int:
        return int(self.features.shape[1])

    def client(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        lo, hi = self.offsets[k], self.offsets[k + 1]
        return self.features[lo:hi], self.labels[lo:hi]

    def round_clients(self, r: int) -> List[Tuple[np.ndarray, np.ndarray]]:
        return [self.client(int(k)) for k in self.rounds[r]]


def client_sizes(n: int, n_clients: int, sigma: float, plan_seed: int) -> np.ndarray:
    """Lognormal client sizes summing to n, each at least 1 (copied from
    ``quantity_skew_sizes``), drawn from the deployment's constant seed."""
    rng = np.random.default_rng(plan_seed)
    raw = rng.lognormal(mean=0.0, sigma=sigma, size=n_clients)
    sizes = np.maximum(1, np.floor(raw / raw.sum() * n).astype(np.int64))
    while sizes.sum() > n:
        sizes[int(np.argmax(sizes))] -= 1
    while sizes.sum() < n:
        sizes[int(np.argmin(sizes))] += 1
    return sizes


def round_plan(n_clients: int, clients_per_round: int, plan_seed: int) -> List[np.ndarray]:
    """Every client once, in rounds of ``clients_per_round`` drawn without
    replacement from the deployment's constant seed; ids sorted in a round."""
    perm = np.random.default_rng([plan_seed, 1]).permutation(n_clients)
    return [np.sort(perm[i:i + clients_per_round])
            for i in range(0, n_clients, clients_per_round)]


def _dirichlet_labels(rng: np.random.Generator, sizes: np.ndarray, n_classes: int,
                      alpha: float) -> np.ndarray:
    """Each client draws a class mixture q_k ~ Dir(α·1) and its labels from it."""
    q = rng.dirichlet(np.full(n_classes, alpha), size=len(sizes))
    cdf = np.cumsum(q, axis=1)
    cdf[:, -1] = 1.0
    owner = np.repeat(np.arange(len(sizes)), sizes)
    # one sorted search over every client's CDF, each shifted by its index
    flat = (cdf + np.arange(len(sizes))[:, None]).ravel()
    u = rng.random(len(owner)) + owner
    hit = np.searchsorted(flat, u, side="right")
    return np.minimum(hit - owner * n_classes, n_classes - 1).astype(np.int32)


def _features(seq: np.random.SeedSequence, means: np.ndarray, labels: np.ndarray,
              noise: float) -> np.ndarray:
    """means[label] + noise·N(0, 1), each of FEATURE_CHUNKS row blocks from
    its own stream, filled on a few threads (the same numbers on any)."""
    n, d = len(labels), means.shape[1]
    out = np.empty((n, d), np.float32)
    edges = np.linspace(0, n, FEATURE_CHUNKS + 1).astype(np.int64)
    streams = seq.spawn(FEATURE_CHUNKS)

    def fill(i: int) -> None:
        lo, hi = edges[i], edges[i + 1]
        block = out[lo:hi]
        np.random.default_rng(streams[i]).standard_normal(
            (hi - lo, d), dtype=np.float32, out=block)
        block *= np.float32(noise)
        block += means[labels[lo:hi]]

    with ThreadPoolExecutor(4) as pool:
        list(pool.map(fill, range(FEATURE_CHUNKS)))
    return out


def make_federation(config: dict, seed: int, warm_rounds: int = 0) -> Federation:
    a = config["assumed"]
    n, d, C, K = (config[k] for k in ("n_samples", "feature_dim", "n_classes", "n_clients"))
    sizes = client_sizes(n, K, a["client_size_sigma"], a["plan_seed"])
    plan = round_plan(K, a["clients_per_round"], a["plan_seed"])
    s_mean, s_lab, s_noise, s_order = np.random.SeedSequence(seed).spawn(4)
    means = np.float32(a["class_scale"]) * np.random.default_rng(s_mean).standard_normal(
        (C, d), dtype=np.float32)
    labels = _dirichlet_labels(np.random.default_rng(s_lab), sizes, C,
                               a["label_dirichlet_alpha"])
    features = _features(s_noise, means, labels, a["feature_noise"])
    r_order = np.random.default_rng(s_order)
    order = np.concatenate([r_order.permutation(warm_rounds),
                            warm_rounds + r_order.permutation(len(plan) - warm_rounds)])
    return Federation(
        features=features,
        labels=labels,
        offsets=np.concatenate([[0], np.cumsum(sizes)]),
        rounds=[plan[i] for i in order],
        n_classes=C,
    )

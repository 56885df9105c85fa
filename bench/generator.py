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

A configuration with a ``backbone`` object gets token sequences in place
of features (``features`` is ``None``):

* Each sample's length is fixed by ``assumed.plan_seed``, as client sizes
  are: lognormal about ``inputs.seq_len.median`` with ``sigma``, clipped
  to ``[1, max]``.
* The seed draws the tokens, ``(n, max)`` int32 with zeros past each
  length: a Zipf-ranked draw over ids ``1 .. vocab-1`` (exponent
  ``ZIPF``) that each class shifts by its own seeded offset, so that the
  classes differ in their frequent tokens.
* The seed also draws the weights, through the backbone module's
  ``init(model, seed)`` (``bench/backbones/<reference>.py``, which the
  harness loads once and hands in as ``module``).  They are kept on the
  host, as numpy, so that the window's device memory holds the program's
  copy alone.

Id 0 is never a real token, so a sample's length can be read back from
its row.  Labels, round order and client sizes are drawn as for features.

Arithmetic copied from the program's generators so that a later change to
them cannot move the yardstick: lognormal client sizes from
``repro.data.partition.quantity_skew_sizes``; Gaussian class-conditional
features from ``repro.data.synthetic.make_feature_dataset``; Dirichlet
label skew in the per-client form of Hsu et al. (2019), which
``repro.data.partition.dirichlet_partition`` applies per class.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

FEATURE_CHUNKS = 8  # the features are drawn in this many fixed row blocks
ZIPF = 1.1  # exponent of the token ranks' Zipf law


class Federation(NamedTuple):
    features: Optional[np.ndarray]  # (n, d) float32, client k's rows at offsets[k]:offsets[k+1]
    labels: np.ndarray  # (n,) int32
    offsets: np.ndarray  # (n_clients + 1,) int64
    rounds: List[np.ndarray]  # client ids of each round, in arrival order
    n_classes: int
    feature_dim: int  # d: the features' width, or the backbone's pooled width
    # a backbone configuration's inputs, in place of ``features``
    tokens: Optional[np.ndarray] = None  # (n, max) int32, 0 past each length
    lengths: Optional[np.ndarray] = None  # (n,) int32, each in [1, max]
    weights: Optional[Dict[str, np.ndarray]] = None  # the backbone's float32 weights
    backbone: Optional[dict] = None  # the configuration's ``backbone`` object
    backbone_module: Any = None  # its plain reference, bench/backbones/<reference>.py

    @property
    def n_samples(self) -> int:
        return int(self.labels.shape[0])

    @property
    def inputs(self) -> np.ndarray:
        """What the program is handed for each sample: features or tokens."""
        return self.features if self.features is not None else self.tokens

    def client(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        lo, hi = self.offsets[k], self.offsets[k + 1]
        return self.inputs[lo:hi], self.labels[lo:hi]

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


def sequence_lengths(n: int, seq_len: dict, plan_seed: int) -> np.ndarray:
    """Each sample's token count: lognormal about ``seq_len["median"]`` with
    ``seq_len["sigma"]``, clipped to ``[1, seq_len["max"]]``, drawn from the
    deployment's constant seed."""
    raw = np.random.default_rng([plan_seed, 2]).lognormal(
        mean=np.log(seq_len["median"]), sigma=seq_len["sigma"], size=n)
    return np.clip(np.rint(raw), 1, seq_len["max"]).astype(np.int32)


def _tokens(shift: np.ndarray, rng: np.random.Generator, labels: np.ndarray,
            lengths: np.ndarray, inputs: dict) -> np.ndarray:
    """Zipf-ranked ids over ``1 .. vocab-1``, each class's ranks shifted by its
    own ``shift``; ``(n, max)`` int32 with zeros past each length."""
    span = inputs["vocab"] - 1
    row = np.repeat(np.arange(len(lengths)), lengths)
    ranks = rng.zipf(ZIPF, size=len(row))
    out = np.zeros((len(lengths), inputs["seq_len"]["max"]), np.int32)
    real = np.arange(out.shape[1])[None, :] < lengths[:, None]
    out[real] = 1 + (ranks - 1 + shift[labels[row]]) % span
    return out


def _backbone_inputs(backbone: dict, module, labels: np.ndarray, n_classes: int,
                     plan_seed: int, s_shift, s_tokens, s_weights) -> dict:
    """A backbone configuration's fields of :class:`Federation`: lengths from
    the plan seed; class shifts, tokens and weights from the run seed."""
    inputs = backbone["inputs"]
    lengths = sequence_lengths(len(labels), inputs["seq_len"], plan_seed)
    shift = np.random.default_rng(s_shift).integers(0, inputs["vocab"] - 1, n_classes)
    weights = module.init(backbone["model"], int(s_weights.generate_state(1)[0]))
    return dict(
        features=None, lengths=lengths, backbone=backbone, backbone_module=module,
        tokens=_tokens(shift, np.random.default_rng(s_tokens), labels, lengths, inputs),
        weights={k: np.asarray(v) for k, v in weights.items()},
    )


def make_federation(config: dict, seed: int, warm_rounds: int = 0,
                    module=None) -> Federation:
    """The deployment ``config`` draws from ``seed``; ``module`` is the plain
    reference that ``config["backbone"]`` names, where it names one."""
    a = config["assumed"]
    n, d, C, K = (config[k] for k in ("n_samples", "feature_dim", "n_classes", "n_clients"))
    sizes = client_sizes(n, K, a["client_size_sigma"], a["plan_seed"])
    plan = round_plan(K, a["clients_per_round"], a["plan_seed"])
    seq = np.random.SeedSequence(seed)
    s_mean, s_lab, s_noise, s_order = seq.spawn(4)
    labels = _dirichlet_labels(np.random.default_rng(s_lab), sizes, C,
                               a["label_dirichlet_alpha"])
    backbone = config.get("backbone")
    if backbone is None:
        means = np.float32(a["class_scale"]) * np.random.default_rng(
            s_mean).standard_normal((C, d), dtype=np.float32)
        inputs = dict(features=_features(s_noise, means, labels, a["feature_noise"]))
    else:
        inputs = _backbone_inputs(backbone, module, labels, C, a["plan_seed"], s_mean,
                                  s_noise, *seq.spawn(1))
    r_order = np.random.default_rng(s_order)
    order = np.concatenate([r_order.permutation(warm_rounds),
                            warm_rounds + r_order.permutation(len(plan) - warm_rounds)])
    return Federation(
        labels=labels,
        offsets=np.concatenate([[0], np.cumsum(sizes)]),
        rounds=[plan[i] for i in order],
        n_classes=C,
        feature_dim=d,
        **inputs,
    )

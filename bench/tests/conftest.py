import sys

import pytest

import bench.drivers

# A deployment small enough for the CPU: the shapes of the real ones
# (C > d, lognormal sizes, rounds of 10, more rounds than a warm start
# holds) at a fraction of their scale.
TINY = {
    "name": "tiny",
    "feature_dim": 128,
    "n_classes": 160,
    "n_clients": 137,
    "n_samples": 4000,
    "assumed": {
        "client_size_sigma": 1.0,
        "plan_seed": 3,
        "label_dirichlet_alpha": 0.1,
        "clients_per_round": 10,
        "ridge_lambda": 0.01,
        "class_scale": 3.0,
        "feature_noise": 1.0,
    },
}


@pytest.fixture
def tiny():
    return {**TINY, "assumed": dict(TINY["assumed"])}


@pytest.fixture
def drivers_from(monkeypatch):
    """Let ``bench.drivers.<name>`` find drivers in a test's own directory
    first, as it finds a new file in ``bench/drivers``."""
    added = []

    def add(directory, *names):
        monkeypatch.setattr(bench.drivers, "__path__",
                            [str(directory), *bench.drivers.__path__])
        added.extend(names)

    yield add
    for name in added:
        sys.modules.pop(f"bench.drivers.{name}", None)

"""FED3R core — the paper's contribution as composable JAX modules."""
from repro.core import calibration, fed3r, ncm, probe, random_features  # noqa: F401
from repro.core.fed3r import (  # noqa: F401
    Fed3RFactored,
    Fed3RStats,
    aggregate_mesh,
    client_stats,
    init_stats,
    merge,
    solve,
)
from repro.core.random_features import RFFParams, rff_init, rff_map  # noqa: F401

"""Streaming FED3R — the paper's stated future work (§6), on the engine.

Clients arrive over time with NEW data (not a fixed federation snapshot).
Because the statistics are an exact running sum, the server can refresh the
closed-form classifier as arrivals land with zero re-training — the
recursive-least-squares formulation of §4.1.  This example runs the
arrival timeline through the STREAMING ENGINE
(repro.federated.streaming_engine): all T waves fold through one jitted
scan (1 dispatch instead of T), carrying the Cholesky factor of A + λI and
refreshing the served W by two triangular solves.  At small λ the
factored state tracks the batch re-solve to fp32 round-off: every update
adds to the factor, none subtracts from a carried inverse.

    PYTHONPATH=src python examples/streaming_fed3r.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fed3r
from repro.data.pipeline import pack_arrival_waves
from repro.data.synthetic import make_feature_dataset
from repro.federated.streaming_engine import (
    StreamConfig,
    StreamingEngine,
    batch_equivalent,
)

D, C, LAM, T = 32, 10, 1e-2, 10

# one underlying distribution; the first 2000 samples are held out, the rest
# arrive over time in waves (streaming clients with consistent classes)
pool = make_feature_dataset(jax.random.PRNGKey(99), 6000, D, C, noise=2.0)
test_x, test_y = pool.features[:2000], pool.labels[:2000]
stream_x, stream_y = np.asarray(pool.features[2000:]), np.asarray(pool.labels[2000:])

# each wave: two clients with 200 fresh samples apiece
waves = []
for t in range(T):
    lo = t * 400
    waves.append([
        (stream_x[lo : lo + 200], stream_y[lo : lo + 200]),
        (stream_x[lo + 200 : lo + 400], stream_y[lo + 200 : lo + 400]),
    ])
packed = pack_arrival_waves(waves)

cfg = StreamConfig(n_classes=C, ridge_lambda=LAM, refresh_every=1)
engine = StreamingEngine(cfg)
state, trace = engine.absorb(engine.init(D), packed)  # T waves, ONE dispatch

print(f"{packed.n_waves} waves, {packed.n_samples} samples: "
      f"engine={engine.dispatches} dispatch")
print(f"served accuracy: {float(fed3r.accuracy(state.W, test_x, test_y)):.4f} "
      f"(refresh-on-arrival; staleness always 0)")

W_batch, _ = batch_equivalent(packed, cfg)
err_fac = float(jnp.max(jnp.abs(state.W - W_batch)))
print(f"\nmax |W − W_batch| at λ={LAM}: {err_fac:.2e} "
      "(the factored form is exact to fp32 round-off)")

"""A whole run with the timed path broken underneath must read not correct.

Each test skips only the harness's look for a chip (``on_chip=False``) and
drives the rest of a run at a tiny size on the CPU, with the cell's own
limits, once sound and once for each fault the cell can have: a step that
returns its state unchanged; half of the batch left out and the rest
weighted up to stand for it; an answer altered where it is produced; one
class's sums scaled, which W's normalized columns cannot show.
"""
import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import fed3r
from repro.federated.engine import AccumulationEngine
from repro.federated.streaming_engine import StreamingEngine

QUIET = dict(on_chip=False, log=lambda *a, **k: None)


def _run(workload, config):
    return harness.run_cell(workload, 2**31 + 9, 0.2, False, config=config,
                            limits=harness.load_limits(workload), **QUIET)


def _half(packed):
    """Leave out the second half of the clients of each shard or wave; weight
    the first half by √2 so each statistic counts it twice."""
    P = packed.mask.shape[1]
    keep = (jnp.arange(P) < (P + 1) // 2).astype(jnp.float32) * jnp.sqrt(2.0)
    return packed._replace(mask=packed.mask * keep[None, :, None])


def _unchanged_acc(self, acc, packed, params=None):
    return acc


def _unchanged_state(self, state, packed, params=None):
    return state, None


def _scale_class(b):
    return b.at[:, 5].multiply(1.01)


def _fault_batch(monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(AccumulationEngine, "accumulate", _unchanged_acc)
    elif fault == "half_batch":
        real = AccumulationEngine.accumulate
        monkeypatch.setattr(AccumulationEngine, "accumulate",
                            lambda self, acc, packed, params=None:
                            real(self, acc, _half(packed), params))
    elif fault == "answer_altered":
        real = fed3r.solve
        monkeypatch.setattr(fed3r, "solve",
                            lambda *a, **k: real(*a, **k).at[3, 5].add(1e-2))
    elif fault == "class_scaled":
        real = AccumulationEngine.accumulate

        def scaled(self, acc, packed, params=None):
            acc = real(self, acc, packed, params)
            return acc._replace(stats=acc.stats._replace(b=_scale_class(acc.stats.b)))
        monkeypatch.setattr(AccumulationEngine, "accumulate", scaled)


def _fault_stream(monkeypatch, fault):
    real = StreamingEngine.absorb
    if fault == "state_unchanged":
        monkeypatch.setattr(StreamingEngine, "absorb", _unchanged_state)
    elif fault == "half_batch":
        monkeypatch.setattr(StreamingEngine, "absorb",
                            lambda self, state, packed, params=None:
                            real(self, state, _half(packed), params))
    elif fault == "answer_altered":
        def altered(self, state, packed, params=None):
            state, trace = real(self, state, packed, params)
            return state._replace(W=state.W.at[3, 5].add(1e-2)), trace
        monkeypatch.setattr(StreamingEngine, "absorb", altered)
    elif fault == "class_scaled":
        def scaled(self, state, packed, params=None):
            state, trace = real(self, state, packed, params)
            return state._replace(b=_scale_class(state.b)), trace
        monkeypatch.setattr(StreamingEngine, "absorb", scaled)


FAULTS = [None, "state_unchanged", "half_batch", "answer_altered", "class_scaled"]


@pytest.mark.parametrize("fault", FAULTS)
def test_batch_run_reads_fault(tiny, monkeypatch, fault):
    _fault_batch(monkeypatch, fault)
    r = _run("landmarks-batch", tiny)
    assert r["correct"] is (fault is None), r["compared"]
    assert (r["failed"] == 0) is (fault is None)


@pytest.mark.parametrize("fault", FAULTS)
def test_stream_run_reads_fault(tiny, monkeypatch, fault):
    _fault_stream(monkeypatch, fault)
    r = _run("landmarks-stream-warm", tiny)
    assert r["correct"] is (fault is None), r["compared"]

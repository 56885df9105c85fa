"""CPU rehearsal of ``chip_smoke.py``: each phase at a tiny size.

The phases are the same functions the chip run calls at full width; here
the kernels run in interpret mode and no kernel is required to compile.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import pytest

from repro.launch.mesh import make_host_mesh

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_device_that_is_not_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chips"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_trainer_rehearsal(smoke):
    # train.run sets the host mesh as the ambient mesh; keep it scoped
    with jax.set_mesh(make_host_mesh()):
        out = smoke.phase_trainer(
            "fed3r-mnv2-proxy-smoke", n_clients=6, clients_per_round=3,
            n_samples=192, seq_len=8, local_batch_size=16,
        )
    assert out["fed3r_acc"] > 1 / 16


def test_phase_closed_form_rehearsal(smoke, capsys):
    smoke.phase_closed_form(
        d=16, n_classes=5, n_samples=320, n_clients=8, clients_per_shard=2,
        n_waves=2, n_slots=6, n_tenants=3, queries_per_tenant=2,
    )
    out = capsys.readouterr().out
    assert out.count(" ok") == 6 and "FAIL" not in out
    assert "bitwise equal under re-sharding" in out


def test_phase_mesh_rehearsal(smoke, capsys):
    smoke.phase_mesh(d=16, n_classes=5, n_clients=8, client_n=12,
                     clients_per_wave=4)
    out = capsys.readouterr().out
    assert out.count(" ok") == 4 and "FAIL" not in out
    assert "claim: A bitwise equal on grid features: True" in out

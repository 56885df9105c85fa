"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode runs every other kernel test on the CPU and never sees the
chip's tiling rules.  Here each kernel is compiled by the TPU compiler for
a described (not attached) v5e at the paper's Landmarks shape, d=1280 and
C=2028, and the compiled program must hold the kernel as a
``tpu_custom_call``.  The topology is described inside a fixture, so only
the test process that runs this file loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chol_update import (
    batched_chol_gram_pallas,
    chol_gram_pallas,
    live_row_blocks,
)
from repro.kernels.fed3r_stats import fed3r_stats_pallas
from repro.kernels.quant import TILE, dequant_acc_pallas, quantize_tiles_pallas

D, C = 1280, 2028  # Landmarks: MobileNetV2 features, 2028 classes
N = 1024  # samples per statistics block
K = 8  # heads per batched Gram update


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip cannot be read back from
        # the persistent cache without that chip: keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_fed3r_stats_compiles(one_chip):
    compiled = fed3r_stats_pallas.lower(
        _spec((N, D), jnp.float32, one_chip),
        _spec((N, C), jnp.float32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("capacity", [64, 1472])  # the deployments' round shapes
def test_fed3r_stats_live_extent_compiles(one_chip, capacity):
    """The engine's call: a client's padded block and its live row extent."""
    compiled = fed3r_stats_pallas.lower(
        _spec((capacity, D), jnp.float32, one_chip),
        _spec((capacity, C), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_chol_gram_compiles(one_chip):
    """The streaming engine's call: a wave of 10 slots × 640 rows, its row
    blocks read live from the mask."""
    n = 10 * 640

    def wave_gram(L, z, yh, mask):
        return chol_gram_pallas(L, z, yh, *live_row_blocks(mask), interpret=False)

    compiled = jax.jit(wave_gram).lower(
        _spec((D, D), jnp.float32, one_chip),
        _spec((n, D), jnp.float32, one_chip),
        _spec((n, C), jnp.float32, one_chip),
        _spec((n,), jnp.float32, one_chip),
    ).compile()
    _assert_kernel(compiled)


def test_chol_gram_compiles_wide_features(one_chip):
    """Random-feature widths: G's full-width row tiles outgrow the default
    VMEM limit, and the kernel asks for what its tiles need."""
    d, n = 8192, 1024
    compiled = chol_gram_pallas.lower(
        _spec((d, d), jnp.float32, one_chip),
        _spec((n, d), jnp.float32, one_chip),
        _spec((n, C), jnp.float32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_batched_chol_gram_compiles(one_chip):
    compiled = batched_chol_gram_pallas.lower(
        _spec((D, D), jnp.float32, one_chip),
        _spec((K, N, D), jnp.float32, one_chip),
        _spec((K, N, C), jnp.float32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("shape", [(D, D), (D, C)], ids=["A", "b"])
def test_quantize_tiles_compiles(one_chip, shape):
    compiled = quantize_tiles_pallas.lower(
        _spec(shape, jnp.float32, one_chip), interpret=False
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("shape", [(D, D), (D, C)], ids=["A", "b"])
def test_dequant_accumulate_compiles(one_chip, shape):
    grid = (-(-shape[0] // TILE), -(-shape[1] // TILE))
    compiled = dequant_acc_pallas.lower(
        _spec(shape, jnp.float32, one_chip),
        _spec(shape, jnp.int8, one_chip),
        _spec(grid, jnp.float32, one_chip),
        interpret=False,
    ).compile()
    _assert_kernel(compiled)

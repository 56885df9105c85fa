"""Sharding-rule unit tests (no devices needed — abstract trees only).

Verifies, for EVERY assigned architecture, that param/batch/cache specs:
  * always produce evenly-divisible shardings (the jit input contract);
  * shard the big tables (embeddings, experts, FFN) rather than replicate;
  * follow the documented fallback chains for indivisible head counts.
"""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.shapes import abstract_params, input_specs, variant_for
from repro.configs.base import INPUT_SHAPES
from repro.sharding.specs import batch_specs, cache_specs, param_specs

AX = {"model": 16, "data": 16, "pod": 2}


def _axis_size(entry):
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        s = 1
        for a in entry:
            s *= AX[a]
        return s
    return AX[entry]


def _check_divisible(tree, specs):
    leaves = jax.tree.leaves(tree)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            assert dim % _axis_size(entry) == 0, (leaf.shape, spec)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("fsdp", [False, True])
def test_param_specs_divisible(arch, fsdp):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, AX, fsdp=fsdp)
    _check_divisible(params, specs)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_embedding_is_sharded_not_replicated(arch):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, AX)
    emb_spec = specs["embed"]["embedding"]
    assert tuple(emb_spec) != (), f"{arch}: embedding replicated"


@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "llama4-scout-17b-a16e"])
def test_moe_experts_expert_parallel(arch):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, AX)
    wg = specs["layers"]["moe"]["w_gate"]
    assert tuple(wg)[1] == "model", "experts must shard on the E axis"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_batch_and_cache_specs_divisible(arch, shape_name):
    cfg = variant_for(get_config(arch), INPUT_SHAPES[shape_name])
    if cfg is None:
        pytest.skip("documented long_500k skip")
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    da = ("data",)
    if "batch" in specs:
        _check_divisible(specs["batch"], batch_specs(cfg, specs["batch"], da, AX))
    if "cache" in specs:
        _check_divisible(specs["cache"], cache_specs(cfg, specs["cache"], da, AX))


def test_qwen2_head_fallback_row_parallel():
    """28 heads don't divide 16 → wq falls back to sharding d_model."""
    cfg = get_config("qwen2-7b")
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, AX)
    wq = tuple(specs["layers"]["attn"]["wq"])  # (L, d, H, hd)
    assert wq[2] != "model" and wq[1] == "model"


def test_command_r_heads_shard_on_model():
    """96 q-heads divide 16 → primary head sharding is used."""
    cfg = get_config("command-r-plus-104b")
    params = abstract_params(cfg)
    specs = param_specs(cfg, params, AX)
    wq = tuple(specs["layers"]["attn"]["wq"])
    assert wq[2] == "model"


def test_hints_leave_shard_map_axes_alone():
    """Inside shard_map the mapped axes are Manual: the body already sees
    its local shard, so a hint must not constrain over them (the engines'
    dist-owned mesh mode runs ``hint`` inside shard_map)."""
    import jax.numpy as jnp

    from repro.sharding import hints

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    seen = []

    def body(x):
        seen.append(hints._partitioned_axes())
        return hints.hint(x, "batch")

    with jax.set_mesh(mesh):
        assert hints._partitioned_axes() == {"data": 1, "model": 1}
        jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(
            jnp.ones((4, 2))
        )
    assert seen == [{}]

"""Architecture configs assigned to this paper (public-literature pool).

Each module defines ``CONFIG`` (the exact assigned configuration, with source
citation) and ``REDUCED`` (a smoke-test variant of the same family: ≤2-3
layers, d_model ≤ 512, ≤4 experts) registered as ``<name>-smoke``.
"""
from repro.configs.base import (  # noqa: F401
    Fed3RConfig,
    FederatedConfig,
    INPUT_SHAPES,
    ModelConfig,
    ShapeConfig,
    get_config,
    list_configs,
    register,
)

ARCH_MODULES = [
    "command_r_plus_104b",
    "minitron_8b",
    "deepseek_moe_16b",
    "qwen2_vl_2b",
    "mamba2_1_3b",
    "recurrentgemma_9b",
    "qwen2_7b",
    "deepseek_coder_33b",
    "llama4_scout_17b_a16e",
    "whisper_large_v3",
    "fed3r_mnv2_proxy",
    "nemotron3_nano_30b_a3b",
]

ASSIGNED_ARCHS = [
    "command-r-plus-104b",
    "minitron-8b",
    "deepseek-moe-16b",
    "qwen2-vl-2b",
    "mamba2-1.3b",
    "recurrentgemma-9b",
    "qwen2-7b",
    "deepseek-coder-33b",
    "llama4-scout-17b-a16e",
    "whisper-large-v3",
]

_loaded = False


def load_all() -> None:
    global _loaded
    if _loaded:
        return
    import importlib

    for m in ARCH_MODULES:
        importlib.import_module(f"repro.configs.{m}")
    _loaded = True

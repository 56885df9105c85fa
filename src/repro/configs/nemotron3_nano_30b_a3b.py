"""NVIDIA Nemotron-3-Nano-30B-A3B — hybrid Mamba-2 / MoE / GQA, single-mixer blocks.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(config.json, ``model_type`` nemotron_h): 52 blocks in the pattern
``hybrid_override_pattern`` (M Mamba-2, E MoE, * attention; 23:23:6),
each ``x + mixer(RMSNorm(x))`` with eps 1e-5, then ``norm_f``; d=2688,
vocab 131,072, untied LM head.

* Mamba-2: 64 heads of 64 (d_inner 4096; the config's ``expand`` is not
  used), 8 groups, state 128, chunk 128, causal conv of width 4 with bias
  over x‖B‖C, gated RMSNorm per group of 512 channels.
* MoE: 128 routed experts, top-6 on sigmoid score + correction bias, the
  chosen scores normalized and scaled by 2.5; experts are non-gated relu²
  MLPs of width 1856; one shared expert of width 3712.
* Attention: 32 Q / 2 KV heads of 128, causal, no bias.  No rotary
  embedding: ``modeling_nemotron_h.py`` applies none (the config's
  ``rope_theta`` is not read there), and neither does this model.

``experts_held`` is this chip's share of each MoE layer's experts under
expert parallelism; the published deployment holds them all.
"""
from repro.configs.base import ModelConfig, register

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = register(
    ModelConfig(
        name="nemotron3-nano-30b-a3b",
        arch_type="nemotron_h",
        n_layers=52,
        d_model=2688,
        n_heads=32,
        n_kv_heads=2,
        head_dim=128,
        d_ff=3712,  # the shared expert's width
        vocab_size=131_072,
        mixer_pattern=PATTERN,
        use_rope=False,
        norm_eps=1e-5,
        mlp_type="relu2",
        tie_embeddings=False,
        n_experts=128,
        n_shared_experts=1,
        top_k=6,
        d_expert=1856,
        routed_scaling=2.5,
        experts_held=(0, 128),
        ssm_state=128,
        ssm_headdim=64,
        ssm_d_inner=4096,
        ssm_chunk=128,
        ssm_conv=4,
        ssm_ngroups=8,
        ssm_group_norm=True,
        source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
    )
)

REDUCED = register(
    CONFIG.replace(
        name="nemotron3-nano-30b-a3b-smoke",
        n_layers=7,  # MEMEM*E: every mixer kind, one period
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=96,
        d_expert=48,
        vocab_size=512,
        n_experts=8,
        top_k=2,
        experts_held=(0, 8),
        ssm_state=16,
        ssm_headdim=16,
        ssm_d_inner=64,
        ssm_chunk=16,
        ssm_ngroups=2,
    )
)

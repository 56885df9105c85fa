"""Batch statistics over a Nemotron-H backbone: the program's own forward
(``repro.models.model.token_feature_fn``) runs inside
``AccumulationEngine``'s scan over each shard's packed tokens, its pooled
features go into the statistics, and the pass ends in ``fed3r.solve``.

The program's configuration is built from the cell's ``backbone.model``
(published names: ``hidden_size``, ``hybrid_override_pattern``, ...), with
``n_routed_experts`` of the router's ``router_outputs`` held from
``held_expert_offset``.  Its weights are the generator's, laid out as the
program's parameters: the matrices in the traffic's ``dtype`` (the
configuration's bf16), the vectors (norm scales, biases, A_log, D, dt_bias)
in float32.  The units' work adds the reference module's ``flops``."""
from __future__ import annotations

import jax.numpy as jnp

from bench.drivers.batch import Driver as Batch
from repro.configs.base import ModelConfig
from repro.models.model import token_feature_fn
from repro.models.transformer import MIXER_SCOPE


def program_config(model: dict, dtype: str) -> ModelConfig:
    """The program's ``ModelConfig`` for ``backbone.model``."""
    if not model["norm_topk_prob"] or model["n_shared_experts"] != 1:
        raise ValueError("the program's MoE normalizes the top-k and holds one shared expert")
    if model["layer_norm_epsilon"] != model["norm_eps"]:
        raise ValueError("the program's norms share one eps")
    cfg = ModelConfig(
        name="nemotron_h-bench",
        arch_type="nemotron_h",
        n_layers=model["num_hidden_layers"],
        d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        d_ff=model["moe_shared_expert_intermediate_size"],
        vocab_size=model["vocab_size"],
        mixer_pattern=model["hybrid_override_pattern"],
        use_rope=False,
        norm_eps=model["norm_eps"],
        mlp_type="relu2",
        tie_embeddings=model["tie_word_embeddings"],
        n_experts=model["router_outputs"],
        n_shared_experts=model["n_shared_experts"],
        top_k=model["num_experts_per_tok"],
        d_expert=model["moe_intermediate_size"],
        routed_scaling=model["routed_scaling_factor"],
        experts_held=(model["held_expert_offset"], model["n_routed_experts"]),
        ssm_state=model["ssm_state_size"],
        ssm_headdim=model["mamba_head_dim"],
        ssm_d_inner=model["mamba_num_heads"] * model["mamba_head_dim"],
        ssm_chunk=model["chunk_size"],
        ssm_conv=model["conv_kernel"],
        ssm_ngroups=model["n_groups"],
        ssm_group_norm=True,
        dtype=dtype,
    )
    cfg.validate()
    return cfg


def program_params(weights: dict, cfg: ModelConfig, dtype) -> dict:
    """The reference module's flat weights as the program's parameter tree."""
    def w(name):
        a = jnp.asarray(weights[name])
        return a.astype(dtype) if a.ndim >= 2 else a

    d, hd = cfg.d_model, cfg.hd
    layers = []
    for i, kind in enumerate(cfg.mixers):
        p = f"{i}."
        if kind == "M":
            mixer = {"in_proj": w(p + "in_proj"),
                     "conv": {"kernel": w(p + "conv_w"), "bias": w(p + "conv_b")},
                     "A_log": w(p + "A_log"), "dt_bias": w(p + "dt_bias"), "D": w(p + "D"),
                     "norm_scale": w(p + "gate_norm"), "out_proj": w(p + "out_proj")}
        elif kind == "E":
            mixer = {"router": w(p + "router"), "router_bias": w(p + "router_bias"),
                     "w_up": w(p + "up"), "w_down": w(p + "down"),
                     "shared": {"w_up": w(p + "shared_up"), "w_down": w(p + "shared_down")}}
        else:
            mixer = {"wq": w(p + "wq").reshape(d, cfg.n_heads, hd),
                     "wk": w(p + "wk").reshape(d, cfg.n_kv_heads, hd),
                     "wv": w(p + "wv").reshape(d, cfg.n_kv_heads, hd),
                     "wo": w(p + "wo").reshape(cfg.n_heads, hd, d)}
        layers.append({"norm": {"scale": w(p + "norm")}, MIXER_SCOPE[kind]: mixer})
    return {"embed": {"embedding": w("embed")}, "final_norm": {"scale": w("norm_f")},
            "layers": layers}


class Driver(Batch):
    def __init__(self, config, traffic, fed, mesh=None, seed=0):
        model = config["backbone"]["model"]
        dtype = traffic.get("dtype", "bfloat16")
        self.model_config = program_config(model, dtype)
        self.feature_fn = token_feature_fn(self.model_config)
        super().__init__(config, traffic, fed, mesh=mesh, seed=seed)
        self.params = program_params(fed.weights, self.model_config, jnp.dtype(dtype))
        self.flops += fed.backbone_module.flops(model, fed.lengths)

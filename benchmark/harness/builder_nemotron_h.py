"""Builds the system under test for a Nemotron-H configuration: the
program's own ``NemotronHForCausalLM`` through its normal constructor, in
the configuration's dtype from the start, every parameter then set from the
seed by ``weights_nemotron_h`` (one layer's leaves at a time: two copies of
the model do not fit the chip)."""
from __future__ import annotations

from . import weights_nemotron_h as W


def nemotron_h_causal_lm(sizes, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import NemotronHConfig, NemotronHForCausalLM

    if len(sizes["hybrid_override_pattern"]) != sizes["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    cfg = NemotronHConfig(
        vocab_size=sizes["vocab_padded"], hidden_size=sizes["hidden_size"],
        hybrid_override_pattern=sizes["hybrid_override_pattern"],
        mamba_num_heads=sizes["mamba_num_heads"],
        mamba_head_dim=sizes["mamba_head_dim"], n_groups=sizes["n_groups"],
        ssm_state_size=sizes["ssm_state_size"],
        conv_kernel=sizes["conv_kernel"], chunk_size=sizes["chunk_size"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        n_routed_experts=sizes["router_outputs"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=sizes[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        held_experts=tuple(range(sizes["n_routed_experts"])),
        layer_norm_epsilon=sizes["layer_norm_epsilon"],
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=sizes["dtype"])
    paddle.seed(0)
    model = NemotronHForCausalLM(cfg)
    params = dict(model.named_parameters())
    for name, v in W.make(seed, sizes, sizes["dtype"]):
        p = params.pop(name)
        if v.shape != tuple(p._value.shape) or v.dtype != p._value.dtype:
            raise ValueError(f"{name}: seeded {v.shape} {v.dtype}, the model "
                             f"has {p._value.shape} {p._value.dtype}")
        p._value = v
    if params:
        raise ValueError(f"parameters not set: {sorted(params)}")
    return model

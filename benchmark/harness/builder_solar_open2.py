"""Builds the system under test for a Solar Open 2 configuration: the
program's own ``SolarOpen2ForCausalLM`` through its normal constructor, in
the configuration's dtype from the start, every parameter then set from the
seed by ``weights_solar_open2`` (one layer's leaves at a time: two copies of
the model do not fit the chip)."""
from __future__ import annotations

from . import weights_solar_open2 as W


def solar_open2_causal_lm(sizes, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import SolarOpen2Config, SolarOpen2ForCausalLM

    kda = sizes["linear_attn_config"]
    cfg = SolarOpen2Config(
        vocab_size=sizes["vocab_padded"], hidden_size=sizes["hidden_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        gqa_layers=tuple(sizes["gqa_layers"]),
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], use_gqa_gate=sizes["use_gqa_gate"],
        kda_num_heads=kda["num_heads"], kda_head_dim=kda["head_dim"],
        kda_conv_kernel=kda["short_conv_kernel_size"],
        kda_gate_rank=sizes["kda_gate_rank"],
        kda_allow_neg_eigval=sizes["kda_allow_neg_eigval"],
        n_routed_experts=sizes["router_outputs"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        n_shared_experts=sizes["n_shared_experts"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        held_experts=tuple(range(sizes["n_routed_experts"])),
        rms_norm_eps=sizes["rms_norm_eps"],
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=sizes["dtype"])
    paddle.seed(0)
    model = SolarOpen2ForCausalLM(cfg)
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

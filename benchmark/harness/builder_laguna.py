"""Builds the system under test for a Laguna configuration: the program's
own ``LagunaForCausalLM`` through its normal constructor, in the
configuration's dtype from the start, every parameter then set from the seed
by ``weights_laguna`` (one layer's leaves at a time)."""
from __future__ import annotations

from . import weights_laguna as W


def laguna_causal_lm(sizes, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import LagunaConfig, LagunaForCausalLM

    n = sizes["num_hidden_layers"]
    cfg = LagunaConfig(
        vocab_size=sizes["vocab_padded"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"], num_hidden_layers=n,
        # the published lists whole: the cut is their first n entries
        layer_types=tuple(sizes["layer_types"][:n]),
        mlp_layer_types=tuple(sizes["mlp_layer_types"][:n]),
        num_attention_heads_per_layer=tuple(
            sizes["num_attention_heads_per_layer"][:n]),
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], sliding_window=sizes["sliding_window"],
        rope_parameters={k: v for k, v in sizes["rope_parameters"].items()
                         if isinstance(v, dict)},
        gating=sizes["gating"], num_experts=sizes["router_outputs"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        shared_expert_intermediate_size=sizes[
            "shared_expert_intermediate_size"],
        moe_routed_scaling_factor=sizes["moe_routed_scaling_factor"],
        held_experts=tuple(range(sizes["num_experts"])),
        rms_norm_eps=sizes["rms_norm_eps"],
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=sizes["dtype"])
    paddle.seed(0)
    model = LagunaForCausalLM(cfg)
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

"""Builds the system under test from a configuration's file: the program's
own model class through its normal constructor (copied from
``chip_smoke.py``), its parameters then set from the seed by ``weights``.
A configuration names its builder as ``<module>.<function>`` under
``harness/``: a new model class brings a module of its own."""
from __future__ import annotations

import json
import os

from . import weights as W

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(kind, name):
    with open(os.path.join(ROOT, kind, name + ".json")) as fh:
        return json.load(fh)


def sizes_of(config, rehearse):
    """The sizes as run: the file's, or its tiny ``rehearse`` set on the CPU."""
    sizes = {k: v for k, v in config.items()
             if k not in ("rehearse", "assumed", "builder", "weights",
                          "reference")}
    sizes["vocab_padded"] = config["assumed"]["vocab_padded"]
    sizes["init"] = config["assumed"].get("init")
    if rehearse:
        sizes.update(config["rehearse"])
    return sizes


def gpt_causal_lm(sizes, seed):
    """``paddle_tpu.models.GPTForCausalLM`` at these sizes: ``dtype``
    weights, float32 LayerNorm, every parameter set from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(
        vocab_size=sizes["vocab_padded"], hidden_size=sizes["n_embd"],
        num_layers=sizes["n_layer"], num_heads=sizes["n_head"],
        max_position_embeddings=sizes["n_positions"],
        initializer_range=sizes["initializer_range"],
        layer_norm_eps=sizes["layer_norm_epsilon"],
        hidden_dropout=0.0, attention_dropout=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.to(dtype=sizes["dtype"])
    for _, sub in model.named_sublayers():
        if type(sub).__name__ == "LayerNorm":
            sub.to(dtype="float32")
    leaves = W.split(W.make(seed, sizes, sizes["dtype"]))
    names = set()
    for name, p in model.named_parameters():
        v = leaves[name]
        if v.shape != tuple(p._value.shape) or v.dtype != p._value.dtype:
            raise ValueError(f"{name}: seeded {v.shape} {v.dtype}, the model "
                             f"has {p._value.shape} {p._value.dtype}")
        p._value = v
        names.add(name)
    if names != set(leaves):
        raise ValueError(f"parameters not set: {set(leaves) ^ names}")
    return model

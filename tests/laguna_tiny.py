"""The tiny Laguna the tier-1 tests share: hidden 64, 5 layers ``F S S S F``
(the first dense, the rest sparse), 6 query heads in the full layers and 8
in the window layers over 2 K/V heads of 16, a window of 8, YaRN over half a
head from an original context of 16 (so a prompt of a few dozen positions
reaches its scaled range), 8 gated experts top-2 of which 4 are held; seeded
weights lively enough that greedy decoding does not repeat."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.laguna import (FULL, WINDOW, LagunaConfig,
                                      LagunaForCausalLM)

import reference_laguna as R

VOCAB = 96
WINDOW_ROWS = 8


def tiny_rope():
    return {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
               "original_max_position_embeddings": 16, "beta_slow": 1,
               "beta_fast": 4, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        WINDOW: {"rope_type": "default", "rope_theta": 10000,
                 "partial_rotary_factor": 1},
    }


def tiny_config(**over):
    kw = dict(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        num_hidden_layers=5, layer_types=(FULL, WINDOW, WINDOW, WINDOW, FULL),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_attention_heads_per_layer=(6, 8, 8, 8, 6),
        num_key_value_heads=2, head_dim=16, sliding_window=WINDOW_ROWS,
        rope_parameters=tiny_rope(), num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        held_experts=(0, 1, 2, 3), dtype="float32", initializer_range=0.3,
        max_position_embeddings=4096)
    kw.update(over)
    return LagunaConfig(**kw)


def build(cfg, seed=0):
    """``(model, named)``: the model with seeded parameters (the router's
    correction random too, so a path that drops it shows) and ``{parameter
    name: numpy array}``."""
    paddle.seed(seed)
    model = LagunaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("gate_bias"):
            p._value = jnp.asarray(rng.normal(size=p.shape) * 0.05, p.dtype)
    return model, {n: np.asarray(p._value)
                   for n, p in model.named_parameters()}


def sizes(cfg):
    return dataclasses.asdict(cfg)


_REFERENCE = {}


def reference_logits(named, cfg, seq, pad_to=128):
    """The reference's logits for ``seq`` with the experts the model holds
    (``named`` carries exactly their weights). One compiled call for every
    sequence of a test file: ``seq`` is padded to ``pad_to`` (what follows a
    position cannot reach back into it)."""
    if id(named) not in _REFERENCE:
        held = cfg.held_experts or range(cfg.num_experts)
        _REFERENCE[id(named)] = (
            R.from_named(named, sizes(cfg)),
            jax.jit(functools.partial(R.forward_held, cfg=sizes(cfg),
                                      held=tuple(held))))
    params, fn = _REFERENCE[id(named)]
    ids = np.zeros((max(pad_to, len(seq)),), np.int32)
    ids[:len(seq)] = seq
    return fn(params, ids)[:len(seq)]


def logits(model, ids):
    """The model's logits for ``ids [b, s]`` as one compiled call."""
    with paddle.no_grad():
        return jax.jit(lambda t: model(paddle.Tensor(t))._value)(
            jnp.asarray(ids))

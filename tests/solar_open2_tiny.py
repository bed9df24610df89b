"""The tiny Solar Open 2 the tier-1 tests share: hidden 64, 4 layers ``G K K
K``, 4 query / 2 K/V heads of 16, 4 KDA heads of 16 x 16 in chunks of 8, 8
gated experts top-2 of which 4 are held; seeded weights lively enough that
greedy decoding does not repeat."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                           SolarOpen2ForCausalLM)
from paddle_tpu.nn.functional import kda

import reference_solar_open2 as R

VOCAB = 96


@pytest.fixture(autouse=True)
def chunks_of_8(monkeypatch):
    """The test files that share this model import this: the chunk is a
    module constant (64), and a tiny prompt should still span several."""
    monkeypatch.setattr(kda, "CHUNK", 8)


def tiny_config(**over):
    kw = dict(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4,
        gqa_layers=(0,), num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, kda_num_heads=4, kda_head_dim=16, kda_gate_rank=8,
        n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, held_experts=(0, 1, 2, 3),
        dtype="float32", initializer_range=0.3, max_position_embeddings=4096)
    kw.update(over)
    return SolarOpen2Config(**kw)


def build(cfg, seed=0):
    """``(model, named)``: the model with seeded parameters (the recurrence's
    own and the biases random too, so a path that drops one shows) and
    ``{parameter name: numpy array}``."""
    paddle.seed(seed)
    model = SolarOpen2ForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith(("A_log", "dt_bias", "g_bias", "gate_bias")):
            scale = 0.05 if name.endswith("gate_bias") else 0.5
            p._value = jnp.asarray(rng.normal(size=p.shape) * scale, p.dtype)
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
        held = cfg.held_experts or range(cfg.n_routed_experts)
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

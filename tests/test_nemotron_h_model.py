"""Nemotron-H against its plain reference (``reference_nemotron_h.py``): the
whole forward pass, the expert layer's share of a deployment, a router
forced onto one expert, and the benchmark's copy of the reference."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn.layer.experts import DroplessExperts

import nemotron_h_tiny as tiny
import reference_nemotron_h as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("held", [None, (0, 1, 2, 3)],
                         ids=["uncut", "share"])
def test_forward_matches_reference(held):
    cfg = tiny.tiny_config(held_experts=held)
    model, named = tiny.build(cfg)
    ids = np.random.default_rng(1).integers(0, tiny.VOCAB, 27)
    out = tiny.logits(model, ids[None])[0]
    ref = tiny.reference_logits(named, cfg, ids)
    assert out.shape == ref.shape == (27, tiny.VOCAB)
    np.testing.assert_allclose(out, ref, atol=2e-4)


def _experts(held, source=None):
    layer = DroplessExperts(64, 32, 8, 2, held=held, shared_width=48,
                            scale=2.5, dtype="float32", init_std=0.3)
    if source is not None:  # the same model's weights, this share of them
        sel = np.asarray(layer.held, np.int64)
        for name in ("gate_weight", "gate_bias", "shared_up", "shared_down"):
            getattr(layer, name)._value = getattr(source, name)._value
        layer.up._value = source.up._value[sel]
        layer.down._value = source.down._value[sel]
    return layer


def _run(layer, x, valid=None):
    """``(out, counts, chosen)`` as arrays, one compiled call."""
    with paddle.no_grad():
        return jax.jit(lambda a: tuple(
            t._value for t in layer(a, valid=valid)))(x)


def _reference_experts(layer, x):
    p = {"gate_w": layer.gate_weight._value, "gate_bias": layer.gate_bias._value,
         "experts_up": jnp.swapaxes(layer.up._value, 1, 2),
         "experts_down": layer.down._value,
         "shared_up": layer.shared_up._value,
         "shared_down": layer.shared_down._value}
    cfg = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.5}
    return jax.jit(lambda u, q: R.experts(u, q, cfg, list(layer.held)))(x, p)


@pytest.mark.parametrize("shares", [([0, 1, 2, 3], [4, 5, 6, 7]),
                                    ([1, 6], [0, 2, 7], [3, 4, 5])])
def test_shares_add_up_to_the_uncut_layer(shares):
    # guide section 4: what every share computes for its own experts, with
    # the shared expert counted once, is what the uncut layer gives
    paddle.seed(3)
    whole = _experts(None)
    whole.gate_bias._value = jnp.asarray(
        np.random.default_rng(3).normal(size=8) * 0.05, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, 64)),
                    jnp.float32)
    uncut = _reference_experts(whole, x[0])
    np.testing.assert_allclose(_run(whole, x)[0][0], uncut, atol=1e-4)
    shared = R._relu2_mlp(x[0], whole.shared_up._value,
                          whole.shared_down._value, None)
    total = shared
    pairs = 0
    for held in shares:
        out, counts, _ = _run(_experts(held, whole), x)
        total = total + (out[0] - shared)
        pairs += int(counts[1])
    np.testing.assert_allclose(total, uncut, atol=1e-4)
    assert pairs == 24 * 2  # every choice fell on exactly one share


def test_no_token_dropped_when_the_router_picks_one_expert():
    paddle.seed(5)
    layer = _experts([0, 1, 2, 3])
    bias = np.zeros(8, np.float32)
    bias[2] = 10.0  # every token's first choice, whatever its score
    layer.gate_bias._value = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 37, 64)),
                    jnp.float32)
    out, counts, chosen = _run(layer, x)
    tokens, on_held, busiest, hit = (int(c) for c in counts)
    assert tokens == 74 and busiest == 74  # expert 2 got every token
    assert 1 <= hit <= 4
    assert np.all(np.any(np.asarray(chosen) == 2, axis=-1))
    assert on_held == int(np.sum(np.asarray(chosen) < 4))
    ref = _reference_experts(layer, x.reshape(74, 64))
    np.testing.assert_allclose(out.reshape(74, 64), ref, atol=1e-4)


def test_padding_tokens_are_routed_nowhere():
    paddle.seed(7)
    layer = _experts([0, 1, 2, 3])
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, 16, 64)),
                    jnp.float32)
    valid = jnp.arange(16)[None, :] < 9
    out, counts, _ = _run(layer, x, valid)
    assert int(counts[0]) == 9
    ref = _reference_experts(layer, x[0])
    np.testing.assert_allclose(out[0, :9], ref[:9], atol=1e-4)


def test_held_experts_must_be_distinct_ids():
    with pytest.raises(ValueError, match="held experts"):
        DroplessExperts(8, 8, 4, 2, held=[1, 1])
    with pytest.raises(ValueError, match="held experts"):
        DroplessExperts(8, 8, 4, 2, held=[4])
    with pytest.raises(ValueError, match="held experts"):
        DroplessExperts(8, 8, 4, 2, held=[])


def test_parameters_are_born_in_the_models_dtype():
    cfg = tiny.tiny_config(dtype="bfloat16", hybrid_override_pattern="M*E")
    model, _ = tiny.build(cfg)
    f32 = ("norm.weight", "norm_f.weight", "norm_weight", "dt_bias", "A_log",
           ".D", "gate_weight", "gate_bias")
    for name, p in model.named_parameters():
        want = "float32" if name.endswith(f32) else "bfloat16"
        assert p.dtype.name == want, (name, p.dtype)
    # the float32 gain does not promote a bfloat16 stream
    norm = model.backbone.norm_f
    assert norm(paddle.to_tensor(jnp.ones((2, 64), jnp.bfloat16))
                ).dtype.name == "bfloat16"


def test_benchmark_copy_of_the_reference_is_the_same():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference_nemotron_h as B
    finally:
        sys.path.pop(0)
    cfg = tiny.tiny_config()
    _, named = tiny.build(cfg)
    params = R.from_named(named, cfg.hybrid_override_pattern)
    ids = np.random.default_rng(9).integers(0, tiny.VOCAB, 17)
    held = list(cfg.held_experts)
    sizes = dataclasses.asdict(cfg)
    for lowp in (None, "fp8"):
        a, b = (jax.jit(lambda p, m=m: m.forward_held(p, ids, sizes, held,
                                                      lowp))(params)
                for m in (R, B))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

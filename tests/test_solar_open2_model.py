"""Solar Open 2 against its plain reference (``reference_solar_open2.py``):
the whole forward pass, the expert sublayer's eight shares of a deployment,
the gated attention and the KDA mixer alone, dtypes, and the benchmark's
copy of the reference."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn.layer.experts import DroplessExperts
from paddle_tpu.nn.layer.kda import KimiDeltaAttention

import reference_solar_open2 as R
import solar_open2_tiny as tiny
from solar_open2_tiny import chunks_of_8  # noqa: F401 (autouse)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("over", [
    {"held_experts": None}, {}, {"use_gqa_gate": False},
    {"kda_allow_neg_eigval": False}],
    ids=["uncut", "share", "no_gqa_gate", "beta_under_1"])
def test_forward_matches_reference(over):
    # the two published switches' off-values too: attention without its
    # output gate (no ``gate_proj`` at all), ``beta`` in (0, 1)
    cfg = tiny.tiny_config(**over)
    model, named = tiny.build(cfg)
    ids = np.random.default_rng(1).integers(0, tiny.VOCAB, 27)
    out = tiny.logits(model, ids[None])[0]
    ref = tiny.reference_logits(named, cfg, ids)
    assert out.shape == ref.shape == (27, tiny.VOCAB)
    np.testing.assert_allclose(out, ref, atol=2e-4)


def test_uncut_reference_picks_the_share():
    # ``forward(held=, vocab_rows=)`` over the uncut model is what the
    # program computes when it is built with that share of the weights
    whole, named = tiny.build(tiny.tiny_config(held_experts=None))
    cfg = tiny.tiny_config(held_experts=(1, 4, 6), vocab_size=48)
    share, _ = tiny.build(cfg)
    sel = np.asarray(cfg.held_experts)
    for (name, p), (_, q) in zip(share.named_parameters(),
                                 whole.named_parameters()):
        v = q._value
        if name.endswith(("experts.up", "experts.down")):
            v = v[sel]
        elif name in ("lm_head", "backbone.embeddings"):
            v = v[:48]
        p._value = v
    ids = np.random.default_rng(2).integers(0, 48, 21)
    want = R.forward(R.from_named(named, tiny.sizes(cfg)), ids,
                     tiny.sizes(cfg), held=cfg.held_experts,
                     vocab_rows=np.arange(48))
    np.testing.assert_allclose(tiny.logits(share, ids[None])[0], want,
                               atol=2e-4)


def _experts(held, source=None):
    layer = DroplessExperts(64, 32, 16, 3, held=held, shared_width=32,
                            dtype="float32", init_std=0.3, form="swiglu")
    if source is not None:  # the same model's weights, this share of them
        sel = np.asarray(layer.held, np.int64)
        for name in ("gate_weight", "gate_bias", "shared_up", "shared_down"):
            getattr(layer, name)._value = getattr(source, name)._value
        layer.up._value = source.up._value[sel]
        layer.down._value = source.down._value[sel]
    return layer


def _run(layer, x, valid=None):
    with paddle.no_grad():
        return jax.jit(lambda a: tuple(
            t._value for t in layer(a, valid=valid)))(x)


def _reference_experts(layer, x, shared_only=False):
    p = R.unstack({
        "gate_w": layer.gate_weight._value,
        "gate_bias": layer.gate_bias._value,
        "experts_gate_up": layer.up._value, "experts_down": layer.down._value,
        "shared_gate_up": layer.shared_up._value,
        "shared_down": layer.shared_down._value})
    if shared_only:
        return R.swiglu_mlp(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
    cfg = {"num_experts_per_tok": 3, "routed_scaling_factor": 1.0}
    return jax.jit(lambda u, q: R.experts(u, q, cfg, list(layer.held)))(x, p)


def test_eight_shares_add_up_to_the_uncut_layer():
    # guide section 4: what every share computes for its own experts, with
    # the shared expert counted once, is what the uncut layer gives; eight
    # shares of two experts each, as the deployment's eight chips of 40
    paddle.seed(3)
    whole = _experts(None)
    whole.gate_bias._value = jnp.asarray(
        np.random.default_rng(3).normal(size=16) * 0.05, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, 64)),
                    jnp.float32)
    uncut = _reference_experts(whole, x[0])
    np.testing.assert_allclose(_run(whole, x)[0][0], uncut, atol=1e-4)
    shared = _reference_experts(whole, x[0], shared_only=True)
    total, pairs = shared, 0
    for c in range(8):
        out, counts, _ = _run(_experts([2 * c, 2 * c + 1], whole), x)
        total = total + (out[0] - shared)
        pairs += int(counts[1])
    np.testing.assert_allclose(total, uncut, atol=1e-4)
    assert pairs == 24 * 3  # every choice fell on exactly one share


def test_experts_know_two_forms():
    with pytest.raises(ValueError, match="form"):
        DroplessExperts(8, 8, 4, 2, form="gelu")
    relu2 = DroplessExperts(8, 16, 4, 2, shared_width=16)
    gated = DroplessExperts(8, 16, 4, 2, shared_width=16, form="swiglu")
    assert relu2.up.shape == [4, 16, 8] and gated.up.shape == [4, 32, 8]
    assert relu2.shared_up.shape == [8, 16]
    assert gated.shared_up.shape == [8, 32]
    assert gated.down.shape == relu2.down.shape == [4, 16, 8]


def test_kda_mixer_alone_against_the_reference():
    # two sequences at once, no state handed in: zeros, as the reference
    paddle.seed(5)
    mixer = KimiDeltaAttention(32, 4, 16, gate_rank=8,
                               dtype="float32", init_std=0.4)
    rng = np.random.default_rng(5)
    for name in ("A_log", "dt_bias", "g_bias", "norm_weight"):
        p = getattr(mixer, name)
        p._value = jnp.asarray(rng.normal(size=p.shape) * 0.5
                               + (name == "norm_weight"), jnp.float32)
    names = {"norm_w": "norm_weight", "o_proj": "out_proj"}
    p = {k: getattr(mixer, names.get(k, k))._value for k in (
        "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "a_down",
        "a_up", "dt_bias", "A_log", "b_proj", "g_down", "g_up", "g_bias",
        "norm_w", "o_proj")}
    cfg = {"kda_num_heads": 4, "kda_head_dim": 16, "rms_norm_eps": 1e-5,
           "kda_allow_neg_eigval": True}
    u = jnp.asarray(rng.normal(size=(2, 21, 32)), jnp.float32)
    with paddle.no_grad():
        got = jax.jit(lambda a: mixer(a)._value)(u)
    for i in range(2):
        np.testing.assert_allclose(got[i], R.kda(u[i], p, cfg), atol=2e-5)


def test_parameters_are_born_in_the_models_dtype():
    cfg = tiny.tiny_config(dtype="bfloat16", num_hidden_layers=2)
    model, _ = tiny.build(cfg)
    f32 = ("norm.weight", "norm_f.weight", "norm_weight", "dt_bias", "A_log",
           "gate_weight", "gate_bias")
    for name, p in model.named_parameters():
        want = "float32" if name.endswith(f32) else "bfloat16"
        assert p.dtype.name == want, (name, p.dtype)
    ids = np.random.default_rng(0).integers(0, tiny.VOCAB, (1, 9))
    assert tiny.logits(model, ids).dtype == jnp.bfloat16


def test_benchmark_copy_of_the_reference_is_the_same():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference_solar_open2 as B
    finally:
        sys.path.pop(0)
    cfg = tiny.tiny_config()
    _, named = tiny.build(cfg)
    sizes = tiny.sizes(cfg)
    params = R.from_named(named, sizes)
    ids = np.random.default_rng(9).integers(0, tiny.VOCAB, 17)
    held = list(cfg.held_experts)
    outs = []
    for lowp in (None, "fp8", "fp8_routed"):
        a, b = (jax.jit(lambda p, m=m: m.forward_held(p, ids, sizes, held,
                                                      lowp))(params)
                for m in (R, B))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        outs.append(np.asarray(a))
    # the routed experts' products alone in fp8 move the logits, and less
    # than every product in fp8 does
    routed, every = (np.abs(o - outs[0]).max() for o in outs[:0:-1])
    assert 0 < routed < every

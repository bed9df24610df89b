"""Laguna against its plain reference (``reference_laguna.py``): the whole
forward pass (two kinds of attention layer, a dense and four expert MLPs),
the switches' other values, the expert sublayer's eight shares of a
deployment, dtypes, and the benchmark's copy of the reference."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.laguna import WINDOW, LagunaAttention
from paddle_tpu.nn.layer.experts import DroplessExperts

import laguna_tiny as tiny
import reference_laguna as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("over", [
    {"held_experts": None}, {}, {"gating": False},
    {"sliding_window": 200},
    {"layer_types": (WINDOW,) * 5, "num_attention_heads_per_layer": (4,) * 5},
    {"mlp_layer_types": ("dense",) * 5}],
    ids=["uncut", "share", "no_gate", "window_past_the_prompt",
         "all_window", "all_dense"])
def test_forward_matches_reference(over):
    cfg = tiny.tiny_config(**over)
    model, named = tiny.build(cfg)
    ids = np.random.default_rng(1).integers(0, tiny.VOCAB, 43)
    out = tiny.logits(model, ids[None])[0]
    ref = tiny.reference_logits(named, cfg, ids)
    assert out.shape == ref.shape == (43, tiny.VOCAB)
    np.testing.assert_allclose(out, ref, atol=3e-4)


def test_the_band_and_the_rotation_matter():
    # the same weights with the window widened, or the positions shifted,
    # give other logits: the comparison above would see either dropped
    cfg = tiny.tiny_config()
    model, named = tiny.build(cfg)
    ids = np.random.default_rng(2).integers(0, tiny.VOCAB, 40)
    base = tiny.logits(model, ids[None])[0]
    wide, _ = tiny.build(tiny.tiny_config(sliding_window=64))
    assert float(jnp.abs(tiny.logits(wide, ids[None])[0] - base).max()) > 0.1
    with paddle.no_grad():
        moved = model(paddle.Tensor(jnp.asarray(ids[None])),
                      position_ids=jnp.arange(40)[None] * 2)._value[0]
    assert float(jnp.abs(moved - base).max()) > 0.1
    np.testing.assert_allclose(moved[0], base[0], atol=1e-5)  # position 0


def test_attention_layers_differ_by_kind():
    cfg = tiny.tiny_config()
    model, _ = tiny.build(cfg)
    full, ring = model.backbone.layers[0], model.backbone.layers[1]
    assert (full.mixer.nq, ring.mixer.nq) == (6, 8)
    assert full.mixer.q_proj.shape == [64, 6 * 16]
    assert ring.mixer.q_proj.shape == [64, 8 * 16]
    assert full.mixer.gate_proj.shape == [64, 6]       # one gate a head
    assert full.mixer.k_proj.shape == ring.mixer.k_proj.shape == [64, 32]
    assert full.mixer.inv_freq.shape == (4,)           # YaRN on half a head
    assert ring.mixer.inv_freq.shape == (8,)           # plain on the whole
    assert full.mixer.rope_scale == pytest.approx(1.4158883)
    assert ring.mixer.rope_scale == 1.0
    assert (full.window, ring.window) == (None, 8)
    assert not full.sparse and ring.sparse
    spec = model.cache_spec()
    assert [e and e["kind"] for e in spec] == [
        "kv", None, "kv", "counts", "kv", "counts", "kv", "counts", "kv",
        "counts"]
    assert [e.get("window") for e in spec[::2]] == [None, 8, 8, 8, None]
    with pytest.raises(ValueError, match="rope_type"):
        LagunaAttention(cfg, 4, None, {"rope_type": "llama3",
                                       "rope_theta": 1e4})
    with pytest.raises(ValueError, match="layer_types"):
        tiny.build(tiny.tiny_config(layer_types=("local",) * 5))


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
                               atol=3e-4)


def _experts(held, source=None):
    layer = DroplessExperts(64, 32, 16, 3, held=held, shared_width=32,
                            scale=2.5, dtype="float32", init_std=0.3,
                            form="swiglu")
    if source is not None:  # the same model's weights, this share of them
        sel = np.asarray(layer.held, np.int64)
        for name in ("gate_weight", "gate_bias", "shared_up", "shared_down"):
            getattr(layer, name)._value = getattr(source, name)._value
        layer.up._value = source.up._value[sel]
        layer.down._value = source.down._value[sel]
    return layer


def _run(layer, x):
    with paddle.no_grad():
        return jax.jit(lambda a: tuple(t._value for t in layer(a)))(x)


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
    cfg = {"num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5}
    return jax.jit(lambda u, q: R.experts(u, q, cfg, list(layer.held)))(x, p)


def test_eight_shares_add_up_to_the_uncut_layer():
    # guide section 4: what every share computes for its own experts, with
    # the shared expert counted once, is what the uncut layer gives; eight
    # shares of two experts each, as the deployment's eight chips of 32,
    # under the published scaling of 2.5
    paddle.seed(3)
    whole = _experts(None)
    whole.gate_bias._value = jnp.asarray(
        np.random.default_rng(3).normal(size=16) * 0.05, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 24, 64)),
                    jnp.float32)
    uncut = _reference_experts(whole, x[0])
    np.testing.assert_allclose(_run(whole, x)[0][0], uncut, atol=2e-4)
    shared = _reference_experts(whole, x[0], shared_only=True)
    total, pairs = shared, 0
    for c in range(8):
        out, counts, _ = _run(_experts([2 * c, 2 * c + 1], whole), x)
        total = total + (out[0] - shared)
        pairs += int(counts[1])
    np.testing.assert_allclose(total, uncut, atol=2e-4)
    assert pairs == 24 * 3  # every choice fell on exactly one share


def test_parameters_are_born_in_the_models_dtype():
    model, _ = tiny.build(tiny.tiny_config(dtype="bfloat16"))
    for name, p in model.named_parameters():
        want = jnp.float32 if name.endswith(
            ("norm.weight", "norm_f.weight", "gate_weight", "gate_bias")) \
            else jnp.bfloat16
        assert p._value.dtype == want, name
    assert model.backbone.layers[0].mixer.inv_freq.dtype == np.float32


def test_benchmark_copy_of_the_reference_is_the_same():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness import reference_laguna as B
    finally:
        sys.path.pop(0)
    cfg = tiny.tiny_config()
    _, named = tiny.build(cfg)
    sizes = tiny.sizes(cfg)
    params = R.from_named(named, sizes)
    ids = np.random.default_rng(9).integers(0, tiny.VOCAB, 29)
    held = list(cfg.held_experts)
    for lowp in (None, "fp8"):
        a, b = (jax.jit(lambda p, m=m: m.forward_held(p, ids, sizes, held,
                                                      lowp))(params)
                for m in (R, B))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the control is another answer, not the same one rounded away
    assert float(jnp.abs(a - jax.jit(lambda p: R.forward_held(
        p, ids, sizes, held))(params)).max()) > 1e-2
    # and the shared functions are the same text
    import inspect
    for name in ("rope_tables", "rotate", "attention", "route", "experts",
                 "swiglu_mlp", "plan", "block", "head", "unstack", "rms_norm",
                 "forward_held", "_fp8", "_mm"):
        assert inspect.getsource(getattr(R, name)) \
            == inspect.getsource(getattr(B, name)), name

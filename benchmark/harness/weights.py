"""Weights of a GPT-2-shaped model from a seed: one jitted call, on the
device, in the dtypes the configuration serves (matrices, biases and
embeddings in ``dtype``; LayerNorm gains and biases float32).

Both sides use it and neither hands arrays to the other: the builder sets
the program's parameters from ``split(make(...))``, the reference calls
``make`` again from the same seed and upcasts. Leaves are stacked over the
layer axis (12 random draws whatever the depth, so the program compiles in
seconds); ``split`` names them as ``GPTForCausalLM.named_parameters`` does.
Biases and LayerNorm parameters are random too, so that a path that drops
one of them shows in the comparison."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: stacked leaf -> (program's name inside ``gpt.layers.<i>.``)
LAYER_LEAVES = {
    "qkv_w": "qkv_proj.weight", "qkv_b": "qkv_proj.bias",
    "out_w": "out_proj.weight", "out_b": "out_proj.bias",
    "up_w": "up_proj.weight", "up_b": "up_proj.bias",
    "down_w": "down_proj.weight", "down_b": "down_proj.bias",
    "ln1_g": "ln_1.weight", "ln1_b": "ln_1.bias",
    "ln2_g": "ln_2.weight", "ln2_b": "ln_2.bias",
}
TOP_LEAVES = {
    "wte": "gpt.embeddings.word_embeddings.weight",
    "wpe": "gpt.embeddings.position_embeddings.weight",
    "lnf_g": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias",
}


def key_of(seed, stream=0):
    """A jax key from any whole number (seeds pass 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def shapes(sizes):
    """Stacked leaf -> (shape, std, mean, low_precision).

    Without ``init`` in the sizes (``assumed.init`` of the configuration's
    file) the published initialisation: N(0, initializer_range), output
    projections over sqrt(2L). Served under greedy decoding that model
    repeats one token with a wide margin, whatever the cache holds, so a
    configuration that is served states a livelier one: ``qk`` sets the
    standard deviation of the attention scores (``qk**2``; attention then
    picks a few keys and the output depends on the context), ``attn_out``
    and ``mlp_out`` the share of each sublayer in the residual stream."""
    h, L, V, P = (sizes["n_embd"], sizes["n_layer"], sizes["vocab_padded"],
                  sizes["n_positions"])
    f = sizes.get("n_inner") or 4 * h
    std = sizes.get("initializer_range", 0.02)
    init = sizes.get("init")
    if init:
        qkv, up = init["qk"] / h ** 0.5, 1.0 / h ** 0.5
        out, down = init["attn_out"] / h ** 0.5, init["mlp_out"] / f ** 0.5
    else:
        qkv = up = std
        out = down = std / (2 * L) ** 0.5
    return {
        "wte": ((V, h), std, 0.0, True), "wpe": ((P, h), std, 0.0, True),
        "qkv_w": ((L, h, 3 * h), qkv, 0.0, True),
        "qkv_b": ((L, 3 * h), std, 0.0, True),
        "out_w": ((L, h, h), out, 0.0, True),
        "out_b": ((L, h), std, 0.0, True),
        "up_w": ((L, h, f), up, 0.0, True),
        "up_b": ((L, f), std, 0.0, True),
        "down_w": ((L, f, h), down, 0.0, True),
        "down_b": ((L, h), std, 0.0, True),
        "ln1_g": ((L, h), std, 1.0, False), "ln1_b": ((L, h), std, 0.0, False),
        "ln2_g": ((L, h), std, 1.0, False), "ln2_b": ((L, h), std, 0.0, False),
        "lnf_g": ((h,), std, 1.0, False), "lnf_b": ((h,), std, 0.0, False),
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec, dtype):
    out = {}
    for i, (name, shape, std, mean, low) in enumerate(spec):
        x = mean + std * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = x.astype(dtype) if low else x
    return out


def make(seed, sizes, dtype="bfloat16"):
    """Stacked weights from the seed, on the default device."""
    spec = tuple((n,) + v for n, v in shapes(sizes).items())
    return _make(key_of(seed), spec, jnp.dtype(dtype).name)


def split(stacked):
    """Program-named leaves: ``{parameter name: array}`` (slices, lazily)."""
    out = {prog: stacked[name] for name, prog in TOP_LEAVES.items()}
    for name, prog in LAYER_LEAVES.items():
        for i in range(stacked[name].shape[0]):
            out[f"gpt.layers.{i}.{prog}"] = stacked[name][i]
    return out


#: leaves compared in three parts (query, key, value): a key's bias has no
#: gradient under softmax, and the rule that leaves such a leaf out of the
#: change has to see it apart from the query's and the value's
SPLIT3 = ("qkv_w", "qkv_b")
PARTS = ("q", "k", "v")


def is_split(prog_name):
    return ".qkv_proj." in prog_name


def per_leaf(stacked_values):
    """Per-program-leaf scalars from per-stacked-leaf vectors: a stacked
    layer leaf gives one number per layer (``SPLIT3`` ones three, named
    ``<leaf>.q/.k/.v``), a top leaf gives one."""
    out = {}
    for name, prog in TOP_LEAVES.items():
        out[prog] = float(np.asarray(stacked_values[name]))
    for name, prog in LAYER_LEAVES.items():
        for i, v in enumerate(np.asarray(stacked_values[name])):
            if name in SPLIT3:
                for part, x in zip(PARTS, v):
                    out[f"gpt.layers.{i}.{prog}.{part}"] = float(x)
            else:
                out[f"gpt.layers.{i}.{prog}"] = float(v)
    return out

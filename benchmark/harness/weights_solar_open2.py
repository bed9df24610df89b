"""Weights of a Solar Open 2 share from a seed, ONE LAYER AT A TIME.

A chip's share of the configuration is 3.3 G parameters: the program holds
them all (6.6 GB in bfloat16), the float32 reference can hold one layer (its
mixer in float32, its expert stacks as drawn: 1.8 GB). So every leaf is
drawn from a key of its own, ``(seed, layer, leaf)``, in the dtype the
program keeps it in, and both sides call :func:`layer` / :func:`top` for the
layer they need: the builder for each in turn, the reference for the one its
requests are passing. Neither hands arrays to the other.

Leaves are named as ``reference_solar_open2.py`` names them before it
unstacks them; :data:`LEAVES` / :data:`TOP` give the program's parameter
names. Each routed expert's gate matrix is drawn stacked on its up matrix,
both out-major (``experts_gate_up [experts, 2 width, hidden]``), and the
shared expert's side by side (``shared_gate_up [hidden, 2 width]``), as the
program keeps them; the reference splits and transposes.
"""
from __future__ import annotations

import math

# the seeded draws (a key of its own a leaf, ``normal`` / ``const`` /
# ``A_log`` / ``dt_bias``) are the hybrid's: one way to turn a seed into a leaf
from .weights_nemotron_h import _make

#: reference leaf -> the program's name inside ``backbone.layers.<i>.``
_EXPERTS = {"norm1": "input_norm.weight", "norm2": "post_norm.weight",
            "gate_w": "experts.gate_weight", "gate_bias": "experts.gate_bias",
            "experts_gate_up": "experts.up", "experts_down": "experts.down",
            "shared_gate_up": "experts.shared_up",
            "shared_down": "experts.shared_down"}
LEAVES = {
    "K": dict(_EXPERTS, **{n: "mixer." + n for n in (
        "q_proj", "k_proj", "v_proj", "q_conv", "k_conv", "v_conv", "a_down",
        "a_up", "dt_bias", "A_log", "b_proj", "g_down", "g_up", "g_bias")},
              norm_w="mixer.norm_weight", o_proj="mixer.out_proj"),
    "G": dict(_EXPERTS, **{n: "mixer." + n for n in (
        "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj")}),
}
TOP = {"embed": "backbone.embeddings", "norm_f": "backbone.norm_f.weight",
       "head": "lm_head"}
#: the train driver's interface; this class is served only
PARTS = ()


def is_split(prog_name):
    return False


def split(leaves):
    return leaves


def kinds(sizes):
    """A letter a layer: ``G`` where ``gqa_layers`` says, else ``K``."""
    return "".join("G" if i in sizes["gqa_layers"] else "K"
                   for i in range(sizes["num_hidden_layers"]))


def layer_spec(sizes, kind):
    """Leaf -> ``(shape, draw, a, b, low)``: ``normal`` has mean ``a`` and
    std ``b``; ``A_log`` / ``dt_bias`` / ``const`` as the published layer
    initialises them. ``low`` leaves are kept in the configuration's dtype,
    the rest float32."""
    init = sizes["init"]
    h, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    fs = sizes["n_shared_experts"] * f
    held, routed = sizes["n_routed_experts"], sizes["router_outputs"]
    up = 1 / math.sqrt(h)
    norm = ((h,), "normal", 1.0, 0.02, False)
    experts = {
        "norm2": norm,
        "gate_w": ((routed, h), "normal", 0.0, init["router"] * up, False),
        "gate_bias": ((routed,), "const", 0.0, None, False),
        "experts_gate_up": ((held, 2 * f, h), "normal", 0.0, up, True),
        "experts_down": ((held, f, h), "normal", 0.0,
                         init["routed_out"] / math.sqrt(f), True),
        "shared_gate_up": ((h, 2 * fs), "normal", 0.0, up, True),
        "shared_down": ((fs, h), "normal", 0.0,
                        init["mlp_out"] / math.sqrt(fs), True),
    }
    if kind == "K":
        kda = sizes["linear_attn_config"]
        H, d, K = kda["num_heads"], kda["head_dim"], kda[
            "short_conv_kernel_size"]
        inner, rank = H * d, sizes["kda_gate_rank"]
        conv = ((K, inner), "normal", 0.0, init["conv"], True)
        mixer = {
            "q_proj": ((h, inner), "normal", 0.0, up, True),
            "k_proj": ((h, inner), "normal", 0.0, up, True),
            "v_proj": ((h, inner), "normal", 0.0, up, True),
            "q_conv": conv, "k_conv": conv, "v_conv": conv,
            "a_down": ((h, rank), "normal", 0.0, up, True),
            "a_up": ((rank, inner), "normal", 0.0,
                     init["kda_decay"] / math.sqrt(rank), True),
            "dt_bias": ((inner,), "dt_bias", (init["dt_min"], init["dt_max"],
                                              init["dt_floor"]), None, False),
            "A_log": ((H,), "A_log", 1.0, 16.0, False),
            "b_proj": ((h, H), "normal", 0.0, up, True),
            "g_down": ((h, rank), "normal", 0.0, up, True),
            "g_up": ((rank, inner), "normal", 0.0, 1 / math.sqrt(rank), True),
            "g_bias": ((inner,), "const", 0.0, None, True),
            "norm_w": ((d,), "normal", 1.0, 0.02, False),
            "o_proj": ((inner, h), "normal", 0.0,
                       init["kda_out"] / math.sqrt(inner), True),
        }
    elif kind == "G":
        nq, nkv, d = (sizes["num_attention_heads"],
                      sizes["num_key_value_heads"], sizes["head_dim"])
        qk = init["qk"] * up
        mixer = {
            "q_proj": ((h, nq * d), "normal", 0.0, qk, True),
            "k_proj": ((h, nkv * d), "normal", 0.0, qk, True),
            "v_proj": ((h, nkv * d), "normal", 0.0, up, True),
            "gate_proj": ((h, nq * d), "normal", 0.0, up, True),
            "o_proj": ((nq * d, h), "normal", 0.0,
                       init["attn_out"] / math.sqrt(nq * d), True),
        }
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return {"norm1": norm, **mixer, **experts}


def top_spec(sizes):
    h, V, init = sizes["hidden_size"], sizes["vocab_padded"], sizes["init"]
    return {"embed": ((V, h), "normal", 0.0, init["emb"], True),
            "norm_f": ((h,), "normal", 1.0, 0.02, False),
            "head": ((V, h), "normal", 0.0, init["head"] / math.sqrt(h),
                     True)}


def layer(seed, sizes, i, dtype="bfloat16"):
    """Layer ``i``'s leaves (reference names), on the default device."""
    return _make(seed, i, layer_spec(sizes, kinds(sizes)[i]), dtype)


def top(seed, sizes, dtype="bfloat16"):
    return _make(seed, -1, top_spec(sizes), dtype)


def make(seed, sizes, dtype="bfloat16"):
    """Every leaf under the program's names, as a generator of ``(name,
    array)``: the builder sets one parameter and drops the array before the
    next is drawn."""
    for name, v in top(seed, sizes, dtype).items():
        yield TOP[name], v
    for i, kind in enumerate(kinds(sizes)):
        for name, v in layer(seed, sizes, i, dtype).items():
            yield f"backbone.layers.{i}.{LEAVES[kind][name]}", v

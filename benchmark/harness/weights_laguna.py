"""Weights of a Laguna share from a seed, ONE LAYER AT A TIME.

A chip's share of the configuration is 1.8 G parameters: the program holds
them all (3.6 GB in bfloat16) beside 8.2 GB of cache, the float32 reference
holds one layer beside the score blocks of requests of thousands of
positions. So every leaf is drawn from a key of its own, ``(seed, layer,
leaf)``, in the dtype the program keeps it in, and both sides call
:func:`layer` / :func:`top` for the layer they need: the builder for each in
turn, the reference for the one its requests are passing. Neither hands
arrays to the other.

Leaves are named as ``reference_laguna.py`` names them before it unstacks
them; :data:`LEAVES` / :data:`TOP` give the program's parameter names. Each
routed expert's gate matrix is drawn stacked on its up matrix, both out-major
(``experts_gate_up [experts, 2 width, hidden]``), the shared expert's and the
dense MLP's side by side (``[hidden, 2 width]``), as the program keeps them;
the reference splits and transposes.
"""
from __future__ import annotations

import math

# the seeded draws (a key of its own a leaf) are the hybrid's: one way to
# turn a seed into a leaf
from .weights_nemotron_h import _make

#: reference leaf -> the program's name inside ``backbone.layers.<i>.``
_ATTENTION = {"norm1": "input_norm.weight", "norm2": "post_norm.weight",
              **{n: "mixer." + n for n in (
                  "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj")}}
LEAVES = {
    "dense": dict(_ATTENTION, mlp_gate_up="mlp_gate_up", mlp_down="mlp_down"),
    "sparse": dict(_ATTENTION, **{
        "gate_w": "experts.gate_weight", "gate_bias": "experts.gate_bias",
        "experts_gate_up": "experts.up", "experts_down": "experts.down",
        "shared_gate_up": "experts.shared_up",
        "shared_down": "experts.shared_down"}),
}
TOP = {"embed": "backbone.embeddings", "norm_f": "backbone.norm_f.weight",
       "head": "lm_head"}
#: the train driver's interface; this class is served only
PARTS = ()


def is_split(prog_name):
    return False


def split(leaves):
    return leaves


def layers(sizes):
    """``(query heads, mlp kind)`` a layer of the cut: the first
    ``num_hidden_layers`` of the published lists."""
    n = sizes["num_hidden_layers"]
    return list(zip(sizes["num_attention_heads_per_layer"][:n],
                    sizes["mlp_layer_types"][:n]))


def layer_spec(sizes, heads, mlp):
    """Leaf -> ``(shape, draw, a, b, low)``: ``normal`` has mean ``a`` and
    std ``b``, ``const`` the value ``a``. ``low`` leaves are kept in the
    configuration's dtype, the rest float32."""
    init = sizes["init"]
    h, d, nkv = (sizes["hidden_size"], sizes["head_dim"],
                 sizes["num_key_value_heads"])
    up = 1 / math.sqrt(h)
    qk = init["qk"] * up
    norm = ((h,), "normal", 1.0, 0.02, False)
    spec = {
        "norm1": norm,
        "q_proj": ((h, heads * d), "normal", 0.0, qk, True),
        "k_proj": ((h, nkv * d), "normal", 0.0, qk, True),
        "v_proj": ((h, nkv * d), "normal", 0.0, up, True),
        "gate_proj": ((h, heads), "normal", 0.0, up, True),
        "o_proj": ((heads * d, h), "normal", 0.0,
                   init["attn_out"] / math.sqrt(heads * d), True),
        "norm2": norm,
    }
    if not sizes["gating"]:
        del spec["gate_proj"]
    if mlp == "dense":
        f = sizes["intermediate_size"]
        spec.update({
            "mlp_gate_up": ((h, 2 * f), "normal", 0.0, up, True),
            "mlp_down": ((f, h), "normal", 0.0,
                         init["mlp_out"] / math.sqrt(f), True)})
    elif mlp == "sparse":
        f, fs = (sizes["moe_intermediate_size"],
                 sizes["shared_expert_intermediate_size"])
        held, routed = sizes["num_experts"], sizes["router_outputs"]
        spec.update({
            "gate_w": ((routed, h), "normal", 0.0, init["router"] * up,
                       False),
            "gate_bias": ((routed,), "const", 0.0, None, False),
            "experts_gate_up": ((held, 2 * f, h), "normal", 0.0, up, True),
            "experts_down": ((held, f, h), "normal", 0.0,
                             init["routed_out"] / math.sqrt(f), True),
            "shared_gate_up": ((h, 2 * fs), "normal", 0.0, up, True),
            "shared_down": ((fs, h), "normal", 0.0,
                            init["mlp_out"] / math.sqrt(fs), True)})
    else:
        raise ValueError(f"unknown MLP kind {mlp!r}")
    return spec


def top_spec(sizes):
    h, V, init = sizes["hidden_size"], sizes["vocab_padded"], sizes["init"]
    return {"embed": ((V, h), "normal", 0.0, init["emb"], True),
            "norm_f": ((h,), "normal", 1.0, 0.02, False),
            "head": ((V, h), "normal", 0.0, init["head"] / math.sqrt(h),
                     True)}


def layer(seed, sizes, i, dtype="bfloat16"):
    """Layer ``i``'s leaves (reference names), on the default device."""
    return _make(seed, i, layer_spec(sizes, *layers(sizes)[i]), dtype)


def top(seed, sizes, dtype="bfloat16"):
    return _make(seed, -1, top_spec(sizes), dtype)


def make(seed, sizes, dtype="bfloat16"):
    """Every leaf under the program's names, as a generator of ``(name,
    array)``: the builder sets one parameter and drops the array before the
    next is drawn."""
    for name, v in top(seed, sizes, dtype).items():
        yield TOP[name], v
    for i, (_, mlp) in enumerate(layers(sizes)):
        for name, v in layer(seed, sizes, i, dtype).items():
            yield f"backbone.layers.{i}.{LEAVES[mlp][name]}", v

"""Weights of a Nemotron-H share from a seed, ONE LAYER AT A TIME.

A chip's share of the configuration is 5.3 G parameters: the program holds
them all (10.6 GB in bfloat16), the float32 reference can hold one layer (an
expert layer is 2.6 GB in float32). So every leaf is drawn from a key of its
own, ``(seed, layer, leaf)``, in the dtype the program keeps it in, and both
sides call :func:`layer` / :func:`top` for the layer they need: the builder
for each in turn, the reference for the one its requests are passing. Neither
hands arrays to the other.

Leaves are named as ``reference_nemotron_h.py`` names them; :data:`LEAVES` /
:data:`TOP` give the program's parameter names. The routed experts' ``up``
is drawn out-major ``[experts, width, hidden]``, as the program keeps it
(and as it is published); the reference transposes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: reference leaf -> the program's name inside ``backbone.layers.<i>.``
LEAVES = {
    "M": {"norm": "norm.weight", "in_proj": "mixer.in_proj",
          "conv_w": "mixer.conv_weight", "conv_b": "mixer.conv_bias",
          "dt_bias": "mixer.dt_bias", "A_log": "mixer.A_log", "D": "mixer.D",
          "norm_w": "mixer.norm_weight", "out_proj": "mixer.out_proj"},
    "*": {"norm": "norm.weight", "q_proj": "mixer.q_proj.weight",
          "k_proj": "mixer.k_proj.weight", "v_proj": "mixer.v_proj.weight",
          "o_proj": "mixer.o_proj.weight"},
    "E": {"norm": "norm.weight", "gate_w": "mixer.gate_weight",
          "gate_bias": "mixer.gate_bias", "experts_up": "mixer.up",
          "experts_down": "mixer.down", "shared_up": "mixer.shared_up",
          "shared_down": "mixer.shared_down"},
}
TOP = {"embed": "backbone.embeddings", "norm_f": "backbone.norm_f.weight",
       "head": "lm_head"}
#: the train driver's interface; this class is served only
PARTS = ()


def is_split(prog_name):
    return False


def split(leaves):
    return leaves


def key_of(seed, layer, leaf):
    """A jax key from any whole number (seeds pass 2**31), a layer, a leaf."""
    words = np.random.SeedSequence(
        [int(seed), int(layer) + 1, int(leaf)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _dims(sizes):
    H, P, G, N = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                  sizes["n_groups"], sizes["ssm_state_size"])
    return sizes["hidden_size"], H, P, G, N, H * P, H * P + 2 * G * N


def layer_spec(sizes, kind):
    """Leaf -> ``(shape, draw, a, b, low)``: ``normal`` has mean ``a`` and
    std ``b``; ``columns`` is N(0, 1) times a per-column scale vector;
    ``A_log`` / ``dt_bias`` / ``const`` as Mamba-2 initialises them. ``low``
    leaves are kept in the configuration's dtype, the rest float32."""
    init = sizes["init"]
    h, H, P, G, N, d_inner, C = _dims(sizes)
    norm = ((h,), "normal", 1.0, 0.02, False)
    if kind == "M":
        cols = np.ones((d_inner + C + H,), np.float32)
        cols[2 * d_inner:2 * d_inner + 2 * G * N] = init["ssm_bc"]
        return {
            "norm": norm,
            "in_proj": ((h, d_inner + C + H), "columns",
                        tuple((cols / math.sqrt(h)).tolist()), None, True),
            "conv_w": ((sizes["conv_kernel"], C), "normal", 0.0,
                       init["conv"], True),
            "conv_b": ((C,), "normal", 0.0, 0.1, True),
            "dt_bias": ((H,), "dt_bias", (sizes["time_step_min"],
                                          sizes["time_step_max"],
                                          sizes["time_step_floor"]), None,
                        False),
            "A_log": ((H,), "A_log", 1.0, 16.0, False),
            "D": ((H,), "const", 1.0, None, False),
            "norm_w": ((d_inner,), "normal", 1.0, 0.02, False),
            "out_proj": ((d_inner, h), "normal", 0.0,
                         init["ssm_out"] / math.sqrt(d_inner), True),
        }
    if kind == "*":
        nq, nkv, d = (sizes["num_attention_heads"],
                      sizes["num_key_value_heads"], sizes["head_dim"])
        qk = init["qk"] / math.sqrt(h)
        return {
            "norm": norm,
            "q_proj": ((h, nq * d), "normal", 0.0, qk, True),
            "k_proj": ((h, nkv * d), "normal", 0.0, qk, True),
            "v_proj": ((h, nkv * d), "normal", 0.0, 1 / math.sqrt(h), True),
            "o_proj": ((nq * d, h), "normal", 0.0,
                       init["attn_out"] / math.sqrt(nq * d), True),
        }
    if kind == "E":
        f, fs = (sizes["moe_intermediate_size"],
                 sizes["moe_shared_expert_intermediate_size"])
        held, routed = sizes["n_routed_experts"], sizes["router_outputs"]
        return {
            "norm": norm,
            "gate_w": ((routed, h), "normal", 0.0,
                       init["router"] / math.sqrt(h), False),
            "gate_bias": ((routed,), "const", 0.0, None, False),
            "experts_up": ((held, f, h), "normal", 0.0, 1 / math.sqrt(h),
                           True),
            "experts_down": ((held, f, h), "normal", 0.0,
                             init["routed_out"] / math.sqrt(f), True),
            "shared_up": ((h, fs), "normal", 0.0, 1 / math.sqrt(h), True),
            "shared_down": ((fs, h), "normal", 0.0,
                            init["mlp_out"] / math.sqrt(fs), True),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def top_spec(sizes):
    h, V, init = sizes["hidden_size"], sizes["vocab_padded"], sizes["init"]
    return {"embed": ((V, h), "normal", 0.0, init["emb"], True),
            "norm_f": ((h,), "normal", 1.0, 0.02, False),
            "head": ((V, h), "normal", 0.0, init["head"] / math.sqrt(h),
                     True)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, shape, draw, a, b, dtype):
    f32 = jnp.float32
    if draw == "normal":
        x = a + b * jax.random.normal(key, shape, f32)
    elif draw == "columns":
        x = jax.random.normal(key, shape, f32) * jnp.asarray(a, f32)
    elif draw == "const":
        x = jnp.full(shape, a, f32)
    elif draw == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, a, b))
    elif draw == "dt_bias":
        lo, hi, floor = a
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (
            math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        x = dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus
    else:
        raise ValueError(draw)
    return x.astype(dtype)


def _make(seed, index, spec, dtype):
    return {name: _draw(key_of(seed, index, j), shape, draw, a, b,
                        jnp.dtype(dtype if low else "float32").name)
            for j, (name, (shape, draw, a, b, low)) in enumerate(
                spec.items())}


def layer(seed, sizes, i, dtype="bfloat16"):
    """Layer ``i``'s leaves (reference names), on the default device."""
    kind = sizes["hybrid_override_pattern"][i]
    return _make(seed, i, layer_spec(sizes, kind), dtype)


def top(seed, sizes, dtype="bfloat16"):
    return _make(seed, -1, top_spec(sizes), dtype)


def make(seed, sizes, dtype="bfloat16"):
    """Every leaf under the program's names, as a generator of ``(name,
    array)``: the builder sets one parameter and drops the array before the
    next is drawn."""
    for name, v in top(seed, sizes, dtype).items():
        yield TOP[name], v
    for i, kind in enumerate(sizes["hybrid_override_pattern"]):
        for name, v in layer(seed, sizes, i, dtype).items():
            yield f"backbone.layers.{i}.{LEAVES[kind][name]}", v

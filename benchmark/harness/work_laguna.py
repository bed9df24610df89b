"""Operations and bytes a Laguna share needs, from shapes and counts.

Nothing here looks at what implements the work: the configuration's sizes,
the driver's counts (positions, keys, outputs, decode steps) and the tick
records' counts (pairs that fell on a held expert, experts hit, the rows a
ring layer's queries saw). Masked-out attention is not counted: a full
layer's query counts the keys up to its own, a window layer's the keys of
its window (the band, not the causal triangle); of the routed experts'
weights a step's bytes count those of the experts that got a row, not of
every expert held; of the keys and values those of LIVE positions (a full
layer's, and a ring's rows up to its window), so that a route that reads
the reserved cache shows as a lower share.

Sizes: ``h`` hidden, ``V`` vocabulary rows held; layer ``l`` is attention
(``F`` full or ``S`` window, by ``layer_types``, with
``num_attention_heads_per_layer[l]`` query heads) and a dense MLP or an
expert sublayer (``mlp_layer_types``); ``num_experts`` is the experts HELD,
the router has ``router_outputs``. The cut is the first
``num_hidden_layers`` entries of the published lists.
"""
from __future__ import annotations


def layers(sizes):
    """``(kind, query heads, mlp)`` a layer: ``F`` / ``S``, ``dense`` /
    ``sparse``."""
    n = sizes["num_hidden_layers"]
    return [("S" if t == "sliding_attention" else "F", int(heads), mlp)
            for t, heads, mlp in zip(
                sizes["layer_types"][:n],
                sizes["num_attention_heads_per_layer"][:n],
                sizes["mlp_layer_types"][:n])]


def kinds(sizes):
    """How many layers of each attention kind and of each MLP kind."""
    ls = layers(sizes)
    return {k: sum(1 for layer in ls if k in (layer[0], layer[2]))
            for k in ("F", "S", "dense", "sparse")}


def attention_params(sizes, heads):
    """One attention sublayer of ``heads`` query heads: q and o, k and v of
    the K/V heads, a gate a head."""
    h, d = sizes["hidden_size"], sizes["head_dim"]
    return 2 * h * heads * d + 2 * h * sizes["num_key_value_heads"] * d \
        + (h * heads if sizes["gating"] else 0)


def dense_mlp_params(sizes):
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_params(sizes):
    """One routed expert's three matrices."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_sublayer_params(sizes, held=None):
    """``(low, float32)``: the experts held (default: as the sizes say) and
    the shared expert; the router and its correction."""
    held = sizes["num_experts"] if held is None else held
    h = sizes["hidden_size"]
    return (held * expert_params(sizes)
            + 3 * h * sizes["shared_expert_intermediate_size"],
            sizes["router_outputs"] * (h + 1))


def weight_bytes(sizes, itemsize=2):
    """Bytes of every weight held: what one decode step must read."""
    h = sizes["hidden_size"]
    total = 2 * sizes["vocab_padded"] * h * itemsize + 4 * h
    low, f32 = expert_sublayer_params(sizes)
    for _, heads, mlp in layers(sizes):
        total += attention_params(sizes, heads) * itemsize + 2 * 4 * h
        total += dense_mlp_params(sizes) * itemsize if mlp == "dense" \
            else low * itemsize + f32 * 4
    return total


def kv_row_bytes(sizes, itemsize=2):
    """K and V of one position in ONE layer (a full layer's key position, a
    ring's row)."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] * itemsize


def cache_bytes(sizes, slots, max_len, itemsize=2):
    """What the engine reserves: full-length rows and rings."""
    n = kinds(sizes)
    return slots * kv_row_bytes(sizes, itemsize) * (
        n["F"] * max_len + n["S"] * min(sizes["sliding_window"], max_len))


def expert_bytes(sizes, itemsize=2):
    """What a step reads for each held expert that got a row (the tick
    records count them: experts hit)."""
    return expert_params(sizes) * itemsize


def expert_pair_flops(sizes):
    """One (token, expert) pair: gate, up and down, a multiply-add each."""
    return 2 * expert_params(sizes)


def position_flops(sizes):
    """Forward FLOPs of one position without its attention scores, its
    routed experts and the head: the attention's products, the dense MLP,
    the router and the shared expert."""
    h = sizes["hidden_size"]
    total = 0
    for _, heads, mlp in layers(sizes):
        total += 2 * attention_params(sizes, heads)
        total += 2 * dense_mlp_params(sizes) if mlp == "dense" \
            else 2 * h * sizes["router_outputs"] \
            + 2 * 3 * h * sizes["shared_expert_intermediate_size"]
    return total


def head_flops(sizes):
    return 2 * sizes["vocab_padded"] * sizes["hidden_size"]


def attn_flops(sizes, full_keys, band_keys):
    """QK^T and PV: ``full_keys`` is the keys the queries of ONE full layer
    saw between them (each its own and all before it), ``band_keys`` those
    of one window layer (each its window's)."""
    d = sizes["head_dim"]
    return sum(4 * heads * d * (band_keys if kind == "S" else full_keys)
               for kind, heads, _ in layers(sizes))


def band_flops(sizes, band_keys):
    """The window layers' part of :func:`attn_flops`."""
    return attn_flops(sizes, 0, band_keys)


def serve_flops(sizes, n_positions, full_keys, band_keys, n_outputs,
                pairs_on_held):
    return n_positions * position_flops(sizes) \
        + attn_flops(sizes, full_keys, band_keys) \
        + pairs_on_held * expert_pair_flops(sizes) \
        + n_outputs * head_flops(sizes)


def decode_step_fixed_bytes(sizes, itemsize=2):
    """What every batched decode step moves whatever is live: every weight
    but the routed experts'. On top come ``expert_bytes`` for each expert
    hit and :func:`live_kv_bytes`."""
    return weight_bytes(sizes, itemsize) \
        - kinds(sizes)["sparse"] * sizes["num_experts"] \
        * expert_bytes(sizes, itemsize)


def live_kv_bytes(sizes, full_rows, ring_rows, itemsize=2):
    """K and V of the live rows: ``full_rows`` live key positions of one
    full layer, ``ring_rows`` live rows of one ring, each in every layer of
    its kind."""
    n = kinds(sizes)
    return kv_row_bytes(sizes, itemsize) * (n["F"] * full_rows
                                            + n["S"] * ring_rows)

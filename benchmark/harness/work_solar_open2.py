"""Operations and bytes a Solar Open 2 share needs, from shapes and counts.

Nothing here looks at what implements the work: the configuration's sizes,
the driver's counts (positions, keys, outputs, decode steps) and the tick
records' routing counts (pairs that fell on a held expert, experts hit).
Masked-out attention is not counted (the causal half only); of the routed
experts' weights a step's bytes count those of the experts that got a row
(the tick records' ``moe.experts_hit``), not of every expert held; of the
keys and values those of LIVE positions, so that a route that reads the
reserved cache shows as a lower share.

Sizes: ``h`` hidden, ``V`` vocabulary rows held; a layer is a mixer (``G``
gated grouped-KV attention where ``gqa_layers`` says, else ``K`` Kimi Delta
Attention) and an expert sublayer; ``n_routed_experts`` is the experts HELD,
the router has ``router_outputs``.
"""
from __future__ import annotations


def kinds(sizes):
    gqa = sum(1 for i in range(sizes["num_hidden_layers"])
              if i in sizes["gqa_layers"])
    return {"G": gqa, "K": sizes["num_hidden_layers"] - gqa}


def _kda(sizes):
    c = sizes["linear_attn_config"]
    return (c["num_heads"], c["head_dim"], c["short_conv_kernel_size"],
            sizes["kda_gate_rank"])


def params_per_sublayer(sizes):
    """Parameters of one sublayer of each kind, ``{kind: (low, float32)}``:
    those kept in the configuration's dtype and those kept in float32.
    ``E`` is the expert sublayer with the experts held."""
    h = sizes["hidden_size"]
    H, d, K, rank = _kda(sizes)
    inner = H * d
    nq, nkv, hd = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    f = sizes["moe_intermediate_size"]
    fs = sizes["n_shared_experts"] * f
    held, routed = sizes["n_routed_experts"], sizes["router_outputs"]
    return {
        # q, k, v, o; the decay's and the output's low-rank gates and its
        # bias; beta; three convolutions | A_log, dt_bias, the head norm,
        # the sublayer's norm
        "K": (4 * h * inner + 2 * (h * rank + rank * inner) + inner + h * H
              + 3 * K * inner, H + inner + d + h),
        # q, gate, o of all query heads; k, v of the K/V heads
        "G": (3 * h * nq * hd + 2 * h * nkv * hd, h),
        "E": (held * 3 * h * f + 3 * h * fs, routed * h + routed + h),
    }


def weight_bytes(sizes, itemsize=2):
    """Bytes of every weight held: what one decode step must read."""
    total = 2 * sizes["vocab_padded"] * sizes["hidden_size"] * itemsize \
        + 4 * sizes["hidden_size"]
    per = params_per_sublayer(sizes)
    n = dict(kinds(sizes), E=sizes["num_hidden_layers"])
    for kind, count in n.items():
        total += count * (per[kind][0] * itemsize + per[kind][1] * 4)
    return total


def kda_state_bytes(sizes):
    """One slot's delta-rule state of one KDA layer, float32."""
    H, d, _, _ = _kda(sizes)
    return H * d * d * 4


def state_bytes_per_slot(sizes, itemsize=2):
    """A slot's recurrent state over all KDA layers: ``S`` in float32 and
    the convolution window (``[q~ | k~ | v~]``) in the configuration's
    dtype."""
    H, d, K, _ = _kda(sizes)
    return kinds(sizes)["K"] * (
        kda_state_bytes(sizes) + (K - 1) * 3 * H * d * itemsize)


def kv_bytes_per_token(sizes, itemsize=2):
    return kinds(sizes)["G"] * 2 * sizes["num_key_value_heads"] \
        * sizes["head_dim"] * itemsize


def expert_bytes(sizes, itemsize=2):
    """One routed expert's three matrices: what a step reads for each held
    expert that got a row (the tick records count them: experts hit)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] \
        * itemsize


def expert_pair_flops(sizes):
    """One (token, expert) pair: gate, up and down, a multiply-add each."""
    return 3 * 2 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def position_flops(sizes):
    """Forward FLOPs of one position without its attention scores, its
    routed experts and the head: the mixers' products, the delta rule's
    state update and read-out, the router and the shared expert."""
    h = sizes["hidden_size"]
    H, d, K, rank = _kda(sizes)
    inner = H * d
    nq, nkv, hd = (sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim"])
    kda = 2 * (4 * h * inner + 2 * (h * rank + rank * inner) + h * H) \
        + 2 * 3 * K * inner + 7 * H * d * d
    attn = 2 * (3 * h * nq * hd + 2 * h * nkv * hd)
    experts = 2 * h * sizes["router_outputs"] \
        + 3 * 2 * h * sizes["n_shared_experts"] * sizes[
            "moe_intermediate_size"]
    n = kinds(sizes)
    return n["K"] * kda + n["G"] * attn \
        + sizes["num_hidden_layers"] * experts


def head_flops(sizes):
    return 2 * sizes["vocab_padded"] * sizes["hidden_size"]


def attn_flops(sizes, n_keys):
    """QK^T and PV for queries that between them see ``n_keys`` keys."""
    return kinds(sizes)["G"] * 4 * sizes["num_attention_heads"] \
        * sizes["head_dim"] * n_keys


def serve_flops(sizes, n_positions, n_keys, n_outputs, pairs_on_held):
    return n_positions * position_flops(sizes) + attn_flops(sizes, n_keys) \
        + pairs_on_held * expert_pair_flops(sizes) \
        + n_outputs * head_flops(sizes)


def decode_step_fixed_bytes(sizes, slots, itemsize=2):
    """What every batched decode step moves whatever is live: every weight
    but the routed experts' once, the state of every slot read and written.
    On top come ``expert_bytes`` for each expert hit and
    ``kv_bytes_per_token`` for each live key position."""
    routed = sizes["num_hidden_layers"] * sizes["n_routed_experts"] \
        * expert_bytes(sizes, itemsize)
    return weight_bytes(sizes, itemsize) - routed \
        + 2 * slots * state_bytes_per_slot(sizes, itemsize)


def kda_step_bytes(sizes, slots):
    """The delta-rule states of every slot and KDA layer, read and written,
    one decode step: what ``kda_step_fwd`` is bound by."""
    return kinds(sizes)["K"] * slots * 2 * kda_state_bytes(sizes)

"""Operations and bytes a Nemotron-H share needs, from shapes and counts.

Nothing here looks at what implements the work: the configuration's sizes,
the driver's counts (positions, keys, outputs, decode steps) and the tick
records' routing counts (pairs that fell on a held expert). Masked-out
attention is not counted (the causal half only); of the routed experts'
weights a step's bytes count those of the experts that got a row (the tick
records' ``moe.experts_hit``), not of every expert held: a step with few
live slots reads half of them, and a share that charged it all read 128%.

Sizes: ``h`` hidden, ``V`` vocabulary rows held, per kind of block the
published widths; ``n_routed_experts`` is the experts HELD, the router has
``router_outputs``.
"""
from __future__ import annotations


def kinds(sizes):
    p = sizes["hybrid_override_pattern"]
    return {k: p.count(k) for k in "M*E"}


def _mamba(sizes):
    H, P, G, N = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                  sizes["n_groups"], sizes["ssm_state_size"])
    return H, P, G, N, H * P, H * P + 2 * G * N


def params_per_block(sizes):
    """Parameters of one block of each kind, ``{kind: (low, float32)}``:
    those kept in the configuration's dtype and those kept in float32."""
    h = sizes["hidden_size"]
    H, P, G, N, d_inner, C = _mamba(sizes)
    nq, nkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    f, fs = (sizes["moe_intermediate_size"],
             sizes["moe_shared_expert_intermediate_size"])
    held, routed = sizes["n_routed_experts"], sizes["router_outputs"]
    return {
        "M": (h * (d_inner + C + H) + d_inner * h
              + (sizes["conv_kernel"] + 1) * C, 3 * H + d_inner + h),
        "*": (2 * h * nq * d + 2 * h * nkv * d, h),
        "E": (held * 2 * h * f + 2 * h * fs, routed * h + routed + h),
    }


def weight_bytes(sizes, itemsize=2):
    """Bytes of every weight held: what one decode step must read."""
    total = 2 * sizes["vocab_padded"] * sizes["hidden_size"] * itemsize \
        + 4 * sizes["hidden_size"]
    per = params_per_block(sizes)
    for kind, n in kinds(sizes).items():
        total += n * (per[kind][0] * itemsize + per[kind][1] * 4)
    return total


def state_bytes_per_slot(sizes, itemsize=2):
    """A slot's recurrent state over all Mamba blocks: ``S`` in float32 and
    the convolution window in the configuration's dtype."""
    H, P, G, N, _, C = _mamba(sizes)
    return kinds(sizes)["M"] * (
        H * P * N * 4 + (sizes["conv_kernel"] - 1) * C * itemsize)


def kv_bytes_per_token(sizes, itemsize=2):
    return kinds(sizes)["*"] * 2 * sizes["num_key_value_heads"] \
        * sizes["head_dim"] * itemsize


def expert_pair_flops(sizes):
    """One (token, expert) pair: up and down, a multiply-add each."""
    return 2 * 2 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def position_flops(sizes):
    """Forward FLOPs of one position without its attention scores, its
    routed experts and the head: the mixers' products, the recurrence, the
    router and the shared expert."""
    h = sizes["hidden_size"]
    H, P, G, N, d_inner, C = _mamba(sizes)
    nq, nkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim"])
    n = kinds(sizes)
    mamba = 2 * h * (d_inner + C + H) + 2 * d_inner * h \
        + 2 * sizes["conv_kernel"] * C + 5 * H * P * N
    attn = 2 * h * (nq + 2 * nkv) * d + 2 * nq * d * h
    experts = 2 * h * sizes["router_outputs"] \
        + 2 * 2 * h * sizes["moe_shared_expert_intermediate_size"]
    return n["M"] * mamba + n["*"] * attn + n["E"] * experts


def head_flops(sizes):
    return 2 * sizes["vocab_padded"] * sizes["hidden_size"]


def attn_flops(sizes, n_keys):
    """QK^T and PV for queries that between them see ``n_keys`` keys."""
    return kinds(sizes)["*"] * 4 * sizes["num_attention_heads"] \
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
    routed = kinds(sizes)["E"] * sizes["n_routed_experts"] \
        * expert_bytes(sizes, itemsize)
    return weight_bytes(sizes, itemsize) - routed \
        + 2 * slots * state_bytes_per_slot(sizes, itemsize)


def expert_bytes(sizes, itemsize=2):
    """One routed expert's two matrices: what a step reads for each held
    expert that got a row (the tick records count them: experts hit)."""
    return 2 * sizes["hidden_size"] * sizes["moe_intermediate_size"] \
        * itemsize


def ssm_step_bytes(sizes, slots):
    """The recurrent states of every slot, read and written, one step."""
    H, P, G, N, _, _ = _mamba(sizes)
    return kinds(sizes)["M"] * slots * 2 * H * P * N * 4

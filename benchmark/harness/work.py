"""Operations and bytes the work needs, from shapes alone.

Nothing here looks at what implements the work: counts come from the
configuration's sizes and the traffic's lengths, so a kernel change cannot
make a share stale. Recomputation is not counted, masked-out attention is
not counted (the causal half only), and a share built on these counts
cannot pass 100%.

Sizes: ``h`` hidden, ``L`` layers, ``V`` padded vocabulary rows (the head
multiplies all of them), ``P`` positions, ``f`` MLP width.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind):
    """The chip's published peaks; an unknown kind is an error."""
    with open(_PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"harness/peaks.json with its source")
    return table[device_kind]


def _hLVf(sizes):
    h = sizes["n_embd"]
    return h, sizes["n_layer"], sizes["vocab_padded"], \
        sizes.get("n_inner") or 4 * h


def n_params(sizes):
    h, L, V, f = _hLVf(sizes)
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return V * h + sizes["n_positions"] * h + L * per_layer + 2 * h


def matmul_params(sizes):
    """Weights a token multiplies in the decoder layers (not the head)."""
    h, L, _, f = _hLVf(sizes)
    return L * (4 * h * h + 2 * h * f)


def head_flops(sizes):
    h, _, V, _ = _hLVf(sizes)
    return 2 * V * h


def attn_flops(sizes, n_keys):
    """QK^T and PV for one query over ``n_keys`` keys, all layers."""
    h, L, _, _ = _hLVf(sizes)
    return L * 4 * h * n_keys


def train_flops_per_token(sizes, seq):
    """Forward + backward (3x forward), head at every position, causal
    attention (a query at position p sees p+1 keys: (seq+1)/2 on average).
    Round 5's 857 MFLOP/token for GPT-2 124M at s1024 counted the full
    square; this counts 798."""
    fwd = 2 * matmul_params(sizes) + head_flops(sizes) \
        + attn_flops(sizes, (seq + 1) / 2)
    return 3 * fwd


def serve_flops(sizes, n_positions, n_keys, n_outputs):
    """Forward for ``n_positions`` tokens that between them see ``n_keys``
    keys (a token at 0-based position p sees p+1), plus one head product
    per output token."""
    return n_positions * 2 * matmul_params(sizes) \
        + attn_flops(sizes, n_keys) + n_outputs * head_flops(sizes)


def kv_bytes_per_token(sizes, cache_itemsize=2):
    h, L, _, _ = _hLVf(sizes)
    return 2 * L * h * cache_itemsize


def decode_step_bytes(sizes, live_tokens, itemsize=2):
    """What one batched decode step must read: every weight once, and the
    keys and values of the live tokens of the active slots."""
    return n_params(sizes) * itemsize \
        + live_tokens * kv_bytes_per_token(sizes, itemsize)


def flash_train_work(sizes, batch, seq, itemsize=2):
    """Causal attention forward + backward over all layers of one step:
    (FLOPs, bytes). Forward: QK^T, PV. Backward: dV, dP, dQ, dK (the
    recomputed QK^T is not counted). Bytes: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    h, L, _, _ = _hLVf(sizes)
    pairs = batch * seq * (seq + 1) / 2
    flops = L * (2 + 4) * 2 * h * pairs
    nbytes = L * (4 + 8) * batch * seq * h * itemsize
    return flops, nbytes

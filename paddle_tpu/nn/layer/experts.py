"""Dropless routed experts that are told which experts they hold.

The router scores every token against ALL ``num_experts`` (sigmoid, float32)
and picks ``top_k`` of ``score + correction``; the weights are ``scale *
score / sum(chosen scores)`` over all ``top_k`` choices. Of those choices
the layer computes the ones that fall on an expert in ``held`` — the
experts whose weights live on this chip — and adds a shared expert. What
the other experts would add is somebody else's: under expert parallelism
another chip's, and no code here stands in for it.

No capacity, nothing dropped: the (token, expert) pairs on held experts are
sorted by expert into tiles of ``tm`` rows, every expert's rows starting on
a tile boundary, and ONE grouped product runs over them (the Pallas kernel
``moe_grouped_*`` on the TPU, a gathered batched product elsewhere); the
results are gathered back per token and summed with their weights.
``form`` is the experts' and the shared expert's alike, no bias either way:
``relu2``, ``down(relu(up(x))**2)``, two matrices; or ``swiglu``,
``down(silu(gate(x)) * up(x))``, three, the gate stacked on the up matrix
(``up [held, 2 width, hidden]``) so that one gated product reads both.

``counts`` (int32 ``[4]``) is what the serving tick records: tokens routed,
pairs that fell on a held expert, rows of the busiest held expert, held
experts that got a row at all (whose weights the step had to read).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.dispatch import op
from ..initializer import Constant, Normal
from .layers import Layer

__all__ = ["DroplessExperts", "ROUTING_COUNTS"]

F32 = jnp.float32
#: names of ``counts``, in order
ROUTING_COUNTS = ("moe.tokens_routed", "moe.pairs_on_held",
                  "moe.busiest_expert_rows", "moe.experts_hit")


def route(x, gate_w, gate_b, top_k, scale):
    """``(chosen [T, k] int32, weights [T, k] float32)``; scores and
    weights in float32 at full precision whatever ``x`` is."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(F32), gate_w.astype(F32).T,
                                  precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + gate_b.astype(F32), top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    w = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w


def tile_rows(tokens, top_k, num_experts):
    """Rows of a tile: about the rows an expert gets, a power of two in
    16..128 (a decode batch fills one small tile an expert, a prefill
    bucket MXU-sized ones)."""
    mean = max(1, tokens * top_k // num_experts)
    return int(min(128, max(16, 1 << (mean - 1).bit_length())))


def dispatch(local, valid, n_held, tm):
    """Lay the pairs on held experts out in tiles. ``local [T, k]`` is the
    held expert's index or -1, ``valid [T]`` masks padding tokens.
    Returns ``(token_of_row [M], dest [T, k], tile_expert [n_tiles],
    n_active [1], counts [n_held])``; ``dest`` is a pair's row, ``M`` for a
    pair that is not computed here."""
    T, k = local.shape
    n_tiles = -(-T * k // tm) + n_held
    M = n_tiles * tm
    flat = jnp.where(valid[:, None], local, -1).reshape(T * k)
    on = flat >= 0
    hot = (flat[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None, :])
    rank = jnp.take_along_axis(jnp.cumsum(hot.astype(jnp.int32), 0) - 1,
                               jnp.maximum(flat, 0)[:, None], 1)[:, 0]
    counts = jnp.sum(hot.astype(jnp.int32), 0)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    n_active = tile_end[-1:]
    row_start = (tile_end - tiles) * tm
    dest = jnp.where(on, row_start[jnp.maximum(flat, 0)] + rank, M)
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    token_of_row = jnp.zeros((M,), jnp.int32).at[dest].set(token, mode="drop")
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    owner = jnp.searchsorted(tile_end, t, side="right").astype(jnp.int32)
    last = jnp.clip(jnp.searchsorted(tile_end, n_active[0] - 1,
                                     side="right"), 0, n_held - 1)
    tile_expert = jnp.where(t < n_active[0], owner, last).astype(jnp.int32)
    return (token_of_row, dest.reshape(T, k), tile_expert,
            n_active.astype(jnp.int32), counts)


def _grouped(xs, w, tile_expert, n_active, tm, activation, transpose_rhs):
    from ...ops import pallas
    from ...ops.pallas import moe_grouped as K

    k, n = w.shape[2 if transpose_rhs else 1], w.shape[1 if transpose_rhs
                                                       else 2]
    fits = K.supports_gated(tm, k, n // 2, w.dtype.itemsize) \
        if activation == "swiglu" \
        else K.supports_grouped(tm, k, n, w.dtype.itemsize)
    if pallas.is_available() and fits:
        return K.grouped_matmul_pallas(xs, w, tile_expert, n_active, tm,
                                       activation, transpose_rhs)
    with jax.named_scope("moe_grouped_xla"):
        return K.grouped_matmul_xla(xs, w, tile_expert, tm, activation,
                                    transpose_rhs)


@op("dropless_experts")
def _dropless_experts(x, valid, gate_w, gate_b, up, down, *, lut, top_k,
                      scale, form="relu2"):
    """``x [T, h]`` -> ``(routed part [T, h], counts [4], chosen [T, k])``."""
    T, h = x.shape
    n_held = up.shape[0]
    chosen, w = route(x, gate_w, gate_b, top_k, scale)
    local = jnp.asarray(np.asarray(lut, np.int32))[chosen]
    tm = tile_rows(T, top_k, len(lut))
    token_of_row, dest, tile_expert, n_active, counts = dispatch(
        local, valid, n_held, tm)
    xs = x[token_of_row]
    hidden = _grouped(xs, up, tile_expert, n_active, tm, form, True)
    ys = _grouped(hidden, down, tile_expert, n_active, tm, None, False)
    ys = jnp.concatenate([ys, jnp.zeros((1, h), ys.dtype)])
    w = jnp.where(dest < ys.shape[0] - 1, w, 0.0)
    out = jnp.einsum("tk,tkh->th", w, ys[dest].astype(F32))
    stats = jnp.stack([jnp.sum(valid.astype(jnp.int32)), jnp.sum(counts),
                       jnp.max(counts),
                       jnp.sum(counts > 0)]).astype(jnp.int32)
    return out.astype(x.dtype), stats, chosen


@op("relu2_mlp")
def _relu2_mlp(x, up, down):
    hid = jnp.square(jax.nn.relu(
        jnp.matmul(x, up, preferred_element_type=F32)))
    return jnp.matmul(hid.astype(x.dtype), down)


@op("swiglu_mlp")
def _swiglu_mlp(x, gate_up, down):
    """``gate_up [hidden, 2 width]``: the gate's columns, then the up's."""
    hid = jnp.matmul(x, gate_up, preferred_element_type=F32)
    n = hid.shape[-1] // 2
    hid = jax.nn.silu(hid[..., :n]) * hid[..., n:]
    return jnp.matmul(hid.astype(x.dtype), down)


FORMS = {"relu2": (1, _relu2_mlp), "swiglu": (2, _swiglu_mlp)}


class DroplessExperts(Layer):
    """``num_experts`` routed experts of which ``held`` (default: all) are
    computed here, ``top_k`` a token, plus one shared expert of
    ``shared_width`` (0: none); ``form`` (``relu2`` or ``swiglu``) is the
    MLP of both."""

    def __init__(self, hidden_size, expert_width, num_experts, top_k, *,
                 held=None, shared_width=0, scale=1.0, dtype=None,
                 init_std=0.02, form="relu2"):
        super().__init__()
        if form not in FORMS:
            raise ValueError(f"experts of form {form!r}: one of "
                             f"{sorted(FORMS)}")
        self.form = form
        first, self._shared_mlp = FORMS[form]  # matrices of the first layer
        held = list(range(num_experts)) if held is None else list(held)
        if not held or len(set(held)) != len(held) or not all(
                0 <= e < num_experts for e in held):
            raise ValueError(f"held experts {held} are not distinct ids "
                             f"below {num_experts} (at least one)")
        self.held = tuple(int(e) for e in held)
        lut = np.full((num_experts,), -1, np.int32)
        lut[list(self.held)] = np.arange(len(self.held))
        self._lut = tuple(int(i) for i in lut)
        self.top_k, self.scale = int(top_k), float(scale)
        init = Normal(std=init_std)
        # the router and its correction stay float32: a rounded score can
        # flip a token's sixth choice
        self.gate_weight = self.create_parameter(
            [num_experts, hidden_size], dtype="float32",
            default_initializer=init)
        self.gate_bias = self.create_parameter(
            [num_experts], dtype="float32", default_initializer=Constant(0.0))
        n = len(self.held)
        # both stacks are [experts, expert_width, hidden]: ``up`` out-major
        # (as published), so that the 128-aligned hidden size is the minor
        # dimension of both and the TPU lays neither out transposed
        self.up = self.create_parameter(
            [n, first * expert_width, hidden_size], dtype=dtype,
            default_initializer=init)
        self.down = self.create_parameter(
            [n, expert_width, hidden_size], dtype=dtype,
            default_initializer=init)
        self.has_shared = bool(shared_width)
        if self.has_shared:
            self.shared_up = self.create_parameter(
                [hidden_size, first * shared_width], dtype=dtype,
                default_initializer=init)
            self.shared_down = self.create_parameter(
                [shared_width, hidden_size], dtype=dtype,
                default_initializer=init)

    def forward(self, x, valid=None):
        """``x [b, s, h]``; ``valid [b, s]`` (bool) masks tokens that are
        padding or belong to no request: they are routed nowhere. Returns
        ``(out, counts, chosen [b, s, k])``."""
        b, s, h = x.shape
        flat = x.reshape([b * s, h])
        ok = jnp.ones((b * s,), bool) if valid is None \
            else jnp.asarray(getattr(valid, "_value", valid)).reshape(b * s)
        out, counts, chosen = _dropless_experts(
            flat, ok, self.gate_weight, self.gate_bias, self.up, self.down,
            lut=self._lut, top_k=self.top_k, scale=self.scale,
            form=self.form)
        if self.has_shared:
            out = out + self._shared_mlp(flat, self.shared_up,
                                         self.shared_down)
        return (out.reshape([b, s, h]), counts,
                chosen.reshape([b, s, self.top_k]))

"""Plain Laguna: the forward pass in float32 ``jax.numpy``.

What the program's ``models/laguna.py`` is held against. One sequence at
a time, no cache, no kernels, no batching: attention is the score matrix of
one K/V head's group of query heads under a dense mask (causal, or the band
of the last ``sliding_window`` positions), a block of query rows at a time so
that it fits at thousands of positions; the experts are a loop over the
experts held with a mask each. Matrix products run at ``highest`` precision.
It imports nothing of ``paddle_tpu``.

A layer is ``h + attention(RMSNorm(h))`` then ``h + mlp(RMSNorm(h))``. Layer
``l`` has ``num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` K/V heads; q and k are rotated at each position
(``rope_parameters[layer_types[l]]``: ``rotate_half`` pairs over the first
``partial_rotary_factor`` of a head, plain or YaRN frequencies); a
``sliding_attention`` layer's query at ``p`` sees the keys ``p - window < j <=
p``; with ``gating`` head ``i`` of the output is multiplied by ``sigmoid(x
w_g,i)``. The MLP is dense (``down(silu(gate x) * up x)``) or experts:
sigmoid-routed (top-k of ``s + b_corr``, weights ``scale * s / sum s`` over
all k choices), each of the same gated form, plus a shared expert. ``held``
lists the routed experts whose weights are given (``experts_up[i]`` is expert
``held[i]``): what the others would add is left out, and the weights stay
normalised over every choice.

``lowp="fp8"`` is the control, not a reference: every linear layer, the
experts and the head multiply operands rounded to float8 (e4m3, scaled per
tensor); the router, the rotation and the softmax stay float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: query rows of a block of the score matrix
ROWS = 256


def _fp8(a):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def _mm(a, b, lowp=None):
    if lowp == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision="highest")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope_tables(rope, head_dim, L):
    """``(cos, sin) [L, pairs]`` float32, as the layer multiplies them in:
    the frequencies of ``rope`` (one entry of ``rope_parameters``) at the
    positions ``0..L-1``, YaRN's ``attention_factor`` folded in."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    pair = np.arange(dim // 2, dtype=np.float64)
    freq = float(rope["rope_theta"]) ** (-2.0 * pair / dim)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        factor = float(rope["factor"])
        original = float(rope["original_max_position_embeddings"])

        def turning(rotations):  # the pair that makes so many turns
            return dim * math.log(original / (2 * math.pi * rotations)) \
                / (2 * math.log(float(rope["rope_theta"])))

        lo = max(math.floor(turning(rope["beta_fast"])), 0)
        hi = min(math.ceil(turning(rope["beta_slow"])), dim - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((pair - lo) / (hi - lo), 0.0, 1.0)
        freq = freq * (1 - ramp) + freq / factor * ramp
        scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    angle = np.arange(L, dtype=np.float64)[:, None] * freq.astype(
        np.float32).astype(np.float64)[None, :]
    return (jnp.asarray(np.cos(angle) * scale, F32),
            jnp.asarray(np.sin(angle) * scale, F32))


def rotate(x, cos, sin):
    """``x [L, heads, d]``: channel ``c`` of the first ``2 * pairs`` with
    ``c + pairs`` (``rotate_half``), the rest as they are."""
    n = cos.shape[-1]
    x1, x2, rest = x[..., :n], x[..., n:2 * n], x[..., 2 * n:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def attention(u, p, cfg, heads, window, rope, lowp=None):
    """Softmax attention over the keys ``j <= p`` (``window`` None) or ``p -
    window < j <= p``, ``num_key_value_heads`` K/V heads each serving a
    group of the ``heads`` query heads, one K/V head and one block of query
    rows at a time; with ``gate_proj`` one sigmoid gate a head."""
    nkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    L = u.shape[0]
    cos, sin = rope_tables(dict(rope), d, L)
    q = rotate(_mm(u, p["q_proj"], lowp).reshape(L, heads, d), cos, sin)
    k = rotate(_mm(u, p["k_proj"], lowp).reshape(L, nkv, d), cos, sin)
    v = _mm(u, p["v_proj"], lowp).reshape(L, nkv, d)
    q = q.reshape(L, nkv, heads // nkv, d)
    rows = ROWS if L % ROWS == 0 else L
    at = jnp.arange(L, dtype=jnp.int32)

    def group(t):  # a K/V head and the query heads it serves
        q_g, k_g, v_g = t

        def block(b):
            q_b, at_b = b
            s = jnp.einsum("qgd,kd->gqk", q_b, k_g, precision="highest") \
                / np.sqrt(d)
            ok = at[None, :] <= at_b[:, None]
            if window is not None:
                ok = ok & (at[None, :] > at_b[:, None] - window)
            s = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", s, v_g, precision="highest")

        o = jax.lax.map(block, (q_g.reshape(L // rows, rows, -1, d),
                                at.reshape(L // rows, rows)))
        return o.reshape(L, -1, d)

    o = jax.lax.map(group, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(L, heads, d)
    if "gate_proj" in p:
        o = o * jax.nn.sigmoid(_mm(u, p["gate_proj"], lowp))[:, :, None]
    return _mm(o.reshape(L, heads * d), p["o_proj"], lowp)


def route(u, p, cfg):
    """The router: ``(chosen [L, k] int32, weights [L, k])``, float32."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["gate_w"].T, precision="highest"))
    _, chosen = jax.lax.top_k(s + p["gate_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    w = cfg["moe_routed_scaling_factor"] * picked \
        / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w


def swiglu_mlp(x, gate, up, down, lowp=None):
    return _mm(silu(_mm(x, gate, lowp)) * _mm(x, up, lowp), down, lowp)


def experts(u, p, cfg, held, lowp=None):
    """Shared expert plus the held routed experts' part of the result: a
    loop over the experts held, each over every token under its mask."""
    chosen, w = route(u, p, cfg)

    def one(acc, e):
        gate, up, down, index = e  # upcast here: one expert at a time
        w_e = jnp.sum(jnp.where(chosen == index, w, 0.0), -1, keepdims=True)
        return acc + w_e * swiglu_mlp(u, gate.astype(F32), up.astype(F32),
                                      down.astype(F32), lowp), None

    out, _ = jax.lax.scan(
        one, swiglu_mlp(u, p["shared_gate"], p["shared_up"],
                        p["shared_down"], lowp),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         jnp.asarray(held, jnp.int32)))
    return out


def plan(cfg):
    """A hashable tuple a layer: ``(query heads, window or None, rope as
    sorted items, sparse)``."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        out.append((
            int(cfg["num_attention_heads_per_layer"][i]),
            int(cfg["sliding_window"]) if kind == "sliding_attention"
            else None,
            tuple(sorted(cfg["rope_parameters"][kind].items())),
            cfg["mlp_layer_types"][i] == "sparse"))
    return out


def block(layer, h, p, cfg, held, lowp=None):
    """One layer of ``plan`` on ``h [L, hidden]``."""
    heads, window, rope, sparse = layer
    eps = cfg["rms_norm_eps"]
    h = h + attention(rms_norm(h, p["norm1"], eps), p, cfg, heads, window,
                      rope, lowp)
    y = rms_norm(h, p["norm2"], eps)
    if sparse:
        return h + experts(y, p, cfg, held, lowp)
    return h + swiglu_mlp(y, p["mlp_gate"], p["mlp_up"], p["mlp_down"], lowp)


def head(h, norm_f, head_w, cfg, lowp=None):
    return _mm(rms_norm(h, norm_f, cfg["rms_norm_eps"]), head_w.T, lowp)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def forward_held(params, ids, cfg, held, lowp=None):
    """``ids [L]`` -> float32 logits. ``params`` holds a SHARE as a chip
    holds it: ``experts_up[i]`` is routed expert ``held[i]``, the embedding
    and the head have the rows held."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        for layer, p in zip(plan(cfg), params["layers"]):
            h = block(layer, h, _f32(p), cfg, list(held), lowp)
        return head(h, _f32(params["norm_f"]), _f32(params["head"]), cfg,
                    lowp)


def forward(params, ids, cfg, held=None, vocab_rows=None, lowp=None):
    """``ids [L]`` -> float32 logits ``[L, rows]``. ``params`` holds the
    UNCUT model (every routed expert, every vocabulary row); ``held`` and
    ``vocab_rows`` pick the share that is computed (default: all)."""
    held = list(range(cfg["num_experts"])) if held is None else list(held)
    rows = slice(None) if vocab_rows is None else np.asarray(vocab_rows)
    sel = np.asarray(held)
    share = dict(params, embed=np.asarray(params["embed"])[rows],
                 head=np.asarray(params["head"])[rows],
                 layers=[{k: np.asarray(v)[sel] if k.startswith("experts_")
                          else v for k, v in p.items()}
                         for p in params["layers"]])
    return forward_held(share, ids, cfg, held, lowp)


def unstack(p):
    """The program keeps a gate stacked on its up matrix: each routed
    expert's out-major (``experts_gate_up [E, 2 f, h]``), the shared
    expert's and the dense MLP's side by side (``[h, 2 f]``). The two
    matrices of each, as the functions above take them."""
    p = dict(p)
    if "experts_gate_up" in p:
        gu, sgu = p.pop("experts_gate_up"), p.pop("shared_gate_up")
        f = gu.shape[1] // 2
        p["experts_gate"] = gu[:, :f].swapaxes(1, 2)
        p["experts_up"] = gu[:, f:].swapaxes(1, 2)
        p["shared_gate"], p["shared_up"] = sgu[:, :f], sgu[:, f:]
    if "mlp_gate_up" in p:
        mgu = p.pop("mlp_gate_up")
        f = mgu.shape[1] // 2
        p["mlp_gate"], p["mlp_up"] = mgu[:, :f], mgu[:, f:]
    return p


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


def from_named(named, cfg):
    """The reference's ``params`` from ``{program parameter name: array}``
    (names only: nothing of the program is imported)."""
    out = {k: named[v] for k, v in TOP.items()}
    out["layers"] = [
        unstack({k: named[f"backbone.layers.{i}.{v}"]
                 for k, v in LEAVES[kind].items()
                 if k != "gate_proj" or cfg["gating"]})
        for i, kind in enumerate(
            cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])]
    return out

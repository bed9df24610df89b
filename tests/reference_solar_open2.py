"""Plain Solar Open 2: the forward pass in float32 ``jax.numpy``.

What the program's ``models/solar_open2.py`` is held against. One sequence at
a time, no cache, no kernels, no batching: the delta-rule recurrence is a
``lax.scan`` over single positions, attention is the full score matrix under
a causal mask, the experts are a loop over the experts held with a mask
each. Matrix products run at ``highest`` precision. It imports nothing of
``paddle_tpu``.

A layer is ``h + mixer(RMSNorm(h))`` then ``h + experts(RMSNorm(h))``. The
mixer is ``G``, gated grouped-KV attention without positional encoding, or
``K``, Kimi Delta Attention (arXiv:2510.26692): per head ``S' = Diag(exp g_t)
S``, ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``. The
experts are sigmoid-routed (top-k of ``s + b_corr``, weights ``scale * s /
sum s`` over all k choices), each ``down(silu(gate x) * up x)``, plus a shared
expert of the same form. ``held`` lists the routed experts whose weights are
given (``experts_up[i]`` is expert ``held[i]``): what the others would add is
left out, and the weights stay normalised over every choice.

``lowp="fp8"`` is the control, not a reference: every linear layer, the
experts and the head multiply operands rounded to float8 (e4m3, scaled per
tensor); the router and the recurrence stay float32. ``lowp="fp8_routed"``
rounds the routed experts' three products alone: what a fault confined to
the grouped product would look like.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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


def _conv_silu(x, w):
    """Causal depthwise convolution, zeros before the start, then SiLU:
    ``x [L, C]``, ``w [K, C]``."""
    K, L = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    return silu(sum(w[j] * padded[j:j + L] for j in range(K)))


def kda(u, p, cfg, lowp=None):
    """``u [L, hidden]`` -> ``[L, hidden]``; state ``S [H, dk, dv]``."""
    H, d = cfg["kda_num_heads"], cfg["kda_head_dim"]
    L = u.shape[0]
    q, k, v = (_conv_silu(_mm(u, p[n + "_proj"], lowp),
                          p[n + "_conv"]).reshape(L, H, d) for n in "qkv")
    q = q / jnp.sqrt(jnp.sum(jnp.square(q), -1, keepdims=True) + 1e-6) \
        / np.sqrt(d)
    k = k / jnp.sqrt(jnp.sum(jnp.square(k), -1, keepdims=True) + 1e-6)
    a = _mm(_mm(u, p["a_down"], lowp), p["a_up"], lowp).reshape(L, H, d)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        a + p["dt_bias"].reshape(H, d))
    beta = (2.0 if cfg["kda_allow_neg_eigval"] else 1.0) * jax.nn.sigmoid(
        _mm(u, p["b_proj"], lowp))                          # [L, H]

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S
        w = v_t - jnp.einsum("hdv,hd->hv", S, k_t, precision="highest")
        S = S + (b_t[:, None] * k_t)[:, :, None] * w[:, None, :]
        return S, jnp.einsum("hdv,hd->hv", S, q_t, precision="highest")

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), F32), (q, k, v, g, beta))
    o = rms_norm(o, p["norm_w"], cfg["rms_norm_eps"])       # over a head
    gate = _mm(_mm(u, p["g_down"], lowp), p["g_up"], lowp) + p["g_bias"]
    return _mm(o.reshape(L, H * d) * jax.nn.sigmoid(gate), p["o_proj"], lowp)


def attention(u, p, cfg, lowp=None):
    """Causal softmax attention, ``num_key_value_heads`` K/V heads each
    serving a group of query heads; no positional encoding; the output
    gated channel by channel by ``sigmoid(u W_z)`` where ``gate_proj`` is
    given. One K/V head at a time, so that the score matrices of a long
    sequence fit (8 x L x L at once, not 64)."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L = u.shape[0]
    q = _mm(u, p["q_proj"], lowp).reshape(L, nkv, nq // nkv, d)
    k = _mm(u, p["k_proj"], lowp).reshape(L, nkv, d)
    v = _mm(u, p["v_proj"], lowp).reshape(L, nkv, d)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def group(t):  # a K/V head and the query heads it serves
        q_g, k_g, v_g = t
        s = jnp.einsum("qgd,kd->gqk", q_g, k_g, precision="highest") \
            / np.sqrt(d)
        s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("gqk,kd->qgd", s, v_g, precision="highest")

    o = jax.lax.map(group, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(L, nq * d)
    if "gate_proj" in p:
        o = o * jax.nn.sigmoid(_mm(u, p["gate_proj"], lowp))
    return _mm(o, p["o_proj"], lowp)


def route(u, p, cfg):
    """The router: ``(chosen [L, k] int32, weights [L, k])``, float32."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["gate_w"].T, precision="highest"))
    _, chosen = jax.lax.top_k(s + p["gate_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    w = cfg["routed_scaling_factor"] * picked \
        / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w


def swiglu_mlp(x, gate, up, down, lowp=None):
    return _mm(silu(_mm(x, gate, lowp)) * _mm(x, up, lowp), down, lowp)


def experts(u, p, cfg, held, lowp=None):
    """Shared expert plus the held routed experts' part of the result: a
    loop over the experts held, each over every token under its mask."""
    chosen, w = route(u, p, cfg)
    routed = "fp8" if lowp else None  # "fp8_routed" reaches these alone

    def one(acc, e):
        gate, up, down, index = e  # upcast here: one expert at a time
        w_e = jnp.sum(jnp.where(chosen == index, w, 0.0), -1, keepdims=True)
        return acc + w_e * swiglu_mlp(u, gate.astype(F32), up.astype(F32),
                                      down.astype(F32), routed), None

    out, _ = jax.lax.scan(
        one, swiglu_mlp(u, p["shared_gate"], p["shared_up"],
                        p["shared_down"], lowp),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         jnp.asarray(held, jnp.int32)))
    return out


MIXERS = {"K": kda, "G": attention}


def kinds(cfg):
    """A letter a layer: ``G`` where ``gqa_layers`` says, else ``K``."""
    return "".join("G" if i in cfg["gqa_layers"] else "K"
                   for i in range(cfg["num_hidden_layers"]))


def block(kind, h, p, cfg, held, lowp=None):
    """One layer on ``h [L, hidden]``: the mixer, then the experts."""
    eps = cfg["rms_norm_eps"]
    h = h + MIXERS[kind](rms_norm(h, p["norm1"], eps), p, cfg, lowp)
    return h + experts(rms_norm(h, p["norm2"], eps), p, cfg, held, lowp)


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
        for kind, p in zip(kinds(cfg), params["layers"]):
            h = block(kind, h, _f32(p), cfg, list(held), lowp)
        return head(h, _f32(params["norm_f"]), _f32(params["head"]), cfg,
                    lowp)


def forward(params, ids, cfg, held=None, vocab_rows=None, lowp=None):
    """``ids [L]`` -> float32 logits ``[L, rows]``. ``params`` holds the
    UNCUT model (every routed expert, every vocabulary row); ``held`` and
    ``vocab_rows`` pick the share that is computed (default: all)."""
    held = list(range(cfg["n_routed_experts"])) if held is None \
        else list(held)
    rows = slice(None) if vocab_rows is None else np.asarray(vocab_rows)
    sel = np.asarray(held)
    share = dict(params, embed=np.asarray(params["embed"])[rows],
                 head=np.asarray(params["head"])[rows],
                 layers=[{k: np.asarray(v)[sel] if k.startswith("experts_")
                          else v for k, v in p.items()}
                         for p in params["layers"]])
    return forward_held(share, ids, cfg, held, lowp)


#: reference leaf -> the program's parameter name inside ``backbone.layers.<i>.``
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


def unstack(p):
    """The program keeps each expert's gate stacked on its up matrix, both
    out-major (``experts_gate_up [E, 2 f, h]``), and the shared expert's
    side by side (``shared_gate_up [h, 2 f]``): the two matrices of each, as
    the functions above take them."""
    p = dict(p)
    gu, sgu = p.pop("experts_gate_up"), p.pop("shared_gate_up")
    f = gu.shape[1] // 2
    p["experts_gate"] = gu[:, :f].swapaxes(1, 2)
    p["experts_up"] = gu[:, f:].swapaxes(1, 2)
    p["shared_gate"], p["shared_up"] = sgu[:, :f], sgu[:, f:]
    return p


def from_named(named, cfg):
    """The reference's ``params`` from ``{program parameter name: array}``
    (names only: nothing of the program is imported)."""
    out = {k: named[v] for k, v in TOP.items()}
    out["layers"] = [
        unstack({k: named[f"backbone.layers.{i}.{v}"]
                 for k, v in LEAVES[kind].items()
                 if k != "gate_proj" or cfg["use_gqa_gate"]})
        for i, kind in enumerate(kinds(cfg))]
    return out

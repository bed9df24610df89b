"""Plain Solar Open 2: the forward pass in float32 ``jax.numpy``.

The yardstick that decides ``correct`` for the Solar Open 2 cells: a copy of
``tests/reference_solar_open2.py`` (a tier-1 test holds the two equal), then
``served_gaps`` as ``drivers/serve.py`` calls it. One sequence at
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

import functools
import gc
import sys

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


from . import weights_solar_open2 as W  # noqa: E402


def train(*args, **kwargs):
    raise NotImplementedError(
        "the Solar Open 2 configurations are served only: the training "
        "path's reference (the chunked form's backward, expert gradients) "
        "comes with the PR that adds a training cell")


def flat(sizes):
    """The sizes the functions above read, as hashable pairs: the published
    file nests the KDA heads under ``linear_attn_config``."""
    kda = sizes["linear_attn_config"]
    cfg = {k: sizes[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "num_experts_per_tok", "routed_scaling_factor",
        "kda_allow_neg_eigval")}
    cfg.update(kda_num_heads=kda["num_heads"], kda_head_dim=kda["head_dim"])
    return tuple(sorted(cfg.items()))


def _layer_params(seed, sizes, i, dtype):
    """Layer ``i``'s weights from the seed under the names and shapes the
    functions above take: float32, but for the routed experts' stacks, which
    stay as drawn (0.84 and 0.42 GB) and are upcast an expert at a time."""
    p = W.layer(seed, sizes, i, dtype)
    return unstack({k: v if k.startswith("experts_") else v.astype(F32)
                    for k, v in p.items()})


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _block(kind, h, p, cfg, held, lowp):
    return block(kind, h, p, dict(cfg), held, lowp)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _gaps(h, h_low, norm_f, head_w, served_valid, cfg, lowp):
    """Per position: how far the served token's logit lies below the best
    one, by the float32 logits; and, for the control, the same gap for the
    token the lower precision puts first."""
    served, valid = served_valid
    head_w = head_w.astype(F32)
    lg = head(h, norm_f, head_w, dict(cfg))
    best = jnp.max(lg, -1)
    gap = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    out = [jnp.max(jnp.where(valid, gap, 0.0))]
    if lowp:
        low = jnp.argmax(head(h_low, norm_f, head_w, dict(cfg), lowp), -1)
        gap_low = best - jnp.take_along_axis(lg, low[:, None], -1)[:, 0]
        out.append(jnp.max(jnp.where(valid, gap_low, 0.0)))
    return out


def served_gaps(seed, sizes, dtype, requests, pad_to, lowp=None):
    """``requests``: ``(prompt, tokens)`` pairs as served (greedy). One
    forward pass over each prompt with its served tokens; returns per request
    the widest gap over its served tokens (and the control's, if ``lowp``).

    The float32 weights of the whole share do not fit the chip beside the
    score matrices of a request of thousands of positions, so the weights
    are made ONE LAYER AT A TIME and every request passes a layer before the
    next is made; all requests are padded to one length (the longest,
    rounded up to 256, at most ``pad_to``: what follows a request cannot
    reach back into it)."""
    # the driver settles the heap (gc.freeze) before its window: the engine
    # it has deleted by now is cyclic garbage among frozen objects, which no
    # collection frees, and its 11 GB would stay on the device beside this
    gc.unfreeze()
    gc.collect()
    cfg = flat(sizes)
    held = tuple(range(sizes["n_routed_experts"]))
    print(f"reference: distinct served tokens per request "
          f"{[len(set(t)) for _, t in requests]} of "
          f"{[len(t) for _, t in requests]}", file=sys.stderr, flush=True)
    L = min(int(pad_to), -(-max(len(p) + len(t) for p, t in requests)
                           // 256) * 256)
    with jax.default_matmul_precision("highest"):
        top = W.top(seed, sizes, dtype)  # upcast where it is used
        hs, marks = [], []
        for prompt, toks in requests:
            seq = list(prompt) + list(toks)
            n, k = len(prompt), len(toks)
            ids = np.zeros((L,), np.int32)
            ids[:len(seq) - 1] = seq[:-1]
            served = np.zeros((L,), np.int32)
            valid = np.zeros((L,), bool)
            served[n - 1:n - 1 + k] = toks  # position p predicts p+1
            valid[n - 1:n - 1 + k] = True
            hs.append(top["embed"][jnp.asarray(ids)].astype(F32))
            marks.append((jnp.asarray(served), jnp.asarray(valid)))
        lows = list(hs) if lowp else [None] * len(hs)
        for i, kind in enumerate(W.kinds(sizes)):
            p = _layer_params(seed, sizes, i, dtype)
            hs = [_block(kind, h, p, cfg, held, None) for h in hs]
            if lowp:
                lows = [_block(kind, h, p, cfg, held, lowp) for h in lows]
            del p
        return [[float(g) for g in _gaps(h, low, top["norm_f"], top["head"],
                                         mark, cfg, lowp)]
                for h, low, mark in zip(hs, lows, marks)]

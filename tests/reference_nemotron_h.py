"""Plain Nemotron-H: the forward pass in float32 ``jax.numpy``.

The yardstick the tests (and, through its copy under ``benchmark/harness``,
the benchmark) compare the program with. One sequence at a time, no cache,
no kernels, no batching: the Mamba-2 recurrence is a ``lax.scan`` over single
positions, attention is the full score matrix under a causal mask, the
experts are a loop over the experts held with a mask each. Matrix products
run at ``highest`` precision. It imports nothing of ``paddle_tpu``.

A block is ``h + mixer(RMSNorm(h))`` with one mixer, chosen by the pattern
letter: ``M`` Mamba-2, ``*`` grouped-KV attention without positional
encoding, ``E`` sigmoid-routed experts (top-k of ``s + b_corr``, weights
``scale * s / sum s`` over all k choices, ``down(relu(up(x))**2)``) plus a
shared expert. ``held`` lists the routed experts whose weights are given
(``experts_up[i]`` is expert ``held[i]``): what the others would add is
left out, and the weights stay normalised over every choice.

``lowp="fp8"`` is the control, not a reference: every linear layer, the
experts and the head multiply operands rounded to float8 (e4m3, scaled per
tensor); the router and the recurrence stay float32.
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


def mamba2(u, p, cfg, lowp=None):
    """``u [L, hidden]`` -> ``[L, hidden]``. State ``S [H, P, N]``:
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D
    x_t``; heads share B and C within a group."""
    H, P, G, N = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["n_groups"], cfg["ssm_state_size"])
    d_inner, K = H * P, cfg["conv_kernel"]
    L = u.shape[0]
    zxbcdt = _mm(u, p["in_proj"], lowp)
    z, xbc, dt = (zxbcdt[:, :d_inner], zxbcdt[:, d_inner:-H],
                  zxbcdt[:, -H:])
    # depthwise causal convolution: zeros before the start
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(p["conv_w"][j] * padded[j:j + L] for j in range(K))
    xbc = silu(conv + p["conv_b"])
    x = xbc[:, :d_inner].reshape(L, H, P)
    B = xbc[:, d_inner:d_inner + G * N].reshape(L, G, N)
    C = xbc[:, d_inner + G * N:].reshape(L, G, N)
    B, C = (jnp.repeat(a, H // G, axis=1) for a in (B, C))  # [L, H, N]
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # [L, H]
    A = -jnp.exp(p["A_log"])

    def step(S, t):
        x_t, B_t, C_t, dt_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, C_t, precision="highest")
        return S, y + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, B, C, dt))
    y = y.reshape(L, d_inner) * silu(z)
    # the gated norm takes its mean square over each group's channels
    yg = y.reshape(L, G, d_inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                            + cfg["layer_norm_epsilon"])
    return _mm(yg.reshape(L, d_inner) * p["norm_w"], p["out_proj"], lowp)


def attention(u, p, cfg, lowp=None):
    """Causal softmax attention, ``num_key_value_heads`` K/V heads each
    serving a group of query heads; no positional encoding."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L = u.shape[0]
    q = _mm(u, p["q_proj"], lowp).reshape(L, nq, d)
    k = _mm(u, p["k_proj"], lowp).reshape(L, nkv, d)
    v = _mm(u, p["v_proj"], lowp).reshape(L, nkv, d)
    k, v = (jnp.repeat(a, nq // nkv, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision="highest")
    return _mm(o.reshape(L, nq * d), p["o_proj"], lowp)


def route(u, p, cfg):
    """The router: ``(chosen [L, k] int32, weights [L, k])``, float32."""
    s = jax.nn.sigmoid(jnp.matmul(u, p["gate_w"].T, precision="highest"))
    _, chosen = jax.lax.top_k(s + p["gate_bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    w = cfg["routed_scaling_factor"] * picked \
        / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w


def _relu2_mlp(x, up, down, lowp):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up, lowp))), down, lowp)


def experts(u, p, cfg, held, lowp=None):
    """Shared expert plus the held routed experts' part of the result: a
    loop over the experts held, each over every token under its mask."""
    chosen, w = route(u, p, cfg)

    def one(acc, e):
        up, down, index = e  # upcast here: one expert at a time
        w_e = jnp.sum(jnp.where(chosen == index, w, 0.0), -1, keepdims=True)
        return acc + w_e * _relu2_mlp(u, up.astype(F32), down.astype(F32),
                                      lowp), None

    out, _ = jax.lax.scan(
        one, _relu2_mlp(u, p["shared_up"], p["shared_down"], lowp),
        (p["experts_up"], p["experts_down"], jnp.asarray(held, jnp.int32)))
    return out


MIXERS = {"M": mamba2, "*": attention}


def block(kind, h, p, cfg, held, lowp=None):
    """One pre-norm block on ``h [L, hidden]``."""
    y = rms_norm(h, p["norm"], cfg["layer_norm_epsilon"])
    if kind == "E":
        return h + experts(y, p, cfg, held, lowp)
    return h + MIXERS[kind](y, p, cfg, lowp)


def head(h, norm_f, head_w, cfg, lowp=None):
    return _mm(rms_norm(h, norm_f, cfg["layer_norm_epsilon"]), head_w.T, lowp)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def forward_held(params, ids, cfg, held, lowp=None):
    """``ids [L]`` -> float32 logits. ``params`` holds a SHARE as a chip
    holds it: ``experts_up[i]`` is routed expert ``held[i]``, the embedding
    and the head have the rows held."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        for kind, p in zip(cfg["hybrid_override_pattern"], params["layers"]):
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
                 layers=[dict(p, experts_up=np.asarray(p["experts_up"])[sel],
                              experts_down=np.asarray(p["experts_down"])[sel])
                         if "experts_up" in p else p
                         for p in params["layers"]])
    return forward_held(share, ids, cfg, held, lowp)


#: reference leaf -> the program's parameter name inside ``backbone.layers.<i>.``
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


def from_named(named, pattern):
    """The reference's ``params`` from ``{program parameter name: array}``
    (names only: nothing of the program is imported)."""
    out = {k: named[v] for k, v in TOP.items()}
    out["layers"] = [
        {k: named[f"backbone.layers.{i}.{v}"] for k, v in LEAVES[kind].items()}
        for i, kind in enumerate(pattern)]
    for p in out["layers"]:
        if "experts_up" in p:  # the program keeps ``up`` out-major
            p["experts_up"] = np.swapaxes(p["experts_up"], 1, 2)
    return out

"""Kimi Delta Attention mixer (arXiv:2510.26692): three projections, each
through its own causal depthwise convolution and a SiLU, the delta-rule
recurrence with a decay per channel (``nn/functional/kda.py``), a
sigmoid-gated per-head RMS norm, output projection.

``q~, k~, v~ = u W_q, u W_k, u W_v``; each passes a convolution of
``conv_kernel`` taps (zeros before the start, no bias) and a SiLU. Per head
``q = q^ / |q^| / sqrt(dk)``, ``k = k^ / |k^|``. Decay ``g = -exp(A_log)
softplus(W_a2 (W_a1 u) + dt_bias)`` per head and channel; step ``beta =
beta_scale sigmoid(u W_b)`` per head (2: eigenvalues of ``I - beta k k^T``
down to -1). ``o = KDA(q, k, v, g, beta)``; ``out = (RMSNorm_head(o) w_n *
sigmoid(W_g2 (W_g1 u) + b_g)) W_o``, the norm over each head's channels with
one gain for all heads.

What a served slot keeps (:meth:`KimiDeltaAttention.state_spec`): the last
``conv_kernel - 1`` rows of ``[q~ | k~ | v~]`` in the model's dtype, and ``S
[H, dk, dv]`` in float32. ``forward(u, state=view)`` reads it with
``view.read()`` and hands the new one to ``view.write()``, as
``Mamba2Mixer`` does: a prefill view starts from zeros and carries
``valid_len`` (positions past it leave state and window untouched: ``beta =
0``, ``g = 0``), a decode view holds every slot's state for one new position.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ops.dispatch import op
from ..functional import kda
from ..initializer import Constant, Normal
from .layers import Layer

__all__ = ["KimiDeltaAttention"]

F32 = jnp.float32


@op("kda_mixer")
def _kda(u, conv_state, S, valid_len, q_w, k_w, v_w, q_conv, k_conv, v_conv,
         a_down, a_up, dt_bias, A_log, b_w, g_down, g_up, g_bias, norm_w,
         out_w, *, heads, head_dim, eps, beta_scale):
    """``u [b, L, hidden]`` -> ``(out, conv_state, S)``; the window holds
    ``[q~ | k~ | v~]`` side by side, and so do the taps."""
    H, d = heads, head_dim
    b, L, _ = u.shape
    conv_w = jnp.concatenate([q_conv, k_conv, v_conv], -1)
    K = conv_w.shape[0]
    qkv = jnp.concatenate([jnp.matmul(u, w) for w in (q_w, k_w, v_w)], -1)
    window = jnp.concatenate([conv_state.astype(qkv.dtype), qkv], axis=1)
    conv = jax.nn.silu(sum(
        conv_w[j].astype(F32) * window[:, j:j + L].astype(F32)
        for j in range(K)))
    if valid_len is None:
        new_conv = window[:, L:]
    else:  # the rows before the last valid position, not the bucket's end
        new_conv = jax.lax.dynamic_slice_in_dim(window, valid_len, K - 1, 1)
    q, k, v = (conv[..., i * H * d:(i + 1) * H * d].reshape(b, L, H, d)
               for i in range(3))
    q = q * jax.lax.rsqrt(jnp.sum(jnp.square(q), -1, keepdims=True) + 1e-6) \
        * (1.0 / math.sqrt(d))
    k = k * jax.lax.rsqrt(jnp.sum(jnp.square(k), -1, keepdims=True) + 1e-6)
    a = jnp.matmul(jnp.matmul(u, a_down), a_up).astype(F32)
    g = -jnp.exp(A_log.astype(F32))[:, None] * jax.nn.softplus(
        a.reshape(b, L, H, d) + dt_bias.astype(F32).reshape(H, d))
    beta = beta_scale * jax.nn.sigmoid(jnp.matmul(u, b_w).astype(F32))
    if valid_len is not None:
        real = jnp.arange(L)[None, :, None] < valid_len
        g, beta = jnp.where(real[..., None], g, 0.0), jnp.where(real, beta,
                                                                0.0)
    if L == 1:
        o, S = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            S)
        o = o[:, None]
    else:
        # beta = g = 0: padding leaves the state
        pad = (-L) % min(kda.CHUNK, L)
        qp, kp, vp, gp, bp = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
        o, S = kda.kda_scan_chunked(qp, kp, vp, gp, bp, S, kda.CHUNK)
        o = o[:, :L]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) \
        * norm_w.astype(F32)
    gate = jnp.matmul(jnp.matmul(u, g_down), g_up).astype(F32) \
        + g_bias.astype(F32)
    y = (o.reshape(b, L, H * d) * jax.nn.sigmoid(gate)).astype(u.dtype)
    return jnp.matmul(y, out_w), new_conv.astype(conv_state.dtype), S


class KimiDeltaAttention(Layer):
    def __init__(self, hidden_size, num_heads, head_dim, *, conv_kernel=4,
                 gate_rank=None, eps=1e-5,
                 allow_neg_eigval=True, dtype=None, init_std=0.02):
        super().__init__()
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.conv_kernel = int(conv_kernel)
        self.eps = float(eps)
        self.beta_scale = 2.0 if allow_neg_eigval else 1.0
        inner = self.num_heads * self.head_dim
        rank = int(gate_rank or head_dim)
        init = Normal(std=init_std)

        def new(shape, dt=dtype, how=init):
            return self.create_parameter(shape, dtype=dt,
                                         default_initializer=how)

        self.q_proj = new([hidden_size, inner])
        self.k_proj = new([hidden_size, inner])
        self.v_proj = new([hidden_size, inner])
        self.q_conv = new([self.conv_kernel, inner])
        self.k_conv = new([self.conv_kernel, inner])
        self.v_conv = new([self.conv_kernel, inner])
        # the decay's gate and the output's, both of low rank
        self.a_down = new([hidden_size, rank])
        self.a_up = new([rank, inner])
        self.g_down = new([hidden_size, rank])
        self.g_up = new([rank, inner])
        self.g_bias = new([inner], how=Constant(0.0))
        self.b_proj = new([hidden_size, self.num_heads])
        # the recurrence's own parameters and the norm's gain stay float32
        self.dt_bias = new([inner], "float32", Constant(0.0))
        self.A_log = new([self.num_heads], "float32", Constant(0.0))
        self.norm_weight = new([self.head_dim], "float32", Constant(1.0))
        self.out_proj = new([inner, hidden_size])

    def state_spec(self):
        """Per-slot recurrent state: name -> (shape, dtype)."""
        inner = self.num_heads * self.head_dim
        return {"conv": ((self.conv_kernel - 1, 3 * inner),
                         self.q_proj.dtype),
                "kda": ((self.num_heads, self.head_dim, self.head_dim),
                        "float32")}

    def forward(self, u, state=None):
        b = u.shape[0]
        if state is None:
            held = {k: jnp.zeros((b,) + tuple(s), d)
                    for k, (s, d) in self.state_spec().items()}
            valid_len = None
        else:
            held, valid_len = state.read(), state.valid_len
        out, conv, S = _kda(
            u, held["conv"], held["kda"], valid_len, self.q_proj,
            self.k_proj, self.v_proj, self.q_conv, self.k_conv, self.v_conv,
            self.a_down, self.a_up, self.dt_bias, self.A_log, self.b_proj,
            self.g_down, self.g_up, self.g_bias, self.norm_weight,
            self.out_proj,
            heads=self.num_heads, head_dim=self.head_dim, eps=self.eps,
            beta_scale=self.beta_scale)
        if state is not None:
            state.write(conv=conv, kda=S)
        return out

"""Mamba-2 mixer: input projection, causal depthwise convolution, the
selective state-space recurrence (``nn/functional/ssm.py``), a gated
per-group RMS norm, output projection.

``[z | xBC | dt] = u W_in``; ``xBC`` passes a causal depthwise convolution
of ``conv_kernel`` taps and a SiLU and splits into ``x [H, P]`` and the
groups' ``B, C [G, N]``; ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``; ``y = SSM(x, dt, A, B, C) + D x``; ``out = (RMSNorm_group(y
silu(z)) w_n) W_out``. ``d_inner = H P`` (not ``expand x hidden``).

What a served slot keeps (:meth:`Mamba2Mixer.state_spec`): the last
``conv_kernel - 1`` rows of the pre-convolution ``xBC`` in the model's
dtype, and ``S [H, P, N]`` in float32. ``forward(u, state=view)`` reads it
with ``view.read()`` and hands the new one to ``view.write()``: a prefill
view starts from zeros and carries ``valid_len`` (positions past it are
padding and leave the state untouched), a decode view holds every slot's
state for one new position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops.dispatch import op
from ..functional import ssm
from ..initializer import Constant, Normal
from .layers import Layer

__all__ = ["Mamba2Mixer"]

F32 = jnp.float32


@op("mamba2_mixer")
def _mamba2(u, conv_state, ssm_state, valid_len, in_w, conv_w, conv_b,
            dt_bias, A_log, D, norm_w, out_w, *, heads, head_dim, groups,
            state, chunk, eps):
    """``u [b, L, hidden]`` -> ``(out, conv_state, ssm_state)``."""
    H, P, G, N = heads, head_dim, groups, state
    d_inner = H * P
    b, L, _ = u.shape
    K = conv_w.shape[0]
    zxbcdt = jnp.matmul(u, in_w)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:zxbcdt.shape[-1] - H]
    dt = zxbcdt[..., -H:]
    window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
    conv = sum(conv_w[j].astype(F32) * window[:, j:j + L].astype(F32)
               for j in range(K))
    xbc = jax.nn.silu(conv + conv_b.astype(F32))
    if valid_len is None:
        new_conv = window[:, L:]
    else:  # the rows before the last valid position, not the bucket's end
        new_conv = jax.lax.dynamic_slice_in_dim(window, valid_len, K - 1, 1)
    x = xbc[..., :d_inner].reshape(b, L, H, P)
    B = xbc[..., d_inner:d_inner + G * N].reshape(b, L, G, N)
    C = xbc[..., d_inner + G * N:].reshape(b, L, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    if valid_len is not None:
        dt = jnp.where(jnp.arange(L)[None, :, None] < valid_len, dt, 0.0)
    A = -jnp.exp(A_log.astype(F32))
    if L == 1:
        y, S = ssm.ssm_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0],
                            ssm_state)
        y = y[:, None]
    else:
        pad = (-L) % min(chunk, L)  # zero dt: padding leaves the state
        xp, dtp, Bp, Cp = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (x, dt, B, C))
        y, S = ssm.ssm_scan_chunked(xp, dtp, A, Bp, Cp, ssm_state, chunk)
        y = y[:, :L]
    y = y + D.astype(F32)[:, None] * x
    y = y.reshape(b, L, d_inner) * jax.nn.silu(z.astype(F32))
    yg = y.reshape(b, L, G, d_inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True)
                            + eps)
    y = (yg.reshape(b, L, d_inner) * norm_w.astype(F32)).astype(u.dtype)
    return jnp.matmul(y, out_w), new_conv.astype(conv_state.dtype), S


class Mamba2Mixer(Layer):
    def __init__(self, hidden_size, num_heads, head_dim, n_groups,
                 state_size, *, conv_kernel=4, chunk_size=128, eps=1e-5,
                 dtype=None, init_std=0.02):
        super().__init__()
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.n_groups, self.state_size = int(n_groups), int(state_size)
        self.conv_kernel, self.chunk_size = int(conv_kernel), int(chunk_size)
        self.eps = float(eps)
        d_inner = self.num_heads * self.head_dim
        self.conv_dim = d_inner + 2 * self.n_groups * self.state_size
        init = Normal(std=init_std)
        new = self.create_parameter
        self.in_proj = new([hidden_size, d_inner + self.conv_dim
                            + self.num_heads], dtype=dtype,
                           default_initializer=init)
        self.conv_weight = new([self.conv_kernel, self.conv_dim],
                               dtype=dtype, default_initializer=init)
        self.conv_bias = new([self.conv_dim], dtype=dtype,
                             default_initializer=Constant(0.0))
        # the recurrence's own parameters stay float32
        self.dt_bias = new([self.num_heads], dtype="float32",
                           default_initializer=Constant(0.0))
        self.A_log = new([self.num_heads], dtype="float32",
                         default_initializer=Constant(0.0))
        self.D = new([self.num_heads], dtype="float32",
                     default_initializer=Constant(1.0))
        self.norm_weight = new([d_inner], dtype="float32",
                               default_initializer=Constant(1.0))
        self.out_proj = new([d_inner, hidden_size], dtype=dtype,
                            default_initializer=init)

    def state_spec(self):
        """Per-slot recurrent state: name -> (shape, dtype)."""
        return {"conv": ((self.conv_kernel - 1, self.conv_dim),
                         self.in_proj.dtype),
                "ssm": ((self.num_heads, self.head_dim, self.state_size),
                        "float32")}

    def forward(self, u, state=None):
        b = u.shape[0]
        if state is None:
            spec = self.state_spec()
            held = {k: jnp.zeros((b,) + tuple(s), d)
                    for k, (s, d) in spec.items()}
            valid_len = None
        else:
            held, valid_len = state.read(), state.valid_len
        out, conv, S = _mamba2(
            u, held["conv"], held["ssm"], valid_len, self.in_proj,
            self.conv_weight, self.conv_bias, self.dt_bias, self.A_log,
            self.D, self.norm_weight, self.out_proj, heads=self.num_heads,
            head_dim=self.head_dim, groups=self.n_groups,
            state=self.state_size, chunk=self.chunk_size, eps=self.eps)
        if state is not None:
            state.write(conv=conv, ssm=S)
        return out

"""Mamba-2 selective state-space recurrence, three forms of one equation.

Per head ``h`` (group ``g(h) = h // (H / G)`` shares ``B`` and ``C``), with
the state ``S [P, N]`` in float32::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t

* :func:`ssm_scan_plain` — ``lax.scan`` over single positions: what the
  other two are tested against;
* :func:`ssm_scan_chunked` — prefill: blocks of ``chunk`` positions, inside
  a block the quadratic form on the MXU, between blocks the recurrence over
  block states (``ssm_scan_carry``);
* :func:`ssm_step` — decode: one position for every slot; on the TPU the
  Pallas kernel ``ssm_step_fwd`` (``ops/pallas/ssm_step.py``), which reads
  and writes each slot's state once.

All take ``dt`` after its softplus. A position whose ``dt`` is 0 leaves the
state as it was (``exp(0) = 1``, nothing added): that is how a padded
prefill bucket returns the state at its last valid position. The ``D x``
skip term and the gate are the mixer's (``nn/layer/mamba.py``).

Shapes: ``x [b, L, H, P]``, ``dt [b, L, H]``, ``A [H]``, ``B, C [b, L, G,
N]``, ``S [b, H, P, N]``; the step drops ``L``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _per_head(a, heads):
    """``[..., G, N]`` -> ``[..., H, N]``: each group's row for its heads."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def _step_xla(x, dt, A, B, C, S):
    H = x.shape[1]
    Bh, Ch = _per_head(B, H), _per_head(C, H)
    S = jnp.exp(dt * A)[..., None, None] * S \
        + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
    return jnp.einsum("bhpn,bhn->bhp", S, Ch, precision=_HI), S


def ssm_step(x, dt, A, B, C, S):
    """One position: ``x [b, H, P]``, ``dt [b, H]``, ``B, C [b, G, N]``,
    ``S [b, H, P, N]`` -> ``(y [b, H, P], S_new)``, all float32."""
    from ...ops import pallas

    x, dt, A, B, C, S = (a.astype(F32) for a in (x, dt, A, B, C, S))
    if pallas.is_available():
        from ...ops.pallas.ssm_step import ssm_step_pallas, supports_step

        if supports_step(x.shape, B.shape):
            return ssm_step_pallas(x, dt, A, B, C, S)
    with jax.named_scope("ssm_step_xla"):
        return _step_xla(x, dt, A, B, C, S)


def ssm_scan_plain(x, dt, A, B, C, S0):
    """The recurrence as written, one position at a time."""
    x, dt, A, B, C, S0 = (a.astype(F32) for a in (x, dt, A, B, C, S0))

    def body(S, t):
        y, S = _step_xla(t[0], t[1], A, t[2], t[3], S)
        return S, y

    t_major = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    S, y = jax.lax.scan(body, S0, t_major)
    return jnp.moveaxis(y, 0, 1), S


def _carry_xla(decay, states, S0):
    """States entering each block and the one after the last:
    ``S_in[c+1] = decay[c] S_in[c] + states[c]``. ``decay [b, nc, H]``,
    ``states [b, nc, H, P, N]``."""

    def body(S, t):
        return t[0][..., None, None] * S + t[1], S

    S_last, S_in = jax.lax.scan(
        body, S0, (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(states, 1, 0)))
    return jnp.moveaxis(S_in, 0, 1), S_last


def ssm_scan_chunked(x, dt, A, B, C, S0, chunk=128):
    """Blocks of ``chunk`` positions (``L`` a multiple of it, or shorter
    than it): ``(y [b, L, H, P], S_last)``."""
    x, dt, A, B, C, S0 = (a.astype(F32) for a in (x, dt, A, B, C, S0))
    b, L, H, P = x.shape
    G, N = B.shape[2:]
    Q = min(int(chunk), L)
    if L % Q:
        raise ValueError(f"ssm_scan_chunked: {L} positions are no multiple "
                         f"of the chunk of {Q}")
    nc, r = L // Q, H // G
    with jax.named_scope("ssm_scan_chunked"):
        cum = jnp.cumsum((dt * A).reshape(b, nc, Q, G, r), axis=2)
        xs = (x * dt[..., None]).reshape(b, nc, Q, G, r, P)
        Bc, Cc = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)
        # inside a block: y_i = sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j) xs_j
        CB = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, precision=_HI)
        diff = cum[:, :, :, None] - cum[:, :, None, :]      # [b,nc,i,j,G,r]
        causal = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None, None]
        decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
        W = jnp.moveaxis(decay, (2, 3), (4, 5)) * CB[:, :, :, None]
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp", W, xs, precision=_HI)
        # each block's own contribution to the state after it
        to_end = jnp.exp(cum[:, :, -1:] - cum)              # [b,nc,Q,G,r]
        states = jnp.einsum("bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, xs, Bc,
                            precision=_HI).reshape(b, nc, H, P, N)
        block_decay = jnp.exp(cum[:, :, -1]).reshape(b, nc, H)
        S_in, S_last = ssm_scan_carry(block_decay, states, S0)
        # what entered the block, decayed to position i
        y = y + jnp.einsum(
            "bcign,bcgrpn,bcigr->bcigrp", Cc,
            S_in.reshape(b, nc, G, r, P, N), jnp.exp(cum), precision=_HI)
    return y.reshape(b, L, H, P), S_last


def ssm_scan_carry(decay, states, S0):
    """The recurrence between blocks; on the TPU the Pallas kernel
    ``ssm_scan_carry`` (one pass over the block states, the running state
    in VMEM)."""
    from ...ops import pallas

    if pallas.is_available():
        from ...ops.pallas.ssm_step import (ssm_scan_carry_pallas,
                                            supports_carry)

        if supports_carry(states.shape):
            return ssm_scan_carry_pallas(decay, states, S0)
    return _carry_xla(decay, states, S0)

"""The delta-rule state update of one decode step as a Pallas TPU kernel.

``kda_step_fwd`` — every slot's state ``S [H, dk, dv]`` (float32, 4.19 MB at
64 x 128 x 128) is read once, decayed per channel, corrected by the rank-1
delta rule, read out and written back in place::

    S' = alpha S      w = v - S'^T k      S = S' + (beta k) w^T      o = S^T q

The grid runs over slots x BLOCKS OF HEADS (:data:`HEAD_BLOCK` heads, 1 MB
of state in and out a step at 16 x 128 x 128): one slot's whole state does
not fit VMEM double-buffered, which is what ``ssm_step``'s one slot a grid
step would need. Inside a step the heads are unrolled so that every slice
is static. What varies along ``dk`` (``alpha``, ``k``, ``beta k``, ``q``)
comes as COLUMNS ``[dk, 1]``, lane slices of one packed input ``[dk, 4 x
heads]``; what varies along ``dv`` (``v``, ``w``, ``o``) as rows ``[1,
dv]``; the two contractions over ``dk`` are sums over sublanes.

The state input is aliased onto the state output, so the caller donates the
buffer (every serving step does). Bound by the bytes of the state. The
backward pass is that of the XLA formulation in ``nn/functional/kda.py``
(serving pulls none).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: heads of one grid step: in + out blocks, double-buffered, take
#: ``4 x HEAD_BLOCK x dk x dv x 4`` bytes of VMEM (4 MB at 16 x 128 x 128)
HEAD_BLOCK = 16


def head_block(heads):
    """Heads of a grid step: the largest divisor of ``heads`` up to
    :data:`HEAD_BLOCK` that fills whole sublane tiles (0: none)."""
    return next((hb for hb in range(min(HEAD_BLOCK, heads), 0, -1)
                 if heads % hb == 0 and hb % 8 == 0), 0)


def supports_step(state_shape):
    """Shape gate: whole (8, 128) tiles of ``[dk, dv]`` a head, and the
    heads divide into blocks of whole sublane tiles."""
    _, H, dk, dv = state_shape
    return dk % 8 == 0 and dv % 128 == 0 and head_block(H) > 0


def _step_kernel(cols_ref, v_ref, s_ref, o_ref, so_ref, *, hb):
    cols = cols_ref[0, 0]                             # [dk, 4 hb]
    for h in range(hb):
        alpha, k, bk, q = (cols[:, j * hb + h:j * hb + h + 1]
                           for j in range(4))         # [dk, 1] each
        S = alpha * s_ref[0, h]                       # [dk, dv]
        w = v_ref[0, 0, h:h + 1, :] - jnp.sum(k * S, axis=0, keepdims=True)
        S = S + bk * w
        so_ref[0, h] = S
        o_ref[0, 0, h:h + 1, :] = jnp.sum(q * S, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(3,))
def _step_call(cols, v, S, interpret):
    b, H, dk, dv = S.shape
    hb = head_block(H)
    nb = H // hb
    kernel = functools.partial(_step_kernel, hb=hb)
    return pl.pallas_call(
        kernel,
        name="kda_step_fwd",
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, 1, dk, 4 * hb), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, hb, dv), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, dv), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, nb, hb, dv), F32),
                   jax.ShapeDtypeStruct(S.shape, F32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(7 * S.size), bytes_accessed=int(8 * S.size),
            transcendentals=0),
    )(cols, v, S)


@jax.custom_vjp
def kda_step_pallas(q, k, v, g, beta, S):
    """``kda_step`` of ``nn/functional/kda.py`` through the kernel."""
    from . import interpret_requested

    b, H, dk, dv = S.shape
    hb = head_block(H)
    nb = H // hb
    # [b, H, dk] x 4 -> [b, nb, dk, 4 hb]: a head's columns on the lanes
    cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1)
    cols = cols.reshape(b, 4, nb, hb, dk).transpose(0, 2, 4, 1, 3)
    o, S = _step_call(cols.reshape(b, nb, dk, 4 * hb),
                      v.reshape(b, nb, hb, dv), S,
                      bool(interpret_requested()))
    return o.reshape(b, H, dv), S


def _step_vjp_fwd(*args):
    return kda_step_pallas(*args), args


def _step_vjp_bwd(args, g):
    from ...nn.functional.kda import _step_xla

    return jax.vjp(_step_xla, *args)[1](g)


kda_step_pallas.defvjp(_step_vjp_fwd, _step_vjp_bwd)

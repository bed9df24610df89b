"""Fused LayerNorm as a Pallas TPU kernel (forward + fused backward).

TPU-native replacement for the reference's fused layernorm CUDA kernels
(``paddle/fluid/operators/fused/fused_layernorm_residual_dropout_bias.h`` and
the LN stages inside ``fused_attention_op.cu``): one VMEM pass per row block
computes mean/var/normalize/affine; the backward kernel recomputes the row
statistics (cheaper than storing them — LN is bandwidth-bound) and
accumulates dgamma/dbeta across row blocks in a revisited output block.

Rows are flattened to ``(rows, features)``; features must be lane-aligned
(multiple of 128) — callers fall back to the XLA path otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
LANES = 128


def _stats(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    return xc, rstd


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    xc, rstd = _stats(x, eps)
    y = xc * rstd * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dgamma_ref, dbeta_ref, *, eps):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dgamma_ref[:] = jnp.zeros_like(dgamma_ref)
        dbeta_ref[:] = jnp.zeros_like(dbeta_ref)

    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)
    xc, rstd = _stats(x, eps)
    xhat = xc * rstd
    dxhat = dy * gamma
    mean_dxhat = jnp.mean(dxhat, axis=-1, keepdims=True)
    mean_dxhat_xhat = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = rstd * (dxhat - mean_dxhat - xhat * mean_dxhat_xhat)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dgamma_ref[:] = dgamma_ref[:] + jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbeta_ref[:] = dbeta_ref[:] + jnp.sum(dy, axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x, gamma, beta, eps, block_rows, interpret):
    rows, feat = x.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="layer_norm_fwd",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, gamma, beta)


def _ln_fwd(x, gamma, beta, eps, block_rows, interpret):
    return _ln(x, gamma, beta, eps, block_rows, interpret), (x, gamma)


def _ln_bwd(eps, block_rows, interpret, res, dy):
    x, gamma = res
    rows, feat = x.shape
    dx, dgamma, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        name="layer_norm_bwd",
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, feat), lambda i: (i, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((1, feat), jnp.float32),
            jax.ShapeDtypeStruct((1, feat), jnp.float32),
        ],
        interpret=interpret,
    )(x, gamma, dy)
    return dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def supports(features):
    return features % LANES == 0


def fused_layer_norm(x, gamma, beta, eps=1e-5, interpret=None):
    """LayerNorm over the last axis. ``x``: (..., features); ``gamma``/``beta``:
    (features,). Returns the same shape/dtype as ``x``."""
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    feat = x.shape[-1]
    if not supports(feat):
        raise ValueError(f"fused_layer_norm needs features % {LANES} == 0, got {feat}")

    def call(x, gamma, beta):
        lead = x.shape[:-1]
        rows = 1
        for s in lead:
            rows *= s
        x2 = x.reshape(rows, feat)
        # sublane-aligned row block; pad rows to a block multiple (padded
        # rows carry zero cotangents through the slice below, so grads are
        # exact)
        block_rows = min(BLOCK_ROWS, -(-rows // 8) * 8)
        pad = -rows % block_rows
        if pad:
            x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        out = _ln(x2, gamma.reshape(1, feat), beta.reshape(1, feat),
                  float(eps), int(block_rows), bool(interpret))
        return out[:rows].reshape(*lead, feat)

    # rows are independent: dim 0 shards over the batch axes (a bare
    # (features,) vector has no batch dim to shard)
    return batch_sharded(call, (x, gamma, beta), (x.ndim > 1, False, False))

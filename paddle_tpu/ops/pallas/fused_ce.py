"""Pallas fused LM-head + softmax cross-entropy ("flash CE").

Reference capability: ``paddle/phi/kernels/gpu/cross_entropy_kernel.cu`` +
``c_softmax_with_cross_entropy_op.cu`` (fused softmax-CE). The XLA-scan
fallback in ``ops/fused.py`` already avoids materializing the full
``[tokens, vocab]`` logits in HBM, but XLA cannot fuse a matmul with its
consumer reductions on TPU: each scan chunk writes its ``[chunk, vocab]``
f32 logits tile to HBM and the while-body fusions read it back (measured
on v5e, GPT-2 124M b16 s1024: ~31 ms/step of while self-time + 6.6 ms of
dW-carry dynamic-update-slice + 4.4 ms of select-reduce — pure HBM
round-trips on top of ~27 ms of near-roofline matmuls).

These kernels keep every logits tile in VMEM:

 - forward: grid (token_block, vocab_block), online logsumexp in scratch
   (running m / l), label logit picked via iota-compare — loss and lse
   written once per token block;
 - backward dx: grid (token_block, vocab_block), recomputes the logits
   tile, forms ``dl = (softmax - onehot) * g`` in registers, accumulates
   ``dl @ W`` in scratch, writes dx once;
 - backward dW (+db): grid (vocab_block, token_block), accumulates
   ``dl^T @ x`` (and ``colsum(dl)``) in scratch, writes once — the scan's
   154 MB f32 dW carry never exists.

Measured outcome (v5e, those shapes): the op is VPU-EXP-BOUND — ~824M f32
exps per forward put an ~8-9 ms floor under any implementation, and the
XLA scan's matmuls already run at ~96% MXU with the while-body overlapped
against them. Forward: Pallas 14.5 ms vs scan 15.7 (blocks 1024x1024).
Fwd+bwd: Pallas 41 vs scan 37 — the split dx/dW backward recomputes the
logits twice where the scan shares one compute per chunk. The scan
therefore remains the hardware default; these kernels are opt-in
(FLAGS_enable_flash_ce) and the interpret-mode default so they stay
correctness-tested. They win where the scan cannot run (e.g. a future
sequence-parallel CE that must fuse a collective per tile).

Arbitrary shapes: tokens pad to the token block (pad g = 0 so padded rows
contribute nothing), vocab pads to the vocab block with masked columns
(``s = -inf`` → p = 0, dl = 0, dW pad rows = 0), sliced off outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_V = 512


def _cols(vi, shape, block_v):
    return vi * block_v + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _logits(x_ref, w_ref, b_ref, vi, block_v, v_real, pad_v):
    s = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    if b_ref is not None:
        s = s + b_ref[...].astype(jnp.float32)
    if pad_v:
        s = jnp.where(_cols(vi, s.shape, block_v) < v_real, s, NEG_INF)
    return s


def _ce_fwd_kernel(x_ref, w_ref, b_ref, y_ref, loss_ref, lse_ref,
                   m_sc, l_sc, pk_sc, *, block_v, v_real, pad_v):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        pk_sc[...] = jnp.zeros_like(pk_sc)

    s = _logits(x_ref, w_ref, b_ref, vi, block_v, v_real, pad_v)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    l_sc[...] = (l_sc[...] * jnp.exp(m_prev - m_new)
                 + jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True))
    m_sc[...] = m_new
    eq = _cols(vi, s.shape, block_v) == y_ref[...]
    pk_sc[...] = pk_sc[...] + jnp.sum(jnp.where(eq, s, 0.0), axis=-1,
                                      keepdims=True)

    @pl.when(vi == nv - 1)
    def _fin():
        lse = m_sc[...] + jnp.log(l_sc[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - pk_sc[...]


def _dl(x_ref, w_ref, b_ref, y_ref, g_ref, lse_ref, vi, block_v, v_real,
        pad_v):
    s = _logits(x_ref, w_ref, b_ref, vi, block_v, v_real, pad_v)
    p = jnp.exp(s - lse_ref[...])
    eq = _cols(vi, s.shape, block_v) == y_ref[...]
    return (p - eq.astype(jnp.float32)) * g_ref[...]


def _ce_dx_kernel(x_ref, w_ref, b_ref, y_ref, g_ref, lse_ref, dx_ref,
                  dx_sc, *, block_v, v_real, pad_v):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        dx_sc[...] = jnp.zeros_like(dx_sc)

    dl = _dl(x_ref, w_ref, b_ref, y_ref, g_ref, lse_ref, vi, block_v,
             v_real, pad_v)
    dx_sc[...] = dx_sc[...] + jax.lax.dot_general(
        dl.astype(w_ref.dtype), w_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )

    @pl.when(vi == nv - 1)
    def _fin():
        dx_ref[...] = dx_sc[...].astype(dx_ref.dtype)


def _ce_dw_kernel(x_ref, w_ref, b_ref, y_ref, g_ref, lse_ref, dw_ref,
                  db_ref, dw_sc, db_sc, *, block_v, v_real, pad_v):
    vi, ni = pl.program_id(0), pl.program_id(1)
    nn = pl.num_programs(1)

    @pl.when(ni == 0)
    def _init():
        dw_sc[...] = jnp.zeros_like(dw_sc)
        if db_sc is not None:
            db_sc[...] = jnp.zeros_like(db_sc)

    dl = _dl(x_ref, w_ref, b_ref, y_ref, g_ref, lse_ref, vi, block_v,
             v_real, pad_v)
    dw_sc[...] = dw_sc[...] + jax.lax.dot_general(
        dl.astype(x_ref.dtype), x_ref[...],
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    if db_sc is not None:
        db_sc[...] = db_sc[...] + jnp.sum(dl, axis=0, keepdims=True)

    @pl.when(ni == nn - 1)
    def _fin():
        dw_ref[...] = dw_sc[...].astype(dw_ref.dtype)
        if db_ref is not None:
            db_ref[...] = db_sc[...]


def _inject(kernel, *positions):
    def wrapped(*refs):
        refs = list(refs)
        for p in sorted(positions):
            refs.insert(p, None)
        return kernel(*refs)

    return wrapped


def _pad_dim(a, axis, size, value=0.0):
    pad = (-a.shape[axis]) % size
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _prep(x, w, b, y, g, block_n, block_v):
    """Pad tokens/vocab to block multiples; reshape 1-D per-token arrays to
    (N, 1) lane-scalar blocks."""
    n, hdim = x.shape
    v = w.shape[0]
    xp = _pad_dim(x, 0, block_n)
    wp = _pad_dim(w, 0, block_v)
    yp = _pad_dim(y.reshape(n, 1).astype(jnp.int32), 0, block_n)
    bp = None if b is None else _pad_dim(b.reshape(1, v), 1, block_v)
    gp = (None if g is None
          else _pad_dim(g.reshape(n, 1).astype(jnp.float32), 0, block_n))
    return xp, wp, bp, yp, gp, xp.shape[0], wp.shape[0]


def supports(hidden_size):
    """H must be lane-tileable; tokens/vocab pad internally."""
    return hidden_size % 128 == 0


def ce_forward(x, w, b, y, *, block_n=DEFAULT_BLOCK_N,
               block_v=DEFAULT_BLOCK_V, interpret=False):
    """Returns (loss, lse), each shape (tokens,) f32."""
    n, hdim = x.shape
    v = w.shape[0]
    xp, wp, bp, yp, _, np_, vp = _prep(x, w, b, y, None, block_n, block_v)
    nn, nv = np_ // block_n, vp // block_v
    kernel = functools.partial(
        _ce_fwd_kernel, block_v=block_v, v_real=v, pad_v=(vp != v))
    if bp is None:
        kernel = _inject(kernel, 2)
    in_specs = [
        pl.BlockSpec((block_n, hdim), lambda ni, vi: (ni, 0)),      # x
        pl.BlockSpec((block_v, hdim), lambda ni, vi: (vi, 0)),      # w
        None if bp is None else
        pl.BlockSpec((1, block_v), lambda ni, vi: (0, vi)),         # b
        pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),         # y
    ]
    loss, lse = pl.pallas_call(
        kernel,
        name="cross_entropy_fwd",
        grid=(nn, nv),
        in_specs=[sp for sp in in_specs if sp is not None],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(2 * np_ * vp * hdim),
            bytes_accessed=int(x.size * 2 + nn * w.size * 2),
            transcendentals=int(np_ * vp),
        ),
    )(*[a for a in (xp, wp, bp, yp) if a is not None])
    return loss[:n, 0], lse[:n, 0]


def ce_backward(x, w, b, y, g, lse, *, block_n=DEFAULT_BLOCK_N,
                block_v=DEFAULT_BLOCK_V, interpret=False):
    """Returns (dx, dw, db) — db is None when b is None. ``g`` is the
    per-token upstream gradient (already zeroed at ignored labels)."""
    n, hdim = x.shape
    v = w.shape[0]
    xp, wp, bp, yp, gp, np_, vp = _prep(x, w, b, y, g, block_n, block_v)
    lp = _pad_dim(lse.reshape(n, 1).astype(jnp.float32), 0, block_n)
    nn, nv = np_ // block_n, vp // block_v
    pad_v = vp != v

    dx_kernel = functools.partial(
        _ce_dx_kernel, block_v=block_v, v_real=v, pad_v=pad_v)
    if bp is None:
        dx_kernel = _inject(dx_kernel, 2)
    dx_specs = [
        pl.BlockSpec((block_n, hdim), lambda ni, vi: (ni, 0)),      # x
        pl.BlockSpec((block_v, hdim), lambda ni, vi: (vi, 0)),      # w
        None if bp is None else
        pl.BlockSpec((1, block_v), lambda ni, vi: (0, vi)),         # b
        pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),         # y
        pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),         # g
        pl.BlockSpec((block_n, 1), lambda ni, vi: (ni, 0)),         # lse
    ]
    dx = pl.pallas_call(
        dx_kernel,
        name="cross_entropy_bwd_dx",
        grid=(nn, nv),
        in_specs=[sp for sp in dx_specs if sp is not None],
        out_specs=pl.BlockSpec((block_n, hdim), lambda ni, vi: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, hdim), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, hdim), jnp.float32)],
        interpret=interpret,
    )(*[a for a in (xp, wp, bp, yp, gp, lp) if a is not None])

    dw_kernel = functools.partial(
        _ce_dw_kernel, block_v=block_v, v_real=v, pad_v=pad_v)
    if bp is None:
        # no bias: drop b input AND the db output/scratch
        def dw_wrapped(x_ref, w_ref, y_ref, g_ref, lse_ref, dw_ref, dw_sc):
            return dw_kernel(x_ref, w_ref, None, y_ref, g_ref, lse_ref,
                             dw_ref, None, dw_sc, None)
        dw_k = dw_wrapped
    else:
        dw_k = dw_kernel
    dw_specs = [
        pl.BlockSpec((block_n, hdim), lambda vi, ni: (ni, 0)),      # x
        pl.BlockSpec((block_v, hdim), lambda vi, ni: (vi, 0)),      # w
        None if bp is None else
        pl.BlockSpec((1, block_v), lambda vi, ni: (0, vi)),         # b
        pl.BlockSpec((block_n, 1), lambda vi, ni: (ni, 0)),         # y
        pl.BlockSpec((block_n, 1), lambda vi, ni: (ni, 0)),         # g
        pl.BlockSpec((block_n, 1), lambda vi, ni: (ni, 0)),         # lse
    ]
    dw_out_specs = [pl.BlockSpec((block_v, hdim), lambda vi, ni: (vi, 0))]
    dw_out_shape = [jax.ShapeDtypeStruct((vp, hdim), w.dtype)]
    scratch = [pltpu.VMEM((block_v, hdim), jnp.float32)]
    if bp is not None:
        dw_out_specs.append(pl.BlockSpec((1, block_v),
                                         lambda vi, ni: (0, vi)))
        dw_out_shape.append(jax.ShapeDtypeStruct((1, vp), jnp.float32))
        scratch.append(pltpu.VMEM((1, block_v), jnp.float32))
    out = pl.pallas_call(
        dw_k,
        name="cross_entropy_bwd_dw",
        grid=(nv, nn),
        in_specs=[sp for sp in dw_specs if sp is not None],
        out_specs=dw_out_specs,
        out_shape=dw_out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*[a for a in (xp, wp, bp, yp, gp, lp) if a is not None])
    if bp is None:
        dw = out if not isinstance(out, (tuple, list)) else out[0]
        db = None
    else:
        dw, db2 = out
        db = db2[0, :v]
    return dx[:n], dw[:v], db

"""Flash attention as a Pallas TPU kernel (forward + fused backward).

TPU-native replacement for the reference CUDA fused attention
(``paddle/fluid/operators/fused/fused_attention_op.cu``, ``fmha_ref.h``):
blockwise online-softmax attention that never materializes the ``[b,h,s,s]``
logits in HBM.  The grid iterates ``(batch, head, q_block, k_block)`` with the
running ``(m, l, acc)`` state held in VMEM scratch across the innermost
k-block sweep — the canonical TPU flash schedule: both matmuls per tile hit
the MXU, softmax runs on the VPU, HBM traffic is O(s·d) not O(s²).

Backward is two fused kernels (dq swept over k-blocks; dk/dv swept over
q-blocks) recomputing p from the saved logsumexp — the FlashAttention-2
recurrence.

The logsumexp is stored sublane-oriented as ``(b, h, s, 8)`` (trailing dim
equal to the full array dim keeps the block legal for Mosaic while staying
16x smaller than a 128-lane broadcast); delta (= rowsum(do*o)) is never
materialized — the backward kernels recompute it per tile from the streamed
``o`` block.  head_dim is used unpadded (block dim = full array dim).

Attention dropout runs IN-KERNEL via the TPU hardware PRNG
(``pltpu.prng_seed`` / ``prng_random_bits``): every kernel (fwd, dq, dk/dv)
re-seeds per (batch, head, q_block, k_block) tile from the caller's seed, so
the three kernels regenerate the identical keep-mask without ever
materializing a ``[b,h,s,s]`` mask in HBM — the same design as the reference
CUDA kernel's in-kernel curand dropout
(``paddle/fluid/operators/fused/fused_attention_op.cu``). Dropout is applied
post-softmax: the l-normalizer accumulates the *undropped* p, the output
accumulates the dropped one. Backward identities (with ``P_d = P·M/keep``):
``delta = rowsum(dO∘O) = Σ_k P_d·dP_d`` still holds, so
``dS = P∘(dP·M/keep − delta)`` and ``dV = P_dᵀ·dO``. Hardware PRNG has no
interpret-mode lowering, so dropout requires a real TPU backend (the F.sdpa
router falls back to the einsum path on CPU).

Layout: public API takes paddle layout ``(batch, seq, heads, head_dim)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e (GPT-2 shapes, d=64): 1024x1024 tiles are ~2x faster than
# 512x512 and ~9x faster than 256x256 at s=4096 (fwd+bwd), and beat XLA's
# fused einsum attention at s=1024 (102.6k vs 88.0k tok/s end-to-end GPT
# training). Bigger tiles exceed VMEM. The entry points take other block
# sizes as arguments; nothing global overrides them.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
LANES = 128
STAT_LANES = 8  # sublane-oriented row-stat arrays
NEG_INF = -1e30


def _causal_mask(s, qi, ki, block_q, block_k, offset):
    """Bottom-right-aligned causal mask (matches the einsum path's
    ``tril(k=seq_k-seq_q)``): query row r attends keys <= r + offset where
    ``offset = seq_k - seq_q``."""
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols <= rows + offset, s, NEG_INF)


def _causal_run(qi, ki, block_q, block_k, offset):
    """Does this (q_block, k_block) tile contain any unmasked entry?"""
    return qi * block_q + block_q - 1 + offset >= ki * block_k


def _zero_masked_rows(p, stat):
    """Zero softmax rows whose running max (fwd) or saved lse (bwd) is
    still NEG_INF: a fully-masked causal query row (the sq > sk boundary
    landing inside a tile) has every logit at NEG_INF, so ``exp(s - stat)``
    collapses to exp(≈0) = 1 — a spurious uniform softmax. The contract
    for such rows is output 0 / lse NEG_INF / zero gradients."""
    return jnp.where(stat > NEG_INF * 0.5, p, 0.0)


def _dropout_mask(seed_ref, qi, ki, shape, dropout_p, head=None):
    """Regenerate the per-tile keep mask from the hardware PRNG. The tile
    coordinates are folded into the two user seed words (``prng_seed``
    accepts at most two scalars through this toolchain) so fwd/dq/dkv
    kernels — whatever their grid order — draw identical bits for the same
    (batch, head, q_block, k_block) tile: distinct tiles map to distinct
    seed pairs (qi, ki < 2^16; heads < 2^10). ``head`` is the static head
    index for kernels that unroll heads in-kernel (the packed layout);
    the layout-swapping kernels carry the head on grid axis 1."""
    bb = pl.program_id(0)
    hh = pl.program_id(1) if head is None else head
    pltpu.prng_seed(seed_ref[0] ^ (qi * 65536 + ki),
                    seed_ref[1] ^ (bb * 1024 + hh))
    return _keep_bits(shape, dropout_p)


def _keep_bits(shape, dropout_p):
    """Draw the keep mask for an already-seeded PRNG. 16 random bits per
    element suffice for the keep test (rate resolution 1/65536) and halve
    the PRNG work vs 32: draw half the sublanes as uint32, bitcast to
    uint16 (which doubles the sublane dim back). Compare in int32: the VPU
    has no 16-bit compare ("Target does not support this comparison"); the
    widening is cheap relative to PRNG."""
    bits = pltpu.bitcast(
        pltpu.prng_random_bits((shape[0] // 2, shape[1])), jnp.uint16
    )
    thr = min(int((1.0 - dropout_p) * 65536.0), 65535)
    return bits.astype(jnp.int32) < thr


def _logits(q_ref, k_ref, b_ref, qi, ki, scale, causal, block_q, block_k,
            offset):
    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    if b_ref is not None:
        s = s + b_ref[0, 0].astype(jnp.float32)
    if causal:
        s = _causal_mask(s, qi, ki, block_q, block_k, offset)
    return s


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                offset, dropout_p):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = _causal_run(qi, ki, block_q, block_k, offset) if causal else (ki >= 0)

    @pl.when(run)
    def _body():
        s = _logits(q_ref, k_ref, b_ref, qi, ki, scale, causal, block_q,
                    block_k, offset)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = _zero_masked_rows(jnp.exp(s - m_new), m_new)
        # l accumulates the UNdropped p (softmax normalizes pre-dropout);
        # only the value matmul sees the dropped probabilities.
        l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            keep = _dropout_mask(seed_ref, qi, ki, s.shape, dropout_p)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = jnp.broadcast_to(
                m_ref[:, 0:1] + jnp.log(l_safe), lse_ref.shape[2:]
            )


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, o_ref,
                   lse_ref, dq_ref, dq_acc, *, scale, causal, block_q,
                   block_k, offset, dropout_p):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _causal_run(qi, ki, block_q, block_k, offset) if causal else (ki >= 0)

    @pl.when(run)
    def _body():
        s = _logits(q_ref, k_ref, b_ref, qi, ki, scale, causal, block_q,
                    block_k, offset)
        lse = lse_ref[0, 0][:, 0:1]
        p = _zero_masked_rows(jnp.exp(s - lse), lse)
        do = do_ref[0, 0]
        # delta = rowsum(do * o): recomputed per tile from the streamed o
        # block — elementwise O(block_q*d), far cheaper than materializing a
        # lane-broadcast (b,h,sq,128) delta array in HBM. With dropout this
        # equals Σ_k P_d·dP_d, exactly the softmax-jacobian rowsum needed.
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        if dropout_p > 0.0:
            keep = _dropout_mask(seed_ref, qi, ki, s.shape, dropout_p)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = p * (dp - delta) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, 0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, o_ref,
                    lse_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, offset, dropout_p):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _causal_run(qi, ki, block_q, block_k, offset) if causal else (qi >= 0)

    @pl.when(run)
    def _body():
        s = _logits(q_ref, k_ref, b_ref, qi, ki, scale, causal, block_q,
                    block_k, offset)
        lse = lse_ref[0, 0][:, 0:1]
        p = _zero_masked_rows(jnp.exp(s - lse), lse)
        do = do_ref[0, 0]
        delta = jnp.sum(
            do.astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        if dropout_p > 0.0:
            keep = _dropout_mask(seed_ref, qi, ki, s.shape, dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            p_d = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_d = p
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p_d.astype(do.dtype), do,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0, 0],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bias_spec(bias, block_q, block_k, kv_major=False):
    """BlockSpec for an additive bias of shape (B|1, H|1, sq, sk), broadcasting
    over size-1 batch/head dims via the index map."""
    if bias is None:
        return None
    bb = bias.shape[0] > 1
    bh = bias.shape[1] > 1

    if kv_major:
        def imap(b, h, ki, qi):
            return (b if bb else 0, h if bh else 0, qi, ki)
    else:
        def imap(b, h, qi, ki):
            return (b if bb else 0, h if bh else 0, qi, ki)

    return pl.BlockSpec((1, 1, block_q, block_k), imap)


def _inject_none(kernel, *positions):
    """Adapt a kernel to a call signature missing some refs (seed / bias /
    lse) by inserting ``None`` at the given positions of the kernel's FULL
    signature (ascending insertion keeps later indices valid)."""

    def wrapped(*refs):
        refs = list(refs)
        for p in sorted(positions):
            refs.insert(p, None)
        return kernel(*refs)

    return wrapped


def _check_shapes(q, k, v, bias):
    b, h, sq, d = q.shape
    bk, hk, sk, dk = k.shape
    assert v.shape == k.shape, (v.shape, k.shape)
    assert (bk, hk, dk) == (b, h, d), (q.shape, k.shape)
    if bias is not None:
        assert bias.ndim == 4 and bias.shape[2:] == (sq, sk), bias.shape
        assert bias.shape[0] in (1, b) and bias.shape[1] in (1, h), bias.shape
    return b, h, sq, sk, d


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, bias, seed, scale, causal, block_q, block_k, interpret,
           need_dbias, dropout_p):
    # primal path (inference / no grad): skip the logsumexp output entirely
    return _flash_fwd_impl(q, k, v, bias, seed, scale, causal, block_q,
                           block_k, interpret, dropout_p, need_stats=False)


def _seed_spec(seed):
    # whole (2,) int32 seed in SMEM, identical for every grid step
    return None if seed is None else pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_fwd_impl(q, k, v, bias, seed, scale, causal, block_q, block_k,
                    interpret, dropout_p, need_stats=True):
    b, h, sq, sk, d = _check_shapes(q, k, v, bias)
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq

    def qmap(bb, hh, qi, ki):
        return (bb, hh, qi, 0)

    def kmap(bb, hh, qi, ki):
        return (bb, hh, ki, 0)

    in_specs = [
        _seed_spec(seed),
        pl.BlockSpec((1, 1, block_q, d), qmap),
        pl.BlockSpec((1, 1, block_k, d), kmap),
        pl.BlockSpec((1, 1, block_k, d), kmap),
        _bias_spec(bias, block_q, block_k),
    ]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, dropout_p=dropout_p,
    )
    # full kernel signature: (seed, q, k, v, bias, o, lse, <scratch>)
    missing = []
    if seed is None:
        missing.append(0)
    if bias is None:
        missing.append(4)
    if need_stats:
        out_specs = [
            pl.BlockSpec((1, 1, block_q, d), qmap),
            pl.BlockSpec((1, 1, block_q, STAT_LANES),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, STAT_LANES), jnp.float32),
        ]
    else:
        missing.append(6)
        out_specs = pl.BlockSpec((1, 1, block_q, d), qmap)
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if missing:
        kernel = _inject_none(kernel, *missing)
    result = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b, h, nq, nk),
        in_specs=[s for s in in_specs if s is not None],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * sq * sk * d * (0.5 if causal else 1.0)),
            bytes_accessed=int(2 * (q.size + k.size + v.size + q.size)),
            transcendentals=int(b * h * sq * sk),
        ),
    )(*[x for x in (seed, q, k, v, bias) if x is not None])
    return result


def _flash_fwd(q, k, v, bias, seed, scale, causal, block_q, block_k,
               interpret, need_dbias, dropout_p):
    out, lse = _flash_fwd_impl(q, k, v, bias, seed, scale, causal, block_q,
                               block_k, interpret, dropout_p, need_stats=True)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, need_dbias,
               dropout_p, res, g):
    q, k, v, bias, seed, out, lse = res
    b, h, sq, sk, d = _check_shapes(q, k, v, bias)
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq

    def qmap(bb, hh, qi, ki):
        return (bb, hh, qi, 0)

    def kmap(bb, hh, qi, ki):
        return (bb, hh, ki, 0)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, dropout_p=dropout_p,
    )
    # full kernel signature: (seed, q, k, v, bias, do, o, lse, dq, <scratch>)
    missing = ([0] if seed is None else []) + ([4] if bias is None else [])
    if missing:
        dq_kernel = _inject_none(dq_kernel, *missing)
    dq_specs = [
        _seed_spec(seed),                              # seed
        pl.BlockSpec((1, 1, block_q, d), qmap),        # q
        pl.BlockSpec((1, 1, block_k, d), kmap),        # k
        pl.BlockSpec((1, 1, block_k, d), kmap),        # v
        _bias_spec(bias, block_q, block_k),            # bias
        pl.BlockSpec((1, 1, block_q, d), qmap),        # do
        pl.BlockSpec((1, 1, block_q, d), qmap),        # o
        pl.BlockSpec((1, 1, block_q, STAT_LANES),
                     lambda bb, hh, qi, ki: (bb, hh, qi, 0)),  # lse
    ]
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(b, h, nq, nk),
        in_specs=[s for s in dq_specs if s is not None],
        out_specs=pl.BlockSpec((1, 1, block_q, d), qmap),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*[x for x in (seed, q, k, v, bias, g, out, lse) if x is not None])

    # dk/dv sweep: grid (b, h, k_block, q_block) so the per-k-block
    # accumulators persist in scratch across the q sweep.
    def kv_qmap(bb, hh, ki, qi):
        return (bb, hh, qi, 0)

    def kv_kmap(bb, hh, ki, qi):
        return (bb, hh, ki, 0)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, dropout_p=dropout_p,
    )
    # full signature: (seed, q, k, v, bias, do, o, lse, dk, dv, <scratch>)
    missing = ([0] if seed is None else []) + ([4] if bias is None else [])
    if missing:
        dkv_kernel = _inject_none(dkv_kernel, *missing)
    dkv_specs = [
        _seed_spec(seed),                              # seed
        pl.BlockSpec((1, 1, block_q, d), kv_qmap),     # q
        pl.BlockSpec((1, 1, block_k, d), kv_kmap),     # k
        pl.BlockSpec((1, 1, block_k, d), kv_kmap),     # v
        _bias_spec(bias, block_q, block_k, kv_major=True),
        pl.BlockSpec((1, 1, block_q, d), kv_qmap),     # do
        pl.BlockSpec((1, 1, block_q, d), kv_qmap),     # o
        pl.BlockSpec((1, 1, block_q, STAT_LANES),
                     lambda bb, hh, ki, qi: (bb, hh, qi, 0)),  # lse
    ]
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(b, h, nk, nq),
        in_specs=[s for s in dkv_specs if s is not None],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), kv_kmap),
            pl.BlockSpec((1, 1, block_k, d), kv_kmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(*[x for x in (seed, q, k, v, bias, g, out, lse) if x is not None])

    if bias is None:
        dbias = None
    elif not need_dbias:
        # constant mask (the common case): a symbolic-zero-like cheap
        # cotangent; no score matrix is ever materialized
        dbias = jnp.zeros_like(bias)
    else:
        # Real bias gradient: dS = P ⊙ (dO·Vᵀ − rowsum(dO⊙O)), reduced onto
        # the bias's broadcast shape. Computed with XLA ops from the saved
        # residuals — this materializes the [b,h,sq,sk] score block, the
        # unavoidable cost of a trainable dense bias (requested explicitly
        # via need_dbias; under jit, XLA additionally DCEs it when the
        # cotangent goes unused).
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = s + bias
        if causal:
            rows = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            cols = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
            s = jnp.where((rows + offset >= cols)[None, None], s, NEG_INF)
        p = _zero_masked_rows(jnp.exp(s - lse[..., 0:1]), lse[..., 0:1])
        dp = jnp.einsum("bhqd,bhkd->bhqk", g, v,
                        preferred_element_type=jnp.float32)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)
        ds = p * (dp - delta[..., None])
        # reduce over the bias's broadcast (size-1) dims
        red = tuple(i for i in (0, 1) if bias.shape[i] == 1)
        dbias = jnp.sum(ds, axis=red, keepdims=True) if red else ds
        dbias = dbias.astype(bias.dtype)
    # integer seed gets a float0 cotangent (jax's tangent type for ints)
    dseed = None if seed is None else np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pick_block(seq, pref):
    """Largest lane-aligned block <= pref that divides seq (0 if none)."""
    b = min(pref, seq)
    b -= b % LANES
    while b >= LANES:
        if seq % b == 0:
            return b
        b -= LANES
    return 0


def supports(seq_q, seq_k, head_dim=None,
             block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Static shape gate: sequence lengths must tile into 128-aligned blocks.
    ``head_dim`` is accepted for signature stability but unconstrained — the
    kernels use it unpadded (block dim equals the full array dim, which
    Mosaic accepts for any size)."""
    return _pick_block(seq_q, block_q) > 0 and _pick_block(seq_k, block_k) > 0


# ---------------------------------------------------------------------------
# length-masked (cached) forward — serving prefill / chunked prefill / verify
# ---------------------------------------------------------------------------

def _masked_tile_update(s, valid, v_ref, acc_ref, m_ref, l_ref):
    """One key block of the cached kernels' online softmax: the scores ``s``
    under ``valid`` folded into the running (max, denominator, output)."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = _zero_masked_rows(jnp.exp(s - m_new), m_new)
    l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _write_normalised(o_ref, acc_ref, l_ref):
    """The sweep's last step: a row that saw no key gives zeros."""
    l = l_ref[:, 0:1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _cached_fwd_kernel(klen_ref, q_ref, k_ref, v_ref, qpos_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, scale, block_k):
    """Online-softmax sweep with per-row validity from streamed positions:
    key slot j attends iff ``j <= q_pos[row]`` and ``j < kv_len[batch]`` —
    the LengthMask contract — so no dense bias ever reaches HBM.
    ``klen_ref`` is the whole ``(batch,)`` kv_len vector, scalar-prefetched
    into SMEM (a per-batch ``(1, 1)`` SMEM block does not lower at
    batch > 1: Mosaic wants the last two block dims tile-aligned)."""
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    s = jax.lax.dot_general(
        q_ref[0, 0], k_ref[0, 0],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qpos = qpos_ref[0, 0][:, 0:1]
    valid = (cols <= qpos) & (cols < klen_ref[pl.program_id(0)])
    _masked_tile_update(s, valid, v_ref, acc_ref, m_ref, l_ref)

    @pl.when(ki == nk - 1)
    def _finish():
        _write_normalised(o_ref, acc_ref, l_ref)


def _flash_cached_impl(q, k, v, qpos, klen, scale, block_q, block_k,
                       interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k

    # index maps take the scalar-prefetch ref as a trailing argument
    def qmap(bb, hh, qi, ki, klen_ref):
        return (bb, hh, qi, 0)

    def kmap(bb, hh, qi, ki, klen_ref):
        return (bb, hh, ki, 0)

    kernel = functools.partial(_cached_fwd_kernel, scale=scale,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        name="flash_cached_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qmap),
                pl.BlockSpec((1, 1, block_k, d), kmap),
                pl.BlockSpec((1, 1, block_k, d), kmap),
                pl.BlockSpec((1, 1, block_q, STAT_LANES),
                             lambda bb, hh, qi, ki, klen_ref: (bb, 0, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), qmap),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * sq * sk * d),
            bytes_accessed=int(2 * (q.size + k.size + v.size + q.size)),
            transcendentals=int(b * h * sq * sk),
        ),
    )(klen, q, k, v, qpos)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_cached(q, k, v, qpos, klen, scale, block_q, block_k, interpret):
    return _flash_cached_impl(q, k, v, qpos, klen, scale, block_q, block_k,
                              interpret)


def _flash_cached_vjp_fwd(q, k, v, qpos, klen, scale, block_q, block_k,
                          interpret):
    out = _flash_cached_impl(q, k, v, qpos, klen, scale, block_q, block_k,
                             interpret)
    return out, ()


def _flash_cached_vjp_bwd(scale, block_q, block_k, interpret, res, g):
    raise NotImplementedError(
        "flash_attention_cached is inference-only (serving holds no "
        "gradients through the KV cache); train-time length masking goes "
        "through the blockwise-scan sdpa path")


_flash_cached.defvjp(_flash_cached_vjp_fwd, _flash_cached_vjp_bwd)


# ---------------------------------------------------------------------------
# the same under a band (LengthMask.window): only the key blocks it meets
# ---------------------------------------------------------------------------

#: query and key rows of a tile of the banded sweep. Of the keys a query
#: block visits, ``window / (window + block)`` lie in the band of a row: at
#: a window of 512, half with 512-row tiles, a quarter with the 1024-row
#: tiles the unbanded kernel takes. On the v5e, 64 heads of 128 under a
#: window of 512: 6.06 ms at 8,192 positions against 6.99 with 1024-row
#: tiles and 11.01 with 256 (1.43 / 1.50 / 2.37 at 2,048; PERF.md, PR 36).
BAND_BLOCK = 512


def _banded_fwd_kernel(klen_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref,
                       qpos_ref, o_ref, acc_ref, m_ref, l_ref, *, scale,
                       block_k, window, nq):
    """:func:`_cached_fwd_kernel` with a lower bound: key slot j attends iff
    ``q_pos[row] - window < j <= q_pos[row]`` and ``j < kv_len[batch]``. The
    innermost grid axis counts the key blocks of ONE query block's band,
    from ``lo_ref[batch, q_block]`` (scalar-prefetched, as is the last one,
    ``hi_ref``): a step past ``hi`` neither fetches (its index map repeats
    the last block) nor computes."""
    bb, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ki = lo_ref[bb * nq + qi] + kj

    @pl.when(ki <= hi_ref[bb * nq + qi])
    def _visit():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = qpos_ref[0, 0][:, 0:1]
        valid = (cols <= qpos) & (cols > qpos - window) \
            & (cols < klen_ref[bb])
        _masked_tile_update(s, valid, v_ref, acc_ref, m_ref, l_ref)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finish():
        _write_normalised(o_ref, acc_ref, l_ref)


def band_blocks(block_q, block_k, window, nk):
    """Key blocks a query block's band can meet: its rows stand at
    ``block_q`` consecutive positions, so their bands cover ``block_q +
    window - 1`` keys, wherever those begin."""
    return min(nk, -(-(block_q + window - 2) // block_k) + 1)


def _flash_banded_impl(q, k, v, qpos, klen, lo, hi, scale, block_q, block_k,
                       window, interpret):
    """``lo, hi [batch, q blocks]``: the first and the last key block of
    each query block's band (``hi < lo``: none)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    nkb = band_blocks(block_q, block_k, window, nk)

    def qmap(bb, hh, qi, kj, klen_ref, lo_ref, hi_ref):
        return (bb, hh, qi, 0)

    def kmap(bb, hh, qi, kj, klen_ref, lo_ref, hi_ref):
        first = lo_ref[bb * nq + qi]
        last = jnp.maximum(hi_ref[bb * nq + qi], first)
        return (bb, hh, jnp.minimum(first + kj, last), 0)

    kernel = functools.partial(_banded_fwd_kernel, scale=scale,
                               block_k=block_k, window=window, nq=nq)
    return pl.pallas_call(
        kernel,
        name="flash_banded_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, nq, nkb),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d), qmap),
                pl.BlockSpec((1, 1, block_k, d), kmap),
                pl.BlockSpec((1, 1, block_k, d), kmap),
                pl.BlockSpec((1, 1, block_q, STAT_LANES),
                             lambda bb, hh, qi, kj, *_: (bb, 0, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d), qmap),
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
                pltpu.VMEM((block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * sq * nkb * block_k * d),
            bytes_accessed=int(2 * (2 * q.size + 2 * b * h * nq * nkb
                                    * block_k * d)),
            transcendentals=int(b * h * sq * nkb * block_k),
        ),
    )(klen, lo.reshape(b * nq), hi.reshape(b * nq), q, k, v, qpos)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_banded(q, k, v, qpos, klen, lo, hi, scale, block_q, block_k,
                  window, interpret):
    return _flash_banded_impl(q, k, v, qpos, klen, lo, hi, scale, block_q,
                              block_k, window, interpret)


def _flash_banded_vjp_fwd(q, k, v, qpos, klen, lo, hi, scale, block_q,
                          block_k, window, interpret):
    return _flash_banded_impl(q, k, v, qpos, klen, lo, hi, scale, block_q,
                              block_k, window, interpret), ()


def _flash_banded_vjp_bwd(scale, block_q, block_k, window, interpret, res,
                          g):
    return _flash_cached_vjp_bwd(scale, block_q, block_k, interpret, res, g)


_flash_banded.defvjp(_flash_banded_vjp_fwd, _flash_banded_vjp_bwd)


def supports_cached(seq_q, seq_k, head_dim=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Shape gate for the length-masked kernel: both sequence dims must tile
    into 128-aligned blocks. Decode's seq_q=1 and verify's few rows have a
    kernel of their own (``flash_decode.supports_decode``, asked first);
    what neither takes (a sub-lane prefill chunk) is left to the blockwise
    XLA scan, which is slow on the TPU and meant for backends without
    Pallas."""
    return _pick_block(seq_q, block_q) > 0 and _pick_block(seq_k, block_k) > 0


def flash_attention_cached(q, k, v, q_pos, kv_len=None, *, scale=None,
                           block_q=None, block_k=None, interpret=None,
                           window=None):
    """Length-masked flash attention over a static-shape KV cache.

    Args:
      q, k, v: ``(batch, seq, heads, head_dim)`` (paddle layout); ``k``/``v``
        are full cache buffers of ``max_len`` rows.
      q_pos: int32 ``(batch, seq_q)`` absolute cache position of each query
        row; key slot ``j`` attends iff ``j <= q_pos[b, i]``.
      kv_len: optional int32 ``(batch,)`` exclusive bound of valid cache
        rows (``None`` -> all ``seq_k`` rows writable-valid).
      window: optional static int: additionally ``j > q_pos[b, i] - window``
        (``LengthMask.window``). The banded kernel then sweeps, for each
        query block, the key blocks its rows' bands meet and no others; it
        sizes that sweep for rows at consecutive positions (a prompt, a
        chunk: :func:`band_blocks`).
      block_q, block_k: tile rows; default 1024, :data:`BAND_BLOCK` under a
        window.

    Forward-only: serving's prefill / chunked-prefill / speculative-verify
    steps. Returns ``(batch, seq_q, heads, head_dim)``.
    """
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    b, sq, h, d = q.shape
    sk = k.shape[1]
    pref = DEFAULT_BLOCK_Q if window is None else BAND_BLOCK
    block_q = _pick_block(sq, block_q or pref)
    block_k = _pick_block(sk, block_k or pref)
    if not (block_q and block_k):
        raise ValueError(
            f"flash_attention_cached needs 128-aligned sequence blocks: "
            f"seq_q={sq}, seq_k={sk}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    qpos = jnp.broadcast_to(
        jnp.asarray(q_pos, jnp.int32)[:, None, :, None],
        (b, 1, sq, STAT_LANES))
    klen = (jnp.full((b,), sk, jnp.int32) if kv_len is None
            else jnp.asarray(kv_len, jnp.int32).reshape(b))

    if window is not None:
        # each query block's first and last key block, from its rows'
        # positions (a row at -1 belongs to no request and sees nothing)
        pos = jnp.asarray(q_pos, jnp.int32).reshape(b, sq // block_q, block_q)
        last = jnp.minimum(jnp.max(pos, -1), klen[:, None] - 1)
        first = jnp.min(jnp.where(pos >= 0, pos, sk + window), -1) \
            - (window - 1)
        lo = jnp.clip(first, 0, sk - 1) // block_k
        hi = jnp.where(last >= 0, last // block_k, -1)

        def banded(qt, kt, vt, qpos, klen, lo, hi):
            return _flash_banded(qt, kt, vt, qpos, klen, lo, hi,
                                 float(scale), int(block_q), int(block_k),
                                 int(window), bool(interpret))

        out = batch_sharded(banded, (qt, kt, vt, qpos, klen, lo, hi),
                            (True,) * 7)
        return jnp.swapaxes(out, 1, 2)

    def call(qt, kt, vt, qpos, klen):
        return _flash_cached(qt, kt, vt, qpos, klen, float(scale),
                             int(block_q), int(block_k), bool(interpret))

    out = batch_sharded(call, (qt, kt, vt, qpos, klen), (True,) * 5)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(q, k, v, bias=None, *, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None, bias_grad=True,
                    dropout_p=0.0, dropout_seed=None):
    """Blockwise flash attention.

    Args:
      q, k, v: ``(batch, seq, heads, head_dim)`` (paddle layout).
      bias: optional additive mask (bool masks are converted), shape
        ``(sq, sk)`` or ``(B|1, H|1, sq, sk)``.
      bias_grad: whether the backward computes the real bias gradient
        (dS reduced onto the bias shape). Correct-by-default; pass False
        for constant masks to guarantee the O(sq·sk) score matrix is never
        materialized in the backward (the F.sdpa wrapper does this
        automatically from ``mask.stop_gradient``).
      causal: bottom-right-aligned causal mask (row r attends keys
        ``<= r + sk - sq``, matching softmax-attention convention).
      scale: softmax scale; default ``1/sqrt(head_dim)``.
      dropout_p: attention-probability dropout rate, applied IN-KERNEL via
        the TPU hardware PRNG (no HBM mask). Requires ``dropout_seed`` and a
        compiled TPU backend (no interpret-mode lowering exists for the
        hardware PRNG). Deterministic given the seed.
      dropout_seed: ``(2,)`` int32 array; fwd and bwd kernels re-derive the
        identical keep mask from it per (batch, head, q_block, k_block) tile.

    Returns ``(batch, seq_q, heads, head_dim)``.
    """
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    dropout_p = float(dropout_p)
    if dropout_p > 0.0:
        if interpret:
            raise ValueError(
                "in-kernel attention dropout needs the TPU hardware PRNG; "
                "no interpret-mode lowering exists (use the einsum path)"
            )
        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed")
        if bias is not None and bias_grad:
            raise ValueError(
                "bias_grad with attention dropout is unsupported: the XLA "
                "dbias recompute cannot regenerate the in-kernel PRNG mask "
                "(pass bias_grad=False for constant masks)"
            )
        if q.shape[2] >= 1024:
            # the per-tile seed fold packs the head index into 10 bits;
            # beyond that distinct heads would silently share keep-masks
            raise ValueError(
                f"in-kernel dropout supports < 1024 heads (got {q.shape[2]})"
            )
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape(2)
    else:
        seed = None
    b, sq, h, d = q.shape
    sk = k.shape[1]
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    if not (block_q and block_k):
        raise ValueError(
            f"flash_attention needs 128-aligned sequence blocks: seq_q={sq}, "
            f"seq_k={sk}"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    # head_dim needs no padding: the kernels' block last dim equals the full
    # array dim, which Mosaic accepts for any d (lanes padded only in VMEM)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if bias is not None:
        bias = jnp.asarray(bias)
        if bias.ndim not in (2, 4):
            raise ValueError(
                f"flash_attention mask must be (sq, sk) or (B|1, H|1, sq, sk); "
                f"got shape {bias.shape} — a 3-D mask is ambiguous"
            )
        if bias.dtype == jnp.bool_:
            bias = jnp.where(bias, 0.0, NEG_INF).astype(jnp.float32)
        else:
            bias = bias.astype(jnp.float32)
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)

    def call(qt, kt, vt, bias, seed):
        return _flash(qt, kt, vt, bias, seed, float(scale), bool(causal),
                      int(block_q), int(block_k), bool(interpret),
                      bool(bias_grad) and bias is not None, dropout_p)

    per_batch_bias = bias is not None and bias.shape[0] > 1
    out = batch_sharded(call, (qt, kt, vt, bias, seed),
                        (True, True, True, per_batch_bias, False),
                        seed_index=4)
    return jnp.swapaxes(out, 1, 2)

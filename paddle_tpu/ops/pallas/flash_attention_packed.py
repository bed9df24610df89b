"""Seq-major ("packed") flash attention: kernels that read the model's
native ``(batch, seq, heads*head_dim)`` activation layout directly.

Motivation (measured on v5e, GPT-2 124M b16 s1024): the layout-swapping
``flash_attention`` kernel forces ``(b,s,h,d) <-> (b,h,s,d)`` transposes
around every attention call — fwd q/k/v + out, and their autodiff duals —
which profiled at ~14% of device step time (24 standalone transpose ops,
~25 ms/step).  These kernels eliminate every one of those transposes: the
qkv-projection output feeds the kernel as-is and the kernel output feeds
the out-projection as-is.

Design: the grid is ``(batch, head_group, q_block, k_block)`` where a head
group is the set of heads whose packed lane range spans exactly 128 lanes
(2 heads at d=64, 1 at d=128, 4 at d=32 …).  Each q/k/v/o block is a
``(1, block, 128)`` slice of the packed array selected purely by the
BlockSpec index map — 128-lane alignment keeps Mosaic happy where per-head
``(1, block, 1, d)`` blocks and dynamic head indexing are rejected (tried;
see repo build notes) — and the kernel unrolls a static loop over the
heads inside the group, slicing each head's ``d``-wide lane range with
static offsets (Mosaic accepts static 64-aligned lane slices).  A VMEM-
budget bonus vs a full-embedding block: per-head softmax-stat tiles pad
their 8-lane minor dim to 128 lanes, so carrying all ``h`` heads in one
kernel instance costs ``h``× that padding; a head group carries at most
128/d of it (the full-E variant OOM'd scoped VMEM at 18 MB > 16 MB).

Same math as ``flash_attention.py`` (online softmax fwd; FlashAttention-2
split dq / dk+dv backward recomputing p from the saved logsumexp; in-kernel
hardware-PRNG dropout with the per-tile reseed scheme).  Supports causal
masking, an optional SHARED 2-D additive bias ``(sq, sk)`` (streamed
per-tile; per-batch/per-head 4-D biases route to the layout-swapping
kernel), and dropout.

Reference capability: fused attention fwd+bwd in
``paddle/fluid/operators/fused/fused_attention_op.cu`` / ``fmha_ref.h``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    LANES,
    NEG_INF,
    STAT_LANES,
    _causal_mask,
    _causal_run,
    _dropout_mask,
    _inject_none,
    _keep_bits,
    _pick_block,
    _zero_masked_rows,
)

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
DEFAULT_BWD_BLOCK = 512


def _group_width(d):
    """(heads_per_group, lane width of one group block)."""
    if d >= LANES:
        return (1, d) if d % LANES == 0 else (0, 0)
    return (LANES // d, LANES) if LANES % d == 0 else (0, 0)


def _tile_bias(b_ref, qi, ki, block_q, block_k, offset, causal):
    """Per-tile additive term, computed ONCE per kernel instance and shared
    by every head in the group (the causal iota pair costs real VPU time —
    paying it per head doubled the masking work at d=64)."""
    add = None if b_ref is None else b_ref[...].astype(jnp.float32)
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        neg = jnp.where(cols <= rows + offset, 0.0, NEG_INF)
        add = neg if add is None else add + neg
    return add


def _head_logits(q_ref, k_ref, add, j, d, scale):
    qh = q_ref[0, :, j * d:(j + 1) * d]
    kh = k_ref[0, :, j * d:(j + 1) * d]
    s = jax.lax.dot_general(
        qh, kh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    if add is not None:
        s = s + add
    return s


DROP_UNIT = 512  # canonical dropout tile: mask depends only on ABSOLUTE
                 # (row-unit, col-unit), so fwd and bwd may tile differently


def _drop(seed_ref, j, hpg, qi, ki, shape, dropout_p):
    """Keep-mask for this tile, assembled from canonical 512x512 units so
    the forward (1024-tiles, single-k fast path) and backward (512-tiles,
    VMEM headroom) regenerate identical bits; non-512-multiple blocks fall
    back to the tile-shape-keyed draw (the caller then unifies fwd/bwd
    block sizes)."""
    head = pl.program_id(1) * hpg + j
    bq, bk = shape
    if bq % DROP_UNIT or bk % DROP_UNIT:
        return _dropout_mask(seed_ref, qi, ki, shape, dropout_p, head=head)
    bb = pl.program_id(0)
    ru, cu = bq // DROP_UNIT, bk // DROP_UNIT
    rows = []
    for ur in range(ru):
        cols = []
        for uc in range(cu):
            aur = qi * ru + ur
            auc = ki * cu + uc
            pltpu.prng_seed(seed_ref[0] ^ (aur * 65536 + auc),
                            seed_ref[1] ^ (bb * 1024 + head))
            cols.append(_keep_bits((DROP_UNIT, DROP_UNIT), dropout_p))
        rows.append(cols[0] if cu == 1 else jnp.concatenate(cols, axis=1))
    return rows[0] if ru == 1 else jnp.concatenate(rows, axis=0)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, hpg, d, scale, causal, block_q,
                block_k, offset, dropout_p, single):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    if single:
        # nk == 1 (whole key range in one tile): plain softmax — no online
        # rescale, no m/l scratch round-trips, no acc rescale multiply
        add = _tile_bias(b_ref, qi, ki, block_q, block_k, offset, causal)
        for j in range(hpg):
            s = _head_logits(q_ref, k_ref, add, j, d, scale)
            m = jnp.max(s, axis=-1, keepdims=True)
            # fully-masked q rows (causal sq > sk): output 0, lse NEG_INF
            p = _zero_masked_rows(jnp.exp(s - m), m)
            l = jnp.sum(p, axis=-1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            if dropout_p > 0.0:
                keep = _drop(seed_ref, j, hpg, qi, ki, s.shape, dropout_p)
                p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
            vh = v_ref[0, :, j * d:(j + 1) * d]
            o_ref[0, :, j * d:(j + 1) * d] = (jax.lax.dot_general(
                p.astype(vh.dtype), vh,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            ) / l_safe).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0, j] = jnp.broadcast_to(
                    m + jnp.log(l_safe), lse_ref.shape[2:])
        return

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = _causal_run(qi, ki, block_q, block_k, offset) if causal else (ki >= 0)

    def _body(masked):
        add = _tile_bias(b_ref, qi, ki, block_q, block_k, offset, masked)
        # phase-separated over the head group: ALL QK matmuls first, then
        # the VPU softmax phase, then ALL PV matmuls — adjacent independent
        # MXU and VPU work lets Mosaic overlap units instead of serializing
        # QK -> softmax -> PV per head (the per-head chain idles the MXU
        # through every softmax)
        for j in range(hpg):
            s = _head_logits(q_ref, k_ref, add, j, d, scale)
            m_prev = m_ref[j][:, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if masked or b_ref is not None:
                # rows fully masked SO FAR keep l = 0 so _finish emits
                # output 0 / lse NEG_INF (same contract as the single
                # path). A shared bias can fully mask rows in ANY tile
                # (padding masks), so the guard stays whenever a bias is
                # streamed; pure-causal interior tiles skip it (their rows
                # always have visible keys)
                p = _zero_masked_rows(p, m_new)
            l_new = l_ref[j][:, 0:1] * alpha + jnp.sum(p, axis=-1,
                                                       keepdims=True)
            if dropout_p > 0.0:
                keep = _drop(seed_ref, j, hpg, qi, ki, s.shape, dropout_p)
                p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
            vh = v_ref[0, :, j * d:(j + 1) * d]
            acc_ref[0, :, j * d:(j + 1) * d] = (
                acc_ref[0, :, j * d:(j + 1) * d] * alpha
                + jax.lax.dot_general(
                    p.astype(vh.dtype), vh,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            m_ref[j] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[j] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    if causal:
        # interior/boundary split: tiles fully below the diagonal skip the
        # per-element iota/compare/select masking — the online softmax at
        # long s is VPU-bound, and interior tiles dominate (profiled 2x
        # forward-kernel speedup at s=8192)
        full = ki * block_k + block_k - 1 <= qi * block_q + offset

        @pl.when(run & full)
        def _interior():
            _body(False)

        @pl.when(run & jnp.logical_not(full))
        def _boundary():
            _body(True)
    else:
        @pl.when(run)
        def _all():
            _body(False)

    @pl.when(ki == nk - 1)
    def _finish():
        for j in range(hpg):
            l = l_ref[j][:, 0:1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, j * d:(j + 1) * d] = (
                acc_ref[0, :, j * d:(j + 1) * d] / l_safe
            ).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0, j] = jnp.broadcast_to(
                    m_ref[j][:, 0:1] + jnp.log(l_safe), lse_ref.shape[2:]
                )


def _bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, b_ref, do_ref, o_ref,
                      lse_ref, dq_ref, dk_ref, dv_ref,
                      dq_sc, dk_acc, dv_acc, *, hpg, d, scale, causal,
                      block_q, block_k, offset, dropout_p):
    """Single-sweep backward: grid (b, group, K block, Q block). dk/dv
    accumulate in per-k-block scratch over the inner q sweep (written once
    per k block); dq accumulates in a scratch slab holding EVERY q block
    (``(nq, block_q, width)`` f32 — VMEM persists across the whole grid),
    written through to the revisited dq output each step so the LAST
    write (ki == nk-1) carries the full sum in both compiled and
    interpret modes. The payoff over split dq / dkv kernels: s,
    p=exp(s-lse), dp and ds are computed ONCE instead of twice — measured
    on v5e they dominate the backward. The slab caps supported seq_q
    (~16k at 512 blocks); longer sequences route to the layout-swapping
    kernel."""
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(ki == 0)
    def _dq_init():
        dq_sc[qi] = jnp.zeros_like(dq_sc[qi])

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _causal_run(qi, ki, block_q, block_k, offset) if causal else (qi >= 0)

    def _body(masked):
        add = _tile_bias(b_ref, qi, ki, block_q, block_k, offset, masked)
        for j in range(hpg):
            s = _head_logits(q_ref, k_ref, add, j, d, scale)
            lse_j = lse_ref[0, j][:, 0:1]
            p = jnp.exp(s - lse_j)
            if masked or b_ref is not None:
                # fully-masked rows saved lse == NEG_INF: zero gradients
                # (bias-masked rows can appear in any tile — see fwd)
                p = _zero_masked_rows(p, lse_j)
            doh = do_ref[0, :, j * d:(j + 1) * d]
            oh = o_ref[0, :, j * d:(j + 1) * d]
            delta = jnp.sum(
                doh.astype(jnp.float32) * oh.astype(jnp.float32),
                axis=-1, keepdims=True,
            )
            vh = v_ref[0, :, j * d:(j + 1) * d]
            dp = jax.lax.dot_general(
                doh, vh,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
            if dropout_p > 0.0:
                keep = _drop(seed_ref, j, hpg, qi, ki, s.shape, dropout_p)
                inv = 1.0 / (1.0 - dropout_p)
                p_d = jnp.where(keep, p * inv, 0.0)
                dp = jnp.where(keep, dp * inv, 0.0)
            else:
                p_d = p
            dv_acc[0, :, j * d:(j + 1) * d] = (
                dv_acc[0, :, j * d:(j + 1) * d] + jax.lax.dot_general(
                    p_d.astype(doh.dtype), doh,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            ds = p * (dp - delta) * scale
            qh = q_ref[0, :, j * d:(j + 1) * d]
            dk_acc[0, :, j * d:(j + 1) * d] = (
                dk_acc[0, :, j * d:(j + 1) * d] + jax.lax.dot_general(
                    ds.astype(qh.dtype), qh,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            kh = k_ref[0, :, j * d:(j + 1) * d]
            dq_sc[qi, :, j * d:(j + 1) * d] = (
                dq_sc[qi, :, j * d:(j + 1) * d] + jax.lax.dot_general(
                    ds.astype(kh.dtype), kh,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )

    if causal:
        # interior/boundary split (see _fwd_kernel): only tiles crossing
        # the diagonal pay the per-element masking and lse row-guard
        full = ki * block_k + block_k - 1 <= qi * block_q + offset

        @pl.when(run & full)
        def _interior():
            _body(False)

        @pl.when(run & jnp.logical_not(full))
        def _boundary():
            _body(True)
    else:
        @pl.when(run)
        def _all():
            _body(False)

    # write-through every step: intermediate write-backs are overwritten by
    # the revisit at the next ki; the ki == nk-1 write is the full sum
    dq_ref[0] = dq_sc[qi].astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[0].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[0].astype(dv_ref.dtype)


def _seed_spec(seed):
    return None if seed is None else pl.BlockSpec(memory_space=pltpu.SMEM)


def _bias_spec(bias, block_q, block_k, kv_major=False):
    """Shared 2-D (sq, sk) bias, streamed per (q_block, k_block) tile."""
    if bias is None:
        return None
    if kv_major:
        return pl.BlockSpec((block_q, block_k),
                            lambda bb, hg, ki, qi: (qi, ki))
    return pl.BlockSpec((block_q, block_k), lambda bb, hg, qi, ki: (qi, ki))


def _check(q, k, v, h):
    b, sq, e = q.shape
    bk, sk, ek = k.shape
    assert v.shape == k.shape, (v.shape, k.shape)
    assert (bk, ek) == (b, e), (q.shape, k.shape)
    assert e % h == 0, (e, h)
    return b, sq, sk, e // h


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, bias, seed, h, scale, causal, block_q, block_k,
           interpret, dropout_p, bwd_block):
    return _fwd_impl(q, k, v, bias, seed, h, scale, causal, block_q, block_k,
                     interpret, dropout_p, need_stats=False)


def _fwd_impl(q, k, v, bias, seed, h, scale, causal, block_q, block_k,
              interpret, dropout_p, need_stats=True):
    b, sq, sk, d = _check(q, k, v, h)
    hpg, width = _group_width(d)
    ng = h // hpg
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq

    def qmap(bb, hg, qi, ki):
        return (bb, qi, hg)

    def kmap(bb, hg, qi, ki):
        return (bb, ki, hg)

    in_specs = [
        _seed_spec(seed),
        pl.BlockSpec((1, block_q, width), qmap),
        pl.BlockSpec((1, block_k, width), kmap),
        pl.BlockSpec((1, block_k, width), kmap),
        _bias_spec(bias, block_q, block_k),
    ]
    kernel = functools.partial(
        _fwd_kernel, hpg=hpg, d=d, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, dropout_p=dropout_p,
        single=(nk == 1),
    )
    # full kernel signature: (seed, q, k, v, bias, o, lse, <scratch>)
    missing = ([0] if seed is None else []) + ([4] if bias is None else [])
    if need_stats:
        out_specs = [
            pl.BlockSpec((1, block_q, width), qmap),
            pl.BlockSpec((1, hpg, block_q, STAT_LANES),
                         lambda bb, hg, qi, ki: (bb, hg, qi, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, STAT_LANES), jnp.float32),
        ]
    else:
        missing.append(6)
        out_specs = pl.BlockSpec((1, block_q, width), qmap)
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if missing:
        kernel = _inject_none(kernel, *missing)
    return pl.pallas_call(
        kernel,
        name="flash_packed_fwd",
        grid=(b, ng, nq, nk),
        in_specs=[s for s in in_specs if s is not None],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1, block_q, width), jnp.float32),
            pltpu.VMEM((hpg, block_q, STAT_LANES), jnp.float32),
            pltpu.VMEM((hpg, block_q, STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * sq * sk * d * (0.5 if causal else 1.0)),
            bytes_accessed=int(2 * (q.size + k.size + v.size + q.size)),
            transcendentals=int(b * h * sq * sk),
        ),
    )(*[x for x in (seed, q, k, v, bias) if x is not None])


def _fwd(q, k, v, bias, seed, h, scale, causal, block_q, block_k, interpret,
         dropout_p, bwd_block):
    out, lse = _fwd_impl(q, k, v, bias, seed, h, scale, causal, block_q,
                         block_k, interpret, dropout_p, need_stats=True)
    return out, (q, k, v, bias, seed, out, lse)


def _bwd(h, scale, causal, block_q, block_k, interpret, dropout_p, bwd_block,
         res, g):
    q, k, v, bias, seed, out, lse = res
    b, sq, sk, d = _check(q, k, v, h)
    hpg, width = _group_width(d)
    ng = h // hpg
    # backward streams q/k/v + do/o + grads (~3x fwd working set): its own,
    # smaller block size keeps it inside the 16 MB scoped-VMEM budget while
    # the forward runs 1024-wide tiles
    block_q = _pick_block(sq, bwd_block)
    block_k = _pick_block(sk, bwd_block)
    nq, nk = sq // block_q, sk // block_k
    offset = sk - sq

    def qmap(bb, hg, ki, qi):
        return (bb, qi, hg)

    def kmap(bb, hg, ki, qi):
        return (bb, ki, hg)

    kernel = functools.partial(
        _bwd_fused_kernel, hpg=hpg, d=d, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, offset=offset, dropout_p=dropout_p,
    )
    # full signature: (seed, q, k, v, bias, do, o, lse, dq, dk, dv,
    #                  <dq slab, dk acc, dv acc scratch>)
    missing = ([0] if seed is None else []) + ([4] if bias is None else [])
    if missing:
        kernel = _inject_none(kernel, *missing)
    in_specs = [
        _seed_spec(seed),
        pl.BlockSpec((1, block_q, width), qmap),       # q
        pl.BlockSpec((1, block_k, width), kmap),       # k
        pl.BlockSpec((1, block_k, width), kmap),       # v
        _bias_spec(bias, block_q, block_k, kv_major=True),
        pl.BlockSpec((1, block_q, width), qmap),       # do
        pl.BlockSpec((1, block_q, width), qmap),       # o
        pl.BlockSpec((1, hpg, block_q, STAT_LANES),
                     lambda bb, hg, ki, qi: (bb, hg, qi, 0)),  # lse
    ]
    operands = [x for x in (seed, q, k, v, bias) if x is not None]
    operands += [g, out, lse]
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_packed_bwd",
        grid=(b, ng, nk, nq),
        in_specs=[sp for sp in in_specs if sp is not None],
        out_specs=[
            pl.BlockSpec((1, block_q, width), qmap),
            pl.BlockSpec((1, block_k, width), kmap),
            pl.BlockSpec((1, block_k, width), kmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, block_q, width), jnp.float32),
            pltpu.VMEM((1, block_k, width), jnp.float32),
            pltpu.VMEM((1, block_k, width), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)

    if bias is None:
        dbias = None
    else:
        # shared constant 2-D masks only (router guarantees stop_gradient)
        dbias = jnp.zeros_like(bias)
    dseed = None if seed is None else np.zeros(seed.shape, jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash.defvjp(_fwd, _bwd)


MAX_BWD_SLAB_BYTES = 10 * 2 ** 20  # dq scratch slab cap (VMEM budget)


def supports(seq_q, seq_k, num_heads, embed_dim,
             block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Shape gate: lane-tileable seqs; head_dim must pack into 128-lane
    groups (d a divisor or multiple of 128) with the head count divisible
    by the group size; seq_q bounded by the backward's resident dq slab
    (~16k at 128-lane groups) — longer routes to the layout-swapping
    kernel (or ring attention)."""
    if embed_dim % num_heads:
        return False
    d = embed_dim // num_heads
    hpg, width = _group_width(d)
    if not hpg or num_heads % hpg:
        return False
    if seq_q * width * 4 > MAX_BWD_SLAB_BYTES:
        return False
    return _pick_block(seq_q, block_q) > 0 and _pick_block(seq_k, block_k) > 0


def flash_attention_packed(q, k, v, num_heads, bias=None, *, causal=False,
                           scale=None, block_q=DEFAULT_BLOCK_Q,
                           block_k=DEFAULT_BLOCK_K,
                           bwd_block=DEFAULT_BWD_BLOCK, interpret=None,
                           dropout_p=0.0, dropout_seed=None):
    """Flash attention over packed ``(batch, seq, heads*head_dim)`` arrays.

    Zero layout changes: inputs and output stay seq-major, exactly as the
    qkv projection produces them and the out-projection consumes them.
    ``bias`` (optional) must be a SHARED 2-D ``(sq, sk)`` additive mask
    (constant — no bias gradient path); use :func:`flash_attention` for
    per-batch/per-head biases.
    """
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    b, sq, e = q.shape
    sk = k.shape[1]
    h = int(num_heads)
    d = e // h
    dropout_p = float(dropout_p)
    if dropout_p > 0.0:
        if interpret:
            raise ValueError(
                "in-kernel attention dropout needs the TPU hardware PRNG; "
                "no interpret-mode lowering exists (use the einsum path)"
            )
        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed")
        if h >= 1024:
            raise ValueError(
                f"in-kernel dropout supports < 1024 heads (got {h})"
            )
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape(2)
    else:
        seed = None
    if (block_q == DEFAULT_BLOCK_Q and block_k == DEFAULT_BLOCK_K
            and sq == sk and sq > 1024):
        if sq <= 4096:
            # measured v5e routing (GPT-2 cfg): at mid sequence lengths the
            # single-k-tile fast path (whole key range, q blocks shrunk to
            # keep the f32 logits tile at 4 MB) beats the online-softmax
            # multi-tile path — no m/l scratch round-trips or rescale
            # rounds (s=2048: 100.5k vs 96.1k tok/s; s=4096: 81.8k vs
            # 81.0k).
            block_q, block_k = max(2 ** 20 // sq, 128), sq
        else:
            # long sequences: keep the causal-skipping multi-tile path but
            # at (512, 2048) tiles — same 4 MB logits area, 4x fewer
            # online-softmax rescale rounds per q row than 1024x1024
            # (s=8192 b4: 61.4k vs 60.1k tok/s, 51.3% vs 50.3% MFU)
            block_q, block_k = 512, 2048
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    bwd_block = _pick_block(sq, bwd_block) or block_q
    if dropout_p > 0.0 and (block_q % DROP_UNIT or block_k % DROP_UNIT
                            or bwd_block % DROP_UNIT):
        # non-canonical tile sizes key the PRNG mask on tile SHAPE: the
        # backward must then re-tile exactly like the forward (canonical
        # 512-unit draws lift this, letting fwd keep 1024 single-k tiles
        # while bwd runs its 512 VMEM-friendly ones). The unified block
        # must divide BOTH seq dims — pick from gcd(sq, sk), never the raw
        # min (which could silently truncate the key range when sq != sk)
        u = min(x for x in (block_q, block_k, bwd_block) if x)
        u = _pick_block(math.gcd(sq, sk), u)
        if not u:
            raise ValueError(
                f"dropout tiling: no common 128-aligned block divides both "
                f"seq_q={sq} and seq_k={sk}"
            )
        block_q = block_k = bwd_block = u
    hpg_chk, width_chk = _group_width(e // h if h else 1)
    if hpg_chk and sq * width_chk * 4 > MAX_BWD_SLAB_BYTES:
        raise ValueError(
            f"flash_attention_packed: seq_q={sq} exceeds the backward dq "
            f"slab budget (~{MAX_BWD_SLAB_BYTES // (width_chk * 4)} rows at "
            f"this head width) — use the layout-swapping flash_attention "
            f"or ring attention for longer sequences"
        )
    if not supports(sq, sk, h, e, block_q or 1, block_k or 1) \
            or not (block_q and block_k):
        raise ValueError(
            f"flash_attention_packed needs 128-aligned seq blocks and "
            f"128-lane head groups: seq_q={sq}, seq_k={sk}, e={e}, h={h}"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if bias is not None:
        bias = jnp.asarray(bias)
        if bias.ndim != 2 or bias.shape != (sq, sk):
            raise ValueError(
                f"packed kernel takes a shared (sq, sk) bias; got "
                f"{bias.shape} — use flash_attention for 4-D biases"
            )
        if bias.dtype == jnp.bool_:
            bias = jnp.where(bias, 0.0, NEG_INF).astype(jnp.float32)
        else:
            bias = bias.astype(jnp.float32)

    def call(q, k, v, bias, seed):
        return _flash(q, k, v, bias, seed, h, float(scale), bool(causal),
                      int(block_q), int(block_k), bool(interpret), dropout_p,
                      int(bwd_block))

    return batch_sharded(call, (q, k, v, bias, seed),
                         (True, True, True, False, False), seed_index=4)

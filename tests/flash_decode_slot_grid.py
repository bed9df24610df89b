"""The decode attention kernel as it was until PR 35, kept as an oracle: a
static grid ``(batch, kv_block)`` that visits every slot, clamps a slot's
dead blocks to its last live one and a slot with no valid key to its block 0.
``ops/pallas/flash_decode.py`` visits the live (slot, block) pairs alone and
must give a live slot this kernel's output bit for bit (same blocks, same
order, same arithmetic)."""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.flash_attention import (LANES, NEG_INF,
                                                   _zero_masked_rows)
from paddle_tpu.ops.pallas.flash_decode import ROWS, _live_bound


def _kernel(qpos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, block_k, sq):
    f32 = jnp.float32
    bb, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_k <= _live_bound(qpos_ref, bb, sq))
    def _body():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32) * scale
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, ROWS, 1), 1)
        qpos = jnp.full((1, ROWS, 1), -1, jnp.int32)
        for i in range(sq):
            qpos = jnp.where(row == i, qpos_ref[bb * sq + i], qpos)
        s = jnp.where(cols <= qpos, s, NEG_INF)
        m_prev = m_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = _zero_masked_rows(jnp.exp(s - m_new), m_new)
        l_new = l_ref[:, :, 0:1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=f32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[:, :, 0:1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def flash_attention_decode_slot_grid(q, k, v, q_pos, kv_len=None, *,
                                     block_k=128, interpret=True):
    """Same arguments and result as ``flash_attention_decode``."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qpos = jnp.minimum(jnp.asarray(q_pos, jnp.int32), sk - 1)
    if kv_len is not None:
        qpos = jnp.minimum(qpos, jnp.asarray(kv_len, jnp.int32)[:, None] - 1)
    qt = jnp.pad(jnp.swapaxes(q, 1, 2).astype(k.dtype),
                 ((0, 0), (0, 0), (0, ROWS - sq), (0, 0)))

    def qmap(bb, ki, qpos_ref):
        return (bb, 0, 0, 0)

    def kvmap(bb, ki, qpos_ref):
        last = jnp.maximum(_live_bound(qpos_ref, bb, sq), 0) // block_k
        return (bb, 0, 0, jnp.minimum(ki, last))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), block_k=block_k,
                          sq=sq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, sk // block_k),
            in_specs=[pl.BlockSpec((1, h, ROWS, d), qmap),
                      pl.BlockSpec((1, h, d, block_k), kvmap),
                      pl.BlockSpec((1, h, d, block_k), kvmap)],
            out_specs=pl.BlockSpec((1, h, ROWS, d), qmap),
            scratch_shapes=[pltpu.VMEM((h, ROWS, d), jnp.float32),
                            pltpu.VMEM((h, ROWS, LANES), jnp.float32),
                            pltpu.VMEM((h, ROWS, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        interpret=interpret,
    )(qpos.reshape(b * sq), qt, jnp.transpose(k, (0, 2, 3, 1)),
      jnp.transpose(v, (0, 2, 3, 1)))
    return jnp.swapaxes(out[:, :, :sq], 1, 2).astype(q.dtype)

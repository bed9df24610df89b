"""Decode-shaped attention over the static-shape KV cache, as a Pallas TPU
kernel: a handful of query rows per batch entry (decode's one, speculative
verify's ``spec_k + 1``) against that entry's whole cache row.

The 128-row-block kernels (``flash_attention_cached``) cannot take such a
query, and the blockwise XLA scan that took it instead copies every layer's
K and V to float32, re-lays them out three times and visits all ``max_len``
positions whatever the lengths say (168 of the 195 ms of a GPT-2 large
decode step on the v5e). This kernel reads the cache ONCE, in its own dtype
and in the layout the TPU keeps it in, and only the blocks that hold a valid
key: of a live slot those up to its position, of a dead slot none. A slot is
dead when none of its query rows has a valid key (``q_pos < 0``): that is
how the serving engine marks a slot that holds no request, whose length is
stale and means nothing (``KVCache``), and its output rows are zeros:

* **layout.** XLA:TPU stores a ``(batch, max_len, heads, head_dim)`` array
  whose ``head_dim`` is under the 128 lanes with ``max_len`` minor:
  physically ``(batch, heads, head_dim, max_len)``, each head's K already
  transposed (``{1,3,2,0:T(8,128)(2,1)}`` in the compiled decode step; the
  row-major ``(b, s, h*d)`` view is a full copy there, not a free reshape).
  The kernel takes exactly that view, so the ``transpose`` in
  :func:`flash_attention_decode` is a bitcast and no cache byte moves before
  the kernel's own DMA. For a shape the TPU lays out otherwise the result is
  the same and XLA pays one copy. Two kernels share this view: the decode
  step's row write (``kv_row_write.py``) hands the buffers over in it,
  aliased onto the donated cache, and this kernel reads them as written,
  with nothing but bitcasts between parameter, write, attention and result
  (``tools/tpu_aot_preflight.py`` compiles that program and
  ``tests/test_pallas_tpu_compile.py`` holds it to no copy of a buffer);
* the grid walks a list of the live (slot, block) pairs, slot by slot and
  each slot's blocks in order (:func:`live_pairs`, built in XLA from the
  positions alone, once a step; scalar-prefetched with the positions). The
  grid keeps its static length ``batch x blocks``: the steps past the list
  repeat the last pair, so no block index changes, nothing is fetched or
  written back, and the body is skipped. On the v5e such a step costs 0.11
  us and a fetched block 2.0 us (PERF.md, PR 35); a grid that ends with the
  list (a dynamic bound) was measured too and costs each call ~40 us more
  than the empty steps it saves;
* all heads of a batch entry in one grid step, as one batched product
  ``[h, rows, d] x [h, d, block_k]`` for the scores and one
  ``[h, rows, block_k] x [h, d, block_k]^T`` for ``P.V``. The kernel is
  bound by the bytes of the live cache;
* products of cache-dtype operands accumulate in float32; running max,
  denominator and accumulator stay float32; the probabilities are cast to
  the cache's dtype for ``P.V`` exactly as ``_cached_fwd_kernel`` does.

Forward-only (serving holds no gradients through the cache).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES, NEG_INF, _pick_block, _zero_masked_rows

#: query rows of one cache row the kernel takes (decode 1; verify spec_k + 1),
#: padded to one sublane tile
ROWS = 8
#: K and V tiles, double-buffered, may hold this much of VMEM
TILE_BYTES = 8 << 20
#: preferred KV block. Serving's mean live length is a few hundred
#: positions, so a wider block reads more dead positions per row and a
#: narrower one pays more grid steps
BLOCK_K = 256


def _decode_block(seq_k, width, itemsize):
    """KV block from the shapes alone: the largest 128-aligned divisor of
    ``seq_k`` up to ``BLOCK_K`` whose four tiles fit ``TILE_BYTES`` (0 if
    none)."""
    fit = TILE_BYTES // (4 * width * itemsize)
    return _pick_block(seq_k, min(BLOCK_K, fit))


def supports_decode(seq_q, seq_k, heads, head_dim, itemsize=2):
    """Shape gate: at most ``ROWS`` query rows and a 128-aligned KV block
    whose tiles fit VMEM."""
    return (1 <= seq_q <= ROWS
            and _decode_block(seq_k, heads * head_dim, itemsize) > 0)


def _live_bound(qpos_ref, bb, sq):
    """Largest query position of batch entry ``bb`` (scalars in SMEM)."""
    return functools.reduce(
        jnp.maximum, [qpos_ref[bb * sq + i] for i in range(sq)])


def live_pairs(qpos, block_k, n_blocks):
    """The (slot, block) pairs the kernel visits, slot by slot and each
    slot's blocks in order, built in XLA from ``qpos`` ``(b, sq)`` alone (the
    same for every layer of a step: XLA keeps one copy). A slot whose largest
    position is ``p >= 0`` owns blocks ``0 .. p // block_k``; a slot with no
    valid key owns none. Returns ``(slot, block, count)``: two int32 lists of
    the static length ``b * n_blocks`` and the number of pairs; entries past
    ``count`` repeat the last pair (the last slot's block 0 when there is
    none), so a grid step there changes no block index and fetches
    nothing."""
    b = qpos.shape[0]
    bound = jnp.max(qpos, axis=1)
    owned = jnp.where(bound >= 0, bound // block_k + 1, 0)
    ends = jnp.cumsum(owned)
    count = ends[-1]
    step = jnp.minimum(jnp.arange(b * n_blocks, dtype=jnp.int32),
                       jnp.maximum(count - 1, 0))
    # the slots that end at or before a step are those before its own
    before = ends[None, :] <= step[:, None]
    slot = jnp.minimum(jnp.sum(before, axis=1), b - 1)
    block = step - jnp.sum(jnp.where(before, owned[None, :], 0), axis=1)
    return (slot.astype(jnp.int32), block.astype(jnp.int32),
            count.astype(jnp.int32).reshape(1))


def _decode_fwd_kernel(qpos_ref, slot_ref, block_ref, count_ref, q_ref,
                       k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale,
                       block_k, sq):
    """Online-softmax sweep over the live (slot, block) pairs, every head at
    once: grid step ``t`` holds block ``block_ref[t]`` of slot
    ``slot_ref[t]``. ``q_ref`` is ``(1, h, ROWS, d)``, ``k_ref``/``v_ref``
    are ``(1, h, d, block_k)``. Key slot ``c`` attends to query row ``i``
    iff ``c <= q_pos[batch, i]``; the padding rows ``i >= sq`` attend to
    nothing. A slot's output block is written with its last pair; a slot
    that owns no pair is never visited and its rows are the caller's to
    zero."""
    f32 = jnp.float32
    t = pl.program_id(0)
    bb, ki = slot_ref[t], block_ref[t]
    listed = t < count_ref[0]

    @pl.when(listed & (ki == 0))
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(listed)
    def _body():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32) * scale          # [h, ROWS, block_k]
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
            preferred_element_type=f32)                  # [h, ROWS, d]
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(listed & (ki == _live_bound(qpos_ref, bb, sq) // block_k))
    def _finish():
        l = l_ref[:, :, 0:1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_decode(q, k, v, qpos, scale, block_k, interpret):
    """``q`` ``(b, h, ROWS, d)``, ``k``/``v`` ``(b, h, d, sk)``, ``qpos``
    ``(b, sq)``; returns ``(b, h, ROWS, d)``, zeros for a slot with no valid
    key."""
    b, h, _, d = q.shape
    sk = k.shape[3]
    sq = qpos.shape[1]
    slot, block, count = live_pairs(qpos, block_k, sk // block_k)

    # index maps take the scalar-prefetch refs as trailing arguments
    def qmap(t, qpos_ref, slot_ref, block_ref, count_ref):
        return (slot_ref[t], 0, 0, 0)

    def kvmap(t, qpos_ref, slot_ref, block_ref, count_ref):
        return (slot_ref[t], 0, 0, block_ref[t])

    kernel = functools.partial(_decode_fwd_kernel, scale=scale,
                               block_k=block_k, sq=sq)
    out = pl.pallas_call(
        kernel,
        name="flash_decode_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slot.shape[0],),
            in_specs=[
                pl.BlockSpec((1, h, ROWS, d), qmap),
                pl.BlockSpec((1, h, d, block_k), kvmap),
                pl.BlockSpec((1, h, d, block_k), kvmap),
            ],
            out_specs=pl.BlockSpec((1, h, ROWS, d), qmap),
            scratch_shapes=[
                pltpu.VMEM((h, ROWS, d), jnp.float32),
                pltpu.VMEM((h, ROWS, LANES), jnp.float32),
                pltpu.VMEM((h, ROWS, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * ROWS * sk * d),
            bytes_accessed=int((k.size + v.size) * k.dtype.itemsize
                               + 2 * q.size * q.dtype.itemsize),
            transcendentals=int(b * h * ROWS * sk),
        ),
    )(qpos.reshape(b * sq), slot, block, count, q, k, v)
    # the rows of a slot the grid never visited are whatever the buffer held
    dead = jnp.max(qpos, axis=1) < 0
    return jnp.where(dead[:, None, None, None], jnp.zeros((), out.dtype), out)


def _flash_decode_vjp_fwd(q, k, v, qpos, scale, block_k, interpret):
    # the op layer asks for a vjp whenever a weight upstream wants gradients;
    # serving never pulls it
    return _flash_decode(q, k, v, qpos, scale, block_k, interpret), ()


def _flash_decode_vjp_bwd(scale, block_k, interpret, res, g):
    raise NotImplementedError(
        "flash_attention_decode is inference-only (serving holds no "
        "gradients through the KV cache); train-time length masking goes "
        "through the blockwise-scan sdpa path")


_flash_decode.defvjp(_flash_decode_vjp_fwd, _flash_decode_vjp_bwd)

#: Under one jit, so that a model's layers share ONE trace and ONE lowering
#: of the kernel: a decode step calls it once a layer with the same shapes,
#: and lowering the body to Mosaic 36 times over is 8 s of every process's
#: set-up on a v5e host that no compile cache gives back.
_decode_call = jax.jit(_flash_decode, static_argnums=(4, 5, 6))


def flash_attention_decode(q, k, v, q_pos, kv_len=None, *, scale=None,
                           block_k=None, interpret=None):
    """Length-masked attention of a few query rows per batch entry over a
    static-shape KV cache, reading only the live blocks.

    Args:
      q: ``(batch, seq_q, heads, head_dim)`` with ``seq_q <= ROWS``.
      k, v: ``(batch, max_len, heads, head_dim)`` full cache buffers.
      q_pos: int32 ``(batch, seq_q)`` absolute cache position of each query
        row; key slot ``j`` attends iff ``j <= q_pos[b, i]``.
      kv_len: optional int32 ``(batch,)`` exclusive bound of valid rows.

    Rows with no valid key (``q_pos < 0`` or ``kv_len == 0``) give zeros.
    Returns ``(batch, seq_q, heads, head_dim)``.
    """
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if block_k is None:
        block_k = _decode_block(sk, h * d, k.dtype.itemsize)
    if not (1 <= sq <= ROWS and block_k and sk % block_k == 0):
        raise ValueError(
            f"flash_attention_decode needs seq_q <= {ROWS} and a 128-aligned "
            f"KV block: seq_q={sq}, seq_k={sk}, block_k={block_k}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # one bound per query row: the last key slot it may read
    qpos = jnp.minimum(jnp.asarray(q_pos, jnp.int32), sk - 1)
    if kv_len is not None:
        qpos = jnp.minimum(qpos, jnp.asarray(kv_len, jnp.int32)[:, None] - 1)
    qt = jnp.pad(jnp.swapaxes(q, 1, 2).astype(k.dtype),
                 ((0, 0), (0, 0), (0, ROWS - sq), (0, 0)))

    def call(qt, kt, vt, qpos):
        return _decode_call(qt, kt, vt, qpos, float(scale), int(block_k),
                            bool(interpret))

    out = batch_sharded(
        call, (qt, jnp.transpose(k, (0, 2, 3, 1)),
               jnp.transpose(v, (0, 2, 3, 1)), qpos), (True,) * 4)
    return jnp.swapaxes(out[:, :, :sq], 1, 2).astype(q.dtype)

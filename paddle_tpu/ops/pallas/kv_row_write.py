"""The decode step's K/V row write, in place, as a Pallas TPU kernel.

``DecodeView.update`` writes one new row a slot (speculative verify: up to
``ROWS``) into each layer's ``(batch, max_len, heads, head_dim)`` buffers at
that slot's own position. XLA:TPU keeps such a buffer, while ``head_dim`` is
under the 128 lanes, physically as ``(batch, heads, head_dim, max_len)`` with
``max_len`` on the lanes (``flash_decode.py`` says how that was found), so
one token's row is ``heads * head_dim`` elements in as many different lane
rows, and the vmapped ``dynamic_update_slice`` compiles to a ``while`` of one
scalar-row update a slot: 0.29 ms a buffer, 72 buffers, 68% of a GPT-2 large
decode step on the v5e.

This kernel moves what must move: of each slot, the one 128-lane column of
tiles that holds its position.

* it takes the buffer in the view the TPU keeps, ``(batch, heads * head_dim,
  max_len)`` (the transpose and the reshape in :func:`kv_row_write` are
  bitcasts there; for a shape the TPU lays out otherwise the result is the
  same and XLA pays a copy), and the per-slot positions scalar-prefetched;
* grid ``(batch, visits)``; the cache block is ``(1, heads * head_dim, 128)``
  at column ``position // 128``. ``visits`` is 1 for one row and 2 for more:
  rows ``pos .. pos + s - 1`` may straddle two columns, the second visit
  takes the column of the last row (the same block again when they do not
  straddle: it is then neither fetched nor written twice);
* the body turns the new row, which arrives along the lanes, into a column
  (a 128 x 128 transpose in the cache's own dtype: data movement, no
  arithmetic), replaces the lanes whose absolute position is one of the
  rows' by an iota compare and a select, and writes the block back. No
  dynamic lane store;
* ``input_output_aliases`` maps each cache operand onto its output: every
  block the grid does not visit is untouched, and under a donated decode
  step no second buffer exists;
* the outputs, and with them the operands they alias, are PINNED TO HBM
  (``out_shape=pltpu.HBM(...)``). Left to itself XLA's memory-space
  assignment stages whole cache buffers (84 MB each at GPT-2 large's shape)
  through the v5e's 128 MiB of VMEM around a custom call it believes reads
  them all, slices in and a copy out: 25 of a 36-layer decode step's 72
  buffers, 5 ms of a 14 ms step on the chip;
* K and V of a layer ride in ONE call (two aliased operands): half the grid
  steps (on the chip 104.7 against 108.8 us a layer).

**The buffers must be donated arguments of the jitted program** (as every
serving step hands its cache over). Where they are not, XLA has to copy
them before the aliased call, may keep that copy in VMEM, and this libtpu
(0.0.34) then ABORTS in its memory-space assignment on the pinned output
("Conflicting pending required assignment ... in alternate memory space")
instead of compiling the copy. Interpret mode has no such limit.

Semantics are ``kv_cache._row_update``'s element for element:
``buf[i, starts[i]:starts[i] + s] = new[i]`` with the start read as
``lax.dynamic_update_slice`` reads it (clamped into ``[0, max_len - s]``);
dead slots included. On the chip the buffers come back bit for bit what
``_row_update`` gives (``chip_smoke.py`` holds them equal at the serving
cells' shape). Forward-only (serving holds no gradients through the cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES
from .flash_decode import ROWS

#: the cache blocks of one call (every buffer's, in and out, double-buffered)
#: may hold this much of VMEM
BLOCK_BYTES = 8 << 20


def supports_row_write(rows, max_len, heads, head_dim, itemsize=2, buffers=2):
    """Shape gate: at most ``ROWS`` rows, ``max_len`` on the lanes in whole
    128-lane columns (``head_dim`` under 128 is when XLA:TPU lays a cache
    out so), a 16- or 32-bit dtype (what Mosaic transposes), head rows that
    fill whole sublane tiles, blocks that fit."""
    width = heads * head_dim
    return (1 <= rows <= ROWS and rows <= max_len
            and head_dim < LANES and max_len % LANES == 0
            and itemsize in (2, 4) and head_dim % (32 // itemsize) == 0
            and 4 * buffers * width * LANES * itemsize <= BLOCK_BYTES)


def clamped_starts(starts, max_len, rows):
    """The starts as ``lax.dynamic_update_slice`` reads them: a negative one
    counts from the end, then each is clamped so that ``rows`` rows fit."""
    pos = jnp.asarray(starts, jnp.int32)
    return jnp.clip(jnp.where(pos < 0, pos + max_len, pos), 0, max_len - rows)


def _visited_column(pos_ref, bb, visit, rows):
    """128-lane column the ``visit``-th grid step of slot ``bb`` takes: the
    first row's, then the last row's."""
    return (pos_ref[bb] + visit * (rows - 1)) // LANES


def _row_write_kernel(pos_ref, *refs, buffers, rows, width):
    """``refs``: per buffer the new rows ``(1, rows, padded width)``, then per
    buffer the cache block ``(1, width, 128)``, then the outputs, which are
    the cache operands again."""
    news, caches, outs = (refs[:buffers], refs[buffers:2 * buffers],
                          refs[2 * buffers:])
    bb = pl.program_id(0)
    pos0 = pos_ref[bb]
    col0 = _visited_column(pos_ref, bb, pl.program_id(1), rows) * LANES
    lane = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    hits = [lane == pos0 + i for i in range(rows)]  # row i's lane, if here
    for new_ref, cache_ref, out_ref in zip(news, caches, outs):
        for c in range(0, width, LANES):
            n = min(LANES, width - c)
            tile = cache_ref[0, c:c + n, :]
            for i, hit in enumerate(hits):
                # the row lies along the lanes; its place in the cache is a
                # column: broadcast over the sublanes and transpose
                row = new_ref[0, i:i + 1, c:c + LANES]
                col = jnp.broadcast_to(row, (LANES, LANES)).T
                tile = jnp.where(hit, col[:n], tile)
            out_ref[0, c:c + n, :] = tile


def _kv_row_write(pos, news, caches, interpret):
    """``pos`` ``(b,)`` clamped starts, ``news`` ``(b, rows, padded width)``
    each, ``caches`` ``(b, width, max_len)`` each; returns the caches."""
    buffers = len(caches)
    b, width, max_len = caches[0].shape
    rows, padded = news[0].shape[1:]
    visits = 1 if rows == 1 else 2

    # index maps take the scalar-prefetch ref as a trailing argument
    def new_map(bb, vi, pos_ref):
        return (bb, 0, 0)

    def cache_map(bb, vi, pos_ref):
        return (bb, 0, _visited_column(pos_ref, bb, vi, rows))

    new_spec = pl.BlockSpec((1, rows, padded), new_map)
    cache_spec = pl.BlockSpec((1, width, LANES), cache_map)
    itemsize = caches[0].dtype.itemsize
    kernel = functools.partial(_row_write_kernel, buffers=buffers, rows=rows,
                               width=width)
    return pl.pallas_call(
        kernel,
        name="kv_row_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, visits),
            in_specs=[new_spec] * buffers + [cache_spec] * buffers,
            out_specs=[cache_spec] * buffers,
        ),
        # pinned to HBM, and with them the operands they alias (module
        # docstring: what XLA's memory-space assignment does otherwise)
        out_shape=[pltpu.HBM(c.shape, c.dtype) for c in caches],
        # operand 0 is the scalar prefetch; cache operand i -> output i
        input_output_aliases={1 + buffers + i: i for i in range(buffers)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=int(2 * buffers * b * visits * width * LANES
                               * itemsize)),
    )(pos, *news, *caches)


#: Under one jit, as ``flash_decode._decode_call`` is and for its reason: a
#: decode step calls this once a layer with the same shapes, and the layers
#: share one trace and one lowering of the kernel.
_write_call = jax.jit(_kv_row_write, static_argnums=(3,))


def kv_row_write(bufs, news, starts, *, interpret=None):
    """``buf[i, starts[i]:starts[i] + s] = new[i]`` for every buffer of
    ``bufs`` (a layer's K and V), in place. On a TPU the buffers must be
    donated arguments of the jitted program (module docstring).

    Args:
      bufs: tuple of ``(batch, max_len, heads, head_dim)`` cache buffers of
        one shape and dtype.
      news: tuple of ``(batch, s, heads, head_dim)`` new rows, one per
        buffer, in the buffers' dtype; ``s <= ROWS``.
      starts: int ``(batch,)`` first position written of each slot.

    Returns the updated buffers, a tuple like ``bufs``.
    """
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    b, max_len, h, d = bufs[0].shape
    s = news[0].shape[1]
    if not supports_row_write(s, max_len, h, d, bufs[0].dtype.itemsize,
                              len(bufs)):
        raise ValueError(
            f"kv_row_write needs at most {ROWS} rows, head_dim < {LANES} in "
            f"whole sublane tiles and max_len % {LANES} == 0: rows={s}, "
            f"buffer={bufs[0].shape} {bufs[0].dtype}")
    width = h * d
    pad = -width % LANES
    pos = clamped_starts(starts, max_len, s)
    flat = [jnp.pad(n.reshape(b, s, width), ((0, 0), (0, 0), (0, pad)))
            for n in news]
    views = [jnp.transpose(x, (0, 2, 3, 1)).reshape(b, width, max_len)
             for x in bufs]

    def call(pos, *operands):
        return _write_call(pos, operands[:len(bufs)], operands[len(bufs):],
                           bool(interpret))

    outs = batch_sharded(call, (pos, *flat, *views),
                         (True,) * (1 + 2 * len(bufs)))
    return tuple(jnp.transpose(o.reshape(b, h, d, max_len), (0, 3, 1, 2))
                 for o in outs)

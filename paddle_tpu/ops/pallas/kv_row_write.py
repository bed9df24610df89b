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

This kernel moves what must move: of each LIVE slot, the one 128-lane
column of tiles that holds its position.

* it takes the buffer in the view the TPU keeps, ``(batch, heads * head_dim,
  max_len)`` (the transpose and the reshape in :func:`kv_row_write` are
  bitcasts there; for a shape the TPU lays out otherwise the result is the
  same and XLA pays a copy), and the per-slot positions scalar-prefetched;
* grid ``(batch, visits)``; grid step ``t`` takes the ``t``-th slot of a
  list of the live slots (``flash_decode.live_pairs`` with one block a slot,
  built in XLA from the engine's mask of live slots, once a step: XLA shares
  it across layers; scalar-prefetched with the positions); the cache block
  is ``(1, heads * head_dim, 128)`` at that slot's column ``position //
  128``. ``visits`` is 1 for one row and 2 for more: rows ``pos .. pos + s
  - 1`` may straddle two columns, the second visit takes the column of the
  last row (the same block again when they do not straddle: it is then
  neither fetched nor written twice);
* the grid keeps its static length: the steps past the list repeat the
  last live slot's last visit, so no block index changes, nothing is
  fetched or written back and the body is skipped (an output block stays
  in VMEM while its index stands, as an accumulating matmul relies on, and
  goes back to HBM as the last live step wrote it). With no slot live the
  list's one entry, a dead slot, is written like a live one, or the block
  the grid visits would go back to HBM unwritten. On the v5e a step that
  moves no block costs 0.11 us against ~2 us for one that moves a column
  (PERF.md, PR 35); a grid cut to the count by a dynamic bound costs each
  call ~40 us more than the empty steps it saves;
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

The contract (``KVCache``'s, stated there once for every write route): a
live slot's rows are written exactly as ``kv_cache._row_update`` writes
them, ``buf[i, starts[i]:starts[i] + s] = new[i]`` element for element with
the start read as ``lax.dynamic_update_slice`` reads it (clamped into ``[0,
max_len - s]``); a dead slot's rows need not be touched, and this kernel
leaves them alone whenever one slot is live. On the chip live slots come
back bit for bit what ``_row_update`` gives and dead ones as they were
(``chip_smoke.py`` holds both at the serving cells' shape). Forward-only
(serving holds no gradients through the cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES
from .flash_decode import ROWS, live_pairs

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


def _visited_column(pos_ref, slot, visit, rows):
    """128-lane column the ``visit``-th visit of ``slot`` takes: the first
    row's, then the last row's."""
    return (pos_ref[slot] + visit * (rows - 1)) // LANES


def _listed(count_ref, t):
    """Whether grid step ``t`` writes its slot: the first ``max(count, 1)``
    entries of the list (with no slot live, the one dead entry)."""
    return t < jnp.maximum(count_ref[0], 1)


def _row_write_kernel(pos_ref, slot_ref, count_ref, *refs, buffers, rows,
                      width):
    """``refs``: per buffer the new rows ``(1, rows, padded width)``, then per
    buffer the cache block ``(1, width, 128)``, then the outputs, which are
    the cache operands again."""
    news, caches, outs = (refs[:buffers], refs[buffers:2 * buffers],
                          refs[2 * buffers:])
    t, visit = pl.program_id(0), pl.program_id(1)

    # past the list the blocks stand where the last listed step left them
    # (module docstring): nothing to do
    @pl.when(_listed(count_ref, t))
    def _write():
        slot = slot_ref[t]
        pos0 = pos_ref[slot]
        col0 = _visited_column(pos_ref, slot, visit, rows) * LANES
        lane = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        hits = [lane == pos0 + i for i in range(rows)]  # row i's lane, if here
        for new_ref, cache_ref, out_ref in zip(news, caches, outs):
            for c in range(0, width, LANES):
                n = min(LANES, width - c)
                tile = cache_ref[0, c:c + n, :]
                for i, hit in enumerate(hits):
                    # the row lies along the lanes; its place in the cache is
                    # a column: broadcast over the sublanes and transpose
                    row = new_ref[0, i:i + 1, c:c + LANES]
                    col = jnp.broadcast_to(row, (LANES, LANES)).T
                    tile = jnp.where(hit, col[:n], tile)
                out_ref[0, c:c + n, :] = tile


def _kv_row_write(pos, live, news, caches, interpret):
    """``pos`` ``(b,)`` clamped starts, ``live`` ``(b,)`` bool, ``news``
    ``(b, rows, padded width)`` each, ``caches`` ``(b, width, max_len)``
    each; returns the caches."""
    buffers = len(caches)
    b, width, max_len = caches[0].shape
    rows, padded = news[0].shape[1:]
    visits = 1 if rows == 1 else 2
    # the live slots in order, one "block" each; entries past the count
    # repeat the last live slot (the last slot when none is live)
    slot, _, count = live_pairs(jnp.where(live, 0, -1)[:, None], 1, 1)

    # index maps take the scalar-prefetch refs as trailing arguments
    def new_map(t, vi, pos_ref, slot_ref, count_ref):
        return (slot_ref[t], 0, 0)

    def cache_map(t, vi, pos_ref, slot_ref, count_ref):
        # past the list every step takes the last visit, as the last listed
        # step did: no block index changes
        visit = jnp.where(_listed(count_ref, t), vi, visits - 1)
        return (slot_ref[t], 0,
                _visited_column(pos_ref, slot_ref[t], visit, rows))

    new_spec = pl.BlockSpec((1, rows, padded), new_map)
    cache_spec = pl.BlockSpec((1, width, LANES), cache_map)
    itemsize = caches[0].dtype.itemsize
    kernel = functools.partial(_row_write_kernel, buffers=buffers, rows=rows,
                               width=width)
    return pl.pallas_call(
        kernel,
        name="kv_row_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, visits),
            in_specs=[new_spec] * buffers + [cache_spec] * buffers,
            out_specs=[cache_spec] * buffers,
        ),
        # pinned to HBM, and with them the operands they alias (module
        # docstring: what XLA's memory-space assignment does otherwise)
        out_shape=[pltpu.HBM(c.shape, c.dtype) for c in caches],
        # operands 0-2 are the scalar prefetch; cache operand i -> output i
        input_output_aliases={3 + buffers + i: i for i in range(buffers)},
        # an output block stays put over the steps past the list: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=int(2 * buffers * b * visits * width * LANES
                               * itemsize)),
    )(pos, slot, count, *news, *caches)


#: Under one jit, as ``flash_decode._decode_call`` is and for its reason: a
#: decode step calls this once a layer with the same shapes, and the layers
#: share one trace and one lowering of the kernel.
_write_call = jax.jit(_kv_row_write, static_argnums=(4,))


def kv_row_write(bufs, news, starts, live=None, *, interpret=None):
    """``buf[i, starts[i]:starts[i] + s] = new[i]`` for every buffer of
    ``bufs`` (a layer's K and V) and every live slot ``i``, in place; a dead
    slot's rows are left alone whenever one slot is live (module
    docstring). On a TPU the buffers must be donated arguments of the
    jitted program (module docstring).

    Args:
      bufs: tuple of ``(batch, max_len, heads, head_dim)`` cache buffers of
        one shape and dtype.
      news: tuple of ``(batch, s, heads, head_dim)`` new rows, one per
        buffer, in the buffers' dtype; ``s <= ROWS``.
      starts: int ``(batch,)`` first position written of each slot.
      live: bool ``(batch,)``, the slots that hold a request (the engine's
        mask); None: every slot.

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
    live = (jnp.ones((b,), bool) if live is None
            else jnp.asarray(live, bool))
    flat = [jnp.pad(n.reshape(b, s, width), ((0, 0), (0, 0), (0, pad)))
            for n in news]
    views = [jnp.transpose(x, (0, 2, 3, 1)).reshape(b, width, max_len)
             for x in bufs]

    # under a mesh each shard lists its own live slots
    def call(pos, live, *operands):
        return _write_call(pos, live, operands[:len(bufs)],
                           operands[len(bufs):], bool(interpret))

    outs = batch_sharded(call, (pos, live, *flat, *views),
                         (True,) * (2 + 2 * len(bufs)))
    return tuple(jnp.transpose(o.reshape(b, h, d, max_len), (0, 3, 1, 2))
                 for o in outs)

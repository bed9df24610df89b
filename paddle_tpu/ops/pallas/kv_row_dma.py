"""The decode step's K/V row write where a row is contiguous: one DMA a slot.

``DecodeView.update`` writes one new row a slot (speculative verify: up to
``ROWS``) into each layer's ``(batch, max_len, heads, head_dim)`` buffers at
that slot's own position. Where ``head_dim`` fills the 128 lanes XLA:TPU keeps
such a buffer row-major, tiled over ``(heads, head_dim)`` alone
(``bf16[48,9216,8,128]{3,2,1,0:T(8,128)(2,1)}``, read from the compiled
``serve_decode`` of the Laguna cut): one position's row is one run of whole
tiles, 2 KB at 8 heads of 128, and no tile holds two positions. The vmapped
``dynamic_update_slice`` there still compiles to a ``while`` of one-row
updates, one trip a slot (48 trips, 0.145 ms a buffer on the v5e: 26
buffers, 3.8 ms of a 19.5 ms decode program).

This kernel is one aliased ``pallas_call`` a layer, K and V together:

* the cache operands alias the outputs (``input_output_aliases``): under a
  donated decode step no second buffer exists;
* the outputs, and with them the operands they alias, are PINNED TO HBM
  (``out_shape=pltpu.HBM(...)``), as ``kv_row_write.py``'s are: left to
  itself XLA's memory-space assignment stages each ring of 512 rows (50 MB)
  through VMEM around the call, in and out. The kernel sees a cache as
  ``(batch * max_len, heads, head_dim)``, a bitcast in the tiled layout,
  which also keeps the pinned output off the program's own outputs (the
  compiler refuses an output in another memory space than the donated
  parameter it aliases);
* the new rows ``(batch, rows, heads, head_dim)`` come where XLA put them
  (``pl.ANY``), the per-slot starts scalar-prefetched;
* the body starts one async copy a slot and buffer, ``new[i] ->
  cache[i, start_i : start_i + rows]``, all of them, then waits for all: no
  vector work, no grid. On the v5e the Laguna cut's 13 calls of a decode
  step read 0.033 ms in a loop of 20 steps whose empty body reads 0.021,
  where its 26 loops of one-row updates read 3.353 ms (``chip_smoke.py``'s
  ``row_dma`` leg); staging the new rows in VMEM first, or one wait a
  buffer, measured no faster.

Mosaic slices an HBM buffer only along whole sublane tiles of its second
minor dimension, so ``heads`` must be a power of two (at least one packed
32-bit row) or a multiple of 8 (:func:`supports_row_dma`; the AOT compiler
refuses 1, 3, 6 and 12 heads in bfloat16).

Semantics are ``kv_cache._row_update``'s element for element, as
``kv_row_write.py``'s: ``buf[i, starts[i]:starts[i] + s] = new[i]`` with the
start read as ``lax.dynamic_update_slice`` reads it; dead slots included.
Forward-only.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES
from .flash_decode import ROWS
from .kv_row_write import clamped_starts


def supports_row_dma(rows, max_len, heads, head_dim, itemsize=2):
    """Shape gate: at most ``ROWS`` rows, ``head_dim`` in whole 128-lane
    tiles (when XLA:TPU keeps a row contiguous), a 16- or 32-bit dtype, and
    heads that fill whole sublane tiles as Mosaic tiles them."""
    return (1 <= rows <= ROWS and rows <= max_len
            and head_dim % LANES == 0 and itemsize in (2, 4)
            and heads >= 4 // itemsize
            and (heads % 8 == 0 or heads & (heads - 1) == 0))


def _row_dma_kernel(pos_ref, *refs, buffers, batch, rows, max_len):
    """``refs``: per buffer the new rows, then per buffer the cache operand
    (unused: its output aliases it), then the outputs, then one DMA
    semaphore a buffer. A cache is seen as ``(batch * max_len, h, d)``."""
    news, outs, sems = refs[:buffers], refs[2 * buffers:3 * buffers], refs[-1]

    def copy(i, bb, start):
        return pltpu.make_async_copy(
            news[i].at[bb], outs[i].at[pl.ds(bb * max_len + start, rows)],
            sems.at[i])

    def start(bb, carry):
        for i in range(buffers):
            copy(i, bb, pos_ref[bb]).start()
        return carry

    def wait(bb, carry):
        # a wait reads the semaphore and the size of the copy alone
        for i in range(buffers):
            copy(i, bb, 0).wait()
        return carry

    jax.lax.fori_loop(0, batch, start, 0)
    jax.lax.fori_loop(0, batch, wait, 0)


def _kv_row_dma(pos, news, caches, interpret):
    """``pos`` ``(b,)`` clamped starts, ``news`` ``(b, rows, h, d)`` each,
    ``caches`` ``(b, max_len, h, d)`` each; returns the caches."""
    buffers = len(caches)
    b, max_len, h, d = caches[0].shape
    rows = news[0].shape[1]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    itemsize = caches[0].dtype.itemsize
    kernel = functools.partial(_row_dma_kernel, buffers=buffers, batch=b,
                               rows=rows, max_len=max_len)
    # (b, max_len) -> b * max_len rows is a bitcast in the tiled layout; it
    # also keeps the pinned output off the program's own outputs, whose
    # memory space must be the donated parameter's
    flat = [c.reshape(b * max_len, h, d) for c in caches]
    outs = pl.pallas_call(
        kernel,
        name="kv_row_dma",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[anywhere] * (2 * buffers),
            out_specs=[anywhere] * buffers,
            scratch_shapes=[pltpu.SemaphoreType.DMA((buffers,))],
        ),
        # pinned to HBM, and with them the operands they alias: left to
        # itself XLA's memory-space assignment stages a ring of 512 rows
        # (50 MB) through VMEM around the call, in and out
        out_shape=[pltpu.HBM(c.shape, c.dtype) for c in flat],
        # operand 0 is the scalar prefetch; cache operand i -> output i
        input_output_aliases={1 + buffers + i: i for i in range(buffers)},
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=int(2 * sum(n.size for n in news) * itemsize)),
    )(pos, *news, *flat)
    return [o.reshape(b, max_len, h, d) for o in outs]


#: Under one jit, as ``kv_row_write._write_call`` is: the layers of a decode
#: step share one trace and one lowering of the kernel a shape.
_dma_call = jax.jit(_kv_row_dma, static_argnums=(3,))


def kv_row_dma(bufs, news, starts, *, interpret=None):
    """``buf[i, starts[i]:starts[i] + s] = new[i]`` for every buffer of
    ``bufs`` (a layer's K and V), in place. Arguments and result as
    ``kv_row_write.kv_row_write``'s; on a TPU the buffers must be donated
    arguments of the jitted program, as every serving step hands its cache
    over."""
    from ..partition import batch_sharded
    from . import interpret_requested

    if interpret is None:
        interpret = interpret_requested()
    _, max_len, h, d = bufs[0].shape
    s = news[0].shape[1]
    if not supports_row_dma(s, max_len, h, d, bufs[0].dtype.itemsize):
        raise ValueError(
            f"kv_row_dma needs at most {ROWS} rows, head_dim in whole "
            f"{LANES}-lane tiles and heads in whole sublane tiles: rows={s}, "
            f"buffer={bufs[0].shape} {bufs[0].dtype}")
    pos = clamped_starts(starts, max_len, s)

    def call(pos, *operands):
        return _dma_call(pos, operands[:len(bufs)], operands[len(bufs):],
                         bool(interpret))

    return tuple(batch_sharded(call, (pos, *news, *bufs),
                               (True,) * (1 + 2 * len(bufs))))

"""The decode step's K/V row write (ISSUE 33, 37): the column kernel
(``ops/pallas/kv_row_write.py``) and the row DMA (``ops/pallas/
kv_row_dma.py``), interpret mode here, against ``kv_cache._row_update``,
the table of ``row_write_route``, and serving through each route.

Contracts under test:
  * exact equality of the WHOLE buffer with the vmapped
    ``dynamic_update_slice`` when every slot is live: every dtype, batch and
    position the engine builds, one row (decode) and several (speculative
    verify, straddling a 128-lane column or not), starts the
    ``dynamic_update_slice`` would clamp;
  * with the engine's mask of live slots, the column kernel writes a live
    slot exactly as ``_row_update`` does and leaves a dead slot's bytes as
    they were whenever one slot is live (with none live, at most one dead
    slot is written, as ``_row_update`` writes it); under a mesh each shard
    lists its own; the engine hands its mask to the kernel from decode and
    verify;
  * everything outside the written rows is the input's, bit for bit;
  * ``row_write_route`` is a pure function of shape facts and of what the
    platform tells, so tier-1 (XLA:CPU) can say what the TPU compiles;
  * greedy serving is byte-identical between the two routes, decode and
    verify, the counter ``kv.row_write_route.<route>`` says which one a step
    traced, and ``serve_decode`` still compiles exactly once.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import kv_row_dma as D
from paddle_tpu.ops.pallas import kv_row_write as W
from paddle_tpu.profiler import telemetry
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving import kv_cache as C
from paddle_tpu.utils import unique_name


def _bits(x):
    """The array's bytes as unsigned integers: equality that tells -0.0 from
    0.0 and one NaN from another."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _buffers(b, max_len, h, d, s, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape, new = (b, max_len, h, d), (b, s, h, d)
    return ([jax.random.normal(k, shape, jnp.float32).astype(dtype)
             for k in ks[:2]],
            [jax.random.normal(k, new, jnp.float32).astype(dtype)
             for k in ks[2:]])


def _write_both(bufs, news, starts):
    got = W.kv_row_write(tuple(bufs), tuple(news), starts, interpret=True)
    want = [C._row_update(x, n, starts) for x, n in zip(bufs, news)]
    return got, want


# ---------------------------------------------------------------------------
# the kernel against _row_update
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("batch", [1, 4, 32])
def test_one_row_a_slot_equals_row_update(dtype, batch):
    """Decode: every slot at a position of its own, the column edges (0,
    127, 128, 255, ``max_len - 1``) among them."""
    max_len = 384
    bufs, news = _buffers(batch, max_len, 2, 64, 1, dtype)
    edges = [0, 127, 128, 255, max_len - 1]
    rest = np.random.RandomState(batch).randint(0, max_len, batch)
    starts = jnp.asarray((edges + list(rest))[:batch], jnp.int32)
    got, want = _write_both(bufs, news, starts)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("pos", [0, 127, 128, 255, 383])
def test_all_slots_at_one_position(pos):
    bufs, news = _buffers(4, 384, 2, 64, 1, jnp.bfloat16, seed=pos)
    got, want = _write_both(bufs, news, jnp.full((4,), pos, jnp.int32))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows", [2, 5, 8])
def test_verify_rows_straddling_a_column_equal_row_update(dtype, rows):
    """Speculative verify: ``rows`` rows from each slot's position on:
    inside one 128-lane column, across two (126, 124, 255), at the end."""
    max_len = 384
    bufs, news = _buffers(6, max_len, 2, 64, rows, dtype, seed=rows)
    starts = jnp.asarray([0, 126, 124, 255, max_len - rows, 17], jnp.int32)
    got, want = _write_both(bufs, news, starts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _assert_live_written_dead_left(bufs, news, starts, live):
    """The column kernel under the mask ``live``: each live slot is bit for
    bit ``_row_update``'s; a dead slot is its input's whenever one slot is
    live, and with none live at most one slot is written, as ``_row_update``
    writes it."""
    got = W.kv_row_write(tuple(bufs), tuple(news), starts, jnp.asarray(live),
                         interpret=True)
    live = np.asarray(live, bool)
    for g, x, n in zip(got, bufs, news):
        g, x = _bits(g), _bits(x)
        w = _bits(C._row_update(x.view(n.dtype), n, starts))
        np.testing.assert_array_equal(g[live], w[live])
        if live.any():
            np.testing.assert_array_equal(g[~live], x[~live])
        else:
            touched = [i for i in range(len(g))
                       if not np.array_equal(g[i], x[i])]
            assert len(touched) <= 1, touched
            for i in touched:
                np.testing.assert_array_equal(g[i], w[i])


def _mask(b, live_slots):
    live = np.zeros((b,), bool)
    live[list(live_slots)] = True
    return live


#: (rows, max_len, starts, live slots): the cases of the column kernel under
#: the engine's mask
LIVE_CASES = {
    # a live slot at a start ``dynamic_update_slice`` clamps (the engine's
    # ``max_len - 1`` decode, ``max_len - W`` verify, past the end, counted
    # from the end) beside a dead slot and a live one
    "clamped-1-255": (1, 256, [5, 255, 130], [1, 2]),
    "clamped-5-251": (5, 256, [5, 251, 130], [1, 2]),
    "clamped-1-past-the-end": (1, 256, [5, 10 ** 6, 130], [1, 2]),
    "clamped-5-254": (5, 256, [5, 254, 130], [1, 2]),
    "clamped-1-from-the-end": (1, 256, [5, -3, 130], [1, 2]),
    # live slots 0, 5 and 31 of 32, dead ones between them
    "gaps-decode": (1, 256, None, [0, 5, 31]),
    "gaps-verify-5": (5, 256, None, [0, 5, 31]),
    "all-live-decode": (1, 256, None, range(32)),
    "all-live-verify-8": (8, 256, None, range(32)),
    "none-live-decode": (1, 256, None, []),
    "none-live-verify-5": (5, 256, None, []),
    # verify rows straddling a 128-lane column (126, 124, 255) or not,
    # dead slots among them
    "straddling-5": (5, 384, [0, 126, 124, 255, 379, 17], [0, 2, 4]),
    "straddling-8": (8, 384, [0, 126, 124, 255, 376, 17], [1, 3, 4]),
}


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_the_live_slots_alone_are_written(case):
    rows, max_len, starts, live_slots = LIVE_CASES[case]
    if starts is None:
        starts = np.random.RandomState(rows).randint(0, max_len - rows, 32)
    b = len(starts)
    bufs, news = _buffers(b, max_len, 2, 64, rows, jnp.bfloat16, seed=7)
    _assert_live_written_dead_left(bufs, news, jnp.asarray(starts, jnp.int32),
                                   _mask(b, live_slots))


@pytest.mark.parametrize("rows", [1, 5])
def test_under_a_mesh_each_shard_lists_its_own_live_slots(rows):
    """Every live slot on one shard of four: the other shards list none,
    and each leaves all but at most one of its own slots alone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.partition import partition_scope

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    bufs, news = _buffers(8, 256, 2, 64, rows, jnp.bfloat16, seed=rows)
    starts = jnp.asarray([0, 127, 128, 250, 1, 2, 3, 200], jnp.int32)
    live = jnp.asarray(_mask(8, [2, 3]))  # the second shard's two slots
    row = NamedSharding(mesh, P("dp"))
    placed = jax.device_put((bufs, news, starts, live), row)

    def write(bufs, news, starts, live):
        with partition_scope((mesh, ("dp",))):
            return W.kv_row_write(tuple(bufs), tuple(news), starts, live,
                                  interpret=True)

    got = jax.jit(write)(*placed)
    for g, x, n in zip(got, bufs, news):
        assert g.sharding.is_equivalent_to(row, g.ndim)
        g, x = _bits(g), _bits(x)
        w = _bits(C._row_update(x.view(n.dtype), n, starts))
        np.testing.assert_array_equal(g[2:4], w[2:4])
        for shard in (0, 2, 3):  # no live slot: at most one slot written
            touched = [i for i in (2 * shard, 2 * shard + 1)
                       if not np.array_equal(g[i], x[i])]
            assert len(touched) <= 1, touched
            for i in touched:
                np.testing.assert_array_equal(g[i], w[i])


@pytest.mark.parametrize("heads,head_dim", [(3, 32), (5, 64), (1, 16)])
def test_widths_that_fill_no_whole_128_rows_chunk(heads, head_dim):
    """``heads * head_dim`` of 96, 320 and 16: the last chunk of the block
    is a partial one."""
    bufs, news = _buffers(3, 256, heads, head_dim, 1, jnp.bfloat16)
    got, want = _write_both(bufs, news, jnp.asarray([3, 127, 200], jnp.int32))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _random_bits(rng, shape, nans):
    """Random bf16 bit patterns: infinities, negative zeros, subnormals;
    NaNs of every payload when asked, else none."""
    raw = rng.randint(0, 2 ** 16, shape).astype(np.uint16)
    nan = (raw & 0x7F80 == 0x7F80) & (raw & 0x007F != 0)
    return raw if nans else np.where(nan, raw & 0xBFFF, raw)


@pytest.mark.parametrize("nans", [False, True], ids=["bits", "nans"])
def test_cells_outside_the_written_rows_keep_their_bytes(nans):
    """Random bits around the written rows and in them: infinities,
    negative zeros and subnormals come back bit for bit, outside the rows
    and inside. A NaN stays a NaN where it was (the select and the float32
    column may not keep its payload, here under XLA:CPU's interpreter)."""
    b, max_len, h, d, s = 2, 256, 2, 64, 3
    rng = np.random.RandomState(3)
    raw = _random_bits(rng, (2, b, max_len, h, d), nans)
    new_raw = _random_bits(rng, (2, b, s, h, d), nans)
    new_raw[0, 0, 0, 0, :3] = [0x8000, 0x7F80, 0xFF80]  # -0.0, inf, -inf
    bufs = [jnp.asarray(r).view(jnp.bfloat16) for r in raw]
    news = [jnp.asarray(r).view(jnp.bfloat16) for r in new_raw]
    starts = np.asarray([126, 40], np.int32)
    got = W.kv_row_write(tuple(bufs), tuple(news), jnp.asarray(starts),
                         interpret=True)
    for g, before, new in zip(got, raw, new_raw):
        want = before.copy()
        for i, p in enumerate(starts):
            want[i, p:p + s] = new[i]
        if nans:
            g = np.asarray(g, np.float32)
            want = np.asarray(jnp.asarray(want).view(jnp.bfloat16),
                              np.float32)
            np.testing.assert_array_equal(np.isnan(g), np.isnan(want))
            np.testing.assert_array_equal(g[~np.isnan(g)],
                                          want[~np.isnan(want)])
        else:
            np.testing.assert_array_equal(_bits(g), want)


def test_one_buffer_a_call_and_two_agree():
    bufs, news = _buffers(4, 256, 2, 64, 1, jnp.bfloat16)
    starts = jnp.asarray([0, 127, 128, 255], jnp.int32)
    both = W.kv_row_write(tuple(bufs), tuple(news), starts, interpret=True)
    for x, n, w in zip(bufs, news, both):
        (alone,) = W.kv_row_write((x,), (n,), starts, interpret=True)
        np.testing.assert_array_equal(_bits(alone), _bits(w))


def test_the_kernel_refuses_what_the_route_does_not_send_it():
    bufs, news = _buffers(2, 250, 2, 64, 1, jnp.bfloat16)
    with pytest.raises(ValueError, match="max_len"):
        W.kv_row_write(tuple(bufs), tuple(news), jnp.zeros((2,), jnp.int32),
                       interpret=True)


def test_under_a_mesh_the_kernel_partitions_itself_over_the_batch():
    """Mosaic kernels are not partitioned by GSPMD: under the ``dp`` mesh of
    ``chip_smoke.py`` each device writes its own slots."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.partition import partition_scope

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    bufs, news = _buffers(8, 256, 2, 64, 1, jnp.bfloat16)
    starts = jnp.asarray([0, 127, 128, 255, 1, 2, 3, 200], jnp.int32)
    row = NamedSharding(mesh, P("dp"))
    placed = jax.device_put((bufs, news, starts), row)

    def write(bufs, news, starts):
        with partition_scope((mesh, ("dp",))):
            return W.kv_row_write(tuple(bufs), tuple(news), starts,
                                  interpret=True)

    got = jax.jit(write)(*placed)
    for g, x, n in zip(got, bufs, news):
        assert g.sharding.is_equivalent_to(row, g.ndim)
        np.testing.assert_array_equal(
            _bits(g), _bits(C._row_update(x, n, starts)))


# ---------------------------------------------------------------------------
# the row DMA (head_dim in whole 128-lane tiles) against _row_update
# ---------------------------------------------------------------------------
def _dma_both(bufs, news, starts):
    got = D.kv_row_dma(tuple(bufs), tuple(news), starts, interpret=True)
    want = [C._row_update(x, n, starts) for x, n in zip(bufs, news)]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("heads", [2, 8])
@pytest.mark.parametrize("rows", [1, 5, 8])
def test_row_dma_equals_row_update(heads, rows):
    """Decode and verify at heads of 128: a slot at the first position, one
    at the last that fits, two past it (clamped as ``dynamic_update_slice``
    clamps), one counted from the end, the rest anywhere."""
    max_len = 64
    bufs, news = _buffers(8, max_len, heads, 128, rows, jnp.bfloat16,
                          seed=rows)
    starts = jnp.asarray([0, max_len - rows, max_len - rows + 1, 10 ** 6,
                          -3, 17, 31, 40], jnp.int32)
    _dma_both(bufs, news, starts)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_row_dma_at_ring_positions(dtype):
    """A ring of 32 rows written at ``pos mod 32``, the positions past the
    window wrapped, as the engine hands a window layer its row."""
    pos = np.asarray([5, 31, 32, 63, 100, 1023], np.int32)
    bufs, news = _buffers(6, 32, 8, 128, 1, dtype, seed=3)
    _dma_both(bufs, news, jnp.asarray(pos % 32))


def test_row_dma_writes_dead_slots_where_row_update_does():
    """A dead slot keeps the stale length its last request left, or the
    engine's clamped one (``max_len - 1``), and is written like any other."""
    bufs, news = _buffers(4, 48, 2, 128, 1, jnp.bfloat16, seed=9)
    _dma_both(bufs, news, jnp.asarray([47, 12, 47, 0], jnp.int32))


def test_row_dma_k_and_v_in_one_call_agree_with_one_buffer_a_call():
    bufs, news = _buffers(4, 32, 8, 128, 5, jnp.bfloat16)
    starts = jnp.asarray([0, 27, 12, 30], jnp.int32)
    both = D.kv_row_dma(tuple(bufs), tuple(news), starts, interpret=True)
    for x, n, w in zip(bufs, news, both):
        (alone,) = D.kv_row_dma((x,), (n,), starts, interpret=True)
        np.testing.assert_array_equal(_bits(alone), _bits(w))


@pytest.mark.parametrize("nans", [False, True], ids=["bits", "nans"])
def test_row_dma_keeps_every_byte_outside_the_written_rows(nans):
    """Random bits around the written rows and in them: infinities,
    negative zeros and subnormals come back bit for bit. A NaN stays a NaN
    where it was (XLA:CPU's interpreter may quiet its payload; the chip's
    DMA moves bytes, and ``chip_smoke.py`` holds it bit for bit)."""
    b, max_len, h, d, s = 3, 32, 2, 128, 3
    rng = np.random.RandomState(5)
    raw = _random_bits(rng, (2, b, max_len, h, d), nans)
    new_raw = _random_bits(rng, (2, b, s, h, d), nans)
    new_raw[0, 0, 0, 0, :3] = [0x8000, 0x7F80, 0xFF80]  # -0.0, inf, -inf
    starts = np.asarray([0, 29, 14], np.int32)
    got = D.kv_row_dma(tuple(jnp.asarray(r).view(jnp.bfloat16) for r in raw),
                       tuple(jnp.asarray(r).view(jnp.bfloat16)
                             for r in new_raw), jnp.asarray(starts),
                       interpret=True)
    for g, before, new in zip(got, raw, new_raw):
        want = before.copy()
        for i, p in enumerate(starts):
            want[i, p:p + s] = new[i]
        if nans:
            g = np.asarray(g, np.float32)
            want = np.asarray(jnp.asarray(want).view(jnp.bfloat16),
                              np.float32)
            np.testing.assert_array_equal(np.isnan(g), np.isnan(want))
            np.testing.assert_array_equal(g[~np.isnan(g)],
                                          want[~np.isnan(want)])
        else:
            np.testing.assert_array_equal(_bits(g), want)


def test_the_row_dma_refuses_what_the_route_does_not_send_it():
    bufs, news = _buffers(2, 32, 3, 128, 1, jnp.bfloat16)
    with pytest.raises(ValueError, match="sublane"):
        D.kv_row_dma(tuple(bufs), tuple(news), jnp.zeros((2,), jnp.int32),
                     interpret=True)


def test_under_a_mesh_the_row_dma_partitions_itself_over_the_batch():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.partition import partition_scope

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    bufs, news = _buffers(8, 32, 2, 128, 1, jnp.bfloat16)
    starts = jnp.asarray([0, 31, 5, 6, 1, 2, 3, 20], jnp.int32)
    row = NamedSharding(mesh, P("dp"))
    placed = jax.device_put((bufs, news, starts), row)

    def write(bufs, news, starts):
        with partition_scope((mesh, ("dp",))):
            return D.kv_row_dma(tuple(bufs), tuple(news), starts,
                                interpret=True)

    got = jax.jit(write)(*placed)
    for g, x, n in zip(got, bufs, news):
        assert g.sharding.is_equivalent_to(row, g.ndim)
        np.testing.assert_array_equal(
            _bits(g), _bits(C._row_update(x, n, starts)))


# ---------------------------------------------------------------------------
# the route table, read without a chip
# ---------------------------------------------------------------------------
TPU = dict(pallas=True)
CPU = dict(pallas=False)
INTERPRET = dict(pallas=True)  # is_available() is true inside interpret_mode

LARGE = dict(max_len=1024, heads=20, head_dim=64, itemsize=2)  # gpt2-large
HYBRID = dict(max_len=2048, heads=2, head_dim=128, itemsize=2)  # nemotron-3
LAGUNA = dict(max_len=9216, heads=8, head_dim=128, itemsize=2)  # full rows
RING = dict(LAGUNA, max_len=512)  # laguna's window layers
SOLAR = dict(max_len=5120, heads=8, head_dim=128, itemsize=2)  # solar-open2


def _row(name, platform, expect, *, rows=1, **facts):
    return pytest.param(dict(rows=rows, **facts), platform, expect, id=name)


ROUTE_ROWS = [
    _row("tpu-large-decode", TPU, "column_kernel", **LARGE),
    _row("tpu-large-verify-5", TPU, "column_kernel", rows=5, **LARGE),
    _row("tpu-large-verify-8", TPU, "column_kernel", rows=8, **LARGE),
    _row("tpu-large-rows-9", TPU, "dus", rows=9, **LARGE),
    _row("tpu-large-f32", TPU, "column_kernel",
         **dict(LARGE, itemsize=4)),
    _row("tpu-124m-decode", TPU, "column_kernel", max_len=1024, heads=12,
         head_dim=64, itemsize=2),
    _row("tpu-generate-batch-1-max-len-128", TPU, "column_kernel",
         max_len=128, heads=20, head_dim=64, itemsize=2),
    _row("tpu-hybrid-head-dim-128", TPU, "row_dma", **HYBRID),
    _row("tpu-laguna-full-decode", TPU, "row_dma", **LAGUNA),
    _row("tpu-laguna-ring-decode", TPU, "row_dma", **RING),
    _row("tpu-solar-decode", TPU, "row_dma", **SOLAR),
    _row("tpu-solar-verify-8", TPU, "row_dma", rows=8, **SOLAR),
    _row("tpu-solar-rows-9", TPU, "dus", rows=9, **SOLAR),
    _row("tpu-solar-f32", TPU, "row_dma", **dict(SOLAR, itemsize=4)),
    _row("tpu-head-dim-256", TPU, "row_dma", **dict(SOLAR, head_dim=256)),
    _row("tpu-head-dim-192", TPU, "dus", **dict(SOLAR, head_dim=192)),
    _row("tpu-one-bf16-head-of-128", TPU, "dus", **dict(SOLAR, heads=1)),
    _row("tpu-one-f32-head-of-128", TPU, "row_dma",
         **dict(SOLAR, heads=1, itemsize=4)),
    _row("tpu-six-heads-of-128", TPU, "dus", **dict(SOLAR, heads=6)),
    _row("tpu-24-heads-of-128", TPU, "row_dma", **dict(SOLAR, heads=24)),
    _row("tpu-head-dim-128-one-byte", TPU, "dus", **dict(SOLAR, itemsize=1)),
    _row("tpu-ring-of-4-verify-5", TPU, "dus", rows=5,
         **dict(RING, max_len=4)),
    _row("tpu-max-len-1000", TPU, "dus", **dict(LARGE, max_len=1000)),
    _row("tpu-max-len-64", TPU, "dus", **dict(LARGE, max_len=64)),
    _row("tpu-head-dim-8-under-a-bf16-tile", TPU, "dus",
         **dict(LARGE, head_dim=8)),
    _row("tpu-head-dim-8-f32", TPU, "column_kernel",
         **dict(LARGE, head_dim=8, itemsize=4)),
    _row("tpu-blocks-over-vmem", TPU, "dus", **dict(LARGE, heads=160)),
    _row("tpu-one-byte-cache", TPU, "dus", **dict(LARGE, itemsize=1)),
    _row("cpu-large-decode", CPU, "dus", **LARGE),
    _row("cpu-large-verify-5", CPU, "dus", rows=5, **LARGE),
    _row("cpu-hybrid", CPU, "dus", **HYBRID),
    _row("cpu-laguna-full-decode", CPU, "dus", **LAGUNA),
    _row("cpu-solar-verify-5", CPU, "dus", rows=5, **SOLAR),
    _row("interpret-large-decode", INTERPRET, "column_kernel", **LARGE),
    _row("interpret-hybrid", INTERPRET, "row_dma", **HYBRID),
    _row("interpret-toy-two-heads-of-64", INTERPRET, "column_kernel",
         max_len=128, heads=2, head_dim=64, itemsize=4),
]


@pytest.mark.parametrize("facts, platform, expect", ROUTE_ROWS)
def test_route_table(facts, platform, expect):
    assert C.row_write_route(**facts, **platform) == expect


def test_the_route_reads_no_global_state(monkeypatch):
    """What the platform tells comes in as an argument: the same call gives
    the same answer inside interpret mode and outside."""
    monkeypatch.setattr(pallas, "is_available", lambda: 1 / 0)
    with pallas.interpret_mode():
        inside = C.row_write_route(rows=1, pallas=False, **LARGE)
    assert inside == C.row_write_route(rows=1, pallas=False, **LARGE) == "dus"


# ---------------------------------------------------------------------------
# serving through either route
# ---------------------------------------------------------------------------
@pytest.fixture
def _no_persistent_compile_cache():
    """Parity across separately compiled executables is only bit-exact with
    in-process compiles (as in tests/test_serving.py)."""
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture
def _counters():
    telemetry.reset()
    telemetry.enable()
    yield telemetry.get_telemetry
    telemetry.disable()
    telemetry.reset()


def _kernel_model(seed=0, head_dim=64):
    """Two heads of 64 over a 128-slot cache: shapes the column kernel
    takes (two heads of 128: the row DMA's)."""
    with unique_name.guard():
        paddle.seed(seed)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=2 * head_dim, num_layers=2,
            num_heads=2,
            max_position_embeddings=128, hidden_dropout=0.0,
            attention_dropout=0.0, initializer_range=0.6))
    model.eval()
    return model


def _route_counts(tm):
    return {k.rsplit(".", 1)[1]: v for k, v in tm().counters().items()
            if k.startswith("kv.row_write_route.")}


#: a kernel's route, the head size that takes it, and how a test takes it
#: away again (the column kernel's VMEM budget at 0; the row DMA's gate shut)
KERNELS = {"column_kernel": (64, W, "BLOCK_BYTES", 0),
           "row_dma": (128, D, "supports_row_dma", lambda *a, **k: False)}


@pytest.mark.parametrize("route", sorted(KERNELS))
@pytest.mark.parametrize("engine_kw,prompt_len", [
    ({}, 7), ({"spec_k": 4}, 9)], ids=["decode", "verify"])
def test_greedy_serving_byte_identical_between_the_two_routes(
        _no_persistent_compile_cache, _counters, monkeypatch, engine_kw,
        prompt_len, route):
    """The same engine, both times in interpret mode (so attention takes the
    same kernel): once with the kernel writing the rows, once with the
    kernel taken away, which leaves ``dus``. The same tokens, and each
    run's counter names the route its steps traced, once a layer."""
    head_dim, module, name, off = KERNELS[route]
    model = _kernel_model(head_dim=head_dim)
    rng = np.random.RandomState(11)
    # a periodic prompt: the n-gram proposer drafts from the first tick
    prompt = np.tile(rng.randint(0, 512, 3), 4)[:prompt_len].tolist()

    def gen():
        telemetry.reset()
        eng = GenerationEngine(model, max_batch=2, max_len=128,
                               prefill_buckets=(16,), **engine_kw)
        return eng.generate(prompt, max_new_tokens=24), _route_counts(
            _counters)

    with pallas.interpret_mode():
        kernel, kernel_routes = gen()
        monkeypatch.setattr(module, name, off)
        dus, dus_routes = gen()
    assert len(set(kernel)) > 2, "degenerate model; parity is vacuous"
    assert kernel == dus
    assert set(kernel_routes) == {route}, kernel_routes
    assert set(dus_routes) == {"dus"}, dus_routes
    # bumped once a layer whenever the step is traced (CompiledStep traces
    # it more than once): the same number either way, the two layers' share
    assert kernel_routes[route] == dus_routes["dus"] > 0
    assert kernel_routes[route] % 2 == 0


@pytest.mark.parametrize("route", ["column_kernel", "dus"])
@pytest.mark.parametrize("step", ["decode", "verify"])
def test_the_engine_hands_its_live_mask_to_the_column_kernel(monkeypatch,
                                                             step, route):
    """One step of two slots from zeroed buffers, the second dead: the
    column kernel leaves its rows zero, ``dus`` writes them; the live slot's
    rows are the same either way."""
    if route == "dus":
        monkeypatch.setattr(W, "BLOCK_BYTES", 0)
    with pallas.interpret_mode():
        eng = GenerationEngine(_kernel_model(), max_batch=2, max_len=128,
                               prefill_buckets=(16,),
                               spec_k=4 if step == "verify" else 0)
        make = (eng.example_verify_args if step == "verify"
                else eng.example_decode_args)
        args = list(make([3, 7]))
        args[-1] = np.asarray([True, False])
        run = eng.verify_step if step == "verify" else eng.decode_step
        cache = run(*args)[-1]
    for buf in (*cache.ks, *cache.vs):
        buf = np.asarray(buf._value if hasattr(buf, "_value") else buf,
                         np.float32)
        assert buf[0].any()
        assert buf[1].any() == (route == "dus")


@pytest.mark.parametrize("family", ["laguna", "solar_open2", "nemotron_h"])
def test_the_expert_decoders_write_every_kv_layer_by_row_dma(_counters,
                                                             family):
    """At heads of 128, as the benchmark's expert cuts hold them, a traced
    decode step writes each K/V layer's rows with the row DMA where Pallas
    is on offer, once a layer: rings and full-length rows alike (the cuts
    themselves: 13 / 1 / 2, ``chip_smoke.py`` counts the first two on the
    chip)."""
    import importlib

    tiny = importlib.import_module(f"{family}_tiny")
    model, _ = tiny.build(tiny.tiny_config(head_dim=128))
    kv_layers = sum(1 for e in model.cache_spec()
                    if e and e["kind"] == "kv")
    with pallas.interpret_mode():
        eng = GenerationEngine(model, max_batch=2, max_len=64)
        eng.decode_step.lower(*eng.example_decode_args([1, 0]))
    assert kv_layers >= 1
    assert _route_counts(_counters) == {"row_dma": kv_layers}


def test_xla_cpu_keeps_the_dynamic_update_slice(_counters):
    """Tier-1's own platform: no Pallas, so the decode step writes its rows
    as it always did."""
    eng = GenerationEngine(_kernel_model(), max_batch=2, max_len=128,
                           prefill_buckets=(16,))
    eng.generate([5, 6, 7], max_new_tokens=4)
    assert set(_route_counts(_counters)) == {"dus"}


def test_decode_still_compiles_once_through_the_column_kernel(_counters):
    with pallas.interpret_mode():
        eng = GenerationEngine(_kernel_model(), max_batch=2, max_len=128,
                               prefill_buckets=(8, 16))
        out = eng.generate([5, 6, 7], max_new_tokens=40)
    tm = _counters()
    assert len(out) == 40
    assert tm.compile_counts().get("serve_decode") == 1, tm.compile_counts()
    assert tm.compile_counts().get("serve_prefill") == 1
    assert tm.recompile_count == 0
    assert set(_route_counts(_counters)) == {"column_kernel"}


@pytest.mark.parametrize("platform", ["interpret", "cpu"])
def test_a_head_dim_that_fills_the_lanes_counts_its_route(_counters,
                                                          platform):
    """The expert configurations' attention holds heads of 128: a row is
    contiguous on the TPU, and where Pallas is on offer the route says
    ``row_dma``; XLA:CPU keeps ``dus``. Either way the same rows land."""
    k = jnp.zeros((2, 128, 2, 128), jnp.bfloat16)
    new = jnp.ones((2, 1, 2, 128), jnp.bfloat16)
    with (pallas.interpret_mode() if platform == "interpret"
          else contextlib.nullcontext()):
        view = C.DecodeView(k, k, jnp.asarray([3, 127], jnp.int32))
        out_k, _, _ = view.update(new, new)
    route = "row_dma" if platform == "interpret" else "dus"
    assert _route_counts(_counters) == {route: 1}
    np.testing.assert_array_equal(
        np.asarray(out_k._value[:, :, 0, 0], np.float32)[[0, 1], [3, 127]],
        [1.0, 1.0])

"""Static-shape KV cache: O(1) autoregressive decode on XLA.

The legacy decode path (``models/gpt.py`` tuple cache) grew K/V with
``ops.concat`` every step — each step changes the cache operand shape, so
XLA compiles ONE EXECUTABLE PER POSITION (the exact hazard the
``retrace-shape-churn`` / ``kv-cache-concat`` lint rules flag) and the
concat re-materializes the full cache in HBM every token: O(n) per step,
O(n²) per sequence.

This module is the compiler-first formulation (PAPERS.md arxiv 2603.09555):
per-layer buffers are preallocated at ``[batch, max_len, heads, head_dim]``
and every step writes the new K/V rows at a *traced* position index — the
shapes entering the compiled step never change, so prefill compiles once per
length bucket and decode compiles exactly once, and with the buffers passed
through ``CompiledStep``'s ``donate_inputs`` the write happens in place in
HBM.

How the decode step's row is written is chosen by :func:`row_write_route`
from the layout the backend keeps the buffer in. On XLA:TPU the vmapped
``lax.dynamic_update_slice`` (:func:`_row_update`) compiles to a ``while``
of one-row updates, one trip a slot, whatever the layout, so the TPU takes
one of two aliased Pallas kernels instead. With ``head_dim`` under 128
XLA:TPU keeps the buffer as ``[batch, heads, head_dim, max_len]``,
``max_len`` on the lanes (what the decode attention kernel wants,
``ops/pallas/flash_decode.py``): a row is ``heads * head_dim`` elements in
as many lane rows (the loop took 68% of a GPT-2 large decode step on the
v5e), and ``ops/pallas/kv_row_write.py`` rewrites the one 128-lane column
of each live slot that holds its position. With ``head_dim`` in whole 128-lane
tiles the buffer is row-major, a position's row a run of whole tiles (the
loops took 3.8 ms of the Laguna cut's 19.5 ms decode program), and
``ops/pallas/kv_row_dma.py`` copies each slot's new rows into place with
one DMA. XLA:CPU keeps the ``dynamic_update_slice``.

Masking carries the variable part: attention always runs over the full
``max_len`` keys and the per-slot lengths mask out the not-yet-written
tail. The engine's step bodies express that as a
``functional.LengthMask`` (ISSUE 15) — a description of the valid
region, not a materialized ``[b, 1, q, max_len]`` tensor — so at long
``max_len`` sdpa routes to the blockwise online-softmax KV scan (on the
TPU: the Pallas decode kernel for decode and verify, the flash cached
kernel for prefill blocks) and the O(q·max_len) score matrix is never
built; short caches fall back to the same additive mask as before.
Correctness invariant either way: position ``j`` of a slot's buffer holds
garbage only while ``j >= length`` — and the mask admits exactly
``j <= position-of-the-query`` — so garbage is never attended to and is
overwritten the moment the sequence reaches it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor

__all__ = ["KVCache", "DecodeView", "PrefillView", "RingPrefillView",
           "ChunkView", "StateDecodeView", "StatePrefillView", "CountsView",
           "pick_bucket", "default_buckets", "row_write_route",
           "cache_route"]

#: additive-mask floor: large enough to zero a softmax lane in fp32/bf16
#: without producing inf-inf NaNs when a whole row is masked
MASK_MIN = -1e9


def _leaf(x):
    """Tensor -> backing array; arrays pass through."""
    return x._value if isinstance(x, Tensor) else x


# ---------------------------------------------------------------------------
# length bucketing
# ---------------------------------------------------------------------------
def default_buckets(max_len, min_bucket=16):
    """Powers-of-two prefill widths ``min_bucket .. max_len`` (inclusive
    when ``max_len`` is itself reachable). One compiled prefill executable
    per bucket serves every prompt length ≤ that bucket."""
    max_len = int(max_len)
    b = int(min_bucket)
    out = []
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def pick_bucket(n, buckets):
    """Smallest bucket that fits ``n`` tokens (compile-once-per-bucket)."""
    for b in sorted(buckets):
        if n <= b:
            return int(b)
    raise ValueError(
        f"sequence of {n} tokens exceeds the largest prefill bucket "
        f"{max(buckets)}; raise max_len/prefill_buckets on the engine")


# ---------------------------------------------------------------------------
# the cache pytree
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
class KVCache:
    """What the served slots keep, layer by layer, as the MODEL declares it
    (``model.cache_spec()``, one entry a layer):

    * ``{"kind": "kv", "heads", "head_dim", "dtype"}`` — K/V rows up to
      ``max_len``: ``ks[l] / vs[l]: [batch, max_len, heads, head_dim]``;
      with ``"window": w`` (a layer that attends to the last ``w``
      positions alone) a RING of ``min(w, max_len)`` rows, position ``p``
      in row ``p mod w``: ``[batch, w, heads, head_dim]``. Which rows of a
      ring are valid follows from the slot's length alone (the first
      ``min(length, w)``), so a ring needs keys that carry their position
      in themselves (rotated before they are cached) or none at all;
    * ``{"kind": "state", "arrays": {name: (shape, dtype)}}`` — fixed-shape
      recurrent state: ``states[l][name]: [batch, *shape]``;
    * anything else (``None``, ``{"kind": "counts", ...}``) — the layer
      keeps nothing per slot: ``ks[l]``, ``vs[l]``, ``states[l]`` are None.

    ``lengths[i]`` is the number of valid cached tokens in batch slot ``i``.
    K/V rows beyond it are garbage by contract (masked until overwritten).
    A dead slot (one that holds no request: never prefilled, or released)
    keeps the length it was left with, and that length means nothing: the
    engine hands the decode and verify steps its mask of live slots, a dead
    slot's query has no valid key (``LengthMask.q_pos`` −1) whatever its
    length says, and the next prefill into the slot sets the length anew.
    The decode-shaped row write (:class:`DecodeView`, every route of
    :func:`row_write_route`) writes a live slot's rows exactly as
    :func:`_row_update` writes them, bit for bit, clamped starts included;
    a dead slot's rows it need not touch (the column kernel leaves them
    alone whenever one slot is live; ``row_dma`` and ``dus`` write them as
    they write a live slot's).
    A recurrent state has no such mask — a stale one would be USED — so a
    prefill always starts a slot's state from zeros
    (:class:`StatePrefillView`) and overwrites what the last request left.

    A registered pytree, so it threads straight through ``CompiledStep``
    arguments (and its leaves can be donated with
    ``donate_inputs=["args[i]"]`` — every leaf path under the cache
    argument matches the prefix). Leaves: the layers' K, then their V, then
    ``lengths``, then the states.
    """

    __slots__ = ("ks", "vs", "lengths", "states")

    def __init__(self, ks, vs, lengths, states=None):
        self.ks = tuple(ks)
        self.vs = tuple(vs)
        self.lengths = lengths
        self.states = (None,) * len(self.ks) if states is None \
            else tuple(states)

    def tree_flatten(self):
        return ((self.ks, self.vs, self.lengths, self.states), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @classmethod
    def from_spec(cls, spec, batch, max_len, kv_dtype=None):
        """Zeroed buffers for ``batch`` slots of ``max_len`` positions as
        ``spec`` declares them (``kv_dtype`` overrides the K/V dtype)."""
        batch, max_len = int(batch), int(max_len)
        ks, vs, states = [], [], []
        for layer in spec:
            kind = layer["kind"] if layer else None
            k = v = state = None
            if kind == "kv":
                rows = min(int(layer.get("window") or max_len), max_len)
                shape = (batch, rows, int(layer["heads"]),
                         int(layer["head_dim"]))
                dtype = kv_dtype or layer["dtype"]
                k, v = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
            elif kind == "state":
                state = {name: jnp.zeros((batch,) + tuple(shape), dtype)
                         for name, (shape, dtype) in layer["arrays"].items()}
            ks.append(k)
            vs.append(v)
            states.append(state)
        return cls(ks, vs, jnp.zeros((batch,), jnp.int32), states)

    @classmethod
    def alloc(cls, num_layers, batch, max_len, num_heads, head_dim,
              dtype=jnp.float32):
        """``num_layers`` layers of K/V alone."""
        return cls.from_spec(
            [{"kind": "kv", "heads": num_heads, "head_dim": head_dim,
              "dtype": dtype}] * int(num_layers), batch, max_len)

    # shape accessors read through Tensor leaves (inside a traced step the
    # leaves are Tensors wrapping tracers; outside, jax arrays)
    @property
    def num_layers(self):
        return len(self.ks)

    def _first_k(self):
        return _leaf(next(k for k in self.ks if k is not None))

    @property
    def batch(self):
        return int(_leaf(self.lengths).shape[0])

    @property
    def max_len(self):
        """Rows of the longest K/V entry (a ring keeps fewer)."""
        return max(int(_leaf(k).shape[1]) for k in self.ks if k is not None)

    @property
    def num_heads(self):
        return int(self._first_k().shape[2])

    @property
    def head_dim(self):
        return int(self._first_k().shape[3])

    def nbytes(self):
        """Bytes of every per-slot buffer (K/V and states; not lengths)."""
        return sum(int(_leaf(a).size) * jnp.dtype(_leaf(a).dtype).itemsize
                   for a in jax.tree_util.tree_leaves(
                       (self.ks, self.vs, self.states)))

    def __repr__(self):
        rows = self.max_len if any(k is not None for k in self.ks) else 0
        kinds = [("kv" if _leaf(k).shape[1] == rows else "ring")
                 if k is not None else "state" if st is not None
                 else "-" for k, st in zip(self.ks, self.states)]
        return (f"KVCache(batch={self.batch}, layers={kinds}, "
                f"bytes={self.nbytes()})")


# ---------------------------------------------------------------------------
# per-layer views (the duck-typed `cache=` object GPTDecoderLayer consumes)
# ---------------------------------------------------------------------------
def _row_update(buf, new, starts):
    """Batched row write: ``buf[i, starts[i]:starts[i]+s] = new[i]`` via a
    vmapped ``dynamic_update_slice`` (per-slot scalar start index, static
    shapes), every slot. XLA:CPU writes it in place in a donated buffer;
    XLA:TPU compiles it to a ``while`` of one-row updates, one trip a slot,
    at any ``head_dim``, and :func:`row_write_route` sends the decode step
    to a kernel there where one takes the shape. What it writes into a live
    slot is what every route writes there; a dead slot's rows a route need
    not touch (``KVCache``)."""

    def one(b, n, s):
        z = jnp.int32(0)
        return jax.lax.dynamic_update_slice(b, n, (s.astype(jnp.int32), z, z))

    return jax.vmap(one)(buf, new, starts)


def row_write_route(*, rows, max_len, heads, head_dim, itemsize, pallas):
    """The one place the decode-shaped row write is chosen: ``column_kernel``
    (``ops/pallas/kv_row_write.py``), ``row_dma``
    (``ops/pallas/kv_row_dma.py``) or ``dus`` (:func:`_row_update`). Shape
    facts and what the platform tells (``pallas.is_available()``: a TPU
    backend, or a test's ``interpret_mode()``) come in as arguments and no
    global state is read, so a test can ask what the chip compiles.

    The column kernel takes the layouts in which XLA:TPU puts ``max_len`` on
    the lanes (``head_dim`` under 128) in whole 128-lane columns; the DMA
    kernel those in which a row is contiguous (``head_dim`` in whole 128-lane
    tiles, heads in whole sublane tiles); XLA:CPU, and every shape neither
    kernel takes, keep the ``dynamic_update_slice``."""
    from ..ops.pallas.kv_row_dma import supports_row_dma
    from ..ops.pallas.kv_row_write import supports_row_write

    if pallas and supports_row_write(rows, max_len, heads, head_dim, itemsize):
        return "column_kernel"
    if pallas and supports_row_dma(rows, max_len, heads, head_dim, itemsize):
        return "row_dma"
    return "dus"


def _count_row_write_route(route):
    """Counter ``kv.row_write_route.<route>``: bumped when a decode-shaped
    write is TRACED (the route is a property of the compiled step, not of a
    tick), once a layer, as ``attn.decode_route.<route>`` is."""
    from ..profiler import telemetry

    if telemetry.enabled():
        telemetry.get_telemetry().inc(f"kv.row_write_route.{route}")


def cache_route(entry, max_len):
    """``ring`` or ``full``: how a ``kv`` entry of a model's ``cache_spec``
    is kept at ``max_len`` positions a slot. A window that reaches the whole
    cache is no ring. Pure; the table is in ``tests/test_kv_ring.py``, and
    the counter ``attn.cache_route.<route>`` says what a traced decode step
    was handed, once a layer."""
    window = entry.get("window")
    return "ring" if window and int(window) < int(max_len) else "full"


def count_cache_route(route):
    """Counter ``attn.cache_route.<route>``: bumped by the engine when a
    decode step is TRACED, once a ``kv`` entry."""
    from ..profiler import telemetry

    if telemetry.enabled():
        telemetry.get_telemetry().inc(f"attn.cache_route.{route}")


class DecodeView:
    """One layer's cache view for the batched decode step (and speculative
    verify's window).

    ``update(k_new, v_new)`` writes each slot's new K/V rows (decode one,
    verify ``spec_k + 1``) from that slot's position index on and returns
    the FULL buffers for attention (the length mask hides the invalid
    tail). :func:`row_write_route` picks the write: on a TPU one of two
    kernels, K and V in one call (the column kernel where the TPU keeps
    ``max_len`` on the lanes, one DMA a slot where a row is contiguous),
    else the vmapped ``dynamic_update_slice``; all agree element for
    element in every live slot. ``live`` is the engine's mask of the slots
    that hold a request (None: every slot): the column kernel visits the
    live slots alone and leaves a dead slot's rows as they were whenever
    one slot is live; ``row_dma`` and ``dus`` ignore it and write every
    slot (their share of a step is too small to gain by it). A ring's view
    carries none. A kernel writes into the buffers it is handed, so on a TPU
    the step that builds this view donates its cache (every serving step
    does; ``kv_row_write.py`` says what XLA does otherwise). The updated
    buffers stay on the view; the engine collects them into the next
    ``KVCache``.

    A ring is this view too: the engine hands it ``pos mod window`` as the
    row and, as ``mask``, what the ring's rows mean (the first ``min(pos +
    1, window)`` are valid, in whatever order: softmax does not care). A
    layer attends under ``mask`` where a view carries one, else under the
    step's.
    """

    __slots__ = ("k", "v", "pos", "mask", "live")

    def __init__(self, k, v, pos, mask=None, live=None):
        self.k = _leaf(k)
        self.v = _leaf(v)
        self.pos = _leaf(pos)
        self.mask = mask
        self.live = live

    def update(self, k_new, v_new):
        from ..ops import pallas

        kn = _leaf(k_new).astype(self.k.dtype)
        vn = _leaf(v_new).astype(self.v.dtype)
        _, max_len, heads, head_dim = self.k.shape
        route = row_write_route(
            rows=kn.shape[1], max_len=max_len, heads=heads, head_dim=head_dim,
            itemsize=self.k.dtype.itemsize, pallas=pallas.is_available())
        _count_row_write_route(route)
        if route == "column_kernel":
            from ..ops.pallas.kv_row_write import kv_row_write

            self.k, self.v = kv_row_write((self.k, self.v), (kn, vn),
                                          self.pos, self.live)
        elif route == "row_dma":
            from ..ops.pallas.kv_row_dma import kv_row_dma

            self.k, self.v = kv_row_dma((self.k, self.v), (kn, vn), self.pos)
        else:
            self.k = _row_update(self.k, kn, self.pos)
            self.v = _row_update(self.v, vn, self.pos)
        return Tensor(self.k), Tensor(self.v), self


class PrefillView:
    """One layer's cache view for the single-request prefill step.

    The prompt chunk's K/V are written into batch row ``slot`` (positions
    ``0..chunk-1``) and the CHUNK tensors are returned for attention — a
    fresh slot has no prior context, so causal attention over the padded
    chunk (with the padding masked by the caller's mask) is exact.
    """

    __slots__ = ("k", "v", "slot", "mask")

    def __init__(self, k, v, slot, mask=None):
        self.k = _leaf(k)
        self.v = _leaf(v)
        self.slot = _leaf(slot)
        self.mask = mask

    def _kept(self, new):
        """The bucket's rows as the buffer keeps them: as they come."""
        return new

    def update(self, k_new, v_new):
        kn = self._kept(_leaf(k_new).astype(self.k.dtype))
        vn = self._kept(_leaf(v_new).astype(self.v.dtype))
        z = jnp.int32(0)
        start = (self.slot.astype(jnp.int32), z, z, z)
        self.k = jax.lax.dynamic_update_slice(self.k, kn, start)
        self.v = jax.lax.dynamic_update_slice(self.v, vn, start)
        return k_new, v_new, self


class RingPrefillView(PrefillView):
    """:class:`PrefillView` of a ring of ``window`` rows: attention runs over
    the bucket's own K/V under ``mask`` (the band), and the ring is left
    with the last ``min(length, window)`` rows of the prompt, each at its
    position mod ``window``, whatever the bucket. A bucket no longer than
    the ring is written as it comes (position ``p`` IS row ``p``)."""

    __slots__ = ("length",)

    def __init__(self, k, v, slot, length, mask):
        super().__init__(k, v, slot, mask)
        self.length = _leaf(length).astype(jnp.int32)

    def _kept(self, new):
        window = self.k.shape[1]
        if new.shape[1] <= window:
            return new
        # row r holds the last position below ``length`` that is r mod
        # window (a row no position has reached yet holds padding: it is
        # written before the slot's length makes it valid)
        r = jnp.arange(window, dtype=jnp.int32)
        laps = jnp.maximum(self.length - 1 - r, 0) // window
        return jnp.take(new, r + laps * window, axis=1)


class ChunkView:
    """One layer's cache view for CHUNKED prefill (prompt chunk ``c`` of a
    long prompt, written at row ``slot`` offset ``off``).

    Unlike :class:`PrefillView` (chunk 0 only: no prior context, so the
    chunk tensors alone feed attention), a later chunk's queries must
    attend to everything already prefilled — so ``update`` writes the
    chunk's K/V at ``(slot, off)`` and returns the slot's FULL buffer row
    ``[1, max_len, heads, head_dim]`` for attention; the caller's additive
    mask admits exactly keys ``j <= off + i`` per chunk query ``i``. The
    shapes entering/leaving the step depend only on the chunk width, so
    chunked prefill compiles ONCE per chunk width regardless of prompt
    length or chunk index (``off``/``slot`` are traced scalars).

    Caller contract: ``off + chunk_width <= max_len`` — XLA clamps a
    ``dynamic_update_slice`` start so an overhanging write would silently
    shift backwards and stomp valid rows (the engine falls back to the
    one-shot bucketed prefill when a padded prompt cannot satisfy this).
    """

    __slots__ = ("k", "v", "slot", "off")

    def __init__(self, k, v, slot, off):
        self.k = _leaf(k)
        self.v = _leaf(v)
        self.slot = _leaf(slot)
        self.off = _leaf(off)

    def update(self, k_new, v_new):
        kn = _leaf(k_new).astype(self.k.dtype)  # [1, chunk, heads, head_dim]
        vn = _leaf(v_new).astype(self.v.dtype)
        z = jnp.int32(0)
        sl = self.slot.astype(jnp.int32)
        start = (sl, self.off.astype(jnp.int32), z, z)
        self.k = jax.lax.dynamic_update_slice(self.k, kn, start)
        self.v = jax.lax.dynamic_update_slice(self.v, vn, start)
        row_shape = (1,) + tuple(self.k.shape[1:])
        row_k = jax.lax.dynamic_slice(self.k, (sl, z, z, z), row_shape)
        row_v = jax.lax.dynamic_slice(self.v, (sl, z, z, z), row_shape)
        return Tensor(row_k), Tensor(row_v), self


# ---------------------------------------------------------------------------
# views of layers that keep recurrent state, or nothing
# ---------------------------------------------------------------------------
class StateDecodeView:
    """One layer's recurrent state in the batched decode step: every slot's
    arrays, read whole and replaced whole (one new position a slot)."""

    __slots__ = ("arrays",)
    valid_len = None  # every position of the step is a real one

    def __init__(self, arrays):
        self.arrays = {k: _leaf(v) for k, v in arrays.items()}

    def read(self):
        return self.arrays

    def write(self, **new):
        self.arrays = {k: _leaf(new[k]).astype(v.dtype)
                       for k, v in self.arrays.items()}


class StatePrefillView:
    """One layer's recurrent state in the single-request prefill step. The
    request starts from ZEROS whatever the slot held (a reused slot's last
    state is not masked by any length: it would be used), runs its padded
    bucket with ``valid_len`` real positions, and writes the state after the
    last of them into row ``slot``."""

    __slots__ = ("arrays", "slot", "valid_len")

    def __init__(self, arrays, slot, valid_len):
        self.arrays = {k: _leaf(v) for k, v in arrays.items()}
        self.slot = _leaf(slot).astype(jnp.int32)
        self.valid_len = _leaf(valid_len).astype(jnp.int32)

    def read(self):
        return {k: jnp.zeros((1,) + v.shape[1:], v.dtype)
                for k, v in self.arrays.items()}

    def write(self, **new):
        z = jnp.int32(0)
        self.arrays = {
            k: jax.lax.dynamic_update_slice(
                v, _leaf(new[k]).astype(v.dtype),
                (self.slot,) + (z,) * (v.ndim - 1))
            for k, v in self.arrays.items()}


class CountsView:
    """What a layer that keeps nothing per slot (``{"kind": "counts",
    "names": ...}``) is handed: ``valid [b, s]`` says which of the step's
    tokens belong to a request, and ``note`` takes the layer's int32 counts,
    which ride back to the host with the step's tokens."""

    __slots__ = ("valid", "counts")

    def __init__(self, valid):
        self.valid = _leaf(valid)
        self.counts = None

    def note(self, counts):
        self.counts = _leaf(counts).astype(jnp.int32)

"""Async host→device staging pipeline (``DeviceLoader``).

The train loops built on :class:`~paddle_tpu.io.DataLoader` produce *host*
batches (numpy, or Tensors whose arrays live on the default device): left
alone, the host→device transfer happens implicitly inside the jitted step
and sits on the device's critical path every iteration. ``DeviceLoader``
wraps any iterable of batches and stages the next ``buffer_size`` (K ≥ 2,
double-buffered) batches onto device from a background thread —
``jax.device_put`` dispatches asynchronously, so by the time the consumer
asks for batch *i*, its DMA was issued while batch *i-1* was computing.

Back-pressure comes from the bounded hand-off queue: the stager never runs
more than ``buffer_size`` batches ahead of the consumer, so host RAM and
device HBM in flight stay bounded. With a mesh/placement active, pass
``place_fn`` (e.g. a ``NamedSharding`` device_put) and every array leaf is
staged directly into its distributed layout.

Staged batches are intended to be *consumed*: pair with
``CompiledStep(donate_inputs=True)`` so each staged batch's HBM is donated
back to XLA for reuse the moment its step runs.
"""
from __future__ import annotations

import queue
import threading

import numpy as np
import jax

from ..framework.tensor import Tensor
from ..profiler import telemetry as _telemetry

__all__ = ["DeviceLoader"]

_END = object()


class _StageError:
    """Exception captured in the stager thread, re-raised by the consumer."""

    def __init__(self, exc):
        self.exc = exc


def _default_place(arr):
    return jax.device_put(arr)


def _leaf_bytes(leaf):
    v = getattr(leaf, "_value", leaf)  # Tensor -> backing array
    try:
        return int(getattr(v, "nbytes", 0) or 0)
    except Exception:
        return 0


class DeviceLoader:
    """Double-buffered host→device prefetcher over any batch iterable.

    Args:
        data: iterable of batches — a ``DataLoader``, a list of batch
            tuples, or a one-shot iterator (re-iterable sources give one
            epoch per ``iter()`` call; one-shot iterators give one total).
        buffer_size: number of staged batches the background thread may
            run ahead of the consumer; clamped to >= 2 (double buffering).
        place_fn: maps one host array leaf -> device ``jax.Array``.
            Defaults to ``jax.device_put`` onto the default device; pass a
            sharded put to stage straight into a mesh layout.

    Batch structure is preserved: array-like leaves (``Tensor``, numpy,
    ``jax.Array``) are staged, ``Tensor`` leaves stay Tensors, and
    non-array leaves pass through untouched.
    """

    def __init__(self, data, buffer_size=2, place_fn=None):
        self.data = data
        self.buffer_size = max(2, int(buffer_size))
        self.place_fn = place_fn or _default_place
        self._lock = threading.Lock()
        self._active = []  # live (thread, done-event) pairs, for shutdown()

    def __len__(self):
        return len(self.data)

    # -- staging -------------------------------------------------------------
    def _stage_leaf(self, leaf):
        if isinstance(leaf, Tensor):
            return Tensor(self.place_fn(leaf._value),
                          stop_gradient=leaf.stop_gradient)
        if isinstance(leaf, (jax.Array, np.ndarray, np.generic)):
            return self.place_fn(leaf)
        return leaf

    def _stage(self, batch):
        from ..fault import inject

        inject.check("stage")  # transient-stage-error injection point
        # Tensors are opaque to tree_flatten, so they arrive here as leaves
        with _telemetry.phase_span("h2d_copy"):
            staged = jax.tree_util.tree_map(self._stage_leaf, batch)
        if not _telemetry.enabled():
            return staged
        nbytes = sum(_leaf_bytes(l)
                     for l in jax.tree_util.tree_leaves(batch))
        tm = _telemetry.get_telemetry()
        tm.inc("device_loader.batches_staged")
        tm.inc("device_loader.bytes_staged", nbytes)
        return staged

    def _instrumented_get(self, out_q):
        """Telemetry-path queue pop: a prefetch *hit* is a batch already
        staged (get_nowait succeeds); a *miss* blocks the consumer — that
        block IS the pipeline's data-wait, accumulated as stall time."""
        tm = _telemetry.get_telemetry()
        with _telemetry.phase_span("data_wait") as wait:
            try:
                item = out_q.get_nowait()
                hit = True
            except queue.Empty:
                hit = False
                item = out_q.get()
        tm.inc("device_loader.prefetch_hit" if hit
               else "device_loader.prefetch_miss")
        if not hit:
            tm.inc("device_loader.stall_s", wait.duration_s)
        tm.set_gauge("device_loader.queue_depth", out_q.qsize())
        return item

    # -- pipeline ------------------------------------------------------------
    def _put(self, out_q, done, item):
        while not done.is_set():
            try:
                out_q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, it, out_q, done):
        from ..fault.retry import retry

        try:
            while not done.is_set():
                try:
                    batch = next(it)  # source errors propagate to consumer
                except StopIteration:
                    break
                try:
                    # transient staging failures (OSError, injected
                    # TransientError) retry with jittered backoff;
                    # anything non-OSError surfaces on the first raise
                    staged = retry(self._stage, batch, tries=3,
                                   base_delay=0.02, retry_on=(OSError,))
                except BaseException as e:
                    self._put(out_q, done, _StageError(e))
                    return
                self._put(out_q, done, staged)
        except BaseException as e:
            self._put(out_q, done, _StageError(e))
            return
        self._put(out_q, done, _END)

    def __iter__(self):
        out_q: queue.Queue = queue.Queue(maxsize=self.buffer_size)
        done = threading.Event()
        t = threading.Thread(target=self._run, args=(iter(self.data), out_q, done),
                             daemon=True, name="DeviceLoader-stager")
        entry = (t, done)
        with self._lock:
            self._active.append(entry)
        t.start()
        try:
            while True:
                if _telemetry.enabled():
                    item = self._instrumented_get(out_q)
                else:
                    item = out_q.get()
                if item is _END:
                    return
                if isinstance(item, _StageError):
                    raise item.exc
                yield item
        finally:
            done.set()
            try:  # unblock a stager waiting on a full queue
                out_q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=5.0)
            with self._lock:
                if entry in self._active:
                    self._active.remove(entry)
            self._clear_gauges()

    def _clear_gauges(self):
        """Retire this loader's point-in-time gauges (queue depth etc.) so
        a finished epoch doesn't leave stale device stats in the next
        ``telemetry.report()``; cumulative counters (prefetch hits/misses,
        bytes staged) stay. Unconditional on the enabled flag — collected
        data stays readable after ``disable()``, so stale gauges would
        too."""
        _telemetry.get_telemetry().clear_gauges("device_loader.")

    def shutdown(self):
        """Stop all live stager threads (abandoned epoch iterators)."""
        with self._lock:
            active, self._active = self._active, []
        for t, done in active:
            done.set()
            t.join(timeout=5.0)
        self._clear_gauges()

    @property
    def _live_threads(self):
        with self._lock:
            return [t for t, _ in self._active if t.is_alive()]

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass

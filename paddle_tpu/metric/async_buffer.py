"""Non-blocking metric accumulation (``AsyncMetricBuffer``).

``float(loss)`` after every jitted step fences the device: the host stalls
until the step's whole dependence chain has executed, serializing dispatch
(the gap analysis in PAPERS.md shows dispatch stalls, not FLOPs, dominate
fused steps). This buffer holds the *device* scalars and defers the
blocking readback to explicit :meth:`drain` calls — the train loops fence
only at ``log_freq`` boundaries and epoch ends, keeping the device queue
full between fences.
"""
from __future__ import annotations


import numpy as np

from ..profiler import telemetry as _telemetry

__all__ = ["AsyncMetricBuffer"]


def _as_array(v):
    # Tensor -> underlying jax.Array without forcing a transfer
    return getattr(v, "_value", v)


class AsyncMetricBuffer:
    """Accumulates device scalars; fences only on :meth:`drain`.

    ``append`` is non-blocking (it stores the ``jax.Array``/Tensor handle).
    ``drain`` performs the blocking device→host readback of everything
    pending, appends the floats to :attr:`values` in arrival order, and
    returns just the newly drained floats.
    """

    def __init__(self):
        self._pending = []
        self.values = []  # all drained floats, in append order

    def append(self, value):
        if value is not None:
            self._pending.append(_as_array(value))

    def __len__(self):
        return len(self.values) + len(self._pending)

    @property
    def num_pending(self):
        return len(self._pending)

    def drain(self):
        """Fence: read back every pending scalar. Returns the new floats."""
        pending, self._pending = self._pending, []
        if not pending:
            return []
        with _telemetry.phase_span("readback"):
            new = [float(np.asarray(v)) for v in pending]
        if _telemetry.enabled():
            tm = _telemetry.get_telemetry()
            tm.inc("metric.fences")
            tm.inc("metric.scalars_read", len(new))
        self.values.extend(new)
        return new

    def last(self):
        """Most recently *drained* value (no fence); None before any."""
        return self.values[-1] if self.values else None

    def result(self):
        """Drain anything pending and return the full history."""
        self.drain()
        return list(self.values)

"""Jittered exponential-backoff retry for transient I/O failures.

One utility serves every fault-tolerance call site — checkpoint I/O
(``fault.checkpoint``), host→device staging (``io.DeviceLoader``) and the
elastic heartbeat (``distributed.elastic``) — so backoff behavior and the
``fault.retries`` / ``fault.giveups`` telemetry counters stay uniform.

``retry(fn, *args)`` is the call form; ``retriable(...)`` the decorator
form. Only exceptions in ``retry_on`` are retried: anything else (a user
bug) propagates immediately on the first raise.
"""
from __future__ import annotations

import functools
import random
import time

__all__ = ["retry", "retriable", "TransientError"]


class TransientError(OSError):
    """An error the caller believes is transient (injected faults, flaky
    filesystems). Subclasses OSError so default retry_on catches
    it."""


def _telemetry_inc(name, n=1):
    from ..profiler import telemetry

    if telemetry.enabled():
        telemetry.get_telemetry().inc(name, n)


def retry(fn, *args, tries=3, base_delay=0.05, max_delay=2.0, jitter=0.5,
          retry_on=(OSError,), sleep=time.sleep, on_retry=None, **kwargs):
    """Call ``fn(*args, **kwargs)``; on a ``retry_on`` exception, back off
    ``base_delay * 2**attempt`` seconds (capped at ``max_delay``) plus up to
    ``jitter`` of that delay uniformly at random, then try again — at most
    ``tries`` total attempts. The final failure re-raises the last error.

    ``on_retry(attempt, exc)`` (if given) observes each retry — tests hook
    it; the elastic watch loop logs through it."""
    if tries < 1:
        raise ValueError("tries must be >= 1")
    for attempt in range(tries):
        try:
            return fn(*args, **kwargs)
        except retry_on as e:
            if attempt == tries - 1:
                _telemetry_inc("fault.giveups")
                raise
            delay = min(base_delay * (2 ** attempt), max_delay)
            delay += random.uniform(0, jitter * delay)
            _telemetry_inc("fault.retries")
            if on_retry is not None:
                on_retry(attempt, e)
            sleep(delay)


def retriable(**retry_kwargs):
    """Decorator form of :func:`retry`."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            return retry(fn, *args, **retry_kwargs, **kwargs)

        return wrapped

    return deco

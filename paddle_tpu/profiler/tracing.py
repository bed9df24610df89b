"""Request-scoped tracing: trace/span ids threaded through serving + training.

Telemetry answers *aggregate* questions (p95 TTFT, compile counts, where a
tick's time goes); it cannot answer "why was *this* request's TTFT 800 ms".
This module mints a trace id per unit of work (a served request, a train
epoch) and records parent-linked spans for every stage it passes through.

Two kinds of span land in the same ring:

* **request-scoped spans**, opened here with :func:`span` /
  :func:`start_span`: ``Scheduler.submit`` opens the request's ``request``
  root and a ``queue`` child; admit closes the queue span and opens
  ``prefill``; the first decoded token opens ONE ``decode`` span per request,
  closed at evict, whose attrs carry a stamp per token (``token_end_ns``),
  the tokens, and the id of the shared ``decode_step`` span each rode
  (``decode_steps``) — so a whole benchmark window of requests fits the
  default ring; evict closes the root. ``hapi.Model.fit`` opens
  ``<mode>_epoch`` / ``<mode>_step``, ``GenerationEngine.generate`` opens
  ``generate``, a scheduler opens ``serve_session`` / ``decode_step``.
* **phase spans**: every boundary the program marks with
  ``telemetry.phase_span(name)`` (the one call per boundary: see
  ``telemetry``'s docstring for the list) files a ``Span`` of the same name
  and the same start/end stamps under whatever span is current on the thread
  — ``serve.prefill_dispatch`` under a request's ``prefill``,
  ``serve.decode_dispatch`` under the shared ``decode_step``, ``dispatch`` or
  ``compile`` (attrs ``step``, ``compile_index``) under whoever made the
  ``CompiledStep`` call, so the export shows which request (or train step)
  paid which compile. A phase never roots a trace: with no span current it
  reaches telemetry only.

Same zero-overhead contract as ``telemetry``: everything guards on a
module-level flag, ``span()``/``start_span()`` return shared no-op
singletons while disabled, and nothing times, locks or allocates until
:func:`enable` flips it.

Export: :meth:`Tracer.export_jsonl` (one span per line, ``trace``/``span``/
``parent`` ids + ns timestamps + attrs) and :meth:`Tracer.export_chrome`
(chrome://tracing / Perfetto ``trace_events``; pass
``include_telemetry=True`` to merge the telemetry phase timeline — both run
on the same ``perf_counter_ns`` clock). Under a live ``jax.profiler`` session
the phase spans are in the profiler's own trace too, as
``paddle_tpu:<name>`` annotations on the device's clock.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time

__all__ = [
    "Span",
    "Tracer",
    "get_tracer",
    "enable",
    "disable",
    "enabled",
    "reset",
    "span",
    "start_span",
    "current_span",
    "activate",
]

_ENABLED = False


def enabled():
    """Cheap global flag every instrumentation site guards on."""
    return _ENABLED


class _NullSpan:
    """Shared no-op stand-in while tracing is disabled: supports the whole
    Span surface (context manager, ``end``, ``set_attr``) so call sites
    never branch beyond the ``enabled()`` guard. Identity-testable for the
    zero-overhead tests."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self, end_ns=None):
        return self

    def set_attr(self, key, value):
        return self

    def __bool__(self):
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One timed, attributed node of a trace tree.

    ``start_span`` creates it open; ``end()`` (or leaving it as a context
    manager) closes it and files it with the tracer. Using a span as a
    context manager also makes it the *current* span for the thread, so
    children (and ``CompiledStep`` compile events) parent under it.
    """

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start_ns",
                 "end_ns", "attrs", "tid", "_tracer", "_activated")

    def __init__(self, tracer, name, trace_id, span_id, parent_id,
                 start_ns, attrs):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_ns = start_ns
        self.end_ns = None
        self.attrs = dict(attrs) if attrs else {}
        self.tid = threading.get_ident()
        self._tracer = tracer
        self._activated = False

    def set_attr(self, key, value):
        self.attrs[key] = value
        return self

    def end(self, end_ns=None):
        """Close the span (idempotent) and file it for export."""
        if self.end_ns is None:
            self.end_ns = end_ns if end_ns is not None \
                else time.perf_counter_ns()
            self._tracer._finish(self)
        return self

    @property
    def duration_s(self):
        if self.end_ns is None:
            return None
        return (self.end_ns - self.start_ns) / 1e9

    def as_dict(self):
        return {
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "name": self.name,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "duration_s": self.duration_s,
            "tid": self.tid,
            "attrs": dict(self.attrs),
        }

    # context-manager use: active (current) for the with-body, ended on exit
    def __enter__(self):
        self._tracer._push(self)
        self._activated = True
        return self

    def __exit__(self, *exc):
        if self._activated:
            self._tracer._pop(self)
            self._activated = False
        self.end()
        return False

    def __repr__(self):
        state = "open" if self.end_ns is None else f"{self.duration_s:.6f}s"
        return (f"<Span {self.name} trace={self.trace_id} "
                f"span={self.span_id} parent={self.parent_id} {state}>")


class _Activation:
    """Context manager making an existing (externally owned) span current
    without ending it — the scheduler holds request spans open across many
    ticks but needs them current only around the engine calls."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer, span):
        self._tracer = tracer
        self._span = span

    def __enter__(self):
        if isinstance(self._span, Span):
            self._tracer._push(self._span)
        return self._span

    def __exit__(self, *exc):
        if isinstance(self._span, Span):
            self._tracer._pop(self._span)
        return False


class Tracer:
    """Process-wide span recorder. Finished spans live in a bounded ring
    (``ring_size``); ids are deterministic counters (``t0000000a`` /
    ``s0000002f``) so tests and diffs are stable run to run."""

    def __init__(self, ring_size=8192):
        self.ring_size = int(ring_size)
        self._lock = threading.Lock()
        self._finished = collections.deque(maxlen=self.ring_size)
        self._trace_ids = itertools.count(1)
        self._span_ids = itertools.count(1)
        self._tls = threading.local()
        self._dropped = 0

    # -- id minting ---------------------------------------------------------
    def new_trace_id(self):
        with self._lock:
            return f"t{next(self._trace_ids):08x}"

    def _new_span_id(self):
        with self._lock:
            return f"s{next(self._span_ids):08x}"

    # -- current-span context (per thread) ----------------------------------
    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, span):
        self._stack().append(span)

    def _pop(self, span):
        st = self._stack()
        if span in st:
            # tolerate out-of-order exits (generators, exceptions): pop
            # through to the named span rather than corrupting the stack
            while st and st[-1] is not span:
                st.pop()
            if st:
                st.pop()

    def current(self):
        st = self._stack()
        return st[-1] if st else None

    # -- span lifecycle -----------------------------------------------------
    def start_span(self, name, parent=None, trace_id=None, attrs=None,
                   start_ns=None):
        """Open a span. Parent resolution: explicit ``parent`` wins, else
        the thread's current span, else the span roots a new trace (or
        joins an explicit ``trace_id``)."""
        if parent is None and trace_id is None:
            parent = self.current()
        parent_id = None
        if isinstance(parent, Span):
            parent_id = parent.span_id
            if trace_id is None:
                trace_id = parent.trace_id
        if trace_id is None:
            trace_id = self.new_trace_id()
        return Span(self, name, trace_id, self._new_span_id(), parent_id,
                    start_ns if start_ns is not None
                    else time.perf_counter_ns(), attrs)

    def record(self, name, start_ns, end_ns, parent=None, trace_id=None,
               attrs=None):
        """Record an already-timed span (a request's terminal events)."""
        sp = self.start_span(name, parent=parent, trace_id=trace_id,
                             attrs=attrs, start_ns=start_ns)
        sp.end(end_ns)
        return sp

    def _finish(self, span):
        with self._lock:
            if len(self._finished) == self._finished.maxlen:
                self._dropped += 1
            self._finished.append(span)

    # -- read / export ------------------------------------------------------
    def spans(self, trace_id=None):
        """Finished spans (oldest first), optionally one trace's only."""
        with self._lock:
            out = list(self._finished)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        return out

    def trace_ids(self):
        with self._lock:
            seen = {}
            for s in self._finished:
                seen.setdefault(s.trace_id, None)
        return list(seen)

    @property
    def dropped(self):
        """Spans evicted from the bounded ring (long-run safety valve)."""
        with self._lock:
            return self._dropped

    def export_jsonl(self, path_or_file, trace_id=None):
        """One span per line. Reconstructing a request is a filter+sort on
        the ``trace`` field — no joins needed."""
        spans = self.spans(trace_id)
        close = False
        f = path_or_file
        if isinstance(path_or_file, (str, bytes)):
            f = open(path_or_file, "w")
            close = True
        try:
            for s in spans:
                f.write(json.dumps(s.as_dict()) + "\n")
        finally:
            if close:
                f.close()
        return len(spans)

    def export_chrome(self, path, trace_id=None, include_telemetry=False):
        """Chrome ``trace_events`` JSON. Spans become complete (``X``)
        events with trace/span/parent ids in ``args``; with
        ``include_telemetry`` the telemetry phase timeline rides along as
        ``telemetry::<phase>`` events on the same clock."""
        events = []
        for s in self.spans(trace_id):
            end = s.end_ns if s.end_ns is not None else s.start_ns
            args = {"trace": s.trace_id, "span": s.span_id,
                    "parent": s.parent_id}
            args.update({k: v for k, v in s.attrs.items()
                         if isinstance(v, (str, int, float, bool))
                         or v is None})
            events.append({
                "name": s.name, "ph": "X", "cat": "trace",
                "ts": s.start_ns / 1e3, "dur": (end - s.start_ns) / 1e3,
                "pid": 0, "tid": s.tid, "args": args,
            })
        if include_telemetry:
            from . import telemetry as _telemetry

            for name, t0, t1, tid in _telemetry.get_telemetry().chrome_spans():
                events.append({
                    "name": f"telemetry::{name}", "ph": "X",
                    "cat": "telemetry", "ts": t0 / 1e3,
                    "dur": (t1 - t0) / 1e3, "pid": 0, "tid": tid,
                })
        events.sort(key=lambda e: e["ts"])
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return len(events)

    def reset(self):
        with self._lock:
            self._finished.clear()
            self._dropped = 0
            self._trace_ids = itertools.count(1)
            self._span_ids = itertools.count(1)
        self._tls = threading.local()


_TRACER = Tracer()


def get_tracer():
    return _TRACER


def enable(ring_size=None):
    """Turn tracing on (optionally resizing the finished-span ring).
    Returns the process-wide :class:`Tracer`."""
    global _ENABLED
    if ring_size is not None and int(ring_size) != _TRACER.ring_size:
        _TRACER.ring_size = int(ring_size)
        with _TRACER._lock:
            _TRACER._finished = collections.deque(
                _TRACER._finished, maxlen=_TRACER.ring_size)
    _ENABLED = True
    return _TRACER


def disable():
    """Turn tracing off. Recorded spans stay exportable until reset()."""
    global _ENABLED
    _ENABLED = False


def reset():
    _TRACER.reset()


def span(name, parent=None, trace_id=None, attrs=None):
    """Context-managed span: current for the body, ended on exit. Shared
    no-op singleton while disabled."""
    if not _ENABLED:
        return NULL_SPAN
    return _TRACER.start_span(name, parent=parent, trace_id=trace_id,
                              attrs=attrs)


def start_span(name, parent=None, trace_id=None, attrs=None):
    """Open a long-lived span (callers hold it across event-loop ticks and
    ``end()`` it themselves). No-op singleton while disabled."""
    if not _ENABLED:
        return NULL_SPAN
    return _TRACER.start_span(name, parent=parent, trace_id=trace_id,
                              attrs=attrs)


def current_span():
    """The thread's current span, or None (always None while disabled)."""
    if not _ENABLED:
        return None
    return _TRACER.current()


def activate(span_):
    """Make an existing open span current for a ``with`` body without
    ending it. Accepts (and ignores) the null span and None."""
    if not _ENABLED or not isinstance(span_, Span):
        return NULL_SPAN
    return _Activation(_TRACER, span_)

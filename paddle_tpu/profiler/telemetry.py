"""Runtime telemetry for the async device pipeline and the serving tick.

The measurement substrate: a process-wide registry of counters, gauges and
time-histograms (extending :class:`~paddle_tpu.utils.log_writer.Monitor`)
plus a *phase timeline* kept in bounded rings.

**One call per boundary, three sinks.** A boundary in the program is marked
once, with ``with telemetry.phase_span(name):``. That one call

  (a) files the phase here (histogram ``phase.<name>``, the raw-span ring,
      and the open step/tick record) when telemetry is on;
  (b) files the parent-linked :class:`~paddle_tpu.profiler.tracing.Span`
      (same name, same start and end stamps, the current span's trace id)
      when request tracing is on and a span is current on the thread;
  (c) whenever either is on, enters
      ``jax.profiler.TraceAnnotation("paddle_tpu:<name>")``, so that under
      any live profiler session (``jax.profiler.start_trace``,
      :class:`~paddle_tpu.profiler.Profiler`) the program's spans sit in the
      xplane on the device's own clock.

With both off it returns the shared no-op singleton
(``tracing.NULL_SPAN``) and does *no* timing, allocation or locking.
``tracing.span`` / ``start_span`` stay for request-scoped spans that are no
phase of a step or tick (``request``, ``queue``, ``prefill``, ``decode``).

Phases of a train step (:data:`PHASES`; one *step record* per
``step_begin()``..``step_end()``, opened by ``Model.fit``):

  * ``data_wait`` — consumer blocked on the ``DeviceLoader`` hand-off queue
  * ``h2d_copy``  — host→device staging time in the stager thread
  * ``compile``   — a ``CompiledStep`` call that (re)traced/compiled
  * ``dispatch``  — a cached ``CompiledStep`` call (host enqueue time)
  * ``readback``  — blocking device→host fences (``AsyncMetricBuffer.drain``)

Phases of a serving tick (:data:`SERVE_PHASES`; one *tick record* per
``Scheduler.step()``, ``kind == "serve.tick"``, ``index`` the scheduler's own
``_step_idx``, ``owner`` the scheduler's id — a reader picks ticks by index,
without a clock): ``serve.tick`` ⊃ ``serve.expire``, ``serve.admit`` (⊃ per
admitted request ``serve.prefill_dispatch`` + ``serve.prefill_readback``),
``serve.prefill_chunk``, ``serve.decode_feed``, ``serve.decode_dispatch``,
``serve.decode_readback`` (the blocking token read-back), ``serve.bookkeep``;
speculative ticks put ``serve.draft``, ``serve.verify_dispatch``,
``serve.verify_readback``, ``serve.accept`` in the decode phases' place. One
``serve.queue_wait`` (due → admit) per admitted request lands in the record
of the tick that admitted it. A record keeps each phase's sum (``phases``)
and its raw intervals (``spans``).

A call that compiles is split where the time goes: ``jax.monitoring``
duration listeners (registered once, at the first ``enable()``) attribute
JAX's own trace / lowering / backend-compile-or-cache-load durations to the
``CompiledStep`` whose call is in flight: :meth:`Telemetry.compile_seconds`
is ``{step: {trace_s, lower_s, backend_s, first_run_s}}`` and a persistent
cache hit counts in ``compile.cache_hits``.

Instrumented producers run on two threads (the fit-loop consumer and the
``DeviceLoader`` stager); the registry is lock-protected and stager-side
phases are attributed to whichever record is currently open — the
overlapped-pipeline reading of "this step's h2d time".

Export surfaces: :meth:`Telemetry.export_scalars` writes JSONL scalars
through a ``utils.log_writer.LogWriter`` (rendered by
``tools/telemetry_report.py``), :meth:`Telemetry.chrome_spans` yields spans
the :class:`~paddle_tpu.profiler.profiler.Profiler` merges into its
``ProfilerResult`` chrome trace, and :func:`report` prints the summary
table. ``hapi.callbacks.TelemetryLogger`` wires all of this into
``Model.fit``.
"""
from __future__ import annotations

import collections
import threading
import time
import warnings

# at import, on the importing thread: a first import from inside a stager
# thread's phase could hold the import lock across a DataLoader's fork
from jax.profiler import TraceAnnotation as _TraceAnnotation

from ..utils.log_writer import Monitor
from . import tracing as _tracing
from .tracing import NULL_SPAN as _NULL_SPAN

__all__ = [
    "PHASES",
    "SERVE_PHASES",
    "Telemetry",
    "get_telemetry",
    "enable",
    "disable",
    "enabled",
    "reset",
    "phase_span",
    "step_begin",
    "step_end",
    "report",
    "summary",
    "serve_metrics",
]

#: canonical per-step pipeline phases, in pipeline order
PHASES = ("data_wait", "h2d_copy", "compile", "dispatch", "readback")

#: the top-level phases of one serving tick, in tick order: disjoint
#: children of ``serve.tick`` (their sum is no longer than it). A plain tick
#: runs the ``decode_*`` three, a speculative one ``draft`` / ``verify_*`` /
#: ``accept`` (and the ``decode_*`` three too when it falls back)
SERVE_PHASES = ("serve.expire", "serve.admit", "serve.prefill_chunk",
                "serve.draft", "serve.verify_dispatch",
                "serve.verify_readback", "serve.accept",
                "serve.decode_feed", "serve.decode_dispatch",
                "serve.decode_readback", "serve.bookkeep")

#: prefix of the program's host annotations in a profiler trace
ANNOTATION_PREFIX = "paddle_tpu:"

_ENABLED = False


def enabled():
    """Cheap global flag every instrumentation site guards on."""
    return _ENABLED


class _PhaseSpan:
    """The one boundary call's live object: stamps once, feeds three sinks
    (module docstring). ``name`` may be reassigned inside the body (a
    ``CompiledStep`` call learns only afterwards that it compiled): the
    phase and the ``Span`` are filed under the final name, the profiler
    annotation keeps the one it was entered with."""

    __slots__ = ("name", "key", "start_ns", "end_ns", "_attrs", "_span",
                 "_ann")

    def __init__(self, name, attrs, key):
        self.name = name
        self.key = key
        self.start_ns = self.end_ns = None
        self._attrs = attrs
        self._span = None
        self._ann = None

    def set_attr(self, key, value):
        """Attribute of the request-trace ``Span`` (dropped when tracing
        is off: telemetry keeps names and times only)."""
        if self._span is not None:
            self._span.set_attr(key, value)
        return self

    def __enter__(self):
        self._ann = _TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        if _tracing.enabled():
            tracer = _tracing.get_tracer()
            # a phase joins a trace, it never roots one: with no span
            # current (a bare train loop) only telemetry hears of it
            if tracer.current() is not None:
                self._span = tracer.start_span(
                    self.name, attrs=self._attrs, start_ns=self.start_ns)
                tracer._push(self._span)
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        if self._span is not None:
            _tracing.get_tracer()._pop(self._span)
            self._span.name = self.name
            self._span.end(self.end_ns)
        if _ENABLED:
            _TELEMETRY.add_phase(self.name, self.start_ns, self.end_ns,
                                 key=self.key)
        self._ann.__exit__(*exc)
        return False

    @property
    def duration_s(self):
        return (self.end_ns - self.start_ns) / 1e9


class _StepRecord:
    """One step's (or one serving tick's) phase breakdown: seconds per
    phase in ``phases``, the raw ``(name, start_ns, end_ns)`` intervals in
    ``spans``. ``kind`` is ``"step"`` for a train step and ``"serve.tick"``
    for a scheduler tick, whose ``index`` is the scheduler's ``_step_idx``
    and whose ``owner`` is the scheduler's id."""

    __slots__ = ("index", "start_ns", "end_ns", "phases", "kind", "owner",
                 "spans", "counts", "_prev")

    def __init__(self, index, start_ns, kind="step", owner=None):
        self.index = index
        self.start_ns = start_ns
        self.end_ns = start_ns
        self.phases = {}
        self.kind = kind
        self.owner = owner
        self.spans = []
        self.counts = {}
        self._prev = None

    @property
    def wall_s(self):
        return max(self.end_ns - self.start_ns, 0) / 1e9

    def as_dict(self):
        return {"step": self.index, "kind": self.kind, "owner": self.owner,
                "wall_s": self.wall_s, "phases": dict(self.phases),
                "spans": list(self.spans), "counts": dict(self.counts)}


#: jax.monitoring duration events -> the part of a compile they time
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
COMPILE_PARTS = ("trace_s", "lower_s", "backend_s", "first_run_s")

_WATCH = threading.local()  # .cur: the in-flight CompiledStep call's events
_LISTENING = False


def _on_duration(event, secs, **_):
    cur = getattr(_WATCH, "cur", None)
    part = _COMPILE_EVENTS.get(event) if cur is not None else None
    if part is not None:
        end = time.perf_counter_ns()
        cur[part].append((end - int(secs * 1e9), end))


def _on_event(event, **_):
    cur = getattr(_WATCH, "cur", None)
    if cur is not None and event == _CACHE_HIT_EVENT:
        cur["cache_hits"] += 1


def _listen():
    """Register the ``jax.monitoring`` listeners, once per process (they
    cannot be taken off again; with no call in flight they return at the
    first test)."""
    global _LISTENING
    if not _LISTENING:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _LISTENING = True


def _new_watch(prev=None):
    return {"trace_s": [], "lower_s": [], "backend_s": [], "cache_hits": 0,
            "prev": prev}


def compile_watch_begin():
    """``CompiledStep.__call__``: from here to ``compile_watch_end`` JAX's
    compile events on this thread belong to the calling step."""
    _WATCH.cur = _new_watch(getattr(_WATCH, "cur", None))
    return _WATCH.cur


def compile_watch_end(watch):
    _WATCH.cur = watch["prev"]


def _union_ns(intervals, lo, hi):
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, edge = 0, lo
    for a, b in sorted(intervals):
        a, b = max(a, edge), min(b, hi)
        if b > a:
            total += b - a
            edge = b
    return total


def _split_compile(watch, start_ns, end_ns):
    """Seconds of one compiling call by part. JAX's events nest (thousands
    of jitted helpers traced inside the step's trace, a trace made while
    lowering), so every instant goes to one part only: backend over
    lowering over trace; what no event covers is ``first_run_s`` (state
    snapshot, argument split, the first execution's enqueue). Unions, so
    the cost stays ``n log n`` in the number of events."""
    backend = list(watch["backend_s"])
    lower = backend + watch["lower_s"]
    trace = lower + watch["trace_s"]
    b, bl, blt = (_union_ns(iv, start_ns, end_ns)
                  for iv in (backend, lower, trace))
    return {"trace_s": (blt - bl) / 1e9, "lower_s": (bl - b) / 1e9,
            "backend_s": b / 1e9,
            "first_run_s": (end_ns - start_ns - blt) / 1e9}


class Telemetry(Monitor):
    """Process-wide counters + gauges + time-histograms + step timeline.

    Histograms reuse the inherited ``Monitor.add`` count/sum/min/max stats
    under ``phase.<name>`` keys; counters are monotonic, gauges hold the
    last value. The step timeline is a ``ring_size``-bounded deque of
    :class:`_StepRecord`; raw phase spans (for the chrome trace) live in a
    separate bounded deque so long runs can't grow memory unboundedly.
    """

    def __init__(self, ring_size=1024, recompile_warn_threshold=3):
        super().__init__()
        self.ring_size = int(ring_size)
        self.recompile_warn_threshold = int(recompile_warn_threshold)
        self._lock = threading.RLock()
        self._counters = {}
        self._gauges = {}
        self._ring = collections.deque(maxlen=self.ring_size)
        self._spans = collections.deque(maxlen=self.ring_size * 8)
        # bounded per-phase sample reservoirs for the p50/p95 columns
        # (Monitor.add only keeps count/sum/min/max)
        self._phase_samples = {}
        # same for observe() histograms (serve.ttft_s etc.): Monitor keeps
        # the EXACT running count/sum, the reservoir adds p50/p95
        self._hist_samples = {}
        self._current = None
        self._next_step = 0
        self._compiles = {}
        self._compile_s = {}
        self._warned = set()
        # step-name -> declared executable-variant count: bucketed programs
        # (one prefill executable per length bucket) compile N times BY
        # DESIGN — declaring N keeps recompile_count a churn-only signal
        self._declared = {}

    # -- scalar registry ----------------------------------------------------
    def inc(self, name, n=1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_gauge(self, name, value):
        with self._lock:
            self._gauges[name] = float(value)

    def clear_gauge(self, name):
        """Drop one gauge (a finished producer retiring its stat)."""
        with self._lock:
            self._gauges.pop(name, None)

    def clear_gauges(self, prefix):
        """Drop every gauge under ``prefix`` — e.g. a shut-down
        ``DeviceLoader`` clearing its ``device_loader.*`` stats so the next
        ``report()`` doesn't show a stale queue depth."""
        with self._lock:
            for k in [k for k in self._gauges if k.startswith(prefix)]:
                del self._gauges[k]

    def observe(self, name, seconds):
        """Time-histogram sample: exact running count/sum/min/max (Monitor)
        plus a bounded reservoir for the p50/p95 columns."""
        with self._lock:
            self.add(name, seconds)
            self._hist_samples.setdefault(
                name, collections.deque(maxlen=2048)).append(float(seconds))

    def counters(self):
        with self._lock:
            return dict(self._counters)

    def gauges(self):
        with self._lock:
            return dict(self._gauges)

    # -- step timeline ------------------------------------------------------
    def _close_record(self, cur):
        """Append a phase-bearing record to the ring and publish its wall
        time as the ``step.time_s`` gauge (the per-rank step-time signal
        the elastic heartbeat forwards for straggler detection). Caller
        holds the lock."""
        self._ring.append(cur)
        if cur.kind == "step":
            self._gauges["step.time_s"] = cur.wall_s

    def step_begin(self):
        """Open a step record, closing (and keeping) any open one that saw
        phases. Loops call this before the iteration *and* at the end of
        each body so the next batch's data_wait lands in the next record."""
        with self._lock:
            cur = self._current
            if cur is not None and cur.phases and cur.kind == "step":
                self._close_record(cur)
            self._current = _StepRecord(self._next_step,
                                        time.perf_counter_ns())
            self._next_step += 1

    def step_end(self):
        """Close the open record; empty (phase-less) records are dropped."""
        with self._lock:
            cur = self._current
            self._current = None
            if cur is not None and cur.phases:
                self._close_record(cur)

    def open_record(self, kind, index, owner=None):
        """Open a record of another kind than a train step (a serving
        tick) with the caller's own index; the record that was open waits
        underneath until :meth:`close_record`."""
        rec = _StepRecord(index, time.perf_counter_ns(), kind, owner)
        with self._lock:
            rec._prev = self._current
            self._current = rec
        return rec

    def close_record(self, rec):
        """Close (and keep) a record opened by :meth:`open_record`."""
        with self._lock:
            if self._current is rec:
                self._current = rec._prev
            rec._prev = None
            self._close_record(rec)

    def add_phase(self, name, start_ns, end_ns, key=None):
        """Record one phase span: histogram + raw-span ring + the open
        record. ``key`` says whose phase it is where several producers
        share a name (the ``CompiledStep``'s name on ``dispatch``)."""
        secs = max(end_ns - start_ns, 0) / 1e9
        tid = threading.get_ident()
        with self._lock:
            self.add(f"phase.{name}", secs)
            self._phase_samples.setdefault(
                name, collections.deque(maxlen=2048)).append(secs)
            self._spans.append((name, start_ns, end_ns, tid, key))
            cur = self._current
            if cur is not None:
                cur.phases[name] = cur.phases.get(name, 0.0) + secs
                cur.spans.append((name, start_ns, end_ns))
                cur.end_ns = max(cur.end_ns, end_ns)

    def add_count(self, name, n=1):
        """Count ``n`` of ``name`` in the open record (a serving tick's
        ``counts``: what the tick's steps routed, held, kept live) and in
        the process-wide counter of the same name."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n
            cur = self._current
            if cur is not None:
                cur.counts[name] = cur.counts.get(name, 0) + n

    def steps(self, kind=None, owner=None):
        """Closed records, oldest first (bounded by ``ring_size``);
        optionally one kind's (``"step"``, ``"serve.tick"``) or one
        owner's (a scheduler's id) only."""
        with self._lock:
            return [r for r in self._ring
                    if (kind is None or r.kind == kind)
                    and (owner is None or r.owner == owner)]

    def phase_records(self, name=None, key=None):
        """The raw-span ring as ``(name, start_ns, end_ns, tid, key)``,
        oldest first (the last ``8 * ring_size`` phases), optionally one
        name's / one key's only."""
        with self._lock:
            return [s for s in self._spans
                    if (name is None or s[0] == name)
                    and (key is None or s[4] == key)]

    # -- recompile detection ------------------------------------------------
    def note_compile(self, key, start_ns, end_ns, watch=None):
        """A ``CompiledStep`` call that traced (its ``compile`` phase is
        already filed by the call's own ``phase_span``): count it per
        step-name, split its seconds by part from the ``jax.monitoring``
        events ``watch`` collected, and warn once when the same step
        recompiles beyond the threshold — recompilation churn means
        shape/dtype instability in the feed."""
        watch = watch or _new_watch()
        split = _split_compile(watch, start_ns, end_ns)
        with self._lock:
            self._counters["compile.count"] = \
                self._counters.get("compile.count", 0) + 1
            if watch["cache_hits"]:
                self._counters["compile.cache_hits"] = (
                    self._counters.get("compile.cache_hits", 0)
                    + watch["cache_hits"])
            n = self._compiles[key] = self._compiles.get(key, 0) + 1
            parts = self._compile_s.setdefault(
                key, dict.fromkeys(COMPILE_PARTS, 0.0))
            for part, secs in split.items():
                parts[part] += secs
            threshold = max(self.recompile_warn_threshold,
                            self._declared.get(key, 1))
            warn = n > threshold and key not in self._warned
            if warn:
                self._warned.add(key)
        if warn:
            warnings.warn(
                f"CompiledStep '{key}' compiled {n} times (threshold "
                f"{threshold}) — recompilation churn usually means batch "
                f"shapes/dtypes vary step to step; pad batches to fixed "
                f"shapes (drop_last=True) to keep one cached executable",
                RuntimeWarning, stacklevel=3)

    def compile_counts(self):
        with self._lock:
            return dict(self._compiles)

    def compile_seconds(self):
        """Seconds spent in calls that traced, per step-name and part:
        ``{step: {trace_s, lower_s, backend_s, first_run_s}}`` — JAX's
        trace, its lowering to MLIR, the backend's compile or
        persistent-cache load, and the rest of those calls (the first
        run). The four sum to the calls' wall time."""
        with self._lock:
            return {k: dict(v) for k, v in self._compile_s.items()}

    def declare_variants(self, key, n):
        """Declare that step ``key`` legitimately compiles up to ``n``
        executables (one per length bucket / chunk width — the serving
        tier's compile-once-per-bucket design). ``recompile_count`` then
        counts only compiles BEYOND the declaration, so the sentinel can
        gate it at zero as a contract metric instead of absorbing the
        by-design bucket compiles as churn. Idempotent; the widest
        declaration wins."""
        with self._lock:
            self._declared[key] = max(self._declared.get(key, 1), int(n))

    def declared_variants(self):
        with self._lock:
            return dict(self._declared)

    @property
    def recompile_count(self):
        """Compilations beyond the declared variant count per step-name
        (the churn number; declarations default to 1)."""
        with self._lock:
            return sum(max(0, n - self._declared.get(k, 1))
                       for k, n in self._compiles.items())

    # -- export -------------------------------------------------------------
    @staticmethod
    def _percentile(xs, q):
        """Nearest-rank percentile over a sorted list."""
        if not xs:
            return 0.0
        idx = min(int(round(q * (len(xs) - 1))), len(xs) - 1)
        return xs[idx]

    def phase_stats(self):
        """{phase: {count, sum, min, max, mean, p50, p95}} from the
        histograms; p50/p95 come from a bounded (last 2048 samples)
        per-phase reservoir."""
        out = {}
        with self._lock:
            for key in self.names():
                if not key.startswith("phase."):
                    continue
                s = self.get(key)
                s["mean"] = s["sum"] / s["count"] if s.get("count") else 0.0
                name = key[len("phase."):]
                xs = sorted(self._phase_samples.get(name, ()))
                s["p50"] = self._percentile(xs, 0.50)
                s["p95"] = self._percentile(xs, 0.95)
                out[name] = s
        return out

    def _reservoir(self, name):
        """The bounded sample reservoir behind histogram ``name`` (phase
        histograms live under their short name). Caller holds the lock."""
        if name.startswith("phase."):
            return self._phase_samples.get(name[len("phase."):], ())
        return self._hist_samples.get(name, ())

    def histogram_stats(self, include_phases=False):
        """{name: {count, sum, min, max, mean, p50, p95}} for every
        ``observe()`` histogram — count/sum are the EXACT running totals
        (scraped rates stay correct), p50/p95 come from the bounded
        reservoirs. ``include_phases`` folds the ``phase.*`` timings in
        (the OpenMetrics exporter wants one flat view)."""
        out = {}
        with self._lock:
            for key in self.names():
                if key.startswith("phase.") and not include_phases:
                    continue
                s = self.get(key)
                s["mean"] = s["sum"] / s["count"] if s.get("count") else 0.0
                xs = sorted(self._reservoir(key))
                s["p50"] = self._percentile(xs, 0.50)
                s["p95"] = self._percentile(xs, 0.95)
                out[key] = s
        return out

    def stat(self, name, stat):
        """One scalar statistic of histogram ``name``: ``count``/``sum``/
        ``min``/``max``/``mean`` from the exact running totals, ``p<NN>``
        from the reservoir. Returns None when there are no samples (the
        SLO monitor skips the check rather than paging on nothing)."""
        with self._lock:
            s = self.get(name)
            if not s.get("count"):
                return None
            if stat == "mean":
                return s["sum"] / s["count"]
            if stat in s:
                return s[stat]
            if stat.startswith("p"):
                xs = sorted(self._reservoir(name))
                if not xs:
                    return None
                return self._percentile(xs, float(stat[1:]) / 100.0)
        raise ValueError(f"unknown histogram stat {stat!r}")

    def chrome_spans(self):
        """Buffered raw spans as (name, start_ns, end_ns, tid) tuples, on
        the same ``perf_counter_ns`` clock as the profiler's host events."""
        with self._lock:
            return [s[:4] for s in self._spans]

    def summary(self):
        with self._lock:
            recs = list(self._ring)
            wall = sum(r.wall_s for r in recs)
            per_phase = {}
            for r in recs:
                for k, v in r.phases.items():
                    per_phase[k] = per_phase.get(k, 0.0) + v
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "phases": self.phase_stats(),
                "histograms": self.histogram_stats(),
                "steps_recorded": len(recs),
                "step_wall_s": wall,
                "step_phase_s": per_phase,
                "compiles": dict(self._compiles),
                "recompile_count": sum(
                    max(0, n - self._declared.get(k, 1))
                    for k, n in self._compiles.items()),
            }

    def export_scalars(self, writer, step=None):
        """Write the registry as JSONL scalars through a ``LogWriter``:
        ``telemetry/counter/<name>``, ``telemetry/gauge/<name>``,
        ``telemetry/phase/<name>/{total_s,count,mean_s}`` (cumulative), and
        ``telemetry/step/<phase>_s`` (the latest closed step record)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            stats = self.phase_stats()
            last = self._ring[-1] if self._ring else None
            last_phases = dict(last.phases) if last is not None else {}
        for k, v in counters.items():
            writer.add_scalar(f"telemetry/counter/{k}", v, step)
        for k, v in gauges.items():
            writer.add_scalar(f"telemetry/gauge/{k}", v, step)
        for name, s in stats.items():
            writer.add_scalar(f"telemetry/phase/{name}/total_s", s["sum"], step)
            writer.add_scalar(f"telemetry/phase/{name}/count", s["count"], step)
            writer.add_scalar(f"telemetry/phase/{name}/mean_s", s["mean"], step)
            writer.add_scalar(f"telemetry/phase/{name}/p50_s", s["p50"], step)
            writer.add_scalar(f"telemetry/phase/{name}/p95_s", s["p95"], step)
        for name, s in self.histogram_stats().items():
            writer.add_scalar(f"telemetry/hist/{name}/count", s["count"], step)
            writer.add_scalar(f"telemetry/hist/{name}/sum", s["sum"], step)
            writer.add_scalar(f"telemetry/hist/{name}/mean", s["mean"], step)
            writer.add_scalar(f"telemetry/hist/{name}/p50", s["p50"], step)
            writer.add_scalar(f"telemetry/hist/{name}/p95", s["p95"], step)
        for name, v in last_phases.items():
            writer.add_scalar(f"telemetry/step/{name}_s", v, step)

    #: gauge/counter prefixes rendered in the device-stats section of
    #: ``report()`` / ``tools/telemetry_report.py`` (devprof harvest)
    DEVICE_PREFIXES = ("hbm.", "comm.", "cost.", "pipeline.", "oom.")

    def report(self, file=None):
        """Phase-breakdown + counter summary table (printed and returned,
        mirroring ``Profiler.summary``)."""
        s = self.summary()
        lines = [f"{'Phase':<12} {'Count':>8} {'Total(s)':>12} "
                 f"{'Mean(ms)':>12} {'P50(ms)':>10} {'P95(ms)':>10} "
                 f"{'Frac(%)':>9}"]
        lines.append("-" * 79)
        wall = s["step_wall_s"]
        denom = wall or sum(st["sum"] for st in s["phases"].values()) or 1.0
        order = [p for p in PHASES if p in s["phases"]]
        order += [p for p in sorted(s["phases"]) if p not in PHASES]
        for name in order:
            st = s["phases"][name]
            lines.append(
                f"{name:<12} {st['count']:>8} {st['sum']:>12.4f} "
                f"{st['mean'] * 1e3:>12.3f} {st.get('p50', 0) * 1e3:>10.3f} "
                f"{st.get('p95', 0) * 1e3:>10.3f} "
                f"{100.0 * st['sum'] / denom:>9.2f}")
        lines.append("-" * 79)
        lines.append(f"steps recorded: {s['steps_recorded']}  "
                     f"(wall {wall:.4f} s over the ring window)")
        dev_prefixes = self.DEVICE_PREFIXES

        def _is_dev(k):
            return any(k.startswith(p) for p in dev_prefixes)

        plain_counters = {k: v for k, v in s["counters"].items()
                          if not _is_dev(k)}
        dev_counters = {k: v for k, v in s["counters"].items() if _is_dev(k)}
        plain_gauges = {k: v for k, v in s["gauges"].items()
                        if not _is_dev(k)}
        dev_gauges = {k: v for k, v in s["gauges"].items() if _is_dev(k)}
        if plain_counters:
            lines.append("counters:")
            for k in sorted(plain_counters):
                v = plain_counters[k]
                lines.append(f"  {k:<38} {v:g}" if isinstance(v, float)
                             else f"  {k:<38} {v}")
        if plain_gauges:
            lines.append("gauges:")
            for k in sorted(plain_gauges):
                lines.append(f"  {k:<38} {plain_gauges[k]:g}")
        if dev_gauges or dev_counters:
            # devprof harvest: HBM breakdown / collective bytes / pipeline
            def _human(n):
                n = float(n)
                for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
                    if abs(n) < 1024.0 or unit == "TiB":
                        return (f"{int(n)} B" if unit == "B"
                                else f"{n:.1f} {unit}")
                    n /= 1024.0

            lines.append("device stats:")
            for k in sorted(dev_gauges):
                v = dev_gauges[k]
                if k.endswith(("_bytes", ".bytes")):
                    lines.append(f"  {k:<38} {_human(v)}")
                else:
                    lines.append(f"  {k:<38} {v:g}")
            for k in sorted(dev_counters):
                v = dev_counters[k]
                if ".bytes." in k:
                    lines.append(f"  {k:<38} {_human(v)}")
                else:
                    lines.append(f"  {k:<38} {v:g}" if isinstance(v, float)
                                 else f"  {k:<38} {v}")
        if s["histograms"]:
            # observe() histograms (serve.ttft_s / serve.latency_s / ...):
            # exact count+sum so rates derived downstream are correct, and
            # the reservoir percentiles alongside
            lines.append(f"histograms: {'':<15} {'Count':>8} {'Sum':>12} "
                         f"{'Mean':>10} {'P50':>10} {'P95':>10}")
            for k in sorted(s["histograms"]):
                st = s["histograms"][k]
                lines.append(
                    f"  {k:<25} {st['count']:>8} {st['sum']:>12.4f} "
                    f"{st['mean']:>10.4f} {st['p50']:>10.4f} "
                    f"{st['p95']:>10.4f}")
        if s["compiles"]:
            lines.append(f"recompiles beyond first: {s['recompile_count']}")
            for k in sorted(s["compiles"]):
                lines.append(f"  compile[{k}] x{s['compiles'][k]}")
        table = "\n".join(lines)
        print(table, file=file)
        return table

    # -- lifecycle ----------------------------------------------------------
    def reset(self, name=None):
        """``reset()`` clears everything; ``reset(name)`` keeps Monitor's
        single-stat semantics for histogram keys."""
        with self._lock:
            if name is not None:
                return super().reset(name)
            super().reset()
            self._counters.clear()
            self._gauges.clear()
            self._ring.clear()
            self._spans.clear()
            self._phase_samples.clear()
            self._hist_samples.clear()
            self._current = None
            self._next_step = 0
            self._compiles.clear()
            self._compile_s.clear()
            self._warned.clear()


_TELEMETRY = Telemetry()


def get_telemetry():
    return _TELEMETRY


def enable(ring_size=None, recompile_warn_threshold=None):
    """Turn instrumentation on (optionally retuning the registry bounds).
    Returns the process-wide :class:`Telemetry` registry."""
    global _ENABLED
    if ring_size is not None and int(ring_size) != _TELEMETRY.ring_size:
        _TELEMETRY.ring_size = int(ring_size)
        with _TELEMETRY._lock:
            _TELEMETRY._ring = collections.deque(
                _TELEMETRY._ring, maxlen=_TELEMETRY.ring_size)
            _TELEMETRY._spans = collections.deque(
                _TELEMETRY._spans, maxlen=_TELEMETRY.ring_size * 8)
    if recompile_warn_threshold is not None:
        _TELEMETRY.recompile_warn_threshold = int(recompile_warn_threshold)
    _listen()
    _ENABLED = True
    return _TELEMETRY


def disable():
    """Turn instrumentation off. Collected data stays readable (``report``/
    ``summary``/``export_scalars``) until :func:`reset`."""
    global _ENABLED
    _ENABLED = False


def reset():
    _TELEMETRY.reset()


def phase_span(name, attrs=None, key=None):
    """THE boundary call: a context manager that stamps the body once and
    feeds the phase timeline, the request trace and the profiler's trace
    (module docstring). ``attrs`` go to the trace ``Span`` only; ``key``
    tags the phase in :meth:`Telemetry.phase_records`. The shared no-op
    singleton (``tracing.NULL_SPAN``) while telemetry and tracing are both
    off."""
    if not _ENABLED and not _tracing._ENABLED:
        return _NULL_SPAN
    return _PhaseSpan(name, attrs, key)


def step_begin():
    if _ENABLED:
        _TELEMETRY.step_begin()


def step_end():
    if _ENABLED:
        _TELEMETRY.step_end()


def serve_metrics(port=0, addr="127.0.0.1"):
    """Start the opt-in OpenMetrics ``/metrics`` endpoint over this
    registry (stdlib ``http.server``, ephemeral port by default). Returns
    the :class:`~paddle_tpu.profiler.export.MetricsServer` — read the
    bound port from ``.port``, stop with ``.close()``. Rendering happens
    per scrape in the handler thread; nothing touches the instrumented hot
    paths, so the zero-overhead-when-disabled contract holds."""
    from .export import serve_metrics as _serve

    return _serve(port=port, addr=addr, telemetry=_TELEMETRY)


def summary():
    return _TELEMETRY.summary()


def report(file=None):
    return _TELEMETRY.report(file=file)

"""Runtime telemetry for the async device pipeline.

PR 1 made the train loop asynchronous (``io.DeviceLoader`` prefetch, donated
compiled steps, deferred metric readback) but opaque: a slow step could be
data-wait, compilation, dispatch, or readback and nothing said which. This
module is the measurement substrate: a process-wide registry of counters,
gauges and time-histograms (extending :class:`~paddle_tpu.utils.log_writer.
Monitor`) plus a per-step *phase timeline* kept in a bounded ring buffer.

Phases (:data:`PHASES`):

  * ``data_wait`` — consumer blocked on the ``DeviceLoader`` hand-off queue
  * ``h2d_copy``  — host→device staging time in the stager thread
  * ``compile``   — a ``CompiledStep`` call that (re)traced/compiled
  * ``dispatch``  — a cached ``CompiledStep`` call (host enqueue time)
  * ``readback``  — blocking device→host fences (``AsyncMetricBuffer.drain``)

Zero overhead when disabled (the default): every instrumentation site guards
on the module-level :func:`enabled` bool and does *no* timing, allocation or
locking until :func:`enable` flips it. ``phase_span`` returns a shared no-op
singleton while disabled.

Instrumented producers run on two threads (the fit-loop consumer and the
``DeviceLoader`` stager); the registry is lock-protected and stager-side
phases are attributed to whichever step record is currently open — the
overlapped-pipeline reading of "this step's h2d time".

Export surfaces: :meth:`Telemetry.export_scalars` writes JSONL scalars
through a ``utils.log_writer.LogWriter`` (rendered by
``tools/telemetry_report.py``), :meth:`Telemetry.chrome_spans` yields spans
the :class:`~paddle_tpu.profiler.profiler.Profiler` merges into its
``ProfilerResult`` chrome trace, and :func:`report` prints the summary
table. ``hapi.callbacks.TelemetryLogger`` wires all of this into
``Model.fit``; ``tools/bench_common.telemetry_block`` embeds the summary
into the BENCH json.
"""
from __future__ import annotations

import collections
import threading
import time
import warnings

from ..utils.log_writer import Monitor

__all__ = [
    "PHASES",
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

_ENABLED = False


def enabled():
    """Cheap global flag every instrumentation site guards on."""
    return _ENABLED


class _NullSpan:
    """Shared no-op context manager returned by ``phase_span`` when
    telemetry is disabled — identity-testable for zero-overhead checks."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _PhaseSpan:
    __slots__ = ("name", "_t0")

    def __init__(self, name):
        self.name = name
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            _TELEMETRY.add_phase(self.name, self._t0, time.perf_counter_ns())
            self._t0 = None
        return False


class _StepRecord:
    """One step's phase breakdown (seconds per phase)."""

    __slots__ = ("index", "start_ns", "end_ns", "phases")

    def __init__(self, index, start_ns):
        self.index = index
        self.start_ns = start_ns
        self.end_ns = start_ns
        self.phases = {}

    @property
    def wall_s(self):
        return max(self.end_ns - self.start_ns, 0) / 1e9

    def as_dict(self):
        return {"step": self.index, "wall_s": self.wall_s,
                "phases": dict(self.phases)}


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
        self._gauges["step.time_s"] = cur.wall_s

    def step_begin(self):
        """Open a step record, closing (and keeping) any open one that saw
        phases. Loops call this before the iteration *and* at the end of
        each body so the next batch's data_wait lands in the next record."""
        with self._lock:
            cur = self._current
            if cur is not None and cur.phases:
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

    def add_phase(self, name, start_ns, end_ns):
        """Record one phase span: histogram + chrome span + the open step."""
        secs = max(end_ns - start_ns, 0) / 1e9
        tid = threading.get_ident()
        with self._lock:
            self.add(f"phase.{name}", secs)
            self._phase_samples.setdefault(
                name, collections.deque(maxlen=2048)).append(secs)
            self._spans.append((name, start_ns, end_ns, tid))
            cur = self._current
            if cur is not None:
                cur.phases[name] = cur.phases.get(name, 0.0) + secs
                cur.end_ns = max(cur.end_ns, end_ns)

    def steps(self):
        """Closed step records, oldest first (bounded by ``ring_size``)."""
        with self._lock:
            return list(self._ring)

    # -- recompile detection ------------------------------------------------
    def note_compile(self, key, start_ns, end_ns):
        """A ``CompiledStep`` call that traced: count it per step-name and
        warn once when the same step recompiles beyond the threshold —
        recompilation churn means shape/dtype instability in the feed."""
        self.add_phase("compile", start_ns, end_ns)
        with self._lock:
            self._counters["compile.count"] = \
                self._counters.get("compile.count", 0) + 1
            n = self._compiles[key] = self._compiles.get(key, 0) + 1
            self._compile_s[key] = (self._compile_s.get(key, 0.0)
                                    + (end_ns - start_ns) / 1e9)
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
        """Seconds spent in calls that traced, per step-name: trace + XLA
        compile (or persistent-cache load) + that call's own execution."""
        with self._lock:
            return dict(self._compile_s)

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
            return list(self._spans)

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
    _ENABLED = True
    return _TELEMETRY


def disable():
    """Turn instrumentation off. Collected data stays readable (``report``/
    ``summary``/``export_scalars``) until :func:`reset`."""
    global _ENABLED
    _ENABLED = False


def reset():
    _TELEMETRY.reset()


def phase_span(name):
    """Context manager timing one phase; shared no-op when disabled."""
    if not _ENABLED:
        return _NULL_SPAN
    return _PhaseSpan(name)


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

"""From a profiler trace to busy time, per-op time and attributed idle gaps.

``load`` turns an ``.xplane.pb`` into a small plain form (JSON-able, which
is also the form of the recorded fixture under ``tests/data``):

    {"window": [start_ns, end_ns],
     "spans": [[name, start_ns, dur_ns], ...],  # host: bench:*, paddle_tpu:*
     "devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}}}

The window is the benchmark's ``bench:window`` annotation, which the
profiler records on the same clock as the device's operations; the other
``bench:*`` annotations and the program's own ``paddle_tpu:*`` phases are
the host spans that idle gaps are attributed to (no metric reads the latter).
Busy time is the *union* of one line's op intervals clipped to the window:
a sum over lines, or over a loop and the ops nested in it, would read over
the window. A trace with no device operation in the window is an error.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re

WINDOW = "bench:window"
#: host annotations kept: the benchmark's own, and the program's phases
SPAN_PREFIXES = ("bench:", "paddle_tpu:")
#: a device plane's lines: the one with every HLO op, the one with programs
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: on the chip an op's name is its whole HLO text; this much of it is kept
NAME_CHARS = 400


def start(trace_dir):
    """Start the profiler: device and host annotations, no Python call
    tracing (it slows the host loop that a serving cell measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path, platform):
    """Read an xplane file into the plain form. On the CPU (``--rehearse``)
    there is no device plane: the host threads' events that carry an
    ``hlo_op`` stand in, one pseudo-device, so that the path is exercised."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
                dev[key] = [[e.name[:NAME_CHARS], e.start_ns, e.duration_ns]
                            for e in line.events]
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif platform == "cpu":
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            dev = devices.setdefault(
                                "/host:CPU", {"ops": [], "modules": []})
                            dev["ops"].append(
                                [e.name, e.start_ns, e.duration_ns])
                            dev["modules"].append(
                                [str(st.get("hlo_module", "")), e.start_ns,
                                 e.duration_ns])
    wins = [s for s in spans if s[0] == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
    lo = wins[0][1]
    return {"window": [lo, lo + wins[0][2]],
            "spans": [s for s in spans if s[0] != WINDOW],
            "devices": devices}


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b, name))
    return out


def _union(intervals):
    """Merged, sorted ``[(a, b)]`` of possibly nested/overlapping ones."""
    merged = []
    for a, b in sorted((i[0], i[1]) for i in intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _length(merged):
    return sum(b - a for a, b in merged)


def window_s(trace):
    return (trace["window"][1] - trace["window"][0]) / 1e9


def busy_s(trace):
    """Seconds in which an op ran, averaged over the devices used."""
    lo, hi = trace["window"]
    per_dev = [_length(_union(_clip(d["ops"], lo, hi))) / 1e9
               for d in trace["devices"].values()]
    per_dev = [b for b in per_dev if b > 0]
    if not per_dev:
        raise ValueError("the trace holds no device operation inside the "
                         "window: nothing to reduce")
    return sum(per_dev) / len(per_dev)


def matched_s(trace, pattern, line="ops"):
    """Device seconds (union, clipped, averaged over devices) of the events
    of ``line`` whose name matches; None where none does."""
    rx = re.compile(pattern)
    lo, hi = trace["window"]
    per_dev = []
    for d in trace["devices"].values():
        hit = [e for e in _clip(d[line], lo, hi) if rx.search(e[2])]
        if hit:
            per_dev.append(_length(_union(hit)) / 1e9)
    return sum(per_dev) / len(per_dev) if per_dev else None


def programs(trace, pattern):
    """Programs (``XLA Modules`` events) on the first device whose name
    matches: ``{name: (runs, seconds)}``, clipped to the window. A name
    carries the program's fingerprint, so two steps jitted under one
    function name still differ."""
    rx = re.compile(pattern)
    lo, hi = trace["window"]
    dev = next(iter(trace["devices"].values()))
    out = {}
    for a, b, name in _clip(dev["modules"], lo, hi):
        if rx.search(name):
            n, s = out.get(name, (0, 0.0))
            out[name] = (n + 1, s + (b - a) / 1e9)
    return out


def busy_inside_s(trace, span_name):
    """(host seconds of the named spans, device-busy seconds inside them,
    how many spans), on the first device."""
    lo, hi = trace["window"]
    dev = next(iter(trace["devices"].values()))
    busy = _union(_clip(dev["ops"], lo, hi))
    host = inside = 0.0
    n = 0
    for a, b, _ in _clip([s for s in trace["spans"] if s[0] == span_name],
                         lo, hi):
        n += 1
        host += b - a
        inside += sum(min(b, y) - max(a, x) for x, y in busy
                      if min(b, y) > max(a, x))
    return host / 1e9, inside / 1e9, n


def span_s(trace, span_name):
    lo, hi = trace["window"]
    return sum(b - a for a, b, _ in _clip(
        [s for s in trace["spans"] if s[0] == span_name], lo, hi)) / 1e9


def breakdown(trace, top=10):
    """``device_ops``: self time by op name on the first device (a loop's
    time goes to the ops nested in it). ``idle_gaps``: the idle time of
    that device by what the host was doing: each gap goes to the innermost
    (shortest) host span that covers over half of it, failing that to the
    span covering most of it, and to ``unattributed`` if none touches it."""
    lo, hi = trace["window"]
    dev = next(iter(trace["devices"].values()))
    ev = sorted(_clip(dev["ops"], lo, hi), key=lambda e: (e[0], -e[1]))
    self_t = collections.Counter()
    stack = []  # (end, name) of enclosing events
    for a, b, name in ev:
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            self_t[stack[-1][1]] -= b - a
        self_t[name] += b - a
        stack.append((b, name))
    spans = sorted(_clip(trace["spans"], lo, hi))
    gaps = collections.Counter()
    edge, nxt, near = lo, 0, []  # near: the spans that can touch this gap
    for a, b in _union(ev) + [[hi, hi]]:
        if a > edge:
            while nxt < len(spans) and spans[nxt][0] < a:
                near.append(spans[nxt])
                nxt += 1
            near = [s for s in near if s[1] > edge]
            gaps[_host_span_of(near, edge, a)] += a - edge
        edge = max(edge, b)
    by_kind = collections.Counter()
    for name, ns in self_t.items():
        by_kind[short_name(name)] += ns
    fmt = lambda c: [[k, v / 1e9] for k, v in c.most_common(top) if v > 0]
    return {"device_ops": fmt(by_kind), "idle_gaps": fmt(gaps)}


def _host_span_of(spans, lo, hi):
    """The name, less its prefix, of the host span a gap ``[lo, hi)`` goes
    to (see ``breakdown``)."""
    best, cover, inner, width = "unattributed", 0.0, None, None
    for x, y, name in spans:
        c = min(hi, y) - max(lo, x)
        if c > cover:
            best, cover = name, c
        if 2 * c > hi - lo and (width is None or y - x < width):
            inner, width = name, y - x
    return (inner or best).partition(":")[2] or best


def short_name(hlo):
    """``%fusion.12 = f32[8,128]{...} fusion(...)`` -> ``fusion f32[8,128]``:
    the op without its number, and the first shape of its result, so that
    the same op of every layer adds up under one name."""
    head, _, rest = hlo.partition(" = ")
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    head = re.sub(r"[.\d]+$", "", head.lstrip("%"))
    return f"{head} {shape.group(0)}" if shape else head


def read(path):
    with (gzip.open if path.endswith(".gz") else open)(path, "rt") as fh:
        return json.load(fh)

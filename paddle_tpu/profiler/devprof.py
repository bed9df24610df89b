"""Device-side observability: per-step cost/memory/comm ground truth.

PR 2's telemetry layer times the *host* side of a step (data_wait, h2d,
dispatch, readback) — it cannot say where HBM goes, how much of a step is
collective traffic vs compute, or why a run OOMed. XLA already knows all of
it per compiled executable: ``compiled.memory_analysis()`` breaks the peak
device allocation into argument/output/temp/generated-code segments and
``compiled.cost_analysis()`` reports FLOPs and bytes accessed. This module
closes the loop from that compiled-executable ground truth back into the
existing telemetry/JSONL/report pipeline.

Pieces:

* :func:`normalize_cost_analysis` — one shared shim over jax's unstable
  ``cost_analysis()`` return shape (newer jax: a list of per-computation
  dicts; older: a dict; unavailable: ``None``) used by ``cost_model``
  and this module.
* :class:`MemoryBreakdown` — the HBM peak decomposition from
  ``memory_analysis()`` (``peak = argument + output + temp +
  generated_code − alias``; the alias term is the donated input bytes the
  outputs reuse).
* **Collective attribution** (:class:`CollectiveStats`) from two
  complementary sources: :func:`collectives_from_jaxpr` walks the step's
  abstract trace (reusing :mod:`paddle_tpu.analysis`) for *explicit*
  collectives (the pipeline's ppermute/psum, ring attention, shard_map
  regions) and prices each with a ring-algorithm bytes-moved model;
  :func:`collectives_from_hlo` parses the *compiled* HLO for the full set
  including GSPMD-inserted ones (dp gradient all-reduce, TP activation
  psum, the MoE all_to_all pair), mapping each op's replica groups back to
  mesh axes. The HLO view is authoritative when available.
* :func:`device_report` / :meth:`CompiledStep.device_report` — harvest a
  :class:`DeviceCostReport` for a step (shape-only lowering: arguments are
  replaced by ``ShapeDtypeStruct`` so donated/consumed batches never need
  to be touched) and register it into the process telemetry registry as
  ``hbm.*`` / ``cost.*`` / ``comm.*`` gauges and per-axis
  ``comm.bytes.<axis>`` / ``comm.count.<axis>`` counters. With telemetry
  enabled, every ``CompiledStep`` auto-harvests once on its first compile
  (:func:`maybe_harvest_on_compile`).
* **Pipeline metrics** — :func:`pipeline_bubble_fraction` (the 1F1B
  schedule's analytic bubble ``(pp−1)/(M+pp−1)``) and
  :func:`bubble_from_spans` (bubble fraction from measured/synthetic
  per-rank microbatch spans); ``PipelinedModel`` publishes them as
  ``pipeline.*`` gauges. Per-rank step-time gauges ride the elastic
  heartbeat for straggler detection (``ElasticManager.stragglers``).
* **OOM forensics** — ``CompiledStep`` dispatch catches
  ``RESOURCE_EXHAUSTED`` and :func:`dump_oom_forensics` writes a ranked
  report (memory breakdown, donation status, batch/state shapes) to
  stderr (+ JSON at ``PADDLE_TPU_OOM_DUMP``) before re-raising.
"""
from __future__ import annotations

import json
import math
import os
import re
import sys
import warnings

import numpy as np

from . import telemetry as _telemetry

__all__ = [
    "normalize_cost_analysis",
    "MemoryBreakdown",
    "CollectiveStats",
    "DeviceCostReport",
    "device_report",
    "collectives_from_jaxpr",
    "collectives_from_hlo",
    "maybe_harvest_on_compile",
    "enable_auto_harvest",
    "auto_harvest_enabled",
    "get_report",
    "last_report",
    "reports",
    "clear_reports",
    "pipeline_bubble_fraction",
    "bubble_from_spans",
    "is_oom_error",
    "OOMForensics",
    "dump_oom_forensics",
    "last_oom_report",
]

#: env var naming a directory for OOM forensics JSON dumps
OOM_DUMP_ENV = "PADDLE_TPU_OOM_DUMP"


# ---------------------------------------------------------------------------
# cost_analysis normalization (shared with cost_model)
# ---------------------------------------------------------------------------

def normalize_cost_analysis(ca):
    """``compiled.cost_analysis()`` → one flat ``{key: float}`` dict.

    Newer jax returns a list of per-computation dicts, older jax a single
    dict, and unavailable backends ``None`` — numeric values are summed
    across computations, non-numeric entries dropped. Always returns a
    dict (possibly empty), so callers never branch on the shape again."""
    if isinstance(ca, dict):
        items = [ca]
    elif isinstance(ca, (list, tuple)):
        items = [d for d in ca if isinstance(d, dict)]
    else:
        return {}
    out = {}
    for d in items:
        for k, v in d.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = out.get(k, 0.0) + float(v)
    return out


# ---------------------------------------------------------------------------
# HBM breakdown
# ---------------------------------------------------------------------------

class MemoryBreakdown:
    """Peak device-memory decomposition of one compiled executable.

    ``peak_bytes = argument + output + temp + generated_code − alias``:
    the alias term is the donated argument bytes whose buffers the outputs
    reuse (counted once, not twice).

    ``alias_unavailable=True`` marks a breakdown whose alias term could not
    be trusted: an executable deserialized from the persistent compilation
    cache reports ``alias_size_in_bytes=0`` even when donation aliases
    buffers (observed on XLA:CPU), so ``peak_bytes`` double-counts the
    donated arguments. Consumers that *gate* on the peak
    (``analysis.crosscheck_mem``, ``tools/mem_report``) skip or annotate
    such a breakdown instead of mis-gating on it."""

    __slots__ = ("argument_bytes", "output_bytes", "temp_bytes",
                 "generated_code_bytes", "alias_bytes", "alias_unavailable")

    def __init__(self, argument_bytes=0, output_bytes=0, temp_bytes=0,
                 generated_code_bytes=0, alias_bytes=0,
                 alias_unavailable=False):
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.generated_code_bytes = int(generated_code_bytes)
        self.alias_bytes = int(alias_bytes)
        self.alias_unavailable = bool(alias_unavailable)

    @property
    def peak_bytes(self):
        return (self.argument_bytes + self.output_bytes + self.temp_bytes
                + self.generated_code_bytes - self.alias_bytes)

    @classmethod
    def from_compiled(cls, compiled):
        """Harvest from ``compiled.memory_analysis()``; None when the
        backend doesn't expose it. Caveat: an executable deserialized from
        the persistent compilation cache can report ``alias_bytes=0`` even
        when donation aliases buffers (observed on XLA:CPU) — the peak is
        then a slight over-estimate."""
        try:
            ma = compiled.memory_analysis()
        except Exception:
            return None
        if ma is None:
            return None
        get = lambda k: int(getattr(ma, k, 0) or 0)  # noqa: E731
        return cls(
            argument_bytes=get("argument_size_in_bytes"),
            output_bytes=get("output_size_in_bytes"),
            temp_bytes=get("temp_size_in_bytes"),
            generated_code_bytes=get("generated_code_size_in_bytes"),
            alias_bytes=get("alias_size_in_bytes"),
        )

    def as_dict(self):
        return {
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "alias_bytes": self.alias_bytes,
            "peak_bytes": self.peak_bytes,
            "alias_unavailable": self.alias_unavailable,
        }

    def __repr__(self):
        return (f"MemoryBreakdown(peak={self.peak_bytes}, "
                f"arg={self.argument_bytes}, out={self.output_bytes}, "
                f"temp={self.temp_bytes}, "
                f"code={self.generated_code_bytes}, "
                f"alias={self.alias_bytes}"
                + (", alias_unavailable" if self.alias_unavailable else "")
                + ")")


# ---------------------------------------------------------------------------
# collective attribution
# ---------------------------------------------------------------------------

#: jaxpr collective primitives and their per-device bytes-moved factor as a
#: function of the participant count S (ring algorithms: an all-reduce is a
#: reduce-scatter + all-gather, each moving (S−1)/S of the buffer)
_COMM_FACTORS = {
    "psum": lambda s: 2.0 * (s - 1) / s,
    "psum2": lambda s: 2.0 * (s - 1) / s,
    "pmax": lambda s: 2.0 * (s - 1) / s,
    "pmin": lambda s: 2.0 * (s - 1) / s,
    "all_gather": lambda s: float(s - 1),          # input is the local shard
    "all_gather_invariant": lambda s: float(s - 1),
    "reduce_scatter": lambda s: (s - 1) / s,       # input is the full buffer
    "all_to_all": lambda s: (s - 1) / s,
    "ppermute": lambda s: 1.0,                     # full buffer, one hop
}

#: HLO collective ops → bytes-moved factor over the op's RESULT bytes
_HLO_FACTORS = {
    "all-reduce": lambda s: 2.0 * (s - 1) / s,     # result == operand
    "all-gather": lambda s: (s - 1) / s,           # result is the gathered buf
    "reduce-scatter": lambda s: float(s - 1),      # result is the local shard
    "all-to-all": lambda s: (s - 1) / s,
    "collective-permute": lambda s: 1.0,
    "collective-broadcast": lambda s: (s - 1) / s,  # root ships to s-1 peers
}

_HLO_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_HLO_OP_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)"
    r"(-start)?\(")
_HLO_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HLO_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)=(\{\}|\{\{.*?\}\}|\[[0-9,]+\]"
    r"<=\[[0-9,]+\](?:T\([0-9,]+\))?)")


class CollectiveStats:
    """Per-mesh-axis collective accounting: count, bytes moved (per
    participating device), and a per-primitive breakdown."""

    def __init__(self):
        self.by_axis = {}  # axis label -> {count, bytes, prims: {prim: n}}

    def add(self, axis, prim, nbytes, count=1):
        st = self.by_axis.setdefault(str(axis), {"count": 0, "bytes": 0.0,
                                                 "prims": {}})
        st["count"] += int(count)
        st["bytes"] += float(nbytes)
        st["prims"][prim] = st["prims"].get(prim, 0) + int(count)

    @property
    def total_bytes(self):
        return sum(st["bytes"] for st in self.by_axis.values())

    @property
    def total_count(self):
        return sum(st["count"] for st in self.by_axis.values())

    def axes(self):
        return sorted(self.by_axis)

    def as_dict(self):
        return {axis: {"count": st["count"], "bytes": st["bytes"],
                       "prims": dict(st["prims"])}
                for axis, st in self.by_axis.items()}

    def __bool__(self):
        return bool(self.by_axis)

    def __repr__(self):
        inner = ", ".join(f"{a}: {st['count']}x/{st['bytes']:.0f}B"
                          for a, st in sorted(self.by_axis.items()))
        return f"CollectiveStats({inner})"


def _subjaxprs(v):
    from ..analysis.graph_lint import _subjaxprs as sub

    return sub(v)


def _aval_bytes(aval):
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _eqn_axis_names(eqn):
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", ())
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collectives_from_jaxpr(closed_jaxpr):
    """Walk a step jaxpr for *explicit* collective primitives, tracking the
    mesh-axis sizes of enclosing ``shard_map`` regions to price each with
    the ring bytes-moved model. GSPMD-inserted collectives (sharding
    constraints on automatic axes) are invisible here — see
    :func:`collectives_from_hlo` for the compiled ground truth."""
    stats = CollectiveStats()

    def walk(jaxpr, axis_sizes):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "shard_map":
                sizes = dict(axis_sizes)
                mesh = eqn.params.get("mesh")
                try:
                    sizes.update({str(k): int(v)
                                  for k, v in dict(mesh.shape).items()})
                except Exception:
                    pass
                for v in eqn.params.values():
                    for sub in _subjaxprs(v):
                        walk(sub, sizes)
                continue
            if prim in _COMM_FACTORS:
                axes = _eqn_axis_names(eqn)
                size = 1
                for a in axes:
                    size *= int(axis_sizes.get(a, 1))
                if size > 1:
                    nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars)
                    moved = _COMM_FACTORS[prim](size) * nbytes
                    stats.add("+".join(axes), prim, moved)
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub, axis_sizes)

    walk(closed_jaxpr.jaxpr, {})
    return stats


def _decode_groups(text):
    """Decode an HLO ``replica_groups``/``source_target_pairs`` value into a
    list of partition-id groups. Handles the explicit ``{{0,1},{2,3}}`` form
    and the iota ``[G,S]<=[dims]T(perm)`` form; ``{}`` (all devices) returns
    None so the caller treats every partition as one group."""
    text = text.strip()
    if text.startswith("{"):
        inner = text[1:-1].strip()
        if not inner:
            return None  # empty => all participants
        groups = []
        for m in re.finditer(r"\{([0-9,\s]*)\}", inner):
            ids = [int(x) for x in m.group(1).replace(" ", "").split(",")
                   if x != ""]
            if ids:
                groups.append(ids)
        return groups or None
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", text)
    if not m:
        return None
    gshape = [int(x) for x in m.group(1).split(",")]
    dims = [int(x) for x in m.group(2).split(",")]
    perm = ([int(x) for x in m.group(3).split(",")] if m.group(3)
            else list(range(len(dims))))
    arr = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm)
    arr = arr.reshape(gshape)
    return [list(map(int, row)) for row in arr]


def _axis_label(members, mesh_dims, axis_names, pairwise=False):
    """Mesh axes that vary across a replica group (or across
    source/target pairs), joined with '+' in mesh order."""
    coords = [np.unravel_index(int(g) % int(np.prod(mesh_dims)), mesh_dims)
              for g in members]
    if pairwise:
        varying = set()
        for i in range(0, len(coords) - 1, 2):
            a, b = coords[i], coords[i + 1]
            for d in range(len(mesh_dims)):
                if a[d] != b[d]:
                    varying.add(d)
    else:
        varying = {d for d in range(len(mesh_dims))
                   if len({c[d] for c in coords}) > 1}
    if not varying:
        return None
    return "+".join(axis_names[d] for d in sorted(varying))


def collectives_from_hlo(hlo_text, mesh=None):
    """Scan optimized HLO text for collective ops (including the
    GSPMD-inserted ones) and attribute each to the mesh axes its replica
    groups span. Partition ids are mapped to mesh coordinates assuming the
    executable's device assignment follows ``mesh.devices`` order (true for
    jitted NamedSharding programs). With no mesh, axes are labelled
    ``unmapped``. Bytes are per participating device, priced with the same
    ring model as the jaxpr walk."""
    stats = CollectiveStats()
    if mesh is not None:
        mesh_dims = tuple(int(s) for s in mesh.devices.shape)
        axis_names = tuple(str(a) for a in mesh.axis_names)
        n_part = int(np.prod(mesh_dims))
    else:
        mesh_dims = axis_names = None
        n_part = 0
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.search(line)
        if m is None:
            continue
        op, is_start = m.group(1), bool(m.group(2))
        head = line[:m.start()]
        shapes = []
        for dm in _HLO_SHAPE_RE.finditer(head):
            dt, dims = dm.group(1), dm.group(2)
            if dt not in _HLO_DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            shapes.append(n * _HLO_DTYPE_BYTES[dt])
        if not shapes:
            continue
        # async *-start ops repeat the buffer in their result tuple; take
        # the largest element instead of double counting
        nbytes = max(shapes) if is_start else sum(shapes)
        if is_start and op == "reduce-scatter":
            # For reduce-scatter the largest tuple element of the -start op is
            # the *input* (size x result), but _HLO_FACTORS prices the result
            # shard.  Rescale so sync and async forms price identically.
            gm0 = _HLO_GROUPS_RE.search(line)
            g0 = _decode_groups(gm0.group(1)) if gm0 else None
            sz = len(g0[0]) if g0 else (n_part or 2)
            if sz > 1:
                nbytes = nbytes // sz
        gm = _HLO_GROUPS_RE.search(line)
        groups = _decode_groups(gm.group(1)) if gm else None
        pairwise = op == "collective-permute"
        if groups is None:
            members = list(range(n_part)) if n_part else []
            size = len(members) or 2  # unknown world: assume pairs
        else:
            if pairwise:
                members = [g for grp in groups for g in grp]
                size = 2
            else:
                members = groups[0]
                size = max(len(g) for g in groups)
        if size <= 1:
            continue  # degenerate single-member groups: no traffic
        if mesh is not None and members:
            label = _axis_label(members, mesh_dims, axis_names,
                                pairwise=pairwise)
            if label is None:
                continue
        else:
            label = "unmapped"
        stats.add(label, op, _HLO_FACTORS[op](size) * nbytes)
    return stats


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

def _fmt_bytes(n):
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0


class DeviceCostReport:
    """Compile-time cost/memory/comm ground truth for one compiled step.

    Attributes:
        name: step name.
        flops / bytes_accessed / optimal_seconds: XLA cost analysis of the
            whole executable (flops include remat recompute — the honest
            hardware-utilization number).
        memory: :class:`MemoryBreakdown` or None.
        collectives: authoritative per-axis :class:`CollectiveStats`
            (compiled-HLO view when available, else the jaxpr view).
        collectives_traced: the jaxpr (explicit-collective) view, kept for
            cross-checking.
        comm_source: ``"hlo"`` | ``"jaxpr"`` | ``"none"``.
    """

    def __init__(self, name, flops=0.0, bytes_accessed=0.0,
                 optimal_seconds=0.0, memory=None, collectives=None,
                 collectives_traced=None, comm_source="none", cost_raw=None):
        self.name = name
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.optimal_seconds = float(optimal_seconds)
        self.memory = memory
        self.collectives = collectives or CollectiveStats()
        self.collectives_traced = collectives_traced or CollectiveStats()
        self.comm_source = comm_source
        self.cost_raw = dict(cost_raw or {})

    @property
    def comm_bytes(self):
        """Interconnect bytes moved per device per step (authoritative)."""
        return self.collectives.total_bytes

    @property
    def comm_fraction(self):
        """Share of the step's memory traffic that crosses the
        interconnect: ``comm_bytes / (comm_bytes + bytes_accessed)``.
        0.0 on a single device; → 1.0 for pure-communication programs."""
        denom = self.comm_bytes + self.bytes_accessed
        return self.comm_bytes / denom if denom > 0 else 0.0

    def as_dict(self):
        return {
            "name": self.name,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "optimal_seconds": self.optimal_seconds,
            "memory": self.memory.as_dict() if self.memory else None,
            "collectives": self.collectives.as_dict(),
            "collectives_traced": self.collectives_traced.as_dict(),
            "comm_source": self.comm_source,
            "comm_bytes": self.comm_bytes,
            "comm_fraction": self.comm_fraction,
        }

    def register(self, tm=None):
        """Publish into the telemetry registry: ``hbm.*`` / ``cost.*`` /
        ``comm.*`` gauges plus per-axis ``comm.{bytes,count}.<axis>``
        counters (counters accumulate across harvested steps)."""
        tm = tm or _telemetry.get_telemetry()
        if self.memory is not None:
            for k, v in self.memory.as_dict().items():
                tm.set_gauge(f"hbm.{k}", v)
        tm.set_gauge("cost.flops", self.flops)
        tm.set_gauge("cost.bytes_accessed", self.bytes_accessed)
        if self.optimal_seconds:
            tm.set_gauge("cost.optimal_seconds", self.optimal_seconds)
        tm.set_gauge("comm.bytes", self.comm_bytes)
        tm.set_gauge("comm.fraction", self.comm_fraction)
        for axis, st in self.collectives.by_axis.items():
            tm.inc(f"comm.bytes.{axis}", int(st["bytes"]))
            tm.inc(f"comm.count.{axis}", int(st["count"]))
        return self

    def table(self):
        """Human-readable summary (mirrors ``telemetry.report`` style)."""
        lines = [f"device cost report — {self.name}"]
        lines.append(f"  flops          {self.flops:,.0f}")
        lines.append(f"  bytes accessed {_fmt_bytes(self.bytes_accessed)}")
        if self.optimal_seconds:
            lines.append(f"  optimal time   {self.optimal_seconds:.6f} s")
        if self.memory is not None:
            md = self.memory.as_dict()
            peak = md.pop("peak_bytes") or 1
            alias = md.pop("alias_bytes")
            alias_unavailable = md.pop("alias_unavailable", False)
            lines.append(f"  hbm peak       {_fmt_bytes(peak)}")
            for k, v in sorted(md.items(), key=lambda kv: -kv[1]):
                if v:
                    lines.append(f"    {k:<22} {_fmt_bytes(v):>12} "
                                 f"({100.0 * v / peak:5.1f}%)")
            if alias:
                lines.append(f"    {'alias_bytes (reused)':<22} "
                             f"{'-' + _fmt_bytes(alias):>12}")
            if alias_unavailable:
                lines.append("    alias term unavailable (persistent-cache "
                             "executable): peak over-counts donated args")
        if self.collectives:
            lines.append(f"  collectives ({self.comm_source}): "
                         f"{_fmt_bytes(self.comm_bytes)} moved/device, "
                         f"comm_fraction {self.comm_fraction:.4f}")
            for axis in self.collectives.axes():
                st = self.collectives.by_axis[axis]
                prims = ",".join(f"{p}x{n}" for p, n in
                                 sorted(st["prims"].items()))
                lines.append(f"    axis {axis:<12} {st['count']:>4} ops "
                             f"{_fmt_bytes(st['bytes']):>12}  [{prims}]")
        else:
            lines.append("  collectives: none (single device)")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# harvesting
# ---------------------------------------------------------------------------

_REPORTS = {}
_LAST_NAME = None
_AUTO = True


def enable_auto_harvest(flag=True):
    """Gate the once-per-step auto-harvest ``CompiledStep`` runs on its
    first compile while telemetry is enabled (on by default)."""
    global _AUTO
    _AUTO = bool(flag)


def auto_harvest_enabled():
    return _AUTO


def get_report(name):
    """Harvested :class:`DeviceCostReport` by step name, or None."""
    return _REPORTS.get(name)


def last_report():
    """The most recently harvested report (or None)."""
    return _REPORTS.get(_LAST_NAME) if _LAST_NAME else None


def reports():
    return dict(_REPORTS)


def clear_reports():
    global _LAST_NAME
    _REPORTS.clear()
    _LAST_NAME = None


def _lower_isolated(step, args, kwargs):
    """Lower the step through a FRESH ``jax.jit`` instance. Going through
    ``step.lower`` (i.e. ``step._jitted``) would populate the step's own
    tracing cache with the harvest-time state signature — and a state
    whose pytree evolves across calls (the lazy-accumulator pattern the
    graph lint exists to catch) would then dispatch its next call from the
    harvest's cache entry without visibly re-tracing, corrupting the
    compile/recompile telemetry contract. XLA's compilation cache still
    dedupes the underlying executable."""
    import jax

    donate = (0,) if step.donate_state else ()
    donate = donate + (1,)
    # the lambda gives the harvest its own function identity: jax's trace
    # cache is keyed on the wrapped callable, so jitting step._pure
    # directly would still share (and pre-populate) the step's entries
    pure = step._pure
    jitted = jax.jit(lambda *a: pure(*a), donate_argnums=donate,
                     static_argnums=(3,))
    state = step.spec.snapshot()
    dyn_donated, dyn_kept, static = step._prepare(args, kwargs)
    try:
        return jitted.lower(state, dyn_donated, dyn_kept, static)
    finally:
        # pure()'s own finally restores the pre-trace state; lazily-born
        # leaves would be tracers there (see analysis.trace_step) — the
        # wholesale re-install below keeps framework state eager
        step.spec.install(state)
        step.spec.clear_grads()


def _shape_only(tree):
    """Replace array-like leaves with ``ShapeDtypeStruct`` (keeping the
    sharding, so the lowered program sees the same SPMD partitioning) —
    lowering never touches real, possibly-donated buffers."""
    import jax

    from ..framework.tensor import Tensor

    def leaf(x):
        if isinstance(x, Tensor):
            x = x._value
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            sharding = getattr(x, "sharding", None)
            try:
                return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                            sharding=sharding)
            except Exception:
                return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def _infer_mesh(step, args, kwargs):
    """Best-effort mesh discovery: a NamedSharding on any argument or
    state leaf (size > 1)."""
    import jax
    from jax.sharding import NamedSharding

    from ..framework.tensor import Tensor

    def scan(tree):
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, Tensor):
                leaf = leaf._value
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
                return sh.mesh
        return None

    mesh = scan((args, kwargs))
    if mesh is None:
        try:
            mesh = scan(step.spec.snapshot())
        except Exception:
            mesh = None
    return mesh


def device_report(step, *args, mesh=None, name=None, register=None, **kwargs):
    """Harvest a :class:`DeviceCostReport` for ``step`` against the example
    batch (real arrays, Tensors, or ``ShapeDtypeStruct``s — arrays are
    reduced to shapes first, so donated batches are safe to pass).

    Lowers and compiles the step (XLA dedupes against its compile cache),
    reads ``memory_analysis``/``cost_analysis``, attributes collectives
    from the compiled HLO (falling back to the jaxpr walk when HLO text is
    unavailable), stores the report in the process registry
    (:func:`get_report`) and — when telemetry is enabled, or
    ``register=True`` — publishes the ``hbm.*``/``cost.*``/``comm.*``
    telemetry scalars."""
    global _LAST_NAME

    from ..jit.functionalize import CompiledStep

    if not isinstance(step, CompiledStep):
        step = CompiledStep(step, stateful=(), donate_state=False)
    sds_args, sds_kwargs = _shape_only((args, kwargs))
    if mesh is None:
        mesh = _infer_mesh(step, args, kwargs)

    traced = CollectiveStats()
    try:
        from .. import analysis

        graph = analysis.trace_step(step, *sds_args, **sds_kwargs)
        traced = collectives_from_jaxpr(graph.closed_jaxpr)
    except Exception as e:  # noqa: BLE001 - advisory view only
        warnings.warn(f"devprof jaxpr collective walk failed on "
                      f"'{step.name}': {e!r}", RuntimeWarning)

    lowered = _lower_isolated(step, sds_args, sds_kwargs)
    compiled = lowered.compile()
    memory = MemoryBreakdown.from_compiled(compiled)
    if (memory is not None and memory.alias_bytes == 0
            and (getattr(step, "donate_state", False)
                 or getattr(step, "donate_inputs", False))):
        # the step donates buffers, yet the executable reports zero alias
        # bytes: the persistent-cache deserialization path loses the alias
        # table (XLA:CPU) — flag it so peak-gating consumers skip this one
        memory.alias_unavailable = True
    try:
        cost = normalize_cost_analysis(compiled.cost_analysis())
    except Exception:
        cost = {}

    hlo_stats = None
    try:
        hlo_stats = collectives_from_hlo(compiled.as_text(), mesh=mesh)
    except Exception as e:  # noqa: BLE001 - fall back to the jaxpr view
        warnings.warn(f"devprof HLO collective scan failed on "
                      f"'{step.name}': {e!r}", RuntimeWarning)
    if hlo_stats is not None and (hlo_stats or not traced):
        coll, source = hlo_stats, "hlo"
    elif traced:
        coll, source = traced, "jaxpr"
    else:
        coll, source = CollectiveStats(), "none"

    rep = DeviceCostReport(
        name=name or step.name,
        flops=cost.get("flops", 0.0),
        bytes_accessed=cost.get("bytes accessed", 0.0),
        optimal_seconds=cost.get("optimal_seconds", 0.0),
        memory=memory,
        collectives=coll,
        collectives_traced=traced,
        comm_source=source,
        cost_raw=cost,
    )
    _REPORTS[rep.name] = rep
    _LAST_NAME = rep.name
    if register is None:
        register = _telemetry.enabled()
    if register:
        rep.register()
    return rep


def maybe_harvest_on_compile(step, args, kwargs):
    """Once-per-step harvest hook ``CompiledStep.__call__`` fires after a
    traced call while telemetry is enabled. Never raises — observability
    must not take down a training run."""
    if not (_AUTO and _telemetry.enabled()):
        return None
    if getattr(step, "_devprof_done", False):
        return None
    try:
        step._devprof_done = True
    except Exception:
        return None
    try:
        return device_report(step, *args, **kwargs)
    except Exception as e:  # noqa: BLE001 - advisory pass only
        warnings.warn(f"devprof harvest failed on '{step.name}': {e!r}",
                      RuntimeWarning)
        return None


# ---------------------------------------------------------------------------
# pipeline / straggler metrics
# ---------------------------------------------------------------------------

def pipeline_bubble_fraction(num_microbatches, pp_degree):
    """Analytic 1F1B/GPipe schedule bubble: with M microbatches over pp
    stages the scan runs ``T = M + pp − 1`` ticks of which ``pp − 1`` are
    ramp-up/drain bubbles on every stage → ``(pp−1)/(M+pp−1)``."""
    m, pp = int(num_microbatches), int(pp_degree)
    if m <= 0 or pp <= 1:
        return 0.0
    return (pp - 1) / float(m + pp - 1)


def bubble_from_spans(spans):
    """Bubble fraction from measured (or synthetic) per-rank microbatch
    phase spans.

    Args:
        spans: ``{rank: [(start, end), ...]}`` or an iterable of
            ``(rank, start, end)`` tuples, on any consistent clock.

    Returns ``{"window_s", "per_rank": {rank: bubble}, "bubble_fraction"}``
    where each rank's bubble is the fraction of the global busy window
    it spent idle, and ``bubble_fraction`` is their mean."""
    if not isinstance(spans, dict):
        folded = {}
        for rank, t0, t1 in spans:
            folded.setdefault(rank, []).append((t0, t1))
        spans = folded
    all_spans = [s for ss in spans.values() for s in ss]
    if not all_spans:
        return {"window_s": 0.0, "per_rank": {}, "bubble_fraction": 0.0}
    t0 = min(s[0] for s in all_spans)
    t1 = max(s[1] for s in all_spans)
    window = max(t1 - t0, 0.0)
    per_rank = {}
    for rank, ss in spans.items():
        busy = sum(max(e - b, 0.0) for b, e in ss)
        per_rank[rank] = (max(1.0 - busy / window, 0.0) if window > 0
                          else 0.0)
    frac = (math.fsum(per_rank.values()) / len(per_rank)) if per_rank else 0.0
    return {"window_s": window, "per_rank": per_rank,
            "bubble_fraction": frac}


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_LAST_OOM = None


def is_oom_error(err):
    """Does this dispatch-time exception look like a device OOM? XLA
    surfaces them as ``XlaRuntimeError: RESOURCE_EXHAUSTED: ...``; the
    fault-injection stand-in carries the same marker."""
    return "RESOURCE_EXHAUSTED" in str(err)


def _leaf_meta(tree, prefix):
    """Flatten a pytree into (path, shape, dtype, nbytes) rows, largest
    first. Reads only array *metadata* — safe on donated/deleted buffers."""
    import jax

    from ..framework.tensor import Tensor

    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if isinstance(leaf, Tensor):
            leaf = leaf._value
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        try:
            dtype = np.dtype(getattr(leaf, "dtype", np.float32))
            nbytes = int(np.prod(shape)) * dtype.itemsize
        except Exception:
            continue
        rows.append({
            "path": prefix + jax.tree_util.keystr(tuple(path)),
            "shape": tuple(int(s) for s in shape),
            "dtype": str(dtype),
            "nbytes": nbytes,
        })
    rows.sort(key=lambda r: -r["nbytes"])
    return rows


class OOMForensics:
    """Structured post-mortem of a ``RESOURCE_EXHAUSTED`` dispatch: the
    compiled memory breakdown (when a harvest exists), donation status,
    and the batch/state arrays ranked by size."""

    def __init__(self, step_name, error, memory=None, donation=None,
                 batch=None, state=None, collectives=None):
        self.step_name = step_name
        self.error = str(error)
        self.memory = memory
        self.donation = dict(donation or {})
        self.batch = list(batch or [])
        self.state = list(state or [])
        self.collectives = dict(collectives or {})

    def as_dict(self):
        return {
            "step": self.step_name,
            "error": self.error,
            "memory": (self.memory.as_dict()
                       if isinstance(self.memory, MemoryBreakdown)
                       else self.memory),
            "donation": self.donation,
            "batch": self.batch,
            "state": self.state,
            "collectives": self.collectives,
        }

    @classmethod
    def from_dict(cls, d):
        mem = d.get("memory")
        if isinstance(mem, dict):
            mem = MemoryBreakdown(
                argument_bytes=mem.get("argument_bytes", 0),
                output_bytes=mem.get("output_bytes", 0),
                temp_bytes=mem.get("temp_bytes", 0),
                generated_code_bytes=mem.get("generated_code_bytes", 0),
                alias_bytes=mem.get("alias_bytes", 0),
                alias_unavailable=mem.get("alias_unavailable", False))
        return cls(d.get("step", "?"), d.get("error", ""), memory=mem,
                   donation=d.get("donation"), batch=d.get("batch"),
                   state=d.get("state"), collectives=d.get("collectives"))

    def report(self):
        lines = [f"OOM forensics — step '{self.step_name}' hit "
                 f"RESOURCE_EXHAUSTED at dispatch"]
        lines.append(f"  error: {self.error.splitlines()[0][:200]}")
        if isinstance(self.memory, MemoryBreakdown):
            md = self.memory.as_dict()
            peak = md.pop("peak_bytes") or 1
            alias = md.pop("alias_bytes")
            md.pop("alias_unavailable", None)
            lines.append(f"  compiled memory breakdown "
                         f"(peak {_fmt_bytes(peak)}):")
            for k, v in sorted(md.items(), key=lambda kv: -kv[1]):
                if v:
                    lines.append(f"    {k:<22} {_fmt_bytes(v):>12} "
                                 f"({100.0 * v / peak:5.1f}%)")
            if alias:
                lines.append(f"    {'alias_bytes (reused)':<22} "
                             f"{'-' + _fmt_bytes(alias):>12}")
        else:
            lines.append("  compiled memory breakdown: unavailable "
                         "(step failed before/without a harvest)")
        don = self.donation
        lines.append(f"  donation: donate_state={don.get('donate_state')} "
                     f"donate_inputs={don.get('donate_inputs')}"
                     + (f" paths={don.get('donate_paths')}"
                        if don.get("donate_paths") else ""))
        if not don.get("donate_inputs"):
            lines.append("    hint: staged single-use batches can hand "
                         "their HBM back via donate_inputs=True")
        if self.batch:
            lines.append("  batch arrays (largest first):")
            for r in self.batch[:8]:
                lines.append(f"    {r['path']:<28} {str(r['shape']):<20} "
                             f"{r['dtype']:<10} {_fmt_bytes(r['nbytes'])}")
        if self.state:
            lines.append("  largest state arrays:")
            for r in self.state[:10]:
                lines.append(f"    {r['path']:<44} "
                             f"{_fmt_bytes(r['nbytes'])}")
        return "\n".join(lines)


def last_oom_report():
    """The most recent :class:`OOMForensics` (or None)."""
    return _LAST_OOM


def dump_oom_forensics(step, err, args, kwargs, file=None):
    """Build, print (stderr) and remember the forensics for an OOM raised
    at ``step``'s dispatch; with ``PADDLE_TPU_OOM_DUMP=<dir>`` also writes
    ``oom_<step>.json`` there. The caller re-raises the original error."""
    global _LAST_OOM

    rep = _REPORTS.get(getattr(step, "name", None))
    donation = {
        "donate_state": bool(getattr(step, "donate_state", False)),
        "donate_inputs": bool(getattr(step, "donate_inputs", False)),
        "donate_paths": list(getattr(step, "_donate_paths", None) or []),
    }
    try:
        state_rows = _leaf_meta(step.spec.snapshot(), "state")[:16]
    except Exception:
        state_rows = []
    fo = OOMForensics(
        step_name=getattr(step, "name", "?"),
        error=err,
        memory=rep.memory if rep is not None else None,
        donation=donation,
        batch=_leaf_meta((args, kwargs or {}), "args")[:16],
        state=state_rows,
        collectives=rep.collectives.as_dict() if rep is not None else {},
    )
    _LAST_OOM = fo
    print(fo.report(), file=file or sys.stderr)
    if _telemetry.enabled():
        _telemetry.get_telemetry().inc("oom.count")
    dump_dir = os.environ.get(OOM_DUMP_ENV, "").strip()
    if dump_dir:
        try:
            os.makedirs(dump_dir, exist_ok=True)
            path = os.path.join(dump_dir, f"oom_{fo.step_name}.json")
            with open(path, "w") as f:
                json.dump(fo.as_dict(), f, indent=1)
        except Exception as e:  # noqa: BLE001 - forensics must not mask OOM
            print(f"OOM forensics dump to {dump_dir} failed: {e!r}",
                  file=sys.stderr)
    return fo

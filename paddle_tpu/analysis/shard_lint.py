"""Static SPMD sharding lint: predict collectives before a multichip run.

PR 3's graph lint sees a single-device jaxpr and PR 5's devprof measures
collective bytes only *after* XLA compiled the program. This module closes
the gap: it propagates shardings **abstractly** over the step jaxpr under a
given :class:`jax.sharding.Mesh` — no device execution and no XLA
invocation, the same contract as :func:`paddle_tpu.analysis.trace_step` —
and predicts, per equation, the collectives GSPMD will insert (op, mesh
axis, bytes) priced with the same ring model
:func:`paddle_tpu.profiler.devprof.collectives_from_jaxpr` uses, plus a
predicted ``comm_fraction``.

The model (the GSPMD propagation rules that matter in practice):

* a ``dot_general`` whose contraction dims are sharded on axis ``a``
  produces partial sums → ring **all-reduce** over ``a`` of the (local)
  result — this one rule covers both the TP row-parallel activation psum
  (forward) and the dp gradient all-reduce (backward: the batch dim is the
  contraction dim of every weight-gradient matmul);
* a ``sharding_constraint`` that *removes* axes from the propagated
  sharding forces an **all-gather** (axes moved between dims: an
  **all-to-all**; axes added: a free local slice);
* elementwise ops unify operand shardings (conflicts = an implicit
  reshard of the minority operand);
* explicit collectives inside ``shard_map`` regions are priced exactly
  (local block shapes × the ring factors — the jaxpr view devprof already
  trusts).

Bytes are **per participating device on local (post-partition) shapes**,
matching what :func:`devprof.collectives_from_hlo` measures from the
compiled HLO — :func:`paddle_tpu.analysis.crosscheck.crosscheck_comm`
joins the two (the accuracy loop; the dp×mp and MoE MULTICHIP configs
agree within 10%, exactly for explicit shard_map collectives).

Entry points::

    sa = shard_lint.analyze_sharding(step, x, y, mesh=mesh)
    print(sa.table())           # per-axis predicted bytes
    sa.collectives              # devprof.CollectiveStats (predicted)
    sa.comm_fraction            # comm / (comm + memory-traffic proxy)

``lint_step(step, x, y, mesh=mesh)`` attaches the analysis to the traced
``StepGraph`` so the ``spmd-*`` rules in :mod:`.rules` run over it, and
``tools/shard_lint.py`` drives the MULTICHIP zoo configs from the CLI.
"""
from __future__ import annotations


import numpy as np

__all__ = [
    "ShardingAnalysis",
    "PredictedCollective",
    "Reshard",
    "analyze_sharding",
    "propagate_jaxpr",
    "spec_from_sharding",
    "SHARD_LINT_DEFAULTS",
]

#: thresholds consumed by the spmd-* rules (merged into StepGraph.config)
SHARD_LINT_DEFAULTS = {
    # spmd-comm-bound-step fires above this predicted comm_fraction
    "comm_bound_fraction": 0.25,
    # spmd-replicated-optimizer-state fires above this many replicated
    # accumulator bytes (per device)
    "zero_min_bytes": 1 << 20,
}

# an empty per-dim axis assignment (replicated) — specs are tuples of
# per-dim tuples of mesh-axis names, e.g. (("dp",), ()) for P("dp", None)
_R = ()


def _aval_shape_dtype(aval):
    shape = tuple(int(s) for s in getattr(aval, "shape", ()))
    return shape, getattr(aval, "dtype", None)


def _aval_bytes(aval):
    shape, dtype = _aval_shape_dtype(aval)
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:  # extended dtypes (PRNG keys)
        return 0
    n = 1
    for s in shape:
        n *= s
    return n * itemsize


def spec_from_sharding(sharding, ndim):
    """``NamedSharding`` → per-dim axis-name tuples (length ``ndim``).
    Anything else (None, GSPMD opaque, single-device) → fully replicated."""
    try:
        from jax.sharding import NamedSharding
    except Exception:  # pragma: no cover
        return tuple(_R for _ in range(ndim))
    if not isinstance(sharding, NamedSharding):
        return tuple(_R for _ in range(ndim))
    spec = []
    parts = tuple(sharding.spec) if sharding.spec is not None else ()
    for d in range(ndim):
        p = parts[d] if d < len(parts) else None
        if p is None:
            spec.append(_R)
        elif isinstance(p, (tuple, list)):
            spec.append(tuple(str(a) for a in p))
        else:
            spec.append((str(p),))
    return tuple(spec)


def _spec_axes(spec):
    return tuple(a for dim in spec for a in dim)


def _local_bytes(aval, spec, sizes):
    """Per-device bytes of a value sharded per ``spec`` (logical bytes
    divided by the product of its sharding-axis sizes)."""
    n = _aval_bytes(aval)
    denom = 1
    for a in _spec_axes(spec):
        denom *= int(sizes.get(a, 1))
    return n / max(denom, 1)


def _dedupe_axes(spec):
    """An axis may shard at most one dim — drop later repeats (they arise
    when e.g. both dot operands carry the same axis on a free dim)."""
    seen = set()
    out = []
    for dim in spec:
        kept = tuple(a for a in dim if a not in seen)
        seen.update(kept)
        out.append(kept)
    return tuple(out)


def _drop_axes(spec, axes):
    axes = set(axes)
    return tuple(tuple(a for a in dim if a not in axes) for dim in spec)



def _env_get(env, v):
    """Spec of a jaxpr atom from an env ('' literals → replicated)."""
    nd = len(getattr(getattr(v, "aval", None), "shape", ()))
    if hasattr(v, "val"):
        return tuple(_R for _ in range(nd))
    try:
        return env.get(v, tuple(_R for _ in range(nd)))
    except TypeError:  # pragma: no cover - defensive
        return tuple(_R for _ in range(nd))


def _path_of(var_paths, v):
    """Input-path provenance for a jaxpr atom ('' for Literals — they are
    unhashable and never step inputs anyway)."""
    if hasattr(v, "val") or not var_paths:
        return ""
    try:
        return var_paths.get(v, "")
    except TypeError:  # pragma: no cover - defensive
        return ""


class PredictedCollective:
    """One predicted GSPMD/explicit collective: HLO-style op name, the mesh
    axes it spans, per-device bytes moved (ring model, local shapes)."""

    __slots__ = ("op", "axes", "bytes", "count", "where", "prim", "reason")

    def __init__(self, op, axes, nbytes, where="", prim="", reason="",
                 count=1):
        self.op = op
        self.axes = tuple(axes)
        self.bytes = float(nbytes)
        self.count = int(count)
        self.where = where
        self.prim = prim
        self.reason = reason

    @property
    def axis_label(self):
        return "+".join(self.axes)

    def as_dict(self):
        return {"op": self.op, "axes": list(self.axes),
                "bytes": self.bytes, "count": self.count,
                "where": self.where, "prim": self.prim,
                "reason": self.reason}

    def __repr__(self):
        return (f"PredictedCollective({self.op}@{self.axis_label}, "
                f"{self.bytes:.0f}B x{self.count})")


#: constraints written by the framework's own sharding-policy modules are
#: placement decisions, not accidents — the ZeRO param all-gather
#: (distributed/sharding/zero.py) deliberately constrains the updated shard
#: back to its replicated spec. These reshards stay PRICED (they are real
#: wire bytes) but ``spmd-implicit-resharding`` must not error on them.
_POLICY_FILES = frozenset({"zero.py", "group_sharded.py"})


class Reshard:
    """A propagated sharding disagreeing with a downstream consumer
    (``with_sharding_constraint``, dot contraction, elementwise merge) —
    the event the ``spmd-implicit-resharding`` / ``spmd-sharding-mismatch``
    rules report. ``declared`` marks reshards issued by the framework's
    sharding-policy modules (see ``_POLICY_FILES``)."""

    __slots__ = ("kind", "axes", "bytes", "where", "from_spec", "to_spec",
                 "path", "op", "declared")

    def __init__(self, kind, axes, nbytes, where="", from_spec=(),
                 to_spec=(), path="", op="all-gather", declared=False):
        self.kind = kind            # "constraint" | "dot" | "elementwise"
        self.axes = tuple(axes)
        self.bytes = float(nbytes)
        self.where = where
        self.from_spec = from_spec
        self.to_spec = to_spec
        self.path = path            # input pytree path when the value IS an
        self.op = op                # invar (first-use mismatch), else ""
        self.declared = bool(declared)

    def as_dict(self):
        return {"kind": self.kind, "axes": list(self.axes),
                "bytes": self.bytes, "where": self.where,
                "from_spec": _spec_str(self.from_spec),
                "to_spec": _spec_str(self.to_spec), "path": self.path,
                "op": self.op, "declared": self.declared}


def _spec_str(spec):
    """Render a spec as a copy-pasteable ``P(...)`` literal."""
    parts = []
    for dim in spec:
        if not dim:
            parts.append("None")
        elif len(dim) == 1:
            parts.append(f"'{dim[0]}'")
        else:
            parts.append("(" + ", ".join(f"'{a}'" for a in dim) + ")")
    return "P(" + ", ".join(parts) + ")"


# ---------------------------------------------------------------------------
# the propagation walker
# ---------------------------------------------------------------------------

#: jaxpr collective primitive → HLO op name (for explicit shard_map regions)
_EXPLICIT_OPS = {
    "psum": "all-reduce", "psum2": "all-reduce", "pmax": "all-reduce",
    "pmin": "all-reduce", "all_gather": "all-gather",
    "all_gather_invariant": "all-gather", "reduce_scatter": "reduce-scatter",
    "all_to_all": "all-to-all", "ppermute": "collective-permute",
}

_REDUCE_PRIMS = ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                 "reduce_and", "reduce_or")

_CALL_PRIMS = ("pjit", "closed_call", "core_call", "remat", "remat2",
               "checkpoint", "custom_jvp_call", "custom_vjp_call",
               "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
               "custom_vjp_call_jaxpr_p", "named_call", "xla_call")


class _Walker:
    def __init__(self, sizes, ctx, fusion=False):
        self.sizes = sizes      # mesh axis -> size
        self.ctx = ctx          # ShardingAnalysis under construction
        self.fusion = bool(fusion)
        self._plans = {}        # id(jaxpr) -> FusionPlan

    def _plan_for(self, jaxpr):
        if not self.fusion:
            return None
        plan = self._plans.get(id(jaxpr))
        if plan is None:
            from . import fusion as fusion_sim
            try:
                plan = fusion_sim.plan_jaxpr(jaxpr)
            except Exception:   # degrade: count raw traffic (over-counts)
                plan = False
            self._plans[id(jaxpr)] = plan
        return plan or None

    # -- helpers -------------------------------------------------------------
    def _ring(self, op, size):
        from ..profiler.devprof import _HLO_FACTORS

        return _HLO_FACTORS[op](size)

    def _group_size(self, axes):
        s = 1
        for a in axes:
            s *= int(self.sizes.get(a, 1))
        return s

    def _emit(self, op, axes, nbytes, where, prim="", reason="", count=1):
        axes = self._mesh_order(axes)
        if not axes or nbytes <= 0 or self._group_size(axes) <= 1:
            return
        self.ctx._add(PredictedCollective(op, axes, nbytes, where=where,
                                          prim=prim, reason=reason,
                                          count=count))

    def _mesh_order(self, axes):
        order = self.ctx.axis_order
        return tuple(sorted(set(axes),
                            key=lambda a: order.get(a, len(order))))

    def _gather_bytes(self, aval, spec, axes):
        """All-gather of ``axes`` out of ``spec``: (S−1)/S × the gathered
        (still sharded on the remaining axes) local result bytes."""
        s = self._group_size(axes)
        gathered = _drop_axes(spec, axes)
        return self._ring("all-gather", s) * _local_bytes(aval, gathered,
                                                          self.sizes)

    # -- eqn dispatch --------------------------------------------------------
    def walk(self, jaxpr, env, var_paths, multiplier=1, manual_axes=()):
        from .graph_lint import _eqn_where, _subjaxprs

        plan = self._plan_for(jaxpr)

        def spec_of(v):
            aval = getattr(v, "aval", None)
            ndim = len(getattr(aval, "shape", ()))
            if hasattr(v, "val"):  # Literal
                return tuple(_R for _ in range(ndim))
            return env.get(v, tuple(_R for _ in range(ndim)))

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            where = _eqn_where(eqn)
            ins = [spec_of(v) for v in eqn.invars]
            out_specs = None

            if prim == "shard_map":
                out_specs = self._shard_map(eqn, ins, env, var_paths,
                                            multiplier)
            elif prim in _EXPLICIT_OPS and manual_axes:
                out_specs = self._explicit_collective(eqn, ins, where,
                                                      multiplier)
            elif prim == "sharding_constraint":
                out_specs = [self._constraint(eqn, ins[0], where, var_paths,
                                              multiplier)]
            elif prim == "dot_general":
                out_specs = [self._dot(eqn, ins, where, var_paths,
                                       multiplier)]
            elif prim in _REDUCE_PRIMS:
                out_specs = [self._reduce(eqn, ins[0], where, multiplier)]
            elif prim == "broadcast_in_dim":
                out_specs = [self._broadcast(eqn, ins[0])]
            elif prim == "transpose":
                perm = eqn.params.get("permutation", ())
                out_specs = [tuple(ins[0][p] for p in perm)]
            elif prim == "reshape":
                out_specs = [self._reshape(eqn, ins[0])]
            elif prim == "squeeze":
                dims = set(eqn.params.get("dimensions", ()))
                out_specs = [tuple(d for i, d in enumerate(ins[0])
                                   if i not in dims)]
            elif prim in ("expand_dims",):
                dims = set(eqn.params.get("dimensions", ()))
                nd = len(eqn.outvars[0].aval.shape)
                it = iter(ins[0])
                out_specs = [tuple(_R if i in dims else next(it, _R)
                                   for i in range(nd))]
            elif prim == "concatenate":
                out_specs = [self._concat(eqn, ins)]
            elif prim in ("dynamic_update_slice", "pad", "rev",
                          "reduce_precision", "copy",
                          "cumsum", "cumprod", "cummax", "cummin",
                          "cumlogsumexp"):
                out_specs = [ins[0]]
            elif prim in ("slice", "dynamic_slice"):
                # slicing a sharded dim would gather; conservatively drop
                # axes on dims whose extent changes, emit nothing
                in_shape = eqn.invars[0].aval.shape
                out_shape = eqn.outvars[0].aval.shape
                out_specs = [tuple(
                    d if int(in_shape[i]) == int(out_shape[i]) else _R
                    for i, d in enumerate(ins[0]))]
            elif prim == "scan":
                out_specs = self._scan(eqn, ins, env, var_paths, multiplier,
                                       manual_axes)
            elif prim in ("while", "cond"):
                out_specs = self._control(eqn, ins, env, var_paths,
                                          multiplier, manual_axes)
            elif prim in _CALL_PRIMS:
                out_specs = self._call(eqn, ins, env, var_paths, multiplier,
                                       manual_axes)
            else:
                out_specs = self._generic(eqn, ins, where, var_paths,
                                          multiplier)

            if out_specs is None:
                out_specs = [tuple(_R for _ in
                                   getattr(v.aval, "shape", ()))
                             for v in eqn.outvars]
            for v, sp in zip(eqn.outvars, out_specs):
                nd = len(getattr(v.aval, "shape", ()))
                sp = tuple(sp)[:nd] + tuple(_R for _ in range(nd - len(sp)))
                env[v] = _dedupe_axes(sp)

            # memory-traffic proxy for the comm_fraction denominator: each
            # eqn reads its inputs and writes its outputs once (local
            # shapes; over-counts vs XLA fusion — documented). The
            # fusion-aware ``bytes_materialized`` variant skips values the
            # fusion plan certifies XLA elides (a fused temporary is never
            # read from or written to HBM), approximating the compiled
            # program's per-group ``bytes_accessed``.
            if prim not in ("shard_map",) + _CALL_PRIMS:
                traffic = mat = 0.0
                for v in eqn.invars:
                    if not hasattr(v, "aval"):
                        continue
                    nb = _local_bytes(v.aval, spec_of(v), self.sizes)
                    traffic += nb
                    if plan is None or not plan.is_fused(v):
                        mat += nb
                for v in eqn.outvars:
                    nb = _local_bytes(v.aval, env[v], self.sizes)
                    traffic += nb
                    if plan is None or not plan.is_fused(v):
                        mat += nb
                self.ctx.bytes_proxy += multiplier * traffic
                self.ctx.bytes_materialized += multiplier * mat

    # -- per-primitive handlers ---------------------------------------------
    def _explicit_collective(self, eqn, ins, where, multiplier):
        from ..profiler.devprof import _COMM_FACTORS

        prim = eqn.primitive.name
        axes = eqn.params.get("axes", None)
        if axes is None:
            axes = eqn.params.get("axis_name", ())
        if isinstance(axes, (str, int)):
            axes = (axes,)
        axes = tuple(a for a in axes if isinstance(a, str))
        size = self._group_size(axes)
        if size > 1:
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                         if hasattr(v, "aval"))
            moved = _COMM_FACTORS[prim](size) * nbytes
            self._emit(_EXPLICIT_OPS[prim], axes, moved, where, prim=prim,
                       reason="explicit shard_map collective",
                       count=multiplier)
        return [tuple(ins[0]) if ins else ()
                for _ in eqn.outvars]

    def _shard_map(self, eqn, ins, env, var_paths, multiplier):
        sizes = dict(self.sizes)
        mesh = eqn.params.get("mesh")
        try:
            sizes.update({str(k): int(v)
                          for k, v in dict(mesh.shape).items()})
        except Exception:
            pass
        sub = None
        for s in self._subjaxprs_of(eqn):
            sub = s
            break
        if sub is None:
            return None
        inner = _Walker(sizes, self.ctx)
        sub_env = {}
        for v in sub.invars:
            nd = len(getattr(v.aval, "shape", ()))
            sub_env[v] = tuple(_R for _ in range(nd))
        manual = tuple(sizes)
        inner.walk(sub, sub_env, {}, multiplier=multiplier,
                   manual_axes=manual)
        # out specs from out_names ({dim: axes} per output)
        outs = []
        out_names = eqn.params.get("out_names", ()) or ()
        for i, v in enumerate(eqn.outvars):
            nd = len(getattr(v.aval, "shape", ()))
            spec = [_R] * nd
            if i < len(out_names):
                try:
                    for d, axes in dict(out_names[i]).items():
                        if int(d) < nd:
                            spec[int(d)] = tuple(str(a) for a in axes)
                except Exception:
                    pass
            outs.append(tuple(spec))
        return outs

    def _subjaxprs_of(self, eqn):
        from .graph_lint import _subjaxprs

        for v in eqn.params.values():
            yield from _subjaxprs(v)

    def _constraint(self, eqn, in_spec, where, var_paths, multiplier):
        sharding = eqn.params.get("sharding")
        aval = eqn.outvars[0].aval
        nd = len(getattr(aval, "shape", ()))
        target = spec_from_sharding(sharding, nd)
        unconstrained = eqn.params.get("unconstrained_dims") or ()
        target = tuple(in_spec[d] if d in unconstrained else target[d]
                       for d in range(nd))
        in_axes = set(_spec_axes(in_spec))
        out_axes = set(_spec_axes(target))
        removed = in_axes - out_axes
        moved = set()
        for d in range(nd):
            for a in in_spec[d]:
                if a in out_axes and a not in target[d]:
                    moved.add(a)
        path = _path_of(var_paths, eqn.invars[0]) if eqn.invars else ""
        declared = where.split(":", 1)[0] in _POLICY_FILES
        if removed:
            nbytes = self._gather_bytes(aval, in_spec, removed)
            self._emit("all-gather", removed, nbytes, where,
                       prim="sharding_constraint",
                       reason="constraint removes sharding axes",
                       count=multiplier)
            self.ctx.reshards.append(Reshard(
                "constraint", self._mesh_order(removed),
                multiplier * nbytes, where=where, from_spec=in_spec,
                to_spec=target, path=path, op="all-gather",
                declared=declared))
        if moved:
            s = self._group_size(moved)
            nbytes = (self._ring("all-to-all", s)
                      * _local_bytes(aval, in_spec, self.sizes))
            self._emit("all-to-all", moved, nbytes, where,
                       prim="sharding_constraint",
                       reason="constraint moves sharding axes between dims",
                       count=multiplier)
            self.ctx.reshards.append(Reshard(
                "constraint", self._mesh_order(moved), multiplier * nbytes,
                where=where, from_spec=in_spec, to_spec=target, path=path,
                op="all-to-all", declared=declared))
        return _dedupe_axes(target)

    def _dot(self, eqn, ins, where, var_paths, multiplier):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = ins[0], ins[1]
        l_aval, r_aval = eqn.invars[0].aval, eqn.invars[1].aval
        out_aval = eqn.outvars[0].aval
        reduce_axes = set()
        for k in range(len(lc)):
            la, ra = set(lhs[lc[k]]), set(rhs[rc[k]])
            if la and ra and la != ra:
                # inconsistent contraction shardings: GSPMD must gather one
                # side before it can contract — gather the smaller operand
                l_small = _aval_bytes(l_aval) <= _aval_bytes(r_aval)
                g_aval = l_aval if l_small else r_aval
                g_spec = lhs if l_small else rhs
                g_axes = la if l_small else ra
                nbytes = self._gather_bytes(g_aval, g_spec, g_axes)
                self._emit("all-gather", g_axes, nbytes, where,
                           prim="dot_general",
                           reason="contraction dims sharded on different "
                                  "axes", count=multiplier)
                v = eqn.invars[0 if l_small else 1]
                self.ctx.reshards.append(Reshard(
                    "dot", self._mesh_order(g_axes), multiplier * nbytes,
                    where=where, from_spec=g_spec,
                    to_spec=_drop_axes(g_spec, g_axes),
                    path=_path_of(var_paths, v), op="all-gather"))
                if l_small:
                    lhs = _drop_axes(lhs, g_axes)
                    la = set()
                else:
                    rhs = _drop_axes(rhs, g_axes)
                    ra = set()
            reduce_axes |= la | ra

        out_spec = []
        for k in range(len(lb)):
            out_spec.append(tuple(set(lhs[lb[k]]) | set(rhs[rb[k]])))
        for d in range(len(lhs)):
            if d not in lc and d not in lb:
                out_spec.append(lhs[d])
        for d in range(len(rhs)):
            if d not in rc and d not in rb:
                out_spec.append(rhs[d])
        out_spec = _dedupe_axes(_drop_axes(tuple(out_spec), reduce_axes))

        if reduce_axes:
            s = self._group_size(reduce_axes)
            nbytes = (self._ring("all-reduce", s)
                      * _local_bytes(out_aval, out_spec, self.sizes))
            self._emit("all-reduce", reduce_axes, nbytes, where,
                       prim="dot_general",
                       reason="contraction over sharded dims → partial sums",
                       count=multiplier)
        return out_spec

    def _reduce(self, eqn, in_spec, where, multiplier):
        axes_param = eqn.params.get("axes", ())
        red_axes = set()
        out_spec = []
        for d, dim in enumerate(in_spec):
            if d in axes_param:
                red_axes.update(dim)
            else:
                out_spec.append(dim)
        out_spec = tuple(out_spec)
        if red_axes:
            s = self._group_size(red_axes)
            nbytes = (self._ring("all-reduce", s)
                      * _local_bytes(eqn.outvars[0].aval, out_spec,
                                     self.sizes))
            self._emit("all-reduce", red_axes, nbytes, where,
                       prim=eqn.primitive.name,
                       reason="reduction over sharded dims", count=multiplier)
        return out_spec

    def _broadcast(self, eqn, in_spec):
        bdims = eqn.params.get("broadcast_dimensions", ())
        in_shape = eqn.invars[0].aval.shape
        out_shape = eqn.outvars[0].aval.shape
        nd = len(out_shape)
        out = [_R] * nd
        for i, d in enumerate(bdims):
            if i < len(in_spec) and int(in_shape[i]) == int(out_shape[d]):
                out[d] = in_spec[i]
        return tuple(out)

    def _reshape(self, eqn, in_spec):
        """Greedy row-major dim mapping: 1:1 dims inherit; a split dim
        keeps its axes on the leading output factor; merged dims keep the
        leading input dim's axes. Anything murkier drops to replicated."""
        in_shape = [int(s) for s in eqn.invars[0].aval.shape]
        out_shape = [int(s) for s in eqn.outvars[0].aval.shape]
        out = [_R] * len(out_shape)
        i = j = 0
        while i < len(in_shape) and j < len(out_shape):
            if in_shape[i] == out_shape[j]:
                out[j] = in_spec[i]
                i += 1
                j += 1
            elif in_shape[i] > out_shape[j]:
                # split: [M] -> [k, M/k, ...]; leading factor inherits when
                # the axis sizes still divide it
                grp = 1
                j0 = j
                while j < len(out_shape) and grp < in_shape[i]:
                    grp *= out_shape[j]
                    j += 1
                if grp == in_shape[i]:
                    axes = in_spec[i]
                    denom = self._group_size(axes)
                    if denom > 1 and out_shape[j0] % denom == 0:
                        out[j0] = axes
                    i += 1
                else:
                    break
            else:
                # merge: [a, b] -> [a*b]; leading dim's axes survive
                grp = 1
                i0 = i
                while i < len(in_shape) and grp < out_shape[j]:
                    grp *= in_shape[i]
                    i += 1
                if grp == out_shape[j]:
                    out[j] = in_spec[i0]
                    j += 1
                else:
                    break
        return tuple(out)

    def _concat(self, eqn, ins):
        dim = int(eqn.params.get("dimension", 0))
        nd = len(eqn.outvars[0].aval.shape)
        out = []
        for d in range(nd):
            dims = [sp[d] if d < len(sp) else _R for sp in ins]
            if d == dim:
                out.append(_R)
            else:
                common = set(dims[0])
                for x in dims[1:]:
                    common &= set(x)
                out.append(tuple(a for a in dims[0] if a in common))
        return tuple(out)

    def _scan(self, eqn, ins, env, var_paths, multiplier, manual_axes):
        sub = next(iter(self._subjaxprs_of(eqn)), None)
        if sub is None:
            return None
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        length = max(1, int(eqn.params.get("length", 1)))
        sub_env = {}
        for k, v in enumerate(sub.invars):
            nd = len(getattr(v.aval, "shape", ()))
            if k < n_consts + n_carry:
                sp = ins[k] if k < len(ins) else ()
            else:
                sp = tuple(ins[k][1:]) if k < len(ins) and ins[k] else ()
            sp = tuple(sp)[:nd] + tuple(_R for _ in range(nd - len(sp)))
            sub_env[v] = sp
        inner = _Walker(self.sizes, self.ctx)
        inner.walk(sub, sub_env, {}, multiplier=multiplier * length,
                   manual_axes=manual_axes)
        outs = []
        for k, v in enumerate(eqn.outvars):
            nd = len(getattr(v.aval, "shape", ()))
            if k < n_carry and k < len(sub.outvars):
                outs.append(_env_get(sub_env, sub.outvars[k]))
            elif k < len(sub.outvars):
                ys = _env_get(sub_env, sub.outvars[k])
                outs.append((_R,) + tuple(ys))
            else:
                outs.append(tuple(_R for _ in range(nd)))
        return outs

    def _control(self, eqn, ins, env, var_paths, multiplier, manual_axes):
        # while/cond: analyze the first body once (no trip-count info)
        sub = None
        for s in self._subjaxprs_of(eqn):
            sub = s
            break
        if sub is None:
            return None
        k = len(eqn.invars) - len(sub.invars)
        sub_env = {}
        for v, sp in zip(sub.invars, ins[max(k, 0):]):
            nd = len(getattr(v.aval, "shape", ()))
            sub_env[v] = (tuple(sp)[:nd]
                          + tuple(_R for _ in range(nd - len(sp))))
        inner = _Walker(self.sizes, self.ctx)
        inner.walk(sub, sub_env, {}, multiplier=multiplier,
                   manual_axes=manual_axes)
        return None

    def _call(self, eqn, ins, env, var_paths, multiplier, manual_axes):
        for sub in self._subjaxprs_of(eqn):
            if len(sub.invars) == len(eqn.invars):
                sub_env = {}
                sub_paths = {}
                for v, sp, ev in zip(sub.invars, ins, eqn.invars):
                    nd = len(getattr(v.aval, "shape", ()))
                    sub_env[v] = (tuple(sp)[:nd]
                                  + tuple(_R for _ in range(nd - len(sp))))
                    p = _path_of(var_paths, ev)
                    if p:
                        sub_paths[v] = p
                inner = _Walker(self.sizes, self.ctx)
                inner.walk(sub, sub_env, sub_paths, multiplier=multiplier,
                           manual_axes=manual_axes)
                return [_env_get(sub_env, v)
                        for v in sub.outvars[:len(eqn.outvars)]]
        return None

    def _generic(self, eqn, ins, where, var_paths, multiplier):
        """Elementwise-shaped ops (every array input has the output's
        shape): per-dim union of operand shardings; a genuine conflict
        (two different non-empty axis sets on one dim) is an implicit
        reshard of the minority operand. Everything else: replicated."""
        if eqn.primitive.name == "optimization_barrier":
            # pure scheduling fence (ZeRO bucketed-overlap chains grads
            # through it): multi-in/multi-out identity — dropping specs
            # here would predict phantom gathers in the sharded update
            return [tuple(sp) for sp in ins]
        if not eqn.outvars:
            return []
        out_aval = eqn.outvars[0].aval
        out_shape = tuple(getattr(out_aval, "shape", ()))
        arrayish = [(v, sp) for v, sp in zip(eqn.invars, ins)
                    if tuple(getattr(getattr(v, "aval", None), "shape", ()))
                    == out_shape and out_shape != ()]
        if len(eqn.outvars) != 1 or not arrayish:
            if (len(eqn.invars) == 1 and len(eqn.outvars) == 1 and ins
                    and tuple(getattr(eqn.invars[0].aval, "shape", ()))
                    == out_shape):
                return [ins[0]]
            return None
        nd = len(out_shape)
        out = [_R] * nd
        for d in range(nd):
            cands = [sp[d] for _, sp in arrayish if d < len(sp) and sp[d]]
            if not cands:
                continue
            chosen = cands[0]
            out[d] = chosen
            for (v, sp) in arrayish:
                got = sp[d] if d < len(sp) else _R
                if got and set(got) != set(chosen):
                    # the minority operand reshards (all-gather its axes)
                    nbytes = self._gather_bytes(v.aval, sp, got)
                    self._emit("all-gather", got, nbytes, where,
                               prim=eqn.primitive.name,
                               reason="elementwise operands sharded "
                                      "differently", count=multiplier)
                    self.ctx.reshards.append(Reshard(
                        "elementwise", self._mesh_order(got),
                        multiplier * nbytes, where=where, from_spec=sp,
                        to_spec=_drop_axes(sp, got),
                        path=_path_of(var_paths, v), op="all-gather"))
        return [_dedupe_axes(tuple(out))]


# ---------------------------------------------------------------------------
# the analysis result
# ---------------------------------------------------------------------------

class ShardingAnalysis:
    """Predicted SPMD communication for one step program.

    Attributes:
        mesh: the analyzed :class:`jax.sharding.Mesh` (or None).
        collectives: predicted per-axis
            :class:`~paddle_tpu.profiler.devprof.CollectiveStats` —
            directly comparable to a harvested ``DeviceCostReport``'s.
        predicted: ordered list of :class:`PredictedCollective`.
        reshards: :class:`Reshard` events (implicit-resharding rule input).
        in_specs: ``{input path: spec}`` as propagated from the example
            batch / state shardings.
        bytes_proxy: static memory-traffic proxy (every eqn reads inputs +
            writes outputs once, local shapes). Over-counts vs XLA's fused
            ``bytes_accessed``.
        bytes_materialized: the fusion-aware variant — same sweep, but
            values the :mod:`.fusion` plan certifies XLA elides are
            skipped (never read from or written to HBM). When the walk
            ran with ``fusion=True`` this is the ``comm_fraction``
            denominator, bringing the predicted fraction much closer to
            devprof's measured one than the raw proxy's floor.
    """

    def __init__(self, mesh=None, axis_order=None):
        from ..profiler.devprof import CollectiveStats

        self.mesh = mesh
        self.axis_order = dict(axis_order or {})
        self.collectives = CollectiveStats()
        self.predicted = []
        self.reshards = []
        self.in_specs = {}
        self.bytes_proxy = 0.0
        self.bytes_materialized = 0.0
        self.fusion = False

    def _add(self, pc):
        self.predicted.append(pc)
        self.collectives.add(pc.axis_label, pc.op, pc.bytes * pc.count,
                             count=pc.count)

    @property
    def comm_bytes(self):
        return self.collectives.total_bytes

    @property
    def comm_fraction(self):
        mem = self.bytes_materialized if self.fusion else self.bytes_proxy
        denom = self.comm_bytes + mem
        return self.comm_bytes / denom if denom > 0 else 0.0

    def bytes_by_axis(self):
        return {axis: st["bytes"]
                for axis, st in self.collectives.by_axis.items()}

    def as_dict(self):
        return {
            "mesh_axes": {a: int(s) for a, s in self.axis_order.items()},
            "collectives": self.collectives.as_dict(),
            "comm_bytes": self.comm_bytes,
            "comm_fraction": self.comm_fraction,
            "bytes_proxy": self.bytes_proxy,
            "bytes_materialized": self.bytes_materialized,
            "fusion": self.fusion,
            "predicted": [p.as_dict() for p in self.predicted],
            "reshards": [r.as_dict() for r in self.reshards],
        }

    def table(self):
        from ..profiler.devprof import _fmt_bytes

        mem = ("mem denominator "
               f"{_fmt_bytes(self.bytes_materialized)} materialized"
               if self.fusion else
               "mem denominator "
               f"{_fmt_bytes(self.bytes_proxy)} proxy (fusion off)")
        lines = [f"shard lint — predicted collectives "
                 f"({_fmt_bytes(self.comm_bytes)} moved/device, "
                 f"comm_fraction {self.comm_fraction:.4f}, {mem})"]
        if not self.collectives:
            lines.append("  none (replicated program or single device)")
        for axis in self.collectives.axes():
            st = self.collectives.by_axis[axis]
            prims = ",".join(f"{p}x{n}"
                             for p, n in sorted(st["prims"].items()))
            lines.append(f"  axis {axis:<12} {st['count']:>4} ops "
                         f"{_fmt_bytes(st['bytes']):>12}  [{prims}]")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _infer_mesh_from_leaves(leaves):
    from jax.sharding import NamedSharding

    for leaf in leaves:
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            return sh.mesh
    return None


def _graph_invar_leaves(graph):
    """(path, leaf) per jaxpr invar, in invar order: state leaves first,
    then donated dyn args, then kept dyn args (mirrors ``trace_step``'s
    ``make_jaxpr(lambda s, dd, dk: ...)`` flattening)."""
    rows = list(graph.state_in_paths)
    rows += [(p, l) for p, l, don in graph.dyn_args if don]
    rows += [(p, l) for p, l, don in graph.dyn_args if not don]
    return rows


def propagate_jaxpr(closed_jaxpr, in_specs, axis_sizes, const_specs=None,
                    mesh=None, in_paths=None, fusion=True):
    """Run the propagation over ``closed_jaxpr`` with explicit per-invar
    specs. ``in_specs``: one spec per ``jaxpr.invars`` entry;
    ``const_specs``: per ``jaxpr.constvars``. Returns the
    :class:`ShardingAnalysis`. This is the raw engine —
    :func:`analyze_sharding` derives the specs from a traced step's
    array shardings for you. ``fusion=True`` (default) makes
    ``comm_fraction`` use the fusion-aware materialized-bytes
    denominator; ``False`` restores the raw-traffic proxy."""
    sizes = {str(a): int(s) for a, s in dict(axis_sizes).items()}
    ctx = ShardingAnalysis(mesh=mesh, axis_order=sizes)
    ctx.fusion = bool(fusion)
    jaxpr = closed_jaxpr.jaxpr
    env = {}
    var_paths = {}
    for i, v in enumerate(jaxpr.invars):
        nd = len(getattr(v.aval, "shape", ()))
        sp = tuple(in_specs[i]) if i < len(in_specs) else ()
        sp = sp[:nd] + tuple(_R for _ in range(nd - len(sp)))
        env[v] = _dedupe_axes(sp)
        if in_paths and i < len(in_paths) and in_paths[i]:
            var_paths[v] = in_paths[i]
    for i, v in enumerate(jaxpr.constvars):
        nd = len(getattr(v.aval, "shape", ()))
        sp = (tuple(const_specs[i]) if const_specs
              and i < len(const_specs) else ())
        sp = sp[:nd] + tuple(_R for _ in range(nd - len(sp)))
        env[v] = _dedupe_axes(sp)
    _Walker(sizes, ctx, fusion=fusion).walk(jaxpr, env, var_paths)
    return ctx


def analyze_sharding(graph_or_step, *args, mesh=None, in_shardings=None,
                     fusion=None, **kwargs):
    """Abstract sharding propagation for a step.

    Args:
        graph_or_step: a :class:`~.graph_lint.StepGraph` (already traced)
            or a ``CompiledStep``/callable (traced here — no device
            execution, same contract as ``trace_step``).
        mesh: the target Mesh; inferred from input/state ``NamedSharding``
            leaves when omitted. No mesh (or size 1) → returns None.
        in_shardings: optional ``{input path: PartitionSpec-like}``
            overrides applied on top of the leaf-derived specs.
        fusion: fusion-aware ``comm_fraction`` denominator (see
            :func:`propagate_jaxpr`). ``None`` (default) reads the
            graph's ``config["fusion"]`` — same knob as mem_lint — and
            falls back to True.

    Returns:
        :class:`ShardingAnalysis` or None when no multi-device mesh is in
        play.
    """
    from .graph_lint import StepGraph, trace_step

    if isinstance(graph_or_step, StepGraph):
        graph = graph_or_step
    else:
        graph = trace_step(graph_or_step, *args, **kwargs)
    if fusion is None:
        fusion = bool(getattr(graph, "config", {}).get("fusion", True))

    rows = _graph_invar_leaves(graph)
    if mesh is None:
        mesh = _infer_mesh_from_leaves([l for _, l in rows]
                                       + list(graph.consts))
    if mesh is None or int(getattr(mesh, "size", 1)) <= 1:
        return None
    sizes = {str(a): int(s) for a, s in dict(mesh.shape).items()}

    overrides = {}
    for path, spec in (in_shardings or {}).items():
        overrides[path] = spec

    in_specs, in_paths = [], []
    for path, leaf in rows:
        nd = len(tuple(getattr(leaf, "shape", ())))
        if path in overrides:
            spec = _coerce_spec(overrides[path], nd)
        else:
            spec = spec_from_sharding(getattr(leaf, "sharding", None), nd)
        in_specs.append(spec)
        in_paths.append(path)
    const_specs = [spec_from_sharding(getattr(c, "sharding", None),
                                      len(tuple(getattr(c, "shape", ()))))
                   for c in graph.consts]

    sa = propagate_jaxpr(graph.closed_jaxpr, in_specs, sizes,
                         const_specs=const_specs, mesh=mesh,
                         in_paths=in_paths, fusion=fusion)
    sa.in_specs = dict(zip(in_paths, in_specs))
    return sa


def _coerce_spec(spec, ndim):
    """PartitionSpec / tuple / list → internal per-dim axis tuples."""
    out = []
    parts = tuple(spec)
    for d in range(ndim):
        p = parts[d] if d < len(parts) else None
        if p is None:
            out.append(_R)
        elif isinstance(p, (tuple, list)):
            out.append(tuple(str(a) for a in p))
        else:
            out.append((str(p),))
    return tuple(out)

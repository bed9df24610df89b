"""Lint-rule registry + the built-in rules.

Each rule is a generator ``rule(graph) -> Iterable[Finding]`` over a
:class:`~paddle_tpu.analysis.graph_lint.StepGraph` (the abstractly-traced
step program: jaxpr + input/state pytrees + donation metadata). Rules are
registered under a stable id; ``lint_step(..., ignore=("rule-id",))`` or the
``PADDLE_TPU_LINT_IGNORE`` env var (comma list) silences them.

Rule families (ISSUE 3):

* ``retrace-*``    — hazards that force jax to re-trace/re-compile the step
* ``host-sync-*``  — ops that stall the async pipeline on the host
* ``hbm-*``        — device-memory waste visible in the lowered program
* ``tpu-*``        — ops the TPU executes poorly (hot-path gathers, opaque
                     custom calls XLA cannot fuse across)
* ``spmd-*``       — (ISSUE 7) multichip sharding hazards predicted by the
                     abstract SPMD propagation in :mod:`.shard_lint`; these
                     run only when the step was linted under a mesh
                     (``lint_step(..., mesh=...)`` or inferable from the
                     example batch/state shardings)
"""
from __future__ import annotations

import numpy as np

from .findings import Finding

__all__ = ["RULES", "register_rule", "rule_ids", "run_rules"]

#: rule id -> (default_severity, one_line_doc, fn)
RULES = {}


def register_rule(rule_id, severity, doc):
    def deco(fn):
        RULES[rule_id] = (severity, doc, fn)
        return fn

    return deco


def rule_ids():
    return tuple(RULES)


def run_rules(graph, ignore=()):
    """Run every registered rule (minus ``ignore``) over the graph."""
    findings = []
    for rule_id, (_, _, fn) in RULES.items():
        if rule_id in ignore:
            continue
        for f in fn(graph):
            f.step = f.step or graph.name
            findings.append(f)
    return findings


# ---------------------------------------------------------------------------
# retrace hazards
# ---------------------------------------------------------------------------
@register_rule(
    "retrace-state-structure", "error",
    "state pytree structure changes inside the step: every call re-traces")
def _state_structure(graph):
    """The compiled step threads mutable framework state as an explicit
    pytree. If the traced function RETURNS a state tree with a different
    structure than it was given (classic case: optimizer accumulators
    materializing lazily on the first step), the second call's input
    signature differs from the first's and jax compiles the whole program
    again — the Adam/AdamW double-trace PR 2's telemetry measured."""
    if graph.state_in_treedef is None or graph.state_out_treedef is None:
        return
    if graph.state_in_treedef == graph.state_out_treedef:
        return
    in_paths = {p for p, _ in graph.state_in_paths}
    out_paths = {p for p, _ in graph.state_out_paths}
    added = sorted(out_paths - in_paths)
    removed = sorted(in_paths - out_paths)
    detail = []
    if added:
        detail.append(f"{len(added)} leaves appear during the step "
                      f"(e.g. {', '.join(added[:4])})")
    if removed:
        detail.append(f"{len(removed)} leaves vanish "
                      f"(e.g. {', '.join(removed[:4])})")
    yield Finding(
        rule="retrace-state-structure",
        severity="error",
        message="state pytree structure differs between step input and "
                "output: " + ("; ".join(detail) or "treedef mismatch"),
        path=(added or removed or ["state"])[0],
        hint="materialize all state before compiling — for paddle_tpu "
             "optimizers call opt._ensure_accumulators() (CompiledStep does "
             "this for Optimizer instances) so accumulators exist from "
             "step 1",
        data={"added": added, "removed": removed},
    )


@register_rule(
    "retrace-state-dtype", "warning",
    "a state leaf changes shape/dtype across the step: re-traces once per "
    "flip")
def _state_dtype(graph):
    if graph.state_in_treedef is None or graph.state_out_treedef is None:
        return
    if graph.state_in_treedef != graph.state_out_treedef:
        return  # structure finding already covers it
    out = dict(graph.state_out_paths)
    for path, leaf in graph.state_in_paths:
        sds = out.get(path)
        if sds is None:
            continue
        in_shape, in_dtype = _shape_dtype(leaf)
        out_shape, out_dtype = _shape_dtype(sds)
        if in_shape != out_shape or in_dtype != out_dtype:
            yield Finding(
                rule="retrace-state-dtype",
                severity="warning",
                message=f"state leaf changes {in_dtype}{list(in_shape)} -> "
                        f"{out_dtype}{list(out_shape)} across the step; the "
                        f"next call re-traces with the new signature",
                path=path,
                hint="keep state leaves at a fixed shape/dtype (cast inside "
                     "the step instead of letting the update promote)",
            )


@register_rule(
    "retrace-static-scalar", "warning",
    "python-scalar argument is baked into the program: new value = new "
    "compile")
def _static_scalar(graph):
    """Python int/float/bool args are STATIC (op attributes, not tensors) —
    deliberate for config flags, a recompile-per-step trap for values that
    vary (step counters, schedules)."""
    for path, value in graph.static_args:
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        yield Finding(
            rule="retrace-static-scalar",
            severity="warning",
            message=f"python scalar {value!r} at {path} is trace-static: "
                    f"every distinct value compiles a new executable",
            path=path,
            hint=f"pass jnp.asarray({path}) (or a 0-d numpy array) if the "
                 f"value varies between calls",
        )


@register_rule(
    "retrace-static-value", "error",
    "a static argument was observed with different values across example "
    "batches")
def _static_value_churn(graph):
    for variant in graph.variants:
        base = dict(graph.static_args)
        for path, value in variant.get("static", ()):
            if path in base and base[path] != value:
                yield Finding(
                    rule="retrace-static-value",
                    severity="error",
                    message=f"static argument {path} varies across example "
                            f"batches ({base[path]!r} vs {value!r}): the "
                            f"step re-compiles on every new value",
                    path=path,
                    hint="make the value an array input, or hoist it out of "
                         "the per-step arguments",
                )


@register_rule(
    "retrace-shape-churn", "warning",
    "an input's shape/dtype varies across example batches: one executable "
    "per distinct shape")
def _shape_churn(graph):
    base = {p: _shape_dtype(l) for p, l, _ in graph.dyn_args}
    for variant in graph.variants:
        for path, shape, dtype in variant.get("dyn", ()):
            b = base.get(path)
            if b is not None and b != (tuple(shape), str(dtype)):
                yield Finding(
                    rule="retrace-shape-churn",
                    severity="warning",
                    message=f"input {path} varies {b[1]}{list(b[0])} vs "
                            f"{dtype}{list(shape)} across example batches: "
                            f"each distinct signature compiles its own "
                            f"executable",
                    path=path,
                    hint="pad batches to a fixed shape (DataLoader "
                         "drop_last=True) so one cached executable serves "
                         "every step",
                )


@register_rule(
    "kv-cache-concat", "error",
    "a cache input grows along one axis step-to-step and is re-emitted "
    "larger: grow-by-concat KV cache, one compile per position")
def _kv_cache_concat(graph):
    """The decode-loop killer: a cache operand whose shape differs between
    two consecutive positions (example batches), growing along exactly one
    axis, while the step also RETURNS a same-rank/same-dtype array that is
    strictly larger on that axis — the signature of a KV cache grown with
    ``concat`` and threaded back in. Every decode step then compiles a new
    executable AND re-materializes the full cache in HBM (O(n) per step,
    O(n²) per sequence). Distinct from generic ``retrace-shape-churn``:
    the grown-output match is what identifies the operand as a cache
    rather than an unpadded batch."""
    base = {p: _shape_dtype(l) for p, l, _ in graph.dyn_args}
    outs = [_shape_dtype(s) for _, s in graph.out_paths]
    flagged = set()
    for variant in graph.variants:
        for path, shape, dtype in variant.get("dyn", ()):
            if path in flagged:
                continue
            b = base.get(path)
            if b is None or b[1] != str(dtype):
                continue
            bs, vs = b[0], tuple(int(s) for s in shape)
            if len(bs) != len(vs) or bs == vs:
                continue
            diff = [i for i in range(len(bs)) if bs[i] != vs[i]]
            if len(diff) != 1:
                continue
            ax = diff[0]
            grown = any(
                odt == b[1] and len(os) == len(bs) and os[ax] > bs[ax]
                and all(os[i] == bs[i] for i in range(len(bs)) if i != ax)
                for os, odt in outs)
            if not grown:
                continue
            flagged.add(path)
            yield Finding(
                rule="kv-cache-concat",
                severity="error",
                message=f"cache input {path} grows {b[1]}{list(bs)} -> "
                        f"{str(dtype)}{list(vs)} between consecutive "
                        f"positions and the step emits it one step larger: "
                        f"grow-by-concat decode compiles a new executable "
                        f"and copies the full cache at EVERY position",
                path=path,
                hint="preallocate a static [batch, max_len, heads, "
                     "head_dim] buffer and write each step in place at the "
                     "position index (lax.dynamic_update_slice) — "
                     "paddle_tpu.serving.KVCache / GenerationEngine "
                     "compile prefill once per length bucket and decode "
                     "exactly once",
                data={"axis": ax, "base_shape": list(bs),
                      "variant_shape": list(vs)},
            )


@register_rule(
    "retrace-weak-type", "info",
    "weakly-typed input leaf: strong/weak flips re-trace and promotions "
    "surprise")
def _weak_type(graph):
    for path, leaf, _ in graph.dyn_args:
        aval = getattr(leaf, "aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            yield Finding(
                rule="retrace-weak-type",
                severity="info",
                message=f"input {path} is weakly typed (python-scalar "
                        f"promotion semantics): a strongly-typed value at "
                        f"the same path later re-traces",
                path=path,
                hint=f"pin the dtype: jnp.asarray(value, jnp.float32)",
            )


# ---------------------------------------------------------------------------
# host-sync points
# ---------------------------------------------------------------------------
#: callback-ish primitives -> severity ("readbacks inside the traced region")
_SYNC_PRIMS = {
    "pure_callback": "warning",
    "io_callback": "warning",
    "debug_callback": "info",
    "debug_print": "info",
    "host_callback": "warning",
    "infeed": "error",
    "outfeed": "error",
}


@register_rule(
    "host-sync-callback", "warning",
    "host callback inside the step: the device pipeline stalls on python")
def _host_sync(graph):
    for eqn, where in graph.eqns():
        name = eqn.primitive.name
        sev = _SYNC_PRIMS.get(name)
        if sev is None:
            continue
        if name == "io_callback" and eqn.params.get("ordered"):
            sev = "error"  # ordered effects serialize every step
        yield Finding(
            rule="host-sync-callback",
            severity=sev,
            message=f"`{name}` inside the compiled step round-trips to the "
                    f"host every execution"
                    + (" (ordered: serializes dispatch)"
                       if sev == "error" and name == "io_callback" else ""),
            where=where,
            hint="move the readback outside the step (AsyncMetricBuffer "
                 "defers it to fence points) or drop the callback from the "
                 "hot path",
        )


# ---------------------------------------------------------------------------
# HBM waste
# ---------------------------------------------------------------------------
@register_rule(
    "hbm-undonated-input", "warning",
    "large single-use input not donated: its HBM can't be reused by the "
    "step")
def _undonated(graph):
    """Donation analysis: an un-donated input whose buffer the step could
    alias to an output (same shape+dtype) or simply hand back to XLA for
    temporaries. Emits the exact pytree path accepted by
    ``CompiledStep(donate_inputs=[...])``."""
    threshold = graph.config.get("donate_min_bytes", 1 << 20)
    out_sigs = {}
    for _, sds in graph.out_paths:
        out_sigs.setdefault(_shape_dtype(sds), 0)
        out_sigs[_shape_dtype(sds)] += 1
    for path, leaf, donated in graph.dyn_args:
        if donated:
            continue
        shape, dtype = _shape_dtype(leaf)
        nbytes = _nbytes(leaf)
        aliasable = out_sigs.get((shape, dtype), 0) > 0
        if not aliasable and nbytes < threshold:
            continue
        why = (f"matches an output buffer {dtype}{list(shape)} (XLA would "
               f"alias it in-place)" if aliasable else
               f"{nbytes / 2**20:.1f} MiB held live across the step for "
               f"nothing")
        data = {"nbytes": int(nbytes), "aliasable": bool(aliasable)}
        # quantify the win from the liveness timeline when one is attached
        # (lint_step wires graph.memory): predicted peak delta if donated
        tl = getattr(graph, "memory", None)
        if tl is not None:
            try:
                freed = float(tl.delta_if_donated(path))
            except Exception:
                freed = 0.0
            if freed > 0:
                data["peak_delta_bytes"] = freed
                why += (f"; donating it is predicted to cut the peak by "
                        f"{_fmt_mib(freed)}")
        yield Finding(
            rule="hbm-undonated-input",
            severity="warning",
            message=f"input {path} is single-use-shaped but not donated: "
                    + why,
            path=path,
            hint=f'CompiledStep(..., donate_inputs=["{path}"]) — only if '
                 f"the caller never reuses the batch after the call "
                 f"(io.DeviceLoader batches qualify)",
            data=data,
        )


@register_rule(
    "hbm-const-folded", "warning",
    "large array captured as a compile-time constant: duplicated into the "
    "executable")
def _const_folded(graph):
    warn_bytes = graph.config.get("const_warn_bytes", 1 << 20)
    error_bytes = graph.config.get("const_error_bytes", 64 << 20)
    for const in graph.consts:
        nbytes = _nbytes(const)
        if nbytes < warn_bytes:
            continue
        shape, dtype = _shape_dtype(const)
        yield Finding(
            rule="hbm-const-folded",
            severity="error" if nbytes >= error_bytes else "warning",
            message=f"captured array {dtype}{list(shape)} "
                    f"({nbytes / 2**20:.1f} MiB) is folded into the program "
                    f"as a constant: it is copied into every executable "
                    f"that closes over it and bloats compile time",
            hint="thread it through the state pytree (Layer buffer) or pass "
                 "it as an argument instead of closing over it",
            data={"nbytes": int(nbytes)},
        )


@register_rule(
    "hbm-f64-promotion", "warning",
    "float64/complex128 values in the program: 2x HBM and no TPU support")
def _f64(graph):
    seen = 0
    for eqn, where in graph.eqns():
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            try:
                wide = dt is not None and np.dtype(dt) in (
                    np.dtype(np.float64), np.dtype(np.complex128))
            except TypeError:  # extended dtypes (PRNG keys)
                wide = False
            if wide:
                yield Finding(
                    rule="hbm-f64-promotion",
                    severity="warning",
                    message=f"`{eqn.primitive.name}` produces {np.dtype(dt).name}: "
                            f"double-width buffers, and TPUs emulate f64 at "
                            f"a fraction of peak",
                    where=where,
                    hint="keep math in f32/bf16 (check np.float64 scalars "
                         "leaking in via numpy defaults)",
                )
                seen += 1
                break
        if seen >= 4:  # cap the noise; one promotion usually cascades
            return


# ---------------------------------------------------------------------------
# TPU-unfriendly ops
# ---------------------------------------------------------------------------
_SLOW_PRIMS = ("gather", "scatter", "scatter-add", "scatter-mul",
               "scatter-min", "scatter-max", "sort", "top_k", "argsort")


@register_rule(
    "tpu-gather-scatter", "info",
    "gathers/scatters/sorts on the hot path: serialized memory traffic on "
    "TPU")
def _gather_scatter(graph):
    counts = {}
    first_where = {}
    for eqn, where in graph.eqns():
        name = eqn.primitive.name
        if name in _SLOW_PRIMS:
            counts[name] = counts.get(name, 0) + 1
            first_where.setdefault(name, where)
    for name, n in sorted(counts.items()):
        yield Finding(
            rule="tpu-gather-scatter",
            severity="info",
            message=f"{n}x `{name}` in the step: dynamic indexing runs on "
                    f"the TPU's scalar/vector units, not the MXU — fine for "
                    f"embedding lookups, a red flag in inner loops",
            where=first_where[name],
            hint="prefer one_hot @ matmul or take_along_axis over repeated "
                 "fancy indexing where the index set is dense",
            data={"count": n},
        )


@register_rule(
    "tpu-opaque-custom-call", "info",
    "opaque custom call: XLA cannot fuse producers/consumers across it")
def _custom_call(graph):
    for eqn, where in graph.eqns():
        name = eqn.primitive.name
        if "custom_call" in name or name == "pallas_call":
            yield Finding(
                rule="tpu-opaque-custom-call",
                severity="info",
                message=f"`{name}` is opaque to the fusion pass: "
                        f"surrounding elementwise work materializes to HBM "
                        f"at its boundary",
                where=where,
                hint="fold pre/post elementwise math into the kernel itself "
                     "if the boundary buffers show up in the profile",
            )


# ---------------------------------------------------------------------------
# SPMD sharding hazards (shard_lint propagation — ISSUE 7)
# ---------------------------------------------------------------------------
def _sharding_of(graph):
    return getattr(graph, "sharding", None)


def _fmt_mib(n):
    return f"{n / 2**20:.2f} MiB" if n >= 2**20 else f"{n / 1024:.1f} KiB"


@register_rule(
    "spmd-implicit-resharding", "error",
    "propagated sharding disagrees with a downstream constraint/contraction:"
    " GSPMD inserts an all-gather")
def _spmd_implicit_resharding(graph):
    """A value flows into a ``with_sharding_constraint`` (or a dot whose
    contraction dims are sharded on *different* axes per operand) that its
    propagated sharding cannot satisfy — the SPMD partitioner silently
    inserts an all-gather/all-to-all every step. The finding carries the
    axis, the predicted bytes/device/step, and a copy-pasteable constraint
    hint. Input-valued conflicts are reported by the more specific
    ``spmd-sharding-mismatch`` instead."""
    sa = _sharding_of(graph)
    if sa is None:
        return
    from .shard_lint import _spec_str

    for r in sa.reshards:
        if r.kind not in ("constraint", "dot") or r.path:
            continue
        if getattr(r, "declared", False):
            # framework sharding policy (ZeRO param all-gather, group_sharded
            # placement): the reshard is the design, not a bug — it stays in
            # the priced-collectives table but must not gate CI
            continue
        axis = "+".join(r.axes)
        what = ("the sharding constraint" if r.kind == "constraint"
                else "a dot contraction sharded on a different axis")
        yield Finding(
            rule="spmd-implicit-resharding",
            severity="error",
            message=f"propagated sharding {_spec_str(r.from_spec)} "
                    f"disagrees with {what}: GSPMD inserts an {r.op} over "
                    f"mesh axis '{axis}' ({_fmt_mib(r.bytes)}/device/step)",
            where=r.where,
            hint=f"make the producer agree with the consumer — constrain "
                 f"it at creation: with_sharding_constraint(value, "
                 f"NamedSharding(mesh, {_spec_str(r.to_spec)})), or fix "
                 f"the mismatched constraint to {_spec_str(r.from_spec)}",
            data={"axis": axis, "bytes": r.bytes, "op": r.op,
                  "kind": r.kind, "from_spec": _spec_str(r.from_spec),
                  "to_spec": _spec_str(r.to_spec)},
        )


@register_rule(
    "spmd-sharding-mismatch", "error",
    "an input's staged sharding conflicts with its first use: silent full "
    "reshard every step")
def _spmd_sharding_mismatch(graph):
    """The example batch/state arrives on the mesh with a sharding its very
    first consumer cannot use — every step pays a full reshard before any
    compute. Distinct from ``spmd-implicit-resharding``: the fix is at the
    staging site (``DeviceLoader place_fn`` / ``device_put`` spec), not in
    the step body."""
    sa = _sharding_of(graph)
    if sa is None:
        return
    from .shard_lint import _spec_str

    seen = set()
    for r in sa.reshards:
        if not r.path or r.path in seen:
            continue
        seen.add(r.path)
        axis = "+".join(r.axes)
        yield Finding(
            rule="spmd-sharding-mismatch",
            severity="error",
            message=f"input {r.path} is staged as "
                    f"{_spec_str(r.from_spec)} but its first use needs "
                    f"{_spec_str(r.to_spec)}: GSPMD reshards it "
                    f"({r.op} over '{axis}', "
                    f"{_fmt_mib(r.bytes)}/device/step)",
            path=r.path,
            where=r.where,
            hint=f"stage it in the layout the step consumes: "
                 f"jax.device_put(x, NamedSharding(mesh, "
                 f"{_spec_str(r.to_spec)})) (DeviceLoader place_fn does "
                 f"this off the hot path)",
            data={"axis": axis, "bytes": r.bytes, "op": r.op,
                  "from_spec": _spec_str(r.from_spec),
                  "to_spec": _spec_str(r.to_spec)},
        )


@register_rule(
    "spmd-replicated-optimizer-state", "warning",
    "optimizer accumulators fully replicated across the data axis: the "
    "ZeRO opportunity")
def _spmd_replicated_optimizer_state(graph):
    """Optimizer accumulator leaves (moments, master weights) replicated
    across the data-parallel axis burn ``(dp-1)/dp`` of their HBM for
    nothing — 'Automatic Cross-Replica Sharding of Weight Update in
    Data-Parallel Training' (arxiv 2004.13336): reduce-scatter the grads,
    shard the update, all-gather the params."""
    sa = _sharding_of(graph)
    if sa is None or sa.mesh is None:
        return
    sizes = sa.axis_order
    data_axis = "dp" if "dp" in sizes else (next(iter(sizes), None))
    if not data_axis or int(sizes.get(data_axis, 1)) <= 1:
        return
    threshold = graph.config.get("zero_min_bytes", 1 << 20)
    repl_bytes = 0
    example = ""
    n_leaves = 0
    for path, leaf in graph.state_in_paths:
        # "others" covers optimizer state threaded through a wrapper that
        # exposes the _state_pytree protocol without subclassing Optimizer
        # (e.g. distributed.sharding.zero.ShardedOptimizer)
        if not (path.startswith("state['optimizers']")
                or path.startswith("state['others']")):
            continue
        spec = sa.in_specs.get(path)
        if spec is None:
            continue
        axes = {a for dim in spec for a in dim}
        if data_axis in axes:
            continue  # already ZeRO-sharded
        nbytes = _nbytes(leaf)
        denom = 1
        for a in axes:
            denom *= int(sizes.get(a, 1))
        local = nbytes / max(denom, 1)
        if local <= 0:
            continue
        repl_bytes += local
        n_leaves += 1
        if not example:
            example = path
    if repl_bytes < threshold:
        return
    dp = int(sizes[data_axis])
    yield Finding(
        rule="spmd-replicated-optimizer-state",
        severity="warning",
        message=f"{n_leaves} optimizer accumulator leaves "
                f"({_fmt_mib(repl_bytes)}/device) are fully replicated "
                f"across the '{data_axis}' axis (size {dp}): "
                f"{_fmt_mib(repl_bytes * (dp - 1) / dp)}/device is "
                f"redundant",
        path=example,
        hint="shard the weight update over the data axis (ZeRO): "
             "distributed.sharding.group_sharded_parallel(model, opt, "
             "level='os', group=...), or strategy.sharding=True with "
             "sharding_configs['stage']=1 on the Engine",
        data={"axis": data_axis, "bytes": repl_bytes,
              "redundant_bytes": repl_bytes * (dp - 1) / dp,
              "leaves": n_leaves},
    )


@register_rule(
    "spmd-comm-bound-step", "warning",
    "predicted interconnect traffic dominates the step's memory traffic")
def _spmd_comm_bound(graph):
    sa = _sharding_of(graph)
    if sa is None or not sa.collectives:
        return
    threshold = graph.config.get("comm_bound_fraction", 0.25)
    frac = sa.comm_fraction
    if frac <= threshold:
        return
    per_axis = {a: st["bytes"] for a, st in sa.collectives.by_axis.items()}
    worst = max(per_axis, key=per_axis.get)
    yield Finding(
        rule="spmd-comm-bound-step",
        severity="warning",
        message=f"predicted comm_fraction {frac:.2f} exceeds "
                f"{threshold:.2f}: "
                f"{_fmt_mib(sa.comm_bytes)}/device/step crosses the "
                f"interconnect (axis '{worst}' moves the most)",
        hint="grow the per-device work (bigger microbatch / longer "
             "sequence), or re-balance the mesh away from the "
             f"'{worst}' axis — compare candidates with "
             "tools/shard_lint.py before burning a multichip run",
        data={"comm_fraction": frac, "comm_bytes": sa.comm_bytes,
              "bytes_by_axis": per_axis},
    )


# ---------------------------------------------------------------------------
# HBM liveness rules (mem_lint timeline — ISSUE 12)
# ---------------------------------------------------------------------------
def _timeline_of(graph):
    """The :class:`~.mem_lint.MemoryTimeline` lint_step attached (None when
    the liveness pass failed or was skipped)."""
    return getattr(graph, "memory", None)


@register_rule(
    "hbm-peak-over-capacity", "error",
    "predicted HBM peak exceeds the device budget: the step will OOM at "
    "dispatch")
def _hbm_peak_over_capacity(graph):
    """The whole point of predicting the peak: compare it against the
    per-device HBM budget BEFORE paying for a compile (or an OOM). The
    budget comes from ``config['hbm_capacity_bytes']`` (the CLI's
    ``--capacity``) or the runtime's reported limit; with neither (plain
    XLA:CPU) the rule stays silent."""
    tl = _timeline_of(graph)
    if tl is None or tl.peak_bytes <= 0:
        return
    cap = graph.config.get("hbm_capacity_bytes")
    if not cap:
        from .mem_lint import device_capacity_bytes

        cap = device_capacity_bytes()
    if not cap or tl.peak_bytes <= float(cap):
        return
    top = tl.contributors(3)
    top_s = "; ".join(
        f"{b.dtype}{list(b.shape)} {_fmt_mib(b.nbytes)} "
        f"[{b.path or b.where or b.kind}]" for b in top)
    yield Finding(
        rule="hbm-peak-over-capacity",
        severity="error",
        message=f"predicted peak {_fmt_mib(tl.peak_bytes)} exceeds the "
                f"{_fmt_mib(float(cap))} device budget "
                f"({tl.peak_bytes / float(cap):.2f}x) — top contributors: "
                f"{top_s}",
        where=tl.peak_where,
        hint="shrink the live set at the peak: donate single-use inputs, "
             "checkpoint long-lived activations (jax.checkpoint), shard "
             "the model further, or cut the batch/sequence",
        data={"peak_bytes": tl.peak_bytes, "capacity_bytes": float(cap),
              "peak_index": tl.peak_index,
              "contributors": [b.as_dict() for b in top]},
    )


@register_rule(
    "hbm-remat-candidate", "warning",
    "large activation held live across the peak for the backward: a "
    "jax.checkpoint boundary would trade it for recompute")
def _hbm_remat_candidate(graph):
    """Long-lived large temporaries alive at the peak — in a train step
    these are the forward activations (or scan residuals) the backward
    consumes much later. Rematerialization ('Checkpointing Beyond
    Sqrt(N)') trades exactly these bytes for recompute FLOPs."""
    tl = _timeline_of(graph)
    if tl is None or tl.peak_bytes <= 0:
        return
    min_bytes = graph.config.get("remat_min_bytes", 8 << 20)
    min_span = graph.config.get("remat_min_span", 0.35)
    for b in tl.long_lived(min_bytes, min_span)[:4]:
        span = (b.death - max(b.birth, 0) + 1) / float(max(tl.n_steps, 1))
        what = ("scan residuals saved for the backward"
                if b.tag in ("residual", "scan-ys")
                else "an activation held for the backward")
        # quantify the win from the liveness timeline (mirror of the
        # donation rule's delta_if_donated): predicted peak delta if THIS
        # buffer were rematerialized — the same number the auto-remat
        # planner (analysis.remat_plan) ranks sites by
        try:
            freed = float(tl.delta_if_remat([b.key]))
        except Exception:
            freed = 0.0
        hint = ("wrap the producing block in jax.checkpoint (a.k.a. "
                "jax.remat): forward recomputes it in the backward "
                "instead of holding it — or let the planner pick the "
                'sites: `Model.prepare(remat="auto")` / '
                "`Engine(remat=budget_bytes)` "
                "(analysis.remat_plan.plan_remat)")
        data = {"nbytes": b.nbytes, "span": span, "tag": b.tag,
                "birth": b.birth, "death": b.death,
                "peak_fraction": b.nbytes / tl.peak_bytes}
        msg = (f"{b.dtype}{list(b.shape)} ({_fmt_mib(b.nbytes)}, "
               f"{100.0 * b.nbytes / tl.peak_bytes:.0f}% of peak) "
               f"lives across {span:.0%} of the step — {what}")
        if freed > 0:
            data["delta_if_remat"] = freed
            msg += (f"; rematerializing it is predicted to cut the peak "
                    f"by {_fmt_mib(freed)}")
        yield Finding(
            rule="hbm-remat-candidate",
            severity="warning",
            message=msg,
            where=b.where,
            hint=hint,
            data=data,
        )


@register_rule(
    "hbm-liveness-spike", "warning",
    "one equation allocates most of the peak at once: a blockwise/fused "
    "formulation would stream it")
def _hbm_liveness_spike(graph):
    """A single eqn materializing ≥ ``spike_fraction`` of the peak in one
    go (the O(seq²) attention-logits matrix is the canonical case) — the
    blockwise/flash formulation streams it through VMEM-sized tiles
    instead of materializing it in HBM."""
    tl = _timeline_of(graph)
    if tl is None or tl.peak_bytes <= 0:
        return
    frac = graph.config.get("spike_fraction", 0.50)
    floor = graph.config.get("spike_min_bytes", 1 << 20)
    spikes = tl.spikes(frac, min_bytes=floor)
    if not spikes:
        return
    i, alloc = spikes[0]
    prim, where = tl.steps[i]
    yield Finding(
        rule="hbm-liveness-spike",
        severity="warning",
        message=f"`{prim}` materializes {_fmt_mib(alloc)} in one equation "
                f"({100.0 * alloc / tl.peak_bytes:.0f}% of the "
                f"{_fmt_mib(tl.peak_bytes)} predicted peak)",
        where=where,
        hint="restructure blockwise so XLA can fuse/stream it (e.g. "
             "flash-style attention over key blocks instead of the full "
             "O(seq^2) logits matrix), or jnp.einsum the producer and "
             "consumer together",
        data={"alloc_bytes": alloc, "eqn_index": i, "prim": prim,
              "peak_fraction": alloc / tl.peak_bytes},
    )


@register_rule(
    "hbm-unfused-chain", "warning",
    "an elementwise chain the fusion simulator predicts XLA will NOT fuse "
    "materializes a large temporary")
def _hbm_unfused_chain(graph):
    """The fusion plan (:mod:`.fusion`) normally elides elementwise
    temporaries — this rule surfaces the big ones it could NOT certify:
    a chain split by an opaque barrier (host callback / custom call —
    XLA cannot see through it), by an output/donation seam (the value is
    written to HBM as a program output — under donation, into the donated
    storage — yet also consumed mid-chain), or by a fanout past the
    duplication limit. Each is a buffer the user can often win back by
    restructuring; the fused neighbours cost nothing."""
    tl = _timeline_of(graph)
    if tl is None or not getattr(tl, "fusion", False):
        return
    floor = graph.config.get("unfused_chain_min_bytes", 1 << 20)
    from .fusion import OPAQUE_BARRIERS

    rows = []
    for b in tl.buffers:
        r = getattr(b, "unfused_reason", "")
        if not r or b.eff_bytes < floor:
            continue
        if r.startswith("barrier:"):
            if r.split(":", 1)[1] not in OPAQUE_BARRIERS:
                continue  # feeding a dot/conv/reduce is normal, not a bug
        elif r == "output-seam":
            pass
        elif r.startswith("fanout:"):
            pass
        else:  # expensive-fanout etc.: expected XLA behavior, not a chain
            continue
        rows.append(b)
    rows.sort(key=lambda b: -b.nbytes)
    for b in rows[:4]:
        r = b.unfused_reason
        if r.startswith("barrier:"):
            prim = r.split(":", 1)[1]
            why = (f"its consumer `{prim}` is opaque to XLA fusion — the "
                   "chain is forced through HBM at the boundary")
            hint = (f"move the `{prim}` out of the hot chain (hoist the "
                    "host round-trip / custom call before or after the "
                    "fused region), or accept the materialization")
        elif r == "output-seam":
            why = ("it is a program output consumed mid-chain — the HBM "
                   "write (the donation-alias target when state is "
                   "donated) splits what would otherwise fuse")
            hint = ("if the output is only needed for logging, compute it "
                    "from the final values instead of mid-chain; "
                    "otherwise this write is the price of returning it")
        else:  # fanout:<n>
            n = r.split(":", 1)[1]
            why = (f"it feeds {n} consumers — past the duplication limit, "
                   "XLA materializes instead of recomputing per consumer")
            hint = ("restructure so fewer fusion groups read the value, "
                    "or accept the materialization (recompute would cost "
                    f"{n}x the producer FLOPs)")
        yield Finding(
            rule="hbm-unfused-chain",
            severity="warning",
            message=f"{b.dtype}{list(b.shape)} ({_fmt_mib(b.nbytes)}) "
                    f"materializes although its producer chain is "
                    f"fusible: {why}",
            where=b.where,
            hint=hint,
            data={"nbytes": b.nbytes, "reason": r, "birth": b.birth,
                  "death": b.death, "key": b.key},
        )


def _arg_prefix(path):
    import re

    m = re.match(r"(args\[\d+\]|kwargs\[[^\]]*\])", path or "")
    return m.group(1) if m else None


@register_rule(
    "hbm-kv-bucket-waste", "warning",
    "serving cache bucket padding wastes a large share of the cache bytes")
def _hbm_kv_bucket_waste(graph):
    """A donated KV-cache argument (groups of identical 4-D
    [batch, max_len, heads, head_dim] buffers + an int32 [batch] lengths
    vector) whose example lengths round up to prefill buckets so much that
    ≥ ``kv_waste_fraction`` of the reserved rows are padding — shrink the
    bucket ladder or max_len. Of several groups (a cache that also keeps
    rings of a window's rows, or 4-D recurrent state) the one with the most
    rows a slot is the full-length K/V: a ring's rows stop at its window
    whatever the bucket, and only that group's bytes are priced."""
    threshold = graph.config.get("kv_waste_fraction", 0.25)
    groups = {}
    for path, leaf, donated in graph.dyn_args:
        pre = _arg_prefix(path)
        if pre is None or not donated:
            continue
        groups.setdefault(pre, []).append((path, leaf))
    for pre, leaves in groups.items():
        bufs = {}
        lengths = None
        for path, leaf in leaves:
            leaf = getattr(leaf, "_value", leaf)
            shape, dtype = _shape_dtype(leaf)
            if len(shape) == 4:
                bufs.setdefault((shape, dtype), []).append(path)
            elif len(shape) == 1 and dtype in ("int32", "int64"):
                lengths = (path, leaf)
        if lengths is None or not bufs:
            continue
        bufs = {key: paths for key, paths in bufs.items() if len(paths) > 1}
        if not bufs:
            continue
        (shape, dtype), paths = max(bufs.items(),
                                    key=lambda kv: (kv[0][0][1], len(kv[1])))
        batch, max_len = int(shape[0]), int(shape[1])
        lpath, lleaf = lengths
        if tuple(getattr(lleaf, "shape", ())) != (batch,):
            continue
        try:
            vals = np.asarray(lleaf).astype(np.int64)
        except Exception:
            continue  # abstract leaf: no concrete occupancy to judge
        active = [int(v) for v in vals if v > 0]
        if not active:
            continue
        from ..serving.kv_cache import default_buckets, pick_bucket

        buckets = graph.config.get("prefill_buckets") or \
            default_buckets(max_len)
        padded = []
        for n in active:
            try:
                padded.append(pick_bucket(n, buckets))
            except ValueError:
                padded.append(max_len)
        reserved = float(sum(padded))
        waste = (reserved - sum(active)) / reserved if reserved else 0.0
        if waste < threshold:
            continue
        group_bytes = sum(_nbytes(l) for path, l in leaves if path in paths)
        per_row = group_bytes / float(batch * max_len) if batch * max_len \
            else 0.0
        wasted_bytes = (reserved - sum(active)) * per_row
        yield Finding(
            rule="hbm-kv-bucket-waste",
            severity="warning",
            message=f"cache {pre} ({len(paths)} buffers of "
                    f"{dtype}{list(shape)}): bucket padding wastes "
                    f"{waste:.0%} of the reserved rows "
                    f"(~{_fmt_mib(wasted_bytes)}) for lengths "
                    f"{sorted(active)[:8]} under buckets "
                    f"{list(buckets)}",
            path=lpath,
            hint="tighten the bucket ladder (prefill_buckets=) toward the "
                 "observed prompt lengths, or lower max_len — every "
                 "padded row is HBM the admission policy must reserve",
            data={"waste_fraction": waste, "wasted_bytes": wasted_bytes,
                  "buckets": [int(b) for b in buckets],
                  "lengths": [int(v) for v in vals],
                  "batch": batch, "max_len": max_len},
        )


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _shape_dtype(leaf):
    shape = tuple(getattr(leaf, "shape", ()))
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        return shape, "?"
    try:
        return shape, str(np.dtype(dtype))
    except TypeError:  # extended dtypes (PRNG key arrays etc.)
        return shape, str(dtype)


def _nbytes(leaf):
    shape, dtype = _shape_dtype(leaf)
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    return n * itemsize

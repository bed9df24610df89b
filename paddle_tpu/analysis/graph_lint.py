"""Static jaxpr analysis of compiled steps — trace, don't run.

``trace_step`` abstractly traces a :class:`~paddle_tpu.jit.functionalize.
CompiledStep` via ``jax.make_jaxpr`` (shape-level evaluation only; nothing
executes on a device) and packages the result as a :class:`StepGraph`:
the closed jaxpr, the input/state/output pytrees with path provenance, and
the step's donation metadata. ``lint_step`` runs the rule registry
(:mod:`.rules`) over it and returns a :class:`~.findings.LintReport`.

This is the compiler-side complement of ``profiler/telemetry.py``: telemetry
measures a recompile or host stall *after* it burned device time; the lint
pass predicts the same defect from the program alone, before the first step
runs (cross-checked in :mod:`.crosscheck`).
"""
from __future__ import annotations

import os
import warnings

import jax
import numpy as np
from jax._src import source_info_util
from jax.extend.core import ClosedJaxpr, Jaxpr

from .findings import LintReport
from .mem_lint import MEM_LINT_DEFAULTS
from .rules import run_rules
from .shard_lint import SHARD_LINT_DEFAULTS

__all__ = ["StepGraph", "trace_step", "lint_step", "LINT_DEFAULTS"]

#: default thresholds consumed by the rules via ``StepGraph.config``
LINT_DEFAULTS = {
    "donate_min_bytes": 1 << 20,   # hbm-undonated-input size floor
    "const_warn_bytes": 1 << 20,   # hbm-const-folded warning floor
    "const_error_bytes": 64 << 20,  # …and the error escalation point
    **SHARD_LINT_DEFAULTS,         # spmd-* rule thresholds (ISSUE 7)
    **MEM_LINT_DEFAULTS,           # hbm-* liveness thresholds (ISSUE 12)
}


def _subjaxprs(v):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def _eqn_where(eqn):
    """User-code ``file:line`` provenance for a jaxpr equation ('' when the
    equation carries no traceback). ``user_frame`` is jax-private and takes
    the traceback itself on jax 0.9; nothing is caught here, so the next
    signature change fails the lint tests instead of emptying every
    finding's ``where``."""
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return ""
    return f"{os.path.basename(frame.file_name)}:{frame.start_line}"


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn, _eqn_where(eqn)
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _path_str(prefix, path):
    from jax.tree_util import keystr

    return prefix + keystr(tuple(path))


def _arg_path_str(path):
    """(args, kwargs) two-tuple paths -> ``args[i]…`` / ``kwargs['k']…``."""
    from jax.tree_util import keystr

    head, rest = path[0], tuple(path[1:])
    base = "args" if getattr(head, "idx", 0) == 0 else "kwargs"
    return base + keystr(rest)


def _flatten_args_classified(tree):
    """Flatten an (args, kwargs) tree into dynamic (traced-array) and static
    (python-attribute) leaves, each with its user-facing path string."""
    from ..jit.functionalize import _is_dynamic_leaf

    dyn, static = [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        p = _arg_path_str(path)
        (dyn if _is_dynamic_leaf(leaf) else static).append((p, leaf))
    return dyn, static


class StepGraph:
    """The abstractly-traced step, as the lint rules consume it.

    Attributes:
        name: step-function name.
        closed_jaxpr / consts: the traced program and its captured constants.
        state_in_paths / state_out_paths: ``[(path, leaf-or-SDS)]`` of the
            threaded state pytree entering and leaving the step.
        state_in_treedef / state_out_treedef: the two structures (retrace
            rule compares them).
        dyn_args: ``[(path, leaf, donated)]`` traced argument leaves.
        static_args: ``[(path, value)]`` python-attribute argument leaves.
        out_paths: ``[(path, ShapeDtypeStruct)]`` of the function outputs.
        variants: per-extra-batch signatures for the shape-churn rules.
        config: thresholds (see :data:`LINT_DEFAULTS`).
    """

    def __init__(self, name, closed_jaxpr, state_in, state_out_shape,
                 out_shape, dyn_args, static_args, donate_state,
                 donate_inputs, config):
        self.name = name
        self.closed_jaxpr = closed_jaxpr
        self.consts = list(getattr(closed_jaxpr, "consts", ()) or ())
        self.donate_state = donate_state
        self.donate_inputs = donate_inputs
        self.config = dict(LINT_DEFAULTS, **(config or {}))
        self.variants = []
        # populated by lint_step when a mesh is in play: the abstract SPMD
        # propagation (shard_lint.ShardingAnalysis) the spmd-* rules read
        self.sharding = None
        # populated by lint_step: the abstract liveness timeline
        # (mem_lint.MemoryTimeline) the hbm-* rules read
        self.memory = None

        def _paths(prefix, tree):
            return [(_path_str(prefix, p), l) for p, l in
                    jax.tree_util.tree_flatten_with_path(tree)[0]]

        self.state_in_paths = _paths("state", state_in)
        self.state_out_paths = _paths("state", state_out_shape)
        self.state_in_treedef = jax.tree_util.tree_structure(state_in)
        self.state_out_treedef = jax.tree_util.tree_structure(state_out_shape)
        self.out_paths = _paths("out", out_shape)
        self.dyn_args = dyn_args
        self.static_args = static_args

    def eqns(self):
        """Yield ``(eqn, where)`` over the program, recursing into
        sub-jaxprs (pjit bodies, scan/while/cond, shard_map regions…)."""
        return _walk_eqns(self.closed_jaxpr.jaxpr)

    def add_variant(self, args, kwargs):
        from ..jit.functionalize import _unwrap

        tree = jax.tree_util.tree_map(_unwrap, (args, kwargs or {}))
        dyn, static = _flatten_args_classified(tree)
        self.variants.append({
            "dyn": [(p, tuple(getattr(l, "shape", ())),
                     str(np.dtype(getattr(l, "dtype", np.float32))))
                    for p, l in dyn],
            "static": static,
        })


def trace_step(step, *args, config=None, **kwargs):
    """Abstractly trace ``step`` (a ``CompiledStep``, or any callable — it
    is wrapped on the fly) with the example ``args`` and return the
    :class:`StepGraph`. No device computation happens: ``jax.make_jaxpr``
    evaluates shapes only, and the step's eager state is snapshotted and
    restored exactly as a real trace would."""
    from ..jit.functionalize import CompiledStep, _unwrap

    if not isinstance(step, CompiledStep):
        step = CompiledStep(step, stateful=(), donate_state=False)

    state = step.spec.snapshot()
    dyn_don, dyn_kept, static = step._prepare(args, kwargs)
    try:
        closed_jaxpr, out_shape = jax.make_jaxpr(
            lambda s, dd, dk: step._pure(s, dd, dk, static),
            return_shape=True)(state, dyn_don, dyn_kept)
    finally:
        # pure()'s own finally restores the state it snapshotted at trace
        # entry — but values created DURING the trace (jnp.asarray of a
        # python counter, lazily-born accumulators) are tracers there.
        # Under jax.jit the subsequent install of the executable's concrete
        # outputs masks that; make_jaxpr has no outputs, so re-install the
        # pre-trace eager snapshot or tracers leak into framework state.
        step.spec.install(state)
        step.spec.clear_grads()
    out_arrays_shape, state_out_shape = out_shape

    tree = jax.tree_util.tree_map(_unwrap, (args, kwargs))
    dyn, static_args = _flatten_args_classified(tree)
    mask = static[2] if len(static) > 2 else ()
    if len(mask) != len(dyn):  # degraded static spec: donation unknown
        mask = (False,) * len(dyn)
    dyn_args = [(p, l, bool(m)) for (p, l), m in zip(dyn, mask)]

    return StepGraph(
        name=step.name,
        closed_jaxpr=closed_jaxpr,
        state_in=state,
        state_out_shape=state_out_shape,
        out_shape=out_arrays_shape,
        dyn_args=dyn_args,
        static_args=static_args,
        donate_state=getattr(step, "donate_state", False),
        donate_inputs=getattr(step, "donate_inputs", False),
        config=config,
    )


def _env_ignore():
    raw = os.environ.get("PADDLE_TPU_LINT_IGNORE", "")
    return tuple(x.strip() for x in raw.split(",") if x.strip())


#: unknown rule ids already warned about (once per process, not per lint)
_WARNED_UNKNOWN_IGNORE = set()


def _check_ignore(ignore, source):
    """An ``ignore=`` entry naming a rule that doesn't exist is almost
    always a typo silently un-silencing the real rule — warn once per
    unknown id instead of no-opping."""
    from .rules import RULES

    for rule_id in ignore:
        if rule_id in RULES or rule_id in _WARNED_UNKNOWN_IGNORE:
            continue
        _WARNED_UNKNOWN_IGNORE.add(rule_id)
        warnings.warn(
            f"graph lint: {source} names unknown rule id '{rule_id}' "
            f"(known: {', '.join(sorted(RULES))})",
            RuntimeWarning, stacklevel=3)
    return tuple(ignore)


def lint_step(step, *args, extra_args=(), ignore=(), config=None, mesh=None,
              in_shardings=None, **kwargs):
    """Lint a step function against the example batch ``args``/``kwargs``.

    Args:
        step: a ``CompiledStep`` or plain callable.
        extra_args: optional additional example batches, each ``(args,)``
            or ``(args, kwargs)`` tuples — enables the cross-batch
            ``retrace-shape-churn`` / ``retrace-static-value`` rules.
        ignore: rule ids to silence (merged with the comma-separated
            ``PADDLE_TPU_LINT_IGNORE`` environment variable; ids are
            checked against the registry — unknown ids warn once).
        config: threshold overrides (see :data:`LINT_DEFAULTS`).
        mesh: a :class:`jax.sharding.Mesh` to run the abstract SPMD
            propagation under (:mod:`.shard_lint`), enabling the
            ``spmd-*`` rules. When omitted, a mesh is inferred from the
            example batch / state ``NamedSharding`` leaves, so multichip
            steps get the sharding lint automatically.
        in_shardings: optional ``{input path: PartitionSpec}`` overrides
            for the propagation (defaults come from the leaves).

    Returns:
        :class:`~paddle_tpu.analysis.findings.LintReport`
    """
    graph = trace_step(step, *args, config=config, **kwargs)
    for extra in extra_args:
        if isinstance(extra, tuple) and len(extra) == 2 \
                and isinstance(extra[1], dict):
            vargs, vkwargs = extra
        else:
            vargs, vkwargs = tuple(extra), {}
        graph.add_variant(vargs, vkwargs)
    try:
        from . import shard_lint

        graph.sharding = shard_lint.analyze_sharding(
            graph, mesh=mesh, in_shardings=in_shardings)
    except Exception as e:  # noqa: BLE001 - the spmd pass is advisory
        warnings.warn(f"shard lint propagation failed on '{graph.name}': "
                      f"{e!r}", RuntimeWarning, stacklevel=2)
        graph.sharding = None
    try:
        from . import mem_lint

        graph.memory = mem_lint.analyze_memory(graph)
    except Exception as e:  # noqa: BLE001 - the liveness pass is advisory
        warnings.warn(f"mem lint timeline failed on '{graph.name}': "
                      f"{e!r}", RuntimeWarning, stacklevel=2)
        graph.memory = None
    # per-call ignore applies first; the env var adds on top (union) — a
    # per-call list can therefore never un-silence an env-ignored rule
    ignore = (_check_ignore(tuple(ignore), "ignore=")
              + _check_ignore(_env_ignore(), "PADDLE_TPU_LINT_IGNORE"))
    report = LintReport(run_rules(graph, ignore=ignore), step=graph.name)
    # expose the propagation to callers (CLI tables, crosscheck_comm) —
    # None when no mesh was in play
    report.sharding = graph.sharding
    # …and the liveness timeline (CLI tables, crosscheck_mem)
    report.memory = graph.memory
    return report

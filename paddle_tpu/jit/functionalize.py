"""Dygraph→XLA functionalization: the "executor" of this framework.

Reference analogue: ``paddle.jit.to_static`` (AST transpile to ProgramDesc,
``dygraph_to_static/program_translator.py:991``) executed by
InterpreterCore (``framework/new_executor/interpretercore.h:38``).

TPU-native redesign: there is no IR of our own and no interpreter. A python
step function (forward+backward+optimizer.step, written in eager dygraph
style) is *traced by jax.jit* — the tape's vjp closures are jax-traceable, so
the entire step lowers to ONE fused XLA program. Mutable framework state
(Layer params/buffers, optimizer accumulators, the RNG key) is threaded as an
explicit donated pytree: functional on the inside, mutable on the outside.

This replaces, in one mechanism: ProgramDesc construction, the op-by-op
executors, stream-aware scheduling, per-op GC, gradient fusion (Reducer
buckets), and fused-optimizer ops — XLA does the scheduling and fusion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from ..framework import random as rnd
from ..profiler import telemetry as _telemetry
from ..profiler import tracing as _tracing
from ..framework.tensor import Tensor
from ..nn.layer.layers import Layer
from ..ops import partition as _partition
from ..optimizer.optimizer import Optimizer

__all__ = ["functionalize", "CompiledStep", "to_static", "not_to_static"]

_analysis_mod = None
_devprof_mod = None


def _analysis():
    """Cached handle to paddle_tpu.analysis (lazy: keep the graph-lint
    subsystem off the import path and the per-call flag check at attribute-
    access cost)."""
    global _analysis_mod
    if _analysis_mod is None:
        from .. import analysis as _a

        _analysis_mod = _a
    return _analysis_mod


def _devprof():
    """Cached handle to paddle_tpu.profiler.devprof (lazy, same rationale
    as :func:`_analysis`)."""
    global _devprof_mod
    if _devprof_mod is None:
        from ..profiler import devprof as _d

        _devprof_mod = _d
    return _devprof_mod


def _layer_refs(layer: Layer):
    refs = {"params": {}, "buffers": {}}
    for name, p in layer.named_parameters():
        refs["params"][name] = p
    for name, b in layer.named_buffers():
        if b is not None:
            refs["buffers"][name] = b
    return refs


class _StateSpec:
    """Collects and swaps mutable state for a set of Layers/Optimizers."""

    def __init__(self, stateful):
        self.layers = [s for s in stateful if isinstance(s, Layer)]
        self.optimizers = [s for s in stateful if isinstance(s, Optimizer)]
        # anything else exposing the _state_pytree protocol (e.g. GradScaler)
        self.others = [
            s
            for s in stateful
            if not isinstance(s, (Layer, Optimizer)) and hasattr(s, "_state_pytree")
        ]
        self._refs = [_layer_refs(l) for l in self.layers]
        # materialize optimizer accumulators BEFORE the first snapshot: lazy
        # creation inside the first traced step changes the state pytree
        # between calls 1 and 2 and forces a second trace+compile (the
        # Adam/AdamW double-trace PR 2's telemetry measured; graph-lint's
        # retrace-state-structure rule catches the pattern statically).
        # "others" covered too: sharded-optimizer wrappers delegate the
        # method to their inner Optimizer via __getattr__.
        for o in self.optimizers + self.others:
            ensure = getattr(o, "_ensure_accumulators", None)
            if ensure is not None:
                ensure()

    def snapshot(self):
        # read through the refs cached at construction instead of re-walking
        # named_parameters() every step (the recursive layer traversal showed
        # up as ~2 ms/step host time in the device profile)
        return {
            "layers": [
                {"params": {n: p._value for n, p in refs["params"].items()},
                 "buffers": {n: b._value for n, b in refs["buffers"].items()}}
                for refs in self._refs
            ],
            "optimizers": [o._state_pytree() for o in self.optimizers],
            "others": [o._state_pytree() for o in self.others],
            "rng": rnd.default_generator.get_state(),
        }

    def install(self, tree):
        for refs, st in zip(self._refs, tree["layers"]):
            for name, p in refs["params"].items():
                p._value = st["params"][name]
            for name, b in refs["buffers"].items():
                b._value = st["buffers"][name]
        for o, st in zip(self.optimizers, tree["optimizers"]):
            o._load_state_pytree(st)
        for o, st in zip(self.others, tree.get("others", [])):
            o._load_state_pytree(st)
        rnd.default_generator.set_state(tree["rng"])

    def clear_grads(self):
        for refs in self._refs:
            for p in refs["params"].values():
                p._grad = None
                p._grad_node = None
                p._out_slot = 0


def _unwrap(x):
    if isinstance(x, Tensor):
        return x._value
    return x


def _wrap(x, stop_gradient=True):
    if isinstance(x, (jax.Array,)) or isinstance(x, jax.core.Tracer):
        return Tensor(x, stop_gradient=stop_gradient)
    return x


class _Dyn:
    """Placeholder marking a dynamic (traced) leaf inside the static spec."""

    __slots__ = ()

    def __repr__(self):
        return "<dyn>"


_DYN = _Dyn()


def _is_dynamic_leaf(leaf):
    """Traced-array leaf vs static python attribute. Python scalars/strings
    are STATIC — they are op attributes in the reference's ProgramDesc, not
    tensors — so a new value recompiles rather than becoming a tracer (this
    is what lets python control flow on them unroll at trace time).
    ``ShapeDtypeStruct`` counts as dynamic so ``lower``/``analyze``/devprof
    harvesting can run from shapes alone, without live (possibly donated)
    buffers."""
    import numpy as np

    return (isinstance(leaf, (jax.Array, np.ndarray, np.generic,
                              jax.ShapeDtypeStruct))
            or _is_tracer_val(leaf))


def _partition_args(args, kwargs):
    """Split the (args, kwargs) tree into traced array leaves and a hashable
    static remainder (see ``_is_dynamic_leaf`` for the boundary)."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    dyn = []
    spec = []
    for leaf in leaves:
        if _is_dynamic_leaf(leaf):
            dyn.append(leaf)
            spec.append(_DYN)
        else:
            spec.append(leaf)
    try:
        hash(tuple(spec))
        static = (treedef, tuple(spec))
    except TypeError:
        # unhashable static leaf: degrade to tracing everything
        static = None
    if static is None:
        return leaves, (treedef, None)
    return dyn, static


def _is_tracer_val(x):
    from ..framework.tensor import _is_tracer

    return _is_tracer(x)


def _arg_path_str(path):
    """(args, kwargs) pytree path -> the user-facing ``args[i]…`` /
    ``kwargs['k']…`` form used by ``donate_inputs=[…]`` and the graph-lint
    findings."""
    head, rest = path[0], tuple(path[1:])
    base = "args" if getattr(head, "idx", 0) == 0 else "kwargs"
    return base + jax.tree_util.keystr(rest)


def _onto_mesh(state, mesh):
    """State leaves still on one device (the optimizer's step counter, the
    RNG key — born on the default device) move onto the batch's mesh,
    replicated. Left there they stay on chip 0 while every other leaf is
    on the mesh, and they come back from the first step with a mesh
    sharding: that change of signature alone recompiles the second step.
    (A mesh that spans processes is left alone: a process-local array cannot
    be device_put onto devices this process does not address.)"""
    if mesh.is_multi_process:
        return state
    rep = NamedSharding(mesh, PartitionSpec())

    def put(v):
        if isinstance(getattr(v, "sharding", None), SingleDeviceSharding):
            return jax.device_put(v, rep)
        return v

    return jax.tree_util.tree_map(put, state)


class CompiledStep:
    """A cached compiled XLA step (≙ the reference's compiled-program cache in
    ``fluid/executor.py`` + InterpreterCore instruction list)."""

    def __init__(self, fn, stateful=(), donate_state=True, donate_inputs=False,
                 static_argnames=None):
        self.fn = fn
        self.name = getattr(fn, "__name__", type(fn).__name__)
        # set True by pure() — which only executes while jax traces, i.e.
        # on a compile-cache miss — so __call__ can attribute its wall time
        # to the `compile` phase instead of `dispatch`
        self._trace_marker = {"traced": False}
        self.spec = _StateSpec(stateful)
        self._pure = self._build_pure()
        # the program's name in a device trace and in the compile cache's
        # key: `jit_<step name>` (jit_train_step, jit_serve_decode, ...)
        self._pure.__name__ = self._pure.__qualname__ = self.name
        # donate_inputs: staged single-use batches (io.DeviceLoader) hand
        # their HBM back to XLA for the step's own temporaries. Contract:
        # donated inputs are CONSUMED — the caller must not touch a batch
        # after passing it in. Besides True/False it accepts an iterable of
        # argument pytree paths ("args[0]", "kwargs['x']…" — the exact form
        # graph-lint's hbm-undonated-input finding prints) to donate only
        # those leaves.
        if isinstance(donate_inputs, bool):
            self._donate_paths = None
            self.donate_inputs = donate_inputs
        else:
            self._donate_paths = tuple(str(p) for p in donate_inputs)
            self.donate_inputs = bool(self._donate_paths)
        self._donate_mask_cache = {}
        self.donate_state = bool(donate_state)
        donate = (0,) if donate_state else ()
        # argnum 1 is the donated-leaves list: empty unless donation was
        # requested, so donating it unconditionally is free
        donate = donate + (1,)
        self._jitted = jax.jit(
            self._pure, donate_argnums=donate, static_argnums=(3,),
            static_argnames=static_argnames
        )

    def _build_pure(self):
        spec = self.spec
        fn = self.fn
        marker = self._trace_marker

        def pure(state, dyn_donated, dyn_kept, static_spec):
            marker["traced"] = True
            treedef, static_leaves, don_mask, partition = static_spec
            it_d, it_k, it_m = iter(dyn_donated), iter(dyn_kept), iter(don_mask)
            if static_leaves is None:
                leaves = [next(it_d) if next(it_m) else next(it_k)
                          for _ in range(len(don_mask))]
            else:
                leaves = [((next(it_d) if next(it_m) else next(it_k))
                           if s is _DYN else s)
                          for s in static_leaves]
            args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
            prev = spec.snapshot()
            spec.install(state)
            try:
                t_args = jax.tree_util.tree_map(_wrap, args)
                t_kwargs = jax.tree_util.tree_map(_wrap, kwargs)
                # the mesh the batch arrived on: Pallas call sites partition
                # themselves over it (GSPMD cannot split a Mosaic kernel)
                with _partition.partition_scope(partition):
                    out = fn(*t_args, **t_kwargs)
                out_arrays = jax.tree_util.tree_map(_unwrap, out)
                new_state = spec.snapshot()
            finally:
                spec.clear_grads()
                spec.install(prev)
            return out_arrays, new_state

        return pure

    def _donation_mask(self, tree, treedef, spec_t, n_dyn):
        """Per-dyn-leaf donate flags. Bool modes are trivial; path mode
        resolves ``self._donate_paths`` against the leaf paths once per
        (treedef, spec) signature and caches the mask."""
        if self._donate_paths is None:
            return ((True,) * n_dyn if self.donate_inputs
                    else (False,) * n_dyn)
        key = (treedef, spec_t) if spec_t is not None else None
        mask = self._donate_mask_cache.get(key) if key is not None else None
        if mask is None or len(mask) != n_dyn:
            flags = []
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                if spec_t is not None and not _is_dynamic_leaf(leaf):
                    continue
                p = _arg_path_str(path)
                flags.append(any(p == d or p.startswith(d)
                                 for d in self._donate_paths))
            mask = tuple(flags)
            if key is not None:
                self._donate_mask_cache[key] = mask
        return mask

    def _prepare(self, args, kwargs):
        arr_args = jax.tree_util.tree_map(_unwrap, args)
        arr_kwargs = jax.tree_util.tree_map(_unwrap, kwargs)
        dyn, (treedef, spec_t) = _partition_args(arr_args, arr_kwargs)
        mask = self._donation_mask((arr_args, arr_kwargs), treedef, spec_t,
                                   len(dyn))
        dyn_donated = [l for l, m in zip(dyn, mask) if m]
        dyn_kept = [l for l, m in zip(dyn, mask) if not m]
        return dyn_donated, dyn_kept, (treedef, spec_t, mask,
                                       _partition.observed_partition(dyn))

    def _invoke(self, args, kwargs):
        from ..fault import inject

        state = self.spec.snapshot()
        dyn_donated, dyn_kept, static = self._prepare(args, kwargs)
        if static[3] is not None:
            state = _onto_mesh(state, static[3][0])
        try:
            inject.check("dispatch")  # oom/error injection (devprof tests)
            out_arrays, new_state = self._jitted(state, dyn_donated, dyn_kept,
                                                 static)
        except Exception as e:
            if _devprof().is_oom_error(e):
                # device OOM at dispatch: dump the ranked forensics
                # (memory breakdown, donation status, batch/state shapes)
                # before re-raising the original XLA error
                try:
                    _devprof().dump_oom_forensics(self, e, args, kwargs)
                except Exception:  # noqa: BLE001 - never mask the OOM
                    pass
            raise
        self.spec.install(new_state)
        self.spec.clear_grads()
        return jax.tree_util.tree_map(lambda a: _wrap(a), out_arrays)

    def __call__(self, *args, **kwargs):
        if (_analysis().lint_on_compile_enabled()
                and not getattr(self, "_autolint_done", False)):
            # opt-in warn-on-compile: lint BEFORE the first execution — the
            # retrace hazards (lazily-materialized optimizer state) are only
            # visible in the PRE-step state pytree; after one real step the
            # state has stabilized and the defect is invisible statically
            _analysis().autolint(self, args, kwargs, enabled=True)
        # the one boundary call: `dispatch` (a cached call: host enqueue
        # time), renamed `compile` below if this call turns out to trace
        span = _telemetry.phase_span("dispatch", key=self.name)
        if span is _tracing.NULL_SPAN:
            return self._invoke(args, kwargs)
        tm_on = _telemetry.enabled()
        marker = self._trace_marker
        marker["traced"] = False
        # capture the batch signature (shapes only) BEFORE the call: if it
        # traces, devprof harvests against it — the real buffers may be
        # donated/consumed by then. Skipped once the harvest has run.
        sig = None
        if tm_on and not getattr(self, "_devprof_done", False) \
                and _devprof().auto_harvest_enabled():
            try:
                sig = _devprof()._shape_only((args, kwargs))
            except Exception:
                sig = None
        # JAX's own trace / lowering / backend-compile events of this
        # thread belong to this step while the call is in flight
        watch = _telemetry.compile_watch_begin() if tm_on else None
        try:
            with span:
                out = self._invoke(args, kwargs)
                if marker["traced"]:
                    # traced this call: wall time is dominated by trace +
                    # XLA compile; repeated hits here for one step name =
                    # shape/dtype churn. In a request's (or train step's)
                    # trace the span says who paid this compile
                    span.name = "compile"
                    span.set_attr("step", self.name)
                    if tm_on:
                        span.set_attr(
                            "compile_index",
                            _telemetry.get_telemetry().compile_counts()
                            .get(self.name, 0) + 1)
        finally:
            if watch is not None:
                _telemetry.compile_watch_end(watch)
        if marker["traced"] and tm_on:
            _telemetry.get_telemetry().note_compile(
                self.name, span.start_ns, span.end_ns, watch)
            if sig is not None:
                # first compile: harvest the DeviceCostReport (memory/
                # cost/comm ground truth) into the telemetry registry
                _devprof().maybe_harvest_on_compile(self, sig[0], sig[1])
        return out

    def analyze(self, *args, **kwargs):
        """Statically lint this step against the example batch — abstract
        trace only, nothing runs on device. Returns a
        :class:`paddle_tpu.analysis.LintReport`."""
        return _analysis().lint_step(self, *args, **kwargs)

    def device_report(self, *args, **kwargs):
        """Harvest the compile-time :class:`~paddle_tpu.profiler.devprof.
        DeviceCostReport` for this step against the example batch: FLOPs,
        bytes accessed, the HBM peak breakdown, and per-mesh-axis
        collective bytes. Arguments are reduced to shapes before lowering,
        so donated/consumed batches are safe to pass."""
        return _devprof().device_report(self, *args, **kwargs)

    def lower(self, *args, **kwargs):
        state = self.spec.snapshot()
        dyn_donated, dyn_kept, static = self._prepare(args, kwargs)
        return self._jitted.lower(state, dyn_donated, dyn_kept, static)


def functionalize(fn=None, *, stateful=(), donate_state=True,
                  donate_inputs=False):
    """Decorator: compile a dygraph-style step function into one XLA program.

        @paddle_tpu.jit.functionalize(stateful=[model, opt])
        def train_step(x, y):
            loss = loss_fn(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

    ``donate_inputs=True`` additionally donates the batch arrays (see
    ``CompiledStep``): use with single-use staged batches only.
    ``donate_inputs=["args[0]"]`` donates just the named argument pytree
    paths — the exact strings graph-lint's ``hbm-undonated-input`` finding
    prints.
    """

    def deco(f):
        step = CompiledStep(f, stateful=stateful, donate_state=donate_state,
                            donate_inputs=donate_inputs)
        functools.update_wrapper(step, f, updated=())
        return step

    return deco(fn) if fn is not None else deco


class StaticFunction:
    """`@to_static` on a Layer's forward / plain function (inference path):
    no in-place state writes expected; buffers treated read-only."""

    def __init__(self, fn, layer=None):
        self.fn = fn
        self.layer = layer
        self._compiled = None

    def _ensure(self):
        if self._compiled is None:
            stateful = [self.layer] if self.layer is not None else []
            self._compiled = CompiledStep(self.fn, stateful=stateful, donate_state=False)
        return self._compiled

    def __call__(self, *args, **kwargs):
        from jax._src import core as _jcore

        if not _jcore.trace_state_clean():
            # already inside a trace (an enclosing CompiledStep, or this
            # function calling itself): inline into the outer program — the
            # reference likewise inlines nested to_static functions into one
            # ProgramDesc rather than nesting executors
            return self.fn(*args, **kwargs)
        return self._ensure()(*args, **kwargs)

    @property
    def code(self):
        import inspect

        return inspect.getsource(self.fn)

    def concrete_program(self, *args, **kwargs):
        return self._ensure().lower(*args, **kwargs)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """paddle.jit.to_static — jax.jit tracing + AST control-flow conversion.

    Tensor-dependent Python ``if``/``while``/``for range`` are rewritten by
    :mod:`paddle_tpu.jit.dy2static` onto ``lax.cond``/``lax.while_loop``
    (the reference's dygraph_to_static AST transpile, retargeted); constructs
    outside the transform contract (early return under a tensor condition)
    keep Python semantics and raise jax's concretization error under trace."""
    from . import dy2static

    def deco(fn):
        if isinstance(fn, Layer):
            layer = fn
            fwd = dy2static.convert_to_static(type(layer).forward)
            sf = StaticFunction(lambda *a, **k: fwd(layer, *a, **k), layer=layer)
            layer.forward = sf
            return layer
        return StaticFunction(dy2static.convert_to_static(fn))

    return deco(function) if function is not None else deco


def not_to_static(fn):
    fn._not_to_static = True
    return fn

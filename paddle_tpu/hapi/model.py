"""hapi Model — the high-level train/eval/predict loop.

Reference: ``python/paddle/hapi/model.py:915`` (``prepare:1499``,
``fit:1574``, ``train_batch:1055``, Dynamic/Static adapters ``:704/:290``).

TPU-native redesign: the reference switches between a DynamicGraphAdapter
(eager op-by-op) and a StaticGraphAdapter (program build + Executor.run).
Here there is one adapter: the dygraph-style train/eval functions are
functionalized by ``jit.CompiledStep`` into cached XLA executables — the
dygraph API *is* the static path on TPU. Metrics accumulate host-side
between steps exactly like the reference's callbacks expect.

Async pipeline (``fit``/``evaluate``): batches are staged host→device
through ``io.DeviceLoader`` (double-buffered background prefetch) and the
per-step loss is NOT read back eagerly — device scalars accumulate in a
``metric.AsyncMetricBuffer`` and the loop fences only every ``log_freq``
steps and at epoch end, so the device never idles waiting on the host.
``logs['loss']`` therefore updates at fence boundaries (exactly where
``ProgBarLogger`` prints). Host-side ``Metric`` objects still synchronize
every step when present, since their ``compute`` runs in numpy.
"""
from __future__ import annotations

import os
import pickle

import numpy as np

from ..framework.tensor import Tensor
from ..metric import Metric
from ..nn.layer.layers import Layer
from .callbacks import config_callbacks

__all__ = ["Model"]


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _to_tensor(x):
    if isinstance(x, Tensor):
        return x
    return Tensor(np.asarray(x))


class Model:
    """Reference ``hapi/model.py:915``. ``Model(net)`` then
    ``prepare(optimizer, loss, metrics)`` then ``fit/evaluate/predict``."""

    def __init__(self, network, inputs=None, labels=None):
        if not isinstance(network, Layer):
            raise TypeError("network must be a paddle Layer")
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self.stop_training = False
        self._train_step = None
        self._eval_step = None
        self._pred_step = None
        self._graph_lint = None
        self._graph_linted = False
        self._remat = None
        self._remat_applied = False
        self._remat_report = None
        self._batch_mesh = None  # (mesh, axis) once prepare(zero=) ran

    # ------------------------------------------------------------------
    # setup
    # ------------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None,
                graph_lint=None, zero=None, remat=None):
        """Reference ``model.py:1499``.

        ``graph_lint=True`` statically lints the compiled train step against
        the first batch of the first fit (``paddle_tpu.analysis``) and warns
        on findings; ``None`` (default) follows the process-wide
        ``analysis.enable_lint_on_compile()`` flag, ``False`` disables.

        ``zero`` shards the weight update over a mesh data axis
        (``distributed.sharding.ShardedOptimizer``): ``zero="dp"`` names
        the axis, ``zero=True`` uses the default mesh's first axis, and a
        dict forwards configs, e.g. ``{"axis": "dp", "quantize": "int8"}``
        for the int8 error-feedback param all-gather.

        ``remat`` arms the selective-remat autopilot
        (``analysis.remat_plan.auto_remat``), applied lazily against the
        first real train batch: ``remat="auto"`` budgets the device's
        reported HBM capacity, a number is an explicit byte budget. The
        planner checkpoints just enough of the repeated decoder blocks
        (``jax.checkpoint`` via fleet recompute) to bring the PREDICTED
        peak (``analysis.analyze_memory``, re-traced after application)
        under the budget; the report lands on
        ``model._remat_report``."""
        if zero and optimizer is not None:
            from ..distributed.mesh import get_mesh
            from ..distributed.sharding import ShardedOptimizer

            cfg = dict(zero) if isinstance(zero, dict) else {}
            mesh = cfg.pop("mesh", None) or get_mesh()
            if mesh is None:
                raise ValueError(
                    "prepare(zero=...) needs a mesh: build one with "
                    "distributed.mesh.build_mesh({'dp': n}) first")
            axis = cfg.pop("axis", None) or (
                zero if isinstance(zero, str) else mesh.axis_names[0])
            optimizer = ShardedOptimizer(optimizer, axis=axis, mesh=mesh,
                                         **cfg)
            self._batch_mesh = (mesh, axis)
        self._optimizer = optimizer
        if loss is not None and not (isinstance(loss, Layer) or callable(loss)):
            raise TypeError("loss must be a Layer or callable")
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metric {m} is not a paddle Metric")
        self._train_step = None
        self._eval_step = None
        self._pred_step = None
        self._graph_lint = graph_lint
        self._graph_linted = False
        self._remat = remat
        self._remat_applied = False
        self._remat_report = None

    def _compute_loss(self, outputs, labels):
        outs = _to_list(outputs)
        labs = _to_list(labels)
        loss = self._loss(*(outs + labs))
        if isinstance(loss, (list, tuple)):
            from .. import ops

            loss = ops.add_n([l.sum() for l in loss])
        return loss.mean() if loss.ndim > 0 else loss

    def _ensure_train_step(self):
        if self._train_step is not None:
            return self._train_step
        from ..jit.functionalize import CompiledStep

        net, opt = self.network, self._optimizer

        def step(*args):
            n_in = step._n_inputs
            ins, labs = args[:n_in], args[n_in:]
            net.train()
            outputs = net(*ins)
            loss = self._compute_loss(outputs, list(labs))
            loss.backward()
            opt.step()
            opt.clear_grad()
            outs = _to_list(outputs)
            return [loss] + outs

        step._n_inputs = self._n_inputs_cached
        # thread the INNER optimizer when opt is a ShardedOptimizer
        # wrapper: the wrapper owns no arrays, the inner holds the
        # (sharded) accumulators
        inner = getattr(opt, "_inner_opt", opt)
        self._train_step = CompiledStep(step, stateful=[net, inner],
                                        donate_state=True)
        return self._train_step

    def _ensure_eval_step(self):
        if self._eval_step is not None:
            return self._eval_step
        from ..jit.functionalize import CompiledStep

        net = self.network

        def step(*args):
            n_in = step._n_inputs
            ins, labs = args[:n_in], args[n_in:]
            net.eval()
            outputs = net(*ins)
            loss = (self._compute_loss(outputs, list(labs))
                    if self._loss is not None else None)
            outs = _to_list(outputs)
            return ([loss] + outs) if loss is not None else outs

        step._n_inputs = self._n_inputs_cached
        self._eval_step = CompiledStep(step, stateful=[net], donate_state=False)
        return self._eval_step

    def _ensure_pred_step(self):
        if self._pred_step is not None:
            return self._pred_step
        from ..jit.functionalize import CompiledStep

        net = self.network

        def step(*ins):
            net.eval()
            return net(*ins)

        self._pred_step = CompiledStep(step, stateful=[net], donate_state=False)
        return self._pred_step

    # ------------------------------------------------------------------
    # batch-level API (reference model.py:1055/:1112/:1160)
    # ------------------------------------------------------------------
    def _split_batch(self, inputs, labels=None):
        ins = [_to_tensor(t) for t in _to_list(inputs)]
        labs = [_to_tensor(t) for t in _to_list(labels)]
        # the compiled steps bake the input/label split point: rebuild them
        # when the batch arity changes
        arity = (len(ins), len(labs))
        if getattr(self, "_step_arity", None) != arity:
            self._step_arity = arity
            self._train_step = None
            self._eval_step = None
            self._pred_step = None
        self._n_inputs_cached = len(ins)
        return ins, labs

    def _train_batch_device(self, inputs, labels=None):
        """One train step WITHOUT host readback: returns the device-resident
        loss Tensor and outputs (the async fit loop defers the fence)."""
        if self._optimizer is None or self._loss is None:
            raise RuntimeError("call prepare(optimizer, loss, ...) before training")
        ins, labs = self._split_batch(inputs, labels)
        if self._remat and not self._remat_applied:
            # one-shot selective-remat autopilot against the first real
            # batch (same lazy hook as the graph autolint below); tracing
            # is abstract, the step compiles once AFTER the wrap decision
            self._remat_applied = True
            from ..analysis import remat_plan as _rp

            def _fresh_step():
                self._train_step = None
                return self._ensure_train_step()

            self._remat_report = _rp.auto_remat(
                self.network, self._remat, _fresh_step,
                tuple(ins + labs), name="train_step")
            self._train_step = None  # rebuild against the final wrapping
        step = self._ensure_train_step()
        if not self._graph_linted:
            # one-shot static lint against the first real batch (opt-in via
            # prepare(graph_lint=True) or analysis.enable_lint_on_compile())
            self._graph_linted = True
            from .. import analysis

            analysis.autolint(step, tuple(ins + labs),
                              enabled=self._graph_lint)
        res = step(*(ins + labs))
        return res[0], res[1:], labs

    def _eval_batch_device(self, inputs, labels=None):
        ins, labs = self._split_batch(inputs, labels)
        res = self._ensure_eval_step()(*(ins + labs))
        if self._loss is not None:
            loss, outs = res[0], res[1:]
        else:
            loss, outs = None, _to_list(res)
        return loss, outs, labs

    def train_batch(self, inputs, labels=None, update=True):
        loss, outs, labs = self._train_batch_device(inputs, labels)
        self._update_metrics(outs, labs)
        return [float(np.asarray(loss._value))]

    def eval_batch(self, inputs, labels=None):
        loss, outs, labs = self._eval_batch_device(inputs, labels)
        self._update_metrics(outs, labs)
        return [float(np.asarray(loss._value))] if loss is not None else []

    def predict_batch(self, inputs):
        ins, _ = self._split_batch(inputs)
        out = self._ensure_pred_step()(*ins)
        return [np.asarray(o._value) for o in _to_list(out)]

    def _update_metrics(self, outputs, labels):
        for m in self._metrics:
            args = list(_to_list(outputs)) + list(labels)
            state = m.compute(*args) if hasattr(m, "compute") else args
            state = _to_list(state)
            m.update(*[np.asarray(s._value) if isinstance(s, Tensor) else s
                       for s in state])

    # ------------------------------------------------------------------
    # epoch loops (reference model.py:1574 fit / :1743 evaluate / :1852 predict)
    # ------------------------------------------------------------------
    def _device_loader(self, batches):
        """Stage batches onto the device(s). Under ``prepare(zero=...)``
        each batch is split over the data axis of the mesh — left to the
        default, the whole batch would land on chip 0 and every chip would
        compute all of it. A batch the axis does not divide (a short last
        one) is replicated over the mesh instead."""
        from ..io.device_loader import DeviceLoader

        if self._batch_mesh is None:
            return DeviceLoader(batches)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis = self._batch_mesh
        n = mesh.shape[axis]

        def place(a):
            split = getattr(a, "ndim", 0) and a.shape[0] % n == 0
            return jax.device_put(
                a, NamedSharding(mesh, P(axis) if split else P()))

        return DeviceLoader(batches, place_fn=place)

    def _loader(self, data, batch_size, shuffle, num_workers, drop_last=False):
        from ..io import DataLoader, Dataset

        if data is None:
            return None
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset) or hasattr(data, "__getitem__"):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        return data  # assume iterable of batches

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            resume=None, ckpt_freq=None, keep_last_n=None):
        """Train. ``resume`` (a directory path or a
        ``fault.CheckpointManager``) makes the run fault-tolerant: the
        newest verified checkpoint there is restored (params, optimizer
        accumulators incl. master weights, LR scheduler, RNG, data cursor)
        and training continues from the exact step it stopped at; a
        SIGTERM mid-run flushes a consistent checkpoint and raises
        ``fault.TrainingPreempted``. Checkpoints are written every epoch
        plus every ``ckpt_freq`` steps; ``keep_last_n`` bounds how many are
        kept."""
        assert train_data is not None, "train_data must be given!"
        sess = None
        start_epoch = start_step = 0
        if resume is not None:
            from ..fault import ResumeSession

            sess = ResumeSession(resume, self.network, self._optimizer,
                                 keep_last_n=keep_last_n, ckpt_freq=ckpt_freq)
            start_epoch, start_step = sess.restore()
            # compiled steps bake the state pytree: rebuild on restored state
            self._train_step = None
            self._eval_step = None
            self._pred_step = None
        loader = self._loader(train_data, batch_size, shuffle, num_workers,
                              drop_last)
        eval_loader = self._loader(eval_data, batch_size, False, num_workers)
        steps = len(loader) if hasattr(loader, "__len__") else None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                steps=steps, log_freq=log_freq,
                                save_freq=save_freq, save_dir=save_dir,
                                verbose=verbose,
                                metrics=["loss"] + self._metrics_name())
        self.stop_training = False
        logs = {}
        cbks.on_begin("train")
        try:
            for epoch in range(start_epoch, epochs):
                if sess is not None:
                    # host-RNG snapshot BEFORE the epoch permutation draws
                    sess.epoch_begin(epoch)
                cbks.on_epoch_begin(epoch)
                skip = start_step if (sess is not None
                                      and epoch == start_epoch) else 0
                logs = self._run_one_epoch(loader, cbks, "train", log_freq,
                                           skip_steps=skip, fault_sess=sess,
                                           epoch=epoch)
                if eval_loader is not None and epoch % eval_freq == 0:
                    cbks.on_begin("eval")
                    eval_logs = self._run_one_epoch(eval_loader, cbks, "eval",
                                                    log_freq)
                    cbks.on_end("eval", eval_logs)
                    logs.update({f"eval_{k}": v for k, v in eval_logs.items()})
                cbks.on_epoch_end(epoch, logs)
                if sess is not None:
                    sess.epoch_end(epoch)
                if self.stop_training:
                    break
        finally:
            if sess is not None:
                sess.close()
        cbks.on_end("train", logs)
        return logs

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None):
        loader = self._loader(eval_data, batch_size, False, num_workers)
        cbks = config_callbacks(callbacks, model=self, log_freq=log_freq,
                                verbose=verbose,
                                metrics=["loss"] + self._metrics_name())
        cbks.on_begin("eval")
        logs = self._run_one_epoch(loader, cbks, "eval", log_freq)
        cbks.on_end("eval", logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._loader(test_data, batch_size, False, num_workers)
        cbks = config_callbacks(callbacks, model=self, verbose=verbose)
        cbks.on_begin("predict")
        outputs = []
        for step, batch in enumerate(self._device_loader(loader)):
            batch = _to_list(batch)
            # labeled datasets: drop the trailing label column(s)
            if self._loss is not None and len(batch) >= 2:
                batch = batch[:-1]
            cbks.on_batch_begin("predict", step)
            outs = self.predict_batch(batch)
            outputs.append(outs)
            cbks.on_batch_end("predict", step, {"batch_size": len(batch[0])})
        # transpose list-of-batches -> per-output list
        by_output = list(zip(*outputs)) if outputs else []
        if stack_outputs:
            result = [np.concatenate(o, axis=0) for o in by_output]
        else:
            result = [list(o) for o in by_output]
        cbks.on_end("predict", {})
        return result

    def _metrics_name(self):
        names = []
        for m in self._metrics:
            n = m.name()
            names.extend(n if isinstance(n, (list, tuple)) else [n])
        return names

    def _run_one_epoch(self, loader, cbks, mode, log_freq=10, skip_steps=0,
                       fault_sess=None, epoch=0):
        import itertools

        from ..metric import AsyncMetricBuffer
        from ..profiler import telemetry, tracing

        for m in self._metrics:
            m.reset()
        logs = {}
        total_samples = 0
        # async pipeline: batches stage host->device behind a background
        # thread; losses stay on device and fence only at log_freq
        # boundaries + epoch end (metric.AsyncMetricBuffer)
        buf = AsyncMetricBuffer()
        log_freq = max(1, int(log_freq or 1))
        src = iter(loader)
        if skip_steps:
            # mid-epoch resume: the host RNG was rewound to this epoch's
            # start, so this iterator replays the interrupted epoch's exact
            # batch order — discard the already-trained prefix on the host
            # (the device never sees the skipped batches)
            for _ in itertools.islice(src, skip_steps):
                pass
        # per-step phase timeline: the flag is global and False by default,
        # so the disabled path does zero telemetry work. step_begin sits
        # BEFORE the for statement (and again at each body end) because the
        # next batch's data_wait happens inside the iterator protocol,
        # between loop bodies.
        tm_on = telemetry.enabled()
        if tm_on:
            telemetry.step_begin()
        # request-scoped tracing, train-side: the epoch roots a trace and
        # every step runs inside a child span — the same span model the
        # serving tier uses, so one export holds both. Compile events
        # (CompiledStep) parent under the active step span.
        tr_on = tracing.enabled()
        epoch_span = None
        if tr_on:
            epoch_span = tracing.start_span(
                f"{mode}_epoch", attrs={"epoch": epoch, "mode": mode})
        for step, batch in enumerate(self._device_loader(src),
                                     start=skip_steps):
            batch = _to_list(batch)
            # convention: trailing element(s) are labels when a loss is set
            if self._loss is not None and len(batch) >= 2:
                ins, labs = batch[:-1], batch[-1:]
            else:
                ins, labs = batch, []
            cbks.on_batch_begin(mode, step, logs)
            with tracing.span(f"{mode}_step", parent=epoch_span,
                              attrs={"step": step}) if tr_on \
                    else tracing.NULL_SPAN:
                if mode == "train":
                    loss, outs, labs = self._train_batch_device(ins, labs)
                else:
                    loss, outs, labs = self._eval_batch_device(ins, labs)
            buf.append(loss)
            # fence at log_freq boundaries; also once at the first step so
            # logs['loss'] exists from the first callback onward (between
            # fences it holds the last drained value)
            if step == skip_steps or (step + 1) % log_freq == 0:
                buf.drain()  # fence: flush pending device losses to host
            if buf.values:
                logs["loss"] = buf.last()
            if self._metrics:
                # host-side numpy metrics force a per-step sync; only paid
                # when the user actually configured metrics
                self._update_metrics(outs, labs)
                for m in self._metrics:
                    res = m.accumulate()
                    for name, v in zip(_to_list(m.name()), _to_list(res)):
                        logs[name] = v
            bs = ins[0].shape[0] if hasattr(ins[0], "shape") else len(ins[0])
            total_samples += bs
            cbks.on_batch_end(mode, step, logs)
            if fault_sess is not None and mode == "train":
                # AFTER on_batch_end: the LRScheduler callback has stepped,
                # so a checkpoint here captures the post-step boundary
                # exactly; raises TrainingPreempted after a SIGTERM flush
                fault_sess.after_step(epoch, step + 1)
            if tm_on:
                telemetry.step_begin()  # roll the phase record over
        buf.drain()  # epoch-end fence
        if epoch_span is not None:
            epoch_span.set_attr("samples", total_samples).end()
        if tm_on:
            telemetry.step_end()
        if buf.values:
            logs["loss"] = buf.last()
        if mode == "eval":
            logs["eval_samples"] = total_samples
        return dict(logs)

    # ------------------------------------------------------------------
    # persistence / introspection
    # ------------------------------------------------------------------
    def save(self, path, training=True):
        """Reference ``model.py:1932``: <path>.pdparams (+ .pdopt)."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        from ..framework.io import save as psave

        psave(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            psave(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io import load as pload

        self.network.set_state_dict(pload(path + ".pdparams"))
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(path + ".pdopt")):
            self._optimizer.set_state_dict(pload(path + ".pdopt"))
        self._train_step = None
        self._eval_step = None
        self._pred_step = None

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def device_report(self):
        """The harvested :class:`~paddle_tpu.profiler.devprof.
        DeviceCostReport` of the compiled train step (auto-harvested on
        first compile while telemetry is enabled — e.g. under the
        ``DeviceStatsLogger``/``TelemetryLogger`` callbacks), else None."""
        from ..profiler import devprof

        if self._train_step is not None:
            rep = devprof.get_report(self._train_step.name)
            if rep is not None:
                return rep
        return None

    def summary(self, input_size=None, dtype=None):
        from .model_summary import summary

        return summary(self.network, input_size, dtypes=dtype)

"""Optimizer base.

Reference ``python/paddle/optimizer/optimizer.py`` (``step:1232``,
``minimize:1167``, ``_append_optimize_op:559``). TPU-native translation: each
optimizer's update rule is a pure jnp function over (param, grad, accumulators)
— executed eagerly per step in dygraph, or traced into the single compiled XLA
train step by paddle_tpu.jit (where XLA fuses all per-param updates; the
reference needs hand-fused "fused_adam"/"merged_momentum" ops for this).

Accumulator state lives in ``self._accumulators[name][param_key]`` as raw jnp
arrays, exposed as a pytree for jit-functionalization via ``_state_pytree``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import dtype as dtypes
from ..framework.tensor import Parameter, Tensor
from ..autograd import no_grad
from .lr import LRScheduler

__all__ = ["Optimizer"]


class Optimizer:
    def __init__(
        self,
        learning_rate=0.001,
        parameters=None,
        weight_decay=None,
        grad_clip=None,
        name=None,
        multi_precision=False,
    ):
        if parameters is not None:
            parameters = list(parameters)
            if parameters and isinstance(parameters[0], dict):
                self._param_groups = parameters
                flat = []
                for g in parameters:
                    flat.extend(g["params"])
                parameters = flat
            else:
                self._param_groups = None
        else:
            self._param_groups = None
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._accumulators = {}
        self._acc_meta = {}  # (name, key) -> (fill_value, shape, dtype)
        # optional placement hook applied to every accumulator AT CREATION
        # (ZeRO sharding / offload — distributed/sharding/group_sharded.py);
        # avoids ever materializing a full-size replicated buffer
        self._accumulator_transform = None
        # ZeRO sharded-update seams (distributed/sharding/zero.py):
        # _grad_transform(p, gv) runs before the update rule — the
        # reduce-scatter point; _param_transform(p, value) runs on the
        # updated value after the (possibly fp32-master) write-back — the
        # all-gather point. Both None outside a sharded wrapper.
        self._grad_transform = None
        self._param_transform = None
        # fp32 master weights + fp32 moments for low-precision params
        # (reference adam_op multi-precision path / amp O2 master weights)
        self._multi_precision = bool(multi_precision)
        self._pending_state = {}
        self._name = name or type(self).__name__
        self._step_count = 0

    # -- learning rate -------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    def _lr_array(self):
        return jnp.asarray(self.get_lr(), jnp.float32)

    # -- accumulators --------------------------------------------------------
    @staticmethod
    def _pkey(p):
        # Parameters are auto-named at creation (framework/tensor.py) so this
        # is a stable, process-portable key. Plain Tensors used as parameters
        # get a name on first touch — deterministic in optimizer order.
        if not p.name:
            from ..utils import unique_name

            p.name = unique_name.generate("param")
        return p.name

    def _add_accumulator(self, name, param, fill_value=0.0, dtype=None, shape=None):
        store = self._accumulators.setdefault(name, {})
        key = self._pkey(param)
        if key not in store:
            pending = self._pending_state.pop(f"{key}_{name}", None)
            if pending is not None:
                # restore-before-first-step: set_state_dict ran before this
                # accumulator was lazily created
                store[key] = jnp.asarray(pending)
            else:
                store[key] = jnp.full(
                    shape if shape is not None else tuple(param.shape),
                    fill_value,
                    dtype or (param._value.dtype if dtypes.is_floating(param.dtype) else jnp.float32),
                )
            if self._accumulator_transform is not None:
                store[key] = self._accumulator_transform(store[key])
            # GradScaler's inf-skip needs the pre-step value of accumulators
            # born mid-step; keep only metadata, never a full-size buffer.
            self._acc_meta[(name, key)] = (
                fill_value,
                tuple(store[key].shape),
                store[key].dtype,
            )
        return store[key]

    def _get_accumulator(self, name, param):
        return self._accumulators[name][self._pkey(param)]

    def _uses_master(self, p) -> bool:
        return self._multi_precision and p._value.dtype in (
            jnp.bfloat16,
            jnp.float16,
        )

    def _master_weight(self, p):
        """fp32 master copy of a low-precision param, initialized (once) from
        the param itself; survives checkpoint restore via _pending_state."""
        store = self._accumulators.setdefault("master_weight", {})
        key = self._pkey(p)
        if key not in store:
            pending = self._pending_state.pop(f"{key}_master_weight", None)
            if pending is not None:
                store[key] = jnp.asarray(pending, jnp.float32)
            else:
                store[key] = p._value.astype(jnp.float32)
            if self._accumulator_transform is not None:
                store[key] = self._accumulator_transform(store[key])
            # fill=None marks "pre-step value is the param itself" for the
            # GradScaler inf-skip restore path
            self._acc_meta[("master_weight", key)] = (
                None,
                tuple(store[key].shape),
                store[key].dtype,
            )
        return store[key]

    def _set_accumulator(self, name, param, value):
        # re-apply the ZeRO placement every store: eager updates would
        # otherwise migrate offloaded/sharded state back to default device
        # memory after the first step
        if self._accumulator_transform is not None:
            value = self._accumulator_transform(value)
        self._accumulators[name][self._pkey(param)] = value

    # -- main API ------------------------------------------------------------
    def _collect_params_grads(self):
        pgs = []
        for p in self._parameter_list or []:
            if p.stop_gradient:
                continue
            pgs.append((p, p.grad))
        return pgs

    def _apply_decay(self, p, g):
        """L2Decay-style regularization folded into the gradient
        (reference regularizer.py L2Decay appended before optimize op)."""
        wd = self._weight_decay
        if wd is None:
            return g
        from ..regularizer import L2Decay, L1Decay

        if isinstance(wd, L2Decay):
            coeff = wd._coeff
            return g + coeff * p._value
        if isinstance(wd, L1Decay):
            return g + wd._coeff * jnp.sign(p._value)
        if isinstance(wd, float) and not getattr(self, "_decoupled_wd", False):
            return g + wd * p._value
        return g

    @no_grad()
    def step(self):
        with jax.named_scope("optimizer_update"):
            self._step()

    def _step(self):
        self._step_count += 1
        pgs = self._collect_params_grads()
        if self._grad_clip is not None:
            pgs = self._grad_clip(pgs)
        lr = self._lr_array()
        from ..framework.selected_rows import SparseGradTensor

        for p, g in pgs:
            if g is None:
                continue
            if (isinstance(g, SparseGradTensor) and g._dense_cache is None
                    and hasattr(self, "_sparse_update")
                    and self._weight_decay is None
                    and getattr(p, "regularizer", None) is None
                    and not self._uses_master(p)):
                # row-sparse fast path (reference sparse-kernel optimizer
                # ops over SelectedRows): only the touched rows update
                param_lr = getattr(p, "optimize_attr", {}).get(
                    "learning_rate", 1.0)
                self._sparse_update(p, g.selected_rows, lr * param_lr)
                continue
            gv = g._value if isinstance(g, Tensor) else g
            # plain leaf Tensors (stop_gradient=False) are optimizable like
            # Parameters (reference allows both); they lack the Parameter
            # attrs, hence the getattr defaults
            reg = getattr(p, "regularizer", None)
            if reg is not None:
                gv = gv + reg._coeff * p._value
            else:
                gv = self._apply_decay(p, gv)
            if self._grad_transform is not None:
                gv = self._grad_transform(p, gv)
            param_lr = getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
            self._step_one(p, gv, lr * param_lr)

    def _step_one(self, p, gv, lr_eff):
        if self._uses_master(p):
            # run the update rule on the fp32 master copy (moments created
            # inside _update_param then inherit fp32), write the master back,
            # and round once to the param dtype
            master = self._master_weight(p)
            low_dtype = p._value.dtype
            p._value = master
            new_master = self._update_param(
                p, gv.astype(jnp.float32), lr_eff
            ).astype(jnp.float32)
            self._set_accumulator("master_weight", p, new_master)
            p._value = new_master.astype(low_dtype)
        else:
            new_val = self._update_param(p, gv, lr_eff)
            p._value = new_val.astype(p._value.dtype)
        if self._param_transform is not None:
            # the sharded master/moments stay exact on their shard; only
            # the working copy is re-gathered (int8 wire optional)
            p._value = self._param_transform(p, p._value)

    def _update_param(self, p, grad, lr):
        raise NotImplementedError

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list or []:
            p.clear_grad()

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        """dygraph: backward + step (reference optimizer.py:1167). Static:
        registers this optimizer on the loss's Program so Executor.run
        computes grads and applies the update inside the compiled replay
        (reference _append_optimize_op:559 appending to the ProgramDesc)."""
        from ..static.program import Variable as _StaticVariable

        if isinstance(loss, _StaticVariable):
            prog = loss.program
            if self._parameter_list is None:
                self._parameter_list = [
                    p for p in prog.all_parameters() if not p.stop_gradient
                ]
            prog._optimizers.append((self, loss))
            prog._version += 1
            from ..static.backward import append_backward

            pairs = append_backward(loss, parameter_list=self._parameter_list)
            return None, pairs
        loss.backward()
        self.step()
        return None, None

    def backward(self, loss, startup_program=None, parameters=None, no_grad_set=None, callbacks=None):
        loss.backward()
        return self._collect_params_grads()

    def apply_gradients(self, params_grads):
        lr = self._lr_array()
        for p, g in params_grads:
            if g is None:
                continue
            gv = g._value if isinstance(g, Tensor) else g
            if self._grad_transform is not None:
                gv = self._grad_transform(p, gv)
            self._step_one(p, gv, lr)

    # -- state dict ----------------------------------------------------------
    def state_dict(self):
        sd = {}
        for name, store in self._accumulators.items():
            for key, v in store.items():
                sd[f"{key}_{name}"] = Tensor(v)
        sd["@step"] = self._step_count
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        if "@step" in state_dict:
            self._step_count = int(state_dict["@step"])
        if "LR_Scheduler" in state_dict and isinstance(self._learning_rate, LRScheduler):
            self._learning_rate.set_state_dict(state_dict["LR_Scheduler"])
        applied = set()
        for name, store in self._accumulators.items():
            for key in store:
                k = f"{key}_{name}"
                if k in state_dict:
                    v = state_dict[k]
                    v = v._value if isinstance(v, Tensor) else jnp.asarray(v)
                    if self._accumulator_transform is not None:
                        # keep the ZeRO sharding/offload placement on restore
                        # (never materialize full replicated state per device)
                        v = self._accumulator_transform(v)
                    store[key] = v
                    applied.add(k)
        # entries for accumulators not yet created are held back and consumed
        # by _add_accumulator on first touch (lazy creation after restore)
        self._pending_state = {
            k: (v._value if isinstance(v, Tensor) else v)
            for k, v in state_dict.items()
            if k not in ("@step", "LR_Scheduler") and k not in applied
        }

    # -- eager accumulator init ---------------------------------------------
    def _eager_accumulator_specs(self):
        """Declares every accumulator ``_update_param`` will touch for one
        param, as ``[(name, _add_accumulator-kwargs)]``. Concrete optimizers
        override this; it is the contract behind ``_ensure_accumulators``:
        eager creation must land the SAME (name, shape, dtype) state the
        lazy first step would, so the jit state pytree is identical either
        way. ``()`` opts out (no accumulators, or an optimizer this base
        doesn't know how to pre-build)."""
        return ()

    def _ensure_accumulators(self):
        """Materialize all accumulators (and fp32 master weights) up front.

        Lazy creation during the FIRST compiled step mutates the state
        pytree between calls 1 and 2, forcing jax to trace+compile the whole
        step twice (the Adam/AdamW double-trace found by PR 2's telemetry).
        ``jit.CompiledStep`` calls this at construction so the state
        signature is stable from step 1; safe to call repeatedly (existing
        entries are kept, checkpoint-restored values in ``_pending_state``
        are honored via ``_add_accumulator``'s restore path)."""
        specs = self._eager_accumulator_specs()
        for p in self._parameter_list or []:
            if p.stop_gradient:
                continue
            master = self._uses_master(p)
            if master:
                self._master_weight(p)
            for name, kw in specs:
                kw = dict(kw)
                if master and "dtype" not in kw:
                    # the lazy path creates moments while p._value is the
                    # fp32 master copy — match that dtype
                    kw["dtype"] = jnp.float32
                self._add_accumulator(name, p, **kw)

    # -- jit functionalization hooks ----------------------------------------
    def _state_pytree(self):
        return {
            "accumulators": self._accumulators,
            "step": jnp.asarray(self._step_count, jnp.int32),
        }

    def _load_state_pytree(self, tree):
        accs = tree["accumulators"]
        if self._accumulator_transform is not None:
            accs = {
                name: {
                    k: (self._accumulator_transform(v)
                        if hasattr(v, "ndim") else v)
                    for k, v in store.items()
                } if isinstance(store, dict) else store
                for name, store in accs.items()
            }
        self._accumulators = accs
        # keep the step counter lazy (device array or tracer): calling int()
        # here would block on the ENTIRE compiled step's result every
        # iteration — a host sync that serializes training
        self._step_count = tree["step"]

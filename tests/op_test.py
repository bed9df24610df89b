"""OpTest-style numeric gradient checker.

Clone of the reference harness idea (``python/paddle/fluid/tests/unittests/
op_test.py:309`` — ``check_grad:1851`` compares analytic grads against
central-difference numeric grads via ``get_numeric_gradient:126``)."""
from __future__ import annotations

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor


def numeric_grad(fn, inputs, idx, out_grad=None, delta=1e-3):
    """Central-difference gradient of sum(fn(*inputs) * out_grad) w.r.t inputs[idx]."""
    # note: jax->numpy arrays may be F-ordered; force C-contiguous copies so
    # in-place perturbation below actually lands in the evaluated array
    base = [np.ascontiguousarray(t.numpy(), dtype=np.float64) for t in inputs]

    def eval_at(vals):
        ts = [paddle.to_tensor(v.astype(np.float32)) for v in vals]
        out = fn(*ts)
        o = out.numpy().astype(np.float64)
        w = out_grad if out_grad is not None else np.ones_like(o)
        return float((o * w).sum())

    x = base[idx]
    g = np.zeros_like(x)
    for i in range(x.size):
        orig = x.flat[i]
        x.flat[i] = orig + delta
        fp = eval_at(base)
        x.flat[i] = orig - delta
        fm = eval_at(base)
        x.flat[i] = orig
        g.flat[i] = (fp - fm) / (2 * delta)
    return g


def analytic_grads(fn, tensors):
    """Forward + backward once; returns the list of input gradients (fp64
    numpy). Gradient seed is ones in the output dtype."""
    out = fn(*tensors)
    out.backward(paddle.ones(out.shape, out.dtype))
    return [np.asarray(t.grad._value, dtype=np.float64) for t in tensors], out


def check_grad_lowp(fn, input_arrays, dtype="bfloat16", rtol=6e-2, atol=1e-2):
    """Low-precision gradient check (reference ``unittests/op_test.py:1851``
    per-dtype check_grad): run the op end-to-end in `dtype` and compare its
    analytic gradient against the fp32 analytic gradient evaluated at the
    SAME low-precision-representable input points. The fp32 analytic path is
    itself validated against finite differences by the fp32 sweep, so this
    chain checks exactly the low-precision computation error."""
    import ml_dtypes

    np_dt = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float16
    snapped = [np.asarray(a, dtype=np_dt).astype(np.float32)
               for a in input_arrays]
    assert all(np.isfinite(s).all() for s in snapped), \
        f"inputs not representable in {dtype}"

    ref_ts = [paddle.to_tensor(a, stop_gradient=False) for a in snapped]
    ref_grads, _ = analytic_grads(fn, ref_ts)

    lp_ts = [paddle.to_tensor(np.asarray(a, dtype=np_dt), stop_gradient=False)
             for a in snapped]
    lp_grads, out = analytic_grads(fn, lp_ts)

    for i, (lp, ref) in enumerate(zip(lp_grads, ref_grads)):
        np.testing.assert_allclose(
            lp, ref, rtol=rtol, atol=atol,
            err_msg=(f"{dtype} gradient deviates from fp32 reference for "
                     f"input {i} of {getattr(fn, '__name__', fn)}"),
        )
    return out


def check_grad(fn, input_arrays, rtol=1e-2, atol=1e-3, delta=1e-3, out_grad=None):
    """Compare analytic backward() grads to finite differences for all inputs."""
    tensors = [paddle.to_tensor(a.astype(np.float32), stop_gradient=False) for a in input_arrays]
    out = fn(*tensors)
    if out_grad is not None:
        out.backward(paddle.to_tensor(out_grad.astype(np.float32)))
    else:
        seed = paddle.ones(out.shape, out.dtype)
        out.backward(seed)
    for i, t in enumerate(tensors):
        ng = numeric_grad(fn, tensors, i, out_grad=out_grad, delta=delta)
        ag = t.grad.numpy().astype(np.float64)
        np.testing.assert_allclose(
            ag, ng, rtol=rtol, atol=atol,
            err_msg=f"gradient mismatch for input {i} of {getattr(fn, '__name__', fn)}",
        )
    return out


def forward_shapes(net, *in_shape):
    """Output shapes of ``net`` on a float32 input of ``in_shape``, from the
    trace alone (``jax.eval_shape``): every layer's forward runs and every
    channel count has to agree, but nothing is compiled. Eagerly a zoo CNN
    costs one XLA:CPU executable per (op, shape), ~60 s for densenet121, to
    assert the same shapes."""
    import jax

    def fwd(a):
        out = net(Tensor(a))
        outs = out if isinstance(out, (tuple, list)) else (out,)
        return tuple(o._value for o in outs)

    with paddle.no_grad():
        outs = jax.eval_shape(fwd, jax.ShapeDtypeStruct(in_shape, np.float32))
    return [list(o.shape) for o in outs]

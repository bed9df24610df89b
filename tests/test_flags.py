"""paddle.set_flags/get_flags + FLAGS_check_nan_inf debug mode.
Reference: python/paddle/fluid/framework.py:7125, platform/flags.cc."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor


def test_set_get_roundtrip():
    assert paddle.get_flags("check_nan_inf") == {"check_nan_inf": False}
    paddle.set_flags({"check_nan_inf": True})
    try:
        assert paddle.get_flags(["check_nan_inf"])["check_nan_inf"] is True
    finally:
        paddle.set_flags({"check_nan_inf": False})


def test_unknown_flag_raises():
    with pytest.raises(ValueError):
        paddle.set_flags({"no_such_flag": 1})
    with pytest.raises(ValueError):
        paddle.get_flags("no_such_flag")
    with pytest.raises(TypeError):
        paddle.set_flags("check_nan_inf")


def test_bool_coercion_from_strings():
    paddle.set_flags({"check_nan_inf": "true"})
    assert paddle.get_flags("check_nan_inf")["check_nan_inf"] is True
    paddle.set_flags({"check_nan_inf": "0"})
    assert paddle.get_flags("check_nan_inf")["check_nan_inf"] is False


def test_check_nan_inf_raises_on_nan():
    paddle.set_flags({"check_nan_inf": True})
    try:
        x = Tensor(np.array([1.0, 0.0], np.float32))
        with pytest.raises(FloatingPointError, match="Inf/Nan"):
            _ = x / x  # 0/0 -> nan
        # clean values pass
        _ = x + x
    finally:
        paddle.set_flags({"check_nan_inf": False})


def test_check_nan_inf_skips_traced_values():
    """Inside jit, outputs are tracers — the flag must not break compilation."""
    from paddle_tpu.jit.functionalize import CompiledStep

    paddle.set_flags({"check_nan_inf": True})
    try:
        def f(x):
            return (x * 0.0) / (x * 0.0)  # nan inside jit: not host-checkable

        step = CompiledStep(f, stateful=[])
        out = step(Tensor(np.ones(2, np.float32)))
        assert np.isnan(np.asarray(out._value)).all()
    finally:
        paddle.set_flags({"check_nan_inf": False})


def test_sdpa_agrees_with_the_einsum_op():
    """No flag routes attention (it once took a kill switch to reach the
    einsum): whatever route the call takes agrees with the einsum op called
    directly."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional.attention import _sdpa_raw

    q = Tensor(np.random.RandomState(0).randn(2, 128, 4, 64).astype(np.float32))
    out1 = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    out2 = _sdpa_raw(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out1._value), np.asarray(out2._value),
                               atol=2e-2)

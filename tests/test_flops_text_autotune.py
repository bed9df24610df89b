"""paddle.flops, paddle.text datasets, incubate.autotune, onnx gating."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor


def test_flops_linear_and_conv():
    # reference conventions: MAC = 1 op, conv counts bias
    net = paddle.nn.Linear(8, 16)
    n = paddle.flops(net, (4, 8))
    assert n == 8 * 4 * 16

    conv = paddle.nn.Conv2D(3, 8, 3, padding=1)
    n = paddle.flops(conv, (1, 3, 16, 16), print_detail=True)
    assert n == (3 * 9 + 1) * 8 * 16 * 16


def test_flops_custom_ops():
    net = paddle.nn.ReLU()
    n = paddle.flops(net, (2, 4),
                     custom_ops={paddle.nn.ReLU: lambda l, x, o: 42})
    assert n == 42


def test_text_datasets():
    from paddle_tpu.text import Imdb, UCIHousing

    h = UCIHousing(mode="train")
    x, y = h[0]
    assert x.shape == (13,) and y.shape == (1,)
    d = Imdb(mode="test", seq_len=32)
    doc, lab = d[5]
    assert doc.shape == (32,) and lab in (0, 1)
    # deterministic across constructions
    d2 = Imdb(mode="test", seq_len=32)
    np.testing.assert_array_equal(d[5][0], d2[5][0])


def test_autotune_config():
    from paddle_tpu.incubate import autotune

    # accepted for compatibility: the status follows, no kernel does
    autotune.set_config({"kernel": {"enable": False}})
    assert autotune.get_status()["kernel"]["enable"] is False
    autotune.set_config({"kernel": {"enable": True}})
    assert autotune.get_status()["kernel"]["enable"] is True
    autotune.set_config({"kernel": None})  # None section is a no-op
    assert autotune.get_status()["kernel"]["enable"] is True
    autotune.set_config({"kernel": {"enable": False}})
    autotune.set_config(None)  # resets every section
    assert autotune.get_status()["kernel"]["enable"] is True
    with pytest.raises(ValueError):
        autotune.set_config({"nope": {}})
    with pytest.raises(TypeError):
        autotune.set_config(3)


def test_onnx_exports_stablehlo(tmp_path):
    import os

    from paddle_tpu.jit.save_load import InputSpec

    lin = paddle.nn.Linear(4, 2)
    path = str(tmp_path / "m")
    # round-5: the default onnx format now writes a real .onnx artifact
    out = paddle.onnx.export(lin, path,
                             input_spec=[InputSpec([2, 4], "float32")])
    assert out == path + ".onnx" and os.path.exists(out)
    # explicit StableHLO opt-in writes the portable artifact
    out = paddle.onnx.export(lin, path, format_="stablehlo",
                             input_spec=[InputSpec([2, 4], "float32")])
    assert out == path and os.path.exists(path + ".pdmodel")

"""bf16/fp16 gradient sweep over the differentiable op surface (round-5
VERDICT item 4). Every entry of paddle_tpu/ops/op_table.py additionally
runs in bfloat16 AND float16 — the framework's actual training dtypes —
with the analytic low-precision gradient compared against the fp32
analytic gradient at representable input points (reference discipline:
``unittests/op_test.py:1851`` per-dtype check_grad). Skips/deviations are
declared in the table's LOWP map, with reasons.

This file holds the check and the cases of the tensor-op surface
(``ops.*``); those of ``nn.functional`` (``F.*``) are
``tests/test_op_grad_sweep_lowp_functional.py``. Under ``--dist loadfile`` a
file is one worker's, and the whole table was 123 s of one."""
import numpy as np
import pytest

from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.op_table import LOWP, LOWP_DEFAULT, OPS

from tests.op_test import check_grad_lowp
from tests.test_op_grad_sweep import _ADAPTERS, _draw, _ids, _resolve  # noqa: F401


def _cases(surface):
    """The table's entries of one surface ("ops." or "F."), in both dtypes."""
    return [pytest.param(e, dtype, id=f"{eid}-{dtype}")
            for e, eid in zip(OPS, _ids()) if e["api"].startswith(surface)
            for dtype in ("bfloat16", "float16")]


def test_lowp_axis_covers_table():
    """>=150 entries x 2 dtypes actually checked (VERDICT done-criterion)."""
    active = [e for e in OPS if LOWP.get(e["api"]) is not False]
    assert len(active) >= 150, len(active)


@pytest.mark.parametrize("entry,dtype", _cases("ops."))
def test_op_gradient_lowp(entry, dtype):
    _check_lowp(entry, dtype)


def _check_lowp(entry, dtype):
    spec = LOWP.get(entry["api"])
    if spec is False:
        pytest.skip(f"{entry['api']}: low-precision skipped (see LOWP map)")
    if isinstance(spec, dict) and spec.get(dtype) is False:
        pytest.skip(f"{entry['api']}: {dtype} skipped (see LOWP map)")
    tol = dict(LOWP_DEFAULT[dtype])
    if isinstance(spec, dict):
        tol.update(spec.get(dtype, {}))

    fn = _resolve(entry["api"])
    assert fn is not None, entry["api"]
    import zlib

    rng = np.random.RandomState(zlib.crc32(entry["api"].encode()) % (2**31))
    arrays = [_draw(s, d, rng) for s, d in entry["inputs"]]
    diffable = [
        i for i, (s, d) in enumerate(entry["inputs"])
        if not (d == "bool" or d == "sign" or d.startswith("int:"))
    ]
    if entry["only"] is not None:
        diffable = [i for i in diffable if i in entry["only"]]

    kwargs = entry["kwargs"]
    fixed = {i: Tensor(a) for i, a in enumerate(arrays) if i not in diffable}

    def wrapped(*diff_tensors):
        args = []
        it = iter(diff_tensors)
        for i in range(len(arrays)):
            args.append(fixed[i] if i in fixed else next(it))
        out = fn(*args, **kwargs)
        if isinstance(out, (tuple, list)):
            out = out[0]
        return out

    if not diffable:
        pytest.skip("no differentiable inputs")

    check_grad_lowp(wrapped, [arrays[i] for i in diffable], dtype=dtype,
                    **tol)

"""The ``nn.functional`` (``F.*``) cases of the low-precision gradient
sweep. The check, the reasons behind it and the tensor-op cases are
``tests/test_op_grad_sweep_lowp.py``: under ``--dist loadfile`` a file is one
worker's, and the whole table was 123 s of one."""
import pytest

from tests.test_op_grad_sweep_lowp import _cases, _check_lowp


@pytest.mark.parametrize("entry,dtype", _cases("F."))
def test_op_gradient_lowp(entry, dtype):
    _check_lowp(entry, dtype)

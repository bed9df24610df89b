"""paddle_tpu — a TPU-native deep learning framework with the PaddlePaddle
API surface, built from scratch on jax/XLA/Pallas/pjit.

Architecture (vs the reference at /root/reference — see SURVEY.md):
 - eager "dygraph" execution = per-op XLA dispatch with a jax.vjp-backed
   autograd tape (paddle_tpu.autograd.engine);
 - static/jit path = whole-train-step functionalization compiled to one XLA
   program (paddle_tpu.jit), replacing ProgramDesc+Executor;
 - distributed = jax.sharding.Mesh + shard_map collectives over ICI/DCN,
   replacing NCCL rings / ProcessGroup (paddle_tpu.distributed);
 - hot kernels = Pallas (paddle_tpu.ops.pallas).
"""
from __future__ import annotations

__version__ = "0.1.0"

# framework core
from .framework.dtype import (  # noqa: F401
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
    get_default_dtype,
    set_default_dtype,
)
import jax.numpy as _jnp_for_dtype

# paddle.dtype / paddle.bool (reference: core.VarDesc.VarType aliases; here
# dtypes ARE numpy/jnp dtypes, so the constructor-alias is jnp.dtype)
dtype = _jnp_for_dtype.dtype
from .framework.dtype import bool_ as bool  # noqa: F401,A001

from .framework.place import (  # noqa: F401
    NPUPlace,
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    XPUPlace,
    get_device,
    set_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
)
from .framework.random import (  # noqa: F401
    seed, get_rng_state, set_rng_state, get_cuda_rng_state,
    set_cuda_rng_state,
)
from .framework.flags import set_flags, get_flags  # noqa: F401
from .framework.tensor import Parameter, Tensor, to_tensor, is_tensor  # noqa: F401

# the whole tensor-op surface (also patches Tensor methods)
from .distributed.data_parallel import DataParallel  # noqa: F401
from .ops import *  # noqa: F401,F403
from .ops import add_n, einsum  # noqa: F401
from .ops.random import (  # noqa: F401
    bernoulli,
    multinomial,
    normal,
    poisson,
    rand,
    randint,
    randint_like,
    randn,
    randperm,
    standard_normal,
    uniform,
)

from .autograd import no_grad, enable_grad, set_grad_enabled, grad  # noqa: F401
from . import autograd  # noqa: F401

# Subsystems are appended here as they land (build order in SURVEY.md §7).
from . import nn  # noqa: F401
from .nn.layer.container import LayerList, ParameterList, Sequential  # noqa: F401
from .nn.layer.layers import ParamAttr  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import static  # noqa: F401
from . import profiler  # noqa: F401
from . import analysis  # noqa: F401
from . import fault  # noqa: F401
from . import hapi  # noqa: F401
from . import distribution  # noqa: F401
from . import sparse  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from . import incubate  # noqa: F401
from . import utils  # noqa: F401
from . import device  # noqa: F401
from . import cost_model  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import version  # noqa: F401
from . import sysconfig  # noqa: F401
from . import compat  # noqa: F401
from . import reader  # noqa: F401
from . import hub  # noqa: F401
from . import callbacks  # noqa: F401
from . import dataset  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import tensor  # noqa: F401
from .batch import batch  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi.model_summary import summary, flops  # noqa: F401
from .framework.io import load, save  # noqa: F401

_static_mode = False


def enable_static():
    """Switch to declarative mode: framework ops touching static.Variables
    record into the current Program (reference paddle.enable_static)."""
    global _static_mode
    _static_mode = True
    from .ops import dispatch
    from .static.program import _recorder

    dispatch.STATIC_RECORDER = _recorder


def disable_static(place=None):
    global _static_mode
    _static_mode = False
    from .ops import dispatch
    from .static import program as _prog

    if not _prog._guard_stack:
        dispatch.STATIC_RECORDER = None


def in_dynamic_mode():
    return not _static_mode


def set_grad_enabled_ctx(mode):
    return set_grad_enabled(mode)


def is_grad_enabled():
    from .autograd import is_grad_enabled as _ige

    return _ige()


def device_count():
    import jax

    return jax.local_device_count()


def set_printoptions(**kwargs):
    import numpy as np

    np.set_printoptions(**{k: v for k, v in kwargs.items() if k in ("precision", "threshold", "edgeitems", "linewidth")})


def __getattr__(name):
    # paddle.distributed is imported lazily: it builds mesh/topology state on
    # import, which not every single-chip program needs at startup
    if name == "distributed":
        import importlib

        return importlib.import_module(".distributed", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def check_shape(shape):
    """Reference ``fluid/data_feeder.py check_shape``: validate a shape spec
    (ints or a 1-D integer Tensor; -1 allowed as the dynamic marker)."""
    from .framework.tensor import Tensor as _T

    if isinstance(shape, _T):
        if shape.ndim != 1:
            raise TypeError("shape tensor must be 1-D")
        return
    for s_ in shape:
        if not isinstance(s_, (int,)) or (s_ < 0 and s_ != -1):
            raise TypeError(
                f"shape entries must be non-negative ints or -1, got {s_!r}")


def disable_signal_handler():
    """Reference ``fluid/framework.py:736``: Paddle installs fault-signal
    handlers at import; jax/XLA installs none, so there is nothing to
    disable — kept for call-site compatibility."""
    return None

"""Global flags registry — ``paddle.set_flags`` / ``paddle.get_flags``.

Reference: gflags exported via ``paddle/fluid/platform/flags.cc:1``
(``PADDLE_DEFINE_EXPORTED``), surfaced to python at
``python/paddle/fluid/framework.py:7125`` and honored from the environment
(``FLAGS_*``) at init (``platform/init.cc``).

TPU-native redesign: a python-side registry.  Flags either hold framework
state read by paddle_tpu subsystems, or bind through to a ``jax.config``
option (the XLA-level knobs the reference's allocator/cudnn flags map onto).
Environment ``FLAGS_<name>`` values seed the defaults at import, matching the
reference's env-first behavior.
"""
from __future__ import annotations

import os

__all__ = ["set_flags", "get_flags", "register_flag", "flag_value"]


class _Flag:
    __slots__ = ("name", "default", "value", "typ", "jax_config", "setter", "help")

    def __init__(self, name, default, typ=None, jax_config=None, setter=None,
                 help=""):
        self.name = name
        self.typ = typ or type(default)
        self.default = default
        self.jax_config = jax_config
        self.setter = setter
        self.help = help
        env = os.environ.get(f"FLAGS_{name}")
        self.value = self._coerce(env) if env is not None else default

    def _coerce(self, v):
        if self.typ is bool:
            if isinstance(v, str):
                return v.lower() not in ("0", "false", "")
            return bool(v)
        return self.typ(v)


_REGISTRY: dict[str, _Flag] = {}


def register_flag(name, default, typ=None, jax_config=None, setter=None, help=""):
    f = _Flag(name, default, typ, jax_config, setter, help)
    _REGISTRY[name] = f
    return f


def flag_value(name):
    """Internal fast read used by subsystems."""
    f = _REGISTRY.get(name)
    return f.value if f is not None else None


def set_flags(flags):
    """Reference ``fluid/framework.py:7125``. ``flags``: dict or single name."""
    if not isinstance(flags, dict):
        raise TypeError("set_flags expects a dict of {flag_name: value}")
    for name, value in flags.items():
        f = _REGISTRY.get(name)
        if f is None:
            raise ValueError(f"unknown flag {name!r}; known: {sorted(_REGISTRY)}")
        v = f._coerce(value)
        f.value = v
        if f.jax_config is not None:
            import jax

            jax.config.update(f.jax_config, v)
        if f.setter is not None:
            f.setter(v)


def get_flags(flags):
    """Reference ``fluid/framework.py:7149``: name or list of names -> dict."""
    names = [flags] if isinstance(flags, str) else list(flags)
    out = {}
    for name in names:
        f = _REGISTRY.get(name)
        if f is None:
            raise ValueError(f"unknown flag {name!r}")
        out[name] = f.value
    return out


# ---------------------------------------------------------------------------
# built-in flags: the subset of platform/flags.cc with a TPU meaning, the
# ones accepted for reference compatibility, and two knobs of this framework
# (matmul_precision, profiler_host_only). None picks a kernel: attention's
# routes and block sizes are constants beside their measurements
# (nn/functional/attention.py, ops/pallas/).
# ---------------------------------------------------------------------------

register_flag("check_nan_inf", False,
              help="scan op outputs for NaN/Inf in eager mode "
                   "(reference FLAGS_check_nan_inf, nan_inf_utils_detail.cc)")
register_flag("matmul_precision", "default", typ=str,
              jax_config="jax_default_matmul_precision",
              help="default/high/highest — TPU matmul precision "
                   "(≙ FLAGS_gemm_use_half_precision_compute_type)")
register_flag("cudnn_deterministic", False,
              help="accepted for reference compat; XLA on TPU is deterministic")
register_flag("benchmark", False,
              help="accepted for reference compat (kernel timing mode)")
register_flag("eager_delete_tensor_gb", 0.0,
              help="accepted for reference compat; XLA manages buffers")
register_flag("allocator_strategy", "auto_growth", typ=str,
              help="accepted for reference compat; XLA BFC allocator")
register_flag("fraction_of_gpu_memory_to_use", 0.92,
              help="accepted for reference compat")
register_flag("use_pinned_memory", True,
              help="accepted for reference compat")
register_flag("max_inplace_grad_add", 0,
              help="accepted for reference compat")
register_flag("profiler_host_only", False,
              help="paddle.profiler: skip the XPlane device capture")

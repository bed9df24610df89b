"""Pallas TPU kernels — the hot fused ops.

TPU-native replacement for the reference's hand-written CUDA fused kernels
(``paddle/fluid/operators/fused/fused_attention_op.cu``, ``fmha_ref.h``,
``fused_softmax_mask.cu.h``, fused layernorm inside
``fused_attention_op.cu``): here each fused op is a Pallas kernel tiled for
MXU/VMEM, with a custom VJP so the backward is fused too.

Capability gating is EXPLICIT (no silent fallbacks): :func:`is_available`
says whether the Mosaic TPU compile path exists for the current backend, and
``interpret_mode()`` lets tests run the same kernels interpreted on CPU.

Mosaic kernels cannot be partitioned by GSPMD, so on a multi-device mesh the
kernel entry points partition themselves (``ops/partition.py``).
"""
from __future__ import annotations

import jax

_FORCE_INTERPRET = False


def interpret_requested() -> bool:
    """True when Pallas kernels should run in interpreter mode (CPU tests)."""
    return _FORCE_INTERPRET


class interpret_mode:
    """Context manager forcing interpreter-mode Pallas (for CPU parity tests).

    Refused on a TPU backend: there the compiled kernels are the only path,
    and an interpreted run would pass for one."""

    def __enter__(self):
        global _FORCE_INTERPRET
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "pallas.interpret_mode() on a TPU backend: the chip runs the "
                "compiled Mosaic kernels; interpret mode is for CPU tests")
        self._prev = _FORCE_INTERPRET
        _FORCE_INTERPRET = True
        return self

    def __exit__(self, *exc):
        global _FORCE_INTERPRET
        _FORCE_INTERPRET = self._prev
        return False


def is_available() -> bool:
    """Mosaic (compiled Pallas) needs a TPU backend; interpreter mode works
    anywhere."""
    return interpret_requested() or jax.default_backend() == "tpu"


from .flash_attention import flash_attention, flash_attention_cached  # noqa: E402,E501
from .layer_norm import fused_layer_norm  # noqa: E402

__all__ = [
    "flash_attention",
    "flash_attention_cached",
    "fused_layer_norm",
    "is_available",
    "interpret_mode",
    "interpret_requested",
]

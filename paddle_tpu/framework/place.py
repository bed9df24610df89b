"""Places — device identity.

Analogue of ``phi::Place`` (reference ``paddle/phi/common/place.h``), collapsed
to the devices that exist in a jax process: TPU chips addressable by this host,
plus host CPU. ``CUDAPlace`` is kept as a compat alias resolving to the
accelerator so reference-style user code runs unchanged.
"""
from __future__ import annotations

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def jax_device(self):
        """The jax device this place names. A place that names no device of
        this process raises: ``TPUPlace(3)`` on one chip is an error, not
        chip 0, and an accelerator place on a CPU-only host is not the CPU."""
        devs = self._devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self!r} names no device: this process has {len(devs)} "
                f"{self.device_type} device(s) "
                f"(default backend {jax.default_backend()!r})")
        return devs[self.device_id]

    def _devices(self):
        return [d for d in jax.devices() if d.platform == self.device_type]


class CPUPlace(Place):
    device_type = "cpu"

    def _devices(self):
        return jax.local_devices(backend="cpu")


class TPUPlace(Place):
    device_type = "tpu"


# Compat: reference user code says CUDAPlace / set_device("gpu"); map to the
# default jax accelerator.
class CUDAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    pass


class NPUPlace(TPUPlace):
    """Reference compat (Ascend NPU): maps to the accelerator place."""

    def __init__(self, device_id=0):
        super().__init__(device_id)


class XPUPlace(TPUPlace):
    pass


class CustomPlace(Place):
    def __init__(self, device_type, device_id=0):
        super().__init__(device_id)
        self.device_type = device_type


_current_device = None


def _default_place() -> Place:
    global _current_device
    if _current_device is None:
        backend = jax.default_backend()
        _current_device = TPUPlace(0) if backend != "cpu" else CPUPlace(0)
    return _current_device


def set_device(device: str) -> Place:
    """paddle.set_device — accepts 'cpu', 'tpu', 'tpu:0', 'gpu' (alias for
    the accelerator). Asking for an accelerator this process does not have
    raises; it is never answered with the CPU."""
    global _current_device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    if name == "cpu":
        place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "xpu", "npu", "mlu"):
        place = TPUPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    place.jax_device()  # raises when the place names no device
    _current_device = place
    return _current_device


def get_device() -> str:
    p = _default_place()
    return f"{p.device_type}:{p.device_id}"


def is_compiled_with_cuda() -> bool:  # compat shim
    return False


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"

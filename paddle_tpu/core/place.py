"""Device/place model.

Mirrors the reference's Place hierarchy (phi::Place / CPUPlace / GPUPlace /
CustomPlace; reference: paddle/phi/common/place.h — unverified, SURVEY.md §0)
with a TPU-first twist: the accelerator place is ``TPUPlace`` and
``paddle.set_device('tpu')`` selects it. On machines without a TPU the
"tpu" place transparently maps to whatever jax's default backend is, so the
same user code runs under the CPU test mesh.
"""
from __future__ import annotations

import jax

__all__ = [
    "Place",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "XPUPlace",
    "CustomPlace",
    "set_device",
    "get_device",
    "device_for_place",
    "is_compiled_with_cuda",
    "is_compiled_with_xpu",
    "is_compiled_with_rocm",
    "is_compiled_with_custom_device",
]


class Place:
    """Base place: a named device slot (device_type, device_id)."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)

    def __repr__(self):
        return "Place(cpu)"


class TPUPlace(Place):
    device_type = "tpu"


class CustomPlace(Place):
    """CustomDevice plugin seam (reference: paddle/phi/backends/custom/)."""

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


# GPU/XPU places exist for API compatibility; they alias the accelerator.
class CUDAPlace(TPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


_current_place: Place | None = None


def _accelerator_devices():
    """Non-CPU jax devices, else the CPU ones. A backend that fails to
    initialise raises here: it must not quietly become the CPU."""
    devs = jax.devices()
    return [d for d in devs if d.platform != "cpu"] or devs


def set_device(device) -> Place:
    """paddle.set_device('tpu' | 'cpu' | 'tpu:0')."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name = str(device)
    if ":" in name:
        kind, _, idx = name.partition(":")
    else:
        kind, idx = name, "0"
    kind = {"gpu": "tpu", "xpu": "tpu", "cuda": "tpu"}.get(kind, kind)
    if kind == "cpu":
        _current_place = CPUPlace()
    elif kind == "tpu":
        _current_place = TPUPlace(int(idx))
    else:
        _current_place = CustomPlace(kind, int(idx))
    return _current_place


def get_device() -> str:
    p = _current_place or _default_place()
    if p.is_cpu_place():
        return "cpu"
    return f"{p.device_type}:{p.device_id}"


def _default_place() -> Place:
    if _accelerator_devices()[0].platform != "cpu":
        return TPUPlace(0)
    return CPUPlace()


def current_place() -> Place:
    return _current_place or _default_place()


def device_for_place(place: Place | None = None):
    """Resolve a Place to a concrete jax Device."""
    place = place or current_place()
    if place.is_cpu_place():
        # the host backend exists beside any accelerator
        return jax.devices("cpu")[0]
    accel = _accelerator_devices()
    return accel[min(place.device_id, len(accel) - 1)]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    return device_type == "tpu"

"""Device/Place API.

The reference models devices as ``Place`` objects (paddle/phi/common/place.h) plus a
``paddle.device`` module (set_device/get_device).  Here a Place resolves to a JAX
device; device/memory management itself rides on PJRT, so this layer is bookkeeping
plus explicit host↔device transfer points.
"""
from __future__ import annotations

import functools

import jax


class Place:
    """Base place: ``Place("tpu", 0)``."""

    def __init__(self, device_type: str, device_id: int = 0):
        self._type = device_type
        self._id = device_id

    @property
    def device_type(self) -> str:
        return self._type

    def get_device_id(self) -> int:
        return self._id

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._type == other._type
            and self._id == other._id
        )

    def __hash__(self):
        return hash((self._type, self._id))

    def __repr__(self):
        return f"Place({self._type}:{self._id})"

    def jax_device(self):
        """Resolve to the concrete jax.Device (None → default)."""
        devs = _devices_by_type(self._type)
        if not devs or self._id >= len(devs):
            raise RuntimeError(
                f"{self!r}: JAX sees {len(devs or ())} {self._type} "
                f"device(s)")
        return devs[self._id]


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


# Alias kept so reference-era code naming CUDAPlace keeps working; it resolves to
# the accelerator actually present (TPU here).
class CUDAPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("accelerator", device_id)


class CUDAPinnedPlace(CPUPlace):
    pass


@functools.cache
def _accelerator_platform() -> str:
    for d in jax.devices():
        if d.platform != "cpu":
            return d.platform
    return "cpu"


def _devices_by_type(device_type: str):
    # "accelerator" (and the reference-era gpu/cuda spellings) mean whatever
    # this host's default backend is — the CPU included, which is how the
    # CPU test harness runs reference-style code.  A platform named
    # outright ("tpu", "cpu") is that platform or nothing.
    if device_type in ("accelerator", "gpu", "cuda"):
        device_type = _accelerator_platform()
    return [d for d in jax.devices() if d.platform == device_type] or None


_current_place: Place | None = None


def set_device(device: str) -> Place:
    """paddle.set_device("tpu") / ("cpu") / ("tpu:1")."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name, _, idx = device.partition(":")
    name = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(name, name)
    place = CPUPlace() if name == "cpu" else Place("accelerator", int(idx or 0))
    _current_place = place
    return place


def get_device() -> str:
    p = _get_current_place()
    return f"{p.device_type}:{p.get_device_id()}" if p.device_type != "cpu" else "cpu"


def _get_current_place() -> Place:
    global _current_place
    if _current_place is None:
        plat = _accelerator_platform()
        _current_place = CPUPlace() if plat == "cpu" else Place("accelerator", 0)
    return _current_place


def default_jax_device():
    return _get_current_place().jax_device()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_distribute() -> bool:
    return True


def device_count() -> int:
    return jax.device_count()

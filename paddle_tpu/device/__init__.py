"""paddle.device parity: device selection + memory/synchronisation helpers.

Memory management itself is PJRT's BFC allocator (reference analog:
paddle/fluid/memory/allocation/); this module exposes the stats/sync surface.
"""
from __future__ import annotations

import jax

from ..core.place import (  # noqa: F401
    Place, CPUPlace, TPUPlace, CUDAPlace, set_device, get_device, device_count,
    is_compiled_with_cuda, is_compiled_with_rocm, is_compiled_with_xpu,
    is_compiled_with_tpu, default_jax_device,
)

from . import cuda  # noqa: E402,F401
from . import tpu  # noqa: E402,F401


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def synchronize(device=None):
    """Block until all queued work on the device is done."""
    jax.effects_barrier()
    for d in jax.live_arrays():
        try:
            d.block_until_ready()
            break
        except Exception:
            break

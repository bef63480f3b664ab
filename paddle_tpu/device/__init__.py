"""Device management (parity: python/paddle/device).

TPU-native: devices are jax devices; a ``Place`` is a thin descriptor. There is
no allocator/stream surface — XLA owns both. ``set_device`` selects the default
jax device for new tensors.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import cuda  # noqa: F401
from .memory import (  # noqa: F401
    empty_cache,
    get_memory_info,
    max_memory_allocated,
    max_memory_reserved,
    memory_allocated,
    memory_reserved,
    memory_stats,
    reset_max_memory_allocated,
    reset_max_memory_reserved,
)

__all__ = [
    "Place", "TPUPlace", "CPUPlace", "CUDAPlace", "CUDAPinnedPlace",
    "on_tpu", "get_device", "set_device",
    "get_all_devices", "device_count", "is_compiled_with_cuda", "is_compiled_with_xpu",
    "is_compiled_with_rocm", "is_compiled_with_custom_device", "synchronize",
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "max_memory_reserved", "reset_max_memory_allocated",
    "reset_max_memory_reserved", "get_memory_info", "empty_cache",
]


def on_tpu() -> bool:
    """The one test for "running on the chip": JAX's default backend is
    ``tpu``. Kernels, benchmarks and entry points all ask this and nothing
    else, so a device the code does not know is never taken for the chip."""
    return jax.default_backend() == "tpu"


class Place:
    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_gpu_place(self):
        return False

    def is_tpu_place(self):
        return self.kind == "tpu"


def TPUPlace(idx: int = 0) -> Place:
    return Place("tpu", idx)


def CPUPlace() -> Place:
    return Place("cpu", 0)


def CUDAPinnedPlace() -> Place:
    """Pinned host memory place (PJRT manages host staging; alias of CPU)."""
    return Place("cpu")


def CUDAPlace(idx: int = 0) -> Place:
    # Accepted for API compatibility; maps to the accelerator jax exposes.
    return Place(jax.default_backend(), idx)


def _place_of(value) -> Place:
    try:
        devs = value.devices() if hasattr(value, "devices") else None
        if devs:
            d = next(iter(devs))
            return Place(d.platform, d.id)
    except Exception:
        pass
    return Place(jax.default_backend(), 0)


_current = None


def get_device() -> str:
    if _current is not None:
        return _current
    b = jax.default_backend()
    return f"{b}:0"


def set_device(device: str):
    global _current
    _current = device
    return Place(*_split(device))


def _split(device: str):
    if ":" in device:
        k, i = device.split(":")
        return k, int(i)
    return device, 0


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    return device_type == "tpu"


def synchronize(device=None):
    """Block until all dispatched work completes (stream sync analog)."""
    (jax.device_put(0) + 0).block_until_ready()


# ---------------------------------------------------- surface-parity tail
# (parity: python/paddle/device/__init__.py __all__)
from .cuda import Event, Stream  # noqa: E402,F401


class XPUPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("xpu", index)


class IPUPlace(Place):
    def __init__(self, index: int = 0):
        super().__init__("ipu", index)


def current_stream(device=None) -> Stream:
    """The one device stream view (XLA serializes per-device dispatch);
    shares device.cuda's registry so both spellings agree."""
    from . import cuda as _cuda

    return _cuda.current_stream(device)


def get_all_device_type():
    return ["cpu", "tpu"]


def get_all_custom_device_type():
    return ["tpu"]  # the PJRT-plugin device (reference: CustomDevice slot)


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices() if d.platform != "cpu"]


def get_cudnn_version():
    return None  # no cudnn on TPU (reference returns None when absent)


def is_compiled_with_cinn() -> bool:
    return False  # the XLA stack replaces CINN wholesale


def is_compiled_with_distribute() -> bool:
    return True  # collectives are always compiled in (XLA)


__all__ += ["Event", "Stream", "XPUPlace", "IPUPlace", "current_stream",
            "get_all_device_type", "get_all_custom_device_type",
            "get_available_device", "get_available_custom_device",
            "get_cudnn_version", "is_compiled_with_cinn",
            "is_compiled_with_distribute"]


def is_compiled_with_ipu() -> bool:
    return False


def set_stream(stream: Stream = None) -> Stream:
    """parity: device.set_stream — XLA exposes one serialized device stream;
    the call records the handle (in device.cuda's single registry) and
    returns the previous one."""
    from . import cuda as _cuda

    prev = _cuda.current_stream()
    if stream is not None:
        _cuda.set_stream(stream)
    return prev


class stream_guard:
    """parity: device.stream_guard — scope a 'current' stream handle (all
    handles view the same XLA dispatch stream)."""

    def __init__(self, stream: Stream = None):
        self.stream = stream

    def __enter__(self):
        self._prev = set_stream(self.stream)
        return self.stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


__all__ += ["is_compiled_with_ipu", "set_stream", "stream_guard"]

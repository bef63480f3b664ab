"""Device memory observability.

Reference capability: the memory stat registry + peak trackers
(/root/reference/paddle/fluid/memory/stats.h) surfaced through the
python/paddle/device/cuda memory APIs (max_memory_allocated etc.).

TPU-native: XLA owns the allocator, so the numbers come from
``jax.Device.memory_stats()`` (PJRT per-device counters: bytes_in_use,
peak_bytes_in_use, bytes_limit, ...). The hardware peak counter is
process-lifetime; ``reset_max_memory_allocated`` therefore switches that
device to a software-observed peak (max over every subsequent stats call),
the same observable-point semantics the reference's HostMemoryStatResetPeak
gives when no allocation happens between observations.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

__all__ = [
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "max_memory_reserved",
    "reset_max_memory_allocated", "reset_max_memory_reserved",
    "get_memory_info", "empty_cache",
]

# device id -> software peak tracking state (set by reset_max_memory_*)
_sw_peak_alloc: Dict[int, int] = {}
_sw_peak_reserved: Dict[int, int] = {}


def _device(device=None) -> "jax.Device":
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        idx = int(device.split(":")[1]) if ":" in device else 0
        return jax.devices()[idx]
    if hasattr(device, "index"):  # Place
        return jax.devices()[device.index]
    return device


def memory_stats(device=None) -> dict:
    """Raw PJRT memory counters for the device. The CPU backend keeps none
    and reports ``{}``; on any other platform a device that reports nothing
    is an error, never an empty answer."""
    d = _device(device)
    st = d.memory_stats()
    if st is None:
        if d.platform != "cpu":
            raise RuntimeError(
                f"{d.platform} device {d.id} ({d.device_kind}) reports no "
                "memory_stats()")
        return {}
    return dict(st)


def _observe(d) -> dict:
    st = memory_stats(d)
    in_use = int(st.get("bytes_in_use", 0))
    reserved = int(st.get("bytes_reserved", st.get("pool_bytes", in_use)) or in_use)
    i = d.id
    if i in _sw_peak_alloc:
        _sw_peak_alloc[i] = max(_sw_peak_alloc[i], in_use)
    if i in _sw_peak_reserved:
        _sw_peak_reserved[i] = max(_sw_peak_reserved[i], reserved)
    return st


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on the device."""
    d = _device(device)
    return int(_observe(d).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak bytes_in_use — the hardware process-lifetime counter, or the
    software-observed peak after reset_max_memory_allocated()."""
    d = _device(device)
    st = _observe(d)
    if d.id in _sw_peak_alloc:
        return _sw_peak_alloc[d.id]
    return int(st.get("peak_bytes_in_use", st.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    d = _device(device)
    st = _observe(d)
    in_use = int(st.get("bytes_in_use", 0))
    return int(st.get("bytes_reserved", st.get("pool_bytes", in_use)) or in_use)


def max_memory_reserved(device=None) -> int:
    d = _device(device)
    st = _observe(d)
    if d.id in _sw_peak_reserved:
        return _sw_peak_reserved[d.id]
    in_use = int(st.get("bytes_in_use", 0))
    cur_reserved = int(st.get("bytes_reserved", st.get("pool_bytes", in_use)) or in_use)
    # no reserved-peak counter in PJRT: never report less than current reserved
    return max(int(st.get("peak_bytes_in_use", in_use)), cur_reserved)


def reset_max_memory_allocated(device=None) -> None:
    d = _device(device)
    _sw_peak_alloc[d.id] = int(memory_stats(d).get("bytes_in_use", 0))


def reset_max_memory_reserved(device=None) -> None:
    d = _device(device)
    st = memory_stats(d)
    in_use = int(st.get("bytes_in_use", 0))
    _sw_peak_reserved[d.id] = int(st.get("bytes_reserved", st.get("pool_bytes", in_use)) or in_use)


def get_memory_info(device=None) -> dict:
    """{'total': bytes_limit, 'free': limit - in_use, 'used': in_use} —
    cudaMemGetInfo-style summary."""
    st = memory_stats(device)
    total = int(st.get("bytes_limit", 0))
    used = int(st.get("bytes_in_use", 0))
    return {"total": total, "used": used, "free": max(total - used, 0)}


def empty_cache() -> None:
    """XLA's allocator has no user-facing cache-drop; provided for API parity."""
    return None

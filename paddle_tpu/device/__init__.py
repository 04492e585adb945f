"""Device management.

Reference parity: python/paddle/device/ (set_device, get_device, cuda submodule).
TPU-native: one logical device namespace over jax.devices(); "gpu" APIs report
absent (no GPU in the loop), "tpu"/"xpu"-style custom device is the native path.
"""
from __future__ import annotations

import jax


def _devices():
    return jax.devices()


def get_device() -> str:
    d = _devices()[0]
    return f"{d.platform}:{d.id}"


def set_device(device: str):
    """Select ``"<platform>"`` or ``"<platform>:<index>"``. Every op runs
    on JAX's default device (other chips are reached through a mesh), so
    that is the only device that can be selected: any other platform or
    index is an error, not a silent run somewhere else."""
    platform, _, index = str(device).partition(":")
    default = _devices()[0]
    if platform != default.platform:
        raise ValueError(
            f"set_device({device!r}): the default backend is "
            f"{default.platform!r}; no {platform!r} device is available "
            "to this process")
    if index and int(index) != default.id:
        raise ValueError(
            f"set_device({device!r}): ops run on the default device "
            f"{get_device()}; use a mesh to place work on other devices")
    return get_device()


def get_all_custom_device_type():
    return ["tpu"] if _devices()[0].platform == "tpu" else []


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    return device_type in ("tpu",)


def is_compiled_with_cinn() -> bool:
    return True  # XLA is the compiler


def device_count() -> int:
    return len(_devices())


class cuda:
    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def synchronize(device=None):
        pass

    @staticmethod
    def empty_cache():
        pass

    # The reference exposes memory stats under device.cuda.*; route to the
    # accelerator actually present so reference code keeps working.
    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def max_memory_reserved(device=None):
        return max_memory_reserved(device)

    @staticmethod
    def reset_peak_memory_stats(device=None):
        return reset_peak_memory_stats(device)


def synchronize(device=None):
    """Block until the device work behind every live array, and every
    ordered side effect, has completed."""
    jax.effects_barrier()
    jax.block_until_ready(jax.live_arrays())


# -- memory stats (reference phi/core/memory/stats.h; python
#    paddle.device.cuda.{memory_allocated,max_memory_allocated,...}) ----------
# TPU-native: XLA owns allocation; PJRT exposes per-device counters via
# Device.memory_stats() (bytes_in_use, peak_bytes_in_use, bytes_limit, ...).

def _device_index(device=None) -> int:
    if isinstance(device, int):
        return device
    if isinstance(device, str) and ":" in device:
        return int(device.rsplit(":", 1)[1])
    return 0


def _mem_stats(device=None) -> dict:
    d = _devices()[_device_index(device)]
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


# Resettable peak overlay (reference stats.h STAT_ResetPeakValue /
# paddle.device.cuda.reset_peak_memory_stats): PJRT's peak counters are
# monotone for the process, so after a reset the peak is tracked HERE —
# the running max of bytes_in_use observed at each stats poll since the
# reset. Polled, not hooked: allocations between polls can exceed the
# reported peak (documented approximation; profiler/memwatch.py polls
# every step, which bounds the gap to within-step churn).
_PEAK_RESET: dict = {}  # device index -> running max since reset


def _note_peak(device, bytes_in_use: int) -> None:
    idx = _device_index(device)
    if idx in _PEAK_RESET:
        _PEAK_RESET[idx] = max(_PEAK_RESET[idx], int(bytes_in_use))


def reset_peak_memory_stats(device=None) -> None:
    """Reset the peak-allocated counter to the CURRENT allocation
    (reference-API parity). Subsequent ``max_memory_allocated`` /
    ``max_memory_reserved`` report the max observed at stats polls since
    this call, letting per-phase peaks be measured."""
    idx = _device_index(device)
    s = _mem_stats(device)
    current = int(s.get("bytes_in_use", 0)) or live_array_bytes()
    _PEAK_RESET[idx] = current


def live_array_bytes() -> int:
    """CPU fallback for backends whose PJRT devices report no allocator
    counters: the sum of ``jax.live_arrays()`` sizes by shape×dtype.
    Committed (undonated/undeleted) buffers only — a close analog of
    bytes_in_use for the host-memory backend."""
    total = 0
    for a in jax.live_arrays():
        n = getattr(a, "nbytes", None)
        if isinstance(n, (int, float)):
            total += int(n)
    return total


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (stats.h STAT_GetCurrentValue
    analog)."""
    return int(_mem_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak allocated bytes (stats.h STAT_GetPeakValue analog).
    After ``reset_peak_memory_stats`` this is the poll-observed max
    since the reset, not the process-lifetime PJRT peak."""
    idx = _device_index(device)
    s = _mem_stats(device)
    if idx in _PEAK_RESET:
        # same fallback as the reset path: a backend with no allocator
        # counters (CPU PJRT) polls live-array bytes, otherwise the
        # post-reset peak would freeze at the reset-time value
        current = int(s.get("bytes_in_use", 0)) or live_array_bytes()
        _note_peak(device, current)
        return _PEAK_RESET[idx]
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (bytes_limit under XLA's
    preallocated BFC arena; falls back to in-use)."""
    s = _mem_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    idx = _device_index(device)
    if idx in _PEAK_RESET:
        return max_memory_allocated(device)
    s = _mem_stats(device)
    return int(s.get("peak_bytes_reserved",
                     s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))))


def memory_stats(device=None) -> dict:
    """Full PJRT allocator counter dict (device-kind dependent keys)."""
    return _mem_stats(device)


class Stream:
    """XLA manages streams internally; kept for API parity."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()

    def wait_event(self, event):
        pass


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib
    return contextlib.nullcontext()

"""Device management (reference: python/paddle/device/__init__.py).

The reference juggles CUDAPlace/XPUPlace/NPUPlace and streams
(paddle/phi/common/place.h, device/cuda/streams). On TPU there is a single
logical device space managed by XLA; placement happens via shardings, and
stream semantics do not exist (XLA program order). We expose the same API
shape with TPU-truthful behavior.
"""
from __future__ import annotations

import os

import jax

_current = [None]


def on_tpu() -> bool:
    """Whether the devices JAX selected are TPUs — the one question every
    kernel-vs-reference gate asks."""
    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:
        return False


def get_all_devices():
    return jax.devices()


def device_count() -> int:
    return len(jax.devices())


def set_device(device: str):
    """Accepts 'tpu', 'tpu:N', 'cpu', 'cpu:N'. Returns the jax device."""
    if ":" in device:
        kind, idx = device.split(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("gpu", "cuda"):
        raise ValueError("paddle_tpu is a TPU framework; no CUDA devices. "
                         "Use 'tpu' or 'cpu'.")
    devs = [d for d in jax.devices() if d.platform == kind]
    if not devs:
        raise RuntimeError(
            f"set_device({device!r}): JAX found no {kind!r} device "
            f"(platforms: {sorted({d.platform for d in jax.devices()})})")
    _current[0] = devs[idx % len(devs)]
    return _current[0]


def get_device() -> str:
    d = _current[0] or jax.devices()[0]
    return f"{d.platform}:{d.id}"


def current_device():
    return _current[0] or jax.devices()[0]


def synchronize():
    """Block until all dispatched work completes (reference:
    paddle.device.cuda.synchronize). jax.block_until_ready on a trivial op."""
    jax.block_until_ready(jax.numpy.zeros(()))


def is_compiled_with_cuda() -> bool:
    return False


# -------- accelerator capability + memory telemetry ------------------------

# bf16 peak matmul FLOP/s per chip by TPU generation (public spec sheets) —
# the denominator of every MFU figure (profiler.StepMonitor)
_PEAK_FLOPS = {"v2": 46e12, "v3": 123e12, "v4": 275e12,
               "v5 lite": 197e12, "v5e": 197e12, "v5litepod": 197e12,
               "v5p": 459e12, "v6e": 918e12, "v6p": 918e12}


def chip_peak_flops(device=None) -> float:
    """Peak bf16 matmul FLOP/s of one chip. A device kind the table does
    not know is an error: an assumed peak makes every MFU figure wrong
    without a trace."""
    d = device if device is not None else (_current[0] or jax.devices()[0])
    kind = getattr(d, "device_kind", "").lower()
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(f"no peak FLOP/s known for device kind "
                     f"{getattr(d, 'device_kind', None)!r}; add it to "
                     f"paddle_tpu.device._PEAK_FLOPS with its source")


# -------- persistent compile cache -----------------------------------------

_COMPILE_CACHE_DIRNAME = ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Keep compiled executables across processes; returns the directory.

    Entry points call this (chip_smoke.py, benchmarks/harness.py, the GPT
    examples), never package import. Where JAX_COMPILATION_CACHE_DIR
    is set JAX already reads it and nothing is set here. Otherwise the
    cache lives at one fixed, git-ignored path in the checkout: the path
    is part of the cache key's environment, so a directory named after a
    pid or a time would never be hit again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, _COMPILE_CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# observed peak live bytes per device id — the fallback tracker for
# runtimes whose allocator exposes no peak (CPU host platform); on TPU the
# allocator's own peak_bytes_in_use wins. _peak_baseline records the
# allocator's CUMULATIVE peak at the last reset so max_memory_allocated
# can report a since-reset figure even though XLA's counter never resets.
_observed_peak = {}
_peak_baseline = {}
_has_alloc_stats = {}


def has_allocator_stats(device=None) -> bool:
    """Whether the runtime exposes real allocator counters for this device
    (cached probe — callers use it to pick a sampling rate for the
    live-array fallback, which scans every live buffer)."""
    d = device if device is not None else (_current[0] or jax.devices()[0])
    cached = _has_alloc_stats.get(d.id)
    if cached is None:
        try:
            cached = d.memory_stats() is not None
        except Exception:
            cached = False
        _has_alloc_stats[d.id] = cached
    return cached


def memory_stats(device=None) -> dict:
    """Allocator statistics for one device (reference:
    paddle.device.cuda.memory_stats; here the XLA allocator).

    TPU: the runtime's own counters (bytes_in_use, peak_bytes_in_use,
    bytes_limit, ...). Host-platform fallback (no allocator stats): live
    bytes are summed over jax.live_arrays() placed on the device — an
    approximation (sharded arrays count full size), with the peak tracked
    across memory_stats() calls."""
    d = device if device is not None else (_current[0] or jax.devices()[0])
    stats = None
    try:
        stats = d.memory_stats()
    except Exception:
        stats = None
    if stats is None:
        live = 0
        try:
            for a in jax.live_arrays():
                try:
                    if d in a.devices():
                        live += a.nbytes
                except Exception:
                    continue
        except Exception:
            pass
        peak = max(_observed_peak.get(d.id, 0), live)
        _observed_peak[d.id] = peak
        stats = {"bytes_in_use": live, "peak_bytes_in_use": peak,
                 "source": "live_arrays"}
    else:
        stats = dict(stats)
        # since-reset peak: XLA's peak_bytes_in_use is process-cumulative;
        # after reset_max_memory_allocated it only counts if a NEW
        # high-water mark was set, else the live figure stands in
        raw_peak = stats.get("peak_bytes_in_use", 0)
        base = _peak_baseline.get(d.id, 0)
        eff = raw_peak if raw_peak > base else stats.get("bytes_in_use", 0)
        peak = max(_observed_peak.get(d.id, 0), eff,
                   stats.get("bytes_in_use", 0))
        _observed_peak[d.id] = peak
        stats["peak_bytes_in_use"] = peak
        stats.setdefault("source", "allocator")
    return stats


def max_memory_allocated(device=None) -> int:
    """Peak device bytes in use (reference:
    paddle.device.cuda.max_memory_allocated)."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_allocated(device=None) -> int:
    """Current device bytes in use."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def reset_max_memory_allocated(device=None):
    """Start a new peak-tracking window (reference:
    paddle.device.cuda.reset_max_memory_allocated): clears the tracked
    peak and, on allocator-backed runtimes, baselines XLA's cumulative
    counter so only a NEW high-water mark counts after this call."""
    d = device if device is not None else (_current[0] or jax.devices()[0])
    _observed_peak.pop(d.id, None)
    try:
        alloc = d.memory_stats()
    except Exception:
        alloc = None
    _peak_baseline[d.id] = (alloc or {}).get("peak_bytes_in_use", 0)
    return memory_stats(d)


class Stream:
    """Compat no-op: XLA has no user-visible streams; ordering is program
    order (replaces reference stream/event machinery,
    paddle/phi/backends/gpu/gpu_context.h:97)."""

    def synchronize(self):
        synchronize()


def cuda_empty_cache():
    pass

from . import cuda  # noqa: E402,F401


# -------- surface completion (reference: python/paddle/device/__init__.py)

class Event:
    """reference: device.Event — cross-stream sync marker. XLA owns
    scheduling (SURVEY §7 StreamSafe row): record/query/synchronize map to
    program-order completion."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream()


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib
    return contextlib.nullcontext()


def XPUPlace(dev_id=0):  # noqa: N802
    from ..fluid import XPUPlace as _x
    return _x(dev_id)


def IPUPlace():  # noqa: N802
    raise RuntimeError("IPU backend is not available in paddle_tpu")


def MLUPlace(dev_id=0):  # noqa: N802
    raise RuntimeError("MLU backend is not available in paddle_tpu")


def get_cudnn_version():
    return None  # no cuDNN in the TPU stack


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_mlu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str) -> bool:
    return False


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []

"""PjRt client bootstrap + device handles.

The analog of the reference's CUDA runtime initialization; on TPU there is no
per-thread "current device" (reference device_guard.h) — device identity is
carried explicitly by JAX device handles, which is why none of the framework's
APIs have set-device side effects.
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional


def _jax():
    import jax
    return jax


@functools.lru_cache(maxsize=None)
def devices(platform: Optional[str] = None) -> tuple:
    """All addressable devices (reference DeviceInfo::Count enumeration).

    jax.local_devices, not jax.devices: under jax.distributed the global
    list includes other processes' devices, and staging to a
    non-addressable device raises — every consumer here (allocators,
    engines, watchdog) wants THIS process's devices."""
    return tuple(_jax().local_devices(backend=platform) if platform
                 else _jax().local_devices())


def device_count() -> int:
    return len(devices())


def local_device(index: int = 0):
    """A device handle by local index."""
    devs = devices()
    if index >= len(devs):
        raise IndexError(f"device {index} out of range ({len(devs)} available)")
    return devs[index]


def platform_name() -> str:
    return devices()[0].platform


def is_tpu() -> bool:
    return platform_name() == "tpu"


def pallas_interpret() -> bool:
    """The default for every Pallas kernel's ``interpret=None``: Mosaic
    compiles the kernel on a TPU, and only off TPU (the CPU test mesh)
    does it run in the Pallas interpreter.  Decided here and nowhere
    else; ``chip_smoke.py`` asserts it is False on the chip."""
    return not is_tpu()


def process_index() -> int:
    """This host's index in a multi-host deployment."""
    return _jax().process_index()


def process_count() -> int:
    return _jax().process_count()


def enable_compilation_cache(path: str = "",
                             min_compile_secs: float = 0.5) -> None:
    """Persistent XLA compilation cache — the runtime side of the AOT-engine
    story: recompiles of the same program/topology become disk hits, so
    server restarts skip the cold-compile (the TRT 'deserialize plan' UX).
    ``min_compile_secs`` sets the caching threshold (the test harness
    lowers it: tier-1 builds hundreds of small near-identical engines).
    """
    jax = _jax()
    cache_dir = path or os.environ.get(
        "TPULAB_COMPILE_CACHE", os.path.expanduser("~/.cache/tpulab/xla"))
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))


def force_cpu(n_devices: int = 8) -> None:
    """Hermetic-test hook: route JAX to N virtual CPU devices.

    Must run before any JAX backend is created.  ``JAX_PLATFORMS=cpu``
    alone selects the CPU backend too, but with ONE device; this also
    sets ``--xla_force_host_platform_device_count`` so mesh code has N.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}").strip()
    _jax().config.update("jax_platforms", "cpu")
    devices.cache_clear()

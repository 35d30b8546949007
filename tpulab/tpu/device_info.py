"""Device/topology introspection (reference device_info.h:35-57 — NVML
affinity/alignment/power/memory queries → PjRt device attributes).

TPU equivalents: chip kind/coords/ICI topology from device attributes, HBM
usage from ``memory_stats`` (absent on CPU backends — reported as None),
host NUMA affinity via :mod:`tpulab.core.affinity` (TPU hosts are
single-socket-local to their chips in Cloud TPU VMs).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

from tpulab.core.affinity import Affinity, CpuSet
from tpulab.tpu import platform as plat


@dataclass
class MemoryInfo:
    bytes_in_use: Optional[int]
    bytes_limit: Optional[int]
    peak_bytes_in_use: Optional[int]


class DeviceInfo:
    """Per-device introspection (reference DeviceInfo static API)."""

    @staticmethod
    def count() -> int:
        return plat.device_count()

    @staticmethod
    def device_kind(index: int = 0) -> str:
        return plat.local_device(index).device_kind

    @staticmethod
    def coords(index: int = 0) -> Optional[tuple]:
        d = plat.local_device(index)
        c = getattr(d, "coords", None)
        return tuple(c) if c is not None else None

    @staticmethod
    def core_on_chip(index: int = 0) -> Optional[int]:
        return getattr(plat.local_device(index), "core_on_chip", None)

    @staticmethod
    def memory_info(index: int = 0) -> MemoryInfo:
        """HBM usage (reference cudaMemGetInfo / NVML memory info)."""
        d = plat.local_device(index)
        stats = None
        if hasattr(d, "memory_stats"):
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
        if not stats:
            return MemoryInfo(None, None, None)
        return MemoryInfo(
            stats.get("bytes_in_use"),
            stats.get("bytes_limit"),
            stats.get("peak_bytes_in_use"),
        )

    # Public per-chip peak dense-matmul throughput (FLOP/s), keyed by the
    # exact PjRt ``device_kind`` string (the same keys jax's own
    # pallas/mosaic/tpu_info.py matches on; a v5e chip reports
    # "TPU v5 lite").  Sources: cloud.google.com/tpu/docs
    # system-architecture tables (bf16 peak; int8 where the generation
    # has an int8 MXU mode).  The reference exposes NVML power/clocks
    # (device_info.cc) — libtpu exposes no power/duty-cycle query via
    # PjRt, so the compute-capability table + HBM stats are the TPU
    # telemetry surface (see docs/PARITY.md).
    _V5E = {"bf16": 197e12, "int8": 394e12}
    _V5P = {"bf16": 459e12, "int8": 918e12}
    _V6E = {"bf16": 918e12, "int8": 1836e12}
    _PEAK_FLOPS = {
        "TPU v6 lite": _V6E, "TPU v6e": _V6E,
        "TPU v5 lite": _V5E, "TPU v5e": _V5E,
        "TPU v5": _V5P, "TPU v5p": _V5P,
        "TPU v4": {"bf16": 275e12, "int8": 275e12},
        "TPU v3": {"bf16": 123e12, "int8": 123e12},
        "TPU v2": {"bf16": 46e12, "int8": 46e12},
    }

    @staticmethod
    def peak_flops(dtype: str = "bf16", index: int = 0) -> Optional[float]:
        """Per-chip peak FLOP/s for ``dtype`` ('bf16'|'int8') — the MFU
        denominator (fp32 matmuls route through the MXU at bf16-class
        rates under XLA's default precision, so bf16 is the honest
        denominator for fp32 models too).  None off TPU (a CPU has no
        entry and no MFU row); a TPU whose ``device_kind`` is not in the
        table raises — a guessed peak is a wrong utilization."""
        dev = plat.local_device(index)
        if dev.platform != "tpu":
            return None
        peaks = DeviceInfo._PEAK_FLOPS.get(dev.device_kind)
        if peaks is None:
            raise KeyError(
                f"no peak-FLOP/s entry for TPU device_kind "
                f"{dev.device_kind!r}; add it to DeviceInfo._PEAK_FLOPS "
                f"with its source (known: {sorted(DeviceInfo._PEAK_FLOPS)})")
        return peaks.get(dtype, peaks["bf16"])

    @staticmethod
    def alignment() -> int:
        """Minimum device allocation alignment (reference DeviceInfo::Alignment).

        XLA TPU buffers are tiled; 512 bytes covers the lane*sublane tile row
        for all dtypes (8 sublanes x 128 lanes x 4B / 8 rows).
        """
        return 512

    @staticmethod
    def cpu_affinity(index: int = 0) -> CpuSet:
        """CPUs local to the device's host (reference GPU<->CPU NUMA mask).

        Cloud TPU VMs dedicate the whole host to its chips, so this is the
        host's full online set unless NUMA nodes are exposed.
        """
        nodes = Affinity.numa_nodes()
        return nodes[0].cpus if len(nodes) == 1 else Affinity.all_cpus()

    @staticmethod
    def attributes(index: int = 0) -> Dict[str, object]:
        d = plat.local_device(index)
        out: Dict[str, object] = {
            "id": d.id, "platform": d.platform, "device_kind": d.device_kind,
            "process_index": d.process_index,
        }
        for attr in ("coords", "core_on_chip", "slice_index"):
            if hasattr(d, attr):
                out[attr] = getattr(d, attr)
        return out

"""tpulab.kvcache — tiered KV cache: the host-memory offload tier.

HBM KV pressure used to destroy state (preempted requests re-prefilled,
evicted prefix-cache entries vanished); this package demotes that state
to a budgeted host-RAM tier and promotes it back — recompute-free
preemption and a spill-backed prefix cache (docs/PERFORMANCE.md "KV
tiering", docs/SERVING.md).

- :class:`HostKVStore` — budgeted LRU host tier on the
  :mod:`tpulab.memory` allocator/descriptor framework.
- :class:`KVOffloadManager` — async device<->host swap policy over a
  :class:`~tpulab.engine.kv_pool.PagedKVPool`, riding the
  :class:`~tpulab.tpu.transfer.TransferEngine` (write-behind swap-out).

Wire-up: ``ContinuousBatcher(..., kv_offload=...)`` (True / budget bytes
/ a manager instance).
"""

from tpulab.kvcache.host_store import HostKVStore  # noqa: F401
from tpulab.kvcache.offload import (DEFAULT_HOST_BUDGET,  # noqa: F401
                                    KVOffloadManager, SwapHandle)

__all__ = ["HostKVStore", "KVOffloadManager", "SwapHandle",
           "DEFAULT_HOST_BUDGET"]

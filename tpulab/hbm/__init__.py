"""Unified device-HBM economy (docs/PERFORMANCE.md "HBM economy").

trtlab's foundation is ONE allocator/descriptor/arena framework that every
higher layer rents from (PAPER.md layer map §0); tpulab reproduced that
for host memory, but device HBM grew into three fiefdoms — the
:class:`~tpulab.engine.kv_pool.PagedKVPool` pre-carves pages, the
:class:`~tpulab.modelstore.WeightMultiplexer` budgets weights *next to*
(not with) KV accounting, and compiled-program scratch was invisible to
both.  This package is the missing common ground:

- :class:`DeviceHBMLedger` — a byte-accurate device-memory ledger.
  Every claim is keyed by ``(tenant, tag)`` (the 2D-mesh work will make
  the key per-axis without another refactor) and mirrors a real tracked
  allocation, so the ledger can be *verified* against the device
  allocator gauges at any time.
- :class:`HBMArbiter` — the pressure protocol between tenants.  A hot
  model needing residency can force cold KV pages to demote to the host
  tier (the KV tier's swap-out path), a KV burst can evict a cold
  unleased model (the weight multiplexer's swap-out path), and the
  admission frontend consults ONE honest headroom number instead of two
  optimistic per-tenant estimates.
"""

from tpulab.hbm.arbiter import (KV_TENANT, SCRATCH_TENANT,  # noqa: F401
                                WEIGHTS_TENANT, HBMArbiter)
from tpulab.hbm.ledger import DeviceHBMLedger  # noqa: F401
from tpulab.hbm.scratch import MeasuredJit, scratch_bytes_of  # noqa: F401

__all__ = ["DeviceHBMLedger", "HBMArbiter", "MeasuredJit",
           "scratch_bytes_of", "KV_TENANT", "WEIGHTS_TENANT",
           "SCRATCH_TENANT"]

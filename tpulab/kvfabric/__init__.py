"""Fleet-wide KV fabric: any replica adopts any replica's prefix KV.

See :mod:`tpulab.kvfabric.fabric` for the design; docs/SERVING.md
"Fleet KV fabric" for the operator view.
"""

from tpulab.kvfabric.fabric import KVFabric, PulledKV, fabric_export

__all__ = ["KVFabric", "PulledKV", "fabric_export"]

"""tpulab.disagg — disaggregated prefill/decode: replica roles with KV
shipping over the host tier.

Prefill is compute-bound and bursty; decode is latency-bound and steady
— serving both from one paged pool wrecks ITL p99 under prefill bursts
(docs/SERVING.md "Replica roles", docs/PERFORMANCE.md).  This package
turns the tiered-KV swap path (tpulab.kvcache) into a wire: a prefill
replica runs the prompt forward only and demotes the finished KV to the
host tier in **wire form**; a decode replica admits the request by
**promoting the shipped KV** through ``KVOffloadManager.restore`` — zero
prefill dispatches on the decode side, bit-identical tokens.

- :mod:`~tpulab.disagg.wire` — versioned, CRC-checked snapshot encoding
  (:func:`serialize_snapshot` / :func:`deserialize_snapshot`,
  :class:`WireFormatError`, :func:`prompt_digest`).  Mismatched replicas
  (dtype / layout / page size / version) reject instead of corrupt.
- :class:`~tpulab.disagg.shipper.KVShipper` — export on the prefill
  replica (write-behind fence included), import + geometry validation on
  the decode replica.  ``disagg.ship`` chaos point on both sides; every
  failure degrades to local prefill on the decode replica.

Serving wire-up: ``mgr.serve(role="prefill"|"decode"|"unified", ...)``
reports the role over the Status RPC;
``GenerationReplicaSet(disaggregate=True)`` routes new requests to
prefill replicas and hands the shipment to a decode replica picked by
the existing admission load gauges.
"""

from tpulab.disagg.shipper import KVShipper, ShippedKV  # noqa: F401
from tpulab.disagg.wire import (WireFormatError,  # noqa: F401
                                deserialize_snapshot, prompt_digest,
                                serialize_snapshot)

__all__ = ["KVShipper", "ShippedKV", "WireFormatError",
           "serialize_snapshot", "deserialize_snapshot", "prompt_digest"]

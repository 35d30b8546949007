"""Multi-model serving: weight multiplexing over the host tier.

trtlab's v1 ``InferenceManager`` serves many models from pooled device
resources (PAPER.md §0); this package is that capability on the tpulab
memory framework: N registered models (LLM + ViT/ResNet + ONNX imports,
quantized variants) share one device's HBM, with cold weights in the
budgeted host tier (:class:`HostParamStore`, on the tpulab.memory
allocator/descriptor framework like the KV tier) and hot models swapped
in/out by :class:`WeightMultiplexer` over the same write-behind
TransferEngine path the KV offload manager uses.  docs/SERVING.md
"Multi-model serving" is the operator view.
"""

from tpulab.modelstore.host_store import (DEFAULT_HOST_BUDGET,
                                          HostParamStore, tree_nbytes)
from tpulab.modelstore.multiplexer import (BatcherAdapter,
                                           CompiledModelAdapter, ModelLease,
                                           WeightMultiplexer)

__all__ = [
    "DEFAULT_HOST_BUDGET",
    "HostParamStore",
    "tree_nbytes",
    "BatcherAdapter",
    "CompiledModelAdapter",
    "ModelLease",
    "WeightMultiplexer",
]

"""tpulab.ops — Pallas TPU kernels for the hot ops.

XLA fuses most of the model graph; these kernels cover the ops where manual
VMEM scheduling wins (the role .cu kernels would play in a CUDA framework —
the reference has none because TensorRT owns its kernels; a TPU-native
framework owns its hot ops):

- :mod:`ragged_attention` — the ragged paged-attention kernel FAMILY:
  per-lane ``(query_len, kv_len)`` segments serve plain decode (q=1),
  K+1 speculative verify, and mixed chunked-prefill+decode batches in
  one program; block tables drive HBM->VMEM page DMAs with online
  softmax, and a ``mesh`` shards the walk over the KV-heads dim via
  shard_map (the kernel side of engine.paged_steps' ragged forward)
- :mod:`selective_scan` — the Mamba-1 recurrence over a packed round,
  segmented by lane: a block of channels' state stays in registers across
  the round's rows, each lane's slot of the state store is read where its
  segment starts and written where it ends, in place
- :mod:`grouped_matmul` — the experts' grouped product over rows sorted by
  expert: each expert that has rows is one read of its weights and as near
  one pass through the MXU as its rows allow, tiled by the traced row count
  (the kernel under ``tpulab.parallel.moe.expert_ffn``)
"""

from tpulab.ops.ragged_attention import ragged_paged_attention

__all__ = ["ragged_paged_attention"]

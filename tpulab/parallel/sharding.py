"""Sharding helpers + transformer partition rules.

The sharding recipe (scaling-book style): pick a mesh, annotate array
shardings with ``NamedSharding``/``PartitionSpec``, let XLA insert the
collectives — psum over the ``model`` axis for row-parallel matmuls,
all-gathers where layouts demand.  Nothing here issues collectives by hand;
the specs below are the single source of truth the jit partitioner consumes.
"""

from __future__ import annotations

from typing import Any, Dict

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Batch-dim sharding for activations/inputs (DP)."""
    return NamedSharding(mesh, P(axis))


def kv_pool_sharding(mesh: Mesh, model_axis: str = "model") -> NamedSharding:
    """Paged-KV page-store sharding: the pool is
    ``(n_layers, n_pages, 2, page_size, n_kv_heads * head_dim)``
    (:func:`tpulab.engine.kv_pool.kv_page_shape`) and the page *payloads*
    shard over the model axis on the row (axis 4): the KV heads lie side
    by side in it, so a shard holds a contiguous group of whole heads —
    matching the column-parallel ``wqkv`` that produces them, so a
    sharded decode step scatters/gathers its own heads with no
    resharding.  Page *tables* (host-side int32 id maps) stay
    replicated.  The same spec places swap payloads
    ``(n_layers, n, 2, page_size, n_kv_heads * head_dim)``."""
    return NamedSharding(mesh, P(None, None, None, None, model_axis))


def transformer_param_shardings(params: Dict[str, Any], mesh: Mesh,
                                model_axis: str = "model") -> Dict[str, Any]:
    """Megatron-style TP rules for tpulab.models.transformer params:

    - ``wqkv``/``w1``/``w3``/``lm_head``: column-parallel (shard output
      dim over the model axis; w3 is the SwiGLU gate, lm_head's sharded
      output dim is the vocab — matching the tied ``embed.T`` layout)
    - ``wo``/``w2``: row-parallel (shard input dim; XLA inserts the psum)
    - embeddings: shard vocab dim; norms replicated
    """
    def rule(path: str):
        if (path.endswith("wqkv") or path.endswith("w1")
                or path.endswith("w3") or path.endswith("lm_head")):
            return P(None, model_axis)
        if path.endswith("wo") or path.endswith("w2"):
            return P(model_axis, None)
        if path.endswith("embed"):
            return P(model_axis, None)
        return P()  # norms, biases: replicated

    def build(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: build(v, f"{prefix}/{k}") for k, v in tree.items()}
        return NamedSharding(mesh, rule(prefix))

    return build(params)

"""Mixture-of-experts FFN + expert parallelism.

Completes the parallelism alphabet (dp/tp/sp covered elsewhere): experts
partition across a mesh axis, each device computes its local experts'
contribution for the token stream, and a ``psum`` over the expert axis
combines — exact MoE (no capacity truncation), communication = one psum
riding ICI.  (The token-dropping all_to_all dispatch variant is the
throughput optimization on top; this form is the correctness baseline and
the right shape for small expert counts.)

Router: top-k softmax gating, renormalized over the selected experts.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(d_model: int = 64, d_ff: int = 128, n_experts: int = 8,
                    seed: int = 0) -> Dict[str, Any]:
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    s = 0.05
    return {
        "router": jax.random.normal(ks[0], (d_model, n_experts)) * s,
        "w1": jax.random.normal(ks[1], (n_experts, d_model, d_ff)) * s,
        "w2": jax.random.normal(ks[2], (n_experts, d_ff, d_model)) * s,
    }


def _gates(params, x, top_k: int):
    """(N, D) tokens -> (N, E) gate weights: softmax over exactly the top-k
    router logits (lax.top_k breaks ties deterministically — tied/uniform
    logits still activate exactly k experts)."""
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    n_experts = logits.shape[-1]
    if top_k >= n_experts:
        return jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(logits, top_k)            # (N, k)
    weights = jax.nn.softmax(vals, axis=-1)             # renormalized over k
    onehot = jax.nn.one_hot(idx, n_experts, dtype=weights.dtype)  # (N, k, E)
    return jnp.einsum("nk,nke->ne", weights, onehot)


def moe_ffn(params: Dict[str, Any], x: jnp.ndarray, top_k: int = 2,
            compute_dtype=jnp.float32) -> jnp.ndarray:
    """Dense single-device MoE FFN reference ((N, D) -> (N, D))."""
    gates = _gates(params, x, top_k)                       # (N, E)
    h = jnp.einsum("nd,edf->nef", x.astype(compute_dtype),
                   params["w1"].astype(compute_dtype))
    h = jax.nn.gelu(h)
    y = jnp.einsum("nef,efd->ned", h, params["w2"].astype(compute_dtype))
    return jnp.einsum("ned,ne->nd", y, gates.astype(compute_dtype))


def make_expert_parallel_ffn(mesh: Mesh, axis_name: str = "model",
                             top_k: int = 2, compute_dtype=jnp.float32):
    """Expert-parallel MoE FFN: experts sharded over ``mesh[axis_name]``,
    outputs combined with a psum.  Exact vs :func:`moe_ffn`.

    Returns (ffn_fn, shard_params_fn): shard the params once with
    ``shard_params_fn``, then call ``ffn_fn(sharded_params, x)``.
    """
    expert_spec = P(axis_name)          # shard dim 0 (experts)
    param_specs = {"router": P(), "w1": expert_spec, "w2": expert_spec}

    def shard_params(params):
        return jax.device_put(params, jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), param_specs))

    def local_ffn(params, x):
        # x replicated; each device computes its LOCAL experts' contribution
        n_local = params["w1"].shape[0]
        e0 = jax.lax.axis_index(axis_name) * n_local
        gates = _gates_local(params, x, top_k, e0, n_local)
        h = jnp.einsum("nd,edf->nef", x.astype(compute_dtype),
                       params["w1"].astype(compute_dtype))
        h = jax.nn.gelu(h)
        y = jnp.einsum("nef,efd->ned", h, params["w2"].astype(compute_dtype))
        out = jnp.einsum("ned,ne->nd", y, gates.astype(compute_dtype))
        return jax.lax.psum(out, axis_name)  # combine expert shards

    def _gates_local(params, x, top_k, e0, n_local):
        # router is replicated: compute GLOBAL top-k gates, slice local cols
        full = _gates({"router": params["router"]}, x, top_k)
        return jax.lax.dynamic_slice_in_dim(full, e0, n_local, axis=1)

    def ffn(sharded_params, x):
        return jax.shard_map(local_ffn, mesh=mesh,
                             in_specs=(param_specs, P()),
                             out_specs=P())(sharded_params, x)

    return ffn, shard_params

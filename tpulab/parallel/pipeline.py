"""Pipeline parallelism: GPipe-style microbatch streaming over ppermute.

Stages partition layers across a mesh axis; microbatches stream through the
stage ring — at step t, stage s computes microbatch t-s and hands its
activation to stage s+1 via ``ppermute``.  The schedule runs
``n_stages + n_micro - 1`` steps (the classic bubble); every device executes
the same program (bubble steps compute on garbage and are masked out),
keeping the HLO static and collective-friendly.

The stage body must be shape-preserving ((mb, d) -> (mb, d)) — the uniform-
width trunk of a transformer fits; embedding/head live outside the pipeline.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pipeline(mesh: Mesh, stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                  axis_name: str = "pp"):
    """Build (pipeline_fn, shard_params_fn).

    ``shard_params_fn(stacked_params)`` shards a pytree whose leaves are
    stacked along dim 0 by stage ((n_stages, ...)); ``pipeline_fn(params, x)``
    takes microbatched input (n_micro, mb, d) and returns (n_micro, mb, d).
    """
    n_stages = mesh.shape[axis_name]
    param_spec = P(axis_name)

    def shard_params(stacked_params):
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] != n_stages:
                raise ValueError(
                    f"stacked stage dim {leaf.shape[0]} != pipeline axis "
                    f"{axis_name}={n_stages} (one stage per device)")
        return jax.device_put(stacked_params, jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, param_spec), stacked_params))

    def local_pipeline(params_local, x):
        # params_local leaves: (1, ...) — this stage's slice; x replicated
        params_me = jax.tree_util.tree_map(lambda p: p[0], params_local)
        s = jax.lax.axis_index(axis_name)
        n_micro, mb, d = x.shape
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
        total = n_stages + n_micro - 1

        def step(carry, t):
            state, collected = carry
            m = t - s                       # my microbatch index this step
            # stage 0 ingests fresh microbatches; others take the handoff
            ingest = x[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(s == 0, ingest, state)
            out = stage_fn(params_me, inp)
            valid = jnp.logical_and(m >= 0, m < n_micro)
            out = jnp.where(valid, out, inp)    # bubbles pass through
            # last stage collects its finished microbatch
            collect_now = jnp.logical_and(valid, s == n_stages - 1)
            collected = jax.lax.cond(
                collect_now,
                lambda c: jax.lax.dynamic_update_index_in_dim(
                    c, out, jnp.clip(m, 0, n_micro - 1), 0),
                lambda c: c, collected)
            state = jax.lax.ppermute(out, axis_name, fwd_perm)
            return (state, collected), None

        def vary(v):  # carries vary over the pipeline axis (cond typing)
            return jax.lax.pcast(v, axis_name, to="varying")

        init = (vary(jnp.zeros((mb, d), x.dtype)), vary(jnp.zeros_like(x)))
        (_, collected), _ = jax.lax.scan(step, init,
                                         jnp.arange(total))
        # only the last stage holds results — psum replicates them out
        mine = jnp.where(s == n_stages - 1, collected,
                         jnp.zeros_like(collected))
        return jax.lax.psum(mine, axis_name)

    def pipeline(sharded_params, x):
        return jax.shard_map(
            local_pipeline, mesh=mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: param_spec,
                                             sharded_params), P()),
            out_specs=P())(sharded_params, x)

    return pipeline, shard_params


def stack_stage_params(per_stage_params) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leaves stacked on
    dim 0 (the layout shard_params_fn expects)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *per_stage_params)

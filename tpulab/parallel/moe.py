"""Mixture-of-experts FFN: routing, the grouped expert product, expert
parallelism.

ONE expert FFN serves every caller: the paged engine's expert layers
(:func:`routed_ffn` from ``tpulab.engine.paged_steps._layer_block``), the dense
MoE transformer of the dry run (:func:`moe_ffn`) and the expert-parallel
form (:func:`make_expert_parallel_ffn`).  It is *exact*: no capacity, no
dropped token, whatever the routing.

Routing (:func:`route`), four router kinds:

``"softmax"``       top-k of the router logits, softmax over the chosen k;
``"sigmoid_bias"``  DeepSeek-V3 / GLM-4.x ``noaux_tc``: scores ``s =
                    sigmoid(x W_g)`` in float32; the k experts are chosen
                    by ``s + bias`` (``e_score_correction_bias``), weighted
                    by ``s`` itself (without the bias), normalised over the
                    chosen k (``+ 1e-20``) and scaled;
``"softmax_bias"``  LongCat-Flash: the same with ``s = softmax(x W_g)`` over
                    ALL the router's columns (and there without the
                    normalisation: a chosen column weighs ``scale * s``);
``"mlp"``           ZAYA1: ``"softmax_bias"``'s choice and weights on the
                    logits of an MLP, not of a matrix: ``r = x W_d + b_d``,
                    *depth averaging* ``r <- r + gamma r_prev`` with the
                    state ``r_prev`` the layer before handed on, ``u =
                    RMSNorm(r)``, ``z = W_3 gelu(W_2 gelu(W_1 u + b_1) +
                    b_2)`` (the exact GELU).  ``route`` returns ``r`` as a
                    third value: the state this layer hands on.

The router's last ``zero`` columns may be *identity experts*
(:func:`routed_ffn`): they hold no weights and return their input, so a row
costs as many expert products as it chose FFN columns, and the identity part
of the result is ``(sum of the chosen identity columns' weights) * x``,
computed for every row wherever the row is (under a share of the experts it
counts once, as a shared expert does).

The expert product (:func:`expert_ffn`) follows rows x top-k, not rows x
experts: the (row, expert) assignments are sorted by expert and each
projection is ONE grouped matrix product over the sorted rows
(``tpulab.ops.grouped_matmul.grouped_product`` with the group sizes: a
Pallas kernel tiled by the traced row count on a TPU, ``jax.lax.ragged_dot``
elsewhere), so an expert that no row chose costs no FLOPs and none of its
weights is read.  It is told which experts it holds (``first``, and
the leading axis of the weights): assignments to other experts contribute
nothing — on one device it holds them all; under
:func:`make_expert_parallel_ffn` each shard holds a contiguous range and a
``psum`` over the expert axis combines.
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


def _mlp_logits(r, x, prev, eps):
    """The ``"mlp"`` router's ``(logits (N, E), state (N, W))`` of rows ``x``:
    ``r`` its leaves (``down``, ``down_b``, ``gamma`` unless it is the first
    expert layer, ``norm``, ``w1 b1 w2 b2 w3``), ``prev`` the state the
    layer before handed on (None: none).  Float32 at full precision, as the
    matrix routers' one product is."""
    f32 = jnp.float32
    dot = lambda a, w: jnp.dot(a, w.astype(f32),
                               precision=jax.lax.Precision.HIGHEST)
    state = dot(x.astype(f32), r["down"]) + r["down_b"].astype(f32)
    if prev is not None and "gamma" in r:
        state = state + r["gamma"].astype(f32) * prev
    u = state * jax.lax.rsqrt(jnp.square(state).mean(-1, keepdims=True)
                              + eps) * r["norm"]["scale"].astype(f32)
    for w, b in (("w1", "b1"), ("w2", "b2")):
        u = jax.nn.gelu(dot(u, r[w]) + r[b].astype(f32), approximate=False)
    return dot(u, r["w3"]), state


def route(router_w, x, top_k: int, kind: str = "softmax", bias=None,
          scale: float = 1.0, norm: bool = True, prev=None,
          eps: float = 1e-6):
    """(N, D) rows -> ``(idx (N, k) int32, weights (N, k) float32)``, in
    float32 whatever the rows' dtype (``lax.top_k`` breaks ties
    deterministically: tied scores still choose exactly k experts).  Kind
    ``"mlp"`` (``router_w`` the MLP's leaves, ``prev`` the state handed on
    to it, ``eps`` its norm's) returns ``(idx, weights, state (N, W))``."""
    with jax.named_scope("moe_router"):
        state = ()
        if kind == "mlp":
            logits, handed = _mlp_logits(router_w, x, prev, eps)
            state = (handed,)
        else:
            logits = jnp.dot(x.astype(jnp.float32),
                             router_w.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        top_k = min(top_k, logits.shape[-1])
        if kind == "softmax":
            vals, idx = jax.lax.top_k(logits, top_k)
            return idx, jax.nn.softmax(vals, axis=-1)
        if kind == "sigmoid_bias":
            s = jax.nn.sigmoid(logits)
        elif kind in ("softmax_bias", "mlp"):
            s = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"unknown router kind {kind!r}")
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        if norm:
            w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return (idx, w * scale, *state)


def routing_stats(idx, n_experts: int, valid=None, first: int = 0,
                  held=None):
    """``(E + 2,)`` int32 counters of one routing: assignments per expert
    (every column of the router), then the number of experts HELD HERE
    (``first .. first + held``; all of them by default) with at least one
    row, which is what the step reads of the routed weights, then 1 if any
    row was valid (so that sums over steps count the steps that had work).
    ``valid (N,)`` masks rows that carry no token."""
    hot = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32).sum(axis=1)
    if valid is not None:
        hot = hot * valid.astype(jnp.int32)[:, None]
    counts = hot.sum(axis=0)
    if held is None:
        hit = (counts > 0).sum()
        work = hit > 0
    else:       # a step whose rows all chose experts held elsewhere had work
        hit = (counts[first:first + held] > 0).sum()
        work = counts.sum() > 0
    return jnp.concatenate([counts, hit[None], work[None]]).astype(jnp.int32)


def expert_ffn(x, idx, weights, w_in, w_out, act: str = "swiglu",
               compute_dtype=jnp.float32, first=0):
    """Exact expert FFN of the assignments ``(idx, weights)`` (N, k) over
    the experts held here: ``w_in (E_here, D, F or 2F)``, ``w_out (E_here,
    F, D)`` are experts ``first .. first + E_here`` of the layer
    (``first`` may be traced).  ``act="swiglu"``: ``w_in`` is ``[gate |
    up]`` and the hidden is ``silu(gate) * up``; ``"gelu"``: ``gelu(x
    w_in)``; ``"relu2"``: ``relu(x w_in)^2`` (no gate: two matrices an
    expert).  Returns (N, D) float32: the weighted sum of each row's
    chosen experts that live here."""
    from tpulab.ops.grouped_matmul import grouped_product
    with jax.named_scope("moe_experts"):
        n, k = idx.shape
        n_here = w_in.shape[0]
        local = idx.reshape(-1) - first
        here = (local >= 0) & (local < n_here)
        # assignments sorted by expert; those of experts held elsewhere go
        # last, past every group, and are weighted 0
        key = jnp.where(here, local, n_here)
        order = jnp.argsort(key)
        sizes = jnp.bincount(key, length=n_here + 1)[:n_here].astype(
            jnp.int32)
        rows = x.astype(compute_dtype)[order // k]
        h = grouped_product(rows, w_in.astype(compute_dtype), sizes)
        if act == "swiglu":
            f = h.shape[-1] // 2
            h = jax.nn.silu(h[:, :f]) * h[:, f:]
        elif act == "gelu":
            h = jax.nn.gelu(h)
        elif act == "relu2":
            h = jnp.square(jax.nn.relu(h))
        else:
            raise ValueError(f"unknown expert activation {act!r}")
        y = grouped_product(h, w_out.astype(compute_dtype), sizes)
        # rows past the last group are not part of any product: drop them
        y = jnp.where(here[order][:, None], y.astype(jnp.float32)
                      * weights.reshape(-1)[order][:, None], 0.0)
        return jnp.zeros((n, y.shape[-1]), jnp.float32).at[order // k].add(y)


def routed_ffn(params: Dict[str, Any], x, top_k: int,
               compute_dtype=jnp.float32, router: str = "softmax",
               act: str = "gelu", scale: float = 1.0, norm: bool = True,
               valid=None, first: int = 0, held=None, zero: int = 0,
               prev=None, eps: float = 1e-6):
    """Route (N, D) rows and run the experts: ``(out (N, D) float32, stats
    (E + 2,))``, and with the ``"mlp"`` router ``(out, stats, state)``:
    what :func:`route` hands on, ``prev`` and ``eps`` being its arguments.
    ``params``: ``router (D, E)`` (the ``"mlp"`` router's leaves), ``bias
    (E,)`` for the routers that choose by it, and the experts as
    ``w13``/``w2`` (SwiGLU) or ``w1``/``w2`` (GELU, relu2).  ``zero``: the router's
    last ``zero`` columns are identity experts, the ``E - zero`` before
    them FFN experts.  ``first`` / ``held``: the share of the FFN experts
    whose weights ``params`` holds (all of them by default); the rows are
    routed over all ``E`` columns and the output is the part the held
    experts give plus, for every row, the identity part."""
    from tpulab.models.transformer import qmat, weight_shape
    idx, weights, *state = route(params["router"], x, top_k, router,
                                 params.get("bias"), scale, norm, prev, eps)
    w_in = params["w13" if act == "swiglu" else "w1"]
    n_experts = params["bias" if router == "mlp" else "router"].shape[-1]
    if zero and held is None:
        held = n_experts - zero
    if weight_shape(w_in)[0] != (n_experts if held is None else held):
        raise ValueError(f"{weight_shape(w_in)[0]} experts' weights for a "
                         f"share of {held} of the router's {n_experts}")
    # (an identity column's assignment is one to an expert not held here:
    # ``expert_ffn`` leaves it out of every product)
    out = expert_ffn(x, idx, weights, qmat(w_in, compute_dtype),
                     qmat(params["w2"], compute_dtype), act, compute_dtype,
                     first=first)
    if zero:
        with jax.named_scope("moe_zero"):
            out = out + jnp.where(idx >= n_experts - zero, weights, 0.0).sum(
                axis=-1, keepdims=True) * x.astype(jnp.float32)
    return (out, routing_stats(idx, n_experts, valid, first, held), *state)


def moe_ffn(params: Dict[str, Any], x: jnp.ndarray, top_k: int = 2,
            compute_dtype=jnp.float32) -> jnp.ndarray:
    """Single-device MoE FFN of the dry run's transformer ((N, D) -> (N,
    D)): softmax top-k gating, GELU experts."""
    return routed_ffn(params, x, top_k, compute_dtype)[0].astype(
        compute_dtype)


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
        # x and the router replicated: every shard routes over ALL experts
        # and computes the part of the result its own experts give
        n_local = params["w1"].shape[0]
        idx, weights = route(params["router"], x, top_k)
        out = expert_ffn(x, idx, weights, params["w1"], params["w2"], "gelu",
                         compute_dtype,
                         first=jax.lax.axis_index(axis_name) * n_local)
        return jax.lax.psum(out.astype(compute_dtype), axis_name)

    def ffn(sharded_params, x):
        return jax.shard_map(local_ffn, mesh=mesh,
                             in_specs=(param_specs, P()),
                             out_specs=P())(sharded_params, x)

    return ffn, shard_params

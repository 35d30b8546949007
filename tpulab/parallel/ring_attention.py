"""Sequence-parallel attention: ring attention + Ulysses all-to-all.

Long-context inference is first-class in this framework (the reference's
closest analog is its cyclic windowed streaming, SURVEY §2.8/§5; true
sequence parallelism postdates it).  Two standard schemes, both expressed as
``shard_map`` bodies so XLA schedules the collectives on the ICI ring:

- :func:`ring_attention` — K/V blocks rotate around the mesh axis via
  ``ppermute`` while each device keeps its Q block, accumulating softmax
  online (running max / normalizer — the blockwise log-sum-exp trick).
  Memory per chip: O(T/P); communication: P-1 neighbor hops riding ICI.
- :func:`ulysses_attention` — ``all_to_all`` re-shards sequence -> heads,
  each device runs *full-sequence* attention for its head slice, and a
  second ``all_to_all`` restores sequence sharding.  Cheaper compute
  structure when heads >= devices; all-to-all bandwidth-bound otherwise.

Both are drop-in ``attention_fn``s for
:func:`tpulab.models.transformer.transformer_apply`.
"""

from __future__ import annotations

from functools import partial


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -1e30


def _ring_attn_local(q, k, v, axis_name: str, causal: bool):
    """Per-device body: q fixed, k/v rotate (B, T_local, H, D).

    Uses lax.scan so HLO size stays constant as the ring grows (pod-scale
    axes), and skips the attention math for blocks that are entirely in the
    causal future (src > p) — roughly half the steps — while the ppermute
    rotation proceeds regardless.
    """
    b, t_q, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    p = jax.lax.axis_index(axis_name)
    scale = 1.0 / np.sqrt(d)

    qf = q.astype(jnp.float32)
    q_pos = p * t_q + jnp.arange(t_q)                   # global q positions
    perm = [(i, (i + 1) % n) for i in range(n)]
    t_k = k.shape[1]

    def attend(carry_mla, k_blk, v_blk, src):
        m, l, acc = carry_mla
        k_pos = src * t_k + jnp.arange(t_k)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :])   # (t_q, t_k)
            scores = jnp.where(mask[None, None], scores, _NEG)
            pmask = mask[None, None].astype(jnp.float32)
        else:
            pmask = 1.0
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new[..., None]) * pmask
        l = l * alpha + probs.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", probs, v_blk.astype(jnp.float32))
        return m_new, l, acc

    def step(carry, s):
        k_blk, v_blk, m, l, acc = carry
        src = (p - s) % n                               # owner of current block
        if causal:
            # blocks fully in the future contribute nothing — skip the math
            m, l, acc = jax.lax.cond(
                src > p,
                lambda mla: mla,
                lambda mla: attend(mla, k_blk, v_blk, src),
                (m, l, acc))
        else:
            m, l, acc = attend((m, l, acc), k_blk, v_blk, src)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    # mark the accumulators as varying over the mesh axis so both cond
    # branches (skip vs attend) carry the same manual-axes type
    def vary(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    init = (k, v,
            vary(jnp.full((b, h, t_q), _NEG, jnp.float32)),  # running max
            vary(jnp.zeros((b, h, t_q), jnp.float32)),       # normalizer
            vary(jnp.zeros((b, h, t_q, d), jnp.float32)))    # numerator
    (_, _, m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(n))

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention(mesh: Mesh, axis_name: str = "model", causal: bool = True):
    """Build a sequence-parallel attention_fn over ``mesh[axis_name]``.

    Accepts global (B, T, H, D) q/k/v; T must divide by the axis size.
    """
    spec = P(None, axis_name, None, None)

    def attn(q, k, v):
        body = partial(_ring_attn_local, axis_name=axis_name, causal=causal)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
    return attn


def _ulysses_local(q, k, v, axis_name: str, causal: bool):
    """seq-sharded -> all_to_all -> head-sharded full attention -> back."""
    from tpulab.models.transformer import dense_attention

    # (B, T/P, H, D) -> (B, T, H/P, D): split heads across the axis
    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = dense_attention(qh, kh, vh, causal=causal)
    return heads_to_seq(out)


def ulysses_attention(mesh: Mesh, axis_name: str = "model",
                      causal: bool = True):
    """Ulysses-style all-to-all sequence parallelism (heads % axis == 0)."""
    spec = P(None, axis_name, None, None)

    def attn(q, k, v):
        if q.shape[2] % mesh.shape[axis_name]:
            raise ValueError(f"heads {q.shape[2]} not divisible by axis "
                             f"{axis_name}={mesh.shape[axis_name]}")
        body = partial(_ulysses_local, axis_name=axis_name, causal=causal)
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
    return attn

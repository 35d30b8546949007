"""Pallas flash attention for TPU.

Blockwise attention with online softmax: the grid walks (batch*heads,
q_blocks, k_blocks) with only one (block_q, d) Q tile and one (block_k, d)
K/V tile resident in VMEM at a time — O(T) memory instead of the O(T^2)
score matrix, QK^T and PV on MXU-native tiles, and the running
(max, normalizer, accumulator) carried in VMEM scratch across the k steps
(out blocks revisit across the innermost grid dim).

Causal masking skips fully-future K blocks via predication.
``interpret=True`` (automatic off TPU) runs the same kernel on CPU for
hermetic tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 sm_scale: float, causal: bool):
    # tiles: q (1, BQ, D); k/v (1, BK, D); o (1, BQ, D)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    num_k = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # (BQ, D)
        k = k_ref[0].astype(jnp.float32)                     # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = q_pos >= k_pos
            s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[:] = m_new

    if causal:
        # skip K blocks strictly in the future of this Q tile
        pl.when(ik * block_k < (iq + 1) * block_q)(_step)
    else:
        _step()

    @pl.when(ik == num_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_bhd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """(BH, T, D) x3 -> (BH, T, D)."""
    bh, t, d = q.shape
    grid = (bh, t // block_q, t // block_k)
    kernel = functools.partial(_attn_kernel, sm_scale=1.0 / np.sqrt(d),
                               causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running normalizer
            pltpu.VMEM((block_q, d), jnp.float32),    # running numerator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Flash attention over (B, T, H, D) q/k/v (same layout as
    :func:`tpulab.models.transformer.dense_attention`).  Forward only: what
    a serving path runs (the engine's ``prefill_flash``)."""
    b, t, h, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"seq len {t} must divide block sizes "
                         f"({block_q}, {block_k})")
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()

    def to_bhd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    out = _flash_bhd(to_bhd(q), to_bhd(k), to_bhd(v), causal,
                     block_q, block_k, interpret)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def make_flash_attention_fn(causal: bool = True, block_q: int = 128,
                            block_k: int = 128):
    """Drop-in ``attention_fn`` for transformer_apply."""
    def attn(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k)
    return attn

"""The EVA chunk summariser: a finished window's rows as one summary row a chunk.

EVA attention (``tpulab.models.spec`` ``evabyte``) keeps every window of
``eva_window`` positions before the query's own as ``eva_window / eva_chunk``
summary rows.  A chunk ``c`` of ``C`` positions, a head ``h`` of width ``D``,
from the chunk's roped keys ``k_m`` and its values ``v_m`` as the page store
holds them::

    k~_c = sum_m a_m k_m,   a = softmax_m(mu_h . k_m)
    v~_c = sum_m b_m v_m,   b = softmax_m(phi_h . k_m)

both softmaxes in float32, neither logit scaled.  The page size IS the chunk,
so a chunk is one page and a summary one row: :func:`summarize_chunks` reads
``n`` pages of every layer once and returns ``n`` rows a layer; the caller
(:func:`tpulab.engine.paged_steps.paged_eva_compact`) writes them over the
window's first pages.  Memory-bound by design: a window's rows read once, one
row in ``C`` written.

Two forms of the same function:

- ``use_kernel=True``: a Pallas kernel, ``eva_chunk_summary``.  Grid over
  (layer, page); the page ids are prefetched scalars and pick the block of
  the store the pipeline fetches, so nothing is gathered into a copy first.
  With ``D`` = 128 a head of a page is one tile of lanes: a loop over the
  heads takes the two logits by a lane reduction, the softmaxes over the
  chunk's ``C`` sublanes and the two weighted sums, everything on the
  vector unit (as matrix products against a block-diagonal scorer the
  ``C`` = 16 rows would fill an eighth of the MXU's rows, six passes each
  in float32: ~10 x the time the page's bytes take).
- ``use_kernel=False``: the same in ``jax.numpy``, the form the kernel is
  tested against and what the XLA-only plan runs.

``interpret=True`` (automatic off TPU) runs the kernel in the Pallas
interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 32 * 1024 * 1024
#: heads of the kernel's loop written out side by side: each head is a chain
#: of a lane reduction, a softmax over sublanes and a sublane reduction, so
#: several in flight hide one another's latency
_UNROLL = 4


def summary_geometry_error(head_dim: int, chunk: int, page_size: int):
    """Why the kernel cannot serve this geometry, or None: a page is one
    chunk, and a head's columns are whole tiles of 128 lanes."""
    if page_size != chunk:
        return (f"page_size {page_size} is not eva_chunk {chunk}: a chunk's "
                "summary is taken from one page")
    if head_dim % 128:
        return (f"head_dim {head_dim} is not a multiple of 128: a head's "
                "columns of a page row must be whole lane tiles")
    return None


def _pool_weights(logits):
    """Softmax over a chunk's positions (axis 0) of ``(C, 1)`` logits."""
    e = jnp.exp(logits - logits.max(axis=0, keepdims=True))
    return e / e.sum(axis=0, keepdims=True)


def _summary_kernel(_pages, kv_ref, mu_ref, phi_ref, out_ref, *, heads: int,
                    head_dim: int):
    f32 = jnp.float32

    def one_head(h):
        cols = pl.ds(pl.multiple_of(h * head_dim, head_dim), head_dim)
        k = kv_ref[0, 0, 0, :, cols].astype(f32)             # (C, D)
        v = kv_ref[0, 0, 1, :, cols].astype(f32)
        a = _pool_weights((k * mu_ref[0, pl.ds(h, 1), :]).sum(
            axis=1, keepdims=True))
        b = _pool_weights((k * phi_ref[0, pl.ds(h, 1), :]).sum(
            axis=1, keepdims=True))
        out_ref[0, 0, 0:1, cols] = (a * k).sum(axis=0, keepdims=True).astype(
            out_ref.dtype)
        out_ref[0, 0, 1:2, cols] = (b * v).sum(axis=0, keepdims=True).astype(
            out_ref.dtype)

    side = _UNROLL if heads % _UNROLL == 0 else 1

    def some_heads(g, carry):
        for j in range(side):
            one_head(g * side + j)
        return carry

    jax.lax.fori_loop(0, heads // side, some_heads, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _summarize_kernel(kv_pool, pages, mu, phi, interpret: bool):
    layers, _, _, size, width = kv_pool.shape
    heads, head_dim = mu.shape[1:]
    n = pages.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(layers, n),
        in_specs=[
            pl.BlockSpec((1, 1, 2, size, width),
                         lambda l, i, pages: (l, pages[i], 0, 0, 0)),
            pl.BlockSpec((1, heads, head_dim), lambda l, i, pages: (l, 0, 0)),
            pl.BlockSpec((1, heads, head_dim), lambda l, i, pages: (l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 2, width),
                               lambda l, i, pages: (l, i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_summary_kernel, heads=heads, head_dim=head_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((layers, n, 2, width), kv_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="eva_chunk_summary",
    )(pages, kv_pool, mu, phi)


def summarize_chunks(kv_pool, pages, mu, phi, *, use_kernel: bool,
                     interpret: bool | None = None):
    """The summaries of ``n`` chunks, every layer.

    ``kv_pool`` (L, P, 2, S, H * D) the fused page store (axis 2 = K/V),
    whose page size ``S`` is the chunk; ``pages`` (n,) int32 the pages to
    summarise; ``mu``, ``phi`` (L, H, D) float32 the layers' scorers.
    Returns ``(L, n, 2, H * D)`` in the store's dtype: ``[l, i, 0]`` is
    ``k~`` and ``[l, i, 1]`` ``v~`` of page ``pages[i]`` of layer ``l``."""
    f32 = jnp.float32
    mu, phi = mu.astype(f32), phi.astype(f32)
    if use_kernel:
        if interpret is None:
            from tpulab.tpu.platform import pallas_interpret
            interpret = pallas_interpret()
        return _summarize_kernel(kv_pool, pages.astype(jnp.int32), mu, phi,
                                 interpret)
    layers, _, _, size, width = kv_pool.shape
    heads, head_dim = mu.shape[1:]
    rows = kv_pool[:, pages].astype(f32).reshape(
        layers, -1, 2, size, heads, head_dim)
    k, v = rows[:, :, 0], rows[:, :, 1]                     # (L, n, S, H, D)
    # products and sums written out: an einsum at the default precision
    # would round its float32 operands to bf16 on a TPU
    a = jax.nn.softmax((k * mu[:, None, None]).sum(-1, keepdims=True), axis=2)
    b = jax.nn.softmax((k * phi[:, None, None]).sum(-1, keepdims=True),
                       axis=2)
    out = jnp.stack([(a * k).sum(axis=2), (b * v).sum(axis=2)], axis=2)
    return out.reshape(layers, -1, 2, width).astype(kv_pool.dtype)

"""Grouped matrix product: the experts' two projections over rows sorted by
expert.

``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``, the groups
consecutive runs of ``sizes[g]`` rows from row 0 (what
``jax.lax.ragged_dot`` computes).  Rows past the last group (in
``tpulab.parallel.moe.expert_ffn``: assignments to experts held elsewhere)
belong to no product: they are never read and what the output holds there is
garbage the caller drops.

The product is bound by the weights it reads (an expert of a few rows is
megabytes of weights for kilobytes of rows) and, right behind that, by the
passes those weights make through the MXU (a pass of up to 128 rows costs
what the weights' own tiles cost to latch, ``~K N / 100`` ns: half of what
they cost to read), so the kernel is laid out around ONE read and as near
ONE pass as the rows allow of the weights of each group that has rows:

- the grid is ``(N tiles, visits)``; a *visit* is one (group, tile of
  ``tm`` rows) pair in which the group has rows, in row order, listed on the
  device from ``sizes`` (:func:`_visits`; scalar prefetch) and counted
  there, so the grid's extent is the number of visits: **a group without
  rows is never visited and its weights are never read**;
- consecutive visits of one group keep the weight block's index and of one
  row tile the row and output blocks', and Pallas copies a block only where
  its index changes: a group's ``(K, tn)`` weights are read once an N tile
  however many row tiles it straddles, the rows once an N tile, and an
  output tile is written back once, after the last group that has rows in
  it (a visit stores only its own group's rows);
- a visit multiplies its group's rows ``ts`` at a time, from the packed tile
  (16 rows of bfloat16) in which the group's first row of the tile lies: a
  group of up to ``ts - 15`` rows is one pass wherever it starts, and the
  row tile is as tall as VMEM holds (all the rows in a decode step), so few
  groups straddle one and pay a second visit;
- K is not tiled (a tiled K read the rows once a visit and measured 7-15 %
  slower at every shape: ``PERF.md``); float32 accumulation, the output in
  the operands' dtype, as ``ragged_dot`` returns it.

The tiles follow the traced shape and nothing else (:func:`_gmm_plan`).
:func:`grouped_product` is what the expert FFN calls: the kernel where the
plan serves the shape and Mosaic compiles it (on a TPU), ``ragged_dot``
elsewhere; every shape it is traced at is on record
(:func:`traced_products`: ``debug_state()["moe"]["product"]``).
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpulab.ops.ragged_attention import (_LANES, _VMEM_REQUEST_MAX,
                                         _VMEM_SCOPED_DEFAULT)

#: what a grid step may plan (the double-buffered blocks and a pass's
#: product): half of what a kernel may request, Mosaic's temporaries come on
#: top
_TILE_BUDGET = _VMEM_REQUEST_MAX // 2
#: the widest product whose rows are one tile and whose passes are one
#: packed tile: the engine's decode steps are traced at ``lanes x top_k`` =
#: 32 .. 384 rows, a row or two a group
_DECODE_ROWS = 512
#: rows a pass where a group holds tens of rows (a round: 17-68 a group)
_ROUND_PASS = 64
#: the tallest row tile: past a thousand rows a taller one wins under 1 %
#: where every row is in a group and loses 1-3 % where most are past the
#: last (a share of the experts: the tile's rows are read whole)
_TM_MAX = 1024


class GmmPlan(NamedTuple):
    """The tiles of one traced shape (:func:`_gmm_plan`)."""
    tm: int             # rows a tile: the row and output blocks' height
    ts: int             # rows a pass of the weights through the MXU
    tn: int             # columns a weight block (all of K deep)
    vmem_bytes: int


def _sublanes(dtype) -> int:
    """Rows of a packed tile of ``dtype``: 8 of float32, 16 of bfloat16."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _vmem_bytes(tm: int, ts: int, tn: int, k: int, dtype) -> int:
    """What a grid step holds: the row, weight and output blocks twice (the
    pipeline's two buffers) and a pass's float32 product."""
    item = jnp.dtype(dtype).itemsize
    return 2 * (tm * k + k * tn + tm * tn) * item + ts * tn * 4


def _divisors(x: int, unit: int, least: int = 0):
    """The divisors of ``x`` that are multiples of ``unit``, widest first."""
    return [c for c in range(x, max(least, unit) - 1, -unit) if x % c == 0]


def _gmm_plan(rows: int, k: int, n: int, dtype) -> Optional[GmmPlan]:
    """``(tm, ts, tn)`` for ``rows`` sorted rows ``(rows, k) x (groups, k,
    n)``, from the traced shape alone; None where the kernel does not serve
    the shape and ``ragged_dot`` is kept (other than two-byte operands, a
    width that is not whole lanes, rows that are not whole packed tiles).

    ``ts``: a packed tile up to ``_DECODE_ROWS`` rows, ``_ROUND_PASS``
    past them.  ``tm``: the tallest divisor of ``rows`` in whole packed
    tiles up to ``_TM_MAX``; ``tn``: the widest divisor of ``n`` in whole
    lanes that keeps the step within ``_TILE_BUDGET`` (a shorter ``tm``
    where none does)."""
    sub = _sublanes(dtype)
    if (jnp.dtype(dtype).itemsize != 2 or k % _LANES or n % _LANES
            or rows % sub):
        return None
    ts = sub if rows <= _DECODE_ROWS else _ROUND_PASS
    if rows < ts:
        return None
    for tm in (c for c in _divisors(rows, sub, ts) if c <= _TM_MAX):
        for tn in _divisors(n, _LANES):
            need = _vmem_bytes(tm, ts, tn, k, dtype)
            if need <= _TILE_BUDGET:
                return GmmPlan(tm, ts, tn, need)
    return None


def _visits(sizes, m: int, tm: int):
    """The (group, row tile) pairs in which a group has rows, in row order:
    ``(group (V,), tile (V,), starts (G,), ends (G,), count ())`` int32, ``V
    = m / tm + G - 1`` the most there can be (every group but the first
    starting inside a tile); entries past ``count`` repeat valid indices
    and are never run."""
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    # visit v is of the first group whose visits end past it
    group = jnp.minimum((visit_ends[None, :] <= v[:, None]).sum(
        axis=1, dtype=jnp.int32), g - 1)
    tile = first[group] + v - (visit_ends[group] - tiles[group])
    return (group, jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32),
            starts, ends, visit_ends[-1])


def _gmm_kernel(group_ref, tile_ref, start_ref, end_ref, lhs_ref, rhs_ref,
                out_ref, *, tm: int, ts: int):
    v = pl.program_id(1)
    g = group_ref[v]
    base = tile_ref[v] * tm
    # the group's rows inside this tile, and the packed tile its first lies in
    lo = jnp.maximum(start_ref[g], base) - base
    hi = jnp.minimum(end_ref[g], base + tm) - base
    sub = _sublanes(lhs_ref.dtype)
    first = lo // sub * sub

    def one_pass(p, carry):
        off = pl.multiple_of(jnp.minimum(first + p * ts, tm - ts), sub)
        rows = pl.ds(off, ts)
        acc = jnp.dot(lhs_ref[rows, :], rhs_ref[...],
                      preferred_element_type=jnp.float32)
        # only this group's rows: the window's others are other visits'
        # (or, past the last group, nobody's)
        row = off + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        out_ref[rows, :] = jnp.where((row >= lo) & (row < hi),
                                     acc.astype(out_ref.dtype),
                                     out_ref[rows, :])
        return carry

    jax.lax.fori_loop(0, (hi - first + ts - 1) // ts, one_pass, 0)


@functools.partial(jax.jit, static_argnames=("tm", "ts", "tn", "interpret"))
def _gmm_call(lhs, rhs, sizes, *, tm: int, ts: int, tn: int, interpret: bool):
    (m, k), n = lhs.shape, rhs.shape[2]
    group, tile, starts, ends, count = _visits(sizes, m, tm)
    need = _vmem_bytes(tm, ts, tn, k, lhs.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,         # group, tile, starts, ends
        grid=(n // tn, count),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, grp, til, *_: (til[v], 0)),
            pl.BlockSpec((None, k, tn), lambda j, v, grp, *_: (grp[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, grp, til, *_:
                               (til[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, ts=ts),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(max(_VMEM_SCOPED_DEFAULT, need * 3 // 2),
                                 _VMEM_REQUEST_MAX)),
        interpret=interpret,
        name="grouped_matmul",
    )(group, tile, starts, ends, lhs, rhs)


def grouped_matmul(lhs, rhs, sizes, *, tm: int, ts: int, tn: int,
                   interpret: bool | None = None):
    """The kernel: ``lhs (M, K)`` rows sorted by group, ``rhs (G, K, N)``,
    ``sizes (G,)`` integers with ``sum(sizes) <= M`` -> ``(M, N)`` in the
    operands' dtype (float32 accumulation).  ``tm`` rows a tile (a divisor
    of M), ``ts`` of them a pass (whole packed tiles, at most ``tm``),
    ``tn`` columns a weight block (a divisor of N).  Rows past the last
    group are not computed: the output holds garbage there."""
    m, k = lhs.shape
    groups, k2, n = rhs.shape
    sub = _sublanes(lhs.dtype)
    if k != k2 or sizes.shape != (groups,) or lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul: lhs {lhs.shape} {lhs.dtype}, rhs "
                         f"{rhs.shape} {rhs.dtype}, sizes {sizes.shape}")
    if m % tm or n % tn or tm % sub or ts % sub or not 0 < ts <= tm:
        raise ValueError(f"grouped_matmul: tiles ({tm}, {ts}, {tn}) do not "
                         f"fit ({m}, {k}, {n}) in packed tiles of {sub} rows")
    need = _vmem_bytes(tm, ts, tn, k, lhs.dtype)
    if need > _VMEM_REQUEST_MAX:
        raise ValueError(f"grouped_matmul: tiles ({tm}, {ts}, {tn}) hold "
                         f"{need >> 20} MiB of VMEM, over "
                         f"{_VMEM_REQUEST_MAX >> 20}")
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _gmm_call(lhs, rhs, sizes.astype(jnp.int32), tm=tm, ts=ts, tn=tn,
                     interpret=bool(interpret))


#: every shape :func:`grouped_product` was traced at in this process, by
#: ``(rows, k, n, dtype)``: host data, written while a program is traced
_TRACED: Dict[tuple, dict] = {}


def traced_products() -> list:
    """The shapes :func:`grouped_product` was traced at: ``{rows, k, n,
    dtype, tiles: [tm, tk, tn] | None, pass_rows, vmem_bytes}`` each
    (``tk`` is ``k``), ``tiles`` None where ``ragged_dot`` was kept."""
    return [dict(entry) for _, entry in sorted(_TRACED.items())]


def grouped_product(lhs, rhs, sizes):
    """The grouped product of the expert FFN: the kernel under the tiles
    :func:`_gmm_plan` gives the traced shape where Mosaic compiles it,
    ``jax.lax.ragged_dot`` where the plan gives none, off TPU (the
    interpreter is for the kernel's own tests) and under ``shard_map``
    (operands that vary over a mesh axis, ``moe.make_expert_parallel_ffn``:
    not measured on more than one chip)."""
    from tpulab.tpu.platform import pallas_interpret
    (m, k), n = lhs.shape, rhs.shape[2]
    dtype = jnp.dtype(lhs.dtype).name
    sharded = any(jax.typeof(x).vma for x in (lhs, rhs, sizes))
    plan = (None if pallas_interpret() or sharded
            else _gmm_plan(m, k, n, lhs.dtype))
    _TRACED[(m, k, n, dtype)] = {
        "rows": m, "k": k, "n": n, "dtype": dtype,
        "tiles": [plan.tm, k, plan.tn] if plan else None,
        "pass_rows": plan.ts if plan else None,
        "vmem_bytes": plan.vmem_bytes if plan else None}
    if plan is None:
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    return grouped_matmul(lhs, rhs, sizes, tm=plan.tm, ts=plan.ts,
                          tn=plan.tn, interpret=False)

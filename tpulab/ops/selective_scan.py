"""Segmented selective scan (the Mamba-1 recurrence) over a packed round.

A mixed round of the paged engine (:func:`tpulab.engine.paged_steps.
paged_mixed_step`) carries T rows, one a token: the chunks of the lanes that
prefill, one lane after the other, and one row for each lane that decodes.
Each row belongs to a lane's *segment*; the recurrence

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * u_t) (x) B_t
    y_t = h_t . C_t + D * u_t

runs along a segment and starts from that lane's slot of the per-lane state
store (``tpulab.engine.kv_pool.LaneStateStore``), or from zeros where the
segment starts at position 0, whatever the slot holds; the slot is written
from the segment's last row; rows without a token and lanes without a
segment write nothing.

Two forms of the same function:

- :func:`selective_scan` with ``use_kernel=True``: a Pallas kernel.  Grid
  over blocks of channels; a block's state ``(d_state, block)`` stays in
  registers across a loop over the T rows, is loaded where a segment starts
  and stored where it ends; the layer's slice of the state store is read and
  written in place (``input_output_aliases``), block by block, so the store
  is never copied.  Why a kernel: an associative scan over T = 288 rows
  moves ~9 passes of the ``(T, d_state, d_inner)`` products (94 MB a layer
  at Jamba2-3B's widths), and a ``lax.scan`` of 288 steps of tiny
  operations a layer pays a loop turn a row; the work itself is 23.6 M
  ``exp`` and ~0.15 GFLOP a layer.
- ``use_kernel=False``: a plain ``lax.scan`` over the rows, the form the
  kernel is tested against (and what the XLA-only plan runs).

``interpret=True`` (automatic off TPU) runs the kernel in the Pallas
interpreter.  Everything is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bits of a row's flag
ROW_VALID, ROW_START, ROW_ZERO, ROW_END = 1, 2, 4, 8
#: rows are loaded and stored as aligned tiles of this many
_TILE = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def row_flags(row_lane, row_off, q_lens, kv_lens):
    """``(T,)`` int32 flags of a packed round's rows: a row holds a token
    (``ROW_VALID``), is its segment's first (``ROW_START``; from zeros where
    the segment starts at position 0: ``ROW_ZERO``) or last (``ROW_END``)."""
    lane = jnp.maximum(row_lane, 0)
    valid = row_lane >= 0
    start = row_off == 0
    zero = start & (kv_lens[lane] == q_lens[lane])
    end = row_off == q_lens[lane] - 1
    flags = (ROW_VALID + ROW_START * start + ROW_ZERO * zero + ROW_END * end)
    return jnp.where(valid, flags, 0).astype(jnp.int32)


def channel_block(d_inner: int) -> int:
    """Channels a grid step of the kernel holds: the widest of 1024 .. 128
    that divides ``d_inner`` (all of it where none does: the interpreter
    takes any width, Mosaic whole 128-lane tiles only)."""
    for blk in (1024, 512, 256, 128):
        if d_inner % blk == 0:
            return blk
    return d_inner


def scan_geometry_error(d_inner: int, d_state: int) -> str | None:
    """Why Mosaic cannot build the kernel at these widths, or None."""
    if d_inner % 128:
        return (f"d_inner {d_inner} is not a whole number of 128-lane tiles")
    if d_state % 8:
        return f"d_state {d_state} is not a whole number of 8-sublane tiles"
    return None


def _scan_rows(u, dt, b, c, a, d, states, row_lane, flags):
    """The plain form: one ``lax.scan`` step a row, the whole ``states
    (lanes, N, Din)`` in the carry."""
    def step(carry, row):
        states, h = carry
        u_t, dt_t, b_t, c_t, lane, flag = row
        lane = jnp.maximum(lane, 0)
        h = jnp.where((flag & ROW_START) != 0,
                      jnp.where((flag & ROW_ZERO) != 0, 0.0, states[lane]), h)
        h_new = (jnp.exp(dt_t[None, :] * a) * h
                 + (dt_t * u_t)[None, :] * b_t[:, None])
        h = jnp.where((flag & ROW_VALID) != 0, h_new, h)
        y = (h * c_t[:, None]).sum(0) + d * u_t
        # a segment's last row writes its lane's slot; any other row's
        # write is dropped past the end
        slot = jnp.where((flag & ROW_END) != 0, lane, states.shape[0])
        return (states.at[slot].set(h, mode="drop"), h), y

    (states, _), y = jax.lax.scan(
        step, (states, jnp.zeros_like(states[0])),
        (u, dt, b, c, row_lane, flags))
    return y, states


def _scan_kernel(layer_ref, lane_ref, flag_ref, u_ref, dt_ref, b_ref, c_ref,
                 a_ref, d_ref, hin_ref, y_ref, hout_ref, *, n_tiles: int):
    del layer_ref                      # the index maps read it
    hout_ref[...] = hin_ref[...]       # lanes without a segment keep theirs
    a = a_ref[...]                     # (N, blk)
    d = d_ref[...]                     # (1, blk)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_TILE, a.shape[1]), 0)

    def tile(g, h):
        base = pl.multiple_of(g * _TILE, _TILE)
        u8 = u_ref[pl.ds(base, _TILE), :]
        dt8 = dt_ref[pl.ds(base, _TILE), :]
        y8 = jnp.zeros_like(u8)
        for j in range(_TILE):
            t = base + j
            lane = jnp.maximum(lane_ref[t], 0)
            flag = flag_ref[t]
            u_t, dt_t = u8[j:j + 1, :], dt8[j:j + 1, :]
            first = jnp.where((flag & ROW_ZERO) != 0, 0.0, hin_ref[0, lane])
            h = jnp.where((flag & ROW_START) != 0, first, h)
            h_new = jnp.exp(dt_t * a) * h + (dt_t * u_t) * b_ref[t]
            h = jnp.where((flag & ROW_VALID) != 0, h_new, h)
            y_t = jnp.sum(h * c_ref[t], axis=0, keepdims=True) + d * u_t
            y8 = jnp.where(sub == j, y_t, y8)

            @pl.when((flag & ROW_END) != 0)
            def _store(h=h, lane=lane):
                hout_ref[0, lane] = h
        y_ref[pl.ds(base, _TILE), :] = y8
        return h

    jax.lax.fori_loop(0, n_tiles, tile, jnp.zeros(a.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(u, dt, b, c, a, d, ssm, layer, row_lane, flags,
               interpret: bool):
    t, d_inner = u.shape
    n = a.shape[0]
    lanes = ssm.shape[1]
    if not interpret:
        err = scan_geometry_error(d_inner, n)
        if err:
            raise ValueError(f"selective_scan: {err}")
    blk = channel_block(d_inner)
    pad = -t % _TILE
    if pad:                            # whole tiles of rows; the pad is dead
        u, dt, b, c = (jnp.pad(x, ((0, pad), (0, 0))) for x in (u, dt, b, c))
        row_lane = jnp.pad(row_lane, (0, pad), constant_values=-1)
        flags = jnp.pad(flags, (0, pad))
    tp = t + pad
    rows = lambda j, *_: (0, j)                       # noqa: E731
    whole = lambda j, *_: (0, 0, 0)                   # noqa: E731
    state = lambda j, layer, *_: (layer[0], 0, 0, j)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,         # layer, row_lane, flags
        grid=(d_inner // blk,),
        in_specs=[
            pl.BlockSpec((tp, blk), rows),            # u
            pl.BlockSpec((tp, blk), rows),            # dt
            pl.BlockSpec((tp, n, 1), whole),          # B, a column a row
            pl.BlockSpec((tp, n, 1), whole),          # C
            pl.BlockSpec((n, blk), rows),             # A
            pl.BlockSpec((1, blk), rows),             # D
            pl.BlockSpec((1, lanes, n, blk), state),  # the layer's states
        ],
        out_specs=[pl.BlockSpec((tp, blk), rows),
                   pl.BlockSpec((1, lanes, n, blk), state)],
    )
    y, ssm = pl.pallas_call(
        functools.partial(_scan_kernel, n_tiles=tp // _TILE),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tp, d_inner), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        # operand 9 (the state store, behind three prefetched scalars) is
        # output 1: the layer's blocks are rewritten in place, the other
        # layers never move
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="selective_scan",
    )(layer, row_lane, flags, u, dt, b[..., None], c[..., None], a,
      d[None, :], ssm)
    return y[:t], ssm


def selective_scan(u, dt, b, c, a, d, ssm, layer: int, row_lane, flags, *,
                   use_kernel: bool, interpret: bool | None = None):
    """The segmented scan of one layer over a packed round.

    ``u``, ``dt`` (T, Din) the convolved input and the step size; ``b``,
    ``c`` (T, N); ``a`` (N, Din) the (negative) state matrix, ``d`` (Din,);
    ``ssm`` (L, lanes, N, Din) float32 the state store, of which layer
    ``layer`` is read and written; ``row_lane`` (T,) each row's lane (-1:
    no token), ``flags`` (T,) from :func:`row_flags`.  Returns ``(y (T,
    Din) float32, ssm)``; rows without a token give garbage the caller
    masks or never reads."""
    f32 = jnp.float32
    u, dt, b, c, a, d = (x.astype(f32) for x in (u, dt, b, c, a, d))
    if not use_kernel:
        y, states = _scan_rows(u, dt, b, c, a, d, ssm[layer], row_lane, flags)
        return y, ssm.at[layer].set(states)
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _scan_call(u, dt, b, c, a, d, ssm,
                      jnp.asarray(layer, jnp.int32).reshape(1), row_lane,
                      flags, interpret=interpret)

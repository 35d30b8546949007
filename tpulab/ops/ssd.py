"""Segmented state-space duality (the Mamba-2 recurrence) over a packed round.

A head keeps a matrix-valued state ``S (P, N)`` in float32 a lane
(``tpulab.engine.kv_pool.LaneStateStore``, kind ``"mamba2"``: ``P`` the
head's channels, ``N`` the state's width); a token with input ``x (P)``, step
``dt > 0``, the head's decay rate ``a < 0`` and its GROUP's ``B``, ``C (N)``
does

    S <- exp(dt a) S + (dt x) (x) B;   y = S C + D x

which is Mamba-1's selective scan (:mod:`tpulab.ops.selective_scan`) with ONE
decay a head where that has one a channel-state, and the gated delta rule
(:mod:`tpulab.ops.gated_delta_rule`) without the delta correction.  The
recurrence runs along a lane's *segment* of a mixed round under the one rule
of those two modules, whose row flags it shares: a segment starts from its
lane's slot, or from zeros where it starts at position 0 whatever the slot
holds; the slot is written from the segment's last row; rows without a token
and lanes without a segment write nothing.

Three forms of the same function, all float32:

- :func:`ssd_step`: one token a lane on states handed to it, in XLA: the
  definition.  :func:`one_token_ssd` runs it on the state store, one row a
  lane (a decode step, and the decode rows of a round).  With
  ``use_kernel=True`` as a Pallas kernel named ``ssd_step``
  (``gated_delta_step``'s construction): a grid over the lanes that hold a
  row (a prefetched visit list; a lane without a row is never visited), a
  lane's slot of the aliased store loaded once and stored once, the readout
  and the update from the one loaded block.  Without, the XLA form reads
  the layer TWICE (XLA computes the readout and the update in a fusion
  each, both from the old state), writes it once, and inside a decode
  block's scan over 52 layers copied the whole store a step (1.6 GB of
  temporaries: the compiled K = 2 block for a described v5e, PR 60).
- :func:`ssd_rows`: a plain ``lax.scan`` over a round's rows, the form the
  chunked one is tested against.
- :func:`chunk_ssd`: the chunked form (arXiv:2405.21060).  The rows are cut
  into aligned chunks of ``chunk`` (the published ``chunk_size``, 128).
  What a row takes from the rows of its own segment INSIDE its chunk is one
  masked matrix product a chunk a head, every chunk at once: ``y = ((C B^T)
  * L) (dt x)`` with ``L_ij = exp(G_i - G_j)`` for ``j <= i`` of the same
  lane, ``G`` the running sum of ``dt a`` inside the chunk (a lane's rows in
  a chunk are one run, so the running sum over the chunk's rows is the
  segment's between any two of them).  What it takes from BEFORE its chunk
  is carried by a loop over the *passes*, a pass being the part of one chunk
  that belongs to one lane (a chunk that holds the end of one segment and
  the start of the next makes two), in row order: ``y += exp(G) (C S)``,
  ``S <- exp(G_last) S + (dt x exp(G_last - G))^T B``, the state from the
  lane's slot (or zeros) where a segment starts, carried where it goes on
  into the next chunk, and written to the slot where it ends.  The loop
  carries the whole store and touches the slots of the round's segments and
  no other; a round of 512 rows in one segment is 4 passes of two matrix
  products a group, where the row-by-row scan is 512 steps of elementwise
  work over ``H x P x N`` values.

Products run at ``HIGHEST`` (a float32 product XLA hands to the MXU runs in
bf16 passes otherwise, and the state is what a long context accumulates its
error in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpulab.ops.selective_scan import (ROW_END, ROW_START, ROW_VALID,
                                       ROW_ZERO)

_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


def step_geometry_error(head_dim: int, state: int) -> str | None:
    """Why Mosaic cannot build the one-token kernel at these widths, or
    None."""
    if state % 128 or head_dim % 8:
        return (f"a head's state ({head_dim}, {state}) is not whole (8, 128) "
                "tiles")
    return None


def ssd_step(x, dt, a, b, c, d, s0):
    """One token a lane: ``x (B, H, P)``, ``dt (B, H)``, ``a``, ``d (H,)``,
    ``b``, ``c (B, G, N)`` (head ``j`` uses group ``j // (H / G)``), ``s0 (B,
    H, P, N)``, all float32, to ``(y (B, H, P), s)``.  Sums on the VPU, not
    ``einsum``: a float32 product that XLA hands to the MXU runs in bf16
    passes."""
    n, h, p = x.shape
    g = b.shape[1]
    grouped = lambda t: t.reshape((n, g, h // g) + t.shape[2:])  # noqa: E731
    s = (grouped(jnp.exp(dt * a))[..., None, None] * grouped(s0)
         + grouped(dt[..., None] * x)[..., None] * b[:, :, None, None, :])
    y = (s * c[:, :, None, None, :]).sum(-1).reshape(n, h, p)
    return y + d[:, None] * x, s.reshape(s0.shape)


def _step_kernel(layer_ref, lanes_ref, n_ref, fresh_ref, decay_ref, xt_ref,
                 b_ref, c_ref, sin_ref, yt_ref, sout_ref):
    """Grid step ``i`` is the ``i``-th lane that holds a row: ``sin_ref`` /
    ``sout_ref (1, 1, H, P, N)`` its slot of the aliased store, ``xt_ref (1,
    P, H)`` its ``dt x`` with a head's channels down the sublanes (as the
    state's rows lie), ``b_ref``, ``c_ref (1, G, N)``, ``yt_ref (1, P, H)``;
    a head's ``exp(dt a)`` is a scalar in SMEM.  Steps past the count repeat
    the last lane's blocks (nothing is fetched, the slot is written back
    once) and do nothing."""
    del layer_ref                      # the index maps read it
    i, n = pl.program_id(0), n_ref[0]
    heads, groups = sin_ref.shape[2], b_ref.shape[1]

    @pl.when(i < n)
    def _lane():
        lane = lanes_ref[i]
        fresh = fresh_ref[lane] != 0
        xt, cols = xt_ref[0], []                               # (P, H)
        for g in range(groups):
            b, c = b_ref[0, g:g + 1, :], c_ref[0, g:g + 1, :]      # (1, N)
            for h in range(g * (heads // groups), (g + 1) * (heads // groups)):
                s0 = jnp.where(fresh, 0.0, sin_ref[0, 0, h])       # (P, N)
                s = decay_ref[lane * heads + h] * s0 + xt[:, h:h + 1] * b
                sout_ref[0, 0, h] = s
                cols.append((s * c).sum(-1, keepdims=True))        # (P, 1)
        yt_ref[0] = jnp.concatenate(cols, axis=1)

    @pl.when((i == 0) & (n == 0))
    def _no_lane():                    # the one slot the pipeline writes back
        sout_ref[...] = sin_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(x, dt, a, b, c, states, layer, live, fresh, interpret: bool):
    lanes, (_, _, h, p, n) = x.shape[0], states.shape
    g = b.shape[1]
    if not interpret:
        err = step_geometry_error(p, n)
        if err:
            raise ValueError(f"ssd_step: {err}")
    # the lanes that hold a row, in order; past their count the last again
    count = live.sum().astype(jnp.int32)
    visit = jnp.nonzero(live, size=lanes, fill_value=0)[0].astype(jnp.int32)[
        jnp.minimum(jnp.arange(lanes), jnp.maximum(count - 1, 0))]
    row = lambda i, at, visit, *_: (visit[i], 0, 0)               # noqa: E731
    slot = lambda i, at, visit, *_: (at[0], visit[i], 0, 0, 0)    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, the visit list, its count, fresh, and a head's decay
        num_scalar_prefetch=5,
        grid=(lanes,),
        in_specs=[pl.BlockSpec((1, p, h), row),                 # (dt x)^T
                  pl.BlockSpec((1, g, n), row),                 # B
                  pl.BlockSpec((1, g, n), row),                 # C
                  pl.BlockSpec((1, 1, h, p, n), slot)],         # the slot
        out_specs=[pl.BlockSpec((1, p, h), row),
                   pl.BlockSpec((1, 1, h, p, n), slot)],
    )
    yt, states = pl.pallas_call(
        _step_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((lanes, p, h), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 8 (the state store, behind five prefetched scalars) is
        # output 1: a visited lane's slot is rewritten in place, no other
        # slot and no other layer ever moves
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ssd_step",
    )(layer, visit, count[None], fresh.astype(jnp.int32),
      jnp.exp(dt * a).reshape(-1), (dt[..., None] * x).transpose(0, 2, 1),
      b, c, states)
    # a lane without a row was never visited: its output is what the
    # buffer held, which may not be a number
    return jnp.where(live[:, None, None], yt.transpose(0, 2, 1), 0.0), states


def one_token_ssd(x, dt, a, b, c, d, states, layer: int, live, fresh, *,
                  use_kernel: bool = False, interpret: bool | None = None):
    """The recurrence of one layer at one row a lane: row ``b`` is lane
    ``b``'s.  Arguments as :func:`ssd_step`'s; ``states (L, B, H, P, N)``
    float32 the state store; ``live (B,)`` the lanes that hold a row,
    ``fresh (B,)`` those among them at position 0, which start from zeros
    whatever their slot holds.  Returns ``(y (B, H, P) float32, states)``: a
    live lane's slot of layer ``layer`` holds its new state, every other
    slot and layer what it held, bit for bit."""
    if use_kernel:
        if interpret is None:
            from tpulab.tpu.platform import pallas_interpret
            interpret = pallas_interpret()
        y, states = _step_call(x, dt, a, b, c, states,
                               jnp.asarray(layer, jnp.int32).reshape(1), live,
                               fresh, interpret=interpret)
        return y + d[:, None] * x, states
    held = states[layer]
    y, s = ssd_step(x, dt, a, b, c, d,
                    jnp.where(fresh[:, None, None, None], 0.0, held))
    return y, states.at[layer].set(
        jnp.where(live[:, None, None, None], s, held))


def ssd_rows(x, dt, a, b, c, d, states, layer: int, row_lane, flags):
    """The plain form over a round's rows ``x (T, H, P)`` ..: one
    ``lax.scan`` step a row, layer ``layer`` of the store in the carry."""
    def step(carry, row):
        held, s = carry
        x_t, dt_t, b_t, c_t, lane, flag = row
        lane = jnp.maximum(lane, 0)
        s = jnp.where((flag & ROW_START) != 0,
                      jnp.where((flag & ROW_ZERO) != 0, 0.0, held[lane]), s)
        y, s_new = ssd_step(x_t[None], dt_t[None], a, b_t[None], c_t[None],
                            d, s[None])
        s = jnp.where((flag & ROW_VALID) != 0, s_new[0], s)
        slot = jnp.where((flag & ROW_END) != 0, lane, held.shape[0])
        return (held.at[slot].set(s, mode="drop"), s), y[0]

    (held, _), y = jax.lax.scan(
        step, (states[layer], jnp.zeros_like(states[layer, 0])),
        (x, dt, b, c, row_lane, flags))
    return y, states.at[layer].set(held)


def _passes(row_lane, flags, q: int, n_max: int):
    """The passes of :func:`chunk_ssd`, from the rows' lanes and flags (``T``
    a whole number of chunks of ``q``): ``(n, first row (n_max,), flag
    (n_max,))``.  A pass starts where a chunk starts on a live row and where
    a segment starts; it ends its segment (``ROW_END``) where the segment's
    last row lies inside its chunk."""
    t = row_lane.shape[0]
    row = jnp.arange(t, dtype=jnp.int32)
    valid = (flags & ROW_VALID) != 0
    starts = valid & ((row % q == 0) | ((flags & ROW_START) != 0))
    at = jnp.nonzero(starts, size=n_max, fill_value=0)[0].astype(jnp.int32)
    # the segment's last row: the first ROW_END at or after the pass's start
    ends = jnp.where((flags & ROW_END) != 0, row, t)
    last = jax.lax.cummin(ends, reverse=True)[at]
    flag = (flags[at] & (ROW_START | ROW_ZERO)) | jnp.where(
        last < (at // q + 1) * q, ROW_END, 0)
    return starts.sum().astype(jnp.int32), at, flag.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("chunk",))
def chunk_ssd(x, dt, a, b, c, d, states, layer, row_lane, flags, *,
              chunk: int):
    """The segmented recurrence of one layer over a packed round's rows, in
    the chunked form.

    ``x (T, H, P)``, ``dt (T, H)``, ``b``, ``c (T, G, N)``, ``a``, ``d
    (H,)``, all float32; ``states (L, lanes, H, P, N)`` float32 the state
    store, of which layer ``layer`` (a traced scalar: the layers of a
    program trace and lower this ONCE a shape) is read and written;
    ``row_lane (T,)`` each row's lane (-1: no token), ``flags (T,)`` from
    :func:`tpulab.ops.selective_scan.row_flags`.  Returns ``(y (T, H, P)
    float32, states)``; a row without a token gives ``d x`` of what it
    holds, which the caller never reads."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    lanes = states.shape[1]
    q = min(chunk, t)
    pad = -t % q
    if pad:                            # whole chunks of rows; the pad is dead
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
        row_lane = jnp.pad(row_lane, (0, pad), constant_values=-1)
        flags = jnp.pad(flags, (0, pad))
    nc, rep = (t + pad) // q, h // g
    valid = (flags & ROW_VALID) != 0
    lane_c = jnp.where(valid, row_lane, -1).reshape(nc, q)
    # the running log-decay and the weighted input, dead rows taken out
    gsum = jnp.cumsum(jnp.where(valid[:, None], dt * a, 0.0).reshape(
        nc, q, g, rep), axis=1)                                # (nc, q, G, R)
    xdt = jnp.where(valid[:, None, None], dt[..., None] * x, 0.0).reshape(
        nc, q, g, rep, p)
    bc, cc = b.reshape(nc, q, g, n), c.reshape(nc, q, g, n)
    # inside a chunk: row i from rows j <= i of its own lane
    same = ((lane_c[:, :, None] == lane_c[:, None, :])
            & (lane_c >= 0)[:, :, None]
            & jnp.tril(jnp.ones((q, q), bool)))                # (nc, q, q)
    decay = jnp.exp(jnp.minimum(
        gsum[:, :, None] - gsum[:, None, :], 0.0))             # (nc,q,q,G,R)
    scores = jnp.where(same[..., None, None], decay, 0.0) * jnp.einsum(
        "cign,cjgn->cijg", cc, bc, precision=_HI)[..., None]
    y = jnp.einsum("cijgr,cjgrp->cigrp", scores, xdt, precision=_HI)

    # before a chunk: the passes, in row order, the state carried
    n_pass, first, pflag = _passes(row_lane, flags, q, nc + min(lanes, t))

    def one_pass(i, carry):
        y, states, s = carry
        r0, flag = first[i], pflag[i]
        ci, lane = r0 // q, jnp.maximum(row_lane[r0], 0)
        take = lambda v: jax.lax.dynamic_index_in_dim(   # noqa: E731
            v, ci, keepdims=False)
        s = jnp.where(
            (flag & ROW_START) != 0,
            jnp.where((flag & ROW_ZERO) != 0, 0.0, states[layer, lane]
                      ).reshape(g, rep, p, n), s)
        mine = take(lane_c) == lane                            # (q,)
        gc = take(gsum)                                        # (q, G, R)
        # ... from the row before the pass's first (0 at a chunk's start)
        base = jnp.where(r0 % q == 0, 0.0,
                         gc[jnp.maximum(r0 % q - 1, 0)])
        rel = jnp.where(mine[:, None, None], gc - base, 0.0)   # <= 0
        y_in = jnp.exp(rel)[..., None] * jnp.einsum(
            "ign,grpn->igrp", take(cc), s, precision=_HI)
        y = jax.lax.dynamic_update_index_in_dim(
            y, take(y) + jnp.where(mine[:, None, None, None], y_in, 0.0),
            ci, 0)
        g_last = rel.min(axis=0)                               # (G, R)
        w = jnp.where(mine[:, None, None],
                      jnp.exp(jnp.minimum(g_last - rel, 0.0)), 0.0)
        s = jnp.exp(g_last)[..., None, None] * s + jnp.einsum(
            "igrp,ign->grpn", w[..., None] * take(xdt), take(bc),
            precision=_HI)
        slot = jnp.where((flag & ROW_END) != 0, lane, lanes)
        return (y, states.at[layer, slot].set(s.reshape(h, p, n),
                                              mode="drop"), s)

    y, states, _ = jax.lax.fori_loop(
        0, n_pass, one_pass, (y, states, jnp.zeros((g, rep, p, n),
                                                   jnp.float32)))
    return (y.reshape(-1, h, p)[:t] + d[:, None] * x[:t]), states

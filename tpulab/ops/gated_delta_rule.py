"""Segmented gated delta rule (the Gated DeltaNet recurrence) over a packed
round.

A value head keeps a matrix-valued state ``S (d_k, d_v)`` in float32 a lane
(``tpulab.engine.kv_pool.LaneStateStore``, kind ``"gdn"``); a token with
query ``q``, key ``k`` (both of ``d_k``: L2-normalised, the query scaled),
value ``v (d_v)``, log-decay ``g <= 0`` and write strength ``beta`` does

    S <- exp(g) S;   d = beta (v - S^T k);   S <- S + k (x) d;   o = S^T q

The recurrence runs along a lane's *segment* of a mixed round
(:func:`tpulab.engine.paged_steps.paged_mixed_step`) under the one rule of
:mod:`tpulab.ops.selective_scan`, whose row flags it shares: a segment
starts from its lane's slot, or from zeros where it starts at position 0
whatever the slot holds; the slot is written from the segment's last row;
rows without a token and lanes without a segment write nothing.

Three forms of the same function:

- :func:`gated_delta_step`: one token a lane, ``(B, 1)``: a decode step, and
  the decode rows of a round.  Elementwise float32 over the state, which it
  reads twice and writes once (both products with the old state in one
  pass, then the update).
- :func:`chunk_gated_delta_rule` with ``use_kernel=True``: the chunk form
  (arXiv:2412.06464, the WY / UT transform with cumulative log-decays) as a
  Pallas kernel named ``chunk_gated_delta_rule``.  Grid over value heads;
  the rows of a round are cut into aligned chunks of :data:`CHUNK`; a
  *pass* is the part of one chunk that belongs to one lane (a chunk that
  holds the end of one segment and the start of the next makes two), found
  from the row flags outside the kernel; a head's loop runs the live passes
  in row order with the state in registers, loading it where a segment
  starts and storing it where one ends.  Inside a pass every product is a
  matrix product: ``T = (I + tril(beta K K^T * D, -1))^-1`` by doubling
  (``I + A`` with ``A`` nilpotent: ``(I - A)(I + A^2)(I + A^4)...``), ``u =
  T (beta V)``, ``w = T (beta K e^G)``, ``v' = u - w S``, ``o = (Q e^G) S +
  tril(Q K^T * D) v'``, ``S <- e^{G_last} S + (K e^{G_last - G})^T v'``,
  with ``G`` the cumulative log-decay inside the pass and ``D_ij = e^{G_i -
  G_j}``.  A row-by-row scan of 288 rows x 32 heads x 6 layers of a 128 x
  128 state is serial VPU work; this form is ~12 MFLOP a pass a head on the
  MXU.
- ``use_kernel=False``: :func:`_rule_rows`, a plain ``lax.scan`` over the
  rows, the definition the kernel is tested against (and what the XLA-only
  plan runs).

``interpret=True`` (automatic off TPU) runs the kernel in the Pallas
interpreter.  Everything is float32; the kernel's products run at
``HIGHEST`` (the state is what a long context accumulates its error in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpulab.ops.selective_scan import (ROW_END, ROW_START, ROW_VALID,
                                       ROW_ZERO)

#: rows of a chunk: whole (8, 128) tiles both ways round, and log2(CHUNK)
#: doublings invert a chunk's triangular system
CHUNK = 64
_VMEM_LIMIT = 64 * 1024 * 1024


def rule_geometry_error(d_k: int, d_v: int) -> str | None:
    """Why Mosaic cannot build the kernel at these head widths, or None."""
    if d_k % 128 or d_v % 128:
        return (f"head widths d_k {d_k}, d_v {d_v} are not whole 128-lane "
                "tiles")
    return None


def gated_delta_step(q, k, v, g, beta, s0):
    """One token a lane: ``q``, ``k (B, H, d_k)``, ``v (B, H, d_v)``, ``g``,
    ``beta (B, H)``, ``s0 (B, H, d_k, d_v)``, all float32, to ``(o (B, H,
    d_v), s)``.  Sums on the VPU, not ``einsum``: a float32 product that
    XLA hands to the MXU runs in bf16 passes."""
    eg = jnp.exp(g)[..., None]
    ks = (k[..., :, None] * s0).sum(-2) * eg
    qs = (q[..., :, None] * s0).sum(-2) * eg
    d = beta[..., None] * (v - ks)
    o = qs + (q * k).sum(-1, keepdims=True) * d
    return o, eg[..., None] * s0 + k[..., :, None] * d[..., None, :]


def _rule_rows(q, k, v, g, beta, states, row_lane, flags):
    """The plain form: one ``lax.scan`` step a row, the whole ``states
    (lanes, H, d_k, d_v)`` in the carry.  ``q``, ``k (T, H, d_k)``."""
    def step(carry, row):
        states, s = carry
        q_t, k_t, v_t, g_t, b_t, lane, flag = row
        lane = jnp.maximum(lane, 0)
        s = jnp.where((flag & ROW_START) != 0,
                      jnp.where((flag & ROW_ZERO) != 0, 0.0, states[lane]), s)
        o, s_new = gated_delta_step(q_t, k_t, v_t, g_t, b_t, s)
        s = jnp.where((flag & ROW_VALID) != 0, s_new, s)
        slot = jnp.where((flag & ROW_END) != 0, lane, states.shape[0])
        return (states.at[slot].set(s, mode="drop"), s), o

    (states, _), o = jax.lax.scan(
        step, (states, jnp.zeros_like(states[0])),
        (q, k, v, g, beta, row_lane, flags))
    return o, states


def _passes(row_lane, flags, n_max: int):
    """The kernel's passes, from the rows' lanes and flags (``T`` a whole
    number of chunks): ``(n, chunk (n_max,), lane (n_max,), flag (n_max,))``.
    A pass starts where a chunk starts on a live row and where a segment
    starts; it ends (``ROW_END``) where its segment's last row lies inside
    its chunk."""
    t = row_lane.shape[0]
    row = jnp.arange(t, dtype=jnp.int32)
    valid = (flags & ROW_VALID) != 0
    starts = valid & ((row % CHUNK == 0) | ((flags & ROW_START) != 0))
    at = jnp.nonzero(starts, size=n_max, fill_value=0)[0].astype(jnp.int32)
    lane = jnp.maximum(row_lane[at], 0)
    chunk = at // CHUNK
    # the segment's last row: the first ROW_END at or after the pass's start
    ends = jnp.where((flags & ROW_END) != 0, row, t)
    last = jax.lax.cummin(ends, reverse=True)[at]
    flag = (flags[at] & (ROW_START | ROW_ZERO)) | jnp.where(
        last < (chunk + 1) * CHUNK, ROW_END, 0)
    return starts.sum().astype(jnp.int32), chunk, lane, flag.astype(jnp.int32)


def _rule_kernel(layer_ref, n_ref, chunk_ref, lane_ref, flag_ref, q_ref,
                 k_ref, v_ref, cols_ref, grow_ref, lcol_ref, lrow_ref,
                 sin_ref, o_ref, sout_ref):
    del layer_ref                      # the index maps read it
    sout_ref[...] = sin_ref[...]       # lanes without a segment keep theirs
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, preferred_element_type=f32,
                            precision=jax.lax.Precision.HIGHEST)
    dot_nt = functools.partial(                        # a @ b.T
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=f32, precision=jax.lax.Precision.HIGHEST)
    dot_tn = functools.partial(                        # a.T @ b
        jax.lax.dot_general, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=f32, precision=jax.lax.Precision.HIGHEST)
    ii = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    eye = (ii == jj).astype(f32)

    def one_pass(p, s):
        c, lane, flag = chunk_ref[p], lane_ref[p], flag_ref[p]
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        first = jnp.where((flag & ROW_ZERO) != 0, 0.0, sin_ref[0, lane, 0])
        s = jnp.where((flag & ROW_START) != 0, first, s)
        mine = lcol_ref[c] == lane                     # (C, 1) the pass's rows
        both = mine & (lrow_ref[c] == lane)            # (C, C)
        q = jnp.where(mine, q_ref[rows, :], 0.0)
        k = jnp.where(mine, k_ref[rows, :], 0.0)
        v = jnp.where(mine, v_ref[rows, :], 0.0)
        cols = cols_ref[0, c]
        beta, gc = cols[:, 0:1], cols[:, 1:2]          # (C, 1)
        decay = jnp.exp(jnp.minimum(gc - grow_ref[0, c], 0.0))     # (C, C)
        kb = k * beta
        a = jnp.where((ii > jj) & both, dot_nt(kb, k) * decay, 0.0)
        # (I + a)^-1, a strictly lower: (I - a)(I + a^2)(I + a^4)...
        inv, pw = eye - a, dot(a, a)
        for _ in range(CHUNK.bit_length() - 3):
            inv, pw = inv + dot(inv, pw), dot(pw, pw)
        inv = inv + dot(inv, pw)
        eg = jnp.exp(gc)
        v_new = dot(inv, v * beta) - dot(dot(inv, kb * eg), s)
        qk = jnp.where((ii >= jj) & both, dot_nt(q, k) * decay, 0.0)
        o = dot(q * eg, s) + dot(qk, v_new)
        o_ref[rows, :] = jnp.where(mine, o, o_ref[rows, :])
        g_last = jnp.min(jnp.where(mine, gc, 0.0))     # g <= 0: G falls
        s = s * jnp.exp(g_last) + dot_tn(
            k * jnp.exp(jnp.minimum(g_last - gc, 0.0)), v_new)

        @pl.when((flag & ROW_END) != 0)
        def _store():
            sout_ref[0, lane, 0] = s
        return s

    jax.lax.fori_loop(0, n_ref[0], one_pass,
                      jnp.zeros(sin_ref.shape[3:], f32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rule_call(q, k, v, g, beta, states, layer, row_lane, flags,
               interpret: bool):
    t = q.shape[0]
    _, lanes, vh, dk, dv = states.shape
    kh = q.shape[1] // dk
    if not interpret:
        err = rule_geometry_error(dk, dv)
        if err:
            raise ValueError(f"chunk_gated_delta_rule: {err}")
    pad = -t % CHUNK
    if pad:                            # whole chunks of rows; the pad is dead
        q, k, v, g, beta = (jnp.pad(x, ((0, pad), (0, 0)))
                            for x in (q, k, v, g, beta))
        row_lane = jnp.pad(row_lane, (0, pad), constant_values=-1)
        flags = jnp.pad(flags, (0, pad))
    tp = t + pad
    nc = tp // CHUNK
    n, chunk, lane, flag = _passes(row_lane, flags, nc + min(lanes, tp))
    # the cumulative log-decay inside each pass: rows of one lane in one
    # chunk are one run, so "same chunk, same lane, at or before" sums it
    lanes_c = row_lane.reshape(nc, CHUNK)
    live = ((flags & ROW_VALID) != 0).reshape(nc, CHUNK)
    tri = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    same = (lanes_c[:, :, None] == lanes_c[:, None, :]) & tri & live[:, None]
    gsum = jnp.einsum("cij,cjh->hci", same.astype(jnp.float32),
                      g.reshape(nc, CHUNK, vh),
                      precision=jax.lax.Precision.HIGHEST)        # (H, nc, C)
    cols = jnp.stack([beta.T.reshape(vh, nc, CHUNK), gsum], axis=-1)
    head = lambda h, *_: (0, h)                            # noqa: E731
    key_head = lambda h, *_: (0, h // (vh // kh))          # noqa: E731
    per_head = lambda h, *_: (h, 0, 0, 0)                  # noqa: E731
    whole = lambda h, *_: (0, 0, 0)                        # noqa: E731
    state = lambda h, layer, *_: (layer[0], 0, h, 0, 0)    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,         # layer, passes, their chunk/lane/flag
        grid=(vh,),
        in_specs=[
            pl.BlockSpec((tp, dk), key_head),                   # q
            pl.BlockSpec((tp, dk), key_head),                   # k
            pl.BlockSpec((tp, dv), head),                       # v
            pl.BlockSpec((1, nc, CHUNK, 2), per_head),          # beta, G
            pl.BlockSpec((1, nc, 1, CHUNK), per_head),          # G along lanes
            pl.BlockSpec((nc, CHUNK, 1), whole),                # rows' lanes
            pl.BlockSpec((nc, 1, CHUNK), whole),
            pl.BlockSpec((1, lanes, 1, dk, dv), state),         # the states
        ],
        out_specs=[pl.BlockSpec((tp, dv), head),
                   pl.BlockSpec((1, lanes, 1, dk, dv), state)],
    )
    o, states = pl.pallas_call(
        _rule_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tp, vh * dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 12 (the state store, behind five prefetched scalars) is
        # output 1: the layer's blocks are rewritten in place, the other
        # layers never move
        input_output_aliases={12: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="chunk_gated_delta_rule",
    )(layer, n[None], chunk, lane, flag, q, k, v, cols,
      gsum[:, :, None, :], lanes_c[:, :, None], lanes_c[:, None, :], states)
    return o[:t], states


def chunk_gated_delta_rule(q, k, v, g, beta, states, layer: int, row_lane,
                           flags, *, use_kernel: bool,
                           interpret: bool | None = None):
    """The segmented gated delta rule of one layer over a packed round.

    ``q``, ``k (T, Hk * d_k)`` (normalised, the query scaled; value head
    ``j`` uses key head ``j // (H / Hk)``), ``v (T, H * d_v)``, ``g``,
    ``beta (T, H)``; ``states (L, lanes, H, d_k, d_v)`` float32 the state
    store, of which layer ``layer`` is read and written; ``row_lane (T,)``
    each row's lane (-1: no token), ``flags (T,)`` from
    :func:`tpulab.ops.selective_scan.row_flags`.  Returns ``(o (T, H * d_v)
    float32, states)``; rows without a token give garbage the caller masks
    or never reads."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if not use_kernel:
        t, (_, _, vh, dk, dv) = q.shape[0], states.shape
        rep = vh // (q.shape[1] // dk)
        o, new = _rule_rows(
            jnp.repeat(q.reshape(t, -1, dk), rep, axis=1),
            jnp.repeat(k.reshape(t, -1, dk), rep, axis=1),
            v.reshape(t, vh, dv), g, beta, states[layer], row_lane, flags)
        return o.reshape(t, vh * dv), states.at[layer].set(new)
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _rule_call(q, k, v, g, beta, states,
                      jnp.asarray(layer, jnp.int32).reshape(1), row_lane,
                      flags, interpret=interpret)

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

- :func:`gated_delta_step`: one token a lane on states handed to it, in
  XLA: the definition, elementwise float32.  :func:`one_token_gated_delta_rule`
  runs it on the state store, one row a lane: a decode step, and the decode
  rows of a round.  With ``use_kernel=True`` as a Pallas kernel named
  ``gated_delta_step``: a grid over the lanes that hold a row (a prefetched
  visit list; a lane without a row is never visited), a lane's slot of the
  aliased store loaded once and stored once, both products with the old
  state and the update from the one loaded block.  Without, the XLA form
  reads the layer twice, writes it once and merges it back under ``live``.
- :func:`chunk_gated_delta_rule` with ``use_kernel=True``: the chunk form
  (arXiv:2412.06464, the WY / UT transform with cumulative log-decays) as a
  Pallas kernel named ``chunk_gated_delta_rule``.  Grid over value heads;
  the rows of a round are cut into aligned chunks of :data:`CHUNK`; a
  *pass* is the part of one chunk that belongs to one lane (a chunk that
  holds the end of one segment and the start of the next makes two), found
  from the row flags outside the kernel; a head's loop runs the live passes
  in row order with the state in registers, from its lane's slot where a
  segment starts and to it where one ends.  The store stays where it is
  (aliased, never a block of it in VMEM): a slot is one DMA each way, and
  no other slot moves.  Inside a pass every product is a
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


def _step_kernel(layer_ref, lanes_ref, n_ref, fresh_ref, eg_ref, beta_ref,
                 qk_ref, kq_ref, v_ref, sin_ref, o_ref, sout_ref):
    """Grid step ``i`` is the ``i``-th lane that holds a row: ``sin_ref`` /
    ``sout_ref (1, 1, H, d_k, d_v)`` its slot of the aliased store, ``kq_ref
    (1, 2 Hk, d_k)`` its key heads over its query heads, ``v_ref``, ``o_ref
    (1, H, d_v)``; a head's ``exp(g)``, ``beta`` and ``q . k`` are scalars
    in SMEM.  Steps past the count repeat the last lane's blocks (nothing
    is fetched, the slot is written back once) and do nothing."""
    del layer_ref                      # the index maps read it
    i, n = pl.program_id(0), n_ref[0]
    vh, kh = v_ref.shape[1], kq_ref.shape[1] // 2

    @pl.when(i < n)
    def _lane():
        lane = lanes_ref[i]
        fresh = fresh_ref[lane] != 0
        # a head's k and q down the sublanes, as the state's rows lie
        kqt = kq_ref[0].T                              # (d_k, 2 Hk)
        for j in range(kh):
            k, q = kqt[:, j:j + 1], kqt[:, kh + j:kh + j + 1]     # (d_k, 1)
            qk = qk_ref[lane * kh + j]
            for h in range(j * (vh // kh), (j + 1) * (vh // kh)):
                eg, beta = eg_ref[lane * vh + h], beta_ref[lane * vh + h]
                s0 = jnp.where(fresh, 0.0, sin_ref[0, 0, h])
                ks = (k * s0).sum(0, keepdims=True) * eg           # (1, d_v)
                qs = (q * s0).sum(0, keepdims=True) * eg
                d = beta * (v_ref[0, h:h + 1, :] - ks)
                o_ref[0, h:h + 1, :] = qs + qk * d
                sout_ref[0, 0, h] = eg * s0 + k * d

    @pl.when((i == 0) & (n == 0))
    def _no_lane():                    # the one slot the pipeline writes back
        sout_ref[...] = sin_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, g, beta, states, layer, live, fresh, interpret: bool):
    b, (_, _, vh, dk, dv) = q.shape[0], states.shape
    kh = q.shape[1] // dk
    if not interpret:
        err = rule_geometry_error(dk, dv)
        if err:
            raise ValueError(f"gated_delta_step: {err}")
    q, k = q.reshape(b, kh, dk), k.reshape(b, kh, dk)
    # the lanes that hold a row, in order; past their count the last again
    n = live.sum().astype(jnp.int32)
    lanes = jnp.nonzero(live, size=b, fill_value=0)[0].astype(jnp.int32)[
        jnp.minimum(jnp.arange(b), jnp.maximum(n - 1, 0))]
    row = lambda i, at, lanes, *_: (lanes[i], 0, 0)               # noqa: E731
    slot = lambda i, at, lanes, *_: (at[0], lanes[i], 0, 0, 0)    # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, the visit list, its count, fresh, and a head's three scalars
        num_scalar_prefetch=7,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, 2 * kh, dk), row),           # k over q
                  pl.BlockSpec((1, vh, dv), row),               # v
                  pl.BlockSpec((1, 1, vh, dk, dv), slot)],      # the slot
        out_specs=[pl.BlockSpec((1, vh, dv), row),
                   pl.BlockSpec((1, 1, vh, dk, dv), slot)],
    )
    o, states = pl.pallas_call(
        _step_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, vh, dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 9 (the state store, behind seven prefetched scalars) is
        # output 1: a visited lane's slot is rewritten in place, no other
        # slot and no other layer ever moves
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="gated_delta_step",
    )(layer, lanes, n[None], fresh.astype(jnp.int32),
      jnp.exp(g).reshape(-1), beta.reshape(-1), (q * k).sum(-1).reshape(-1),
      jnp.concatenate([k, q], axis=1), v.reshape(b, vh, dv), states)
    # a lane without a row was never visited: its output is what the
    # buffer held, which may not be a number
    return jnp.where(live[:, None], o.reshape(b, vh * dv), 0.0), states


def one_token_gated_delta_rule(q, k, v, g, beta, states, layer: int, live,
                               fresh, *, use_kernel: bool,
                               interpret: bool | None = None):
    """The rule of one layer at one row a lane: row ``b`` is lane ``b``'s.

    ``q``, ``k (B, Hk * d_k)``, ``v (B, H * d_v)``, ``g``, ``beta (B, H)``
    as :func:`chunk_gated_delta_rule` takes a round's rows; ``states (L, B,
    H, d_k, d_v)`` float32 the state store; ``live (B,)`` the lanes that
    hold a row, ``fresh (B,)`` those among them at position 0, which start
    from zeros whatever their slot holds.  Returns ``(o (B, H * d_v)
    float32, states)``: a live lane's slot of layer ``layer`` holds its new
    state, every other slot and layer what it held, bit for bit."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if not use_kernel:
        b, (_, _, vh, dk, dv) = q.shape[0], states.shape
        rep = vh // (q.shape[1] // dk)
        s0 = jnp.where(fresh[:, None, None, None], 0.0, states[layer])
        o, s1 = gated_delta_step(
            jnp.repeat(q.reshape(b, -1, dk), rep, axis=1),
            jnp.repeat(k.reshape(b, -1, dk), rep, axis=1),
            v.reshape(b, vh, dv), g, beta, s0)
        return o.reshape(b, vh * dv), states.at[layer].set(
            jnp.where(live[:, None, None, None], s1, states[layer]))
    if interpret is None:
        from tpulab.tpu.platform import pallas_interpret
        interpret = pallas_interpret()
    return _step_call(q, k, v, g, beta, states,
                      jnp.asarray(layer, jnp.int32).reshape(1), live, fresh,
                      interpret=interpret)


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
                 sin_ref, o_ref, sout_ref, s_in, s_out, sem, stored):
    """One value head's passes.  ``sin_ref`` / ``sout_ref`` are the whole
    store, left where it is (one buffer: aliased), and only the slots of
    the round's segments move, a DMA each way a slot.

    The passes are every head's, so a head knows the next head's loads: it
    starts them (the slots of the passes that start a segment from its
    slot, in pass order, into the other half of ``s_in``) before its own
    loop and finds its own, started a grid step ago, complete: a slot's DMA
    between a pass's products is not hidden by them (2.2 us a head: my
    chip runs, PR 53).  A
    pass that ends a segment copies ``s_out`` to the slot, and nobody waits
    for that until the buffer is needed again, by a later segment or the
    next head (``stored`` in SMEM says so across grid steps; the last head
    waits for the last)."""
    f32 = jnp.float32
    layer, head, heads = layer_ref[0], pl.program_id(0), pl.num_programs(0)
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

    def loads_slot(flag):              # a segment that starts from its slot
        return (flag & (ROW_START | ROW_ZERO)) == ROW_START

    def load(p, h, j):                 # pass p's slot of head h: its j-th
        return pltpu.make_async_copy(sin_ref.at[layer, lane_ref[p], h],
                                     s_in.at[h % 2, j], sem.at[h % 2])

    def store(lane):
        return pltpu.make_async_copy(s_out, sout_ref.at[layer, lane, head],
                                     sem.at[2])

    def each_load(fn):
        def one(p, j):
            need = loads_slot(flag_ref[p])
            pl.when(need)(lambda: fn(p, j))
            return j + need.astype(jnp.int32)
        jax.lax.fori_loop(0, n_ref[0], one, jnp.int32(0))

    @pl.when(head == 0)
    def _first():
        stored[0] = 0
        each_load(lambda p, j: load(p, head, j).start())

    def next_and_mine(p, j):
        pl.when(head + 1 < heads)(lambda: load(p, head + 1, j).start())
        load(p, head, j).wait()
    each_load(next_and_mine)

    def one_pass(p, carry):
        s, j = carry
        c, lane, flag = chunk_ref[p], lane_ref[p], flag_ref[p]
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        # a segment at position 0 starts from zeros whatever its slot holds
        first = jnp.where((flag & ROW_ZERO) != 0, 0.0,
                          s_in[head % 2, jnp.minimum(j, s_in.shape[1] - 1)])
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
            pl.when(stored[0] != 0)(lambda: store(lane).wait())
            s_out[...] = s
            store(lane).start()
            stored[0] = 1
        return s, j + loads_slot(flag).astype(jnp.int32)

    jax.lax.fori_loop(0, n_ref[0], one_pass,
                      (jnp.zeros(s_out.shape, f32), jnp.int32(0)))
    pl.when((head == heads - 1) & (stored[0] != 0))(lambda: store(0).wait())


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
            pl.BlockSpec(memory_space=pl.ANY),                  # the store
        ],
        out_specs=[pl.BlockSpec((tp, dv), head),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            # the slots a head loads, for this head and the next: as many
            # as segments can start in the round
            pltpu.VMEM((2, min(lanes, tp), dk, dv), jnp.float32),
            pltpu.VMEM((dk, dv), jnp.float32),                  # a slot out
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((1,), jnp.int32)],                       # on its way?
    )
    o, states = pl.pallas_call(
        _rule_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tp, vh * dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 12 (the state store, behind five prefetched scalars) is
        # output 1: the segments' slots are rewritten in place, no other
        # slot and no other layer ever moves
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

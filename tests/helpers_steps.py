"""The host's side of a dispatch without a scheduler, for the tests that
call the packed step programs directly: the arguments a test has as arrays
go into the program's ONE buffer as ``ContinuousBatcher`` packs them, and
its ONE result array comes apart again.

``fn`` is :func:`~tpulab.engine.paged_steps.paged_mixed_step` or
:func:`~tpulab.engine.paged_steps.paged_decode_block` with ``lanes``,
``max_pages`` and the model bound (a ``partial`` or a jit of one).
"""

import jax.numpy as jnp
import numpy as np

from tpulab.engine.paged_steps import (dispatch_fields, moe_shape, pack_words,
                                       result_fields, unpack_words)


def _sampling(lanes, temps, seeds):
    return dict(
        temps=(np.zeros((lanes,), np.float32) if temps is None
               else np.asarray(temps, np.float32)),
        seeds=(np.zeros((lanes, 2), np.uint32) if seeds is None
               else np.asarray(seeds, np.uint32)))


def no_carry(lanes):
    """A carry of the right shapes for a dispatch fresh in every lane."""
    return tuple(jnp.zeros((lanes,), t)
                 for t in (jnp.int32, jnp.int32, bool, jnp.int32))


def mixed_step(fn, params, kv, tables, toks, row_lane, row_off, q_lens,
               kv_lens, temps=None, seeds=None, spec=None, carry=None,
               fresh=None, rem=None, stops=None, with_carry=False,
               wtables=None):
    """One round on unpacked arguments: ``(next_tokens, logprobs, last
    logits, kv, *moe)``, the first two and the counters as numpy.
    ``carry`` (with ``fresh`` false in the lanes that take it) is what the
    dispatch before returned; ``with_carry`` puts the round's own carry
    before ``kv`` in what is returned.  ``wtables``: the window group's
    table a lane, for a model with window layers."""
    lanes, max_pages = np.shape(tables)
    fields = dispatch_fields("round", lanes, max_pages, wtables is not None)
    groups = ({} if wtables is None
              else {"wtables": np.asarray(wtables, np.int32)})
    width = dict((name, shape) for name, _t, shape in fields)["stops"][1]
    sent = np.full((lanes, width), -1, np.int32)
    if stops is not None:
        stops = np.asarray(stops, np.int32)
        sent[:, :stops.shape[1]] = stops
    packed = pack_words(fields, dict(
        tables=np.asarray(tables, np.int32),
        q_lens=np.asarray(q_lens, np.int32),
        kv_lens=np.asarray(kv_lens, np.int32),
        fresh=(np.ones((lanes,), bool) if fresh is None
               else np.asarray(fresh, bool)),
        rem=(np.zeros((lanes,), np.int32) if rem is None
             else np.asarray(rem, np.int32)),
        stops=sent,
        rows=np.stack([np.asarray(a, np.int32)
                       for a in (toks, row_lane, row_off)]),
        **groups, **_sampling(lanes, temps, seeds)))
    out, last, *after, kv = fn(params, kv, jnp.asarray(packed),
                               no_carry(lanes) if carry is None
                               else tuple(carry))
    res = unpack_words(result_fields(lanes, moe=moe_shape(spec)),
                       np.asarray(out))
    return (res["tokens"], res["logprobs"], last,
            *([tuple(after)] if with_carry else []), kv,
            *([res["moe"]] if "moe" in res else []))


def decode_block(fn, params, kv, tables, carry, k, stops=None, temps=None,
                 seeds=None, fresh=True, spec=None, wtables=None):
    """One block of ``k`` steps from ``carry = (lengths, tokens, live,
    steps_rem)``: sent in the buffer when ``fresh`` (a chain's first
    block), else passed on as the device arrays the block before returned.
    Returns ``(tokens, logprobs, emitted, carry, kv, *moe)``, the first
    three and the counters as numpy."""
    lanes, max_pages = np.shape(tables)
    zeros = (np.zeros((lanes,), np.int32), np.zeros((lanes,), np.int32),
             np.zeros((lanes,), bool), np.zeros((lanes,), np.int32))
    sent = tuple(np.asarray(c, z.dtype) for c, z in zip(carry, zeros)) \
        if fresh else zeros
    groups = ({} if wtables is None
              else {"wtables": np.asarray(wtables, np.int32)})
    packed = pack_words(dispatch_fields("block", lanes, max_pages,
                                        wtables is not None), dict(
        groups,
        tables=np.asarray(tables, np.int32), lengths=sent[0], tokens=sent[1],
        active=sent[2], rem=sent[3], fresh=np.full((lanes,), fresh),
        stops=(np.full((lanes, 1), -1, np.int32) if stops is None
               else np.asarray(stops, np.int32)),
        **_sampling(lanes, temps, seeds)))
    out, *carry, kv = fn(params, kv, jnp.asarray(packed),
                         tuple(jnp.asarray(z) for z in zeros) if fresh
                         else tuple(carry))
    res = unpack_words(result_fields(lanes, k, moe_shape(spec)),
                       np.asarray(out))
    return (res["tokens"], res["logprobs"], res["emitted"], tuple(carry), kv,
            *([res["moe"]] if "moe" in res else []))

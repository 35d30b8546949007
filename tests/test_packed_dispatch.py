"""A dispatch crosses the host-device boundary once each way.

``pack_words`` / ``unpack_words`` carry every field of every program's buffer
bit for bit, in both directions (the host packs what a program takes apart;
a program packs what the host takes apart); the scheduler makes ONE transfer
in a dispatch and ONE blocking fetch for it, for a chain's first block, a
chained block and a mixed round, whatever the model keeps beside its pages
(``debug_state()["dispatch"]["transfers"]``); a chain's first block and the
blocks behind it are one compiled program.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_glm_moe
import test_jamba
import test_keye_sparse
from helpers_engine import TokenGate
from helpers_steps import decode_block
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.engine.paged_steps import (dispatch_fields, pack_words,
                                       paged_decode_block, result_fields,
                                       unpack_words)
from tpulab.models.spec import (glm4_moe_lite_spec, init_params, jamba_spec,
                                keye_vl2_spec)
from tpulab.models.transformer import init_transformer_params

MAX_PAGES = 6
#: bit patterns a conversion by value would lose: a NaN with a payload, a
#: denormal, minus zero, a temperature that is no short binary fraction
F32_BITS = np.array([0x7FC00123, 0x00000001, 0x80000000, 0x3F333333],
                    np.uint32)
#: 64-bit seeds whose words fill the sign bit and every other bit
SEEDS = [0xFFFFFFFF_80000000, 0x80000001_FFFFFFFF, 0x00000000_00000001,
         0xDEADBEEF_CAFEF00D]


def _bits(x):
    """An array as what a buffer carries of it (a bool as itself)."""
    x = np.asarray(x)
    return x if x.dtype == bool else x.view(np.int32)


def _fill(fields, width, rng):
    """Arrays for ``fields`` (the open field ``width`` wide) that use every
    bit: float32 from :data:`F32_BITS`, seeds as the two words of
    :data:`SEEDS`, masks of both values, ids padded with -1."""
    out = {}
    for name, dtype, shape in fields:
        shape = tuple(width if n < 0 else n for n in shape)
        if dtype == np.float32:
            x = np.resize(F32_BITS, shape).view(np.float32)
        elif dtype == np.uint32:
            words = [(s & 0xFFFFFFFF, s >> 32) for s in SEEDS]
            x = np.resize(np.asarray(words, np.uint32), shape)
        elif dtype == bool:
            x = rng.integers(0, 2, shape).astype(bool)
        else:
            x = rng.integers(-1, 2 ** 31 - 1, shape, dtype=np.int64).astype(
                np.int32)
            x.reshape(-1)[::3] = -1            # a pad, a row without a token
        out[name] = x
    return out


def _assert_same_bits(got, want):
    assert set(got) == set(want)
    for name, x in want.items():
        g = np.asarray(got[name])
        assert g.dtype == x.dtype and g.shape == x.shape, name
        np.testing.assert_array_equal(_bits(g), _bits(x), err_msg=name)


@pytest.mark.parametrize("lanes", [8, 32])
@pytest.mark.parametrize("program,width", [
    ("tick", 0), ("block", 1), ("block", 4), ("spec", 2), ("round", 8 + 32),
    ("round", 256 + 32)])
def test_the_host_packs_what_a_program_takes_apart(program, width, lanes):
    fields = dispatch_fields(program, lanes, MAX_PAGES)
    sent = _fill(fields, width, np.random.default_rng(lanes + width))
    buf = pack_words(fields, sent)
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert isinstance(buf, np.ndarray)
    assert buf.size == sum(x.size for x in sent.values())
    # on the host again, and inside a jitted program by static slices
    _assert_same_bits(unpack_words(fields, buf), sent)
    _assert_same_bits(
        jax.jit(lambda b: unpack_words(fields, b))(jnp.asarray(buf)), sent)


@pytest.mark.parametrize("lanes", [8, 32])
@pytest.mark.parametrize("k,moe,spec", [
    (None, None, False), (None, (3, 10), False), (2, None, False),
    (8, (3, 10), False), (5, None, True)])
def test_a_program_packs_what_the_host_takes_apart(k, moe, spec, lanes):
    fields = result_fields(lanes, k, moe, spec)
    made = _fill(fields, 0, np.random.default_rng(lanes))
    out = jax.jit(lambda a: pack_words(fields, a))(
        {name: jnp.asarray(x) for name, x in made.items()})
    assert out.dtype == jnp.int32 and out.ndim == 1
    _assert_same_bits(unpack_words(fields, np.asarray(out)), made)


def test_a_buffer_that_does_not_fit_its_fields_is_refused():
    fields = dispatch_fields("tick", 4, MAX_PAGES)
    sent = _fill(fields, 0, np.random.default_rng(0))
    with pytest.raises(ValueError, match="lengths"):
        pack_words(fields, dict(sent, lengths=sent["lengths"][:3]))
    with pytest.raises(ValueError, match="temps"):
        pack_words(fields, dict(sent, temps=sent["temps"].astype(np.float64)))
    with pytest.raises(ValueError, match="arrays"):
        pack_words(fields, {k: v for k, v in sent.items() if k != "seeds"})
    with pytest.raises(ValueError, match="words"):
        unpack_words(fields, pack_words(fields, sent)[:-1])


# ------------------------------------------------------------ the programs --

def _dense():
    return None, init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                         n_layers=1, d_ff=64)


def _experts():
    spec = glm4_moe_lite_spec(test_glm_moe.CONFIG)
    return spec, init_params(spec, test_glm_moe.VOCAB, test_glm_moe.D_FF,
                             seed=3, scale=0.1)


def _lane_state():
    spec = jamba_spec(test_jamba.CONFIG)
    return spec, init_params(spec, test_jamba.VOCAB, test_jamba.D_FF, seed=3,
                             scale=0.1)


def _index_rows():
    spec = keye_vl2_spec(test_keye_sparse.CONFIG)
    return spec, init_params(spec, test_keye_sparse.VOCAB, 0, seed=3,
                             scale=0.3)


MODELS = {"dense": _dense, "experts": _experts, "lane-state": _lane_state,
          "index-rows": _index_rows}


def _engine(spec, params, **kw):
    heads = (2, 1) if spec is None else (spec.n_heads, spec.n_layers)
    kw = dict(dict(lanes=3, max_len=96, page_size=8, use_kernel=False,
                   compute_dtype=jnp.float32, prefill_chunk=8), **kw)
    return ContinuousBatcher(params, *heads, spec=spec, **kw)


def _count_transfers(cb):
    """Record ``(what, h2d, d2h)`` around every block dispatch, block
    consume and round of ``cb``: the transfers it made itself (a consume
    enqueues the next block first: that dispatch's are taken off)."""
    seen = []

    def counted(name, label):
        inner = getattr(cb, name)

        def call(*args, **kw):
            before, at = dict(cb.transfers), len(seen)
            out = inner(*args, **kw)
            nested = seen[at:]
            seen.append((label(args, kw), *(
                cb.transfers[way] - before[way]
                - sum(n[i] for n in nested)
                for i, way in ((1, "h2d"), (2, "d2h")))))
            return out
        setattr(cb, name, call)
    counted("_dispatch_block", lambda a, kw: (
        "chained block" if kw.get("carry") is not None else "first block"))
    counted("_dispatch_round", lambda a, kw: (
        "chained round" if kw.get("chain") is not None else "first round"))
    counted("_consume_block", lambda a, kw: "fetch")
    counted("_consume_round", lambda a, kw: "fetch")
    counted("_ragged_round", lambda a, kw: "head")
    return seen


@pytest.mark.parametrize("model", list(MODELS))
def test_one_transfer_a_dispatch_and_one_fetch_for_it(model):
    spec, params = MODELS[model]()
    cb = _engine(spec, params)
    seen = _count_transfers(cb)
    rng = np.random.default_rng(1)
    try:
        with cb._cv:             # both admitted by one pass
            futs = [cb.submit(rng.integers(0, 64, n), steps=40)
                    for n in (19, 11)]
        outs = [f.result(timeout=300) for f in futs]
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert [len(o) for o in outs] == [40, 40]
    kinds, moved = state["kinds"], state["transfers"]
    # every dispatch of the run: one buffer in, one array out
    assert moved == {"h2d": sum(kinds.values()),
                     "d2h": state["decode_host_syncs"]}
    assert state["decode_host_syncs"] == sum(kinds.values())
    # and each kind by itself: a dispatch is one buffer in whether it heads
    # a chain or goes behind an un-fetched predecessor, a consume one array
    # out (the head of a chain does nothing beside its dispatch and consume)
    by_kind = {}
    for what, h2d, d2h in seen:
        by_kind.setdefault(what, set()).add((h2d, d2h))
    assert by_kind.pop("head") == {(0, 0)}
    assert by_kind.pop("first block", {(1, 0)}) == {(1, 0)}
    assert by_kind == {"first round": {(1, 0)}, "chained round": {(1, 0)},
                       "chained block": {(1, 0)}, "fetch": {(0, 1)}}
    assert kinds["mixed"] >= 3 and state["ahead_blocks"] >= 1
    assert state["ahead_rounds"] >= 2


def test_a_host_sampled_lane_pays_its_rows_beside_the_one_fetch():
    """``top_k`` sampling picks on the host: the tick's one buffer and one
    result array, then the lane's index up and its logits row down."""
    spec, params = _dense()
    cb = _engine(spec, params, lanes=2)
    try:
        out = cb.submit(np.arange(5), steps=6, sampling=SamplingParams(
            temperature=0.8, top_k=4, seed=3)).result(timeout=120)
        state = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert len(out) == 6
    dispatches = sum(state["kinds"].values())
    assert state["decode_host_syncs"] == 2 * dispatches
    assert state["transfers"] == {"h2d": 2 * dispatches,
                                  "d2h": 2 * dispatches}


def test_first_and_chained_blocks_are_one_compiled_program():
    """A geometry no other test of this process has, so that the shared
    jit's cache counts this engine's programs alone."""
    spec, params = _dense()
    cb = _engine(spec, params, lanes=5, max_len=136)
    streaming = threading.Event()
    try:
        out = cb.submit(np.arange(9), steps=64,
                        on_token=lambda t, i: streaming.set()).result(
                            timeout=300)
        state = cb.debug_state()["dispatch"]
        sizes = {k: fn._cache_size()
                 for k, fn in cb.programs.blocks.items()}
    finally:
        cb.shutdown()
    assert len(out) == 64 and streaming.is_set()
    assert state["ahead_blocks"] >= 5             # a chain ran
    assert state["kinds"]["decode"] > state["ahead_blocks"]
    assert sizes and set(sizes.values()) == {1}, sizes


def test_first_and_chained_rounds_are_one_compiled_program():
    """A chain's first round (fresh in every lane, beside ``_no_carry``)
    and the rounds behind un-fetched blocks and rounds (decode rows from
    the carry) are one program a width: a geometry no other test of this
    process has, so that the shared jit's cache counts this engine's."""
    spec, params = _dense()
    cb = _engine(spec, params, lanes=4, max_len=120)
    rng = np.random.default_rng(5)
    streaming = TokenGate(4)
    try:
        futs = [cb.submit(np.arange(7), steps=50, on_token=streaming)]
        assert streaming.wait(60)
        # prompts of 8 + 8 + 8 + 3 tokens: rounds of width 8, 8, 8 and 4
        futs += [cb.submit(rng.integers(0, 64, 27), steps=4)
                 for _ in range(3)]
        streaming.release()
        for f in futs:
            f.result(timeout=300)
        state = cb.debug_state()["dispatch"]
        size = cb.programs.mixed._cache_size()
    finally:
        cb.shutdown()
    assert state["kinds"]["mixed"] > state["ahead_rounds"] >= 4
    assert state["mixed_decode_rows"] >= 4
    assert size == 2, size                  # widths 8 and 4, chained or not


def test_a_chained_block_takes_its_state_from_the_carry_alone():
    """Two blocks of K = 2 on a dense model: the second from the first's
    carry, with a buffer that says nothing of lengths, tokens or budget,
    equals the second sent ``fresh`` with that state read back."""
    spec, params = _dense()
    lanes, mp = 3, 4
    block = jax.jit(lambda *a: paged_decode_block(
        *a, lanes=lanes, max_pages=mp, k=2, n_heads=2, n_layers=1,
        compute_dtype=jnp.float32))
    tables = 1 + np.arange(lanes * mp, dtype=np.int32).reshape(lanes, mp)
    kv = jnp.zeros((1, 1 + lanes * mp, 2, 8, 32), jnp.float32)
    first = ([0, 3, 0], [7, 9, 0], [True, True, False], [8, 3, 0])
    temps = [0.0, 0.9, 0.0]
    seeds = [[0, 0], [SEEDS[0] & 0xFFFFFFFF, SEEDS[0] >> 32], [0, 0]]
    kw = dict(temps=temps, seeds=seeds, stops=[[-1], [-1], [-1]])
    _t, _l, _e, carry, kv1 = decode_block(block, params, kv, tables, first,
                                          2, **kw)
    chained = decode_block(block, params, kv1, tables, carry, 2, fresh=False,
                           **kw)
    resent = decode_block(block, params, kv1, tables,
                          [np.asarray(c) for c in carry], 2, **kw)
    for got, want in zip(chained[:3], resent[:3]):
        np.testing.assert_array_equal(_bits(got), _bits(want))
    for got, want in zip(chained[3], resent[3]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # lane 1 had three steps: one left for the second block, lane 2 never ran
    np.testing.assert_array_equal(chained[2], [[True, True], [True, False],
                                               [False, False]])
    np.testing.assert_array_equal(np.asarray(chained[4]),
                                  np.asarray(resent[4]))

"""A round's chunk rows reach the K/V walk one chunk LANE at a time (PR 59).

``paged_steps._kv_walk`` is the one walk over K/V pages of the plain GQA
layers (dense, EVA, CCA, window and full layers) and of the gated attention
layers: in a packed round it calls the attention once a lane that holds a
chunk, on a slice of the round's rows (``_chunk_lanes``), where it used to
gather the chunk rows into the padded ``(lanes, M)`` form, make one call over
every lane and gather the rows back.  Here every kind that takes the walk is
held to that padded form, written out again (``_spread_walk``): the same
round through both, on a page store (and a lane state) filled with random
numbers, gives the same last-row logits and writes the same K/V rows (a
layer's rows are a function of EVERY row the layer before it attended for),
and the walk alone gives every row what the padded form gives it.
"""

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_evabyte
import test_mellum
import test_qwen3_next
import test_zaya
from helpers_steps import mixed_step
from tpulab.engine import paged_steps
from tpulab.engine.kv_pool import LaneStateStore, PagedKVPool
from tpulab.engine.paged_steps import pack_round, paged_mixed_step
from tpulab.models.spec import (evabyte_spec, init_params, mellum_spec,
                                qwen3_next_spec, zaya_spec)
from tpulab.models.transformer import init_transformer_params
from tpulab.ops import ragged_attention as ra

LANES, MAX_ROWS, VOCAB = 4, 96, 50
i32 = lambda x: jnp.asarray(x, jnp.int32)      # noqa: E731


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The walk's key block at 32 rows, so that a tiny lane crosses several
    (and a window layer's walk starts past the first)."""
    monkeypatch.setattr(ra, "_TARGET_BLOCK_ROWS", 32)


#: the tree's walk, whatever ``paged_steps._kv_walk`` is patched to meanwhile
_LANE_WALK = paged_steps._kv_walk


def _spread_walk(q, pos, kv_pool, at, tables, seg, compute_dtype, window=0):
    """The padded form the lane loop replaced: a packed round's chunk rows
    gathered into ``(B, M)``, ONE call over every lane, and gathered back
    (``_segment_calls`` / ``_segment_rows``, which the latent attention
    under its limit still takes); each call is ``_kv_walk``'s own of a
    padded form."""
    if seg.get("rows") is None:
        return _LANE_WALK(q, pos, kv_pool, at, tables, seg, compute_dtype,
                          window)
    one = {k: v for k, v in seg.items() if k not in ("rows", "row_seg")}
    return paged_steps._segment_rows([
        _LANE_WALK(qq, qpos, kv_pool, at, tables,
                   dict(one, q_lens=q_lens, qpos=qpos), compute_dtype, window)
        for qq, q_lens, qpos in paged_steps._segment_calls(q, pos, seg)], seg)


@lru_cache(maxsize=None)
def _family(kind):
    """``(spec, params, step keywords, page size)`` of a kind's tiny model:
    the widths its own test file serves."""
    if kind == "dense":
        params = init_transformer_params(
            vocab=VOCAB, d_model=64, n_heads=4, n_layers=3, d_ff=64,
            n_kv_heads=2, ffn="swiglu", tie_embeddings=False)
        return None, params, dict(n_heads=4, n_kv_heads=2, n_layers=3,
                                  rope_theta=10000.0), 8
    make, mod, d_ff, page = {
        "evabyte-eva": (evabyte_spec, test_evabyte, test_evabyte.D_FF,
                        test_evabyte.CHUNK),
        "zaya": (zaya_spec, test_zaya, 0, 8),
        "mellum": (mellum_spec, test_mellum, 0, 8),
        "qwen3next-gdn": (qwen3_next_spec, test_qwen3_next, 0, 8)}[kind]
    spec = make(mod.CONFIG)
    return (spec, init_params(spec, mod.VOCAB, d_ff, seed=3, scale=0.1),
            dict(n_heads=spec.n_heads, n_layers=spec.n_layers, spec=spec),
            page)


def _stores(kind, seed=11):
    """``(kv_pool as the kind's step programs take it, tables, wtables)``:
    every lane its own ascending pages, the page store(s) and the lane
    state filled with random numbers: what other sequences left, and the
    keys a lane's context is made of."""
    spec, _params, kw, page = _family(kind)
    mp = MAX_ROWS // page
    heads, dim = ((kw["n_kv_heads"], 16) if spec is None
                  else (spec.n_kv_heads, spec.head_dim))
    groups = ([n for _name, n in spec.page_groups]
              if spec is not None and spec.window else
              [kw["n_layers"] if spec is None
               else len(spec.attention_layers)])
    rng = np.random.default_rng(seed)

    def junk(a):
        return jnp.asarray(rng.normal(0, 0.5, a.shape), a.dtype)
    pools = [junk(PagedKVPool(n_pages=1 + LANES * mp, page_size=page,
                              n_layers=n, n_heads=heads, head_dim=dim,
                              dtype=jnp.float32).kv) for n in groups]
    tables = 1 + np.arange(LANES * mp).reshape(LANES, mp)
    if len(pools) == 2:
        return tuple(pools), i32(tables), i32(tables[::-1].copy())
    if spec is not None and spec.state_layers:
        state = tuple(junk(a) for a in LaneStateStore(
            spec, LANES, jnp.float32).arrays)
        return (pools[0], state), i32(tables), None
    return pools[0], i32(tables), None


#: ``ctx`` the positions a lane holds before the round, ``prefill`` {lane:
#: chunk length} in the order the rows are packed, ``decode`` lanes; every
#: mix fills the same bucket of 16 prompt rows, so a kind compiles one
#: program a form
MIXES = {
    "one-lane": dict(ctx=[9, 14, 21, 7], prefill={2: 13}, decode=[0, 1, 3]),
    "two-lanes": dict(ctx=[17, 6, 0, 30], prefill={3: 9, 0: 5}, decode=[1]),
    "every-lane": dict(ctx=[12, 0, 33, 5], prefill={2: 5, 0: 3, 3: 4, 1: 2},
                       decode=[]),
}
#: EVA windows of 32 positions: lane 2 stands in its second window (its
#: rows are 8 summaries and the window's own), no chunk crosses a window's
#: end
EVA_CTX = {"one-lane": [9, 14, 45, 7], "two-lanes": [17, 6, 0, 40],
           "every-lane": [12, 0, 37, 5]}
KINDS = ("dense", "evabyte-eva", "zaya", "mellum", "qwen3next-gdn")


@lru_cache(maxsize=None)
def _program(kind, use_kernel, form):
    """The kind's mixed round, jitted once a form: ``"lanes"`` is the
    tree's, ``"spread"`` the same program over :func:`_spread_walk`
    (patched in while it is traced: :func:`_run`)."""
    _spec, _params, kw, page = _family(kind)
    return jax.jit(partial(paged_mixed_step, lanes=LANES,
                           max_pages=MAX_ROWS // page,
                           compute_dtype=jnp.float32, use_kernel=use_kernel,
                           **kw))


def _run(kind, use_kernel, form, case, monkeypatch, behind=None):
    """The round ``case`` through the kind's program in ``form``: ``(last
    logits of the lanes that hold a row, the stores' leaves)``.  ``behind``
    {lane: n}: the lane's first ``n`` entries of the window group's table
    are the scratch page's (blocks wholly behind its window)."""
    spec, params, _kw, _page = _family(kind)
    store, tables, wtables = _stores(kind)
    for lane, n in (behind or {}).items():
        wtables = wtables.at[lane, :n].set(0)
    rng = np.random.default_rng(5)
    prefill = {lane: rng.integers(0, VOCAB, c)
               for lane, c in case["prefill"].items()}
    decode = {lane: int(rng.integers(VOCAB)) for lane in case["decode"]}
    toks, row_lane, row_off, q_lens = pack_round(LANES, prefill, decode)
    kv_lens = np.where(q_lens > 0, np.asarray(case["ctx"]) + q_lens, 0)
    with monkeypatch.context() as patch:
        if form == "spread":
            patch.setattr(paged_steps, "_kv_walk", _spread_walk)
        _picks, _lp, last, kv, *_moe = mixed_step(
            _program(kind, use_kernel, form), params, store, tables, toks,
            row_lane, row_off, q_lens, kv_lens, spec=spec, wtables=wtables)
    return (np.asarray(last)[q_lens > 0],
            [np.asarray(a) for a in jax.tree.leaves(kv)])


def _same(got, want, check):
    """Both forms' logits and stores (page 0 is where rows without a token
    land)."""
    check(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        paged = a.ndim == 5
        check(a[:, 1:] if paged else a, b[:, 1:] if paged else b)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("kind", KINDS)
def test_a_rounds_chunk_lanes_are_the_padded_forms_rows(kind, mix, use_kernel,
                                                        monkeypatch):
    """A round that holds the chunks of one, two and every lane (packed in
    another order than the lanes') beside decode rows and idle lanes,
    through the lane loop and through the padded ``(B, M)`` form: the
    gather form to float32's bits, the interpreted kernel within 1e-6 (it
    gave the same bits wherever this was read)."""
    case = dict(MIXES[mix])
    if kind == "evabyte-eva":
        case["ctx"] = EVA_CTX[mix]
    got = _run(kind, use_kernel, "lanes", case, monkeypatch)
    want = _run(kind, use_kernel, "spread", case, monkeypatch)
    assert np.isfinite(got[0]).all()
    _same(got, want,
          partial(np.testing.assert_allclose, rtol=1e-6, atol=1e-6)
          if use_kernel else np.testing.assert_array_equal)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
def test_a_chunk_that_slides_past_the_window(use_kernel, monkeypatch):
    """Mellum's window of 24 keys under a chunk of 30 rows at position 60,
    beside a second chunk lane and a decode row: the chunk's last rows (keys
    66-89) see none of the keys its first row sees (37-60), the window
    layers' walk starts at the SECOND key block (the window table's entries
    of the first are the scratch page's), and both forms give the same
    rows, within 2e-6."""
    spec, _params, _kw, page = _family("mellum")
    assert spec.window == 24 and page == 8
    case = dict(ctx=[60, 7, 19, 0], prefill={0: 30, 2: 2}, decode=[1])
    # lane 0's rows 0-31 (four pages) lie behind its first row's window.
    # (At 32 rows XLA's CPU dot blocks one lane's product otherwise than
    # four lanes': the gather forms differ in float32's last bit here)
    got, want = (_run("mellum", use_kernel, form, case, monkeypatch,
                      behind={0: 4}) for form in ("lanes", "spread"))
    _same(got, want, partial(np.testing.assert_allclose, rtol=2e-6,
                             atol=2e-6))


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel-interpret"])
@pytest.mark.parametrize("window", [0, 24], ids=["full", "window"])
def test_the_walk_alone_gives_every_row_the_padded_forms(window, use_kernel):
    """``_kv_walk`` on a packed round's queries against ``_spread_walk``,
    row by row: three chunk lanes packed out of lane order (one of them a
    single row), two decode rows, an idle lane; rows that hold no token are
    whatever a form leaves there (``_layer_block`` zeroes them)."""
    lanes, m, h, hkv, d, page, mp = 6, 16, 4, 2, 16, 8, 12
    rng = np.random.default_rng(3)
    kv = jnp.asarray(rng.normal(0, 0.5, (2, 1 + lanes * mp, 2, page,
                                         hkv * d)), jnp.float32)
    tables = i32(1 + rng.permutation(lanes * mp).reshape(lanes, mp))
    ctx = np.asarray([40, 0, 61, 9, 17, 33])
    prefill = {4: 6, 0: 1, 2: 8}
    decode = [3, 5]
    _toks, row_lane, row_off, q_lens = pack_round(
        lanes, {lane: np.zeros(c, np.int32) for lane, c in prefill.items()},
        {lane: 0 for lane in decode})
    kv_lens = np.where(q_lens > 0, ctx + q_lens, 0).astype(np.int32)
    valid = row_lane >= 0
    lane = np.maximum(row_lane, 0)
    back = lane * m + row_off
    spread = np.zeros((lanes * m,), np.int32)
    spread[back[valid]] = np.arange(m + lanes)[valid]
    qpos = (kv_lens - q_lens)[:, None] + np.arange(m)[None]
    decodes = valid[m:]
    seg = dict(tables=tables, q_lens=i32(q_lens), kv_lens=i32(kv_lens),
               use_kernel=use_kernel, kernel_geometry=None, mesh=None,
               row_seg=(i32(row_lane), i32(row_off)),
               rows=(i32(spread), i32(back), i32(qpos),
                     i32(np.where(decodes, 0, q_lens)), i32(decodes)))
    q = jnp.asarray(rng.normal(0, 1, (1, m + lanes, h, d)), jnp.float32)
    got, want = (np.asarray(jax.jit(
        lambda q, kv, walk=walk: walk(q, None, kv, 1, tables, seg,
                                      jnp.float32, window))(q, kv))
        for walk in (paged_steps._kv_walk, _spread_walk))
    assert got.shape == q.shape and valid.sum() == 17
    if use_kernel:
        np.testing.assert_allclose(got[0, valid], want[0, valid], rtol=1e-6,
                                   atol=1e-6)
    else:
        np.testing.assert_array_equal(got[0, valid], want[0, valid])

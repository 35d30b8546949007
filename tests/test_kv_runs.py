"""The page pool hands a lane ascending runs of ids, and the scheduler counts
the key blocks that are one.

``PagedKVPool`` keeps its free ids as address-ordered extents: a grant is
ascending, continues the table it goes behind where ``after + 1`` is free,
and a release coalesces with its neighbours, so that a block of ``g_pages``
adjacent table entries is one DMA in the page walk
(:mod:`tpulab.ops.ragged_attention`).  The allocator is held to a set model
under seeded random traffic; the scheduler's counters to a closed loop that
recycles its pool.
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher, _PagedRequest
from tpulab.models.transformer import init_transformer_params


def _pool(n_pages=64):
    return PagedKVPool(n_pages, 8, 1, 2, 16, jnp.float32)


def _runs(ids):
    """``ids`` cut where the next is not the last plus one."""
    out = []
    for p in ids:
        if out and out[-1][-1] + 1 == p:
            out[-1].append(p)
        else:
            out.append([p])
    return out


def _extents(pool):
    return list(pool._free)


def _check(pool, free, refs):
    """The pool against the model: ``free`` a set of ids, ``refs`` id ->
    count."""
    assert pool.free_pages == len(free)
    ext = _extents(pool)
    assert sorted(free) == [p for lo, hi in ext for p in range(lo, hi)]
    # coalesced: no extent touches the next, none is empty, none holds 0
    assert all(lo < hi for lo, hi in ext) and all(lo >= 1 for lo, _ in ext)
    assert all(a[1] < b[0] for a, b in zip(ext, ext[1:]))
    top = 0
    while pool.n_pages - 1 - top in free:
        top += 1
    assert pool.shrinkable_pages() == top
    for p, n in refs.items():
        assert pool.refcount(p) == n
    assert not free & set(refs)


@pytest.mark.parametrize("seed", range(6))
def test_allocator_against_a_set_model(seed):
    """Seeded random allocate / extend / add_ref / release / grow / shrink:
    every grant ascending runs from free ids, behind ``after`` where ``after
    + 1`` is free, all or nothing; every release coalesced; page 0 never
    granted; ``free_pages`` exact."""
    rng = random.Random(seed)
    pool = _pool(48)
    try:
        free = set(range(1, 48))
        refs = {}
        tables = []                       # lists of ids, as lanes hold them
        for _ in range(400):
            op = rng.choice(["alloc", "alloc", "extend", "extend", "ref",
                             "release", "release", "grow", "shrink"])
            if op in ("alloc", "extend"):
                n = rng.choice([1, 1, 2, 5, 9, 17])
                table = (rng.choice(tables) if op == "extend" and tables
                         else [])
                after = table[-1] if table else 0
                before = _extents(pool)
                got = pool.allocate_pages(n, after)
                if n > len(free):
                    assert got is None and _extents(pool) == before
                    continue
                assert got is not None and len(got) == n == len(set(got))
                assert set(got) <= free and 0 not in got
                if after and after + 1 in free:
                    assert got[0] == after + 1          # the run goes on
                    head = _runs(got)[0]
                    # ... as far as the extent does
                    assert len(head) == n or head[-1] + 1 not in free
                    rest = got[len(head):]
                else:
                    rest = got
                assert rest == sorted(rest)
                # from as few extents as hold it: one where one does
                sizes = sorted((hi - lo for lo, hi in before
                                if lo != after + 1), reverse=True)
                if rest and sizes and sizes[0] >= len(rest):
                    assert len(_runs(rest)) == 1
                free -= set(got)
                refs.update(dict.fromkeys(got, 1))
                if not table:
                    tables.append(table)
                table.extend(got)
            elif op == "ref" and refs:
                p = rng.choice(sorted(refs))
                pool.add_ref(p)
                refs[p] += 1
            elif op == "release" and tables:
                table = tables.pop(rng.randrange(len(tables)))
                pool.release_pages(table)
                for p in table:
                    refs[p] -= 1
                    if not refs[p]:
                        del refs[p]
                        free.add(p)
            elif op == "grow" and pool.n_pages < 80:
                k = rng.choice([1, 4, 8])
                assert pool.grow(k) == k
                free |= set(range(pool.n_pages - k, pool.n_pages))
            elif op == "shrink":
                want = rng.choice([1, 3, 100])
                top = pool.shrinkable_pages()
                dropped = pool.shrink(want)
                assert dropped == min(want, top)
                free -= set(range(pool.n_pages, pool.n_pages + dropped))
            _check(pool, free, refs)
        # what add_ref shared frees with its last release, and only then
        for p in sorted(refs):
            while refs[p]:
                assert pool.refcount(p) == refs[p]
                pool.release_pages([p])
                refs[p] -= 1
            free.add(p)
        _check(pool, free, {})
        assert _extents(pool) == [(1, pool.n_pages)]
    finally:
        pool.close()


def test_a_grant_continues_the_table_and_packs_low():
    pool = _pool(32)
    try:
        a = pool.allocate_pages(4)
        assert a == [1, 2, 3, 4]                      # lowest ids first
        assert pool.allocate_page(after=a[-1]) == 5   # the run goes on
        b = pool.allocate_pages(3)
        assert b == [6, 7, 8]
        # 9 is free, 5 + 1 is not: a's next page is the lowest free id
        assert pool.allocate_page(after=5) == 9
        pool.release_pages(b)
        # the hole holds 3: a grant of 4 skips it whole, a grant of 2 fills it
        assert pool.allocate_pages(4) == [10, 11, 12, 13]
        assert pool.allocate_pages(2) == [6, 7]
        # nothing holds 20 whole: the largest extent first, ids ascending
        assert pool.free_pages == 19
        assert pool.allocate_pages(20) is None and pool.free_pages == 19
        got = pool.allocate_pages(19)
        assert got == [8] + list(range(14, 32))
        assert pool.allocate_page() is None
        pool.release_pages([1, 2, 3, 4, 5, 9] + got + [10, 11, 12, 13, 6, 7])
        assert _extents(pool) == [(1, 32)] and pool.free_pages == 31
        pool.reset()
        assert _extents(pool) == [(1, 32)]
    finally:
        pool.close()


def test_a_request_counts_its_run_blocks_as_its_table_grows():
    req = _PagedRequest(np.zeros(1, np.int32), 1)
    req.pages = [5, 6, 7, 8, 9, 10, 11, 12, 20, 22, 21, 23, 30, 31]
    assert req.run_blocks(4, 0) == 0
    assert req.run_blocks(4, 2) == 2            # 5-8 and 9-12
    assert req.run_blocks(4, 3) == 2            # 20, 22, 21, 23 is none
    req.pages += [32, 33, 40]
    assert req.run_blocks(4, 4) == 3            # 30-33, looked at now
    assert req.walk_runs == (4, 3)
    # a table cut below what was looked at (a compaction, a preemption) is
    # counted again from its start
    req.pages = [7, 8, 9, 10, 2, 1]
    assert req.run_blocks(4, 1) == 1 and req.walk_runs == (1, 1)


def _engine(**kw):
    lm = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64)
    opts = dict(n_heads=2, n_layers=2, lanes=2, max_len=384, page_size=8,
                n_pages=100, compute_dtype=jnp.float32, use_kernel=False)
    opts.update(kw)
    return ContinuousBatcher(lm, **opts)


def test_closed_loop_past_one_recycling_counts_runs_and_keeps_tokens():
    """Two lanes served in a closed loop until the pool's pages have all
    been handed out more than once: ``debug_state()["pool"]`` counts the
    full key blocks the decode rows walked and those that were a run, and
    every stream is the stream of the same prompt served alone on a fresh
    pool (which page ids a lane holds changes no token)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, size=n).astype(np.int32)
               for n in (260, 300, 275, 330, 290, 310, 265, 320)]
    steps = 12
    alone = []
    for p in prompts[:3]:
        cb = _engine()
        try:
            alone.append(cb.submit(p, steps).result(timeout=120))
        finally:
            cb.shutdown()
    cb = _engine()
    try:
        g = cb.debug_state()["pool"]["walk_block_pages"]
        assert g == 32                 # 256 keys a block: 32 pages of 8
        outs = []
        for i in range(0, len(prompts), 2):      # two callers, closed loop
            futs = [cb.submit(p, steps) for p in prompts[i:i + 2]]
            outs += [f.result(timeout=120) for f in futs]
        pool = cb.debug_state()["pool"]
        granted = sum(-(-(len(p) + steps) // 8) for p in prompts)
        assert granted > pool["n_pages"] - 1     # past one recycling
        assert 0 < pool["walk_run_blocks"] <= pool["walk_blocks"]
        assert pool["free_pages"] == pool["n_pages"] - 1
        for got, want in zip(outs, alone):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    finally:
        cb.shutdown()

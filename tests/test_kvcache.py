"""Tiered KV cache tests (tpulab.kvcache): host-tier store semantics,
device<->host swap roundtrips, recompute-free preemption resume,
spill-backed prefix cache, chaos-degraded swaps, admission headroom."""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from helpers_engine import FirstTokenGate
from tpulab import chaos
from tpulab.engine.kv_pool import PagedKVPool, kv_rows_view
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.kvcache import HostKVStore, KVOffloadManager
from tpulab.models.transformer import init_transformer_params, make_generate_fn


@pytest.fixture(scope="module")
def lm():
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)


# -- HostKVStore -------------------------------------------------------------

def test_host_store_roundtrip_bit_exact():
    store = HostKVStore(1 << 20)
    a = np.random.default_rng(0).standard_normal((2, 3, 4)).astype(
        np.float32)
    assert store.put("a", a)
    got = store.get("a")
    np.testing.assert_array_equal(got, a)
    assert got is not a                       # a copy, never the live view
    np.testing.assert_array_equal(store.pop("a"), a)
    assert store.get("a") is None
    assert len(store) == 0 and store.bytes_used == 0


def test_host_store_budget_lru():
    item = np.zeros((1024,), np.float32)      # 4 KiB each
    store = HostKVStore(3 * item.nbytes)
    for k in "abc":
        assert store.put(k, item)
    store.get("a")                            # touch: "b" is now coldest
    assert store.put("d", item)               # budget forces one eviction
    assert "b" not in store
    assert all(k in store for k in "acd")
    assert store.evictions == 1
    assert not store.put("big", np.zeros((4096,), np.float32))  # > budget
    assert store.drops == 1
    assert store.bytes_used <= store.budget_bytes
    store.clear()
    assert store.headroom_bytes == store.budget_bytes


def test_host_store_peek_no_lru_touch():
    """``peek`` (the fabric's export read) returns a copy WITHOUT the
    recency bump: a fetch storm on one entry must not pin it hot and
    evict the owner's own working set, and peeks must not skew the
    hit/miss ratios."""
    item = np.arange(1024, dtype=np.float32)    # 4 KiB each
    store = HostKVStore(3 * item.nbytes)
    for k in "abc":
        assert store.put(k, item + ord(k))
    hits0, misses0 = store.hits, store.misses
    for _ in range(5):                          # a peek storm on "a"
        got = store.peek("a")
        np.testing.assert_array_equal(got, item + ord("a"))
        assert got is not item                  # a copy, never the view
    assert store.peeks == 5
    assert store.hits == hits0 and store.misses == misses0
    assert store.peek("nope") is None           # miss: uncounted either way
    assert store.peeks == 5
    # "a" stayed coldest despite the storm: the next put evicts IT
    assert store.put("d", item)
    assert "a" not in store
    assert all(k in store for k in "bcd")
    # contrast: get DOES touch — "b" survives the next eviction
    store.get("b")
    assert store.put("e", item)
    assert "c" not in store and "b" in store


# -- swap roundtrip ----------------------------------------------------------

def test_swap_out_in_roundtrip_bit_exact():
    """Device pages -> host tier -> (different) device pages is the
    identity on the page payload."""
    pool = PagedKVPool(10, 4, 2, 2, 8, jnp.float32)
    mgr = KVOffloadManager(pool, 8 << 20)
    try:
        src = [pool.allocate_page() for _ in range(3)]
        data = np.random.default_rng(1).standard_normal(
            (2, 3, 2, 4, 2, 8)).astype(np.float32)
        pool.kv = pool.kv.at[:, np.asarray(src)].set(kv_rows_view(data))
        h = mgr.swap_out(src, length=12, kv=pool.kv)
        assert h is not None
        assert h.wait(10)                     # write-behind landed
        pool.release_pages(src)
        dst = [pool.allocate_page() for _ in range(3)]
        new_kv = mgr.restore(h, dst, pool.kv)
        assert new_kv is not None
        pool.kv = new_kv
        np.testing.assert_array_equal(
            np.asarray(pool.kv[:, np.asarray(dst)]), kv_rows_view(data))
        assert mgr.swap_outs == 1 and mgr.swap_ins == 1
        assert mgr.recompute_tokens_saved == 12
        assert len(mgr.store) == 0            # one-shot: restore pops
    finally:
        mgr.close()
        pool.close()


# -- recompute-free preemption ----------------------------------------------

def test_preempt_resume_no_reprefill_token_parity(lm):
    """A preempted-then-resumed request emits tokens identical to an
    unpreempted run while issuing ZERO prefill dispatches for the
    offloaded pages (greedy and seeded-sampled), and pages balance."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    # prompt > page_size so the resume must allocate MULTIPLE pages (the
    # multi-page swap-in path, not just the admission page)
    p_low = np.random.default_rng(21).integers(0, 64, (12,), np.int32)
    p_hi = np.random.default_rng(22).integers(0, 64, (5,), np.int32)

    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1,
                               max_len=64, page_size=8,
                               compute_dtype=jnp.float32)
    try:
        sampled_ref = ref_cb.submit(
            p_low, 10, sampling=SamplingParams(temperature=0.9, seed=123)
        ).result(timeout=120)
    finally:
        ref_cb.shutdown()

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=64,
                           page_size=8, compute_dtype=jnp.float32,
                           kv_offload=32 << 20)
    try:
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 10, on_token=started)
        assert started.wait(timeout=60)
        f_hi = cb.submit(p_hi, 4, priority=10)    # outranks -> preempts
        started.release()
        got_hi = f_hi.result(timeout=120)
        got_low = f_low.result(timeout=120)
        assert cb.preemptions >= 1
        mgr = cb.kv_offload
        assert mgr.swap_outs >= 1 and mgr.swap_ins >= 1
        assert mgr.recompute_tokens_saved >= len(p_low)
        # zero re-prefill: a round a prompt (each fits the budget), and
        # no prompt row computed for the resume
        assert cb.dispatch_kinds["mixed"] == 2
        assert cb.mixed_prompt_tokens == len(p_low) + len(p_hi)
        np.testing.assert_array_equal(
            np.asarray(got_low), np.asarray(dense(p_low[None, :], 10)[0]))
        np.testing.assert_array_equal(
            np.asarray(got_hi), np.asarray(dense(p_hi[None, :], 4)[0]))

        # seeded-sampled victim: the swap restore must not perturb the
        # host PRNG stream either
        started2 = FirstTokenGate()
        pf = cb.dispatch_kinds["mixed"]
        f_s = cb.submit(p_low, 10,
                        sampling=SamplingParams(temperature=0.9, seed=123),
                        on_token=started2)
        assert started2.wait(timeout=60)
        f_hi2 = cb.submit(p_hi, 2, priority=10)
        started2.release()
        f_hi2.result(timeout=120)
        assert list(f_s.result(timeout=120)) == list(sampled_ref)
        assert cb.dispatch_kinds["mixed"] == pf + 2   # still no re-prefill
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


# -- spill-backed prefix cache ----------------------------------------------

def test_demoted_prefix_promotion_hit(lm):
    """A prefix entry evicted under pressure is served from the host tier
    on the next lookup: demote on evict, promote on hit, exact tokens."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    prompt = np.random.default_rng(5).integers(0, 64, (20,), np.int32)
    want = np.asarray(dense(prompt[None, :], 5)[0])
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=64,
                           page_size=8, compute_dtype=jnp.float32,
                           prefix_cache=True, kv_offload=32 << 20)
    try:
        got1 = cb.submit(prompt, 5).result(timeout=120)
        pc, mgr = cb.prefix_cache, cb.kv_offload
        n_cached = len(pc)
        assert n_cached == 2                  # two full prompt pages
        while pc.evict_for_alloc():           # pressure eviction path
            pass
        assert len(pc) == 0
        assert mgr.drain(10)                  # write-behind demotions land
        assert mgr.demotions == n_cached
        got2 = cb.submit(prompt, 5).result(timeout=120)
        assert mgr.promotions == n_cached     # served from the host tier
        assert pc.host_promotions == n_cached
        assert pc.hits >= n_cached            # lookup counted them as hits
        np.testing.assert_array_equal(np.asarray(got1), want)
        np.testing.assert_array_equal(np.asarray(got2), want)
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


# -- chaos degradation -------------------------------------------------------

def _preempt_run(cb, p_low, p_hi):
    started = FirstTokenGate()
    f_low = cb.submit(p_low, 10, on_token=started)
    assert started.wait(timeout=60)
    f_hi = cb.submit(p_hi, 4, priority=10)
    started.release()
    return f_hi.result(timeout=120), f_low.result(timeout=120)


@pytest.mark.parametrize("spec", ["kvcache.swap=error+1",     # swap-out dies
                                  "kvcache.swap=error@1+1"])  # swap-in dies
def test_chaos_swap_degrades_to_recompute(lm, spec):
    """A tripped swap (either side) must fall back to the exact re-prefill
    path: tokens unchanged, lane intact, failure counted — never a
    corrupted lane or a dead request."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    p_low = np.random.default_rng(31).integers(0, 64, (6,), np.int32)
    p_hi = np.random.default_rng(32).integers(0, 64, (5,), np.int32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=64,
                           page_size=8, compute_dtype=jnp.float32,
                           kv_offload=32 << 20)
    try:
        with chaos.inject(spec) as sched:
            got_hi, got_low = _preempt_run(cb, p_low, p_hi)
            assert sched.fired("kvcache.swap") == 1
        assert cb.preemptions >= 1
        assert cb.kv_offload.swap_failures >= 1
        assert cb.kv_offload.swap_ins == 0    # the resume re-prefilled
        # 2 prefills + >= 1 re-prefill, of the prompt and what was generated
        assert cb.dispatch_kinds["mixed"] >= 3
        assert cb.mixed_prompt_tokens > len(p_low) + len(p_hi)
        np.testing.assert_array_equal(
            np.asarray(got_low), np.asarray(dense(p_low[None, :], 10)[0]))
        np.testing.assert_array_equal(
            np.asarray(got_hi), np.asarray(dense(p_hi[None, :], 4)[0]))
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


# -- telemetry + admission headroom -----------------------------------------

def test_kv_tier_metrics_poll():
    pytest.importorskip("prometheus_client")
    from tpulab.utils.metrics import KVTierMetrics

    pool = PagedKVPool(6, 4, 2, 2, 8, jnp.float32)
    m = KVTierMetrics()
    mgr = KVOffloadManager(pool, 8 << 20, metrics=m)
    try:
        src = [pool.allocate_page()]
        h = mgr.swap_out(src, length=4, kv=pool.kv)
        assert h is not None and h.wait(10)
        pool.release_pages(src)
        dst = [pool.allocate_page()]
        pool.kv = mgr.restore(h, dst, pool.kv)
        m.poll(mgr)
        val = m.registry.get_sample_value
        assert val("tpulab_kv_tier_swap_outs_total") == 1
        assert val("tpulab_kv_tier_swap_ins_total") == 1
        assert val("tpulab_kv_tier_recompute_tokens_saved_total") == 4
        assert val("tpulab_kv_tier_swap_out_bytes_total") == \
            mgr.page_nbytes
        assert val("tpulab_kv_tier_swap_out_seconds_count") == 1
        assert val("tpulab_kv_tier_swap_in_seconds_count") == 1
    finally:
        mgr.close()
        pool.close()


def test_admission_counts_host_headroom():
    """Cost-aware admission sees effective capacity = free HBM pages +
    pages the engine could demote to the host tier."""
    from tpulab.serving import AdmissionController

    class _Pool:
        free_pages = 1

    class _Off:
        def __init__(self, extra):
            self._extra = extra

        def demotable_pages(self, prefix_cache):
            return self._extra

    class _Eng:
        pool = _Pool()
        page_size = 8
        lanes = 4
        active_lanes = 0
        queued_requests = 0
        prefix_cache = None

        def __init__(self, extra):
            self.kv_offload = _Off(extra) if extra else None

    # cost 64 tokens = 8 pages; 1 free page is not enough alone
    assert not AdmissionController(load=_Eng(0))._capacity_ok_locked(64)
    assert AdmissionController(load=_Eng(7))._capacity_ok_locked(64)
    assert not AdmissionController(load=_Eng(3))._capacity_ok_locked(64)


# -- HostKVStore edge cases ---------------------------------------------------

def test_host_store_get_result_survives_eviction():
    """A caller still holding a get() result must keep bit-exact data
    after the entry's LRU eviction closes the backing mapping — the
    copy-not-view contract under real eviction pressure."""
    item = np.arange(1024, dtype=np.float32)          # 4 KiB
    store = HostKVStore(2 * item.nbytes)
    assert store.put("a", item)
    held = store.get("a")                             # live result in hand
    # pressure "a" out: two more puts exceed the budget and "a" is LRU'd
    # ("a" was just touched by get, so fill past the WHOLE budget)
    assert store.put("b", item + 1) and store.put("c", item + 2)
    assert "a" not in store and store.evictions >= 1  # mapping is closed
    np.testing.assert_array_equal(held, item)         # still bit-exact
    store.clear()


def test_host_store_oversize_put_does_not_evict_the_world():
    """A payload larger than the ENTIRE budget must drop cleanly: refused
    without evicting a single incumbent entry."""
    item = np.zeros((1024,), np.float32)
    store = HostKVStore(3 * item.nbytes)
    for k in "abc":
        assert store.put(k, item)
    before = store.bytes_used
    assert not store.put("huge", np.zeros((4096,), np.float32))
    assert store.drops == 1 and store.evictions == 0
    assert all(k in store for k in "abc")             # nobody was evicted
    assert store.bytes_used == before
    store.clear()


def test_swap_drop_counted_separately_from_failures():
    """A budget-refused snapshot is a swap_DROP (undersized host budget),
    not a swap_failure (transfer/chaos) — and KVTierMetrics mirrors the
    split."""
    pool = PagedKVPool(6, 4, 2, 2, 8, jnp.float32)
    # budget smaller than one page payload: the write-behind put refuses
    mgr = KVOffloadManager(pool, host_budget_bytes=16)
    try:
        src = [pool.allocate_page()]
        h = mgr.swap_out(src, length=4, kv=pool.kv)
        assert h is not None
        assert not h.wait(10)                 # landed nowhere
        assert mgr.swap_drops == 1 and mgr.swap_failures == 0
        pool.release_pages(src)
        dst = [pool.allocate_page()]
        # the restore then degrades (snapshot unavailable = failure path)
        assert mgr.restore(h, dst, pool.kv) is None
        assert mgr.swap_failures == 1
        try:
            import prometheus_client  # noqa: F401
        except ImportError:
            return
        from tpulab.utils.metrics import KVTierMetrics
        m = KVTierMetrics()
        m.poll(mgr)
        val = m.registry.get_sample_value
        assert val("tpulab_kv_tier_swap_drops_total") == 1
        assert val("tpulab_kv_tier_swap_failures_total") == 1
    finally:
        mgr.close()
        pool.close()

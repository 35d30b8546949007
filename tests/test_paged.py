"""Paged KV cache + continuous batching tests."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from helpers_engine import FirstTokenGate
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.models.transformer import init_transformer_params, make_generate_fn


@pytest.fixture(scope="module")
def lm():
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    return params


def test_paged_matches_dense_generation(lm):
    """Continuous-batched paged decode == dense KV-cache greedy decode."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=64,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        prompts = [np.random.default_rng(s).integers(0, 64, (5,), np.int32)
                   for s in range(3)]
        futs = [cb.submit(p, 7) for p in prompts]
        for p, f in zip(prompts, futs):
            got = f.result(timeout=120)
            want = np.asarray(dense(p[None, :], 7)[0])
            np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()


def test_continuous_admission(lm):
    """More requests than lanes: later requests join as lanes free."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=32,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        prompts = [np.full((3,), i + 1, np.int32) for i in range(5)]
        futs = [cb.submit(p, 4) for p in prompts]
        outs = [f.result(timeout=120) for f in futs]
        # every queued-then-admitted request matches its single-request
        # reference — admission churn must not cross-contaminate lanes
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                np.asarray(o), np.asarray(dense(p[None, :], 4)[0]))
    finally:
        cb.shutdown()


def test_paged_pool_accounting(lm):
    pool = PagedKVPool(n_pages=8, page_size=8, n_layers=2, n_heads=2,
                       head_dim=16, dtype=jnp.float32)
    # page 0 is the reserved scratch page -> 7 allocatable
    pages = [pool.allocate_page() for _ in range(7)]
    assert 0 not in pages  # scratch page never handed out
    assert pool.allocate_page() is None  # exhausted
    pool.release_pages(pages)
    assert pool.free_pages == 7
    pool.reset()
    assert pool.free_pages == 7


def test_prefix_cache_evict_for_alloc_skips_shared(lm):
    """Pool pressure must not wipe cache entries whose pages are still
    shared with active requests (refcount > 1): evicting them frees
    nothing.  Only sole-reference entries fall."""
    from tpulab.engine.kv_pool import PrefixCache
    pool = PagedKVPool(n_pages=8, page_size=8, n_layers=1, n_heads=2,
                       head_dim=16, dtype=jnp.float32)
    cache = PrefixCache(pool)
    shared = pool.allocate_page()
    pool.add_ref(shared)                       # an "active request" ref
    sole = pool.allocate_page()
    cache.insert([b"shared-dig", b"sole-dig"], [shared, sole])
    assert pool.refcount(shared) == 3 and pool.refcount(sole) == 2
    # "sole" page: only the cache + original alloc hold it; release the
    # original so the cache truly holds the last meaningful ref path
    pool.release_pages([sole])
    assert pool.refcount(sole) == 1
    # first evict-for-alloc skips the shared (cold-end) entry, drops sole
    assert cache.evict_for_alloc() is True
    assert pool.refcount(sole) == 0 and pool.refcount(shared) == 3
    # nothing evictable remains -> False, shared entry survives
    assert cache.evict_for_alloc() is False
    assert len(cache) == 1
    cache.clear()
    pool.release_pages([shared, shared])
    assert pool.free_pages == 7


def test_submit_over_capacity_rejected(lm):
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=16,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        with pytest.raises(ValueError, match="max_len"):
            cb.submit(np.zeros(12, np.int32), 8)
    finally:
        cb.shutdown()


def test_paged_kernel_flag_matches_fallback(lm):
    """ContinuousBatcher(use_kernel=True) == XLA-gather fallback."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=32,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                           page_size=8, compute_dtype=jnp.float32,
                           use_kernel=True)
    try:
        p = np.random.default_rng(9).integers(0, 64, (4,), np.int32)
        got = cb.submit(p, 5).result(timeout=120)
        want = np.asarray(dense(p[None, :], 5)[0])
        np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()


def test_fused_prefill_matches_dense(lm):
    """Fused-prefill continuous batching == dense generation, including
    the steps==1 complete-at-prefill edge and long prompts."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=64,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        rng = np.random.default_rng(3)
        for t_prompt, steps in ((1, 3), (8, 1), (17, 6), (30, 4)):
            p = rng.integers(0, 64, (t_prompt,), np.int32)
            got = cb.submit(p, steps).result(timeout=120)
            want = np.asarray(dense(p[None, :], steps)[0])
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"t={t_prompt} s={steps}")
    finally:
        cb.shutdown()


def test_on_token_streaming(lm):
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        streamed = []
        p = np.random.default_rng(4).integers(0, 64, (4,), np.int32)
        fut = cb.submit(p, 6, on_token=lambda tok, i: streamed.append((i, tok)))
        final = fut.result(timeout=120)
        assert [t for _i, t in sorted(streamed)] == list(final)
        assert [i for i, _t in sorted(streamed)] == list(range(6))
    finally:
        cb.shutdown()


def test_generate_rpc_over_continuous_batcher(lm):
    """The Generate RPC can serve straight from the paged batcher: many
    concurrent RPC streams share fused decode ticks."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=4, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=32,
                             compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        import threading
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 64, (5,), np.int32) for _ in range(6)]
        results = [None] * 6

        def gen(i):
            results[i] = list(GenerateStreamClient(remote, "lm").generate(
                prompts[i], 5))

        threads = [threading.Thread(target=gen, args=(i,)) for i in range(6)]
        [t.start() for t in threads]
        [t.join(timeout=180) for t in threads]
        for p, got in zip(prompts, results):
            want = np.asarray(dense(p[None, :], 5)[0])
            np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()


def test_cancel_frees_lane_and_pages(lm):
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=64,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        p = np.zeros(4, np.int32)
        f1 = cb.submit(p, 50)          # long generation holds the only lane
        f2 = cb.submit(p, 3)           # queued behind it
        cb.cancel(f1)
        out2 = f2.result(timeout=120)  # cancel freed the lane for f2
        assert len(out2) == 3
        with pytest.raises(Exception):
            f1.result(timeout=5)
        # all non-scratch pages back
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    finally:
        cb.shutdown()


def test_sampling_params_policies(lm):
    from tpulab.engine.paged import SamplingParams
    logits = np.array([0.1, 5.0, 0.2, 4.9], np.float32)
    assert SamplingParams().pick(logits) == 1           # greedy
    s = SamplingParams(temperature=0.7, top_k=2, seed=0)
    picks = {s.pick(logits) for _ in range(50)}
    assert picks <= {1, 3}                              # top-2 only
    assert len(picks) == 2                              # actually samples
    # determinism per seed
    a = [SamplingParams(1.0, 0, seed=7).pick(logits) for _ in range(5)]
    b = [SamplingParams(1.0, 0, seed=7).pick(logits) for _ in range(5)]
    # fresh instances with the same seed produce the same stream
    assert a == b
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1)


def test_sampled_generation_reproducible(lm):
    from tpulab.engine.paged import SamplingParams
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        p = np.random.default_rng(11).integers(0, 64, (4,), np.int32)
        out1 = cb.submit(p, 6, sampling=SamplingParams(0.8, 5, seed=3)).result(
            timeout=120)
        out2 = cb.submit(p, 6, sampling=SamplingParams(0.8, 5, seed=3)).result(
            timeout=120)
        assert out1 == out2                 # same seed, same tokens
        greedy = cb.submit(p, 6).result(timeout=120)
        assert len(greedy) == 6
    finally:
        cb.shutdown()


def test_gqa_paged_matches_dense_generation():
    """Grouped-query attention end to end: GQA params through the paged
    continuous batcher == the dense KV-cache decode (which stores compact
    Hkv caches and broadcasts at attention time)."""
    params = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=64, n_kv_heads=2)
    dense = make_generate_fn(params, n_heads=4, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32, n_kv_heads=2)
    cb = ContinuousBatcher(params, n_heads=4, n_layers=2, lanes=2,
                           max_len=64, page_size=8,
                           compute_dtype=jnp.float32, n_kv_heads=2)
    try:
        # pool stores the compact KV form: a row is n_kv_heads heads
        assert cb.pool.n_kv_heads == 2
        assert cb.pool.kv.shape[4] == 2 * cb.pool.head_dim
        prompts = [np.random.default_rng(s).integers(0, 64, (4 + s,),
                                                     np.int32)
                   for s in range(3)]
        futs = [cb.submit(p, 6) for p in prompts]
        for p, f in zip(prompts, futs):
            got = f.result(timeout=120)
            want = np.asarray(dense(p[None, :], 6)[0])
            np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()


def test_gqa_paged_kernel_flag_matches_fallback():
    """GQA decode via the pallas kernel (interpret) == the gather path."""
    params = init_transformer_params(vocab=64, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=64, n_kv_heads=1)
    outs = {}
    for uk in (True, False):
        cb = ContinuousBatcher(params, n_heads=4, n_layers=2, lanes=2,
                               max_len=32, page_size=8,
                               compute_dtype=jnp.float32, n_kv_heads=1,
                               use_kernel=uk)
        try:
            p = np.random.default_rng(0).integers(0, 64, (5,), np.int32)
            outs[uk] = list(cb.submit(p, 5).result(timeout=120))
        finally:
            cb.shutdown()
    assert outs[True] == outs[False]


def test_pool_refcounting():
    """add_ref'd pages need one release per reference before freeing."""
    pool = PagedKVPool(n_pages=4, page_size=8, n_layers=1, n_heads=2,
                       head_dim=16, dtype=jnp.float32)
    p = pool.allocate_page()
    pool.add_ref(p)
    pool.release_pages([p])
    assert pool.free_pages == 2          # still held by the second ref
    pool.release_pages([p])
    assert pool.free_pages == 3
    with pytest.raises(ValueError):
        pool.add_ref(p)                  # freed pages can't be shared


def test_prefix_cache_reuse_matches_uncached(lm):
    """Identical and shared-prefix prompts served through the prefix cache
    produce exactly the uncached token sequences, and the repeat prompt's
    full prefix pages come from cache."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=64,
                           page_size=8, compute_dtype=jnp.float32,
                           prefix_cache=True)
    try:
        rng = np.random.default_rng(3)
        base = rng.integers(0, 64, (20,), np.int32)     # 2 full pages + 4
        got1 = cb.submit(base, 6).result(timeout=120)
        hits_before = cb.prefix_cache.hits
        got2 = cb.submit(base, 6).result(timeout=120)   # identical prompt
        assert cb.prefix_cache.hits - hits_before == 2  # both full pages
        # shared-prefix prompt: same first 2 pages, different tail
        branch = np.concatenate([base[:16], rng.integers(0, 64, (7,),
                                                         np.int32)])
        got3 = cb.submit(branch, 6).result(timeout=120)
        for p, got in ((base, got1), (base, got2), (branch, got3)):
            want = np.asarray(dense(p[None, :], 6)[0])
            np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()
    # shutdown cleared the cache's refs: every page back in the pool
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_chunked_prefill_matches_oneshot(lm):
    """prefill_chunk splits a long prompt into page-aligned extend calls;
    outputs must equal the one-shot fused prefill."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=64,
                           page_size=8, compute_dtype=jnp.float32,
                           prefill_chunk=16)
    try:
        p = np.random.default_rng(5).integers(0, 64, (37,), np.int32)
        got = cb.submit(p, 5).result(timeout=120)
        want = np.asarray(dense(p[None, :], 5)[0])
        np.testing.assert_array_equal(np.asarray(got), want)
    finally:
        cb.shutdown()


def test_prefix_cache_eviction_under_pressure(lm):
    """A tight pool forces LRU eviction of cached prefixes; distinct
    prompts keep completing (cache never deadlocks the pool)."""
    # 1 lane, max_len 32 -> 4 pages/lane; pool = 6 pages total
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=32,
                           page_size=8, n_pages=7, compute_dtype=jnp.float32,
                           prefix_cache=True)
    try:
        rng = np.random.default_rng(11)
        for i in range(6):
            p = rng.integers(0, 64, (17,), np.int32)    # 2 full pages each
            out = cb.submit(p, 3).result(timeout=120)
            assert len(out) == 3
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_priority_admission_order(lm):
    """With one lane, queued requests admit by priority (high first),
    FIFO within a class."""
    gate = FirstTokenGate()
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        order = []
        f0 = cb.submit(np.full((3,), 1, np.int32), 4, on_token=gate)
        assert gate.wait(timeout=60)
        # lane busy: queue three more at mixed priorities
        fs = [cb.submit(np.full((3,), 2 + i, np.int32), 2, priority=pri,
                        on_token=lambda tok, i, tag=tag: (
                            order.append(tag) if i == 0 else None))
              for i, (pri, tag) in enumerate([(0, "low"), (5, "hi"),
                                              (1, "mid")])]
        gate.release()
        f0.result(timeout=120)
        for f in fs:
            f.result(timeout=120)
        assert order == ["hi", "mid", "low"]
    finally:
        cb.shutdown()


def test_preemption_exact_resume(lm):
    """A high-priority arrival evicts the active low-priority request;
    the victim resumes later with EXACTLY the tokens an undisturbed run
    produces (greedy and seeded-sampled), and pages balance."""
    import threading
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    p_low = np.random.default_rng(21).integers(0, 64, (6,), np.int32)
    p_hi = np.random.default_rng(22).integers(0, 64, (5,), np.int32)

    # un-preempted seeded reference
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
                           page_size=8, compute_dtype=jnp.float32)
    try:
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 10, on_token=started)
        assert started.wait(timeout=60)
        f_hi = cb.submit(p_hi, 4, priority=10)      # outranks -> preempts
        started.release()
        got_hi = f_hi.result(timeout=120)
        got_low = f_low.result(timeout=120)
        assert cb.preemptions >= 1
        np.testing.assert_array_equal(
            np.asarray(got_low), np.asarray(dense(p_low[None, :], 10)[0]))
        np.testing.assert_array_equal(
            np.asarray(got_hi), np.asarray(dense(p_hi[None, :], 4)[0]))

        # seeded-sampled victim: preemption must not perturb the PRNG
        started2 = FirstTokenGate()
        f_s = cb.submit(p_low, 10,
                        sampling=SamplingParams(temperature=0.9, seed=123),
                        on_token=started2)
        assert started2.wait(timeout=60)
        f_hi2 = cb.submit(p_hi, 2, priority=10)
        started2.release()
        f_hi2.result(timeout=120)
        assert list(f_s.result(timeout=120)) == list(sampled_ref)
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_generate_rpc_sampling_and_priority(lm):
    """GenerateRequest's sampling/priority fields reach the batcher: a
    seeded remote request reproduces the local seeded run, and priority
    requests complete through the same endpoint."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1,
                               max_len=32, page_size=8,
                               compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        prompt = np.random.default_rng(4).integers(0, 64, (6,), np.int32)
        want = ref_cb.submit(
            prompt, 6, sampling=SamplingParams(temperature=0.8, top_k=8,
                                               seed=99)).result(timeout=120)
        got = list(GenerateStreamClient(remote, "lm").generate(
            prompt, 6, temperature=0.8, top_k=8, seed=99, priority=3))
        assert got == list(want)
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()
        ref_cb.shutdown()


def test_generate_rpc_dense_rejects_sampling(lm):
    """Sampling/priority against a dense session backend is a clean
    INVALID_ARGUMENT, not silently-greedy output."""
    import tpulab
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    eng = GenerationEngine(lm, n_heads=2, n_layers=2, max_len=32,
                           max_sessions=1, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": eng})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        with pytest.raises(RuntimeError, match="continuous-batching"):
            list(GenerateStreamClient(remote, "lm").generate(
                np.zeros(4, np.int32), 2, temperature=0.5))
    finally:
        remote.close()
        mgr.shutdown()


def test_generate_rpc_negative_temperature_rejected(lm):
    """temperature < 0 is INVALID_ARGUMENT on any backend (mirrors the
    local SamplingParams contract), never silently greedy."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        with pytest.raises(RuntimeError, match="temperature"):
            list(GenerateStreamClient(remote, "lm").generate(
                np.zeros(4, np.int32), 2, temperature=-0.5))
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()


def test_kv_cache_quantization_fp8(lm):
    """kv_dtype narrower than compute: pages store fp8 (4x less HBM than
    f32), decode reads upcast, and the serving loop runs end to end with
    logits tracking the full-precision pool closely."""
    from tpulab.engine.paged_steps import paged_decode_step

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                           page_size=8, compute_dtype=jnp.float32,
                           kv_dtype=jnp.float8_e4m3fn)
    cb32 = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                             page_size=8, compute_dtype=jnp.float32)
    try:
        assert cb.pool.dtype == jnp.float8_e4m3fn
        assert cb.pool.hbm_bytes * 4 == cb32.pool.hbm_bytes
        p = np.random.default_rng(2).integers(0, 64, (6,), np.int32)
        out = cb.submit(p, 5).result(timeout=120)
        assert len(out) == 5
    finally:
        cb.shutdown()
        cb32.shutdown()

    # numerics: one decode tick over identical KV content, fp8 vs f32 pool
    rng = np.random.default_rng(0)
    # fused pool shape: (n_layers, n_pages, 2, page_size, n_heads * head_dim)
    kv32 = jnp.asarray(rng.uniform(-1, 1, (2, 4, 2, 8, 2 * 16)), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    lengths = jnp.asarray([12], jnp.int32)
    tokens = jnp.asarray([3], jnp.int32)
    active = jnp.ones((1,), bool)
    step = lambda kv: paged_decode_step(
        lm, kv, tables, lengths, tokens, active, n_heads=2, n_layers=2,
        compute_dtype=jnp.float32)[0]
    l32 = np.asarray(step(kv32))
    l8 = np.asarray(step(kv32.astype(jnp.float8_e4m3fn)))
    corr = np.corrcoef(l32.ravel(), l8.ravel())[0, 1]
    assert corr > 0.98, corr


@pytest.mark.slow
def test_scheduler_churn_soak(lm):
    """Priorities, preemption, prefix sharing, cancels, and page pressure
    all at once: every surviving request must return EXACTLY its
    single-request greedy reference — scheduler churn can reorder work but
    never corrupt it — and all pages must come home."""
    import random
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    rng = np.random.default_rng(31)
    pyrng = random.Random(31)
    shared = rng.integers(0, 64, (16,), np.int32)       # 2 full pages
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=64,
                           page_size=8, n_pages=13,     # 12 usable: tight
                           compute_dtype=jnp.float32, prefix_cache=True,
                           prefill_chunk=16)
    try:
        jobs = []
        explicitly_cancelled = set()
        for i in range(14):
            if pyrng.random() < 0.5:  # shared-prefix family
                p = np.concatenate([shared,
                                    rng.integers(0, 64, (pyrng.randint(1, 6),),
                                                 np.int32)])
            else:
                p = rng.integers(0, 64, (pyrng.randint(3, 10),), np.int32)
            steps = pyrng.randint(1, 6)
            fut = cb.submit(p, steps, priority=pyrng.choice([0, 0, 1, 5]))
            jobs.append((p, steps, fut))
            if pyrng.random() < 0.2:
                cb.cancel(fut)
                explicitly_cancelled.add(id(fut))
        import concurrent.futures as _f
        ok = 0
        for p, steps, fut in jobs:
            try:
                got = fut.result(timeout=180)
            except (Exception, _f.CancelledError):
                # ONLY futures this test cancelled may raise — anything
                # else is an engine regression, not churn
                # (CancelledError is a BaseException on CPython >= 3.8)
                assert id(fut) in explicitly_cancelled
                continue
            want = np.asarray(dense(p[None, :], steps)[0])
            np.testing.assert_array_equal(np.asarray(got), want)
            ok += 1
        assert ok >= len(jobs) - len(explicitly_cancelled)
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_an_engine_at_the_defaults_serves_prompts_in_rounds(lm):
    """There is one dispatch plan.  An engine built with the constructor's
    defaults on the CPU takes the XLA gather (``use_kernel`` chooses the
    attention, never the plan) and every prompt rides mixed rounds: the
    counter of rounds moves, and tokens equal the dense engine's across the
    rounds' widths (a one-token prompt pads to two rows; 33 tokens with the
    prefix cache on take two rounds the second time: the shared pages'
    positions are not computed again)."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=64,
                           page_size=8, compute_dtype=jnp.float32,
                           prefix_cache=True)
    try:
        assert cb.use_kernel is False
        assert cb.debug_state()["dispatch"]["kinds"]["mixed"] == 0
        rng = np.random.default_rng(17)
        prompts = [rng.integers(0, 64, (n,), np.int32) for n in (1, 5, 16, 33)]
        for p in prompts + prompts[-1:]:
            np.testing.assert_array_equal(
                np.asarray(cb.submit(p, 5).result(timeout=120)),
                np.asarray(dense(p[None, :], 5)[0]))
        d = cb.debug_state()["dispatch"]
        # a round a prompt (the budget is max_len / 2 = 32: 33 tokens take
        # two, and one the second time, 32 of them shared)
        assert d["kinds"]["mixed"] == 3 + 2 + 1
        assert cb.prefix_cache.hits == 4
    finally:
        cb.shutdown()


def test_a_round_that_fails_is_an_error_the_requester_sees(lm):
    """A failure of the round's program (a Mosaic refusal of its bucket,
    say) is an error the requester sees: the batcher swaps in no other
    path behind its back, and serving goes on for the next request."""
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    try:
        real = cb.programs.mixed

        def boom(*a, **k):
            raise RuntimeError("Mosaic rejected this bucket")
        cb.programs.mixed = boom
        p = np.random.default_rng(1).integers(0, 64, (6,), np.int32)
        with pytest.raises(RuntimeError, match="Mosaic rejected"):
            cb.submit(p, 4).result(timeout=120)
        assert cb.programs.mixed is boom
        cb.programs.mixed = real
        assert len(cb.submit(p, 4).result(timeout=120)) == 4
    finally:
        cb.shutdown()


def test_stop_tokens_end_generation_early(lm):
    """A stop token ends the request at that tick (stop token included as
    the final token), frees the lane, and rides the Generate RPC."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    p = np.random.default_rng(8).integers(0, 64, (5,), np.int32)
    ref = list(np.asarray(dense(p[None, :], 10)[0]))
    stop = ref[3]          # greedy run's 4th token becomes the stop token
    want = ref[:ref.index(stop) + 1]

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=64,
                           page_size=8, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        got = cb.submit(p, 10, stop_tokens=[stop]).result(timeout=120)
        assert list(got) == want
        got_rpc = list(GenerateStreamClient(remote, "lm").generate(
            p, 10, stop_tokens=[stop]))
        assert got_rpc == want
        # a stop token at the PREFILL-emitted first token also terminates
        got1 = cb.submit(p, 10, stop_tokens=[ref[0]]).result(timeout=120)
        assert list(got1) == ref[:1]
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_stop_tokens_on_dense_session_backend(lm):
    """The dense session Generate path honors stop_tokens too (parity with
    the paged backend)."""
    import tpulab
    from tpulab.engine.generation import GenerationEngine
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    p = np.random.default_rng(8).integers(0, 64, (5,), np.int32)
    ref = list(np.asarray(dense(p[None, :], 10)[0]))
    stop = ref[3]
    eng = GenerationEngine(lm, n_heads=2, n_layers=2, max_len=64,
                           max_sessions=1, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": eng})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        got = list(GenerateStreamClient(remote, "lm").generate(
            p, 10, stop_tokens=[stop]))
        assert got == ref[:ref.index(stop) + 1]
    finally:
        remote.close()
        mgr.shutdown()


def test_device_sampling_reproducible_and_batch_invariant(lm):
    """device=True sampling: the (seed, position)-folded on-chip stream is
    reproducible across engines, invariant to batch-mates, unperturbed by
    preemption, and never fetches logits for those lanes."""
    import threading
    p = np.random.default_rng(6).integers(0, 64, (5,), np.int32)

    def run(extra_traffic=False, preempt=False):
        cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2,
                               max_len=64, page_size=8,
                               compute_dtype=jnp.float32)
        try:
            started = FirstTokenGate()
            if not preempt:
                started.release()
            fut = cb.submit(p, 10,
                            sampling=SamplingParams(temperature=0.9,
                                                    seed=1234, device=True),
                            on_token=started)
            if extra_traffic:
                cb.submit(np.full((3,), 7, np.int32), 10,
                          sampling=SamplingParams(temperature=1.5, seed=9,
                                                  device=True))
            if preempt:
                assert started.wait(timeout=60)
                hi = cb.submit(np.full((4,), 2, np.int32), 3, priority=10)
                started.release()
                hi.result(timeout=120)
            return list(fut.result(timeout=120))
        finally:
            cb.shutdown()

    base = run()
    assert run(extra_traffic=True) == base
    assert run(preempt=True) == base
    assert len(base) == 10


def test_device_sampling_rejects_top_k(lm):
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(temperature=0.5, top_k=10, device=True)


def test_device_and_host_sampling_coexist(lm):
    """A tick mixing greedy, device-sampled, and host-sampled lanes keeps
    every stream independent and correct."""
    dense = make_generate_fn(lm, n_heads=2, n_layers=2, max_len=64,
                             compute_dtype=jnp.float32)
    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=3, max_len=64,
                           page_size=8, compute_dtype=jnp.float32)
    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1,
                               max_len=64, page_size=8,
                               compute_dtype=jnp.float32)
    try:
        pg = np.random.default_rng(1).integers(0, 64, (4,), np.int32)
        ph = np.random.default_rng(2).integers(0, 64, (4,), np.int32)
        pd = np.random.default_rng(3).integers(0, 64, (4,), np.int32)
        host_ref = ref_cb.submit(
            ph, 8, sampling=SamplingParams(temperature=0.8, top_k=8,
                                           seed=55)).result(timeout=120)
        futs = [
            cb.submit(pg, 8),                                     # greedy
            cb.submit(ph, 8, sampling=SamplingParams(
                temperature=0.8, top_k=8, seed=55)),              # host
            cb.submit(pd, 8, sampling=SamplingParams(
                temperature=0.8, seed=77, device=True)),          # device
        ]
        outs = [f.result(timeout=120) for f in futs]
        np.testing.assert_array_equal(
            np.asarray(outs[0]), np.asarray(dense(pg[None, :], 8)[0]))
        assert list(outs[1]) == list(host_ref)
        assert len(outs[2]) == 8
    finally:
        cb.shutdown()
        ref_cb.shutdown()


def test_generate_rpc_device_sampling(lm):
    """device_sampling over the wire: seeded remote run == local seeded
    device-sampled run; invalid top_k combo is a clean error."""
    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=2, max_len=32,
                           page_size=8, compute_dtype=jnp.float32)
    ref_cb = ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=1,
                               max_len=32, page_size=8,
                               compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb})
    remote = RemoteInferenceManager(f"localhost:{mgr.server.bound_port}")
    try:
        p = np.random.default_rng(4).integers(0, 64, (6,), np.int32)
        want = ref_cb.submit(p, 6, sampling=SamplingParams(
            temperature=0.8, seed=321, device=True)).result(timeout=120)
        got = list(GenerateStreamClient(remote, "lm").generate(
            p, 6, temperature=0.8, seed=321, device_sampling=True))
        assert got == list(want)
        with pytest.raises(RuntimeError, match="top_k"):
            list(GenerateStreamClient(remote, "lm").generate(
                p, 4, temperature=0.8, top_k=5, device_sampling=True))
    finally:
        remote.close()
        mgr.shutdown()
        cb.shutdown()
        ref_cb.shutdown()


def test_sampling_top_p_nucleus():
    """Nucleus truncation: only the smallest prob-descending prefix with
    mass >= top_p can be sampled; composes after top_k; validation and
    the device-sampling rejection mirror top_k's contract."""
    import numpy as np
    import pytest

    from tpulab.engine.paged import SamplingParams
    logits = np.log(np.array([0.5, 0.3, 0.15, 0.05]))
    # top_p=0.6: {0.5, 0.3} is the smallest prefix with mass >= 0.6
    sp = SamplingParams(temperature=1.0, top_p=0.6, seed=7)
    draws = {sp.pick(logits) for _ in range(200)}
    assert draws <= {0, 1} and draws == {0, 1}
    # tiny top_p degenerates to argmax-only
    sp1 = SamplingParams(temperature=1.0, top_p=0.01, seed=7)
    assert {sp1.pick(logits) for _ in range(50)} == {0}
    # top_k=2 then top_p=0.99 over the renormalized pair: still {0,1}
    spk = SamplingParams(temperature=1.0, top_k=2, top_p=0.99, seed=7)
    assert {spk.pick(logits) for _ in range(200)} == {0, 1}
    # top_p=1.0 disables truncation (all four reachable)
    sp_all = SamplingParams(temperature=1.0, top_p=1.0, seed=7)
    assert len({sp_all.pick(logits) for _ in range(400)}) == 4
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=1.0, top_p=1.5)
    with pytest.raises(ValueError, match="static shape"):
        SamplingParams(temperature=1.0, top_p=0.9, device=True)

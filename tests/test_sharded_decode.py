"""Sharded serving: the paged engine on a {"model": M} device mesh.

Parity discipline (the decode-block contract extended once more): a mesh
is a PLACEMENT change, never a content change — every array op in the
fused prefill/decode/speculative programs is mathematically identical
under sharding (XLA inserts psums over the model axis; it never reorders
the reductions the single-device program already runs in f32), so the
sharded token stream must be BIT-identical to mesh=None for greedy and
(seed, position)-folded device sampling, through speculative blocks and
preempt/resume over the host tier.  The host-sync guard pins the other
half of the contract: the collectives ride INSIDE the compiled blocks,
so sharding never adds a host sync.

Runs on the 8 fake CPU devices conftest forces
(--xla_force_host_platform_device_count-style), mesh {"model": 2}: the
pool's 2 KV heads shard one per device.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from helpers_engine import FirstTokenGate
from tpulab.engine.kv_pool import PagedKVPool, kv_rows_view
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.models.transformer import (early_exit_draft,
                                       init_transformer_params,
                                       make_generate_fn)
from tpulab.parallel import make_mesh


@pytest.fixture(scope="module")
def lm():
    p = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64)
    # same trained-model emulation as test_speculative_block: the 1-layer
    # early-exit draft must actually agree with the target sometimes
    for w in ("wo", "w2"):
        p["layer1"][w] = p["layer1"][w] * 0.05
    return p


@pytest.fixture(scope="module")
def dense(lm):
    return make_generate_fn(lm, n_heads=2, n_layers=2, max_len=96,
                            compute_dtype=jnp.float32)


def _mesh(m=2):
    return make_mesh({"model": m}, jax.devices()[:m])


def _batcher(lm, mesh=None, **kw):
    kw.setdefault("lanes", 2)
    kw.setdefault("max_len", 64)
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, page_size=8,
                             compute_dtype=jnp.float32, mesh=mesh, **kw)


# ----------------------------------------------------------- placement ---
def test_pool_and_params_are_actually_sharded(lm):
    """The mesh build really shards: page payloads carry the KV-heads
    PartitionSpec, params follow the Megatron-TP rules, and per-shard
    HBM is the logical figure divided by the shard count."""
    cb = _batcher(lm, mesh=_mesh(2))
    try:
        assert cb.pool.kv_sharding is not None
        assert cb.pool.kv.sharding.spec == P(None, None, None, None,
                                             "model")
        assert cb.pool.n_shards == 2
        assert cb.pool.hbm_bytes_per_shard == cb.pool.hbm_bytes // 2
        assert cb.params["layer0"]["wqkv"].sharding.spec == P(None, "model")
        assert cb.params["layer0"]["wo"].sharding.spec == P("model", None)
        assert cb.params["layer0"]["ln1"]["scale"].sharding.spec == P()
    finally:
        cb.shutdown()


def test_pool_rejects_bad_mesh_geometry(lm):
    with pytest.raises(ValueError, match="model"):
        PagedKVPool(8, 8, 2, 2, 16, jnp.float32,
                    mesh=make_mesh({"data": 2}, jax.devices()[:2]))
    with pytest.raises(ValueError, match="not divisible"):
        PagedKVPool(8, 8, 2, 3, 16, jnp.float32, mesh=_mesh(2))


def test_batcher_accepts_kernel_under_mesh_rejects_foreign_pool(lm):
    """The ragged pallas kernel shards over the KV-heads dim (PR 8's
    named follow-up retired): use_kernel=True under a mesh constructs,
    and a provided pool must be built on the batcher's own mesh."""
    cb = _batcher(lm, mesh=_mesh(2), use_kernel=True)
    try:
        assert cb.use_kernel and cb.mesh is not None
    finally:
        cb.shutdown()
    other = PagedKVPool(17, 8, 2, 2, 16, jnp.float32, mesh=_mesh(2))
    with pytest.raises(ValueError, match="different mesh"):
        _batcher(lm, mesh=make_mesh({"model": 2}, jax.devices()[2:4]),
                 pool=other)


# -------------------------------------------------------------- parity ---
def test_sharded_greedy_parity_with_page_crossings(lm, dense):
    """mesh={"model": 2} greedy == mesh=None greedy == dense reference,
    including decode runs that cross page boundaries mid-block."""
    rng = np.random.default_rng(5)
    cases = [(rng.integers(0, 64, (n,), np.int32), s)
             for n, s in ((5, 20), (8, 17), (13, 30), (1, 9))]
    outs = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh)
        try:
            outs[name] = [list(cb.submit(p, s).result(timeout=300))
                          for p, s in cases]
        finally:
            cb.shutdown()
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    assert outs["sharded"] == outs["single"]
    for (p, s), got in zip(cases, outs["sharded"]):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense(p[None, :], s)[0]))


def test_sharded_device_sampled_parity(lm):
    """The (seed, position)-folded device sampling stream survives
    sharding bit-exactly: the Gumbel pick reduces over the full
    (replicated-output) logits row on every shard identically."""
    p = np.random.default_rng(6).integers(0, 64, (5,), np.int32)
    outs = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh)
        try:
            outs[name] = list(cb.submit(
                p, 20, sampling=SamplingParams(temperature=0.9, seed=1234,
                                               device=True)
            ).result(timeout=300))
        finally:
            cb.shutdown()
    assert outs["sharded"] == outs["single"] and len(outs["sharded"]) == 20


def test_sharded_logprobs_parity(lm):
    """logprobs ride the sharded fetch too (tokens exact; the log-softmax
    float stream allclose — reduction fusion may differ across layouts)."""
    p = np.random.default_rng(12).integers(0, 64, (6,), np.int32)
    outs = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh)
        try:
            outs[name] = cb.submit(p, 12, logprobs=True).result(timeout=300)
        finally:
            cb.shutdown()
    assert list(outs["sharded"][0]) == list(outs["single"][0])
    np.testing.assert_allclose(outs["sharded"][1], outs["single"][1],
                               rtol=1e-5, atol=1e-6)


def test_sharded_host_sync_counts_preserved(lm):
    """Sharding must not add host syncs: the same greedy workload issues
    the SAME number of decode dispatches and blocking fetches on the
    mesh as on one device (collectives stay inside the programs)."""
    p = np.random.default_rng(7).integers(0, 64, (5,), np.int32)
    counts = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh, lanes=1)
        try:
            cb.submit(p, 17).result(timeout=300)    # warm compiles
            s0, d0 = cb.decode_host_syncs, cb.decode_dispatches
            cb.submit(p, 17).result(timeout=300)
            counts[name] = (cb.decode_host_syncs - s0,
                            cb.decode_dispatches - d0)
        finally:
            cb.shutdown()
    assert counts["sharded"] == counts["single"]


def test_sharded_host_sampled_stream_parity(lm):
    """Host-sampled (top_k) lanes fetch gathered logits rows off a
    sharded fetch: the seeded host-PRNG stream must match mesh=None."""
    p = np.random.default_rng(2).integers(0, 64, (4,), np.int32)
    outs = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh, lanes=1)
        try:
            outs[name] = list(cb.submit(p, 10, sampling=SamplingParams(
                temperature=0.8, top_k=8, seed=55)).result(timeout=300))
        finally:
            cb.shutdown()
    assert outs["sharded"] == outs["single"]


# --------------------------------------------------------- speculative ---
def test_sharded_speculative_parity(lm, dense):
    """Speculative blocks under the mesh: draft propose + target verify +
    accept all run as ONE sharded dispatch and the accepted stream stays
    bit-identical to the single-device speculative run AND the dense
    greedy reference; draft pages come home."""
    draft = early_exit_draft(lm, 1)
    p = np.random.default_rng(4).integers(0, 64, (5,), np.int32)
    outs = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh, lanes=1, max_len=96, n_pages=25,
                      draft_params=draft, draft_n_layers=1)
        try:
            outs[name] = list(cb.submit(p, 24).result(timeout=300))
            assert cb.spec_dispatches > 0
        finally:
            cb.shutdown()
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    assert outs["sharded"] == outs["single"]
    np.testing.assert_array_equal(
        np.asarray(outs["sharded"]), np.asarray(dense(p[None, :], 24)[0]))


# ------------------------------------------------------ preempt/resume ---
def test_sharded_preempt_resume_through_host_tier(lm, dense):
    """A sharded lane preempted to the host tier resumes bit-exactly with
    zero re-prefill: the swap gather's payload is assembled into ONE
    unsharded host array and restore's device_put re-shards it onto the
    pool placement (the mesh round-trips the host tier)."""
    p_low = np.random.default_rng(21).integers(0, 64, (12,), np.int32)
    p_hi = np.random.default_rng(22).integers(0, 64, (5,), np.int32)
    cb = _batcher(lm, mesh=_mesh(2), lanes=1, kv_offload=32 << 20)
    try:
        started = FirstTokenGate()
        f_low = cb.submit(p_low, 10, on_token=started)
        assert started.wait(timeout=120)
        f_hi = cb.submit(p_hi, 4, priority=10)    # outranks -> preempts
        started.release()
        got_hi = list(f_hi.result(timeout=300))
        got_low = list(f_low.result(timeout=300))
        assert cb.preemptions >= 1
        assert cb.kv_offload.swap_outs >= 1 and cb.kv_offload.swap_ins >= 1
        assert cb.dispatch_kinds["mixed"] == 2   # zero re-prefill
        np.testing.assert_array_equal(
            np.asarray(got_low), np.asarray(dense(p_low[None, :], 10)[0]))
        np.testing.assert_array_equal(
            np.asarray(got_hi), np.asarray(dense(p_hi[None, :], 4)[0]))
    finally:
        cb.shutdown()
    assert cb.pool.free_pages == cb.pool.n_pages - 1


def test_sharded_swap_payload_is_mesh_portable(lm):
    """The host tier holds UNSHARDED bytes: a payload swapped out of a
    2-shard pool scatters bit-exactly into a single-device pool — the
    cross-mesh import path disagg rides (and the scatter jits are keyed
    by placement, so the second pool never reuses the first's program)."""
    from tpulab.kvcache import HostKVStore, KVOffloadManager
    rng = np.random.default_rng(9)
    payload = rng.standard_normal((2, 1, 2, 8, 2, 16)).astype(np.float32)
    pool_a = PagedKVPool(9, 8, 2, 2, 16, jnp.float32, mesh=_mesh(2))
    pool_b = PagedKVPool(9, 8, 2, 2, 16, jnp.float32)
    store = HostKVStore(32 << 20)
    mgr_a = KVOffloadManager(pool_a, store=store)
    mgr_b = KVOffloadManager(pool_b, store=store)
    page_a = pool_a.allocate_page()
    rows = kv_rows_view(payload[:, 0])      # the device keeps rows
    pool_a.kv = pool_a.kv.at[:, page_a].set(jnp.asarray(rows))
    h = mgr_a.swap_out([page_a], 8, pool_a.kv)
    assert h is not None
    mgr_a.drain()
    page_b = pool_b.allocate_page()
    new_kv = mgr_b.restore(h, [page_b], pool_b.kv)
    assert new_kv is not None
    np.testing.assert_array_equal(np.asarray(new_kv[:, page_b]), rows)
    assert mgr_a._placement_key() != mgr_b._placement_key()


# ------------------------------------------------------------ dryrun ----
def test_mesh_parity_matches_dryrun_contract(lm):
    """The exact check __graft_entry__.py's multichip dryrun records
    (greedy + device-sampled on one batcher pair) passes in-process."""
    pg = np.random.default_rng(0).integers(0, 64, (6,), np.int32)
    outs = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh)
        try:
            outs[name] = [
                list(cb.submit(pg, 12).result(timeout=300)),
                list(cb.submit(pg, 12, sampling=SamplingParams(
                    temperature=0.8, seed=7,
                    device=True)).result(timeout=300)),
            ]
        finally:
            cb.shutdown()
    assert outs["sharded"] == outs["single"]


def test_sharded_prefix_cache_and_chunked_prefill_parity(lm, dense):
    """Prefix-cache hits and chunked long-prompt prefill ride the sharded
    ``paged_extend`` jit: repeated, branched, and chunk-prefilled prompts
    all match the dense reference under the mesh, with the same hit
    counts as single-device, and pages balance."""
    rng = np.random.default_rng(3)
    base = rng.integers(0, 64, (20,), np.int32)       # 2 full pages + 4
    branch = np.concatenate([base[:16], rng.integers(0, 64, (7,), np.int32)])
    long_p = rng.integers(0, 64, (37,), np.int32)     # 3 chunks of 16
    hits = {}
    for name, mesh in (("single", None), ("sharded", _mesh(2))):
        cb = _batcher(lm, mesh=mesh, lanes=1, max_len=96,
                      prefix_cache=True, prefill_chunk=16)
        try:
            for p, s in ((base, 16), (base, 16), (branch, 16), (long_p, 8)):
                got = list(cb.submit(p, s).result(timeout=300))
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(dense(p[None, :], s)[0]))
            hits[name] = cb.prefix_cache.hits
        finally:
            cb.shutdown()
        assert cb.pool.free_pages == cb.pool.n_pages - 1
    assert hits["sharded"] == hits["single"] > 0

"""Disaggregated prefill/decode tests (tpulab.disagg): wire-format
round-trip + reject-don't-corrupt, prefill-replica -> decode-replica
handoff with ZERO decode-side prefill dispatches and token parity vs a
unified replica, chaos/corruption degradation to local prefill, and the
role-aware GenerationReplicaSet routing over real gRPC replicas."""

import numpy as np
import pytest

import jax.numpy as jnp

from tpulab import chaos
from tpulab.disagg import (KVShipper, WireFormatError,
                           deserialize_snapshot, prompt_digest,
                           serialize_snapshot)
from tpulab.engine.paged import ContinuousBatcher, SamplingParams
from tpulab.models.transformer import init_transformer_params


@pytest.fixture(scope="module")
def lm():
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)


def _batcher(lm, lanes=1, page_size=8, **kw):
    kw.setdefault("kv_offload", 32 << 20)
    return ContinuousBatcher(lm, n_heads=2, n_layers=2, lanes=lanes,
                             max_len=64, page_size=page_size,
                             compute_dtype=jnp.float32, **kw)


def _sampling():
    """Device sampling: varied tokens (greedy on the tiny fixture model
    degenerates into repeats, which would vacuously pass parity)."""
    return SamplingParams(temperature=0.8, device=True, seed=1234)


def _handoff(bp, bd, prompt, steps, sampling=None, corrupt=None):
    """Drive one prefill->ship->decode handoff; returns the full token
    stream (index 0 from the prefill replica) and the import shipper."""
    dig = prompt_digest(prompt)
    fut = bp.submit(prompt, 1, export_digest=dig, sampling=sampling)
    first = fut.result(timeout=120)[0]
    out_sh = KVShipper(bp.kv_offload)
    blob = out_sh.export(getattr(fut, "_tpulab_kv_export", None),
                         digest=dig, first_token=first)
    if corrupt is not None and blob is not None:
        blob = corrupt(blob)
    in_sh = KVShipper(bd.kv_offload)
    ship = in_sh.import_shipment(blob) if blob is not None else None
    if ship is not None:
        f2 = bd.submit_shipped(prompt, steps, first, ship.handle,
                               sampling=sampling)
    else:  # lost shipment: local prefill on the decode replica
        f2 = bd.submit_shipped(prompt, steps, first, None,
                               sampling=sampling)
    return list(f2.result(timeout=120)), in_sh


# -- wire format --------------------------------------------------------------

def test_wire_roundtrip_bit_exact():
    arr = np.random.default_rng(0).standard_normal(
        (2, 3, 2, 4, 2, 8)).astype(np.float32)
    dig = prompt_digest([1, 2, 3])
    blob = serialize_snapshot(arr, digest=dig, length=11, page_size=4,
                              first_token=42)
    got, hdr = deserialize_snapshot(blob)
    np.testing.assert_array_equal(got, arr)
    assert got.dtype == arr.dtype
    assert hdr["length"] == 11 and hdr["page_size"] == 4
    assert hdr["first_token"] == 42 and hdr["digest"] == dig


def test_wire_rejects_bad_magic_version_and_corruption():
    arr = np.zeros((1, 1, 2, 4, 2, 8), np.float32)
    blob = serialize_snapshot(arr, digest=b"\x00" * 16, length=3,
                              page_size=4, first_token=0)
    with pytest.raises(WireFormatError, match="magic"):
        deserialize_snapshot(b"NOPE" + blob[4:])
    with pytest.raises(WireFormatError, match="version"):
        deserialize_snapshot(blob[:4] + b"\x63\x00" + blob[6:])
    # flip one payload byte: the CRC must catch it
    bad = bytearray(blob)
    bad[-1] ^= 0xFF
    with pytest.raises(WireFormatError, match="corrupt"):
        deserialize_snapshot(bytes(bad))
    with pytest.raises(WireFormatError):
        deserialize_snapshot(blob[:len(blob) // 2])  # truncated


def test_shipper_rejects_mismatched_geometry(lm):
    """A shipment from a replica with a different page size must be
    REJECTED at import (never scattered into the pool)."""
    bp = _batcher(lm, page_size=8)
    bd = _batcher(lm, page_size=16)  # mismatched decode replica
    try:
        prompt = np.random.default_rng(1).integers(0, 64, (12,), np.int32)
        dig = prompt_digest(prompt)
        fut = bp.submit(prompt, 1, export_digest=dig)
        first = fut.result(timeout=120)[0]
        blob = KVShipper(bp.kv_offload).export(
            fut._tpulab_kv_export, digest=dig, first_token=first)
        assert blob is not None
        in_sh = KVShipper(bd.kv_offload)
        assert in_sh.import_shipment(blob) is None
        assert in_sh.import_failures == 1 and in_sh.imports == 0
    finally:
        bp.shutdown()
        bd.shutdown()


# -- engine-level handoff -----------------------------------------------------

def test_handoff_zero_prefill_dispatches_token_parity(lm):
    """The acceptance contract: a prefill-replica -> decode-replica
    handoff admits with ZERO prefill dispatches on the decode replica (no
    mixed round: a prompt's rows are computed in one and nowhere else)
    and the stream is bit-identical to a unified-replica run."""
    prompt = np.random.default_rng(2).integers(0, 64, (13,), np.int32)
    ref = _batcher(lm)
    try:
        want = ref.submit(prompt, 8, sampling=_sampling()).result(
            timeout=120)
    finally:
        ref.shutdown()
    bp, bd = _batcher(lm), _batcher(lm)
    try:
        got, in_sh = _handoff(bp, bd, prompt, 8, sampling=_sampling())
        assert got == want
        assert bd.dispatch_kinds["mixed"] == 0          # the headline
        assert bp.dispatch_kinds["mixed"] == 1
        assert in_sh.imports == 1 and in_sh.import_failures == 0
        assert bd.kv_offload.swap_ins == 1         # admitted via restore
    finally:
        bp.shutdown()
        bd.shutdown()
    # pages balance on both replicas (page 0 stays reserved scratch)
    assert bp.pool.free_pages == bp.pool.n_pages - 1
    assert bd.pool.free_pages == bd.pool.n_pages - 1


def test_handoff_greedy_parity_and_multi_request(lm):
    """Greedy parity plus several interleaved handoffs through one
    decode replica (lanes shared, zero prefills throughout)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, (n,), np.int32) for n in (5, 12, 17)]
    ref = _batcher(lm, lanes=2)
    try:
        wants = [ref.submit(p, 6).result(timeout=120) for p in prompts]
    finally:
        ref.shutdown()
    bp, bd = _batcher(lm, lanes=2), _batcher(lm, lanes=2)
    try:
        for p, want in zip(prompts, wants):
            got, _ = _handoff(bp, bd, p, 6)
            assert got == want
        assert bd.dispatch_kinds["mixed"] == 0
    finally:
        bp.shutdown()
        bd.shutdown()


@pytest.mark.chaos
@pytest.mark.parametrize("spec", ["disagg.ship=error+1",
                                  "disagg.ship=drop+1"])
def test_chaos_tripped_shipment_degrades_to_local_prefill(lm, spec):
    """A chaos-tripped export loses the shipment: the decode replica
    prefills locally, tokens are unchanged, nothing is stuck."""
    prompt = np.random.default_rng(4).integers(0, 64, (11,), np.int32)
    ref = _batcher(lm)
    try:
        want = ref.submit(prompt, 6, sampling=_sampling()).result(
            timeout=120)
    finally:
        ref.shutdown()
    bp, bd = _batcher(lm), _batcher(lm)
    try:
        with chaos.inject(spec) as sched:
            got, _ = _handoff(bp, bd, prompt, 6, sampling=_sampling())
            assert sched.fired("disagg.ship") == 1
        assert got == want
        assert bd.dispatch_kinds["mixed"] == 1   # the local-prefill fallback
        assert bd.kv_offload.swap_ins == 0
    finally:
        bp.shutdown()
        bd.shutdown()
    assert bd.pool.free_pages == bd.pool.n_pages - 1


def test_corrupt_shipment_degrades_to_local_prefill(lm):
    """A bit-flipped wire payload is caught by the CRC at import and the
    decode replica falls back to local prefill — same tokens, and the
    pool is never touched by the corrupt bytes."""
    prompt = np.random.default_rng(5).integers(0, 64, (9,), np.int32)
    ref = _batcher(lm)
    try:
        want = ref.submit(prompt, 5, sampling=_sampling()).result(
            timeout=120)
    finally:
        ref.shutdown()

    def flip(blob):
        bad = bytearray(blob)
        bad[-3] ^= 0x55
        return bytes(bad)

    bp, bd = _batcher(lm), _batcher(lm)
    try:
        got, in_sh = _handoff(bp, bd, prompt, 5, sampling=_sampling(),
                              corrupt=flip)
        assert got == want
        assert in_sh.import_failures == 1
        assert bd.dispatch_kinds["mixed"] == 1
    finally:
        bp.shutdown()
        bd.shutdown()


def test_submit_shipped_rejects_host_sampled_and_bad_inputs(lm):
    """Host-sampled PRNG streams are draw-order-keyed and do not survive
    the replica hop — the engine rejects them (routers fall back to
    unified); plus the deterministic input checks."""
    bd = _batcher(lm)
    try:
        p = np.arange(4, dtype=np.int32)
        with pytest.raises(ValueError, match="host"):
            bd.submit_shipped(p, 4, 1, None,
                              sampling=SamplingParams(temperature=0.5))
        with pytest.raises(ValueError, match="first token"):
            bd.submit_shipped(p, 4, 64, None)
        with pytest.raises(ValueError, match="empty"):
            bd.submit_shipped([], 4, 1, None)
        # steps==1: the shipped first token IS the whole request
        assert bd.submit_shipped(p, 1, 7, None).result(timeout=30) == [7]
    finally:
        bd.shutdown()


def test_export_fences_write_behind(lm):
    """export() must wait out the write-behind swap before serializing —
    the shipment always carries the landed bytes (drain fencing)."""
    bp = _batcher(lm)
    try:
        prompt = np.random.default_rng(6).integers(0, 64, (12,), np.int32)
        dig = prompt_digest(prompt)
        fut = bp.submit(prompt, 1, export_digest=dig)
        first = fut.result(timeout=120)[0]
        handle = fut._tpulab_kv_export
        # export immediately — the D2H may still be in flight; the wait
        # inside export is the fence
        blob = KVShipper(bp.kv_offload).export(handle, digest=dig,
                                               first_token=first)
        assert blob is not None
        arr, hdr = deserialize_snapshot(blob)
        assert hdr["length"] == len(prompt)
        assert arr.shape[1] == (len(prompt) + 7) // 8  # pages covered
        assert len(bp.kv_offload.store) == 0  # export pops the host copy
    finally:
        bp.shutdown()


# -- RPC + role-aware routing -------------------------------------------------

def _serve(lm, role, lanes=2):
    import tpulab
    from tpulab.models.mnist import make_mnist
    cb = _batcher(lm, lanes=lanes)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb}, role=role)
    return mgr, cb


def test_replicaset_disagg_routing_end_to_end(lm):
    """The full wire: role discovery over the Status RPC, prefill on the
    prefill replica, shipment to the decode replica (zero prefill
    dispatches there), token parity with a unified run — then a chaos-
    lost shipment degrading to local prefill on the decode replica
    without losing the stream."""
    from tpulab.rpc.replica import GenerationReplicaSet
    mp, cbp = _serve(lm, "prefill")
    md, cbd = _serve(lm, "decode")
    mu, cbu = _serve(lm, "unified")
    rs = None
    try:
        prompt = np.random.default_rng(7).integers(0, 64, (14,), np.int32)
        want = cbu.submit(prompt, 7, sampling=_sampling()).result(
            timeout=120)
        addrs = [f"127.0.0.1:{m.server.bound_port}" for m in (mp, md)]
        rs = GenerationReplicaSet(addrs, "lm", disaggregate=True)
        load = rs.poll_load()
        assert load[addrs[0]]["role"] == "prefill"
        assert load[addrs[1]]["role"] == "decode"
        got = list(rs.generate(prompt, 7, temperature=0.8,
                               device_sampling=True, seed=1234))
        assert got == want
        assert cbd.dispatch_kinds["mixed"] == 0       # shipped admit only
        assert cbp.dispatch_kinds["mixed"] == 1
        assert rs.disagg_handoffs == 1 and rs.disagg_fallbacks == 0

        # chaos: the export trips server-side -> no shipment ships; the
        # decode replica prefills locally and the stream still completes
        with chaos.inject("disagg.ship=error+1") as sched:
            got2 = list(rs.generate(prompt, 7, temperature=0.8,
                                    device_sampling=True, seed=1234))
            assert sched.fired("disagg.ship") == 1
        assert got2 == want
        assert cbd.dispatch_kinds["mixed"] == 1       # the local fallback ran
        assert rs.disagg_handoffs == 2           # still a two-hop serve
    finally:
        if rs is not None:
            rs.close()
        for m in (mp, md, mu):
            m.shutdown()
        for c in (cbp, cbd, cbu):
            c.shutdown()


def test_replicaset_disagg_falls_back_without_roles(lm):
    """No decode-role replica visible: disaggregate=True must transparently
    serve on the unified path (never refuse, never hang)."""
    from tpulab.rpc.replica import GenerationReplicaSet
    mu, cbu = _serve(lm, "unified")
    rs = None
    try:
        prompt = np.random.default_rng(8).integers(0, 64, (6,), np.int32)
        want = cbu.submit(prompt, 5).result(timeout=120)
        addr = f"127.0.0.1:{mu.server.bound_port}"
        rs = GenerationReplicaSet([addr, addr], "lm", disaggregate=True)
        got = list(rs.generate(prompt, 5))
        assert got == want
        assert rs.disagg_fallbacks == 1 and rs.disagg_handoffs == 0
    finally:
        if rs is not None:
            rs.close()
        mu.shutdown()
        cbu.shutdown()


def test_disagg_prefill_side_affinity_keeps_prompt_kv_home(lm):
    """ROADMAP item 1 follow-up (b): with prefix affinity on, the
    prefill-side pick rendezvous-ranks WITHIN the prefill role — every
    request sharing a prompt prefix runs its prefill on the SAME
    prefill replica (its prefix cache / host tier stay warm), instead
    of the load-only spread that paid one cold prefill per replica."""
    from tpulab.rpc.replica import GenerationReplicaSet
    mp1, cbp1 = _serve(lm, "prefill")
    mp2, cbp2 = _serve(lm, "prefill")
    md, cbd = _serve(lm, "decode")
    rs = None
    try:
        rng = np.random.default_rng(11)
        prefix = rng.integers(0, 64, (12,), np.int32)
        addrs = [f"127.0.0.1:{m.server.bound_port}"
                 for m in (mp1, mp2, md)]
        rs = GenerationReplicaSet(addrs, "lm", disaggregate=True,
                                  prefix_affinity=True,
                                  affinity_tokens=12)
        rs.poll_load()
        for k in range(4):  # same prefix, unique suffix, 4 requests
            prompt = np.concatenate(
                [prefix, rng.integers(0, 64, (2,), np.int32)])
            toks = list(rs.generate(prompt.astype(np.int32), 5))
            assert len(toks) == 5
        assert rs.disagg_handoffs == 4 and rs.disagg_fallbacks == 0
        # ALL prefills landed on the prefix's one home replica
        counts = sorted([cbp1.dispatch_kinds["mixed"],
                         cbp2.dispatch_kinds["mixed"]])
        assert counts == [0, 4], counts
        assert cbd.dispatch_kinds["mixed"] == 0   # decode stayed shipped-only
    finally:
        if rs is not None:
            rs.close()
        for m in (mp1, mp2, md):
            m.shutdown()
        for c in (cbp1, cbp2, cbd):
            c.shutdown()

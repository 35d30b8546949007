"""TPU device-layer tests (reference cuda/tests: allocators, memory,
device_info) — hermetic on the CPU backend."""

import asyncio

import numpy as np
import pytest

import tpulab.memory as tm
import tpulab.tpu as tt
from tpulab.tpu.allocators import TpuRawAllocator
from tpulab.tpu.device_info import DeviceInfo


def test_platform_devices():
    assert tt.device_count() >= 8  # virtual CPU mesh from conftest
    assert tt.platform_name() == "cpu"
    assert not tt.is_tpu()


def test_device_info():
    assert DeviceInfo.count() >= 8
    assert isinstance(DeviceInfo.device_kind(), str)
    info = DeviceInfo.memory_info()
    assert info.bytes_in_use is None or info.bytes_in_use >= 0
    attrs = DeviceInfo.attributes()
    assert attrs["platform"] == "cpu" and "id" in attrs
    assert DeviceInfo.alignment() == 512
    assert len(DeviceInfo.cpu_affinity()) >= 1


def test_peak_flops_table(monkeypatch):
    # CPU backend: no entry -> None (MFU rows are skipped, not wrong)
    assert DeviceInfo.peak_flops("bf16") is None

    class _Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    from tpulab.tpu import device_info as di
    # exact device_kind keys: the string a v5e chip reports resolves...
    monkeypatch.setattr(di.plat, "local_device", lambda i=0: _Dev("TPU v5 lite"))
    assert DeviceInfo.peak_flops("bf16") == 197e12
    assert DeviceInfo.peak_flops("int8") == 2 * 197e12
    # ...and is never confused with v5p by substring
    monkeypatch.setattr(di.plat, "local_device", lambda i=0: _Dev("TPU v5"))
    assert DeviceInfo.peak_flops("bf16") == 459e12
    # an unknown TPU kind is an error, not a default
    monkeypatch.setattr(di.plat, "local_device",
                        lambda i=0: _Dev("TPU v5 experimental"))
    with pytest.raises(KeyError, match="TPU v5 experimental"):
        DeviceInfo.peak_flops("bf16")


def test_tpu_memory_types():
    assert not tt.TpuMemory.host_accessible
    assert tt.TpuMemory.access_alignment == 512
    assert tt.HostPinnedMemory.host_accessible
    per_dev = tt.make_tpu_memory_type(3)
    assert per_dev.name == "tpu:3"


def test_tpu_raw_allocator_blocks():
    raw = tt.make_tpu_allocator()
    addr = raw.allocate_node(1024)
    buf = raw.buffer(addr)
    assert buf.shape == (1024,) and buf.dtype == np.uint8
    # offsets within the block resolve to the same buffer
    assert raw.buffer(addr + 512) is buf
    raw.deallocate_node(addr)
    assert raw.live_allocations == 0
    with pytest.raises(Exception):
        raw.buffer(addr)


def test_tpu_allocator_composes_with_framework():
    """The whole arena stack works over HBM blocks (SURVEY §2.1 TPU note)."""
    raw = tt.make_tpu_allocator()
    arena = tm.BlockArena(tm.FixedSizeBlockAllocator(raw, 4096), cached=True)
    b = arena.allocate_block()
    assert b.size == 4096
    arena.deallocate_block(b)
    b2 = arena.allocate_block()
    assert b2.addr == b.addr  # recycled without re-materializing on device
    arena.deallocate_block(b2)
    arena.shrink_to_fit()
    assert raw.live_allocations == 0


def test_staging_allocator_pinned_properties():
    alloc = tt.make_staging_allocator()
    addr = alloc.allocate_node(1000)
    assert addr % 4096 == 0  # page-aligned
    view = alloc.view(addr, 1000)
    assert bytes(view[:8]) == b"\x00" * 8  # first-touched
    alloc.deallocate_node(addr, 1000)


def test_copy_roundtrip():
    host = np.arange(128, dtype=np.float32)
    dev = tt.copy_to_device(host)
    back = tt.copy_to_host(dev)
    np.testing.assert_array_equal(host, back)
    out = np.empty_like(host)
    tt.copy_to_host(dev, out)
    np.testing.assert_array_equal(host, out)


def test_copy_device_to_device():
    import jax
    d0, d1 = jax.devices()[0], jax.devices()[1]
    x = tt.copy_to_device(np.ones(16, np.float32), d0)
    y = tt.copy_device_to_device(x, d1)
    assert y.devices() == {d1}
    np.testing.assert_array_equal(np.asarray(y), np.ones(16, np.float32))


def test_sync_standard_and_async():
    import jax.numpy as jnp
    x = jnp.ones((32, 32)) @ jnp.ones((32, 32))
    tt.tpu_sync_standard(x)
    assert x.is_ready()

    async def scenario():
        y = jnp.ones((16, 16)) * 3
        await tt.tpu_sync_async({"out": y})
        return float(y[0, 0])

    assert asyncio.run(scenario()) == 3.0


def test_tpu_cyclic_windowed_stack():
    from tpulab.tpu.cyclic_buffer import TpuCyclicWindowedStack
    alloc = tm.make_allocator(tm.MallocAllocator())
    buf = alloc.allocate_descriptor(4 * 64)
    seen = []

    def compute(wid, dev):
        seen.append((wid, float(dev.astype(np.float32).sum())))
        return dev

    stack = TpuCyclicWindowedStack(buf, window_count=4, window_size=64,
                                   overlap=0, compute_fn=compute)
    stack.append(bytes([1] * 256))
    stack.sync_all()
    assert [w for w, _ in seen] == [0, 1, 2, 3]
    assert all(s == 64.0 for _, s in seen)
    stack.release()


def test_transfer_engine_direct_mode():
    import jax.numpy as jnp
    from tpulab.tpu.transfer import TransferEngine
    eng = TransferEngine()
    try:
        trees = [{"a": jnp.full((8,), i, jnp.float32), "n": i}
                 for i in range(10)]
        futs = [eng.fetch(t) for t in trees]
        outs = [f.result(timeout=30) for f in futs]
        for i, out in enumerate(outs):
            assert isinstance(out["a"], np.ndarray)
            assert out["a"][0] == i and out["n"] == i  # non-arrays pass through
    finally:
        eng.shutdown()


def test_transfer_engine_stack_mode_groups_same_shape():
    import jax.numpy as jnp
    from tpulab.tpu.transfer import TransferEngine
    eng = TransferEngine(mode="stack")
    try:
        futs = [eng.fetch(jnp.full((4, 4), i, jnp.float32)) for i in range(9)]
        outs = [f.result(timeout=30) for f in futs]
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(out, np.full((4, 4), i, np.float32))
    finally:
        eng.shutdown()


def test_transfer_engine_rejects_after_shutdown():
    from tpulab.tpu.transfer import TransferEngine
    eng = TransferEngine()
    eng.shutdown()
    with pytest.raises(RuntimeError):
        eng.fetch({"x": np.zeros(2)})


def test_event_poller_fires_on_ready():
    import threading
    import jax.numpy as jnp
    from tpulab.tpu.sync import EventPoller
    poller = EventPoller(interval_s=0.001)
    try:
        done = threading.Event()
        x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
        poller.watch({"out": x}, done.set)
        assert done.wait(timeout=10)
        # plain values (no is_ready) fire immediately
        done2 = threading.Event()
        poller.watch({"n": 3}, done2.set)
        assert done2.wait(timeout=10)
    finally:
        poller.shutdown()


def test_benchmark_workspace_run():
    from tpulab.engine import BenchmarkWorkspace
    from tpulab.models.mnist import make_mnist
    ws = BenchmarkWorkspace(make_mnist(max_batch_size=2), batch_size=2)
    ws.host_inputs["Input3"][:] = 0.5
    ws.run()
    ws.synchronize()
    ws.async_d2h()
    assert np.isfinite(ws.host_outputs["Plus214_Output_0"]).all()


def test_transfer_engine_put_coalesced():
    import jax
    import jax.numpy as jnp
    from tpulab.tpu.transfer import TransferEngine
    eng = TransferEngine()
    try:
        dev = jax.devices()[0]
        trees = [{"x": np.full((4,), i, np.float32)} for i in range(6)]
        futs = [eng.put(t, dev) for t in trees]
        outs = [f.result(timeout=30) for f in futs]
        for i, out in enumerate(outs):
            assert out["x"].devices() == {dev}
            np.testing.assert_array_equal(np.asarray(out["x"]),
                                          np.full((4,), i, np.float32))
        # mixed puts + fetches in one engine
        pf = eng.put({"y": np.ones(3, np.float32)}, dev)
        ff = eng.fetch({"z": jnp.full((2,), 9.0)})
        assert pf.result(timeout=30)["y"].devices() == {dev}
        assert ff.result(timeout=30)["z"][0] == 9.0
    finally:
        eng.shutdown()


# -- HBM accounting through the device allocator framework -------------------

def test_tpu_allocator_typed_nodes_and_accounting():
    import numpy as np
    from tpulab.tpu.allocators import TpuRawAllocator, make_tpu_allocator

    alloc = make_tpu_allocator()
    base = alloc.bytes_in_use
    addr, arr = alloc.allocate_array((4, 8), np.float32)
    assert arr.shape == (4, 8)
    assert alloc.bytes_in_use == base + 4 * 8 * 4
    taddr, tree = alloc.allocate_tree({"w": np.zeros((2, 2), np.float32),
                                       "b": np.zeros((2,), np.float32)})
    assert alloc.bytes_in_use == base + 4 * 8 * 4 + (4 + 2) * 4
    assert TpuRawAllocator.total_bytes_in_use() >= alloc.bytes_in_use
    # donation-rotation: replace keeps the accounting slot
    import jax.numpy as jnp
    addr2 = alloc.replace(addr, jnp.ones((4, 8), jnp.float32))
    assert addr2 is not None and alloc.bytes_in_use == base + 128 + 24
    alloc.deallocate_node(addr)
    alloc.deallocate_node(taddr)
    assert alloc.bytes_in_use == base


def test_compiled_model_weights_are_tracked():
    from tpulab.engine.runtime import Runtime
    from tpulab.models.mnist import make_mnist

    rt = Runtime()
    model = make_mnist(max_batch_size=2)
    compiled = rt.compile_model(model)
    assert compiled.weights_addr is not None
    assert rt.allocator.bytes_in_use >= model.weights_size_in_bytes()
    compiled.release_weights()
    assert rt.allocator.bytes_in_use == 0


def test_paged_pool_hbm_tracked_and_closed():
    import jax.numpy as jnp
    from tpulab.engine.kv_pool import PagedKVPool

    pool = PagedKVPool(n_pages=4, page_size=8, n_layers=2, n_heads=2,
                       head_dim=4, dtype=jnp.float32)
    expect = (2 * 4 * 2 * 8 * 2 * 4) * 4  # fused (L,P,2,S,H,D) * itemsize
    assert pool.hbm_bytes == expect
    # setter keeps accounting through a rotation
    pool.kv = jnp.ones_like(pool.kv)
    assert pool.hbm_bytes == expect
    pool.close()
    assert pool.hbm_bytes == 0


def test_failed_compile_does_not_leak_weights():
    import numpy as np
    import pytest
    from tpulab.engine.model import IOSpec, Model
    from tpulab.engine.runtime import Runtime

    rt = Runtime()

    def bad_apply(params, inputs):
        raise ValueError("boom")

    model = Model("bad", bad_apply, {"w": np.zeros((1024,), np.float32)},
                  [IOSpec("x", (4,), np.float32)],
                  [IOSpec("y", (4,), np.float32)], max_batch_size=1,
                  batch_buckets=[1])
    before = rt.allocator.bytes_in_use
    with pytest.raises(Exception):
        rt.compile_model(model)
    assert rt.allocator.bytes_in_use == before, \
        "failed compile pinned a weight copy in the allocator"

"""Memory framework tests — mirrors the coverage matrix of the reference's
memory/tests/test_memory.cc (40 tests: traits, block allocators, descriptors,
arenas, transactional, huge pages, pool, bfit, trackers, iallocator)."""

import gc

import numpy as np
import pytest

from helpers_engine import join_all
from tpulab import memory as tm
from tpulab.memory.raw_allocators import FirstTouchAllocator


# ---------------------------------------------------------------- literals ---
def test_literals():
    assert tm.KiB == 1024 and tm.MiB == 1024 ** 2 and tm.GiB == 1024 ** 3


@pytest.mark.parametrize("s,expected", [
    ("10MiB", 10 * tm.MiB), ("1.5KiB", 1536), ("2gb", 2 * 10 ** 9),
    ("128", 128), (4096, 4096), ("7 B", 7),
])
def test_string_to_bytes(s, expected):
    assert tm.string_to_bytes(s) == expected


def test_string_to_bytes_rejects_garbage():
    with pytest.raises(ValueError):
        tm.string_to_bytes("ten megs")


def test_bytes_to_string_roundtrip_style():
    assert tm.bytes_to_string(512) == "512 B"
    assert tm.bytes_to_string(10 * tm.MiB) == "10.00 MiB"


# ------------------------------------------------------------------ traits ---
def test_memory_type_traits():
    assert tm.is_memory_type(tm.HostMemory)
    assert not tm.is_memory_type(object())
    assert tm.is_host_accessible(tm.HostMemory)
    assert tm.HostMemory.min_allocation_alignment == 8


def test_raw_allocator_concept():
    raw = tm.MallocAllocator()
    assert raw.memory_type is tm.HostMemory
    addr = raw.allocate_node(128, 64)
    assert addr % 64 == 0
    raw.deallocate_node(addr, 128, 64)
    assert raw.live_allocations == 0


def test_aligned_allocator():
    raw = tm.AlignedAllocator(4096)
    addr = raw.allocate_node(100)
    assert addr % 4096 == 0
    raw.deallocate_node(addr, 100)


def test_huge_page_allocator():
    raw = tm.HugePageAllocator()
    addr = raw.allocate_node(100)
    assert addr % tm.HugePageAllocator.HUGE_PAGE_SIZE == 0
    raw.deallocate_node(addr, 100)


def test_first_touch_allocator():
    raw = FirstTouchAllocator(fill=0)
    addr = raw.allocate_node(4096)
    view = raw.view(addr, 4096)
    assert bytes(view[:16]) == b"\x00" * 16
    raw.deallocate_node(addr, 4096)


def test_invalid_free_raises():
    raw = tm.MallocAllocator()
    with pytest.raises(Exception):
        raw.deallocate_node(0xdead, 8)


# -------------------------------------------------------- block allocators ---
def test_single_block_allocator():
    raw = tm.MallocAllocator()
    ba = tm.SingleBlockAllocator(raw, 4096)
    assert tm.is_block_allocator(ba)
    b = ba.allocate_block()
    assert b.size == 4096
    with pytest.raises(tm.OutOfMemory):
        ba.allocate_block()
    ba.deallocate_block(b)
    b2 = ba.allocate_block()  # usable again after free
    ba.deallocate_block(b2)


def test_fixed_size_block_allocator():
    ba = tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 1024)
    blocks = [ba.allocate_block() for _ in range(4)]
    assert all(b.size == 1024 for b in blocks)
    for b in blocks:
        ba.deallocate_block(b)


def test_growing_block_allocator():
    ba = tm.GrowingBlockAllocator(tm.MallocAllocator(), 1024, growth_factor=2.0)
    b1, b2, b3 = ba.allocate_block(), ba.allocate_block(), ba.allocate_block()
    assert (b1.size, b2.size, b3.size) == (1024, 2048, 4096)
    for b in (b1, b2, b3):
        ba.deallocate_block(b)


def test_count_limited_block_allocator():
    ba = tm.CountLimitedBlockAllocator(
        tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 1024), max_blocks=2)
    b1, b2 = ba.allocate_block(), ba.allocate_block()
    with pytest.raises(tm.OutOfMemory):
        ba.allocate_block()
    ba.deallocate_block(b1)
    b3 = ba.allocate_block()
    ba.deallocate_block(b2)
    ba.deallocate_block(b3)


def test_size_limited_block_allocator():
    ba = tm.SizeLimitedBlockAllocator(
        tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 1024), max_bytes=2048)
    b1, b2 = ba.allocate_block(), ba.allocate_block()
    with pytest.raises(tm.OutOfMemory):
        ba.allocate_block()
    assert ba.allocated_bytes == 2048
    ba.deallocate_block(b1)
    ba.deallocate_block(b2)


# ------------------------------------------------------------- descriptors ---
def test_descriptor_lifecycle():
    alloc = tm.make_allocator(tm.MallocAllocator())
    d = alloc.allocate_descriptor(256, 64)
    assert d.size == 256 and d.addr % 64 == 0
    view = d.memoryview()
    view[:4] = b"abcd"
    assert d.numpy(np.uint8)[:4].tobytes() == b"abcd"
    d.release()
    with pytest.raises(Exception):
        _ = d.addr  # released descriptors are dead


def test_descriptor_context_manager_and_gc():
    raw = tm.MallocAllocator()
    alloc = tm.make_allocator(raw)
    with alloc.allocate_descriptor(64) as d:
        assert len(d) == 64
    assert raw.live_allocations == 0
    d2 = alloc.allocate_descriptor(64)
    del d2
    gc.collect()
    assert raw.live_allocations == 0  # finalizer reclaimed


def test_descriptor_numpy_shape():
    alloc = tm.make_allocator(tm.MallocAllocator())
    with alloc.allocate_descriptor(4 * 6) as d:
        arr = d.numpy(np.float32, (2, 3))
        arr[:] = 7.0
        assert d.numpy(np.float32, (6,)).sum() == pytest.approx(42.0)


def test_shared_descriptor_refcount():
    raw = tm.MallocAllocator()
    alloc = tm.make_allocator(raw)
    d = alloc.allocate_descriptor(64)
    s = d.share()
    s2 = s.ref()
    s.unref()
    assert raw.live_allocations == 1
    s2.unref()
    assert raw.live_allocations == 0


# ------------------------------------------------------------------ arenas ---
def test_cached_arena_recycles_blocks():
    raw = tm.MallocAllocator()
    arena = tm.BlockArena(tm.FixedSizeBlockAllocator(raw, 4096), cached=True)
    b = arena.allocate_block()
    arena.deallocate_block(b)
    assert arena.cached_blocks == 1
    b2 = arena.allocate_block()
    assert b2.addr == b.addr  # recycled, not re-mapped
    arena.deallocate_block(b2)
    assert raw.live_allocations == 1
    arena.shrink_to_fit()
    assert raw.live_allocations == 0


def test_uncached_arena_passes_through():
    raw = tm.MallocAllocator()
    arena = tm.BlockArena(tm.FixedSizeBlockAllocator(raw, 4096), cached=False)
    b = arena.allocate_block()
    arena.deallocate_block(b)
    assert arena.cached_blocks == 0
    assert raw.live_allocations == 0


def test_block_stack_carving():
    arena = tm.BlockArena(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    stack = tm.BlockStack(arena)
    a1 = stack.allocate(1000, 256)
    a2 = stack.allocate(1000, 256)
    assert a1 % 256 == 0 and a2 % 256 == 0 and a2 > a1
    assert stack.depth == 1
    stack.allocate(3000, 256)  # forces a second block
    assert stack.depth == 2
    stack.reset()
    assert stack.depth == 0


def test_block_stack_oversize_rejected():
    arena = tm.BlockArena(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    stack = tm.BlockStack(arena)
    with pytest.raises(tm.OutOfMemory):
        stack.allocate(8192)


def test_block_manager_lookup():
    mgr = tm.BlockManager()
    from tpulab.memory.block import MemoryBlock
    mgr.add_block(MemoryBlock(0x1000, 0x100))
    mgr.add_block(MemoryBlock(0x3000, 0x100))
    assert mgr.find_block(0x1080).addr == 0x1000
    assert mgr.find_block(0x2000) is None
    assert mgr.owns(0x30ff) and not mgr.owns(0x3100)
    mgr.drop_block(0x1000)
    assert mgr.find_block(0x1080) is None
    assert mgr.size == 1


# ----------------------------------------------------------- transactional ---
def test_transactional_bump_and_rotate():
    arena = tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096)
    t = tm.make_transactional_allocator(arena)
    a1 = t.allocate_node(1024)
    a2 = t.allocate_node(1024)
    assert a2 == a1 + 1024  # O(1) bump within a stack
    a3 = t.allocate_node(3000)  # forces rotation
    assert t.live_stacks == 2
    t.deallocate_node(a1)
    t.deallocate_node(a2)
    assert t.live_stacks == 1  # retired stack released when drained
    t.deallocate_node(a3)


def test_transactional_whole_stack_release():
    raw = tm.MallocAllocator()
    t = tm.TransactionalAllocator(tm.FixedSizeBlockAllocator(raw, 4096))
    addrs = [t.allocate_node(512) for _ in range(8)]  # exactly one stack
    assert t.live_stacks == 1
    for a in addrs[:-1]:
        t.deallocate_node(a)
    assert t.live_stacks == 1  # current stack stays while live
    t.allocate_node(4096)      # rotation retires the old stack
    t.deallocate_node(addrs[-1])
    assert t.live_stacks == 1  # old stack fully drained and released


def test_transactional_oversize():
    t = tm.TransactionalAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    with pytest.raises(tm.BadAllocationSize):
        t.allocate_node(8192)


def test_transactional_descriptors():
    t = tm.TransactionalAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    with t.allocate_descriptor(256) as d:
        d.memoryview()[:3] = b"tpu"
    assert t.live_stacks == 1  # current stack retained for reuse


def test_transactional_thread_safety():
    import threading
    t = tm.TransactionalAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 1 << 16))
    errors = []

    def worker():
        try:
            for _ in range(200):
                a = t.allocate_node(64)
                t.deallocate_node(a)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [th.start() for th in threads]
    join_all(threads)
    assert not errors


# ------------------------------------------------------------------- pools ---
def test_memory_pool_basics():
    pool = tm.MemoryPool(256, tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    a = pool.allocate_node()
    b = pool.allocate_node()
    assert a != b
    pool.deallocate_node(a)
    c = pool.allocate_node()
    assert c == a  # LIFO free list
    pool.deallocate_node(b)
    pool.deallocate_node(c)
    pool.close()


def test_memory_pool_array():
    pool = tm.MemoryPool(256, tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    addr = pool.allocate_array(4)
    pool.deallocate_array(addr, 4)
    pool.close()


def test_memory_pool_leak_report():
    leaks = []
    old = tm.set_leak_handler(lambda name, n: leaks.append((name, n)))
    try:
        pool = tm.MemoryPool(256, tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
        pool.allocate_node()
        pool.close()
    finally:
        tm.set_leak_handler(old)
    assert leaks and leaks[0][1] == 256


# -------------------------------------------------------------------- bfit ---
def test_bfit_best_fit_and_coalesce():
    bf = tm.BFitAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 1 << 16))
    a = bf.allocate_node(1000)
    b = bf.allocate_node(2000)
    c = bf.allocate_node(500)
    bf.deallocate_node(b)
    # best fit should reuse the 2000-hole for a 1500 request
    d = bf.allocate_node(1500)
    assert d == b
    bf.deallocate_node(a)
    bf.deallocate_node(c)
    bf.deallocate_node(d)
    # all free spans coalesced back into one block-sized span
    assert bf.free_bytes == 1 << 16
    assert len(bf._free_by_addr) == 1


def test_bfit_alignment():
    bf = tm.BFitAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 1 << 16))
    a = bf.allocate_node(100, alignment=4096)
    assert a % 4096 == 0
    bf.deallocate_node(a)


def test_bfit_no_grow_exhaustion():
    bf = tm.BFitAllocator(
        tm.SingleBlockAllocator(tm.MallocAllocator(), 4096), grow_on_demand=True)
    a = bf.allocate_node(4096)
    with pytest.raises(tm.OutOfMemory):
        bf.allocate_node(1)
    bf.deallocate_node(a)


# ---------------------------------------------------------------- trackers ---
def test_size_tracker():
    raw = tm.SizeTracker(tm.MallocAllocator())
    alloc = tm.make_allocator(raw)
    d1 = alloc.allocate_descriptor(1000)
    d2 = alloc.allocate_descriptor(500)
    assert raw.bytes_in_use == 1500 and raw.peak_bytes == 1500
    d1.release()
    assert raw.bytes_in_use == 500
    d2.release()
    assert raw.bytes_in_use == 0 and raw.total_allocations == 2


def test_tracked_block_allocator():
    events = []
    ba = tm.TrackedBlockAllocator(
        tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096),
        on_allocate=lambda b: events.append(("+", b.size)),
        on_deallocate=lambda b: events.append(("-", b.size)))
    b = ba.allocate_block()
    ba.deallocate_block(b)
    assert events == [("+", 4096), ("-", 4096)]
    assert ba.bytes_in_use == 0


# -------------------------------------------------------------- iallocator ---
def test_make_allocator_is_idempotent():
    alloc = tm.make_allocator(tm.MallocAllocator())
    assert tm.make_allocator(alloc) is alloc


def test_iallocator_device_context():
    alloc = tm.make_allocator(tm.MallocAllocator())
    dev_type, dev_id = alloc.device_context()
    assert int(dev_type) == 1 and dev_id == 0  # kDLCPU


def test_raii_allocator_reclaims():
    raw = tm.MallocAllocator()
    leaks = []
    old = tm.set_leak_handler(lambda name, n: leaks.append(n))
    try:
        with tm.RaiiAllocator(tm.make_allocator(raw)) as ra:
            ra.allocate(128)
            ra.allocate(128)
            assert ra.live_allocations == 2
        assert raw.live_allocations == 0  # reclaimed on close
    finally:
        tm.set_leak_handler(old)
    assert leaks == [256]


# -------------------------------------------- regression: review findings ---
def test_block_stack_pop_preserves_lower_cursor():
    """pop() must not reset the cursor of the uncovered block (review finding)."""
    arena = tm.BlockArena(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    stack = tm.BlockStack(arena)
    a1 = stack.allocate(1000)
    stack.allocate(3500)           # pushes block B
    stack.pop()                    # drops B
    a2 = stack.allocate(100)
    assert a2 >= a1 + 1000         # must not alias the live allocation
    stack.reset()


def test_transactional_max_stacks_enforced():
    t = tm.TransactionalAllocator(
        tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096), max_stacks=2)
    held = [t.allocate_node(4096), t.allocate_node(4096)]  # 2 full stacks, referenced
    with pytest.raises(tm.OutOfMemory):
        t.allocate_node(4096)
    for a in held:
        t.deallocate_node(a)


def test_transactional_rejects_zero_size():
    t = tm.TransactionalAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    with pytest.raises(tm.BadAllocationSize):
        t.allocate_node(0)


def test_cached_arena_respects_growing_block_size():
    """Cache must not serve a too-small block when next_block_size grew."""
    raw = tm.MallocAllocator()
    ga = tm.GrowingBlockAllocator(raw, 4096, growth_factor=2.0)
    arena = tm.BlockArena(ga, cached=True)
    b1 = arena.allocate_block()          # 4096; next is 8192
    arena.deallocate_block(b1)           # 4096 block cached
    b2 = arena.allocate_block()          # needs >= 8192 now
    assert b2.size >= 8192
    arena.deallocate_block(b2)
    arena.shrink_to_fit()


def test_bfit_single_grow_satisfies():
    bf = tm.BFitAllocator(tm.FixedSizeBlockAllocator(tm.MallocAllocator(), 4096))
    a = bf.allocate_node(4096)           # grows once, satisfied
    b = bf.allocate_node(4096)           # grows again, satisfied
    bf.deallocate_node(a)
    bf.deallocate_node(b)


def test_detach_after_release_raises():
    alloc = tm.make_allocator(tm.MallocAllocator())
    d = alloc.allocate_descriptor(64)
    d.release()
    with pytest.raises(Exception):
        d.detach()


# ------------------------------------------------------------ shared memory --
def test_shared_memory_cross_process():
    """Producer process fills a named segment; we read it zero-copy
    (reference SysV shm ingress, examples/02 server.cc:110-137)."""
    import subprocess
    import sys
    from tpulab.memory.shm import SharedMemoryAllocator

    alloc = SharedMemoryAllocator()
    addr = alloc.allocate_node(4096)
    name = alloc.segment_name(addr)
    code = (
        "from tpulab.memory.shm import SharedMemoryAllocator;"
        f"seg = SharedMemoryAllocator.attach('{name}');"
        "seg.numpy()[:8] = list(range(8)); seg.close()"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    view = alloc.view(addr, 4096)
    assert bytes(view[:8]) == bytes(range(8))
    alloc.deallocate_node(addr)


def test_shared_memory_attach_and_descriptors():
    from tpulab.memory.allocator import make_allocator
    from tpulab.memory.shm import SharedMemoryAllocator

    alloc_raw = SharedMemoryAllocator()
    alloc = make_allocator(alloc_raw)
    d = alloc.allocate_descriptor(1024)
    arr = d.numpy(np.float32, (256,))
    arr[:] = 2.5
    with SharedMemoryAllocator.attach(
            alloc_raw.segment_name(d.addr)) as seg:
        peer = seg.numpy(np.float32, (256,))
        assert peer.sum() == 640.0
    d.release()
    with pytest.raises(Exception):
        alloc_raw.deallocate_node(0x1234)
    alloc_raw.close()

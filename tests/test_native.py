"""Python-side tests of the native runtime core (skipped when cpp/ is not
built).  Verifies the RawAllocator-concept adapters compose with the Python
memory framework exactly like the pure-Python allocators."""

import threading

import numpy as np
import pytest

from helpers_engine import join_all
from tpulab import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library not built")


def test_version():
    assert native.version().startswith("tpulab-native")


def test_native_arena_recycles():
    arena = native.NativeArena(4096, max_blocks=2)
    b = arena.allocate_block()
    arena.deallocate_block(b)
    assert arena.cached_blocks == 1
    b2 = arena.allocate_block()
    assert b2.addr == b.addr
    arena.deallocate_block(b2)
    arena.shrink_to_fit()
    arena.close()


def test_native_transactional_raw():
    tx = native.NativeTransactionalAllocator(block_size=1 << 16)
    a = tx.allocate_node(256)
    b = tx.allocate_node(256)
    assert b > a
    tx.deallocate_node(a)
    tx.deallocate_node(b)
    with pytest.raises(Exception):
        tx.allocate_node(1 << 20)  # oversize
    tx.close()


def test_native_transactional_with_descriptors():
    """Native allocator under the Python descriptor framework."""
    from tpulab.memory.allocator import make_allocator
    tx = native.NativeTransactionalAllocator(block_size=1 << 16)
    alloc = make_allocator(tx)
    with alloc.allocate_descriptor(1024, 64) as d:
        arr = d.numpy(np.float32, (256,))
        arr[:] = 3.0
        assert arr.sum() == 768.0
    tx.close()


def test_native_transactional_threads():
    tx = native.NativeTransactionalAllocator(block_size=1 << 20)
    errors = []

    def worker():
        try:
            for _ in range(500):
                a = tx.allocate_node(128)
                tx.deallocate_node(a)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [t.start() for t in threads]
    join_all(threads)
    assert not errors
    tx.close()


def test_native_bfit():
    bf = native.NativeBFitAllocator(block_size=1 << 16)
    a = bf.allocate_node(1000)
    b = bf.allocate_node(2000)
    bf.deallocate_node(b)
    d = bf.allocate_node(1500)
    assert d == b  # best-fit reuse
    bf.deallocate_node(a)
    bf.deallocate_node(d)
    assert bf.free_bytes == 1 << 16  # coalesced
    bf.close()


def test_native_token_pool():
    pool = native.NativeTokenPool()
    pool.push(42)
    assert pool.pop() == 42
    with pytest.raises(TimeoutError):
        pool.pop(timeout=0.02)
    results = []

    def popper():
        results.append(pool.pop(timeout=2))

    t = threading.Thread(target=popper)
    t.start()
    pool.push(7)
    t.join(timeout=5)
    assert results == [7]
    pool.close()

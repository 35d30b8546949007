"""Pool tests (reference core/tests/test_pool.cc: v1-v4 semantics,
deleter-return trick)."""

import asyncio
import gc
import threading
import time

import pytest

from helpers_engine import join_all
from tpulab.core import Pool, Queue, UniquePool


def test_queue_fifo_and_timeout():
    q = Queue()
    q.push(1)
    q.push(2)
    assert q.pop() == 1 and q.pop() == 2
    with pytest.raises(TimeoutError):
        q.pop(timeout=0.05)


def test_pool_pop_returns_on_close():
    pool = Pool(["a", "b"])
    item = pool.pop()
    assert item.get() in ("a", "b")
    assert pool.available == 1
    item.release()
    assert pool.available == 2


def test_pool_context_manager_return():
    pool = Pool([1])
    with pool.pop() as v:
        assert v == 1
        assert pool.available == 0
    assert pool.available == 1


def test_pool_gc_returns_item():
    """The v1 deleter trick: dropping the handle returns the resource."""
    pool = Pool(["x"])
    item = pool.pop()
    del item
    gc.collect()
    assert pool.available == 1


def test_pool_blocking_backpressure():
    pool = Pool([1])
    item = pool.pop()
    results = []

    def blocked_popper():
        got = pool.pop(timeout=2)
        results.append(got.get())
        got.release()

    t = threading.Thread(target=blocked_popper)
    t.start()
    time.sleep(0.05)
    assert not results  # still blocked — backpressure
    item.release()
    t.join(timeout=2)
    assert results == [1]


def test_pool_on_return_reset_hook():
    resets = []
    pool = Pool([{"n": 0}], on_return=lambda d: resets.append(d["n"]))
    with pool.pop() as d:
        d["n"] = 7
    assert resets == [7]


def test_pool_per_pop_on_return():
    events = []
    pool = Pool([1])
    item = pool.pop(on_return=lambda v: events.append(("extra", v)))
    item.release()
    assert events == [("extra", 1)]


def test_pool_detach_removes_resource():
    pool = Pool([1, 2])
    item = pool.pop()
    item.detach()
    del item
    gc.collect()
    assert pool.available == 1  # detached item never came back


def test_unique_pool_pop_unique():
    pool = UniquePool([1])
    item = pool.pop_unique()
    assert item.get() == 1
    item.release()
    assert pool.available == 1


def test_pool_pop_async_event_loop():
    """The fiber-policy pop: waiters awaken without blocking the loop."""
    pool = Pool([1])

    async def scenario():
        i1 = await pool.pop_async()
        waiter = asyncio.ensure_future(pool.pop_async())
        await asyncio.sleep(0.02)
        assert not waiter.done()  # blocked on empty pool
        i1.release()              # wakes the waiter via call_soon_threadsafe
        i2 = await asyncio.wait_for(waiter, timeout=2)
        assert i2.get() == 1
        i2.release()

    asyncio.run(scenario())


def test_pool_async_cancelled_waiter_requeues():
    pool = Pool([1])

    async def scenario():
        i1 = await pool.pop_async()
        waiter = asyncio.ensure_future(pool.pop_async())
        await asyncio.sleep(0.01)
        waiter.cancel()
        await asyncio.sleep(0.01)
        i1.release()
        await asyncio.sleep(0.05)
        assert pool.available == 1  # resource not lost to cancelled waiter

    asyncio.run(scenario())


def test_pool_concurrent_stress():
    pool = Pool(range(4))
    counts = []

    def worker():
        for _ in range(50):
            with pool.pop(timeout=5) as v:
                counts.append(v)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [t.start() for t in threads]
    join_all(threads)
    assert len(counts) == 400
    assert pool.available == 4


# -- native-backed serving pool (cpp TokenPool behind Pool's surface) --------

def _native_pool_or_skip(items=()):
    import pytest
    from tpulab import native
    from tpulab.core.pool import NativeBackedPool
    if not native.available():
        pytest.skip("native library not built")
    return NativeBackedPool(items)


def test_native_backed_pool_raii_and_backpressure():
    pool = _native_pool_or_skip([1, 2])
    a = pool.pop()
    b = pool.pop()
    assert pool.available == 0 and pool.size == 2
    import pytest
    with pytest.raises(TimeoutError):
        pool.pop(timeout=0.05)
    a.release()
    c = pool.pop(timeout=1)
    assert c.get() in (1, 2)
    c.release()
    b.release()
    assert pool.available == 2


def test_native_backed_pool_on_return_hook():
    from tpulab import native
    from tpulab.core.pool import NativeBackedPool
    import pytest
    if not native.available():
        pytest.skip("native library not built")
    seen = []
    pool = NativeBackedPool(["x"], on_return=seen.append)
    pool.pop().release()
    assert seen == ["x"]


def test_native_backed_pool_concurrent_stress():
    pool = _native_pool_or_skip(range(4))
    counts = []
    lock = threading.Lock()

    def worker():
        for _ in range(50):
            with pool.pop(timeout=5) as v:
                with lock:
                    counts.append(v)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    [t.start() for t in threads]
    join_all(threads)
    assert len(counts) == 400
    assert pool.available == 4


def test_native_backed_pool_pop_async():
    pool = _native_pool_or_skip([1])

    async def scenario():
        i1 = await pool.pop_async()
        waiter = asyncio.ensure_future(pool.pop_async())
        await asyncio.sleep(0.05)
        assert not waiter.done()
        i1.release()
        i2 = await asyncio.wait_for(waiter, timeout=2)
        assert i2.get() == 1
        i2.release()

    asyncio.run(scenario())


def test_make_serving_pool_selection(monkeypatch):
    from tpulab import native
    from tpulab.core.pool import (NativeBackedPool, Pool, make_serving_pool)
    monkeypatch.setenv("TPULAB_NO_NATIVE", "1")
    assert type(make_serving_pool([1])) is Pool
    monkeypatch.delenv("TPULAB_NO_NATIVE")
    if native.available():
        assert type(make_serving_pool([1])) is NativeBackedPool


def test_native_backed_pool_pop_async_cancel_reclaims():
    """A cancelled pop_async waiter must not leak the slot its executor
    pop later wins."""
    pool = _native_pool_or_skip([1])

    async def scenario():
        i1 = await pool.pop_async()
        waiter = asyncio.ensure_future(pool.pop_async())
        await asyncio.sleep(0.1)  # waiter parked in the executor poll
        waiter.cancel()
        try:
            await waiter
        except asyncio.CancelledError:
            pass
        i1.release()
        # the executor poll wins the released slot and must re-return it
        for _ in range(100):
            if pool.available == 1:
                break
            await asyncio.sleep(0.05)
        assert pool.available == 1

    asyncio.run(scenario())

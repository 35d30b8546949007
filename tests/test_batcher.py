"""Batcher/Dispatcher tests (reference core/tests/test_batcher.cc: window
close by size and by timeout; FullBatcherUserThreads = the async dispatcher)."""

import asyncio
import threading
import time

import pytest

from helpers_engine import join_all
from tpulab.core import AsyncDispatcher, Dispatcher, StandardBatcher
from tpulab.core.async_compute import async_compute


def test_batcher_close_by_size():
    b = StandardBatcher(max_batch_size=3)
    f1 = b.enqueue("a")
    f2 = b.enqueue("b")
    assert b.update() is None          # not full yet
    f3 = b.enqueue("c")
    batch = b.update()
    assert batch is not None and batch.items == ["a", "b", "c"]
    assert f1 is f2 is f3              # one promise per batch
    batch.complete("done")
    assert f1.result(timeout=1) == "done"


def test_batcher_close_batch_timeout_path():
    b = StandardBatcher(max_batch_size=10)
    b.enqueue(1)
    batch = b.close_batch()
    assert batch is not None and batch.items == [1]
    assert b.empty()
    assert b.close_batch() is None     # nothing open


def test_batcher_new_batch_after_close():
    b = StandardBatcher(max_batch_size=2)
    f1 = b.enqueue(1)
    b.enqueue(2)
    first = b.update()
    f2 = b.enqueue(3)
    assert f1 is not f2                # new batch, new promise
    assert b.current_batch_id == first.batch_id + 1


def test_dispatcher_full_batch_executes():
    executed = []

    def execute(items, complete):
        executed.append(list(items))
        complete(sum(items))

    with Dispatcher(max_batch_size=4, window_s=5.0, execute_fn=execute) as d:
        futs = [d.enqueue(i) for i in range(4)]
        assert futs[0].result(timeout=2) == 6
    assert executed == [[0, 1, 2, 3]]


def test_dispatcher_window_timeout_fires():
    executed = []

    def execute(items, complete):
        executed.append(list(items))
        complete(len(items))

    with Dispatcher(max_batch_size=100, window_s=0.05, execute_fn=execute) as d:
        fut = d.enqueue("only")
        assert fut.result(timeout=2) == 1  # timeout closed the partial batch
    assert executed == [["only"]]


def test_dispatcher_stale_timer_ignored():
    """Batch closes by size before the window; the timer must not fire twice."""
    executed = []

    def execute(items, complete):
        executed.append(list(items))
        complete(None)

    with Dispatcher(max_batch_size=2, window_s=0.05, execute_fn=execute) as d:
        d.enqueue(1)
        d.enqueue(2)          # closes by size immediately
        time.sleep(0.15)      # let the stale timer fire
        d.enqueue(3)          # opens a new batch; closed by flush on exit
    assert [0, 1] == sorted(len(b) - 1 for b in executed[:2]) or executed
    assert sum(len(b) for b in executed) == 3


def test_dispatcher_execute_exception_fails_future():
    def execute(items, complete):
        raise RuntimeError("boom")

    with Dispatcher(max_batch_size=1, window_s=1.0, execute_fn=execute) as d:
        fut = d.enqueue(1)
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=2)


def test_dispatcher_concurrent_producers():
    lock = threading.Lock()
    total = []

    def execute(items, complete):
        with lock:
            total.extend(items)
        complete(None)

    with Dispatcher(max_batch_size=8, window_s=0.02, execute_fn=execute,
                    n_workers=2) as d:
        threads = [threading.Thread(
            target=lambda base=b: [d.enqueue(base * 100 + i) for i in range(25)])
            for b in range(4)]
        [t.start() for t in threads]
        join_all(threads)
        time.sleep(0.3)
    assert sorted(total) == sorted(b * 100 + i for b in range(4) for i in range(25))


def test_async_dispatcher_fiber_analog():
    """The userspace-threads specialization (reference FullBatcherUserThreads)."""
    executed = []

    async def scenario():
        async def execute(items, complete):
            await asyncio.sleep(0.01)   # may await device/pool readiness
            executed.append(list(items))
            complete(len(items))

        d = AsyncDispatcher(max_batch_size=2, window_s=0.05, execute_fn=execute)
        f1 = d.enqueue("a")
        f2 = d.enqueue("b")             # closes by size
        assert await asyncio.wait_for(f1, 2) == 2
        f3 = d.enqueue("c")             # will close by window timeout
        assert await asyncio.wait_for(f3, 2) == 1
        await d.flush()

    asyncio.run(scenario())
    assert executed == [["a", "b"], ["c"]]


def test_async_compute_wrap():
    task = async_compute(lambda x, y: x + y)
    fut = task.get_future()
    task(2, 3)
    assert fut.result(timeout=1) == 5
    with pytest.raises(RuntimeError):
        task(1, 1)  # single-shot


def test_async_compute_exception():
    task = async_compute(lambda: 1 / 0)
    task()
    with pytest.raises(ZeroDivisionError):
        task.get_future().result(timeout=1)

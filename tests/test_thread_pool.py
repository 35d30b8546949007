"""ThreadPool + DeferredShortTaskPool + EventLoopGroup tests
(reference core/tests/test_thread_pool.cc incl. affinity)."""

import os
import threading
import time

import pytest

from tpulab.core import (CpuSet, DeferredShortTaskPool, EventLoopGroup,
                         ThreadPool)
from tpulab.core.affinity import Affinity, AffinityGuard


def test_thread_pool_executes():
    with ThreadPool(4) as tp:
        futs = [tp.enqueue(lambda i=i: i * i) for i in range(10)]
        assert [f.result(timeout=5) for f in futs] == [i * i for i in range(10)]


def test_thread_pool_exception_propagates():
    with ThreadPool(1) as tp:
        fut = tp.enqueue(lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            fut.result(timeout=5)


def test_thread_pool_affinity_shared_mask():
    cpus = CpuSet(list(os.sched_getaffinity(0))[:1])
    with ThreadPool(2, cpus=cpus) as tp:
        seen = tp.enqueue(lambda: Affinity.get_affinity()).result(timeout=5)
        assert seen == cpus


def test_thread_pool_one_per_cpu():
    avail = sorted(os.sched_getaffinity(0))[:2]
    tp = ThreadPool.one_per_cpu(CpuSet(avail))
    try:
        assert tp.size == len(avail)
        pins = set()
        # each worker is pinned to exactly one cpu
        futs = [tp.enqueue(lambda: tuple(Affinity.get_affinity()))
                for _ in range(8)]
        for f in futs:
            pin = f.result(timeout=5)
            assert len(pin) == 1
            pins.add(pin[0])
        assert pins <= set(avail)
    finally:
        tp.shutdown()


def test_enqueue_after_shutdown_raises():
    tp = ThreadPool(1)
    tp.shutdown()
    with pytest.raises(RuntimeError):
        tp.enqueue(lambda: None)


def test_deferred_task_pool_ordering():
    events = []
    with DeferredShortTaskPool() as pool:
        pool.enqueue_deferred(0.10, lambda: events.append("late"))
        pool.enqueue_deferred(0.02, lambda: events.append("early"))
        time.sleep(0.3)
    assert events == ["early", "late"]


def test_deferred_task_pool_immediate():
    done = threading.Event()
    with DeferredShortTaskPool() as pool:
        pool.enqueue_deferred(0.0, done.set)
        assert done.wait(timeout=2)


def test_affinity_set_algebra():
    a, b = CpuSet([0, 1, 2]), CpuSet([2, 3])
    assert a & b == CpuSet([2])
    assert a | b == CpuSet([0, 1, 2, 3])
    assert a - b == CpuSet([0, 1])
    assert CpuSet.from_string("0-2,4") == CpuSet([0, 1, 2, 4])
    assert len(CpuSet.from_string("")) == 0


def test_affinity_guard_restores():
    before = Affinity.get_affinity()
    one = CpuSet(sorted(before)[:1])
    with AffinityGuard(one):
        assert Affinity.get_affinity() == one
    assert Affinity.get_affinity() == before


def test_numa_topology_enumerates():
    nodes = Affinity.numa_nodes()
    assert nodes and all(n.id >= 0 for n in nodes)
    all_node_cpus = CpuSet()
    for n in nodes:
        all_node_cpus = all_node_cpus | n.cpus
    assert len(all_node_cpus) >= 1


def test_round_robin_allocator():
    pool = CpuSet([0, 1])
    got = Affinity.round_robin(4, pool)
    assert len(got) == 4 and set(got) <= {0, 1}


def test_event_loop_group_runs_coroutines():
    import asyncio

    async def work(i):
        await asyncio.sleep(0.01)
        return i * 2

    with EventLoopGroup(2) as elg:
        futs = [elg.submit(work(i)) for i in range(8)]
        assert sorted(f.result(timeout=5) for f in futs) == [i * 2 for i in range(8)]


def test_event_loop_group_submit_fn():
    with EventLoopGroup(1) as elg:
        assert elg.submit_fn(lambda: 42).result(timeout=5) == 42


def test_on_one_frame_chunk_reserves_a_chunk_for_the_frames_beneath_it():
    """The mechanism, not the clock: the helper returns ``fn``'s result, and
    its frame asks for 256 KiB of 8-byte slots, more than the 16 KiB chunk
    of CPython's frame stack, so it gets a chunk of its own whose rest its
    callees share (what that buys is timed by the scan in
    docs/PERFORMANCE.md, "Where a program is lowered")."""
    from tpulab.core.threads import on_one_frame_chunk

    assert on_one_frame_chunk(lambda a, b: a + b, 1, 2) == 3
    assert on_one_frame_chunk.__code__.co_stacksize * 8 >= 256 << 10

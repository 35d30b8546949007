"""Test harness config.

Tests run hermetically on CPU with 8 virtual XLA devices so multi-chip sharding
logic is exercised without TPU hardware.  ``JAX_PLATFORMS=cpu`` (the tier-1
command sets it) selects the CPU backend with one device; force_cpu(8) also
sets the virtual-device count, before any backend is created.  What only a
chip can show is in ``chip_smoke.py`` (run through the chip tool).
"""

import gc
import os
import signal
import sys
import threading
import traceback

import pytest

os.environ.setdefault("JAX_ENABLE_X64", "0")

from tpulab.tpu.platform import force_cpu  # noqa: E402

force_cpu(8)
# The persistent XLA compilation cache is not enabled here: in-process
# compile reuse for the serving engine comes from the step programs' memo
# (engine/paged_steps.py _JIT_MEMO), which shares jitted programs across
# identical-geometry engines without any serialization.


def free_port() -> int:
    """Ephemeral localhost port (best-effort: tiny close-to-rebind window)."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _mappings() -> int:
    """Memory mappings of this process (0 where ``/proc`` has no such file)."""
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _mappings_allowed() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


_MAPPINGS_ALLOWED = _mappings_allowed()


@pytest.fixture(autouse=True)
def _bounded_mappings():
    """Drop JAX's compiled programs once this process holds half the memory
    mappings the kernel allows it (``vm.max_map_count``, 65,530).

    The CPU backend maps three regions for every object file of a compiled
    program and the jit caches keep every program alive, so a worker that
    ``--dist loadfile`` happens to hand several engine-heavy files in a row
    (``test_packed_round.py`` alone leaves ~55,000 mappings) reaches the
    limit, the next ``mmap`` fails inside the compiler and the worker dies
    of a segmentation fault in whatever test compiles next (PR 50: the same
    files in the same order killed the parent's tree too).  A program a later
    test needs again compiles again."""
    yield
    if _mappings() > _MAPPINGS_ALLOWED // 2:
        import jax
        jax.clear_caches()
        gc.collect()


#: Seconds a test may run (its call, not its fixtures): three times the
#: slowest honest test of a whole run under the driver's six workers (105 s at
#: PR 57), so that it never fails a test that is only slow on a loaded box.  No
#: marker, option or environment variable changes it: a test that needs more
#: is ``slow``.  It stays well under ``pytest.ini``'s ``faulthandler_timeout``,
#: the net under it.
TEST_LIMIT_S = 315


def _every_threads_stack() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    return "\n".join(
        f"--- thread {names.get(ident, '?')} ({ident}) ---\n"
        + "".join(traceback.format_stack(frame))
        for ident, frame in sys._current_frames().items())


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """A test that stands still fails alone: at ``TEST_LIMIT_S`` a
    ``SIGALRM`` raises in the main thread, where pytest (and an xdist worker)
    runs the test, out of ``time.sleep``, ``Event.wait``, ``Thread.join`` or
    ``Future.result``; a call into a compiler is left when it returns.  The
    test FAILS with every thread's stack, its ``finally`` blocks run, and the
    run goes on with the next test (PR 56's whole run was cut at its limit by
    one poll with no end, and counted nine tests short)."""
    def stood_still(signum, frame):
        pytest.fail(f"{item.nodeid} ran past its {TEST_LIMIT_S} s limit "
                    f"(tests/conftest.py TEST_LIMIT_S)\n"
                    + _every_threads_stack(), pytrace=False)

    before = signal.signal(signal.SIGALRM, stood_still)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)

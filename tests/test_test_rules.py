"""The rule of ``tests/`` and the code that keeps it: a test never races the
engine's clock and never waits without an end.

- ``helpers_engine.TokenGate`` holds the engine at a token, ``GatedSink`` a
  batch item; ``wait_until`` and ``join_all`` are the waits, and they end.
- ``conftest.py`` fails a test that stands still at ``TEST_LIMIT_S`` and
  goes on (shown on a throw-away file, with the limit patched to a second).
- every ``tests/*.py`` is read with ``ast``: a ``while`` that sleeps reads a
  clock, and no thread, event, process or future is waited for bare.
"""

import ast
import functools
import os
import pathlib
import subprocess
import sys
import textwrap
import threading

import pytest

from helpers_engine import GatedSink, TokenGate, join_all, wait_until

TESTS = pathlib.Path(__file__).resolve().parent


# -- the gate -----------------------------------------------------------------

def _engine(hook, n_tokens, passed):
    """What the scheduler's thread does with a hook: one call a token."""
    def run():
        for i in range(n_tokens):
            hook(100 + i, i)
            passed.append(i)
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


@pytest.mark.parametrize("n", [1, 3, 5])
def test_gate_holds_at_token_n_and_not_before(n):
    gate, passed = TokenGate(n), []
    t = _engine(gate, n + 2, passed)
    assert gate.wait(timeout=10)
    # the tokens before the n-th went through; the n-th's hook has not returned
    assert passed == list(range(n - 1)) and t.is_alive()
    gate.release()
    join_all([t], timeout_s=10)
    assert passed == list(range(n + 2))     # it holds once


def test_gate_takes_the_logprob_form_of_the_hook():
    gate = TokenGate(2)
    gate(7, 0, -0.5)
    gate.release()
    gate(8, 1, -0.25)
    assert gate.wait(timeout=0)


class _Sink:
    def __init__(self):
        self.calls = []

    def load_progress(self, job_id):
        return {0: "progress"}

    def __getattr__(self, name):
        return lambda *a: self.calls.append((name,) + a)


def test_gated_sink_passes_the_held_token_through_before_the_hold():
    gate, inner, passed = TokenGate(2), _Sink(), []
    sink = GatedSink(gate, inner)
    t = _engine(lambda tok, i: sink.append_token("j", 0, i, tok), 3, passed)
    assert gate.wait(timeout=10)
    assert passed == [0]
    assert inner.calls == [("append_token", "j", 0, 0, 100),
                           ("append_token", "j", 0, 1, 101)]
    gate.release()
    join_all([t], timeout_s=10)
    sink.mark_reset("j", 0)
    sink.mark_done("j", 0, 3)
    sink.flush()
    assert [c[0] for c in inner.calls[3:]] == ["mark_reset", "mark_done",
                                               "flush"]
    assert sink.load_progress("j") == {0: "progress"}


# -- the waits ----------------------------------------------------------------

def test_wait_until_returns_a_true_predicates_value_at_once():
    calls = []
    assert wait_until(lambda: calls.append(1) or "ready", "never", 0) == "ready"
    assert calls == [1]


def test_wait_until_fails_at_its_end_with_what_and_the_last_value():
    seen = []
    with pytest.raises(AssertionError) as ei:
        wait_until(lambda: seen.append(1) and None, "the waiter queued",
                   timeout_s=0.05, poll_s=0.01)
    assert "the waiter queued" in str(ei.value)
    assert "last value None" in str(ei.value)
    assert len(seen) >= 2       # it polled, then looked once more at the end


def test_join_all_fails_on_a_thread_that_is_still_alive():
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, args=(30,), name="stuck",
                         daemon=True)
    t.start()
    try:
        with pytest.raises(AssertionError, match="stuck"):
            join_all([t], timeout_s=0.05)
    finally:
        stop.set()
    join_all([t], timeout_s=10)


# -- the limit of one test ----------------------------------------------------

_STANDS_STILL = '''
import threading
import time

import pytest


def _left(name):
    open(name + ".finally", "w").close()


def test_sleep():
    try:
        time.sleep(60)
    finally:
        _left("sleep")


def test_event_wait():
    try:
        threading.Event().wait()
    finally:
        _left("event_wait")


def test_thread_join():
    t = threading.Thread(target=time.sleep, args=(60,), daemon=True)
    t.start()
    try:
        t.join()
    finally:
        _left("thread_join")


def test_swallowing_every_exception_does_not_swallow_the_limit():
    try:
        time.sleep(60)
    except Exception:
        pass
    finally:
        _left("swallow")


def test_next():
    pass
'''

_PATCHED_CONFTEST = '''
import importlib.util

spec = importlib.util.spec_from_file_location("tier1_conftest", {conftest!r})
tier1 = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tier1)
assert tier1.TEST_LIMIT_S >= 300   # the tier-1 run's own
tier1.TEST_LIMIT_S = 1
pytest_runtest_call = tier1.pytest_runtest_call
'''


@pytest.mark.parametrize("workers", [(), ("-p", "xdist", "-n", "1")],
                         ids=["alone", "xdist-worker"])
def test_a_test_that_stands_still_fails_alone(tmp_path, workers):
    """``conftest.py``'s limit, patched to a second, on a throw-away file:
    each standing test fails with every thread's stack, its ``finally`` runs,
    and the test behind them passes."""
    (tmp_path / "conftest.py").write_text(
        _PATCHED_CONFTEST.format(conftest=str(TESTS / "conftest.py")))
    (tmp_path / "test_stands_still.py").write_text(_STANDS_STILL)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_stands_still.py", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", *workers],
        cwd=tmp_path, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(TESTS.parent)})
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "4 failed, 1 passed" in out, out
    assert out.count("ran past its 1 s limit") >= 4, out
    assert "--- thread MainThread" in out, out
    for name in ("sleep", "event_wait", "thread_join", "swallow"):
        assert (tmp_path / f"{name}.finally").exists(), name


# -- the rule keeps itself ----------------------------------------------------

#: ``while`` loops that sleep and read no clock, by ``file:function``: the
#: bodies of child processes, which the parent test kills in a ``finally``.
ENDLESS_POLLS = {
    "helpers_lm_server.py:main",   # the server's body and its autosave
}
#: Bare ``.join()`` / ``.wait()`` / ``.result()`` / ``.get()``: none.
BARE_WAITS = set()

_CLOCKS = {"monotonic", "time", "perf_counter"}


def _reads_a_clock(nodes) -> bool:
    return any(isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
               and n.func.attr in _CLOCKS
               for node in nodes for n in ast.walk(node))


def _sleeps(loop: ast.While) -> bool:
    return any(isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
               and n.func.attr == "sleep"
               for stmt in loop.body for n in ast.walk(stmt))


def _functions(tree):
    """(name of the outermost function or ``<module>``, node) for every node."""
    def walk(node, owner):
        for child in ast.iter_child_nodes(node):
            inner = owner
            if owner == "<module>" and isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = child.name
            yield inner, child
            yield from walk(child, inner)
    return walk(tree, "<module>")


@functools.cache
def _findings(tests=TESTS):
    polls, waits = set(), set()
    for path in sorted(tests.glob("*.py")):
        tree = ast.parse(path.read_text(), str(path))
        queues = {t.id for node in ast.walk(tree)
                  if isinstance(node, ast.Assign)
                  and isinstance(node.value, ast.Call)
                  and ast.unparse(node.value.func).endswith("Queue")
                  for t in node.targets if isinstance(t, ast.Name)}
        for owner, node in _functions(tree):
            where = f"{path.name}:{owner}"
            if isinstance(node, ast.While) and _sleeps(node):
                # its end is a clock in the condition, or in an assert, an
                # ``if`` or a ``break`` of its body
                guards = [node.test] + [
                    n for stmt in node.body for n in ast.walk(stmt)
                    if isinstance(n, (ast.Assert, ast.If, ast.Raise))]
                if not _reads_a_clock(guards):
                    polls.add(where)
            if (isinstance(node, ast.Call) and not node.args
                    and not node.keywords
                    and isinstance(node.func, ast.Attribute)):
                attr, on = node.func.attr, node.func.value
                if attr in ("join", "wait", "result") or (
                        attr == "get" and isinstance(on, ast.Name)
                        and on.id in queues):
                    waits.add(where)
    return polls, waits


def test_no_poll_in_tests_is_without_a_clock():
    """A ``while <condition>:`` that sleeps and reads no clock waits for a
    state that may have been and gone (PR 56: ``while cb.active_lanes < 2``
    cost the whole run its limit).  Hold the engine with a ``TokenGate``, or
    wait with ``wait_until``."""
    assert _findings()[0] == set(ENDLESS_POLLS)


def test_no_wait_in_tests_is_without_an_end():
    """``t.join()``, ``evt.wait()``, ``fut.result()``, ``q.get()``: each
    takes a timeout, and what it returns is asserted (``join_all``)."""
    assert _findings()[1] == set(BARE_WAITS)


def test_the_reader_above_sees_what_it_refuses(tmp_path):
    (tmp_path / "test_bad.py").write_text(textwrap.dedent("""
        import queue, threading, time
        def test_polls(cb):
            while cb.active_lanes < 2:
                time.sleep(0.001)
        def test_waits(fut):
            q = queue.Queue()
            threading.Event().wait(); fut.result(); q.get()
        def test_good(cb, t, item, deadline):
            while cb.active_lanes < 2 and time.monotonic() < deadline:
                time.sleep(0.001)
            while not cb.idle:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            t.join(timeout=5); item.get(); ", ".join(["a"])
        """))
    assert _findings(tmp_path) == ({"test_bad.py:test_polls"},
                           {"test_bad.py:test_waits"})

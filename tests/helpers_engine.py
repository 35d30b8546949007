"""What tests of the scheduler share."""

import threading


class FirstTokenGate:
    """An ``on_token`` hook that reports the request's first token and HOLDS
    the scheduler's thread there (hooks run outside its lock) until
    :meth:`release`: what the test submits in between is queued before the
    request takes another step, however fast the engine and however loaded
    the machine.  (A bare ``started.set()`` raced the request's remaining
    steps against the test thread's wake-up.)"""

    def __init__(self):
        self._seen, self._go = threading.Event(), threading.Event()

    def __call__(self, tok, i):
        self._seen.set()
        self._go.wait(timeout=60)

    def wait(self, timeout: float = 60) -> bool:
        return self._seen.wait(timeout)

    def release(self) -> None:
        self._go.set()

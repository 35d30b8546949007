"""What tests of the scheduler share: a test never races the engine's clock
and never waits without an end.

- :class:`TokenGate` HOLDS the engine at a chosen token until the test has
  done what it must; :class:`GatedSink` puts the same gate on a batch item,
  whose tokens leave through the sink.
- :func:`wait_until` is the one poll, for what no hook can hold (a second
  thread's arrival in a queue, a write-behind publish): it ends, and says
  what it waited for.
- :func:`greedy_reference` is the dense decoder's greedy stream by the plain
  forward over the whole sequence: a reference that shares no code with the
  engine (no pages, no cache, no step program).
"""

import threading
import time

import numpy as np


class TokenGate:
    """An ``on_token`` hook that reports the request's ``n``-th token (the
    first by default) and HOLDS the scheduler's thread there (hooks run
    outside its lock) until :meth:`release`: what the test submits in between
    is queued before the request takes another step, however fast the engine
    and however loaded the machine.  (A bare ``started.set()`` raced the
    request's remaining steps against the test thread's wake-up.)  It holds
    once: after :meth:`release` every token passes."""

    def __init__(self, n: int = 1):
        self.n = n
        self._seen, self._go = threading.Event(), threading.Event()

    def __call__(self, tok, i, logprob=None):
        if i == self.n - 1:
            self._seen.set()
            self._go.wait(timeout=60)

    def wait(self, timeout: float) -> bool:
        """Whether the request reached its ``n``-th token (and is held)."""
        return self._seen.wait(timeout)

    def release(self) -> None:
        self._go.set()


FirstTokenGate = TokenGate  # the name the online preemption tests took it by


class GatedSink:
    """A ``JSONLResultSink`` with a gate on it, which holds the batch item
    at ``gate``'s token: ``BatchScheduler._submit_item``'s hook hands every
    token to ``sink.append_token`` on the scheduler's thread, so this is
    where a batch lane can be held.  The token is appended to ``inner``
    BEFORE the hold, so what the test reads there while the item stands
    still includes the held token; everything else is ``inner``'s."""

    def __init__(self, gate: TokenGate, inner):
        self.gate, self.inner = gate, inner

    def append_token(self, job_id, item, index, token):
        self.inner.append_token(job_id, item, index, token)
        self.gate(token, index)

    def __getattr__(self, name):  # load_progress, mark_done, mark_reset, flush
        return getattr(self.inner, name)


def join_all(threads, timeout_s: float = 60.0) -> None:
    """Join every thread; one that is still alive at the end fails the test."""
    for t in threads:
        t.join(timeout=timeout_s)
    alive = [t.name for t in threads if t.is_alive()]
    assert not alive, f"threads still running after {timeout_s:g} s: {alive}"


def wait_until(predicate, what: str, timeout_s: float = 60.0,
               poll_s: float = 0.005):
    """Poll ``predicate`` until it is true and return its value; at the end
    of ``timeout_s`` fail with ``what`` and the predicate's last value.  For
    a state another THREAD reaches on its own (no hook to hold it at); what
    the engine does mid-request is held with a :class:`TokenGate`."""
    deadline = time.monotonic() + timeout_s
    while True:
        got = predicate()
        if got or time.monotonic() >= deadline:
            break
        time.sleep(poll_s)
    assert got, f"{what}: not within {timeout_s:g} s (last value {got!r})"
    return got


def greedy_reference(params, prompt, steps: int, n_heads: int, n_layers: int,
                     **model):
    """``(tokens, logprobs)`` of ``steps`` greedy tokens after ``prompt``,
    each from ``tpulab.models.transformer.transformer_apply`` over the WHOLE
    sequence so far at float32 (``model``: ``n_kv_heads``, ``rope_theta``):
    no K/V is kept from one token to the next, so nothing of the engine (its
    pages, its step programs, its sampler) is in it.  The sequence is padded
    to one power of two (a causal row reads nothing behind it), so the
    forward compiles once."""
    import jax
    import jax.numpy as jnp

    from tpulab.models.transformer import transformer_apply
    n = len(prompt)
    seq = np.zeros((1, 1 << (n + steps - 1).bit_length()), np.int32)
    seq[0, :n] = prompt
    forward = jax.jit(lambda tokens: transformer_apply(
        params, {"tokens": tokens}, n_heads=n_heads, n_layers=n_layers,
        compute_dtype=jnp.float32, **model)["logits"])
    tokens, logprobs = [], []
    for at in range(n, n + steps):
        row = np.asarray(forward(seq)[0, at - 1], np.float64)
        tokens.append(int(row.argmax()))
        logprobs.append(float(row[tokens[-1]] - row.max()
                              - np.log(np.exp(row - row.max()).sum())))
        seq[0, at] = tokens[-1]
    return tokens, logprobs

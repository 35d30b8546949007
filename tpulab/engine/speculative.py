"""Speculative decoding: draft-model proposal + single-pass target verify.

Beyond the reference's scope (trtlab predates LLM serving), squarely in
this framework's serving mandate: decode is HBM-bandwidth bound (one
weight read per token), so a small draft model proposes ``k`` tokens and
the target model verifies all of them in ONE chunked forward
(:func:`tpulab.models.transformer.transformer_chunk_step`) — ``a+1``
tokens emitted per target weight-read instead of 1, where ``a`` is the
accepted prefix length.

Greedy acceptance rule: accept draft tokens while they equal the target's
own greedy choice, then emit the target's correction (or bonus) token.
The output is therefore EXACTLY the target model's greedy sequence —
speculation changes latency, never content.  Both KV caches tolerate
rejected-token writes because positions only advance: stale slots are
overwritten before any later step can attend to them (see
transformer_chunk_step's docstring).

.. note:: This module is the LEGACY DENSE path (one session, one
   max_len cache per model).  Production serving speculates inside the
   continuous batcher's fused paged decode blocks instead:
   ``ContinuousBatcher(draft_params=..., draft_n_layers=...)``
   (:mod:`tpulab.engine.paged`) runs draft + verify + accept in one
   device dispatch over the shared paged pool, with adaptive fallback
   to plain blocks.  New integrations should target that path; this one
   stays for the dense Generate-RPC adapter and as the acceptance-rule
   reference.
"""

from __future__ import annotations

from functools import partial
from typing import Any, List, Optional

import numpy as np


class SpeculativeGenerator:
    """Greedy speculative decoding over two transformer-family models."""

    def __init__(self, target_params: Any, draft_params: Any, *,
                 n_heads: int, n_layers: int,
                 draft_n_heads: Optional[int] = None,
                 draft_n_layers: Optional[int] = None,
                 k: int = 4, max_len: int = 1024,
                 compute_dtype=None, device=None,
                 n_kv_heads: Optional[int] = None,
                 draft_n_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None):
        import jax
        import jax.numpy as jnp

        from tpulab.models.transformer import (init_kv_cache,
                                               transformer_chunk_step,
                                               transformer_decode_step)
        from tpulab.tpu import platform as plat

        if k < 1:
            raise ValueError("k must be >= 1")
        self.k = k
        self.max_len = max_len
        self.device = device if device is not None else plat.local_device(0)
        cdt = compute_dtype or jnp.float32
        self._jnp = jnp
        #: id-validation bound (public: the Generate RPC checks it)
        self.vocab = int(target_params["embed"].shape[0])
        self.target_params = jax.device_put(target_params, self.device)
        self.draft_params = jax.device_put(draft_params, self.device)

        dh = draft_n_heads or n_heads
        dl = draft_n_layers or n_layers
        t_kv = n_kv_heads or n_heads
        # same-arch draft (draft_n_heads omitted) inherits the target's KV
        # head count; an explicit draft arch defaults to MHA
        d_kv = draft_n_kv_heads or (t_kv if draft_n_heads is None else dh)
        t_dim = target_params["embed"].shape[1] // n_heads
        d_dim = draft_params["embed"].shape[1] // dh
        self._t_cache = partial(init_kv_cache, 1, max_len, n_layers, t_kv,
                                t_dim, cdt)
        self._d_cache = partial(init_kv_cache, 1, max_len, dl, d_kv,
                                d_dim, cdt)
        # target: one chunked forward verifies a whole proposal window
        # (M = k+1 fixed -> one compiled program; prefill buckets by pow2)
        self._verify = jax.jit(partial(
            transformer_chunk_step, n_heads=n_heads, n_layers=n_layers,
            compute_dtype=cdt, n_kv_heads=n_kv_heads, rope_theta=rope_theta))
        # draft: chunked prefill + k single-token steps under one jitted scan
        self._d_prefill = jax.jit(partial(
            transformer_chunk_step, n_heads=dh, n_layers=dl,
            compute_dtype=cdt, n_kv_heads=d_kv,
            rope_theta=rope_theta))
        d_step = partial(transformer_decode_step, n_heads=dh, n_layers=dl,
                         compute_dtype=cdt, n_kv_heads=d_kv,
                         rope_theta=rope_theta)

        @jax.jit
        def draft_propose(params, cache, tok, pos0):
            # k+1 iterations: the extra one FEEDS drafts[k-1] so its K/V
            # lands in the draft cache (a fully-accepted round advances
            # past position pos0+k — without this the slot would stay a
            # zero hole every later draft query attends).  Its output is
            # discarded; on partial acceptance the extra writes are stale
            # but positions only advance, so they are overwritten before
            # they become visible.
            def body(carry, i):
                cache, tok = carry
                logits, cache = d_step(params, cache, tok, pos0 + i)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (cache, nxt), nxt[0]
            (cache, _), toks = jax.lax.scan(body, (cache, tok),
                                            jnp.arange(self.k + 1))
            return toks[:self.k], cache
        self._propose = draft_propose

    # -- public --------------------------------------------------------------
    def stream(self, prompt, steps: int):
        """Yield exactly ``steps`` greedy tokens as they are VERIFIED —
        one burst per speculation round (accepted prefix + correction).
        Tokens never stream before the target has verified them, so a
        consumer sees the same exactly-greedy sequence ``generate``
        returns, with burst granularity.  Each call owns fresh KV caches
        (concurrent streams on one instance are safe; the jitted
        programs are shared).  ``rounds``/``accepted`` telemetry from the
        last finished call is exposed on the instance."""
        # validate EAGERLY (at call time, not first iteration): direct
        # stream() callers get the ValueError before they start consuming
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size and (prompt.min() < 0 or prompt.max() >= self.vocab):
            # XLA gather CLAMPS out-of-bounds ids — silent garbage; reject
            # at the host boundary, mirroring ContinuousBatcher.submit
            # (ADVICE r5: direct library callers, not just the RPC)
            raise ValueError(f"prompt token ids outside [0, {self.vocab})")
        t_p = prompt.shape[0]
        if max(t_p + steps + self.k + 1,
               1 << (t_p - 1).bit_length()) > self.max_len:
            raise ValueError("prompt+steps+k exceeds max_len")
        if steps <= 0:  # exactly-steps contract holds at zero too
            self.rounds = self.accepted = 0
            return iter(())
        return self._stream_impl(prompt, t_p, steps)

    def _stream_impl(self, prompt, t_p: int, steps: int):
        jnp = self._jnp
        t_cache, d_cache = self._t_cache(), self._d_cache()
        # prefill both models with one chunked forward each (pow2 bucket)
        t_pad = 1 << (t_p - 1).bit_length()
        padded = np.zeros((1, t_pad), np.int32)
        padded[0, :t_p] = prompt
        tl, t_cache = self._verify(self.target_params, t_cache,
                                   jnp.asarray(padded), jnp.int32(0))
        _, d_cache = self._d_prefill(self.draft_params, d_cache,
                                     jnp.asarray(padded), jnp.int32(0))
        cur = int(np.asarray(tl)[0, t_p - 1].argmax())
        emitted_n = 1
        yield cur
        p = t_p                     # tokens FED to the target so far
        rounds = accepted = 0
        while emitted_n < steps:
            drafts, d_cache = self._propose(
                self.draft_params, d_cache,
                jnp.asarray([cur], jnp.int32), jnp.int32(p))
            drafts = np.asarray(drafts, np.int32)          # (k,)
            chunk = np.concatenate([[cur], drafts])[None, :]  # (1, k+1)
            logits, t_cache = self._verify(
                self.target_params, t_cache, jnp.asarray(chunk),
                jnp.int32(p))
            greedy = np.asarray(logits)[0].argmax(-1).astype(np.int32)
            # accept the agreeing prefix; token a's correction (or the
            # bonus after a full match) is always emitted
            a = 0
            while a < self.k and drafts[a] == greedy[a]:
                a += 1
            cur = int(greedy[a])
            p += a + 1
            rounds += 1
            accepted += a
            for tok in list(drafts[:a]) + [cur]:
                if emitted_n < steps:
                    emitted_n += 1
                    yield int(tok)
        self.rounds = rounds
        self.accepted = accepted

    def generate(self, prompt, steps: int) -> List[int]:
        """Greedy-decode ``steps`` tokens; returns exactly the target
        model's greedy continuation (see :meth:`stream`)."""
        return list(self.stream(prompt, steps))


# canonical home: tpulab.models.transformer (draft-param plumbing shared
# with the paged speculative path); re-exported here for existing callers
from tpulab.models.transformer import early_exit_draft  # noqa: E402,F401


class _SpeculativeSession:
    """One admitted decode: usable directly (``close()``) or as a context
    manager, mirroring the dense :class:`GenerationSession` shape.  The
    semaphore slot releases exactly once — on close/exit or, as a last
    resort, at GC, so an abandoned session cannot deadlock admission."""

    def __init__(self, spec: SpeculativeGenerator, sem, on_close=None):
        self._spec = spec
        self._sem = sem
        self._on_close = on_close
        self._prompt: Optional[np.ndarray] = None
        self._completed = False
        self._served = 0
        self._errored = False
        self._closed = False

    def prefill(self, prompt) -> None:
        if self._closed:
            raise RuntimeError("session is closed")
        self._prompt = np.asarray(prompt, np.int32).reshape(-1)

    def stream(self, steps: int, deadline=None):
        if self._closed:
            raise RuntimeError("session is closed")
        if self._prompt is None:
            raise RuntimeError("prefill() before stream()")
        inner = self._spec.stream(self._prompt, steps)
        if deadline is not None:
            # deadline checks ride the burst boundaries: verified tokens
            # already computed still stream, the NEXT round is what stops
            inner = self._deadlined(inner, deadline)

        def counted():
            # a session completes when its stream is EXHAUSTED, or when
            # the consumer closes it early after >=1 served token (the
            # stop-token break path).  The served count lives on the
            # session (updated per token) rather than in a GeneratorExit
            # handler, so completion does not depend on the generator
            # being finalized before close() runs (refcount ordering is
            # a CPython detail).  Errors flag the session instead —
            # close() must NOT count an errored stream, mirroring
            # ContinuousBatcher.completed_requests (success-only)
            try:
                for tok in inner:
                    self._served += 1
                    yield tok
            except GeneratorExit:   # early close by the consumer: no error
                raise
            except BaseException:
                self._errored = True
                raise
            self._completed = True

        return counted()

    @staticmethod
    def _deadlined(inner, deadline):
        # check BEFORE pulling the next round, so already-verified tokens
        # still reach the consumer and no compute starts past expiry
        while True:
            deadline.check("generation")
            try:
                tok = next(inner)
            except StopIteration:
                return
            yield tok

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._sem.release()
            if ((self._completed or (self._served > 0
                                     and not self._errored))
                    and self._on_close is not None):
                self._on_close()

    def __enter__(self) -> "_SpeculativeSession":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def __del__(self):  # GC fallback; close() is idempotent
        self.close()


class SpeculativeSessionEngine:
    """Serving adapter: a :class:`SpeculativeGenerator` behind the
    Generate RPC's dense-session interface (``start_session`` ->
    ``prefill``/``stream``), so speculative decoding plugs into
    ``manager.serve(generation_engines={...})`` like any engine.

    Tokens stream in verified bursts (one per speculation round); the
    wire sequence is exactly the target model's greedy output.  Sessions
    are admission tokens (``max_sessions`` bounds concurrent decodes —
    the generator itself is stateless per call); sampling requests are
    rejected upstream by the dense-path greedy-only check.

    .. deprecated:: PR 7
       The batcher path supersedes this adapter for serving: speculation
       now runs inside the fused paged decode blocks
       (``ContinuousBatcher(draft_params=...)``), which batches lanes,
       shares the paged pool, supports device sampling, and degrades
       adaptively — serve through the batcher and keep this adapter only
       for the single-session dense contract."""

    def __init__(self, spec: SpeculativeGenerator, max_sessions: int = 2):
        import threading
        self._spec = spec
        self._sem = threading.BoundedSemaphore(max_sessions)
        self._count_lock = threading.Lock()
        #: sessions that streamed and closed (oneshot/ops accounting,
        #: mirroring ContinuousBatcher.completed_requests)
        self.completed_requests = 0

    def _count_completion(self) -> None:
        with self._count_lock:
            self.completed_requests += 1

    @property
    def vocab(self):
        return self._spec.vocab

    #: telemetry passthrough (last finished call)
    @property
    def rounds(self):
        return getattr(self._spec, "rounds", 0)

    @property
    def accepted(self):
        return getattr(self._spec, "accepted", 0)

    def start_session(self, timeout: Optional[float] = None
                      ) -> _SpeculativeSession:
        if not self._sem.acquire(timeout=timeout):
            raise TimeoutError("no speculative session available")
        return _SpeculativeSession(self._spec, self._sem,
                                   on_close=self._count_completion)

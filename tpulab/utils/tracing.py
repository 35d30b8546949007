"""Tracing/profiling hooks (SURVEY §5 aux subsystems: the reference has
per-stage cudaEvent timing + Walltime + InferBench metrics; the TPU
equivalent adds the XLA profiler).

- :func:`start` / :func:`stop` / :func:`active` — the process's ONE
  profiler switch: the only code in ``tpulab/`` that calls
  ``jax.profiler.start_trace`` / ``stop_trace`` (the Debug RPC's
  ``profile_ticks``, :func:`trace` and the benchmark all go through it).
- :func:`annotate` / :func:`stage` / :func:`part` / :class:`StageClock` —
  named regions in the profiler's trace, on the device trace's clock (the
  nvtx-range analog the reference lacked); :func:`stage` also adds the
  region's host-clock seconds to an accumulator its caller owns (the
  scheduler's ``debug_state()["dispatch"]["stages"]``), and the same
  clock reads the thread's turns with an empty device queue and the
  parts of a stage (``["turns"]``, ``["dispatch_parts"]``), and names a
  working stage's run that stood still (``["host"]["stalls"]``).
- :func:`watch_collector` / :func:`collector_pauses` — the cyclic
  collector's pauses, which stop every Python thread at once: counters by
  generation over the process's life and a ``host.gc`` span on the device
  trace's clock, on the thread that collects.
- :class:`TraceContext` / :class:`ChromeTraceRecorder` /
  :func:`merge_chrome_traces` — request-scoped distributed tracing: the
  client mints a trace id, carries it over gRPC (request field + metadata),
  both processes tag their spans with it, and the saved traces merge into
  ONE chrome://tracing / perfetto timeline (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import collections
import contextlib
import gc
import os
import threading
import time
from typing import Dict, Iterable, Optional

#: gRPC metadata key carrying the trace id (request-field carriage is the
#: primary channel; the metadata rides along for middleboxes/interceptors
#: that never parse the payload)
TRACE_METADATA_KEY = "tpulab-trace-id"


def mint_trace_id() -> str:
    """16-hex request-scoped trace id (random; no coordination needed)."""
    import uuid
    return uuid.uuid4().hex[:16]


class TraceContext:
    """One request's trace identity, propagated client -> server.

    The client mints it once per logical request (NOT per attempt — a
    failover replay keeps the id, so all attempts line up under one
    request in the merged timeline); servers recover it from the request
    message's ``trace_id`` field or the ``tpulab-trace-id`` gRPC metadata.
    """

    __slots__ = ("trace_id",)

    def __init__(self, trace_id: Optional[str] = None):
        self.trace_id = trace_id or mint_trace_id()

    def metadata(self) -> tuple:
        """gRPC call metadata carrying this context."""
        return ((TRACE_METADATA_KEY, self.trace_id),)

    @classmethod
    def from_metadata(cls, metadata: Optional[Iterable]) -> Optional["TraceContext"]:
        """Parse from an iterable of (key, value) pairs; None when absent."""
        for k, v in metadata or ():
            if k == TRACE_METADATA_KEY and v:
                return cls(str(v))
        return None

    @classmethod
    def of_request(cls, request, grpc_context=None) -> Optional["TraceContext"]:
        """Server-side recovery: the request's ``trace_id`` field first,
        else the invocation metadata; None for untraced requests."""
        rid = getattr(request, "trace_id", "")
        if rid:
            return cls(rid)
        if grpc_context is not None and hasattr(grpc_context,
                                                "invocation_metadata"):
            try:
                return cls.from_metadata(grpc_context.invocation_metadata())
            except Exception:  # pragma: no cover - exotic grpc shims
                return None
        return None

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id})"


class ProfilerBusy(RuntimeError):
    """:func:`start` while a capture is open: the profiler is one per
    process, so the second owner is told so here instead of failing
    inside JAX."""


_switch_lock = threading.Lock()
_switch_dir: Optional[str] = None   # log_dir of the open capture


def start(log_dir: str, python_tracer: bool = False) -> str:
    """Open a profiler capture into ``log_dir`` (TensorBoard-loadable;
    ``<log_dir>/plugins/profile/<time>/*.xplane.pb``).  Thread-safe, any
    number of captures a process, one at a time: a second ``start`` while
    one is open raises :class:`ProfilerBusy` and leaves the first intact.

    With the Python tracer off (the default) the host planes hold the
    program's own spans (:func:`annotate`, :func:`stage`) and JAX's
    runtime events only: a smaller trace and a host slowed less.
    ``python_tracer=True`` adds every Python call, for a person
    debugging."""
    global _switch_dir
    import jax
    with _switch_lock:
        if _switch_dir is not None:
            raise ProfilerBusy(
                f"a profiler capture into {_switch_dir!r} is already open "
                "in this process (one at a time: tracing.stop() it first)")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1 if python_tracer else 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        _switch_dir = str(log_dir)
    return _switch_dir


def stop() -> Optional[str]:
    """Close the open capture and write it; returns its ``log_dir``, or
    None when none was open (idempotent)."""
    global _switch_dir
    import jax
    with _switch_lock:
        if _switch_dir is None:
            return None
        log_dir, _switch_dir = _switch_dir, None
        jax.profiler.stop_trace()
    return log_dir


def active() -> bool:
    """Is a capture open (by whichever owner)?"""
    return _switch_dir is not None


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/tpulab-trace", **start_kw):
    """:func:`start` / :func:`stop` around a block::

        with tracing.trace("/tmp/trace"):
            runner.infer(**arrays).result()
        # -> tensorboard --logdir /tmp/trace
    """
    start(log_dir, **start_kw)
    try:
        yield log_dir
    finally:
        stop()


def annotate(name: str, **kw):
    """Named region inside a trace (nvtx-range analog): a
    ``jax.profiler.TraceAnnotation``, i.e. an atomic check while no
    capture is open and an event on the device trace's clock while one
    is.  ``kw`` (e.g. ``trace_id=``) become the event's stats."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


_GENERATIONS = ("gen0", "gen1", "gen2")
#: what :func:`collector_pauses` reports, counted by :func:`_on_collection`
_gc = {"n": dict.fromkeys(_GENERATIONS, 0),
       "s": dict.fromkeys(_GENERATIONS, 0.0), "max_s": 0.0, "collected": 0}
#: the last pauses of generation 1 and 2, ``(t0, seconds, generation)`` on
#: ``perf_counter``: what :class:`StageClock` sets a slow run against
_gc_ring: collections.deque = collections.deque(maxlen=64)
_gc_open: Optional[tuple] = None     # (t0, span) of the running collection


def _on_collection(phase: str, info: Dict[str, int]) -> None:
    """The process's one entry in ``gc.callbacks``.  It runs on the thread
    that collects, and a collection never starts inside another, so
    ``start`` and ``stop`` pair up through one module global."""
    global _gc_open
    if phase == "start":
        span = None
        if info["generation"]:
            span = annotate("host.gc", generation=info["generation"])
            span.__enter__()
        _gc_open = (time.perf_counter(), span)
    elif _gc_open is not None:
        now = time.perf_counter()
        (t0, span), _gc_open = _gc_open, None
        dt, gen = now - t0, _GENERATIONS[info["generation"]]
        _gc["n"][gen] += 1
        _gc["s"][gen] += dt
        _gc["collected"] += info["collected"]
        _gc["max_s"] = max(_gc["max_s"], dt)
        if span is not None:
            span.set_metadata(collected=info["collected"])
            span.__exit__(None, None, None)
            _gc_ring.append((t0, dt, info["generation"]))


def watch_collector() -> None:
    """Count and span the cyclic collector's pauses from here on
    (idempotent: ONE entry in ``gc.callbacks`` however often it is
    called).  A collection stops every Python thread of the process for
    as long as it lasts, on whichever thread crossed the threshold."""
    import jax  # noqa: F401  (the callback's annotate() must find it loaded)
    if _on_collection not in gc.callbacks:
        gc.callbacks.append(_on_collection)


def collector_pauses() -> Dict[str, object]:
    """Collections and their seconds by generation since
    :func:`watch_collector` (process-wide, monotone), the longest one,
    the objects they freed, and the collector's settings now."""
    return {**_gc, "n": dict(_gc["n"]), "s": dict(_gc["s"]),
            "threshold": list(gc.get_threshold()),
            "frozen": gc.get_freeze_count()}


def _collector_seconds_within(t0: float, t1: float) -> float:
    """Seconds of the ring's pauses that lie inside ``[t0, t1]``."""
    return sum(max(0.0, min(p0 + dt, t1) - max(p0, t0))
               for p0, dt, _gen in tuple(_gc_ring))


class StageClock:
    """Seconds and entries per named stage, for ONE thread (the
    scheduler's).  ``stages()`` is monotone; the stages never overlap (a
    nested :func:`stage` pauses the one around it) and leave no hole (an
    outermost stage lasts until the next one begins: what the thread
    loses between two ``with`` blocks, e.g. the interpreter lock to the
    threads it has just handed work, belongs to the stage that ended),
    so their seconds sum to the thread's time since its first stage.

    The same clock keeps two finer readings of the same seconds:

    - **the exposed turn** (``turn`` names the stages that count): the
      thread says where a program went onto the device's queue
      (:meth:`launched`) and, inside the stage that waited for it, that
      it came back (:meth:`landed`).  The queue is in order, so what is
      un-fetched then was launched later.  A *turn* is the time the
      thread works with nothing un-fetched: it opens where a stage
      outside ``turn`` (the fetch, the idle wait) ends with the queue
      empty and closes at the next :meth:`launched`.  While one is open
      the seconds of the stages in ``turn`` are also added to
      ``turn_seconds``: the stages' own readings do not move, and a
      turn's seconds are its stages' by construction (a wait inside it,
      an idle wait or the fetch of a program that had already ended,
      is no part of them).  In a capture a turn is a span
      ``<prefix>turn.<cause>``, closed over such a wait and opened again
      behind it under what that wait gave as the cause; the turn's
      seconds are booked to the cause its span carries while they pass
      and its entry to the cause it closes under (``turns()["by_cause"]``).
    - **parts** (:func:`part`): named regions inside a stage that do not
      pause it, seconds and entries of their own.
    - **stalls**: one run of seconds booked to a stage in ``turn`` (the
      stages that are work; a wait is long by nature) that lasted
      ``slow_s`` or more: counted by stage, with the seconds of it the
      collector's pauses (:func:`watch_collector`, any thread's) account
      for, the last few kept whole; in a capture a span
      ``<prefix>stall`` where the run ends.  A mean cannot hold one run
      of 100 ms among thousands of 3 ms; this can."""

    def __init__(self, names: Iterable[str], prefix: str = "",
                 turn: Iterable[str] = (), causes: Iterable[str] = (),
                 parts: Iterable[str] = (), slow_s: float = 0.02):
        self.prefix = prefix
        self.slow_s = slow_s
        self.seconds: Dict[str, float] = {n: 0.0 for n in names}
        self.entries: Dict[str, int] = {n: 0 for n in names}
        self._open: list = []          # the stack of open _Stage
        self._ended: Optional[tuple] = None   # (name, when): last outermost
        self.turn_seconds: Dict[str, float] = {n: 0.0 for n in turn}
        self.turn_entries = 0
        self.part_seconds: Dict[str, float] = {p: 0.0 for p in parts}
        self.part_entries: Dict[str, int] = {p: 0 for p in parts}
        # every span name the clock can emit, built once
        self.span_names: Dict[str, str] = {
            n: prefix + n for n in (*self.seconds, *self.part_seconds)}
        self.turn_names: Dict[str, str] = {
            c: f"{prefix}turn.{c}" for c in (*causes, "other")}
        self._launched = self._landed = 0     # tickets, in queue order
        self._in_turn = False
        self._turn = None                     # the open turn's open span
        self._cause: Optional[tuple] = None   # (cause, stats) of landed()
        self._turn_cause = "other"            # the cause that span carries
        self.cause_seconds: Dict[str, float] = {
            c: 0.0 for c in self.turn_names}
        self.cause_entries: Dict[str, int] = {c: 0 for c in self.turn_names}
        self.stall_name = prefix + "stall"
        self._stalls = {"n": 0, "s": 0.0, "max_s": 0.0, "gc_s": 0.0}
        self._stalls_by_stage = {n: {"n": 0, "s": 0.0}
                                 for n in self.turn_seconds}
        self._last_stalls: collections.deque = collections.deque(maxlen=8)

    def stages(self) -> Dict[str, Dict[str, float]]:
        return {n: {"s": s, "n": self.entries[n]}
                for n, s in self.seconds.items()}

    def turns(self) -> Dict[str, object]:
        """``{"n", "s", "stages": {stage: s}}`` of the turns so far; the
        open one's seconds are in as far as its stages have booked."""
        by_stage = dict(self.turn_seconds)
        return {"n": self.turn_entries, "s": sum(by_stage.values()),
                "stages": by_stage,
                "by_cause": {c: {"n": self.cause_entries[c], "s": s}
                             for c, s in self.cause_seconds.items()}}

    def stalls(self) -> Dict[str, object]:
        """``{"n", "s", "max_s", "gc_s", "by_stage": {stage: {n, s}},
        "last": [{stage, s, gc_s, in_turn}]}``: the runs of a working
        stage that lasted ``slow_s`` or more (monotone but ``last``)."""
        return {**self._stalls,
                "by_stage": {n: dict(v)
                             for n, v in self._stalls_by_stage.items()},
                "last": list(self._last_stalls)}

    def parts(self) -> Dict[str, Dict[str, float]]:
        return {p: {"s": s, "n": self.part_entries[p]}
                for p, s in self.part_seconds.items()}

    def launched(self) -> int:
        """A program went onto the device's queue (call where the jitted
        function returns): an open turn ends here.  Returns the ticket
        :meth:`landed` takes."""
        self._launched += 1
        if self._in_turn:
            self._book(time.perf_counter())   # the turn's edge, mid-stage
            self._in_turn = False
            self.turn_entries += 1
            self.cause_entries[self._turn_cause] += 1
            self._close_span()
        return self._launched

    def landed(self, ticket: int, cause: str = "other", **stats) -> None:
        """The program ``ticket`` names is fetched (call inside the stage
        that waited for it).  Where nothing launched after it, a turn
        opens as that stage ends, its span named by ``cause`` (one of the
        clock's ``causes``, else ``other``) with ``stats``; where a turn
        is open already, its span goes on under that name."""
        if ticket > self._landed:
            self._landed = ticket
        self._cause = (cause, stats)

    def note(self, **stats) -> None:
        """Stats on the innermost open stage's span: read only while a
        capture is open."""
        if self._open:
            self._open[-1].span.set_metadata(**stats)

    def _book(self, now: float) -> None:
        """Add the running stage's seconds up to ``now``: the innermost
        open stage's, or else the last outermost one's, which lasts
        until the next begins."""
        if self._open:
            top = self._open[-1]
            self._add(top.name, now - top.t0, now)
            top.t0 = now
        elif self._ended is not None:
            name, when = self._ended
            self._add(name, now - when, now)
            self._ended = (name, now)

    def _add(self, name: str, dt: float, now: float) -> None:
        """Book the run of ``dt`` seconds that ends at ``now``."""
        self.seconds[name] += dt
        if self._in_turn and name in self.turn_seconds:
            self.turn_seconds[name] += dt
            self.cause_seconds[self._turn_cause] += dt
        if dt >= self.slow_s and name in self.turn_seconds:
            self._stalled(name, dt, now)

    def _stalled(self, name: str, dt: float, now: float) -> None:
        gc_s = min(dt, _collector_seconds_within(now - dt, now))
        total, by_stage = self._stalls, self._stalls_by_stage[name]
        total["n"] += 1
        total["s"] += dt
        total["gc_s"] += gc_s
        total["max_s"] = max(total["max_s"], dt)
        by_stage["n"] += 1
        by_stage["s"] += dt
        self._last_stalls.append({"stage": name, "s": dt, "gc_s": gc_s,
                                  "in_turn": self._in_turn})
        with annotate(self.stall_name, stage=name, ms=round(dt * 1e3, 3),
                      gc_ms=round(gc_s * 1e3, 3)):
            pass

    def _close_span(self) -> None:
        if self._turn is not None:
            self._turn.__exit__(None, None, None)
            self._turn = None

    def _leave(self) -> None:
        """A stage outside the turn's set (a wait) has ended."""
        (cause, stats), self._cause = self._cause or ("other", {}), None
        if self._launched == self._landed:
            self._in_turn = True
            self._turn_cause = cause if cause in self.turn_names else "other"
            self._turn = annotate(self.turn_names[self._turn_cause], **stats)
            self._turn.__enter__()


class _Stage:
    __slots__ = ("clock", "name", "t0", "span")

    def __init__(self, clock: StageClock, name: str):
        self.clock, self.name = clock, name
        self.span = annotate(clock.span_names[name])

    def __enter__(self):
        clock = self.clock
        self.span.__enter__()
        now = time.perf_counter()
        clock._book(now)    # pause the stage around this one, or end the last
        if clock._turn is not None and self.name not in clock.turn_seconds:
            clock._close_span()        # a wait is no part of a turn
        clock._open.append(self)
        self.t0 = now
        return self

    def __exit__(self, *exc):
        clock = self.clock
        now = time.perf_counter()
        clock._add(self.name, now - self.t0, now)
        clock.entries[self.name] += 1
        clock._open.pop()
        if clock._open:
            clock._open[-1].t0 = now   # resume it
        else:
            clock._ended = (self.name, now)
        self.span.__exit__(*exc)
        if clock.turn_seconds and self.name not in clock.turn_seconds:
            clock._leave()
        return False


def stage(clock: StageClock, name: str) -> _Stage:
    """``with stage(clock, "dispatch"):`` — one span
    ``<clock.prefix>dispatch`` in the profiler's trace (when a capture is
    open) plus the block's ``perf_counter`` seconds and one entry on
    ``clock``.  Always on: two clock reads and an inactive-TraceMe check
    per use."""
    return _Stage(clock, name)


class _Part:
    __slots__ = ("clock", "name", "t0", "span")

    def __init__(self, clock: StageClock, name: str):
        self.clock, self.name = clock, name
        self.span = annotate(clock.span_names[name])

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        clock = self.clock
        clock.part_seconds[self.name] += time.perf_counter() - self.t0
        clock.part_entries[self.name] += 1
        self.span.__exit__(*exc)
        return False


def part(clock: StageClock, name: str) -> _Part:
    """``with part(clock, "dispatch.put"):`` inside a :func:`stage`: a
    span and seconds of its own, while the stage around it runs on (its
    seconds include the part's).  Same cost as :func:`stage`."""
    return _Part(clock, name)


class ChromeTraceRecorder:
    """Host-side request-lifecycle trace in Chrome trace-event format
    (load in chrome://tracing or ui.perfetto.dev) — the chrome-trace
    tooling SURVEY §5 notes the reference lacked.

    The serving path (``build_infer_service(trace=recorder)``) records one
    span per request stage (batch_wait / pipeline / respond) on the
    handling thread's row; ``save()`` writes the JSON trace.  Collection
    is thread-safe and bounded (a ring of ``max_events`` — a long-running
    server keeps the most recent window rather than growing without
    limit)."""

    def __init__(self, max_events: int = 100_000,
                 process_name: Optional[str] = None):
        import collections
        self._events = collections.deque(maxlen=max_events)
        self._lock = threading.Lock()
        #: events the ring has discarded (oldest-first) to stay bounded —
        #: a saved trace that silently lost its head reads as "the server
        #: was idle before this window", so the drop count rides save()'s
        #: otherData and the first drop warns once
        self.dropped_events = 0
        self._warned_drop = False
        # paired clock anchor: _epoch0 is the wall-clock instant at which
        # perf_counter read _t0.  Event ts stay perf_counter-relative (sub-
        # microsecond deltas within the process); the anchor rides the
        # saved file so merge_chrome_traces can re-base traces from
        # DIFFERENT processes onto one wall-clock axis.
        self._t0 = time.perf_counter()
        self._epoch0 = time.time()
        self._pid = os.getpid()
        self.process_name = process_name

    def add_span(self, name: str, start_s: float, dur_s: float,
                 tid: Optional[int] = None, **args) -> None:
        """One complete ('X') event; ``start_s`` is a time.perf_counter
        value from the same process."""
        ev = {"name": name, "ph": "X", "pid": self._pid,
              "tid": tid if tid is not None else threading.get_ident(),
              "ts": round((start_s - self._t0) * 1e6, 3),
              "dur": round(dur_s * 1e6, 3)}
        if args:
            ev["args"] = args
        with self._lock:
            self._append_locked(ev)

    def _append_locked(self, ev: dict) -> None:
        """Ring append that COUNTS what the bounded deque would silently
        discard (deque(maxlen=N) drops the oldest event on overflow)."""
        if len(self._events) == self._events.maxlen:
            self.dropped_events += 1
            if not self._warned_drop:
                self._warned_drop = True
                import logging
                logging.getLogger("tpulab.tracing").warning(
                    "ChromeTraceRecorder ring full (max_events=%d): oldest "
                    "events are being dropped; saved traces carry the count "
                    "in otherData.dropped_events", self._events.maxlen)
        self._events.append(ev)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def save(self, path: str) -> str:
        """Atomic write (tmp + rename): a concurrent reader — e.g. the
        merge step polling another process's autosaved trace — never
        observes a torn JSON document."""
        import json
        with self._lock:
            events = list(self._events)
            dropped = self.dropped_events
        if self.process_name:
            events.insert(0, {"name": "process_name", "ph": "M",
                              "pid": self._pid, "tid": 0,
                              "args": {"name": self.process_name}})
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"epoch_origin_s": self._epoch0,
                             "pid": self._pid,
                             "dropped_events": dropped}}
        tmp = f"{path}.tmp.{self._pid}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


def merge_chrome_traces(out_path: str, *paths: str) -> str:
    """Merge per-process Chrome trace files into ONE timeline.

    Each input carries its recorder's ``epoch_origin_s`` anchor (wall
    clock at its events' ts=0); events are shifted by the anchor deltas so
    spans from different processes line up on one wall-clock axis (cross-
    machine accuracy = NTP skew — fine for the >=100us spans recorded
    here).  Events keep their pid, so perfetto shows one process track per
    input.  Metadata ('M') events pass through unshifted."""
    import json
    docs = []
    for p in paths:
        with open(p) as f:
            docs.append(json.load(f))
    origins = [float(d.get("otherData", {}).get("epoch_origin_s", 0.0))
               for d in docs]
    base = min((o for o in origins if o), default=0.0)
    merged = []
    for doc, origin in zip(docs, origins):
        shift_us = (origin - base) * 1e6 if origin else 0.0
        for ev in doc.get("traceEvents", []):
            if ev.get("ph") != "M":
                ev = dict(ev, ts=round(ev.get("ts", 0.0) + shift_us, 3))
            merged.append(ev)
    with open(out_path, "w") as f:
        json.dump({"traceEvents": merged, "displayTimeUnit": "ms",
                   "otherData": {"epoch_origin_s": base,
                                 "merged_from": len(docs)}}, f)
    return out_path

"""tpulab.obs — per-request wide events + live engine introspection.

The two views aggregate telemetry (PR 2's metrics/traces) cannot give:

- :class:`FlightRecorder` (flight.py): ONE structured wide event per
  request, tail-sampled — errors, deadline/overload outcomes, stalls,
  chaos-hit requests and the rolling slowest-p99 exemplars always
  survive the bounded ring; healthy traffic is uniformly sampled.
  Answers "why was THIS request slow" from the record, not a regex over
  logs.
- :func:`debug_snapshot` (debugz.py): the live "what is the engine
  holding right now" document — lanes, elastic pool ladder position,
  HBM ledger claims + verify, modelstore leases, admission queue depths,
  chaos armament, flight exemplar pointers — served over the ``Debug``
  RPC with on-demand XLA profiler capture.
- :class:`EventJournal` (journal.py): the control plane's crash-safe
  append-only JSONL decision log — deaths with evidence, election
  transitions with fencing tokens, autoscaler actions with their
  signals; :func:`replay_journal` reads it back torn-write-tolerantly.
- :class:`SLOTracker` (slo.py): per-tenant availability/latency error
  budgets over fast+slow burn-rate windows, fed from the flight-event
  stream (``flight.add_tap``); exports ``_slo_*`` gauges and the
  autoscaler's optional secondary scale-up signal.

See docs/OBSERVABILITY.md ("Flight recorder", "Debugz", "Fleet
observability").
"""

from tpulab.obs.debugz import arm_profile, debug_snapshot  # noqa: F401
from tpulab.obs.flight import KEEP_REASONS, FlightRecorder  # noqa: F401
from tpulab.obs.journal import (EventJournal, replay_journal,  # noqa: F401
                                sequence_gaps)
from tpulab.obs.slo import SLOTracker  # noqa: F401

__all__ = ["FlightRecorder", "KEEP_REASONS", "debug_snapshot",
           "arm_profile", "EventJournal", "replay_journal", "sequence_gaps",
           "SLOTracker"]

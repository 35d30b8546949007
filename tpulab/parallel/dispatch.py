"""Multi-device serving dispatch: per-chip resource bundles + round robin
(SURVEY §2.8 axis 7 / BASELINE config 5: examples/97's N-streams becomes
N-chips data-parallel on a pod slice).

Each device gets its own InferenceManager (weights replicated, pools local —
the per-socket bundle pattern of reference examples/10_Internals); the
dispatcher routes requests round-robin (or least-loaded) across chips.
"""

from __future__ import annotations

import itertools
import threading
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence


class MultiDeviceDispatcher:
    """Round-robin/least-loaded request router over per-chip managers."""

    def __init__(self, managers: Sequence, policy: str = "round_robin"):
        if not managers:
            raise ValueError("need at least one manager")
        if policy not in ("round_robin", "least_loaded"):
            raise ValueError(f"unknown policy {policy!r}")
        self._managers = list(managers)
        self._policy = policy
        self._rr = itertools.cycle(range(len(self._managers)))
        self._inflight = [0] * len(self._managers)
        #: requests routed to each device so far (cf. ReplicaSet.served)
        self.served = [0] * len(self._managers)
        self._lock = threading.Lock()

    @classmethod
    def create(cls, model_builder: Callable[[], object], model_name: str,
               devices: Optional[Sequence] = None, max_executions: int = 2,
               policy: str = "round_robin") -> "MultiDeviceDispatcher":
        """Build one manager per device, each with its own weight copy."""
        import jax
        from tpulab.engine.inference_manager import InferenceManager

        devs = list(devices) if devices is not None else list(jax.devices())
        managers = []
        for d in devs:
            mgr = InferenceManager(max_executions=max_executions, device=d)
            mgr.register_model(model_name, model_builder())
            mgr.update_resources()
            managers.append(mgr)
        return cls(managers, policy)

    @property
    def device_count(self) -> int:
        return len(self._managers)

    def _pick(self) -> int:
        with self._lock:
            if self._policy == "least_loaded":
                return min(range(len(self._managers)),
                           key=lambda i: self._inflight[i])
            return next(self._rr)

    def infer(self, model_name: str, **arrays) -> Future:
        """Route one request to a chip; returns the request future."""
        i = self._pick()
        with self._lock:
            self._inflight[i] += 1
            self.served[i] += 1
        fut = self._managers[i].infer_runner(model_name).infer(**arrays)

        def _done(_f):
            with self._lock:
                self._inflight[i] -= 1
        fut.add_done_callback(_done)
        return fut

    def manager(self, i: int):
        return self._managers[i]

    def shutdown(self) -> None:
        for m in self._managers:
            m.shutdown()

"""Device watchdog: in-process failure detection.

The reference handles failure at the deployment layer (envoy health routing,
k8s liveness — SURVEY §5 'no in-process retry/failover').  tpulab keeps that
deployment posture (k8s probes hit the Health RPC) but adds the in-process
detector those probes need on TPU: a periodic *canary dispatch* (tiny compiled
program) that catches wedged runtimes — the failure mode where the process is
alive but the device no longer completes work.

``DeviceWatchdog`` flips ``healthy`` when canaries stop completing within
their deadline; the Health RPC reports it, so k8s/envoy rotate the replica
out exactly as the reference's deployment assets expect.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

log = logging.getLogger("tpulab.utils")


class DeviceWatchdog:
    """Periodic canary dispatch with a completion deadline."""

    def __init__(self, device=None, period_s: float = 10.0,
                 deadline_s: float = 30.0,
                 on_unhealthy: Optional[Callable[[str], None]] = None):
        self.period_s = period_s
        self.deadline_s = deadline_s
        self._on_unhealthy = on_unhealthy
        self._device = device
        self._healthy = True
        self._last_ok: Optional[float] = None
        self._reason = ""
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._canary = None

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "DeviceWatchdog":
        import jax
        import jax.numpy as jnp
        from tpulab.tpu import platform as plat

        device = self._device if self._device is not None else plat.local_device(0)
        x = jax.device_put(jnp.ones((8, 8), jnp.float32), device)
        fn = jax.jit(lambda a: (a @ a).sum()).lower(x).compile()
        self._canary = (fn, x)
        self._thread = threading.Thread(target=self._run, name="watchdog",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    # -- state --------------------------------------------------------------
    @property
    def healthy(self) -> bool:
        return self._healthy

    @property
    def reason(self) -> str:
        return self._reason

    @property
    def seconds_since_ok(self) -> Optional[float]:
        return None if self._last_ok is None else time.monotonic() - self._last_ok

    # -- loop ---------------------------------------------------------------
    _probe_thread: Optional[threading.Thread] = None

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            # a still-running probe means the device is still wedged — do
            # NOT stack another thread on it (unbounded leak otherwise)
            if self._probe_thread is not None and self._probe_thread.is_alive():
                self._mark_unhealthy(
                    f"canary still outstanding after {self.deadline_s}s+")
                continue
            fn, x = self._canary  # re-read: canaries are hot-swappable
            done = threading.Event()
            err = []

            def canary():
                try:
                    fn(x).block_until_ready()
                    done.set()
                except Exception as e:  # noqa: BLE001
                    err.append(e)
                    done.set()

            t = threading.Thread(target=canary, daemon=True)
            self._probe_thread = t
            t.start()
            if not done.wait(self.deadline_s) or err:
                self._mark_unhealthy(
                    f"canary error: {err[0]}" if err else
                    f"canary exceeded {self.deadline_s}s deadline")
            else:
                if not self._healthy:
                    log.warning("device recovered")
                self._healthy = True
                self._reason = ""
                self._last_ok = time.monotonic()

    def _mark_unhealthy(self, reason: str) -> None:
        self._reason = reason
        if self._healthy:
            log.error("device unhealthy: %s", reason)
            self._healthy = False
            if self._on_unhealthy is not None:
                try:
                    self._on_unhealthy(reason)
                except Exception:  # pragma: no cover
                    log.exception("on_unhealthy hook failed")

"""Transfer engine: coalesced device->host transfers.

The reference overlaps H2D/compute/D2H by giving each Buffers its own CUDA
stream (buffers.h, SURVEY §2.8 axis 2).  On TPU-via-PjRt the analog problem is
*per-buffer transfer round-trip cost*: every device->host materialization pays
a fixed per-buffer cost independent of size (not measured on the attached
chip yet) — N requests fetching individually pay it N times.

The TransferEngine erases that: a collector thread drains pending result trees
in cycles; each cycle groups same-shape leaves, *stacks them on device* with a
jitted ``jnp.stack`` (device-side copies are ~free), fetches the single
stacked buffer with one ``np.asarray`` (one round trip), and splits rows back
into per-request numpy results.  Group count is padded to powers of two by
repeating the last leaf so the jit cache stays small (the same
bucketing trick the engine uses for batch shapes).

This is the framework's answer to the reference's "post" stage D2H
(bindings CopyFromDevice + Synchronize): post stages await a future from here.
"""

from __future__ import annotations

import collections
import logging
import threading
from concurrent.futures import Future
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

log = logging.getLogger("tpulab.tpu")


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class TransferEngine:
    """Batched D2H collector (one per InferenceManager)."""

    #: below this many leaves in a group, direct fetch beats stack+fetch
    MIN_STACK = 2

    def __init__(self, name: str = "d2h", mode: str = "direct"):
        """``mode``:
        - "direct" (default): per cycle, start copy_to_host_async on every
          pending leaf (one flush) then materialize — robust everywhere.
        - "stack": additionally stack same-shape leaves on device and fetch
          one buffer per group.  Wins when per-transfer fixed cost dominates
          AND program-argument registration is cheap; loses when each
          program argument has a cost of its own.
        """
        if mode not in ("direct", "stack"):
            raise ValueError(f"unknown transfer mode {mode!r}")
        self.mode = mode
        #: entries: (kind "fetch"|"put", tree, device-or-None, future)
        self._queue: Deque[Tuple[str, Any, Any, Future]] = collections.deque()
        self._cv = threading.Condition()
        self._shutdown = False
        self._stack_fn = None  # lazily built jitted stack
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    # -- public API ---------------------------------------------------------
    def fetch(self, tree: Any) -> Future:
        """Enqueue a JAX pytree; the future resolves to the same tree with
        numpy leaves."""
        fut: Future = Future()
        with self._cv:
            if self._shutdown:
                raise RuntimeError("TransferEngine is shut down")
            self._queue.append(("fetch", tree, None, fut))
            self._cv.notify()
        return fut

    def put(self, tree: Any, device=None) -> Future:
        """Coalesced host->device: pending puts ship in ONE jax.device_put
        call per cycle (one fixed cost, not N).  The future resolves to
        the device tree."""
        fut: Future = Future()
        with self._cv:
            if self._shutdown:
                raise RuntimeError("TransferEngine is shut down")
            self._queue.append(("put", tree, device, fut))
            self._cv.notify()
        return fut

    def fetch_sync(self, tree: Any, timeout: Optional[float] = None) -> Any:
        return self.fetch(tree).result(timeout)

    @property
    def backlog(self) -> int:
        with self._cv:
            return len(self._queue)

    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify()
        self._thread.join(timeout=10)

    # -- collector ----------------------------------------------------------
    def _run(self) -> None:
        import jax
        self._stack_fn = jax.jit(lambda xs: jax.numpy.stack(xs))
        while True:
            with self._cv:
                while not self._queue and not self._shutdown:
                    self._cv.wait()
                if self._shutdown and not self._queue:
                    return
                entries = list(self._queue)
                self._queue.clear()
            fetches = [(t, f) for kind, t, _d, f in entries if kind == "fetch"]
            puts = [(t, d, f) for kind, t, d, f in entries if kind == "put"]
            if puts:
                try:
                    self._process_puts(jax, puts)
                except Exception:  # pragma: no cover - collector must live
                    log.exception("put cycle failed")
            if not fetches:
                continue
            cycle = fetches
            try:
                self._process_cycle(jax, cycle)
            except Exception:  # pragma: no cover - never kill the collector
                log.exception("transfer cycle failed; falling back per-item")
                for tree, fut in cycle:
                    if fut.done():
                        continue
                    try:
                        fut.set_result(jax.tree_util.tree_map(np.asarray, tree))
                    except BaseException as e:  # noqa: BLE001
                        fut.set_exception(e)

    @staticmethod
    def _settle(fut: Future, value=None, exc=None) -> None:
        """Resolve a future tolerating concurrent cancellation."""
        try:
            if exc is not None:
                fut.set_exception(exc)
            elif not fut.done():
                fut.set_result(value)
        except Exception:  # InvalidStateError on racing cancel — drop
            pass

    def _process_puts(self, jax, puts) -> None:
        """One jax.device_put per (device, cycle): ships every pending host
        tree together."""
        by_device: Dict[Any, List] = {}
        for tree, device, fut in puts:
            by_device.setdefault(device, []).append((tree, fut))
        for device, group in by_device.items():
            try:
                shipped = jax.device_put([t for t, _f in group], device)
            except Exception:
                # fall back per-item so one bad tree doesn't sink the group
                for tree, fut in group:
                    if fut.done():
                        continue
                    try:
                        self._settle(fut, jax.device_put(tree, device))
                    except BaseException as e:  # noqa: BLE001
                        self._settle(fut, exc=e)
                continue
            for dev_tree, (_t, fut) in zip(shipped, group):
                self._settle(fut, dev_tree)

    def _process_cycle(self, jax, cycle: List[Tuple[Any, Future]]) -> None:
        # Flatten every pending tree; group leaves by (shape, dtype).
        flat: List[Tuple[int, list, Any]] = []  # (cycle idx, leaves, treedef)
        groups: Dict[Tuple, List[Tuple[int, int, Any]]] = {}
        for i, (tree, _fut) in enumerate(cycle):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            flat.append((i, leaves, treedef))
            for j, leaf in enumerate(leaves):
                # only device arrays join a fetch group: a plain numpy leaf
                # has no copy_to_host_async and would abort the whole cycle
                # into the per-item fallback, losing coalescing
                if hasattr(leaf, "copy_to_host_async"):
                    key = (tuple(leaf.shape), str(leaf.dtype))
                    groups.setdefault(key, []).append((i, j, leaf))

        host_leaves: Dict[Tuple[int, int], np.ndarray] = {}
        for key, entries in groups.items():
            n = len(entries)
            if self.mode == "stack" and n >= self.MIN_STACK:
                # pad to a power of two with repeats: keeps the jit cache at
                # log2 variants per shape signature
                padded = [e[2] for e in entries]
                padded += [padded[-1]] * (_next_pow2(n) - n)
                try:
                    stacked = self._stack_fn(padded)
                    host = np.asarray(stacked)          # ONE round trip
                    for row, (i, j, _leaf) in enumerate(entries):
                        host_leaves[(i, j)] = host[row]
                    continue
                except Exception:  # fall through to per-leaf fetch
                    log.exception("stacked fetch failed for group %s", key)
            for (i, j, leaf) in entries:
                leaf.copy_to_host_async()
            for (i, j, leaf) in entries:
                host_leaves[(i, j)] = np.asarray(leaf)

        for i, leaves, treedef in flat:
            fut = cycle[i][1]
            if fut.done():
                continue
            try:
                out = []
                for j in range(len(leaves)):
                    if (i, j) in host_leaves:
                        out.append(host_leaves[(i, j)])
                    elif hasattr(leaves[j], "shape"):
                        out.append(np.asarray(leaves[j]))
                    else:
                        out.append(leaves[j])
                fut.set_result(jax.tree_util.tree_unflatten(treedef, out))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

"""KV offload manager: device<->host tiering policy over the paged pool.

HBM pressure in the serving stack used to destroy state: a preempted
request re-prefilled prompt+generated from scratch, an evicted prefix
cache entry was simply gone.  This module turns both into *demotions* to
a host-RAM tier (:class:`~tpulab.kvcache.host_store.HostKVStore`) and
back:

- **Preemption** — :meth:`KVOffloadManager.swap_out` snapshots the
  victim lane's live KV pages device->host *asynchronously* (device-side
  gather dispatched inline, the host fetch rides the
  :class:`~tpulab.tpu.transfer.TransferEngine` collector thread — the
  decode tick never blocks on swap-out: write-behind).  On resume,
  :meth:`restore` scatters the snapshot into freshly allocated pages and
  the request continues decoding with ZERO prefill dispatches.
- **Prefix-cache eviction** — :meth:`demote` moves an evicted entry's
  page to the host tier keyed by its prompt digest; :meth:`promote`
  brings it back on the next lookup hit, making the prefix cache's
  effective capacity host-RAM-sized.

Every degraded path is the pre-offload behavior: a snapshot that was
dropped (budget), failed (transfer error) or chaos-tripped
(``kvcache.swap``) simply leaves the request on today's
re-prefill/recompute path — offload can only *save* work, never corrupt
a lane.

Sharded pools (mesh serving): the snapshot gather of a sharded pool
produces a payload sharded like the pool (KV-heads dim); the
TransferEngine fetch assembles it into ONE unsharded host array, so the
host tier and the disagg wire always hold mesh-portable bytes, and
restore's device_put re-shards them onto the LOCAL pool placement — a
decode replica on a different mesh imports bit-exactly.

Ordering safety: the gather that snapshots pages is dispatched BEFORE
the pages are released, and XLA executes a device's programs in
dispatch order — any later write into a recycled page is ordered after
the gather's read, so the snapshot observes the victim's bytes even
though the fetch completes later.
"""

from __future__ import annotations

import logging
import threading
import time as _time
from typing import Any, Dict, List, Optional

import numpy as np

from tpulab import chaos
from tpulab.kvcache.host_store import HostKVStore

log = logging.getLogger("tpulab.kvcache")

#: default host-tier budget (bytes) when ``kv_offload=True``-style knobs
#: construct the manager implicitly
DEFAULT_HOST_BUDGET = 256 << 20

#: swap-handle states
_PENDING, _RESIDENT, _DROPPED, _FAILED = range(4)


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class SwapHandle:
    """One lane snapshot's lifecycle token.  Returned by ``swap_out``;
    consumed by ``restore``.  ``wait()`` is the write-behind fence —
    True once the snapshot is resident in the host tier."""

    __slots__ = ("key", "n_pages", "length", "_done", "_state")

    def __init__(self, key, n_pages: int, length: int):
        self.key = key
        self.n_pages = n_pages
        self.length = length            # resident positions the snapshot covers
        self._done = threading.Event()
        self._state = _PENDING

    def wait(self, timeout: Optional[float] = None) -> bool:
        """True when the snapshot landed in the host tier; False while
        still in flight (timeout) or when it was dropped/failed."""
        self._done.wait(timeout)
        return self._state == _RESIDENT

    @property
    def resident(self) -> bool:
        return self._state == _RESIDENT


class KVOffloadManager:
    """Device<->host KV tiering for one :class:`PagedKVPool` (module
    docstring).  ``transfer`` is an optional shared
    :class:`~tpulab.tpu.transfer.TransferEngine` (one is owned
    otherwise); ``metrics`` an optional
    :class:`~tpulab.utils.metrics.KVTierMetrics` observing swap
    latency/bytes at the source.
    """

    #: bound on how long a resume waits for its write-behind snapshot to
    #: land before falling back to re-prefill (the snapshot is normally
    #: resident long before the victim reaches the queue head)
    RESTORE_WAIT_S = 10.0

    def __init__(self, pool, host_budget_bytes: int = DEFAULT_HOST_BUDGET,
                 store: Optional[HostKVStore] = None, transfer=None,
                 metrics=None):
        import jax
        import jax.numpy as jnp

        self.pool = pool
        # identity check, not truthiness: an EMPTY HostKVStore is falsy
        # (__len__ == 0) and `store or ...` would silently replace it
        self.store = store if store is not None \
            else HostKVStore(host_budget_bytes)
        if transfer is None:
            from tpulab.tpu.transfer import TransferEngine
            transfer = TransferEngine(name="kvswap")
            self._owns_transfer = True
        else:
            self._owns_transfer = False
        self._transfer = transfer
        self.metrics = metrics
        # per-page payload size: pool store is (L, P, 2, S, Hkv*D); one
        # page carries every layer's K+V rows for its S slots
        shape = tuple(pool.kv.shape)
        self.page_nbytes = int(np.prod(shape) // shape[1]
                               * jnp.dtype(pool.dtype).itemsize)
        # page-index gathers/scatters, padded to pow2 page counts so the
        # jit cache stays at log2 variants (padding rides the RESERVED
        # scratch page 0: reads of it are discarded, writes to it are
        # harmless by the pool's own contract).  Cached per (pow2 count,
        # POOL PLACEMENT): the placement — mesh axes + spec + device set,
        # or the single bound device — must be part of the key, so a pool
        # re-pointed at a different mesh (a decode replica importing onto
        # its own topology) can never reuse a scatter compiled for the
        # old placement.  Sharded pools round-trip bit-exactly: the
        # gather's payload is fetched to ONE unsharded host array (the
        # host tier and the disagg wire always hold mesh-portable bytes)
        # and restore re-shards it onto the local placement at device_put.
        self._gather_fns: Dict[Any, Any] = {}
        self._scatter_fns: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        self._ops_cv = threading.Condition(self._lock)
        self._seq = 0
        self._pending_ops = 0   # write-behind copies still in flight
        # -- counters (KVTierMetrics.poll advances from these) --------------
        self.swap_outs = 0              # lane snapshots dispatched
        self.swap_ins = 0               # lane snapshots restored
        self.swap_out_bytes = 0
        self.swap_in_bytes = 0
        self.swap_failures = 0          # chaos/transfer degradations
        self.swap_drops = 0             # host-budget-refused snapshots
        self.demotions = 0              # prefix pages demoted to host
        self.promotions = 0             # prefix pages promoted back
        self.recompute_tokens_saved = 0  # prefill tokens resumes skipped

    # -- placement-keyed jits ---------------------------------------------
    def _placement_key(self):
        """Fingerprint of where pool-shaped arrays live: mesh axes + spec
        + device ids for a sharded pool, the bound device otherwise."""
        sh = getattr(self.pool, "kv_sharding", None)
        if sh is None:
            d = self.pool.device
            return ("dev", getattr(d, "id", id(d)))
        return ("mesh", tuple(sh.mesh.shape.items()), str(sh.spec),
                tuple(int(d.id) for d in sh.mesh.devices.flat))

    def _gather_fn(self, n_padded: int):
        import jax
        key = (n_padded, self._placement_key())
        fn = self._gather_fns.get(key)
        if fn is None:
            fn = jax.jit(lambda kv, idx: kv[:, idx])
            self._gather_fns[key] = fn
        return fn

    def _scatter_fn(self, n_padded: int):
        import jax
        key = (n_padded, self._placement_key())
        fn = self._scatter_fns.get(key)
        if fn is None:
            fn = jax.jit(lambda kv, idx, data: kv.at[:, idx].set(data),
                         donate_argnums=(0,))
            self._scatter_fns[key] = fn
        return fn

    def _host_view(self, fetched) -> np.ndarray:
        """A fetched gather of the pool's rows ``(L, n, 2, S, Hkv*D)`` as
        the host tier and the wire hold it: ``(L, n, 2, S, Hkv, D)``, the
        same bytes."""
        arr = np.asarray(fetched)
        return arr.reshape(self.pool.host_shape(arr.shape[1]))

    def _device_put(self, arr: np.ndarray):
        """A host snapshot back on the device, as rows, for the scatter:
        on the pool's NamedSharding under a mesh (the import RE-SHARDS
        host bytes onto the local topology), the pool device otherwise."""
        import jax

        from tpulab.engine.kv_pool import kv_rows_view
        return jax.device_put(kv_rows_view(arr), self.pool.placement)

    # -- lane swap (preemption) ----------------------------------------------
    def swap_out(self, pages: List[int], length: int, kv,
                 key=None) -> Optional[SwapHandle]:
        """Snapshot ``pages`` (covering positions ``[0, length)``) to the
        host tier.  Dispatches the device gather and returns immediately;
        the D2H fetch + store happen behind the decode loop (write-
        behind).  None = degraded (chaos/failure): caller keeps today's
        drop-and-re-prefill path.

        ``key`` overrides the minted ``("lane", seq)`` store key — the
        disaggregation path keys finished-prefill exports by prompt
        digest (``("ship", digest)``) so the shipper can find them."""
        if not pages or length <= 0:
            return None
        try:
            if chaos.trip("kvcache.swap") == "drop":
                raise chaos.ChaosError("injected swap drop")
            n = len(pages)
            idx = np.zeros((_next_pow2(n),), np.int32)  # pad -> scratch 0
            idx[:n] = pages
            gathered = self._gather_fn(idx.shape[0])(kv, idx)
        except Exception as e:  # noqa: BLE001 - degrade, never corrupt
            self.swap_failures += 1
            log.warning("KV swap-out degraded to recompute path: %s: %s",
                        type(e).__name__, str(e)[:200])
            return None
        with self._lock:
            self._seq += 1
            handle = SwapHandle(key if key is not None
                                else ("lane", self._seq), n, length)
            self._pending_ops += 1
        t0 = _time.perf_counter()
        fut = self._transfer.fetch(gathered)
        fut.add_done_callback(
            lambda f: self._on_fetched(handle, f, n, t0, ("lane",)))
        return handle

    def _on_fetched(self, handle: SwapHandle, fut, n: int, t0: float,
                    kind) -> None:
        """TransferEngine-thread completion: land the snapshot in the host
        tier (the future itself is dropped afterwards, so the only host
        copy is the budgeted one)."""
        try:
            arr = self._host_view(fut.result())[:, :n]  # strip pow2 padding
            stored = self.store.put(handle.key, arr)
        except Exception:  # noqa: BLE001 - collector thread must live
            handle._state = _FAILED
            self.swap_failures += 1
            log.exception("KV swap-out fetch failed")
        else:
            if stored:
                handle._state = _RESIDENT
                self.swap_outs += 1
                self.swap_out_bytes += arr.nbytes
                if self.metrics is not None:
                    self.metrics.observe_swap_out(
                        _time.perf_counter() - t0, arr.nbytes)
            else:
                # budget-rejected put: NOT a transfer failure — a distinct
                # counter (and log line) so an undersized host budget is
                # diagnosable separately from a flaky transfer path
                handle._state = _DROPPED
                self.swap_drops += 1
                log.warning(
                    "KV swap-out dropped: host tier refused %d bytes "
                    "(budget %d, headroom %d) — host budget undersized?",
                    arr.nbytes, self.store.budget_bytes,
                    self.store.headroom_bytes)
        finally:
            handle._done.set()
            with self._ops_cv:
                self._pending_ops -= 1
                self._ops_cv.notify_all()

    def restore(self, handle: SwapHandle, pages: List[int], kv):
        """Scatter ``handle``'s snapshot into ``pages`` (freshly allocated
        by the caller, same count).  Returns the new donated pool buffer,
        or None when the snapshot is unavailable (still in flight past
        :data:`RESTORE_WAIT_S`, dropped, failed, or chaos-tripped) — the
        caller then re-prefills exactly as before offload existed.

        Degradation boundary: every failure BEFORE the scatter dispatch
        returns None with ``kv`` untouched.  A failure in the scatter
        itself propagates — the donated buffer is gone and the scheduler's
        pool-reset recovery path must run, same as any failed step."""
        t0 = _time.perf_counter()
        try:
            if chaos.trip("kvcache.swap") == "drop":
                raise chaos.ChaosError("injected swap drop")
            if not handle.wait(self.RESTORE_WAIT_S):
                raise chaos.ChaosError("snapshot unavailable")
            arr = self.store.pop(handle.key)
            if arr is None or len(pages) != handle.n_pages:
                raise chaos.ChaosError("snapshot evicted from host tier")
            n = handle.n_pages
            idx = np.zeros((_next_pow2(n),), np.int32)  # pad -> scratch 0
            idx[:n] = pages
            if n != idx.shape[0]:
                # padded slots all land on the reserved scratch page 0,
                # so their payload is never read back: pad with ONE zero
                # page broadcast across the pad width instead of
                # np.repeat-ing the last real page (which allocated and
                # shipped real-page copies for every non-pow2 snapshot)
                zero = np.zeros_like(arr[:, :1])
                pad = np.broadcast_to(
                    zero, (arr.shape[0], idx.shape[0] - n) + arr.shape[2:])
                arr = np.concatenate([arr, pad], axis=1)
            data = self._device_put(arr)
        except Exception as e:  # noqa: BLE001 - pre-dispatch: degrade
            self.swap_failures += 1
            self.store.remove(handle.key)
            log.warning("KV swap-in degraded to re-prefill: %s: %s",
                        type(e).__name__, str(e)[:200])
            return None
        new_kv = self._scatter_fn(idx.shape[0])(kv, idx, data)
        self.swap_ins += 1
        self.swap_in_bytes += handle.n_pages * self.page_nbytes
        self.recompute_tokens_saved += handle.length
        if self.metrics is not None:
            self.metrics.observe_swap_in(
                _time.perf_counter() - t0,
                handle.n_pages * self.page_nbytes)
        return new_kv

    def discard(self, handle: SwapHandle) -> None:
        """Forget a snapshot that will never be restored (request
        cancelled/expired while queued)."""
        self.store.remove(handle.key)

    # -- KV shipping (tpulab.disagg) -----------------------------------------
    def take_snapshot(self, handle: SwapHandle,
                      timeout: Optional[float] = None) -> Optional[np.ndarray]:
        """One-shot fetch of a snapshot's host payload for wire export
        (the disaggregation path).  Waits out the write-behind fence,
        then POPS the entry — after a successful export the only copy is
        the wire payload.  None when the snapshot was dropped/failed or
        evicted (the caller degrades to shipping nothing: the decode
        side prefills locally)."""
        if not handle.wait(self.RESTORE_WAIT_S if timeout is None
                           else timeout):
            return None
        return self.store.pop(handle.key)

    def adopt(self, key, array: np.ndarray,
              length: int) -> Optional[SwapHandle]:
        """Land an externally produced snapshot (a shipped-KV import) in
        the host tier and mint the already-RESIDENT handle that
        :meth:`restore` consumes — the decode replica's admit-from-
        shipped-KV entry point.  None when the budget refuses the
        payload (counted in ``swap_drops``; the caller degrades to local
        prefill)."""
        array = np.ascontiguousarray(array)
        n = int(array.shape[1])
        if not self.store.put(key, array):
            self.swap_drops += 1
            log.warning("shipped KV snapshot refused by host tier "
                        "(%d bytes, budget %d)", array.nbytes,
                        self.store.budget_bytes)
            return None
        handle = SwapHandle(key, n, int(length))
        handle._state = _RESIDENT
        handle._done.set()
        return handle

    # -- prefix-cache tiering ------------------------------------------------
    def demote(self, digest: bytes, page: int, kv) -> None:
        """Async-copy one evicted prefix page to the host tier (called by
        the cache's eviction path BEFORE the page is released — dispatch
        order makes the snapshot safe, see module docstring)."""
        try:
            if chaos.trip("kvcache.swap") == "drop":
                raise chaos.ChaosError("injected swap drop")
            gathered = self._gather_fn(1)(kv, np.asarray([page], np.int32))
        except Exception as e:  # noqa: BLE001 - the entry just drops
            self.swap_failures += 1
            log.warning("prefix demotion skipped: %s: %s",
                        type(e).__name__, str(e)[:200])
            return
        t0 = _time.perf_counter()
        with self._lock:
            self._pending_ops += 1
        fut = self._transfer.fetch(gathered)

        def land(f):
            try:
                if self.store.put(("px", digest),
                                  self._host_view(f.result())):
                    self.demotions += 1
                    self.swap_out_bytes += self.page_nbytes
                    if self.metrics is not None:
                        self.metrics.observe_swap_out(
                            _time.perf_counter() - t0, self.page_nbytes)
            except Exception:  # noqa: BLE001
                self.swap_failures += 1
                log.exception("prefix demotion fetch failed")
            finally:
                with self._ops_cv:
                    self._pending_ops -= 1
                    self._ops_cv.notify_all()

        fut.add_done_callback(land)

    def has_prefix(self, digest: bytes) -> bool:
        return ("px", digest) in self.store

    def promote(self, digest: bytes, page: int, kv):
        """Upload a demoted prefix page into ``page``.  Returns the new
        donated pool buffer, or None (miss/failure — caller releases the
        page and recomputes, today's path)."""
        t0 = _time.perf_counter()
        try:
            if chaos.trip("kvcache.swap") == "drop":
                raise chaos.ChaosError("injected swap drop")
            arr = self.store.pop(("px", digest))
            if arr is None:
                return None
            data = self._device_put(arr)
        except Exception as e:  # noqa: BLE001 - pre-dispatch: degrade
            self.swap_failures += 1
            log.warning("prefix promotion degraded to recompute: %s: %s",
                        type(e).__name__, str(e)[:200])
            return None
        new_kv = self._scatter_fn(1)(kv, np.asarray([page], np.int32), data)
        self.promotions += 1
        self.swap_in_bytes += self.page_nbytes
        if self.metrics is not None:
            self.metrics.observe_swap_in(_time.perf_counter() - t0,
                                         self.page_nbytes)
        return new_kv

    # -- load signals ---------------------------------------------------------
    def headroom_pages(self) -> int:
        """How many more KV pages the host tier can absorb without
        evicting (admission's host-tier headroom term)."""
        return self.store.headroom_bytes // max(1, self.page_nbytes)

    def demotable_pages(self, prefix_cache) -> int:
        """Device pages that pressure could DEMOTE instead of drop right
        now: capped both by what the cache holds and by host headroom."""
        cached = len(prefix_cache) if prefix_cache is not None else 0
        return min(cached, self.headroom_pages())

    # -- lifecycle ------------------------------------------------------------
    def drain(self, timeout: float = 10.0) -> bool:
        """Block until every write-behind copy (lane swap-outs AND prefix
        demotions) has settled (tests, shutdown).  False on timeout."""
        with self._ops_cv:
            return self._ops_cv.wait_for(
                lambda: self._pending_ops == 0, timeout)

    def close(self) -> None:
        self.drain(timeout=2.0)
        if self._owns_transfer:
            self._transfer.shutdown()
        self.store.clear()

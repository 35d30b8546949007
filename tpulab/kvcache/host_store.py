"""Host-memory KV tier: budgeted, LRU, page-granular byte store.

The serving stack's KV pages live in HBM (:class:`~tpulab.engine.kv_pool.
PagedKVPool`); this module is the tier BELOW it — host RAM holding KV
snapshots that HBM pressure pushed out (preempted lanes, evicted prefix
cache entries).  It is deliberately dumb: keys map to opaque byte
payloads with shape/dtype metadata, an LRU order, and a hard byte
budget.  All tiering *policy* (what to demote, when to promote) lives in
:class:`~tpulab.kvcache.offload.KVOffloadManager`.

The storage itself comes from the :mod:`tpulab.memory` framework — each
entry owns a :class:`~tpulab.memory.descriptor.Descriptor` from a host
``IAllocator`` (default: the mmap-backed
:class:`~tpulab.memory.raw_allocators.MallocAllocator` behind the
``make_allocator`` facade), written through the descriptor's zero-copy
numpy view.  That finally puts the typed allocator/descriptor library —
the reference framework's core (SURVEY §2.1) — on the serving hot path
instead of beside it.

Thread safety: one lock.  The TransferEngine collector thread writes
(swap-out completions land here), the scheduler thread reads/promotes.
``get`` returns a *copy*, never the live view: an LRU eviction from
another thread closes the backing mapping, and a zero-copy view must not
outlive it.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Optional, Tuple

import numpy as np

from tpulab.memory.allocator import make_allocator
from tpulab.memory.descriptor import Descriptor
from tpulab.memory.raw_allocators import MallocAllocator


class _Entry:
    __slots__ = ("desc", "shape", "dtype", "nbytes")

    def __init__(self, desc: Descriptor, shape: Tuple[int, ...], dtype,
                 nbytes: int):
        self.desc = desc
        self.shape = shape
        self.dtype = dtype
        self.nbytes = nbytes


class HostKVStore:
    """Budgeted LRU byte store for KV page payloads (module docstring).

    ``budget_bytes`` caps resident payload bytes; inserting past it
    evicts cold entries first, and a single payload larger than the whole
    budget is refused (``put`` returns False — the caller's drop path,
    identical to not having a host tier for that entry).
    """

    def __init__(self, budget_bytes: int, allocator=None):
        if budget_bytes <= 0:
            raise ValueError("budget_bytes must be > 0")
        self.budget_bytes = int(budget_bytes)
        self._alloc = allocator or make_allocator(MallocAllocator())
        self._entries: "OrderedDict[Any, _Entry]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        # -- counters (poll-advanced by KVTierMetrics) ----------------------
        self.puts = 0          # payloads stored
        self.hits = 0          # get/pop found the key
        self.misses = 0        # get/pop did not
        self.evictions = 0     # LRU entries pushed out by budget pressure
        self.drops = 0         # payloads refused (larger than the budget)
        self.peeks = 0         # non-LRU export reads (fabric fetches)

    # -- sizing --------------------------------------------------------------
    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes

    @property
    def headroom_bytes(self) -> int:
        """Bytes storable right now WITHOUT evicting (admission's host-tier
        headroom signal reads this)."""
        with self._lock:
            return max(0, self.budget_bytes - self._bytes)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    # -- the tier ------------------------------------------------------------
    def put(self, key, array: np.ndarray) -> bool:
        """Store ``array`` under ``key`` (replacing any incumbent), evicting
        LRU entries until it fits.  False = refused (payload exceeds the
        whole budget) — the entry is simply NOT in the tier, which callers
        must treat as today's drop-and-recompute path."""
        array = np.ascontiguousarray(array)
        nbytes = int(array.nbytes)
        with self._lock:
            if nbytes > self.budget_bytes:
                self.drops += 1
                return False
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
                old.desc.release()
            while self._bytes + nbytes > self.budget_bytes and self._entries:
                _, cold = self._entries.popitem(last=False)
                self._bytes -= cold.nbytes
                cold.desc.release()
                self.evictions += 1
            desc = self._alloc.allocate_descriptor(max(1, nbytes))
            desc.numpy(array.dtype, array.shape)[...] = array
            self._entries[key] = _Entry(desc, array.shape, array.dtype,
                                        nbytes)
            self._bytes += nbytes
            self.puts += 1
            return True

    def get(self, key) -> Optional[np.ndarray]:
        """A COPY of the payload (and an LRU touch), or None."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return e.desc.numpy(e.dtype, e.shape).copy()

    def peek(self, key) -> Optional[np.ndarray]:
        """A COPY of the payload WITHOUT the LRU touch — the fleet KV
        fabric's export read (tpulab.kvfabric).  A remote replica pulling
        a prefix must not look like local reuse: under a fetch storm,
        ``get``'s recency bump would pin fabric-popular entries hot and
        evict the owner's OWN working set instead.  Counted separately
        (``peeks``) so fetch traffic never skews hit/miss ratios."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            self.peeks += 1
            return e.desc.numpy(e.dtype, e.shape).copy()

    def pop(self, key) -> Optional[np.ndarray]:
        """``get`` + remove — the one-shot read for preemption snapshots
        (a restored lane's host copy is dead weight)."""
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                self.misses += 1
                return None
            self._bytes -= e.nbytes
            self.hits += 1
            out = e.desc.numpy(e.dtype, e.shape).copy()
            e.desc.release()
            return out

    def remove(self, key) -> bool:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                return False
            self._bytes -= e.nbytes
            e.desc.release()
            return True

    def clear(self) -> None:
        with self._lock:
            for e in self._entries.values():
                e.desc.release()
            self._entries.clear()
            self._bytes = 0

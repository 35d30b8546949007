"""tpulab.native — cffi bindings to the C++ runtime core (cpp/).

The reference's memory/concurrency machinery is C++ (trtlab/memory,
trtlab/core); ours lives in ``cpp/`` as ``libtpulab_native.so`` with a C API
(cpp/include/tpulab/c_api.h).  This module loads it when built and exposes:

- :class:`NativeArena`, :class:`NativeTransactionalAllocator`,
  :class:`NativeBFitAllocator` — RawAllocator-concept adapters that compose
  with the Python framework (descriptors, trackers, make_allocator) while the
  allocation math runs native
- :class:`NativeTokenPool` — futex-backed blocking token pool
- :func:`available` / :func:`enabled` — the feature gate.  The library is
  taken from ``TPULAB_NATIVE_LIB`` if set, else from ``cpp/build/`` (build
  with ``cmake -S cpp -B cpp/build -G Ninja && ninja -C cpp/build``;
  git-ignored, so a clean checkout has none).  Without it the engine's
  pools and allocators are the pure-Python implementations: a different
  host program, so anything that reports a result also reports
  :func:`loaded_path` (``chip_smoke.py`` builds its own copy and prints
  ``native_core=true|false``).
"""

from __future__ import annotations

import os
import weakref
from typing import Optional

from tpulab.memory.debugging import InvalidPointer, OutOfMemory
from tpulab.memory.memory_type import HostMemory, MemoryType

_ffi = None
_lib = None
_path: Optional[str] = None

_CDEF = """
typedef struct tpl_arena tpl_arena;
tpl_arena* tpl_arena_create(size_t, size_t, size_t);
void tpl_arena_destroy(tpl_arena*);
void* tpl_arena_allocate_block(tpl_arena*);
void tpl_arena_deallocate_block(tpl_arena*, void*);
size_t tpl_arena_block_size(tpl_arena*);
size_t tpl_arena_live_blocks(tpl_arena*);
size_t tpl_arena_cached_blocks(tpl_arena*);
size_t tpl_arena_shrink(tpl_arena*);

typedef struct tpl_txalloc tpl_txalloc;
tpl_txalloc* tpl_txalloc_create(tpl_arena*, size_t);
void tpl_txalloc_destroy(tpl_txalloc*);
void* tpl_txalloc_allocate(tpl_txalloc*, size_t, size_t);
int tpl_txalloc_deallocate(tpl_txalloc*, void*);
size_t tpl_txalloc_live_stacks(tpl_txalloc*);

typedef struct tpl_bfit tpl_bfit;
tpl_bfit* tpl_bfit_create(tpl_arena*, int);
void tpl_bfit_destroy(tpl_bfit*);
void* tpl_bfit_allocate(tpl_bfit*, size_t, size_t);
int tpl_bfit_deallocate(tpl_bfit*, void*);
size_t tpl_bfit_free_bytes(tpl_bfit*);
size_t tpl_bfit_live(tpl_bfit*);

typedef struct tpl_pool tpl_pool;
tpl_pool* tpl_pool_create(void);
void tpl_pool_destroy(tpl_pool*);
void tpl_pool_push(tpl_pool*, int64_t);
int tpl_pool_pop(tpl_pool*, int64_t*, int64_t);
int tpl_pool_try_pop(tpl_pool*, int64_t*);
size_t tpl_pool_size(tpl_pool*);

const char* tpl_version(void);
"""


def _candidate_paths():
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = os.environ.get("TPULAB_NATIVE_LIB")
    if env:
        yield env
    yield os.path.join(here, "cpp", "build", "libtpulab_native.so")


def _load():
    global _ffi, _lib, _path
    if _lib is not None:
        return True
    try:
        import cffi
    except ImportError:
        return False
    for path in _candidate_paths():
        if os.path.exists(path):
            ffi = cffi.FFI()
            ffi.cdef(_CDEF)
            try:
                lib = ffi.dlopen(path)
            except OSError:
                continue
            _ffi, _lib, _path = ffi, lib, path
            return True
    return False


def available() -> bool:
    return _load()


def enabled() -> bool:
    """Built AND not disabled via ``TPULAB_NO_NATIVE=1`` (the A/B knob the
    engine's pool/staging selection honors)."""
    return os.environ.get("TPULAB_NO_NATIVE") != "1" and available()


def loaded_path() -> Optional[str]:
    """The file the native core was loaded from; None = pure Python."""
    return _path if _load() else None


def version() -> Optional[str]:
    if not _load():
        return None
    return _ffi.string(_lib.tpl_version()).decode()


class NativeArena:
    """Caching block arena (native block_arena)."""

    def __init__(self, block_size: int, alignment: int = 64,
                 max_blocks: int = 0):
        if not _load():
            raise RuntimeError("native library not built")
        self._h = _lib.tpl_arena_create(block_size, alignment, max_blocks)
        # GC backstop: native memory must not outlive the Python handle
        self._finalizer = weakref.finalize(
            self, _lib.tpl_arena_destroy, self._h)
        self.memory_type: MemoryType = HostMemory

    @property
    def next_block_size(self) -> int:
        return _lib.tpl_arena_block_size(self._h)

    block_size = next_block_size

    def allocate_block(self):
        from tpulab.memory.block import MemoryBlock
        ptr = _lib.tpl_arena_allocate_block(self._h)
        if ptr == _ffi.NULL:
            raise OutOfMemory("NativeArena", self.next_block_size)
        return MemoryBlock(int(_ffi.cast("uintptr_t", ptr)),
                           self.next_block_size)

    def deallocate_block(self, block) -> None:
        _lib.tpl_arena_deallocate_block(
            self._h, _ffi.cast("void*", block.addr))

    @property
    def live_blocks(self) -> int:
        return _lib.tpl_arena_live_blocks(self._h)

    @property
    def cached_blocks(self) -> int:
        return _lib.tpl_arena_cached_blocks(self._h)

    def shrink_to_fit(self) -> int:
        return _lib.tpl_arena_shrink(self._h)

    def close(self) -> None:
        if self._finalizer.alive:
            self._finalizer()
        self._h = None


def _destroy_with_arena(destroy_fn, handle, arena_destroy, arena_handle):
    """Ordered teardown for allocators that own their arena: the allocator's
    destructor returns blocks to the arena, so it must die first."""
    destroy_fn(handle)
    if arena_handle is not None:
        arena_destroy(arena_handle)


class _NativeAllocBase:
    """RawAllocator concept over a native allocator handle."""

    is_stateful = True
    memory_type: MemoryType = HostMemory

    def view(self, addr: int, size: int):
        from tpulab.memory.descriptor import host_view
        return host_view(addr, size)


class NativeTransactionalAllocator(_NativeAllocBase):
    """Native rotating bump-stack allocator (RawAllocator concept)."""

    def __init__(self, block_size: int = 1 << 20, max_stacks: int = 0,
                 arena: Optional[NativeArena] = None):
        if not _load():
            raise RuntimeError("native library not built")
        self._owns_arena = arena is None
        self._arena = arena or NativeArena(block_size)
        self._h = _lib.tpl_txalloc_create(self._arena._h, max_stacks)
        # ~TransactionalAllocator returns blocks to the arena: when we own
        # the arena, one ordered finalizer tears down both (GC finalizer
        # order within a cycle is unspecified, so the arena's own is
        # detached); an externally-owned arena stays alive via self._arena
        arena_h = None
        if self._owns_arena:
            self._arena._finalizer.detach()
            arena_h = self._arena._h
        self._finalizer = weakref.finalize(
            self, _destroy_with_arena, _lib.tpl_txalloc_destroy, self._h,
            _lib.tpl_arena_destroy, arena_h)

    def allocate_node(self, size: int, alignment: int = 64) -> int:
        ptr = _lib.tpl_txalloc_allocate(self._h, size, alignment)
        if ptr == _ffi.NULL:
            raise OutOfMemory("NativeTransactionalAllocator", size)
        return int(_ffi.cast("uintptr_t", ptr))

    def deallocate_node(self, addr: int, size: int = 0,
                        alignment: int = 0) -> None:
        if not _lib.tpl_txalloc_deallocate(self._h, _ffi.cast("void*", addr)):
            raise InvalidPointer(f"0x{addr:x} rejected by native allocator")

    @property
    def live_stacks(self) -> int:
        return _lib.tpl_txalloc_live_stacks(self._h)

    def max_node_size(self, alignment: int = 64) -> int:
        # block minus the 8B in-band header and worst-case alignment pad
        return self._arena.next_block_size - 8 - alignment

    def close(self) -> None:
        if self._finalizer.alive:
            self._finalizer()
        self._h = None


class NativeBFitAllocator(_NativeAllocBase):
    """Native best-fit allocator (RawAllocator concept)."""

    def __init__(self, block_size: int = 1 << 24,
                 arena: Optional[NativeArena] = None):
        if not _load():
            raise RuntimeError("native library not built")
        self._owns_arena = arena is None
        self._arena = arena or NativeArena(block_size)
        self._h = _lib.tpl_bfit_create(self._arena._h, 1)
        arena_h = None
        if self._owns_arena:  # see NativeTransactionalAllocator
            self._arena._finalizer.detach()
            arena_h = self._arena._h
        self._finalizer = weakref.finalize(
            self, _destroy_with_arena, _lib.tpl_bfit_destroy, self._h,
            _lib.tpl_arena_destroy, arena_h)

    def allocate_node(self, size: int, alignment: int = 64) -> int:
        ptr = _lib.tpl_bfit_allocate(self._h, size, alignment)
        if ptr == _ffi.NULL:
            raise OutOfMemory("NativeBFitAllocator", size)
        return int(_ffi.cast("uintptr_t", ptr))

    def deallocate_node(self, addr: int, size: int = 0,
                        alignment: int = 0) -> None:
        if not _lib.tpl_bfit_deallocate(self._h, _ffi.cast("void*", addr)):
            raise InvalidPointer(f"0x{addr:x} rejected by native allocator")

    @property
    def free_bytes(self) -> int:
        return _lib.tpl_bfit_free_bytes(self._h)

    @property
    def live_allocations(self) -> int:
        return _lib.tpl_bfit_live(self._h)

    def close(self) -> None:
        if self._finalizer.alive:
            self._finalizer()
        self._h = None


class NativeTokenPool:
    """Futex-backed blocking token pool (native TokenPool)."""

    def __init__(self):
        if not _load():
            raise RuntimeError("native library not built")
        self._h = _lib.tpl_pool_create()
        self._finalizer = weakref.finalize(
            self, _lib.tpl_pool_destroy, self._h)

    def push(self, token: int) -> None:
        _lib.tpl_pool_push(self._h, token)

    def pop(self, timeout: Optional[float] = None) -> int:
        out = _ffi.new("int64_t*")
        timeout_ns = -1 if timeout is None else int(timeout * 1e9)
        if not _lib.tpl_pool_pop(self._h, out, timeout_ns):
            raise TimeoutError("NativeTokenPool.pop timed out")
        return int(out[0])

    def try_pop(self) -> Optional[int]:
        out = _ffi.new("int64_t*")
        if _lib.tpl_pool_try_pop(self._h, out):
            return int(out[0])
        return None

    def __len__(self) -> int:
        return _lib.tpl_pool_size(self._h)

    def close(self) -> None:
        if self._finalizer.alive:
            self._finalizer()
        self._h = None

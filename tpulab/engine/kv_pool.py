"""The paged KV store: page shapes, the device page pool, the prefix cache.

K/V live in a global pool of fixed-size *pages*; a session owns a *block
table* of page ids.  This module is everything that knows what a page is
and who holds it:

- :func:`kv_page_shape` / :func:`latent_page_shape` /
  :func:`index_page_shape` — the ONE definition of each cache-entry kind's
  page payload (K/V rows, one latent row per position for multi-head latent
  attention, or K/V rows with an index key a position beside them for
  learned sparse attention); :func:`kv_rows_view` is the
  ``(Hkv, D)`` -> row reshape the host-side formats share;
- :class:`PagedKVPool` — the device array ``(L, P, ...page)`` plus the
  host-side free extents (a grant is ascending runs of ids: a key block
  whose pages are one run is one DMA), reference counts, grow/shrink and
  the host<->device page moves the host tier, the disagg wire and the
  fabric ride;
- :class:`PrefixCache` — page-granular sharing of prompt prefixes over the
  pool's reference counts;
- :class:`LaneStateStore` — the second kind of state, indexed by lane and
  not by page: what a Mamba layer keeps of a sequence (its SSM state and
  the tail of its causal convolution), of one size whatever the context.

**Layer groups.**  A :class:`PagedKVPool` is ONE group of layers under ONE
table a lane: what every model whose attention layers agree on what a lane's
pages are is served with.  A model with window layers beside full ones
(``ModelSpec.page_groups``) is served with TWO pools, the full layers' (the
engine's ``pool``: what ``n_pages`` sizes, admission waits on and the gauges
read) and the window layers' (``wpool``), each with its own device array
``(L_g, P_g) + kv_page_shape``, free extents, reference counts and table a
lane; the step programs take, donate and return the pair, as they carry
``(kv, index)``.  The scheduler takes and returns the window group's pages
in whole key blocks of the kernels' walk
(:meth:`~tpulab.engine.plan.EnginePlan.window_lane_pages`), every block one
grant, so that the group's extents stay whole blocks and a block a window
layer walks is one run of ids; the blocks wholly behind a lane's window go
back to the group while the request lives
(``ContinuousBatcher._window_pages`` says why the next owner may have them
at once).

The step programs that read and write the pool are
:mod:`tpulab.engine.paged_steps`; the scheduler that hands pages out is
:mod:`tpulab.engine.paged`.  Neither is imported here.
"""

from __future__ import annotations

import bisect
import threading
from typing import Dict, List, Optional

import numpy as np


def kv_page_shape(page_size: int, n_kv_heads: int, head_dim: int) -> tuple:
    """``(2, S, Hkv*D)``: one layer's share of one page as the device
    keeps it — the ONE definition of the page payload.

    FUSED: a page's K rows (``[0]``) and V rows (``[1]``) are adjacent in
    HBM, so the ragged kernel fetches both with one DMA (the walk is
    DMA-issue-bound; fusing halves the issue count), and so are the pages
    of one layer: a key block whose page ids are an ascending run is ONE
    DMA (``_page_walk``; :meth:`PagedKVPool.allocate_pages` hands out
    runs).  A row is one
    position's KV heads side by side, ``Hkv*D`` wide: the shape the kernel
    DMAs into VMEM, so the page store goes into the ``pallas_call`` as it
    is and no step reshapes or slices it first (on a TPU merging
    ``(Hkv, D)`` into one minor dimension changes the tiled layout: a copy
    of a whole layer of the pool per layer per step).  The bytes are those
    of ``(2, S, Hkv, D)`` row-major, which is what the host-side formats
    (host tier, disagg wire, fabric) hold: ``PagedKVPool.host_shape``."""
    return (2, page_size, n_kv_heads * head_dim)


def latent_page_shape(page_size: int, latent_width: int) -> tuple:
    """``(1, S, row)``: one layer's share of one page of the *latent*
    cache-entry kind (multi-head latent attention) — the ONE definition of
    it.  A position leaves one row ``[c_kv ; k_rope]`` (after norm and
    RoPE), once: it is the key of every query head and its first
    ``kv_lora_rank`` columns are the value, so there is no second half
    (axis 2 is 1 where a K/V page has 2; a program tells the entry kind
    from it).  ``row`` is ``latent_width`` padded with zeros to whole
    128-lane tiles: what the device's tiled layout occupies anyway, and
    what a page DMA into VMEM needs."""
    return (1, page_size, -(-latent_width // 128) * 128)


def index_page_shape(page_size: int, index_dim: int) -> tuple:
    """``(S, row)``: one layer's share of one page of *index rows*, the
    second array of the ``"kv_index"`` cache-entry kind — the ONE definition
    of it.  A position leaves one index key of ``index_dim`` values (after
    its norm and RoPE) beside its K and V rows, under the same page id and
    slot; ``row`` is ``index_dim`` padded with zeros to whole 128-lane
    tiles (what the device's tiled layout occupies anyway)."""
    return (page_size, -(-index_dim // 128) * 128)


def kv_rows_view(pages):
    """``(..., Hkv, D)`` heads as the ``(..., Hkv*D)`` rows the page store
    takes (numpy or jax; the same bytes in the same order)."""
    return pages.reshape(pages.shape[:-2] + (-1,))


class _FreeExtents:
    """The free page ids as address-ordered extents ``[start, end)``: a
    released id coalesces with its neighbours, and a grant comes off the
    START of an extent, so its ids ascend.  Not locked: the pool's lock
    covers every call."""

    def __init__(self, lo: int, hi: int):
        self._starts: List[int] = []        # ascending
        self._end: Dict[int, int] = {}      # start -> end (exclusive)
        self._start: Dict[int, int] = {}    # end -> start
        self.count = 0                      # free ids, all extents
        self.add(lo, hi)

    def add(self, lo: int, hi: int) -> None:
        """Free ``[lo, hi)``, merged with the extent that ends at ``lo`` and
        the one that starts at ``hi``."""
        if hi <= lo:
            return
        self.count += hi - lo
        if lo in self._start:               # an extent ends where this starts
            lo = self._start.pop(lo)
        else:
            bisect.insort(self._starts, lo)
        if hi in self._end:                 # and one starts where it ends
            self._starts.pop(bisect.bisect_left(self._starts, hi))
            hi = self._end.pop(hi)
        self._end[lo] = hi
        self._start[hi] = lo

    def take(self, start: int, n: int) -> range:
        """The first ``n`` ids of the extent that starts at ``start``."""
        end = self._end.pop(start)
        i = bisect.bisect_left(self._starts, start)
        if start + n == end:
            self._starts.pop(i)
            del self._start[end]
        else:
            self._starts[i] = start + n
            self._end[start + n] = end
            self._start[end] = start + n
        self.count -= n
        return range(start, start + n)

    def size_at(self, start: int) -> int:
        """Ids of the extent that starts at ``start`` (0: none does)."""
        return self._end.get(start, start) - start

    def top(self, hi: int) -> int:
        """Ids of the extent that ends at ``hi`` (0: none does)."""
        return hi - self._start.get(hi, hi)

    def cut_top(self, hi: int, n: int) -> None:
        """Forget the last ``n`` ids of the extent that ends at ``hi``."""
        lo = self._start.pop(hi)
        self.count -= n
        if hi - n == lo:
            self._starts.pop(bisect.bisect_left(self._starts, lo))
            del self._end[lo]
        else:
            self._end[lo] = hi - n
            self._start[hi - n] = lo

    def grant(self, n: int, after: int = 0) -> Optional[List[int]]:
        """``n`` ids, or None (and nothing taken) where fewer are free.
        First what continues ``after`` (the extent that starts at ``after +
        1``, as far as it goes), then the rest ascending from as few extents
        as hold it: the first, by address, that holds it whole, behind the
        largest ones whole while none does."""
        if n > self.count:
            return None
        out: List[int] = []
        if after and after + 1 in self._end:
            out.extend(self.take(after + 1, min(n, self.size_at(after + 1))))
        rest: List[int] = []
        need = n - len(out)
        while need:
            fit = next((s for s in self._starts
                        if self._end[s] - s >= need), None)
            if fit is None:
                fit = max(self._starts, key=self.size_at)
            got = self.take(fit, min(need, self.size_at(fit)))
            rest.extend(got)
            need -= len(got)
        return out + sorted(rest)

    def __iter__(self):
        return ((s, self._end[s]) for s in self._starts)


class PagedKVPool:
    """Global paged K/V storage + free-page accounting (host side).

    The device array ``kv`` is ``(L, P) + kv_page_shape(S, Hkv, D)`` =
    ``(n_layers, n_pages, 2, page_size, n_kv_heads * head_dim)``: stored
    as the ragged kernel reads it.  Under a ``mesh`` the row shards on
    the model axis (contiguous head groups).

    ``latent_width`` > 0 selects the latent cache-entry kind instead:
    ``(L, P) + latent_page_shape(S, latent_width)``, one row a token a
    layer (``n_heads``/``head_dim`` are then unused: pass 0).  The host
    tier, the wire and the fabric do not carry it (``host_shape``
    raises).

    ``index_dim`` > 0 selects ``"kv_index"``: K/V pages as above and a
    second device array ``index`` of ``(L, P) + index_page_shape(S,
    index_dim)``, one index key a token a layer under the SAME page ids,
    so the free list, the reference counts and every block table serve
    both; the step programs take, donate and return the pair.  It is not
    sharded, grown, shrunk nor carried by the host-side formats."""

    def __init__(self, n_pages: int, page_size: int, n_layers: int,
                 n_heads: int, head_dim: int, dtype=None, device=None,
                 allocator=None, mesh=None, latent_width: int = 0,
                 index_dim: int = 0):
        import jax.numpy as jnp
        from tpulab.tpu import platform as plat
        from tpulab.tpu.allocators import make_tpu_allocator

        dtype = dtype or jnp.bfloat16
        self.n_pages = n_pages
        self.page_size = page_size
        self.n_layers = n_layers
        # sharded serving: with a ``mesh`` the page *payloads* shard over
        # the ``model`` axis on the row of KV heads (each shard holds its
        # own heads' K/V, matching the column-parallel wqkv that writes them)
        # while the page *tables* — host-side int32 id maps — stay
        # replicated: one logical page id still names one logical page.
        self.mesh = mesh
        self.kv_sharding = None
        if mesh is not None:
            from tpulab.parallel.sharding import kv_pool_sharding
            n_model = dict(mesh.shape).get("model", 0)
            if not n_model:
                raise ValueError("pool mesh needs a 'model' axis")
            if n_heads % n_model:
                raise ValueError(
                    f"pool KV heads ({n_heads}) not divisible by the mesh "
                    f"model axis ({n_model}) — page payloads shard on "
                    "whole KV heads")
            self.kv_sharding = kv_pool_sharding(mesh)
            self.device = (device if device is not None
                           else mesh.devices.flat[0])
        else:
            self.device = (device if device is not None
                           else plat.local_device(0))
        self.n_kv_heads = n_heads
        self.head_dim = head_dim
        #: "kv" (K and V rows), "latent" (one row a token) or "kv_index" (K
        #: and V rows, and an index key a token in ``index``)
        self.entry_kind = ("latent" if latent_width
                           else "kv_index" if index_dim else "kv")
        if latent_width and mesh is not None:
            raise NotImplementedError(
                "mesh=: a latent page store is not sharded (every head "
                "reads the whole row)")
        if index_dim and (latent_width or mesh is not None):
            raise NotImplementedError(
                "index rows go beside K/V pages on one device (no latent "
                "entry, no mesh)")
        self._shape = (n_layers, n_pages) + (
            latent_page_shape(page_size, latent_width) if latent_width
            else kv_page_shape(page_size, n_heads, head_dim))
        self._dtype = dtype
        # the KV page store is an HBM block owned by the device allocator
        # framework (tracked bytes; reference cuda_allocators device memory);
        # each donated decode step rotates the buffer via replace().  Under
        # a mesh the allocator binds the NamedSharding (device_put accepts
        # it) and its byte accounting stays LOGICAL — per-shard HBM is
        # hbm_bytes_per_shard.
        self._alloc = allocator or make_tpu_allocator(self.placement)
        self._kv_addr, self._kv = self._alloc.allocate_array(self._shape,
                                                             dtype)
        # the index rows: a tracked block of their own through the same
        # allocator, rotated through the donated steps beside ``kv``
        self._index_shape = ((n_layers, n_pages) + index_page_shape(
            page_size, index_dim)) if index_dim else None
        self._index_addr = self._index = None
        if index_dim:
            self._index_addr, self._index = self._alloc.allocate_array(
                self._index_shape, dtype)
        # page 0 is RESERVED as scratch: inactive/padded lanes scatter their
        # (masked-out) K/V there, so it must never hold live data
        # the free ids, address-ordered: a grant is the lowest extent that
        # holds it, so live data packs toward page 0 and the TOP of the
        # store stays contiguously free for :meth:`shrink`
        self._free = _FreeExtents(1, n_pages)
        self._refs: Dict[int, int] = {}  # live page -> refcount
        self._lock = threading.Lock()

    # the KV buffer rotates through XLA donation; the setter keeps the
    # device allocator's accounting slot pointing at the live generation
    @property
    def kv(self):
        return self._kv

    @kv.setter
    def kv(self, value) -> None:
        self._kv = self._alloc.replace(self._kv_addr, value)

    @property
    def index(self):
        """The index rows ``(L, P, S, row)`` of a ``"kv_index"`` pool (None
        otherwise); rotates through donation like ``kv``."""
        return self._index

    @index.setter
    def index(self, value) -> None:
        self._index = self._alloc.replace(self._index_addr, value)

    @property
    def dtype(self):
        """Page storage dtype (may be narrower than the compute dtype —
        KV-cache quantization)."""
        return self._dtype

    @property
    def placement(self):
        """``device_put`` target for pool-shaped (and page-payload-shaped)
        arrays: the NamedSharding under a mesh, the bound device
        otherwise."""
        return self.kv_sharding if self.kv_sharding is not None \
            else self.device

    def host_shape(self, n_pages: int) -> tuple:
        """``(L, n, 2, S, Hkv, D)``: ``n_pages`` pages as the host-side
        formats hold them (host tier, disagg wire, fabric) — heads apart,
        the bytes of the device's rows: the view for code that wants
        heads is a reshape to this."""
        if self.entry_kind != "kv":
            raise NotImplementedError(
                "the host-side formats (host tier, disagg wire, fabric) "
                "hold K/V pages only, not the latent cache-entry kind")
        return (self.n_layers, n_pages, 2, self.page_size,
                self.n_kv_heads, self.head_dim)

    @property
    def n_shards(self) -> int:
        """Model-axis shard count of the page payloads (1 single-device)."""
        return int(self.mesh.shape["model"]) if self.mesh is not None else 1

    @property
    def hbm_bytes(self) -> int:
        """Live LOGICAL HBM of this pool's page store (not allocator-wide:
        the allocator may be shared, e.g. a Runtime's).  Under a mesh this
        is the whole-array figure; each shard holds hbm_bytes_per_shard."""
        if self._kv_addr is None:
            return 0
        return self._alloc.node_size(self._kv_addr) + self.index_hbm_bytes

    @property
    def index_hbm_bytes(self) -> int:
        """Tracked bytes of the index rows (0 without any)."""
        return (self._alloc.node_size(self._index_addr)
                if self._index_addr is not None else 0)

    @property
    def hbm_bytes_per_shard(self) -> int:
        """Per-device HBM of the page store — the figure that must fit one
        chip (admission headroom counts logical pages; a logical page
        costs 1/n_shards of its bytes on each shard)."""
        return self.hbm_bytes // self.n_shards

    def reset(self) -> None:
        """Re-materialize the pool (recovery after a failed donated step)."""
        import jax
        import jax.numpy as jnp
        self.kv = jax.device_put(jnp.zeros(self._shape, self._dtype),
                                 self.placement)
        if self._index_addr is not None:
            self.index = jax.device_put(
                jnp.zeros(self._index_shape, self._dtype), self.placement)
        with self._lock:
            self._free = _FreeExtents(1, self.n_pages)  # page 0: scratch
            self._refs.clear()

    def close(self) -> None:
        """Eagerly free the page store's HBM."""
        if self._kv_addr is not None:
            self._alloc.deallocate_node(self._kv_addr)
            self._kv_addr = None
            self._kv = None
        if self._index_addr is not None:
            self._alloc.deallocate_node(self._index_addr)
            self._index_addr = self._index = None

    @property
    def page_nbytes(self) -> int:
        """Tracked HBM bytes one logical page costs (every layer's K+V
        rows for its slots) — the ledger/admission conversion factor."""
        return self.hbm_bytes // max(1, self.n_pages)

    @property
    def bytes_per_token(self) -> int:
        """Page-store bytes one cached token occupies, all layers: what
        the cache-entry kind costs (a latent row against K and V of every
        KV head; K, V and the index key for ``"kv_index"``)."""
        return self.page_nbytes // self.page_size

    @property
    def index_bytes_per_token(self) -> int:
        """Of those, the index keys' (0 without any)."""
        return self.index_hbm_bytes // max(1, self.n_pages * self.page_size)

    @property
    def free_pages(self) -> int:
        with self._lock:
            return self._free.count

    def allocate_pages(self, n: int, after: int = 0) -> Optional[List[int]]:
        """``n`` page ids in ascending runs, all or nothing: None, and
        nothing held, where fewer are free.  ``after`` is the last page of
        the table the grant goes behind: the grant starts at ``after + 1``
        where that id is free, so that a lane's admission page, its prompt's
        pages and the pages decode adds later stay ONE run of ids, which
        the page walk reads a block of as one copy
        (:mod:`tpulab.ops.ragged_attention`).  What does not continue
        ``after`` comes ascending from as few extents as hold it
        (:meth:`_FreeExtents.grant`)."""
        with self._lock:
            pages = self._free.grant(int(n), int(after or 0))
            if pages is not None:
                self._refs.update(dict.fromkeys(pages, 1))
            return pages

    def allocate_page(self, after: int = 0) -> Optional[int]:
        """:meth:`allocate_pages` of one."""
        pages = self.allocate_pages(1, after)
        return pages[0] if pages else None

    def add_ref(self, page: int) -> None:
        """Share an allocated page (prefix caching): one extra
        release_pages() is now required before the page frees."""
        with self._lock:
            if page not in self._refs:
                raise ValueError(f"add_ref on non-live page {page}")
            self._refs[page] += 1

    def release_pages(self, pages: List[int]) -> None:
        """Drop one reference per page; pages free when the count hits 0
        (pages from pre-refcount callers behave exactly as before: one
        allocate, one release)."""
        with self._lock:
            freed = []
            for p in pages:
                if not p:
                    continue  # 0/None never re-enter
                n = self._refs.get(p, 1) - 1
                if n <= 0:
                    self._refs.pop(p, None)
                    freed.append(p)
                else:
                    self._refs[p] = n
            # a lane's pages are mostly runs: free each run as one extent
            freed = sorted(set(freed))
            lo = 0
            for i, p in enumerate(freed):
                if i + 1 == len(freed) or freed[i + 1] != p + 1:
                    self._free.add(freed[lo], p + 1)
                    lo = i + 1

    def refcount(self, page: int) -> int:
        """Current reference count (0 for free/unknown pages)."""
        with self._lock:
            return self._refs.get(page, 0)

    # -- elastic capacity (the HBM economy, tpulab.hbm) ----------------------
    # The page store is no longer a fixed pre-carve: under an arbiter the
    # batcher grows it when a KV burst wins bytes from the other tenants
    # and shrinks it when a model's residency squeezes KV back.  Both ops
    # re-materialize the store through the tracked allocator's replace()
    # slot, so the framework HBM gauge (and the ledger claim mirroring
    # it) follows the real byte count exactly.  Page ids are STABLE:
    # grow appends ids, shrink only drops contiguously free ids off the
    # top — no live block table ever needs remapping.
    def shrinkable_pages(self) -> int:
        """Free pages contiguously at the TOP of the store — the ids a
        shrink could drop right now without touching live data."""
        with self._lock:
            return self._free.top(self.n_pages)

    def grow(self, extra_pages: int) -> int:
        """Append ``extra_pages`` zeroed pages to the store (one device
        concat through the allocator's accounting slot).  Returns the
        pages added.  Scheduler-thread only, like every other mutation of
        the live ``kv`` buffer."""
        extra = int(extra_pages)
        if extra <= 0:
            return 0
        if self._index_addr is not None:
            raise NotImplementedError("a pool with index rows is not elastic")
        import jax
        import jax.numpy as jnp
        pad_shape = (self._shape[0], extra) + self._shape[2:]
        pad = jax.device_put(jnp.zeros(pad_shape, self._dtype),
                             self.placement)
        self.kv = jnp.concatenate([self._kv, pad], axis=1)
        with self._lock:
            self._free.add(self.n_pages, self.n_pages + extra)
            self.n_pages += extra
            self._shape = (self._shape[0], self.n_pages) + self._shape[2:]
        return extra

    def shrink(self, drop_pages: int) -> int:
        """Drop up to ``drop_pages`` contiguously free pages off the TOP
        of the store (one device slice through the accounting slot).
        Returns the pages actually dropped — capped by what is free at
        the top; never page 0, never a live id."""
        if self._index_addr is not None:
            raise NotImplementedError("a pool with index rows is not elastic")
        with self._lock:
            k = min(self._free.top(self.n_pages), int(drop_pages))
            if k <= 0:
                return 0
            self._free.cut_top(self.n_pages, k)
            cut = self.n_pages - k
            self.n_pages = cut
            self._shape = (self._shape[0], cut) + self._shape[2:]
        self.kv = self._kv[:, :cut]
        return k


def lane_state_shapes(spec, lanes: int, dtype) -> tuple:
    """``((shape, dtype) of ssm, (shape, dtype) of conv)``: the ONE
    definition of what a model's Mamba-1, Gated DeltaNet, CCA or Mamba-2
    layers (``spec.state_kind``: ``"mamba"``, ``"gdn"``, ``"cca"``,
    ``"mamba2"``, the four kinds) keep a lane.  Kind ``"mamba"``:

    ``ssm``   ``(mamba layers, lanes, d_state, d_inner)`` float32: the SSM
              state ``h`` (the published kernel accumulates in float32; in
              bf16 its rounding compounds over every token of a sequence).
              Channels are the minor dimension, so a lane's ``(d_state,
              d_inner)`` is whole (8, 128) tiles on the device;
    ``conv``  ``(mamba layers, d_conv - 1, lanes, d_inner)`` in ``dtype``
              (the compute type): the last ``d_conv - 1`` inputs of the
              layer's causal convolution, oldest first (lanes ahead of
              channels, so the tile's sublanes are not padded from 3 to
              16).

    Kind ``"gdn"`` keeps the same pair: ``ssm`` is the delta rule's
    matrix-valued state ``(layers, lanes, value heads, d_k, d_v)`` float32
    (a head's ``(d_k, d_v)`` whole tiles), ``conv`` the tail of the
    convolution over the ``[q | k | v]`` channels.

    Kind ``"cca"`` keeps tails only, three of them and no recurrent matrix,
    each ``(layers, inputs kept, lanes, channels)`` in ``dtype`` as ``conv``
    is: the last ``cca_taps[0] - 1`` rows of ``c = [q~ ; k~]`` (what the
    depthwise taps reach back to), the last ``cca_taps[1] - 1`` rows of the
    depthwise convolution's output ``a`` (the grouped taps'), and ``h W_v2``
    of the lane's last token (the value's shifted half).

    Kind ``"mamba2"`` keeps the pair again: ``ssm`` is a head's matrix
    ``(layers, lanes, heads, head_dim, state)`` float32 (the state's width
    minor: a head's ``(head_dim, state)`` whole tiles), ``conv`` the tail of
    the convolution over the ``[x | B | C]`` channels together."""
    n_layers = len(spec.state_layers)
    if spec.state_kind == "mamba2":
        return (((n_layers, lanes, spec.m2_heads, spec.m2_head_dim,
                  spec.m2_state), np.dtype(np.float32)),
                ((n_layers, spec.d_conv - 1, lanes, spec.m2_conv_dim),
                 np.dtype(dtype)))
    if spec.state_kind == "cca":
        c = (spec.n_heads + spec.n_kv_heads) * spec.head_dim
        return tuple(((n_layers, kept, lanes, width), np.dtype(dtype))
                     for kept, width in (
                         (spec.cca_taps[0] - 1, c), (spec.cca_taps[1] - 1, c),
                         (1, spec.n_kv_heads * spec.head_dim // 2)))
    if spec.state_kind == "gdn":
        return (((n_layers, lanes, spec.gdn_v_heads, spec.gdn_k_dim,
                  spec.gdn_v_dim), np.dtype(np.float32)),
                ((n_layers, spec.d_conv - 1, lanes,
                  2 * spec.gdn_k_heads * spec.gdn_k_dim
                  + spec.gdn_v_heads * spec.gdn_v_dim), np.dtype(dtype)))
    return (((n_layers, lanes, spec.d_state, spec.d_inner),
             np.dtype(np.float32)),
            ((n_layers, spec.d_conv - 1, lanes, spec.d_inner),
             np.dtype(dtype)))


class LaneStateStore:
    """Per-lane recurrent state of a model's Mamba (-1 or -2) or Gated
    DeltaNet layers, beside the page store: ``arrays = (ssm, conv)``, shaped by
    :func:`lane_state_shapes` (a CCA model's three tails: ``arrays = (c
    tail, a tail, shifted value)``).

    A lane's slot belongs to whatever sequence runs in the lane.  Nothing
    here resets it: the step programs start a segment at position 0 from
    zeros whatever the slot holds (:mod:`tpulab.engine.paged_steps`), so
    admission, lane reuse and a resume after preemption need no dispatch.
    The pair rotates through the donated step programs like the page store;
    the device allocator tracks it as one block."""

    def __init__(self, spec, lanes: int, dtype=None, device=None):
        import jax.numpy as jnp
        from tpulab.tpu import platform as plat
        from tpulab.tpu.allocators import make_tpu_allocator

        if not spec.state_layers:
            raise ValueError("the model has no layer with a lane state")
        self.kind = spec.state_kind
        self.lanes = lanes
        self.device = device if device is not None else plat.local_device(0)
        self._shapes = lane_state_shapes(spec, lanes, dtype or jnp.bfloat16)
        self._alloc = make_tpu_allocator(self.device)
        self._addr, self._arrays = self._alloc.allocate_tree(self._zeros())

    def _zeros(self):
        import jax.numpy as jnp
        return tuple(jnp.zeros(shape, dtype) for shape, dtype in self._shapes)

    @property
    def arrays(self):
        return self._arrays

    @arrays.setter
    def arrays(self, value) -> None:
        self._arrays = self._alloc.replace(self._addr, tuple(value))

    @property
    def hbm_bytes(self) -> int:
        return (self._alloc.node_size(self._addr)
                if self._addr is not None else 0)

    @property
    def bytes_per_lane(self) -> int:
        """State bytes a lane holds, all its layers, whatever its
        context."""
        return self.hbm_bytes // self.lanes

    def reset(self) -> None:
        """Re-materialize the store (recovery after a failed donated
        step)."""
        import jax
        self.arrays = jax.device_put(self._zeros(), self.device)

    def close(self) -> None:
        if self._addr is not None:
            self._alloc.deallocate_node(self._addr)
            self._addr = self._arrays = None


class PrefixCache:
    """Prompt prefix cache over the paged pool (full-page granularity).

    Maps a digest of the token prefix ``prompt[:(i+1)*S]`` to the page
    holding that S-token span's K/V.  A hit lets a new request *share* the
    cached pages (``PagedKVPool.add_ref``) and compute only the tail's
    rows (its rounds start at the first position not shared) — the
    paged-serving time-to-first-token optimization for shared system
    prompts / few-shot preambles.

    Safety: only FULL prompt pages enter the cache, and a request's write
    region (tail prefill + decode appends) always sits at page boundaries
    at-or-after its shared prefix — shared pages are read-only by
    construction, so no copy-on-write is needed.  The last prompt token is
    never served from cache (its logits seed generation), which the
    lookup guarantees by capping reuse at ``(t-1) // S`` pages.

    LRU: entries hold one pool reference each; under pool pressure the
    batcher evicts from the cold end.  Single-threaded by design — only
    the scheduler thread touches it (documented invariant).
    """

    def __init__(self, pool: PagedKVPool):
        from collections import OrderedDict
        self._pool = pool
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()
        self.hits = 0       # pages served from cache
        self.misses = 0     # full prompt pages computed fresh
        #: optional host-tier hooks (set by the batcher when kv_offload is
        #: on): ``on_evict(digest, page)`` fires on pressure eviction
        #: BEFORE the page is released (demotion window);
        #: ``promote_fn(digest) -> Optional[page]`` may resurrect a
        #: demoted entry during lookup — the returned page's single pool
        #: reference belongs to the cache.
        self.on_evict = None
        self.promote_fn = None
        self.host_promotions = 0  # lookup pages served from the host tier

    @staticmethod
    def _digests(prompt: np.ndarray, page_size: int, n_pages: int):
        import hashlib
        # incremental chain: extend one page per step and snapshot — O(t)
        # total bytes hashed (a from-scratch prefix hash per page is O(t^2))
        out = []
        raw = np.ascontiguousarray(prompt, np.int32)
        h = hashlib.blake2b(digest_size=16)
        for i in range(n_pages):
            h.update(raw[i * page_size:(i + 1) * page_size].tobytes())
            out.append(h.copy().digest())
        return out

    def lookup(self, prompt: np.ndarray, page_size: int):
        """Longest cached full-page prefix of ``prompt``.

        Returns (shared_pages, digests) where ``shared_pages`` are
        ref-bumped for the caller (caller owns one release each) and
        ``digests`` covers every full prompt page (for insert later).
        Hit/miss accounting is the CALLER's job (count_lookup) once the
        prefill actually proceeds — a page-pressure retry re-runs lookup
        and must not double-count.
        """
        t = len(prompt)
        cacheable = max(0, (t - 1) // page_size)  # last token never cached
        digests = self._digests(prompt, page_size,
                                t // page_size)
        shared: List[int] = []
        for i in range(cacheable):
            page = self._entries.get(digests[i])
            if page is None and self.promote_fn is not None:
                # spill-backed cache: a demoted entry can come back from
                # the host tier mid-lookup (the hook allocates + uploads;
                # the new page's one ref is the cache's)
                page = self.promote_fn(digests[i])
                if page is not None:
                    self._entries[digests[i]] = page
                    self.host_promotions += 1
            if page is None:
                break
            self._entries.move_to_end(digests[i])
            self._pool.add_ref(page)
            shared.append(page)
        return shared, digests

    def count_lookup(self, n_shared: int, n_full_pages: int) -> None:
        """Record one *successful* lookup's hit/miss stats."""
        self.hits += n_shared
        self.misses += max(0, n_full_pages - n_shared)

    def coverage(self, prompt, page_size: int) -> int:
        """Cached-page count of ``prompt``'s full-page prefix WITHOUT the
        lookup's side effects (no LRU touch, no ref bump, no host-tier
        promotion) — the fleet KV fabric's local-hit probe
        (tpulab.kvfabric): deciding whether a remote pull is worth it
        must not perturb the cache it is measuring.  Advisory by nature:
        the RPC thread calls it while the scheduler mutates entries, so
        the answer can be one tick stale — staleness in either direction
        only costs work (a skipped pull, a redundant one), never
        correctness: the real ``lookup`` still runs at prefill."""
        t = len(prompt)
        cacheable = max(0, (t - 1) // page_size)
        if cacheable == 0:
            return 0
        digests = self._digests(np.asarray(prompt, np.int32), page_size,
                                cacheable)
        n = 0
        for d in digests:
            if d not in self._entries:
                break
            n += 1
        return n

    def insert(self, digests: List[bytes], pages: List[int]) -> None:
        """Publish a prefilled request's full prompt pages (one extra pool
        ref each, owned by the cache).  Digest collisions with existing
        entries keep the incumbent (both pages hold identical K/V)."""
        for dig, page in zip(digests, pages):
            if dig in self._entries:
                self._entries.move_to_end(dig)
                continue
            self._pool.add_ref(page)
            self._entries[dig] = page

    def evict_one(self) -> bool:
        """Drop the coldest entry (its pool ref); True if something fell."""
        if not self._entries:
            return False
        _, page = self._entries.popitem(last=False)
        self._pool.release_pages([page])
        return True

    def evict_for_alloc(self) -> bool:
        """Evict the coldest entry whose page would actually FREE (cache
        holds the only reference).  Entries shared with active requests
        (refcount > 1) are skipped: dropping them frees nothing now, so
        transient pool pressure must not wipe them.  False when no
        eviction can produce a free page."""
        for dig, page in self._entries.items():  # OrderedDict: cold first
            if self._pool.refcount(page) == 1:
                del self._entries[dig]
                if self.on_evict is not None:
                    # demotion window: the hook's device-side copy is
                    # dispatched before the release below, so a recycled
                    # page's later writes are stream-ordered after it
                    try:
                        self.on_evict(dig, page)
                    except Exception:  # demotion is best-effort
                        import logging
                        logging.getLogger("tpulab.engine").exception(
                            "prefix-cache demotion hook failed")
                self._pool.release_pages([page])
                return True
        return False

    def clear(self) -> None:
        while self.evict_one():
            pass

    def drop_all(self) -> None:
        """Forget every entry WITHOUT touching the pool — for use after
        ``PagedKVPool.reset()`` already rebuilt the free list (releasing
        into a reset pool would double-free)."""
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

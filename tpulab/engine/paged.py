"""Paged KV cache + continuous batching.

The dense :mod:`generation` engine leases one max_len cache per session; this
module is the scalable successor (the TPU literature's ragged/paged-attention
serving shape): K/V live in a global pool of fixed-size *pages*, sessions own
*block tables* of page ids, and a scheduler steps every active session in one
fused batched decode per tick — continuous batching: new requests join the
batch the moment a slot frees, finished ones leave without draining the rest.

TPU-first mechanics:
- the page pools are donated through the jitted step, so XLA updates K/V
  in place (no per-token pool copies);
- the step has a *static* shape (fixed lane count B, fixed max pages per
  sequence) — one compiled program regardless of which sessions occupy the
  lanes; inactive lanes are masked, not recompiled;
- attention either gathers pages via the block table (pool[tables] ->
  (B, MP*S, ...), the XLA fallback) or walks them in the pallas ragged
  paged-attention kernel family (tpulab.ops.ragged_attention: per-lane
  (query_len, kv_len) segments serve decode, K+1 verify, and mixed
  chunked-prefill+decode rounds in one program, KV-heads-sharded under
  a mesh — docs/PERFORMANCE.md "Ragged paged attention");
- decode runs K ticks per dispatch (:func:`paged_decode_block`: lax.scan over
  the step, on-device sampling + stop masks), so the host pays one dispatch
  and ONE blocking fetch per K tokens — off-chip the per-token cost is the
  host<->device RTT, and K amortizes it (docs/PERFORMANCE.md).
"""

from __future__ import annotations

import threading
import time as _time
from concurrent.futures import Future
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

from tpulab import chaos
from tpulab.core.deadline import Deadline, DeadlineExceeded
from tpulab.utils import tracing
from tpulab.utils.tracing import stage


def kv_page_shape(page_size: int, n_kv_heads: int, head_dim: int) -> tuple:
    """``(2, S, Hkv*D)``: one layer's share of one page as the device
    keeps it — the ONE definition of the page payload.

    FUSED: a page's K rows (``[0]``) and V rows (``[1]``) are adjacent in
    HBM, so the ragged kernel fetches both with one DMA per page (the walk
    is DMA-issue-bound; fusing halves the issue count).  A row is one
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


def kv_rows_view(pages):
    """``(..., Hkv, D)`` heads as the ``(..., Hkv*D)`` rows the page store
    takes (numpy or jax; the same bytes in the same order)."""
    return pages.reshape(pages.shape[:-2] + (-1,))


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
    raises)."""

    def __init__(self, n_pages: int, page_size: int, n_layers: int,
                 n_heads: int, head_dim: int, dtype=None, device=None,
                 allocator=None, mesh=None, latent_width: int = 0):
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
        #: "kv" (K and V rows) or "latent" (one row a token)
        self.entry_kind = "latent" if latent_width else "kv"
        if latent_width and mesh is not None:
            raise NotImplementedError(
                "mesh=: a latent page store is not sharded (every head "
                "reads the whole row)")
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
        # page 0 is RESERVED as scratch: inactive/padded lanes scatter their
        # (masked-out) K/V there, so it must never hold live data
        self._free: List[int] = list(range(1, n_pages))
        self._refs: Dict[int, int] = {}  # live page -> refcount
        self._lock = threading.Lock()
        #: allocate lowest page ids first (the HBM arbiter arms this):
        #: live data packs toward page 0, so the TOP of the store stays
        #: contiguously free and :meth:`shrink` can return real bytes
        self.prefer_low_pages = False

    # the KV buffer rotates through XLA donation; the setter keeps the
    # device allocator's accounting slot pointing at the live generation
    @property
    def kv(self):
        return self._kv

    @kv.setter
    def kv(self, value) -> None:
        self._kv = self._alloc.replace(self._kv_addr, value)

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
        return (self._alloc.node_size(self._kv_addr)
                if self._kv_addr is not None else 0)

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
        with self._lock:
            self._free = list(range(1, self.n_pages))  # page 0 stays scratch
            self._refs.clear()

    def close(self) -> None:
        """Eagerly free the page store's HBM."""
        if self._kv_addr is not None:
            self._alloc.deallocate_node(self._kv_addr)
            self._kv_addr = None
            self._kv = None

    @property
    def page_nbytes(self) -> int:
        """Tracked HBM bytes one logical page costs (every layer's K+V
        rows for its slots) — the ledger/admission conversion factor."""
        return self.hbm_bytes // max(1, self.n_pages)

    @property
    def bytes_per_token(self) -> int:
        """Page-store bytes one cached token occupies, all layers: what
        the cache-entry kind costs (a latent row against K and V of every
        KV head)."""
        return self.page_nbytes // self.page_size

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def allocate_page(self) -> Optional[int]:
        with self._lock:
            if not self._free:
                return None
            if self.prefer_low_pages:
                page = min(self._free)
                self._free.remove(page)
            else:
                page = self._free.pop()
            self._refs[page] = 1
            return page

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
            for p in pages:
                if not p:
                    continue  # 0/None never re-enter
                n = self._refs.get(p, 1) - 1
                if n <= 0:
                    self._refs.pop(p, None)
                    self._free.append(p)
                else:
                    self._refs[p] = n

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
            free = set(self._free)
            n = 0
            p = self.n_pages - 1
            while p >= 1 and p in free:
                n += 1
                p -= 1
            return n

    def grow(self, extra_pages: int) -> int:
        """Append ``extra_pages`` zeroed pages to the store (one device
        concat through the allocator's accounting slot).  Returns the
        pages added.  Scheduler-thread only, like every other mutation of
        the live ``kv`` buffer."""
        extra = int(extra_pages)
        if extra <= 0:
            return 0
        import jax
        import jax.numpy as jnp
        pad_shape = (self._shape[0], extra) + self._shape[2:]
        pad = jax.device_put(jnp.zeros(pad_shape, self._dtype),
                             self.placement)
        self.kv = jnp.concatenate([self._kv, pad], axis=1)
        with self._lock:
            self._free.extend(range(self.n_pages, self.n_pages + extra))
            self.n_pages += extra
            self._shape = (self._shape[0], self.n_pages) + self._shape[2:]
        return extra

    def shrink(self, drop_pages: int) -> int:
        """Drop up to ``drop_pages`` contiguously free pages off the TOP
        of the store (one device slice through the accounting slot).
        Returns the pages actually dropped — capped by what is free at
        the top; never page 0, never a live id."""
        with self._lock:
            free = set(self._free)
            k = 0
            p = self.n_pages - 1
            while p >= 1 and p in free and k < int(drop_pages):
                k += 1
                p -= 1
            if k == 0:
                return 0
            cut = self.n_pages - k
            self._free = [q for q in self._free if q < cut]
            self.n_pages = cut
            self._shape = (self._shape[0], cut) + self._shape[2:]
        self.kv = self._kv[:, :cut]
        return k


def _scatter_kv(kv_pool, layer, page_idx, slot_idx, knew, vnew):
    """Write new K/V ``(..., Hkv, D)`` at ``(page_idx, slot_idx)`` (both
    shaped ``(...)``) of ``layer``, as rows of the page store: a reshape
    of the new rows, never of the pool.  Callers route what must not land
    to the reserved scratch page 0."""
    knew = kv_rows_view(knew.astype(kv_pool.dtype))
    vnew = kv_rows_view(vnew.astype(kv_pool.dtype))
    kv_pool = kv_pool.at[layer, page_idx, 0, slot_idx].set(knew)
    return kv_pool.at[layer, page_idx, 1, slot_idx].set(vnew)


def _gather_attend(q, k_layer, v_layer, tables, qpos, compute_dtype):
    """Dense-gather paged attention (the XLA fallback math, single source
    of truth for decode ticks and extend/chunked prefill).

    q (B, M, H, D) query tokens; k_layer/v_layer (P, S, Hkv*D) one
    layer's K and V rows (XLA fuses the slice of the pool into the
    gather); tables (B, MP) page ids; qpos (B, M) global position
    of each query token (visibility: context j attends iff j <= qpos).
    Returns (B, M, H*D).
    """
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import repeat_kv

    b, m, h, d = q.shape
    mp = tables.shape[1]
    page_size = k_layer.shape[1]
    k_ctx = repeat_kv(k_layer[tables].reshape(b, mp * page_size, -1, d), h)
    v_ctx = repeat_kv(v_layer[tables].reshape(b, mp * page_size, -1, d), h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_ctx.astype(jnp.float32)) / np.sqrt(d)
    j = jnp.arange(mp * page_size)
    mask = j[None, None, :] <= qpos[:, :, None]          # (B, M, K)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v_ctx.astype(compute_dtype)).reshape(b, m, h * d)


def _scatter_latent(kv_pool, layer, page_idx, slot_idx, rows):
    """Write latent rows ``(..., W)`` at ``(page_idx, slot_idx)`` of
    ``layer`` of a latent page store, zero-padded to the page row."""
    import jax.numpy as jnp
    pad = [(0, 0)] * (rows.ndim - 1) + [(0, kv_pool.shape[4] - rows.shape[-1])]
    return kv_pool.at[layer, page_idx, 0, slot_idx].set(
        jnp.pad(rows.astype(kv_pool.dtype), pad))


def _gather_attend_latent(q, c_layer, tables, qpos, v_width, sm_scale,
                          compute_dtype):
    """:func:`_gather_attend` for latent pages (absorbed MLA): q (B, M, H,
    W) against one shared key row a position, c_layer (P, S, row >= W);
    the value is the first ``v_width`` columns of the same rows.  Returns
    (B, M, H, v_width)."""
    import jax
    import jax.numpy as jnp

    b, mp = tables.shape
    page_size = c_layer.shape[1]
    ctx = c_layer[tables].reshape(b, mp * page_size, -1)
    scores = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32),
                        ctx[..., :q.shape[-1]].astype(jnp.float32)) * sm_scale
    j = jnp.arange(mp * page_size)
    mask = j[None, None, :] <= qpos[:, :, None]          # (B, M, K)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    return jnp.einsum("bhqk,bkc->bqhc", probs,
                      ctx[..., :v_width].astype(compute_dtype))


def _step_spec(spec, d_model: int, n_heads: int, n_layers: int, n_kv_heads,
               rope_theta):
    """The spec a step function runs: the caller's, or the dense decoder's
    from the arguments the step functions always took."""
    from tpulab.models.spec import dense_spec
    return spec or dense_spec(d_model, n_heads, n_layers, n_kv_heads,
                              rope_theta)


def _mla_attention(spec, p, layer, h, pos, kv_pool, page_idx, slot_idx, seg,
                   compute_dtype):
    """Multi-head latent attention of one layer in the absorbed form, on a
    latent page store: ``(attn (B, M, H * v_head_dim), kv_pool)``.  The
    row ``[c_kv ; k_rope]`` (after norm and RoPE) is scattered once; the
    key up-projection moves into the query, the value up-projection
    behind the weighted latent sum.  In a packed round (``seg["rows"]``,
    see :func:`_layer_block`) ``h`` is ``(1, T, D)``: only the absorbed
    query is spread to ``(B, M)`` for the walk over the pages, and the
    weighted latent sum is gathered back to rows before ``w_uv``."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _rmsnorm, apply_rope, qmat

    with jax.named_scope("mla_attention"):
        eps = spec.rms_eps
        b, m = h.shape[:2]
        nope, rope = spec.qk_nope_head_dim, spec.qk_rope_head_dim
        scale = 1.0 / np.sqrt(spec.qk_head_dim)
        cq = _rmsnorm(h @ qmat(p["wq_a"], compute_dtype),
                      p["q_norm"]["scale"], eps)
        q = (cq @ qmat(p["wq_b"], compute_dtype)).reshape(
            b, m, spec.n_heads, nope + rope)
        kva = h @ qmat(p["wkv_a"], compute_dtype)
        ckv = _rmsnorm(kva[..., :spec.kv_lora_rank], p["kv_norm"]["scale"],
                       eps)
        kr = apply_rope(kva[..., None, spec.kv_lora_rank:], pos,
                        spec.rope_theta)[..., 0, :]
        qr = apply_rope(q[..., nope:], pos, spec.rope_theta)
        rows = jnp.concatenate([ckv, kr], axis=-1)           # (B, M, W)
        kv_pool = _scatter_latent(
            kv_pool, layer, page_idx, slot_idx,
            rows.reshape(page_idx.shape + rows.shape[-1:]))
        qa = jnp.concatenate(
            [jnp.einsum("bmhn,hnc->bmhc", q[..., :nope],
                        qmat(p["w_uk"], compute_dtype)), qr], axis=-1)
        packed = seg.get("rows")
        if packed is not None:
            spread, back, pos = packed
            qa = jnp.take(qa.reshape(qa.shape[1:]), spread, axis=0,
                          mode="clip").reshape(pos.shape + qa.shape[2:])
        if seg["use_kernel"]:
            from tpulab.ops.ragged_attention import ragged_latent_attention
            lat = ragged_latent_attention(
                qa, kv_pool, layer, seg["tables"], seg["q_lens"],
                seg["kv_lens"], v_width=spec.kv_lora_rank, sm_scale=scale)
        else:
            lat = _gather_attend_latent(
                qa, kv_pool[layer, :, 0], seg["tables"], pos,
                spec.kv_lora_rank, scale, compute_dtype)
        if packed is not None:                 # (B, M, H, C) -> (1, T, H, C)
            lat = jnp.take(lat.reshape((-1,) + lat.shape[2:]), back, axis=0,
                           mode="clip")[None]
        attn = jnp.einsum("bmhc,hcv->bmhv", lat.astype(compute_dtype),
                          qmat(p["w_uv"], compute_dtype))
        return attn.reshape(b, m, -1), kv_pool


def _ffn_block(spec, p, layer, x, valid, compute_dtype):
    """``x + ffn(norm(x))`` of one layer: the dense FFN, or the routed
    experts plus the shared expert.  Returns ``(x, stats)``, ``stats``
    the expert layer's ``(E + 2,)`` counters or None."""
    import jax
    from tpulab.models.transformer import _dense_ffn, _rmsnorm

    h = _rmsnorm(x, p["ln2"]["scale"], spec.rms_eps)
    if spec.layer_kinds[layer] != "moe":
        return x + _dense_ffn(p, h, compute_dtype).astype(x.dtype), None
    from tpulab.parallel.moe import routed_ffn
    b, m = x.shape[:2]
    y, stats = routed_ffn(p["moe"], h.reshape(b * m, -1), spec.top_k,
                          compute_dtype, router="sigmoid_bias", act="swiglu",
                          scale=spec.routed_scale, norm=spec.norm_topk,
                          valid=valid.reshape(-1))
    with jax.named_scope("moe_shared"):
        shared = _dense_ffn(p["shared"], h, compute_dtype)
    return x + (y.reshape(b, m, -1) + shared).astype(x.dtype), stats


def _layer_block(spec, p, layer, x, pos, valid, kv_pool, page_idx, slot_idx,
                 seg, compute_dtype):
    """ONE decoder layer over paged state, for every model and every step
    function: norm, projections (+RoPE), the new rows scattered into the
    lane's pages, attention over the block table (gather-after-scatter,
    global causality), output projection, norm, FFN; residuals around both
    halves.

    x (B, M, D) at positions ``pos`` (B, M); ``page_idx``/``slot_idx`` are
    the write targets, shaped (B, M) — or (B,) in a decode step, whose one
    row a lane is then written without the M axis; rows that must not land
    go to scratch page 0.  ``seg`` is the dispatch's segment description,
    the same for every layer: ``tables`` (B, MP), ``q_lens``/``kv_lens``
    (B,), and the attention path (``use_kernel``: the Pallas ragged kernel
    of the cache-entry kind, else the XLA gather; ``kernel_geometry``,
    ``mesh``).  ``valid`` (B, M) bool masks the expert counters only.

    Three forms, told apart by what ``seg`` carries.  A decode step is
    (B, 1).  The padded form is (B, M), lane b's segment left-packed in
    row b (K+1 verify, where every lane's segment has one length).  A
    packed round (:func:`paged_mixed_step`) carries ``seg["rows"]``: x is
    (1, T, D), one row a token of the round, and everything but the walk
    over the pages runs on those T rows; ``rows = (spread (B * M,), back
    (T,), qpos (B, M))`` holds the row behind each slot of the (B, M)
    form the attention takes and the slot behind each row: the query rows
    are spread by one row gather and the attention's output gathered back
    by another, two copies of at most lanes x M rows a layer, where the
    padded form ran every product on lanes x M rows.  (``jnp.take``, not
    ``x[idx]``: it is jitted, so sixteen layers trace it once.)
    Returns ``(x, kv_pool, stats)``: ``stats`` is the expert layer's
    ``(E + 2,)`` int32 counters
    (:func:`tpulab.parallel.moe.routing_stats`) or None on a dense layer.

    Kept short, the K/V kernel called from here and the rest in functions
    of their own: on the v5e host, tracing a kernel body costs more with
    every Python frame between the step function and the ``pallas_call``
    (PR 28, my chip runs: a kernel's trace took 0.63 s a program with the
    parent's frames, 0.87-0.97 s behind one more, 1.42 s behind four more
    and a helper inside the kernel; the dense cell's set-up grew 10 %,
    96 -> 106 s, until the count was the parent's again).
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import (_rmsnorm, apply_rope, qmat,
                                           split_qkv)

    h = _rmsnorm(x, p["ln1"]["scale"], spec.rms_eps)
    if spec.attention == "mla":
        attn, kv_pool = _mla_attention(spec, p, layer, h, pos, kv_pool,
                                       page_idx, slot_idx, seg,
                                       compute_dtype)
    else:
        b, m = x.shape[:2]
        q, knew, vnew = split_qkv(h @ qmat(p["wqkv"], compute_dtype), b, m,
                                  spec.n_heads, spec.n_kv_heads,
                                  spec.head_dim)
        if spec.rope_theta:
            q = apply_rope(q, pos, spec.rope_theta)
            knew = apply_rope(knew, pos, spec.rope_theta)
        tail = knew.shape[2:]
        kv_pool = _scatter_kv(kv_pool, layer, page_idx, slot_idx,
                              knew.reshape(page_idx.shape + tail),
                              vnew.reshape(page_idx.shape + tail))
        packed = seg.get("rows")
        if packed is not None:
            spread, back, pos = packed
            b, m = pos.shape
            q = jnp.take(q.reshape(q.shape[1:]), spread, axis=0,
                         mode="clip").reshape((b, m) + q.shape[2:])
        if seg["use_kernel"]:
            # pallas ragged kernel: walks block tables page-by-page, no
            # dense gather materialization; fused pages = 1 DMA/page;
            # under a mesh the walk shards on the KV-heads dim via
            # shard_map (tpulab.ops.ragged_attention)
            from tpulab.ops import ragged_attention as ra
            gk, nk = seg["kernel_geometry"] or (None, None)
            if seg["mesh"] is None:
                # the jitted entry itself, not ``ragged_paged_attention``
                # around it: one Python frame fewer above the kernel
                # (the docstring says what a frame costs)
                from tpulab.tpu.platform import pallas_interpret
                attn = ra._ragged_attn(
                    q, kv_pool, jnp.asarray(layer, jnp.int32).reshape(1),
                    seg["tables"], seg["q_lens"], seg["kv_lens"],
                    pallas_interpret(), g_pages=gk, nbuf=nk)
            else:
                attn = ra.ragged_paged_attention(
                    q, kv_pool, layer, seg["tables"], seg["q_lens"],
                    seg["kv_lens"], mesh=seg["mesh"], g_pages=gk, nbuf=nk)
            attn = attn.astype(compute_dtype).reshape(b, m, -1)
        else:
            # XLA fallback: gather pages densely then mask
            attn = _gather_attend(q, kv_pool[layer, :, 0],
                                  kv_pool[layer, :, 1], seg["tables"], pos,
                                  compute_dtype)
        if packed is not None:                     # (B, M, H*D) -> (1, T, H*D)
            attn = jnp.take(attn.reshape(b * m, -1), back, axis=0,
                            mode="clip")[None]
    x, stats = _ffn_block(spec, p, layer,
                          x + attn @ qmat(p["wo"], compute_dtype), valid,
                          compute_dtype)
    return x, kv_pool, stats


def paged_decode_step(params, kv_pool, tables, lengths, tokens,
                      active, n_heads: int, n_layers: int,
                      compute_dtype, use_kernel: bool = False,
                      n_kv_heads: Optional[int] = None,
                      rope_theta: Optional[float] = None,
                      temps=None, seeds=None,
                      kernel_geometry: Optional[tuple] = None,
                      mesh=None, spec=None):
    """One batched decode tick over the paged pool.

    Shapes: kv_pool (L, P, 2, S, Hkv*D) fused page store (axis 2 = K/V,
    :func:`kv_page_shape`),
    tables (B, MP) int32 page ids (padded rows repeat page 0),
    lengths (B,) current position per lane, tokens (B,), active (B,) bool.
    Returns (logits (B, vocab), kv_pool) — the pool donated by the caller.
    Under GQA (``n_kv_heads < n_heads``) the pool holds ``n_kv_heads``
    heads per slot.

    With ``temps (B,) f32`` + ``seeds (B, 2) uint32`` the return becomes
    (next_tokens (B,) i32, logprobs (B,) f32, logits, kv_pool): lanes
    with temp > 0 are Gumbel-max temperature-sampled ON DEVICE with a key
    folded from (seed, position) — batch-composition- and
    preemption-invariant — and temp == 0 lanes take the argmax;
    ``logprobs`` is each lane's chosen-token log-probability
    (log-softmax at the chosen id).  Callers then fetch only (B,)-sized
    arrays (no per-tick (B, vocab) logits transfer).
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    b = tokens.shape[0]
    page_size = kv_pool.shape[3]
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens][:, None, :]
    spec = _step_spec(spec, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    # write target per lane: page id + slot for position `lengths`;
    # inactive/padded lanes are routed to the RESERVED scratch page 0 so
    # they can never clobber a live lane's pages
    page_idx = tables[jnp.arange(b), lengths // page_size]      # (B,)
    safe_page = jnp.where(active, page_idx, 0)
    safe_slot = jnp.where(active, lengths % page_size, 0)
    # the ragged kernel at the q=1 decode shape; per-lane positions: each
    # lane decodes at its own length
    pos = lengths[:, None]
    seg = dict(tables=tables, q_lens=jnp.ones_like(lengths),
               kv_lens=lengths + 1, use_kernel=use_kernel,
               kernel_geometry=kernel_geometry, mesh=mesh)
    moe_stats = []
    for layer in range(spec.n_layers):
        x, kv_pool, stats = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos, active[:, None],
            kv_pool, safe_page, safe_slot, seg, compute_dtype)
        if stats is not None:
            moe_stats.append(stats)

    x = _rmsnorm(x, params["final_norm"]["scale"], spec.rms_eps)
    logits = _lm_head(params, x[:, 0])
    # inactive lanes emit neutral logits (argmax 0) — callers mask on active
    logits = jnp.where(active[:, None], logits, 0.0)
    # an expert model's counters ride behind the pool (one small array)
    moe = (jnp.stack(moe_stats),) if moe_stats else ()
    if temps is None:
        return (logits, kv_pool) + moe
    import jax
    next_tokens = jax.vmap(_device_sample_token)(
        logits, temps, seeds.astype(jnp.uint32), lengths)
    logp_rows = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    logprobs = jnp.take_along_axis(logp_rows, next_tokens[:, None],
                                   axis=-1)[:, 0]
    return (next_tokens, logprobs, logits, kv_pool) + moe


def paged_decode_step_sampled(params, kv_pool, tables, lengths, tokens,
                              active, temps, seeds, **kw):
    """Positional-signature variant of :func:`paged_decode_step` with
    device sampling armed — sharded jits need every array argument
    positional so explicit ``in_shardings`` can be attached."""
    return paged_decode_step(params, kv_pool, tables, lengths, tokens,
                             active, temps=temps, seeds=seeds, **kw)


def paged_decode_block(params, kv_pool, tables, lengths, tokens, active,
                       temps, seeds, steps_rem, stop_ids,
                       n_heads: int, n_layers: int, compute_dtype,
                       k: int = 8, use_kernel: bool = False,
                       n_kv_heads: Optional[int] = None,
                       rope_theta: Optional[float] = None,
                       kernel_geometry: Optional[tuple] = None,
                       mesh=None, spec=None):
    """K fused decode ticks in ONE dispatch: ``lax.scan`` over
    :func:`paged_decode_step`, sampling every step on device.

    The per-token serving cost off-chip is dominated by the host<->device
    round trip (dispatch + blocking fetch), not the decode math — chaining
    K steps inside one compiled program amortizes that RTT over K tokens
    (the host then syncs once per K tokens instead of once per token, the
    fused multi-token decode shape of TPU-native serving stacks).

    Per-lane device-side stop mask: a lane is *live* while it is active,
    has steps remaining, and has not emitted a stop token.  ``steps_rem
    (B,) i32`` counts tokens still wanted per lane; ``stop_ids (B, S)
    i32`` holds each lane's stop-token ids padded with -1 (token ids are
    always >= 0, so the pad never matches).  A stop token IS emitted as
    the lane's final token (matching the host-side contract), then the
    lane goes dead for the rest of the block: its K/V writes route to the
    reserved scratch page and its position stops advancing — which also
    keeps the (seed, position)-folded device-sampling stream identical to
    a K=1 run.

    The CALLER pre-allocates pages: step j writes K/V at ``lengths + j``
    for live lanes, so ``tables`` must already cover every position the
    block can reach.

    Returns ``(tokens (B, K) i32, logprobs (B, K) f32, emitted (B, K)
    bool, lengths (B,), last_tokens (B,), live (B,), steps_rem (B,),
    kv_pool)`` — and, for a ``spec`` with expert layers, their counters
    ``(n_moe, E + 2)`` summed over the K steps as one more element;
    ``lengths`` .. ``steps_rem`` and the pool are the carried state
    *after* the block, returned as device arrays so a follow-up block can be
    dispatched without a host round trip (dispatch-ahead overlap).
    ``emitted[b]`` is a prefix mask: lane b's valid tokens are
    ``tokens[b, :emitted[b].sum()]``.
    """
    import jax
    import jax.numpy as jnp

    def body(carry, _):
        kv, lens, toks, live, rem = carry
        nt, lp, _logits, kv, *moe = paged_decode_step(
            params, kv, tables, lens, toks, live,
            n_heads=n_heads, n_layers=n_layers,
            compute_dtype=compute_dtype, use_kernel=use_kernel,
            n_kv_heads=n_kv_heads, rope_theta=rope_theta,
            temps=temps, seeds=seeds, kernel_geometry=kernel_geometry,
            mesh=mesh, spec=spec)
        emitted = live
        nt = jnp.where(live, nt, toks)           # dead lanes hold position
        lens = lens + emitted.astype(jnp.int32)
        rem = rem - emitted.astype(jnp.int32)
        hit_stop = (nt[:, None] == stop_ids).any(axis=1)
        live = live & (rem > 0) & ~hit_stop
        return (kv, lens, nt, live, rem), (nt, lp, emitted, *moe)

    init = (kv_pool, lengths, tokens, active, steps_rem)
    (kv_pool, lengths, tokens, live, steps_rem), (toks, lps, ems, *moe) = \
        jax.lax.scan(body, init, None, length=k)
    # an expert model's counters, summed over the block's steps
    return (toks.T, lps.T, ems.T, lengths, tokens, live, steps_rem,
            kv_pool) + tuple(m.sum(axis=0) for m in moe)


def _device_sample_token(row, temp, seed2, pos):
    """Gumbel-max temperature sample of one lane: key folded from the full
    64-bit seed (lo, hi words) and the token position — the SINGLE
    definition of the device-sampling stream (the decode step vmaps it;
    the prefill first-token pick replays it on the fetched logits row so
    one request is one stream end to end)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(
        jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed2[0]), seed2[1]),
        pos)
    g = jax.random.gumbel(key, row.shape, jnp.float32)
    safe_t = jnp.where(temp > 0, temp, 1.0)
    sampled = jnp.argmax(row / safe_t + g)
    return jnp.where(temp > 0, sampled, jnp.argmax(row)).astype(jnp.int32)


def paged_ragged_forward(params, kv_pool, tables, seq, q_lens, kv_lens,
                         n_heads: int, n_layers: int, compute_dtype,
                         use_kernel: bool = False,
                         n_kv_heads: Optional[int] = None,
                         rope_theta: Optional[float] = None,
                         mesh=None,
                         kernel_geometry: Optional[tuple] = None,
                         last_only: bool = False, spec=None):
    """One fused multi-token forward over ragged per-lane segments in the
    PADDED form, every product on ``B x M`` rows (ROADMAP item 2, "Ragged
    Paged Attention" in PAPERS.md).  The K+1 speculative verify runs it
    (every lane's segment has one length there, so the padding is dense);
    a mixed round runs the same segments packed by token
    (:func:`paged_mixed_step`) and is tested against this form.

    ``seq (B, M)`` int32, left-packed: lane b's valid tokens are
    ``seq[b, :q_lens[b]]``, token j at global position
    ``kv_lens[b] - q_lens[b] + j``.  Per layer all valid positions' K/V
    scatter into the lane's pages first (invalid positions route to the
    reserved scratch page 0), then attention gathers the lane's whole
    block table masked by global causality — the gather-after-scatter
    shape of :func:`paged_extend`, batched over ragged lanes.  One
    static ``M`` serves every segment mix: plain decode (``q_lens=1``),
    K+1 speculative verify (``q_lens=k+1``), chunked prefill
    (``q_lens=chunk``) and any combination in one batch.

    ``use_kernel`` selects the pallas ragged kernel
    (:func:`tpulab.ops.ragged_attention.ragged_paged_attention`; under a
    ``mesh`` it shards on the KV-heads dim via shard_map) over the XLA
    dense-gather fallback.  ``last_only=True`` runs the vocab head over
    each lane's LAST valid position only and returns ``(logits (B,
    vocab), kv_pool)``; otherwise ``(logits (B, M, vocab), kv_pool)``
    with invalid positions' logits garbage the caller must not consume.
    The fused pool is donated by the caller either way.  A ``spec`` with
    expert layers appends their counters ``(n_moe, E + 2)`` (valid
    positions only) as a third element.
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    b, m = seq.shape
    page_size = kv_pool.shape[3]
    emb = params["embed"].astype(compute_dtype)
    x = emb[seq]                                      # (B, M, D)
    spec = _step_spec(spec, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    valid = jnp.arange(m)[None, :] < q_lens[:, None]  # (B, M)
    pos = (kv_lens - q_lens)[:, None] + jnp.arange(m)[None, :]
    # invalid positions' page index may run past the table width — XLA
    # clamps the gather, and the mask below discards the clamped id
    page_idx = jnp.where(valid,
                         jnp.take_along_axis(
                             tables,
                             jnp.clip(pos // page_size, 0,
                                      tables.shape[1] - 1), axis=1), 0)
    slot_idx = jnp.where(valid, pos % page_size, 0)
    # gather-after-scatter: token m sees cached context + the segment's
    # own writes up to its position (global causality); one program for
    # every segment mix
    seg = dict(tables=tables, q_lens=q_lens, kv_lens=kv_lens,
               use_kernel=use_kernel, kernel_geometry=kernel_geometry,
               mesh=mesh)
    moe_stats = []
    for layer in range(spec.n_layers):
        x, kv_pool, stats = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos, valid, kv_pool,
            page_idx, slot_idx, seg, compute_dtype)
        if stats is not None:
            moe_stats.append(stats)
    moe = (jnp.stack(moe_stats),) if moe_stats else ()

    if last_only:
        # only each lane's last valid token seeds a pick — run the
        # vocab-sized head over ONE row per lane (paged_extend's trick,
        # batched)
        x = jnp.take_along_axis(
            x, jnp.maximum(q_lens - 1, 0)[:, None, None], axis=1)[:, 0]
    x = _rmsnorm(x, params["final_norm"]["scale"], spec.rms_eps)
    return (_lm_head(params, x), kv_pool) + moe


def round_width(prefill_tokens: int) -> int:
    """``M`` of the mixed round that carries ``prefill_tokens`` prompt
    tokens: the pow2 bucket its program is keyed by (few jits) and the
    segment width its attention is called at."""
    return 1 << (prefill_tokens - 1).bit_length()


def pack_round(lanes: int, prefill: Dict[int, Any], decode: Dict[int, int]):
    """Host half of :func:`paged_mixed_step`'s input: a round packed by
    token.  ``prefill`` maps a lane to its chunk's tokens (at least one
    token in all; packed in the mapping's order), ``decode`` a lane to its
    current token.  Returns numpy ``(toks (T,), row_lane (T,), row_off
    (T,), q_lens (lanes,))`` with ``T = round_width(prefill tokens) +
    lanes``."""
    m = round_width(sum(len(chunk) for chunk in prefill.values()))
    toks = np.zeros((m + lanes,), np.int32)
    row_lane = np.full((m + lanes,), -1, np.int32)
    row_off = np.zeros((m + lanes,), np.int32)
    q_lens = np.zeros((lanes,), np.int32)
    row = 0
    for lane, chunk in prefill.items():
        rows = slice(row, row + len(chunk))
        toks[rows], row_lane[rows] = chunk, lane
        row_off[rows] = np.arange(len(chunk))
        q_lens[lane] = len(chunk)
        row = rows.stop
    for lane, tok in decode.items():
        toks[m + lane], row_lane[m + lane], q_lens[lane] = tok, lane, 1
    return toks, row_lane, row_off, q_lens


def paged_mixed_step(params, kv_pool, tables, toks, row_lane, row_off,
                     q_lens, kv_lens, temps, seeds, n_heads: int,
                     n_layers: int, compute_dtype, use_kernel: bool = False,
                     n_kv_heads: Optional[int] = None,
                     rope_theta: Optional[float] = None,
                     mesh=None,
                     kernel_geometry: Optional[tuple] = None, spec=None):
    """One mixed prefill+decode round, packed by token: a ragged forward
    over per-lane segments plus each lane's next-token pick, in ONE
    dispatch whose rows are the round's tokens.

    Prefilling lanes carry a prompt chunk (``q_lens = chunk``), decoding
    lanes their current token (``q_lens = 1``), idle lanes nothing
    (``q_lens = 0``).  ``toks (T,)`` holds the round with ``T = M +
    lanes``: rows ``[0, M)`` are the prefilling lanes' chunk tokens one
    lane after the other, row ``M + b`` is lane b's decode token.
    ``row_lane (T,)`` is each row's lane (-1: the row holds no token) and
    ``row_off (T,)`` its offset in the lane's segment: token ``(b, j)``
    sits at global position ``kv_lens[b] - q_lens[b] + j``.  Embedding,
    norms, projections, RoPE, the row scatter into the lane's pages,
    ``wo``, the FFN or the routed experts and their counters run on the T
    rows; only the attention call sees the ``(B, M)`` form of
    :func:`paged_ragged_forward` (:func:`_layer_block`), so a round costs
    what its tokens cost, not lanes x the longest chunk.  ``M`` (from the
    shapes, ``T - lanes``) is the ONE number the program is keyed by.

    Every lane's pick is :func:`_device_sample_token` on its LAST valid
    row's logits at position ``kv_lens - 1`` — exactly the decode tick's
    stream for decode lanes and exactly the prefill first-token stream
    (position ``t - 1``) for lanes finishing their prompt, so one request
    is one (seed, position)-keyed stream regardless of which dispatch
    kind served it.  The caller consumes picks only for lanes that emit
    this round (a mid-prompt chunk's pick is discarded; device sampling
    is stateless, so a discarded pick costs nothing).

    Returns ``(next_tokens (B,) i32, logprobs (B,) f32, last_logits
    (B, vocab), kv_pool)`` — ``last_logits`` stays device-resident
    unless a host-sampled lane fetches its row — and the expert layers'
    counters behind the pool where ``spec`` has any.  The same segments
    through ``paged_ragged_forward(last_only=True)`` give the same
    logits: that is the plain form this one is tested against.
    """
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    b, t = tables.shape[0], toks.shape[0]
    m = t - b
    page_size = kv_pool.shape[3]
    emb = params["embed"].astype(compute_dtype)
    x = emb[toks][None]                               # (1, T, D)
    spec = _step_spec(spec, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    valid = row_lane >= 0
    lane = jnp.maximum(row_lane, 0)
    start = kv_lens - q_lens                          # (B,) segment starts
    pos = jnp.where(valid, start[lane] + row_off, 0)
    page_idx = jnp.where(valid, tables[lane, pos // page_size], 0)
    slot_idx = jnp.where(valid, pos % page_size, 0)
    # the slot of the padded (B, M) form behind each row, and the row
    # behind each slot; slots past a lane's segment read row 0, which the
    # attention masks by q_lens
    back = lane * m + row_off
    spread = jnp.zeros((b * m,), jnp.int32).at[
        jnp.where(valid, back, b * m)].set(
            jnp.arange(t, dtype=jnp.int32), mode="drop")
    qpos = start[:, None] + jnp.arange(m)[None, :]
    seg = dict(tables=tables, q_lens=q_lens, kv_lens=kv_lens,
               use_kernel=use_kernel, kernel_geometry=kernel_geometry,
               mesh=mesh, rows=(spread, back, qpos))
    moe_stats = []
    for layer in range(spec.n_layers):
        x, kv_pool, stats = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos[None], valid[None],
            kv_pool, page_idx[None], slot_idx[None], seg, compute_dtype)
        if stats is not None:
            moe_stats.append(stats)
    moe = (jnp.stack(moe_stats),) if moe_stats else ()

    # the vocab-sized head over ONE row a lane: its last valid token's
    last_row = spread[jnp.arange(b) * m + jnp.maximum(q_lens - 1, 0)]
    last = _lm_head(params, _rmsnorm(x[0][last_row],
                                     params["final_norm"]["scale"],
                                     spec.rms_eps))
    pos_last = jnp.maximum(kv_lens - 1, 0)
    next_tokens = jax.vmap(_device_sample_token)(
        last, temps, seeds.astype(jnp.uint32), pos_last)
    logp_rows = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
    logprobs = jnp.take_along_axis(logp_rows, next_tokens[:, None],
                                   axis=-1)[:, 0]
    return (next_tokens, logprobs, last, kv_pool, *moe)


def paged_speculative_block(params, draft_params, kv_pool, tables,
                            draft_tables, lengths, tokens, active, temps,
                            seeds, steps_rem, stop_ids,
                            n_heads: int, n_layers: int,
                            draft_n_heads: int, draft_n_layers: int,
                            compute_dtype, k: int = 4,
                            n_kv_heads: Optional[int] = None,
                            draft_n_kv_heads: Optional[int] = None,
                            rope_theta: Optional[float] = None,
                            use_kernel: bool = False, mesh=None,
                            kernel_geometry: Optional[tuple] = None):
    """Speculative decode: draft-propose + target-verify + per-lane
    accept/reject, ALL inside one device dispatch.

    A small draft model proposes ``k`` tokens per lane (a ``lax.scan``
    of single-token draft steps through a SECOND page table on the same
    fused pool), the target model verifies the current token plus all k
    proposals in ONE batched forward (:func:`_paged_verify_forward`),
    and acceptance runs on device: each lane emits the longest prefix of
    proposals matching the target's own choices, plus the target's
    correction (or bonus) token — so emitted tokens are EXACTLY the
    non-speculative stream, and one dispatch emits up to ``k + 1``
    tokens instead of ``k``.  The target's "choice" is
    :func:`_device_sample_token` at each position — greedy argmax for
    temp==0 lanes, and for device-sampled lanes the same
    (seed, position)-folded stream plain blocks use, so token parity is
    bit-exact in both modes.  The draft proposes through the SAME
    sampling function on its own logits (a perfect draft then reaches
    full acceptance under sampling too).

    Stop-mask machinery matches :func:`paged_decode_block`: a stop token
    is emitted as the lane's final token and truncates the emission; the
    per-lane steps-remaining budget caps it, and writes past the budget
    route to the scratch page (so a full-K block at the tail of a
    request can never write past the positions its reservation covers).
    Dead lanes emit nothing and write only scratch.  The draft scan runs
    ``k + 1`` iterations (last proposal discarded) so a fully-accepted
    round leaves no hole in the draft KV — the dense
    :class:`~tpulab.engine.speculative.SpeculativeGenerator` trick.
    Rejected proposals leave stale K/V past the accepted horizon in both
    tables; positions only advance, so every stale slot is overwritten
    before any later query may attend it.

    The CALLER pre-allocates BOTH tables to cover positions
    ``lengths .. lengths + k`` (see ``_reserve_spec_pages``).
    ``use_kernel`` routes attention on BOTH models through the ragged
    pallas kernel family (draft proposal steps at q=1, the verify
    forward at q=k+1 — the PR 7 follow-up retired); the XLA gather is
    the fallback, and under a ``mesh`` the kernel shards on KV heads.

    Returns ``(tokens (B, k+1) i32, logprobs (B, k+1) f32, emitted
    (B, k+1) bool prefix mask, lengths (B,), last_tokens (B,), live
    (B,), steps_rem (B,), drafted (B,) i32, accepted (B,) i32,
    kv_pool)``.
    """
    import jax
    import jax.numpy as jnp

    seeds = seeds.astype(jnp.uint32)

    # 1) draft proposes k tokens per lane through the second page table;
    #    iterations past a lane's step budget write only scratch (their
    #    proposals can never be emitted)
    def dbody(carry, i):
        kv, tok = carry
        nt, _lp, _lg, kv = paged_decode_step(
            draft_params, kv, draft_tables, lengths + i, tok,
            active & (i < steps_rem),
            n_heads=draft_n_heads, n_layers=draft_n_layers,
            compute_dtype=compute_dtype, use_kernel=use_kernel,
            n_kv_heads=draft_n_kv_heads, rope_theta=rope_theta,
            temps=temps, seeds=seeds, kernel_geometry=kernel_geometry,
            mesh=mesh)
        return (kv, nt), nt

    (kv_pool, _), props = jax.lax.scan(dbody, (kv_pool, tokens),
                                       jnp.arange(k + 1))
    drafts = props[:k].T                               # (B, k)

    # 2) target verifies [cur, d_0..d_{k-1}] in ONE batched ragged
    #    forward (q_lens = the valid prefix per lane); position j's
    #    write is real only while the lane can still emit token j
    #    (emitted n <= steps_rem, and query j consumes writes 0..j only,
    #    so masking j >= steps_rem discards nothing live)
    seq = jnp.concatenate([tokens[:, None], drafts], axis=1)  # (B, k+1)
    q_lens = jnp.where(active,
                       jnp.minimum(k + 1, jnp.maximum(steps_rem, 0)), 0)
    logits, kv_pool = paged_ragged_forward(
        params, kv_pool, tables, seq, q_lens, lengths + q_lens,
        n_heads=n_heads, n_layers=n_layers, compute_dtype=compute_dtype,
        use_kernel=use_kernel, n_kv_heads=n_kv_heads,
        rope_theta=rope_theta, mesh=mesh, kernel_geometry=kernel_geometry)

    # 3) the target's own choice at every position — the same sampling
    #    stream as plain blocks, so the output is bit-identical
    pos = lengths[:, None] + jnp.arange(k + 1)[None, :]
    cand = jax.vmap(jax.vmap(_device_sample_token,
                             in_axes=(0, None, None, 0)))(
        logits, temps, seeds, pos)                      # (B, k+1)
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    lps = jnp.take_along_axis(lsm, cand[..., None], axis=-1)[..., 0]

    # 4) accept/reject + stop-mask, on device: emit the agreeing prefix
    #    + correction, truncated by stop tokens and steps remaining
    agree = drafts == cand[:, :k]
    acc = jnp.cumprod(agree.astype(jnp.int32), axis=1).sum(axis=1)  # (B,)
    avail = acc + 1                     # accepted prefix + correction
    hit = (cand[:, :, None] == stop_ids[:, None, :]).any(axis=2)
    first_stop = jnp.argmax(hit, axis=1)
    stop_cap = jnp.where(hit.any(axis=1), first_stop + 1, k + 1)
    n = jnp.minimum(jnp.minimum(avail, stop_cap), steps_rem)
    n = jnp.where(active, n, 0)
    emitted = jnp.arange(k + 1)[None, :] < n[:, None]   # (B, k+1)
    lengths = lengths + n
    last = jnp.take_along_axis(cand, jnp.maximum(n - 1, 0)[:, None],
                               axis=1)[:, 0]
    tokens = jnp.where(n > 0, last, tokens).astype(jnp.int32)
    steps_rem = steps_rem - n
    stopped = hit.any(axis=1) & (stop_cap <= n)
    live = active & (steps_rem > 0) & ~stopped
    drafted = jnp.where(active, k, 0)
    accepted = jnp.where(active, jnp.minimum(acc, n), 0)
    return (cand.astype(jnp.int32), lps, emitted, lengths, tokens, live,
            steps_rem, drafted, accepted, kv_pool)


def paged_prefill(params, kv_pool, tables, tokens, valid_len,
                  n_heads: int, n_layers: int, compute_dtype,
                  n_kv_heads: Optional[int] = None,
                  rope_theta: Optional[float] = None,
                  attention_fn=None):
    """Fused prefill: ONE causal forward over the (padded) prompt, with each
    layer's K/V scattered straight into the lane's pages.

    tokens (1, T_pad) int32 (padded tail arbitrary), valid_len scalar int32,
    tables (MP,) page ids for this lane.  Padded positions scatter to the
    reserved scratch page 0.  Returns (last-valid-token logits (vocab,),
    kv_pool) — the fused pool donated by the caller.
    """
    import jax
    import jax.numpy as jnp
    from tpulab.models.transformer import (causal_attention,
                                           transformer_forward_collect_kv)

    page_size = kv_pool.shape[3]
    t_pad = tokens.shape[1]
    logits, kvs = transformer_forward_collect_kv(
        params, tokens, n_heads=n_heads, n_layers=n_layers,
        compute_dtype=compute_dtype, n_kv_heads=n_kv_heads,
        rope_theta=rope_theta,
        attention_fn=attention_fn or causal_attention)
    pos = jnp.arange(t_pad)
    valid = pos < valid_len
    page_idx = jnp.where(valid, tables[pos // page_size], 0)  # scratch if pad
    slot_idx = jnp.where(valid, pos % page_size, 0)
    for layer, (k, v) in enumerate(kvs):
        kv_pool = _scatter_kv(kv_pool, layer, page_idx, slot_idx,
                              k[0], v[0])
    last = logits[0, valid_len - 1]
    return last, kv_pool


def paged_extend(params, kv_pool, tables, tokens, start, valid_total,
                 n_heads: int, n_layers: int, compute_dtype,
                 n_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None):
    """Chunked/tail prefill against EXISTING paged context.

    One fused forward over M tail tokens (positions ``start ..
    start+M-1``) for a single lane whose positions ``[0, start)`` are
    already resident in the pool (prefix-cache hits or earlier chunks of a
    chunked prefill).  Per layer the tail K/V scatter into their pages
    first, then attention gathers the lane's WHOLE block table — the
    gather-after-scatter sees cached prefix and tail together, so the mask
    is just global causality (tail token m attends position j iff
    ``j <= start+m``).

    tokens (1, M_pad) int32 (padded tail arbitrary); start scalar int32
    (page-aligned: the tail must never write into a shared prefix page);
    valid_total scalar int32 = true total length (prompt so far + tail);
    tables (MP,) page ids covering all of it.  Returns (logits of the last
    valid token (vocab,), kv_pool) — the fused pool donated by the caller.
    """
    import jax.numpy as jnp
    from tpulab.models.transformer import _lm_head, _rmsnorm

    page_size = kv_pool.shape[3]
    m_pad = tokens.shape[1]
    emb = params["embed"].astype(compute_dtype)
    x = emb[tokens]                                   # (1, M_pad, D)
    spec = _step_spec(None, x.shape[-1], n_heads, n_layers, n_kv_heads,
                      rope_theta)
    pos = start + jnp.arange(m_pad)                   # global positions
    valid = pos < valid_total
    page_idx = jnp.where(valid, tables[pos // page_size], 0)  # pad -> scratch
    slot_idx = jnp.where(valid, pos % page_size, 0)
    # gather-after-scatter: context = cached prefix + this tail
    seg = dict(tables=tables[None], use_kernel=False)
    for layer in range(n_layers):
        x, kv_pool, _ = _layer_block(
            spec, params[f"layer{layer}"], layer, x, pos[None], valid[None],
            kv_pool, page_idx, slot_idx, seg, compute_dtype)

    # only the last valid token's logits are ever consumed — run the
    # vocab-sized head over ONE row, not all M_pad rows
    x_last = x[0, valid_total - 1 - start][None]      # (1, D)
    x_last = _rmsnorm(x_last, params["final_norm"]["scale"])
    last = _lm_head(params, x_last)[0]                # (vocab,)
    return last, kv_pool


class PrefixCache:
    """Prompt prefix cache over the paged pool (full-page granularity).

    Maps a digest of the token prefix ``prompt[:(i+1)*S]`` to the page
    holding that S-token span's K/V.  A hit lets a new request *share* the
    cached pages (``PagedKVPool.add_ref``) and prefill only the tail via
    :func:`paged_extend` — the paged-serving time-to-first-token
    optimization for shared system prompts / few-shot preambles.

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


class SamplingParams:
    """Token selection policy (greedy by default).

    ``device=False`` (default): host-side temperature / top-k sampling
    with a per-request numpy PRNG — requires fetching the lane's full
    (vocab,) logits row every tick.

    ``device=True``: TPU-first temperature sampling computed ON CHIP
    (Gumbel-max over the logits with a per-lane key folded from
    (seed, position)) — the tick fetches only (B,) token ids, never the
    logits.  Reproducible per request (the key depends only on seed and
    position, not batch-mates or preemption) but a DIFFERENT stream than
    the host PRNG.  ``top_k`` / ``top_p`` are host-side features:
    device=True with either set is rejected (per-lane truncation is not
    a static compile-time shape).

    ``top_p`` (nucleus sampling, 0 < top_p < 1) keeps the smallest set
    of tokens whose probabilities sum to at least top_p; composes with
    ``top_k`` (k-truncation first, then the nucleus), the standard order.
    """

    __slots__ = ("temperature", "top_k", "top_p", "device", "seed", "_rng")

    def __init__(self, temperature: float = 0.0, top_k: int = 0,
                 seed: Optional[int] = None, device: bool = False,
                 top_p: float = 0.0):
        if temperature < 0:
            raise ValueError("temperature must be >= 0")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError("top_p must be in [0, 1]")
        if device and (top_k > 0 or 0.0 < top_p < 1.0):
            raise ValueError("device sampling does not support top_k/top_p "
                             "(per-lane truncation is not a static shape); "
                             "use host sampling")
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.device = device
        if seed is None:
            # full 64-bit draw: device sampling keys on both seed words,
            # a 31-bit default would zero the hi word for every unseeded
            # request and shrink the stream space
            seed = int(np.random.default_rng().integers(
                0, 2**64, dtype=np.uint64))
        self.seed = int(seed)
        self._rng = np.random.default_rng(seed)

    def pick(self, logits: np.ndarray) -> int:
        """Select the next token from a (vocab,) logits row."""
        if self.temperature == 0.0:
            return int(logits.argmax())
        z = logits.astype(np.float64) / self.temperature
        if self.top_k > 0 and self.top_k < z.shape[0]:
            kth = np.partition(z, -self.top_k)[-self.top_k]
            z = np.where(z >= kth, z, -np.inf)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        if 0.0 < self.top_p < 1.0:
            # nucleus: smallest prob-descending prefix summing >= top_p
            order = np.argsort(p)[::-1]
            csum = np.cumsum(p[order])
            cut = int(np.searchsorted(csum, self.top_p)) + 1
            mask = np.zeros_like(p, dtype=bool)
            mask[order[:cut]] = True
            p = np.where(mask, p, 0.0)
            p /= p.sum()
        return int(self._rng.choice(z.shape[0], p=p))


class _PagedRequest:
    __slots__ = ("prompt", "steps", "future", "tokens_out", "pages",
                 "length", "pending_prompt", "on_token", "cancelled",
                 "sampling", "priority", "resumed", "admit_seq",
                 "stop_tokens", "want_logprobs", "logprobs_out", "deadline",
                 "trace_id", "t_submit", "t_prefill0", "t_first", "t_last",
                 "chunk_t0", "chunk_start", "kv_handle", "export_digest",
                 "draft_pages", "draft_len", "spec_enabled", "spec_ewma",
                 "spec_drafted", "spec_accepted", "spec_probe_in",
                 "spec_probing", "tenant", "lane", "fl", "batch",
                 "pf_started", "pf_digests", "pf_shared", "pf_t0")

    def __init__(self, prompt: np.ndarray, steps: int, on_token=None,
                 sampling: Optional[SamplingParams] = None,
                 priority: int = 0, stop_tokens=None,
                 logprobs: bool = False, deadline: Optional[float] = None,
                 trace_id: Optional[str] = None,
                 tenant: Optional[str] = None, batch: bool = False):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.steps = steps
        self.future: Future = Future()
        self.tokens_out: List[int] = []
        self.pages: List[int] = []
        self.length = 0
        self.pending_prompt = list(self.prompt)
        self.on_token = on_token
        self.cancelled = False
        self.sampling = sampling or SamplingParams()
        self.priority = priority
        #: offline batch lane (docs/SERVING.md "Offline batch lane"):
        #: batch requests rank strictly below EVERY online request —
        #: they queue behind all online arrivals regardless of priority
        #: and are the first preemption victims when an online arrival
        #: needs a lane or pages.  Within the batch class, priority and
        #: FIFO order apply as usual.
        self.batch = bool(batch)
        self.resumed = False     # preempted mid-decode; resume skips the
        #                          prefill pick (its token was already emitted)
        self.kv_handle = None    # host-tier KV snapshot of a preempted lane
        #                          (kvcache.SwapHandle); resume swaps it back
        #                          in instead of re-prefilling
        self.export_digest = None  # disagg: demote finished KV to the host
        #                            tier under ("ship", digest) at release
        self.admit_seq = -1      # admission order (preemption tie-break)
        self.stop_tokens = frozenset(int(t) for t in (stop_tokens or ()))
        self.want_logprobs = logprobs
        self.logprobs_out: List[float] = []
        #: absolute monotonic expiry (None = unbounded); the scheduler's
        #: per-iteration sweep cancels expired requests before their next
        #: step, freeing the lane and pages
        self.deadline = deadline
        # -- speculative decode lane state (second page table) --------------
        self.draft_pages: List[int] = []  # draft KV page ids (never shared)
        self.draft_len = 0         # context positions the draft KV covers
        self.spec_enabled = True   # False: plain blocks (chaos verify trip
        #                            degrades for the REST of the request;
        #                            an acceptance-EWMA degrade is transient
        #                            — see spec_probe_in)
        self.spec_probe_in = None  # plain dispatches until the next probe
        #                            block re-tries speculation (None = no
        #                            probe scheduled: never degraded, or
        #                            degraded permanently by chaos)
        self.spec_probing = False  # the next/current spec dispatch is a
        #                            probe: its acceptance decides recovery
        self.spec_ewma = 1.0       # rolling acceptance (optimistic start)
        self.spec_drafted = 0      # draft proposals verified for this lane
        self.spec_accepted = 0     # of those, emitted (accepted) ones
        # -- request-lifecycle telemetry (trace spans + latency metrics) ----
        self.trace_id = trace_id
        #: admission tenant (flight-recorder / debugz attribution only —
        #: the scheduler never reads it)
        self.tenant = tenant
        #: last lane this request occupied (-1 = never admitted)
        self.lane = -1
        #: flight-recorder per-request detail (None = recorder disarmed:
        #: the scheduling hot path pays one None check per site)
        self.fl: Optional[dict] = None
        # -- ragged dispatch plan: multi-round chunked-prefill state --------
        self.pf_started = False      # pages secured, chunks may dispatch
        self.pf_digests = None       # full-prompt-page digests (insert at
        #                              prompt completion)
        self.pf_shared = 0           # prefix-cache pages served shared
        self.pf_t0: Optional[float] = None  # this prefill's start (spans)
        self.t_submit = _time.perf_counter()
        self.t_prefill0: Optional[float] = None  # first prefill start
        self.t_first: Optional[float] = None     # first emitted token
        self.t_last: Optional[float] = None      # latest emitted token
        self.chunk_t0: Optional[float] = None    # open decode-chunk start
        self.chunk_start = 0                     # first token idx in chunk

    def finished(self) -> bool:
        """steps exhausted, or the last emitted token is a stop token
        (which stays in the output, ending it)."""
        return bool(self.tokens_out) and (
            len(self.tokens_out) >= self.steps
            or self.tokens_out[-1] in self.stop_tokens)


#: process-level memo of jitted engine programs (see
#: ContinuousBatcher._jit): identical-geometry engines share one jitted
#: callable and therefore one compiled-program cache.  Bounded by the
#: process's program-config variety; entries hold compiled executables,
#: never parameter or pool buffers (those are traced arguments).
_JIT_MEMO: Dict[Any, Any] = {}
_JIT_MEMO_LOCK = threading.Lock()


class ContinuousBatcher:
    """Continuous-batching scheduler over the paged pool.

    ``submit(prompt, steps) -> Future[list[int]]``; a background scheduler
    thread runs one fused decode dispatch per iteration over up to
    ``lanes`` concurrent requests, admitting queued requests whenever a
    lane (and pages) free up — no head-of-line draining.
    ``cancel(future)`` aborts a request and frees its lane/pages at the
    next dispatch boundary.

    Multi-step fused decode: each dispatch covers an adaptive K decode
    ticks (``decode_block`` is the ceiling) chained on device via
    :func:`paged_decode_block`, so the host pays ONE dispatch + ONE
    blocking fetch per K tokens instead of per token — off-chip the
    per-token cost is the link RTT, and K amortizes it.  Greedy and
    device-sampled lanes run at full K (sampling and the EOS /
    steps-remaining stop mask live on device); any host-sampled
    (``top_k``/``top_p``) lane in the batch drops the whole batch to K=1
    (its sampling needs the logits row on host every token).  K adapts
    down to 1-2 when a lane's deadline is tight or a streaming consumer
    is attached with no queue pressure, so interactive TTFT/ITL does not
    regress; per-token ``on_token`` callbacks still fire in order, and
    cancellation/deadline sweeps act at block boundaries (a request stops
    within at most one block of the sweep observing it).

    Speculative decoding (``draft_params=``, docs/PERFORMANCE.md): a
    small draft model (e.g. :func:`tpulab.models.transformer.
    early_exit_draft`) rides the SAME paged pool through a second
    per-lane page table; each fused dispatch drafts K tokens, verifies
    them in one batched target forward, and emits up to K+1 accepted
    tokens — multiplying the block amortization by the acceptance rate
    with bit-identical output.  Host-sampled lanes never speculate, and
    lanes degrade to plain blocks on low acceptance, chaos verify trips,
    or draft-table pool pressure.

    Sharded serving (``mesh=``, tpulab.parallel): a ``{"model": M}`` mesh
    runs this replica tensor-parallel over M devices — params placed by
    the Megatron-TP partition rules, the KV page store sharded on the
    KV-heads dim (page tables stay replicated), every dispatch a sharded
    jit whose collectives ride INSIDE the fused program.  Emitted tokens
    are bit-identical to mesh=None for greedy and device-sampled
    streams, and the host-sync count per block is unchanged — see
    docs/PERFORMANCE.md "Sharded serving".

    Ragged dispatch plan (``use_kernel=True`` or ``ragged=True``,
    docs/PERFORMANCE.md "Ragged paged attention"): prompts and decode
    lanes advance together through fused mixed rounds
    (:func:`paged_mixed_step`) — per-lane (query_len, kv_len) segments
    packed by token, at most ``RAGGED_CHUNK_CAP`` prompt tokens a round
    for all lanes together, ONE dispatch and one host sync per round, no
    separate prefill programs — and the speculative verify forward rides
    the same ragged kernel family.  Tokens are bit-exact vs the legacy split
    dispatch (``use_kernel=False``, the escape hatch), mesh on or off.

    Model spec (``spec=``, tpulab.models.spec): a ``ModelSpec`` names the
    attention kind, the layer kinds and the cache-entry kind; without one
    the engine serves the dense decoder of ``n_heads``/``n_kv_heads``.
    Multi-head latent attention keeps ONE latent row a token a layer in
    the page store and runs in the absorbed form (gather or the latent
    ragged kernel); expert layers run the routed FFN of
    tpulab.parallel.moe and count assignments (``debug_state()["moe"]``).
    Such a spec is served on the ragged plan only; the options that plan
    or that cache entry does not carry are refused at construction, by
    name.

    Tiered KV (``kv_offload=``, tpulab.kvcache): preemption swaps the
    victim's KV pages to a budgeted host-RAM tier (async, write-behind)
    and resume swaps them back with ZERO prefill dispatches; prefix-cache
    entries evicted under pool pressure demote to the host tier and
    promote back on the next lookup hit.  Every degraded swap falls back
    to the exact re-prefill/recompute path — see docs/PERFORMANCE.md.
    """

    #: explicit capability marker for routers (e.g. the Generate RPC)
    continuous_batching = True

    #: decode tokens per trace span ("each decode chunk"): per-token spans
    #: would swamp the bounded event ring at serving rates.  K>1 decode
    #: flushes one span per BLOCK instead (block-sized decode spans).
    TRACE_DECODE_CHUNK = 8

    #: fused-decode block sizes: the adaptive K snaps DOWN onto this menu
    #: so the jit cache stays tiny (one compiled scan per size in use)
    BLOCK_K_MENU = (1, 2, 4, 8, 16)

    #: shortest max_len at which use_kernel=None auto-selects the pallas
    #: kernel on TPU (see __init__'s auto-select comment)
    KERNEL_AUTO_MIN_CTX = 8192

    def __init__(self, params, n_heads: int, n_layers: int,
                 pool: Optional[PagedKVPool] = None, lanes: int = 4,
                 max_len: int = 256, page_size: int = 16,
                 n_pages: int = 0, compute_dtype=None, device=None,
                 use_kernel: Optional[bool] = None,
                 n_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None,
                 prefix_cache: bool = False,
                 prefill_chunk: Optional[int] = None,
                 kv_dtype=None,
                 prefill_flash: Optional[bool] = None,
                 trace=None, metrics=None,
                 decode_block: int = 8,
                 kv_offload=None,
                 draft_params=None,
                 draft_n_layers: Optional[int] = None,
                 draft_n_heads: Optional[int] = None,
                 draft_n_kv_heads: Optional[int] = None,
                 spec_accept_floor: float = 0.35,
                 mesh=None, hbm=None, flight=None,
                 ragged: Optional[bool] = None,
                 kv_publish: bool = False,
                 spec=None):
        import jax
        import jax.numpy as jnp

        compute_dtype = compute_dtype or jnp.bfloat16
        #: tpulab.models.spec.ModelSpec: None serves the dense decoder of
        #: ``n_heads``/``n_kv_heads``/``rope_theta`` with today's constants;
        #: a spec with a latent cache or expert layers is served on the
        #: ragged plan alone, and the options that plan or that cache-entry
        #: kind does not carry yet are refused here, by name
        self.model_spec = spec
        special = spec is not None and (spec.cache_entry != "kv"
                                        or spec.moe_layers)
        if special:
            refused = {
                "ragged=False (the legacy split plan)": ragged is False,
                "draft_params (speculative blocks)": draft_params is not None,
                "mesh": mesh is not None
                or getattr(pool, "mesh", None) is not None,
                "kv_offload": kv_offload not in (None, False),
                "kv_publish": bool(kv_publish),
                "prefix_cache": bool(prefix_cache),
                "kv_dtype other than the compute dtype":
                    kv_dtype is not None
                    and jnp.dtype(kv_dtype) != jnp.dtype(compute_dtype),
                "hbm (the elastic page store)": hbm is not None,
            }
            bad = [name for name, on in refused.items() if on]
            if bad:
                raise NotImplementedError(
                    "a model with a latent cache or expert layers is served "
                    "on the ragged plan only; not supported with it: "
                    + ", ".join(bad))
            if (spec.n_heads, spec.n_layers) != (n_heads, n_layers):
                raise ValueError(
                    f"spec (n_heads {spec.n_heads}, n_layers {spec.n_layers})"
                    f" disagrees with n_heads={n_heads}, n_layers={n_layers}")
            ragged = True
        # KV-cache quantization: pages may store a NARROWER dtype than the
        # compute path (e.g. kv_dtype=jnp.float8_e4m3fn under bf16 compute
        # halves KV HBM *and* decode bandwidth — the decode tick is
        # KV-bandwidth-bound).  Writes round on scatter, reads upcast in
        # the gather/kernel; attention math stays in f32 either way.
        kv_dtype = kv_dtype or compute_dtype
        n_kv = n_kv_heads or n_heads
        self.lanes = lanes
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages = (max_len + page_size - 1) // page_size
        if prefill_chunk is not None:
            if prefill_chunk < page_size:
                raise ValueError("prefill_chunk must be >= page_size")
            # chunk starts must stay page-aligned (a chunk's successor
            # writes from a page boundary)
            prefill_chunk -= prefill_chunk % page_size
        self.prefill_chunk = prefill_chunk
        from tpulab.models.transformer import weight_shape
        d_model = int(weight_shape(params["embed"])[1])
        #: id-validation bound (public: the Generate RPC checks it too)
        self.vocab = int(weight_shape(params["embed"])[0])
        # +1: page 0 is the reserved scratch page.  GQA pools store the
        # compact n_kv_heads form — KV HBM shrinks by n_heads/n_kv_heads.
        self._owns_pool = pool is None
        if pool is not None and kv_dtype != compute_dtype \
                and pool.dtype != kv_dtype:
            raise ValueError(
                f"kv_dtype={jnp.dtype(kv_dtype).name} conflicts with the "
                f"provided pool's dtype {jnp.dtype(pool.dtype).name}")
        latent = (spec.latent_width
                  if spec is not None and spec.cache_entry == "latent" else 0)
        if pool is not None and (pool.entry_kind == "latent") != bool(latent):
            raise ValueError(f"the provided pool holds {pool.entry_kind!r} "
                             "entries, the model another kind")
        self.pool = pool or PagedKVPool(
            n_pages or self.max_pages * lanes + 1, page_size, n_layers,
            0 if latent else n_kv, 0 if latent else d_model // n_heads,
            kv_dtype, device, mesh=mesh, latent_width=latent)
        if pool is not None and mesh is not None and pool.mesh is not mesh:
            raise ValueError("provided pool was built on a different mesh "
                             "than the batcher's")
        # unified HBM economy (tpulab.hbm, docs/PERFORMANCE.md "HBM
        # economy"): with an arbiter the batcher is the KV TENANT — the
        # pool's page store becomes elastic (a KV burst wins bytes from
        # cold models via the arbiter's pressure protocol; a hot model's
        # acquire squeezes idle KV down to the host tier), and every jit
        # this engine compiles records its scratch with the ledger.  Set
        # before the first _jit so scratch measuring can wrap them.
        self.hbm = hbm
        self._hbm_reclaim_bytes = 0  # outstanding arbiter reclaim target
        self.hbm_grows = 0           # pool grow ops granted by the arbiter
        self.hbm_shrinks = 0         # pool shrink ops under pressure
        self.hbm_demotions = 0       # lanes demoted (preempted) by pressure
        #: elastic pool sizes snap to a geometric ladder off the initial
        #: size (n0, 2*n0, 4*n0, ...) — every pool shape recompiles the
        #: fused programs, so sizes must come from a bounded menu the
        #: warm-up can cover (the BLOCK_K_MENU / pow2-prefill-bucket
        #: discipline applied to capacity)
        self._hbm_pool_base = self.pool.n_pages
        self._hbm_starved_passes = 0  # hold-and-wait breaker streak
        if hbm is not None:
            self.pool.prefer_low_pages = True
        # sharded serving (docs/PERFORMANCE.md "Sharded serving"): with a
        # ``mesh`` ({"model": M}, tpulab.parallel) one replica serves a
        # model sharded over M devices — params placed by the Megatron-TP
        # rules (wqkv/w1/w3/lm_head column-, wo/w2 row-parallel), the KV
        # page store sharded on the KV-heads dim, and every dispatch a
        # sharded jit with explicit in/out shardings so XLA inserts the
        # psums INSIDE the fused program: the one-host-sync-per-block
        # contract and device-side sampling are unchanged, and per-lane
        # carry/state stays replicated.  mesh=None is bit-for-bit today's
        # single-device path.
        self.mesh = getattr(self.pool, "mesh", None)
        if self.hbm is not None and self.mesh is not None:
            # PR 11's named follow-up, closed as an explicit contract:
            # the elastic pool's grow/shrink per-shard accounting is
            # UNTESTED under a mesh (the ladder recompiles sharded
            # programs per size and concat/slice re-infer the output
            # sharding) — reject at construction rather than leave a
            # silent corruption path.  ROADMAP item 3 (per-axis ledger)
            # is where this lands properly.
            if self._owns_pool:
                self.pool.close()
            raise NotImplementedError(
                "HBM-arbiter-armed serving (elastic PagedKVPool) under a "
                "mesh is not supported: grow/shrink per-shard accounting "
                "is untested — serve the arbiter single-device, or the "
                "mesh without an arbiter (hbm=None)")
        if self.mesh is not None:
            from tpulab.parallel.sharding import (replicate,
                                                  transformer_param_shardings)
            self._rep = replicate(self.mesh)
            self._param_sh = transformer_param_shardings(params, self.mesh)
            self.params = jax.device_put(params, self._param_sh)
            if prefill_flash:
                raise ValueError(
                    "the pallas flash prefill kernel is single-device; "
                    "mesh serving prefills through the dense or ragged "
                    "paths (prefill_flash must be False or None)")
            prefill_flash = False
        else:
            self._rep = self._param_sh = None
            self.params = jax.device_put(params, self.pool.device)
        n_shards = self.pool.n_shards
        if use_kernel and self.mesh is not None and n_heads % n_shards:
            raise ValueError(
                f"use_kernel under a mesh needs query heads ({n_heads}) "
                f"divisible by the model axis ({n_shards}) — the ragged "
                "kernel shards the page walk on the heads dim")
        from tpulab.tpu.platform import is_tpu, pallas_interpret

        def kernel_error():
            """Mosaic's shape rule at the PER-SHARD geometry (one shard's
            program is the one that must build) and the widest segment a
            dispatch can carry: a mixed round that spends its whole token
            budget under the ragged plan, a K+1 verify otherwise."""
            from tpulab.ops.ragged_attention import (kernel_geometry_error,
                                                     latent_geometry_error)
            widest = (round_width(self._round_budget)
                      if ragged is not False else self.BLOCK_K_MENU[-1] + 1)
            if latent:
                return latent_geometry_error(
                    widest, n_heads, self.pool.kv.shape[4],
                    spec.kv_lora_rank, self.pool.page_size, self.max_pages,
                    compute_dtype, self.pool.dtype)
            return kernel_geometry_error(
                widest, n_heads // n_shards, n_kv // n_shards,
                d_model // n_heads, self.pool.page_size, self.max_pages,
                compute_dtype, self.pool.dtype)

        if use_kernel is None:
            # auto: the pallas ragged kernel on TPU at LONG contexts only
            # (where the gather path's O(lanes*max_len) dense HBM
            # materialization per step should dominate) and only at a
            # geometry the shape rule admits; the XLA gather elsewhere.
            # No chip measurement backs the threshold yet (ROADMAP S3);
            # explicit use_kernel=True overrides it.  Under a mesh the
            # kernel shards on the KV-heads dim (shard_map), so the auto
            # pick covers sharded serving too.
            use_kernel = (is_tpu()
                          and max_len >= self.KERNEL_AUTO_MIN_CTX
                          and n_heads % n_shards == 0
                          and kernel_error() is None)
        elif use_kernel and not pallas_interpret():
            # asked for a kernel the geometry cannot have: say which
            # constraint, up front — a Mosaic error past this rule is a
            # real error and propagates
            err = kernel_error()
            if err:
                if self._owns_pool:
                    self.pool.close()
                raise ValueError(f"use_kernel=True: {err}")
        self.use_kernel = bool(use_kernel)
        #: ragged dispatch plan (docs/PERFORMANCE.md "Ragged paged
        #: attention"): mixed prefill+decode rounds run as ONE fused
        #: ragged program (paged_mixed_step) instead of per-lane prefill
        #: dispatches followed by a separate decode kind.  Default rides
        #: ``use_kernel`` (the kernel family and the dispatch plan ship
        #: together); ``ragged=True`` forces the unified plan onto the
        #: XLA gather path, ``use_kernel=False`` alone keeps the legacy
        #: split dispatch — the escape hatch.
        self.ragged = self.use_kernel if ragged is None else bool(ragged)
        self._step_kw = dict(n_heads=n_heads, n_layers=n_layers,
                             compute_dtype=compute_dtype,
                             use_kernel=self.use_kernel,
                             n_kv_heads=n_kv, rope_theta=rope_theta,
                             mesh=self.mesh)
        if spec is not None:
            # only where a spec was given: a dense engine's programs keep
            # the key they always had in the jit memo
            self._step_kw["spec"] = spec
        #: expert layers' counters (``debug_state()["moe"]``), summed on
        #: the host from the small array every dispatch of an expert model
        #: returns and the scheduler fetches WITH the dispatch's tokens
        self._moe_assignments = (
            np.zeros((len(spec.moe_layers), spec.n_experts), np.int64)
            if spec is not None and spec.moe_layers else None)
        self.moe_decode_steps = 0    # decode steps that had a live lane
        self.moe_experts_hit = 0     # over those steps and expert layers
        rep, psh = self._rep, self._param_sh
        kvsh = self.pool.kv_sharding
        self._step = self._jit(
            partial(paged_decode_step, **self._step_kw), (1,),
            (psh, kvsh, rep, rep, rep, rep), (rep, kvsh))
        # sampled K=1 variant (positional temps/seeds so the sharded jit
        # can attach in_shardings; identical compiled programs at mesh=None
        # — jit specialized on temps=None vs arrays before too)
        self._step_sampled = self._jit(
            partial(paged_decode_step_sampled, **self._step_kw), (1,),
            (psh, kvsh, rep, rep, rep, rep, rep, rep),
            (rep, rep, rep, kvsh))
        # mixed prefill+decode rounds (the ragged dispatch plan): ONE
        # jitted program respecializes per pow2 bucket of the round's
        # prefill tokens (round_width) — the chunks packed by token and
        # a row for each lane's decode token through a single ragged
        # forward + on-device pick
        self._mixed = self._jit(
            partial(paged_mixed_step, **self._step_kw), (1,),
            (psh, kvsh) + (rep,) * 8, (rep, rep, rep, kvsh))
        if decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        #: max fused-decode steps per dispatch (K): a K-block amortizes the
        #: host<->device round trip over K tokens.  The per-block K is
        #: adaptive (see _pick_block_k) — this is the ceiling; 1 disables
        #: multi-step dispatch entirely.
        self.decode_block = min(int(decode_block), self.BLOCK_K_MENU[-1])
        self._block_cache: Dict[int, Any] = {}
        self._pending_block: Optional[Dict[str, Any]] = None
        self._step_ewma_s = 0.0   # per-scan-step device time estimate
        # -- dispatch/sync accounting (tokens_per_dispatch telemetry and
        #    the host-syncs-per-request regression guard read these) ------
        self.decode_dispatches = 0   # device decode dispatches (any K)
        self.decode_host_syncs = 0   # blocking device->host decode fetches
        self.prefill_dispatches = 0  # prefill passes (one per prompt fill;
        #                              stays 0 under the ragged plan —
        #                              prompts ride mixed rounds instead)
        #: dispatches through the ragged kernel family: every mixed
        #: round, plus plain/spec dispatches whose attention ran the
        #: pallas ragged kernel (use_kernel)
        self.ragged_dispatches = 0
        #: per-dispatch-kind counts (the ragged plan's three descriptor
        #: kinds): "decode" = plain K-blocks and single ticks, "verify"
        #: = speculative draft+verify blocks, "mixed" = ragged mixed
        #: prefill+decode rounds
        self.dispatch_kinds: Dict[str, int] = {"decode": 0, "verify": 0,
                                               "mixed": 0}
        #: rows the mixed rounds computed (``M + lanes`` a round) and the
        #: rows among them that held a token: their ratio is the fill of
        #: the packed round
        self.mixed_rows = 0
        self.mixed_tokens = 0
        #: sum of K over plain decode dispatches (K-blocks and single
        #: ticks): over ``dispatch_kinds["decode"]`` it is the mean block
        self.decode_block_steps = 0
        #: where a scheduler pass goes (docs/OBSERVABILITY.md "Debugz"):
        #: disjoint stages of the scheduler thread, each a ``sched.<stage>``
        #: span in a profiler capture and seconds + entries here
        self._stages = tracing.StageClock(self.STAGES, prefix="sched.")
        #: request waits, summed where the observers above see them
        #: (seconds, count): submit -> prefill start, submit -> first
        #: token, first token -> second token (what a newly admitted lane
        #: waits for the running chain)
        self.queue_wait_s, self.queue_waits = 0.0, 0
        self.ttft_s, self.ttfts = 0.0, 0
        self.first_decode_wait_s, self.first_decode_waits = 0.0, 0
        if prefill_flash is None:
            # auto: pallas flash attention for the FULL-PROMPT forward on
            # TPU (O(T*block) VMEM instead of a dense (T, T) score
            # materialization).  Scope: the start==0 un-chunked prefill
            # only — chunked prefills and prefix-cache tails run
            # paged_extend's gather attention, which has no flash analog
            # here.
            prefill_flash = is_tpu()
        self.prefill_flash = bool(prefill_flash)
        self._prefill_kw = dict(n_heads=n_heads, n_layers=n_layers,
                                compute_dtype=compute_dtype,
                                n_kv_heads=n_kv, rope_theta=rope_theta)
        self._prefill = self._build_prefill(self.prefill_flash)
        # tail/chunk prefill against existing pool context (prefix-cache
        # hits, chunked long prompts) — compiled per tail-length bucket
        self._extend = self._jit(
            partial(paged_extend, n_heads=n_heads, n_layers=n_layers,
                    compute_dtype=compute_dtype, n_kv_heads=n_kv,
                    rope_theta=rope_theta),
            (1,), (psh, kvsh, rep, rep, rep, rep), (rep, kvsh))
        # -- speculative decoding (a draft model riding the SAME pool
        #    through a second per-lane page table; docs/PERFORMANCE.md) -----
        # ``draft_params`` arms it: the draft proposes K tokens per lane
        # inside the fused dispatch, the target verifies all of them in one
        # batched forward, and each dispatch emits up to K+1 ACCEPTED
        # tokens — multiplying the decode-block dispatch amortization by
        # the acceptance rate.  Emitted tokens are bit-identical to the
        # non-speculative stream (greedy and device-sampled); host-sampled
        # lanes never enter the speculative path, and a lane whose rolling
        # acceptance EWMA falls below ``spec_accept_floor`` (or whose
        # verify dispatch trips chaos) degrades to plain blocks for the
        # rest of its request.
        self._spec: Optional[Dict[str, Any]] = None
        self.spec_accept_floor = float(spec_accept_floor)
        self.spec_dispatches = 0        # speculative decode dispatches
        self.spec_fallbacks = 0         # lanes degraded to plain blocks
        self.spec_draft_prefills = 0    # draft-table warm-up forwards
        self.spec_tokens_drafted = 0    # proposals verified by the target
        self.spec_tokens_accepted = 0   # of those, emitted (accepted)
        self.spec_probes = 0            # probe blocks re-trying a degraded
        #                                 lane (EWMA degrades only)
        self.spec_probe_recoveries = 0  # probes whose lane stayed
        #                                 speculative (acceptance came back)
        self._spec_block_cache: Dict[int, Any] = {}
        if draft_params is not None:
            dl = draft_n_layers or n_layers
            dh = draft_n_heads or n_heads
            dkv = draft_n_kv_heads or (n_kv if draft_n_heads is None else dh)
            dd = weight_shape(draft_params["layer0"]["wqkv"])[0]
            if dd // dh != d_model // n_heads or dkv != n_kv:
                raise ValueError(
                    "draft model KV geometry (head_dim, n_kv_heads) must "
                    "match the target's — both write the shared paged pool")
            if dl > n_layers:
                raise ValueError("draft_n_layers must be <= n_layers (the "
                                 "draft shares the pool's layer axis)")
            if self.mesh is not None:
                from tpulab.parallel.sharding import \
                    transformer_param_shardings
                self._draft_param_sh = transformer_param_shardings(
                    draft_params, self.mesh)
                draft_dev = jax.device_put(draft_params,
                                           self._draft_param_sh)
            else:
                self._draft_param_sh = None
                draft_dev = jax.device_put(draft_params, self.pool.device)
            self._spec = {"params": draft_dev,
                          "n_heads": dh, "n_layers": dl, "n_kv_heads": dkv}
            self._spec_kw = dict(n_heads=n_heads, n_layers=n_layers,
                                 draft_n_heads=dh, draft_n_layers=dl,
                                 compute_dtype=compute_dtype,
                                 n_kv_heads=n_kv, draft_n_kv_heads=dkv,
                                 rope_theta=rope_theta,
                                 use_kernel=self.use_kernel,
                                 mesh=self.mesh)
            # draft-table warm-up: one fused draft forward over whatever
            # context tail the second table is missing (never synced)
            self._draft_extend = self._jit(
                partial(paged_extend, n_heads=dh, n_layers=dl,
                        compute_dtype=compute_dtype, n_kv_heads=dkv,
                        rope_theta=rope_theta),
                (1,), (self._draft_param_sh, kvsh, rep, rep, rep, rep),
                (rep, kvsh))
        self.prefix_cache = PrefixCache(self.pool) if prefix_cache else None
        # host-memory KV tier (tpulab.kvcache): None/False = off (zero
        # cost); True = a manager with the default host budget; an int =
        # budget bytes; a KVOffloadManager = bring-your-own (shared
        # store/transfer).  When on, preemption swaps KV device->host and
        # resume swaps back (no re-prefill), and prefix-cache eviction
        # demotes to / promotes from the host tier.
        self._owns_offload = False
        if kv_offload is None or kv_offload is False:
            self.kv_offload = None
        else:
            from tpulab.kvcache import (DEFAULT_HOST_BUDGET,
                                        KVOffloadManager)
            if isinstance(kv_offload, KVOffloadManager):
                self.kv_offload = kv_offload
            else:
                budget = (DEFAULT_HOST_BUDGET if kv_offload is True
                          else int(kv_offload))
                self.kv_offload = KVOffloadManager(self.pool, budget)
                self._owns_offload = True
        if self.kv_offload is not None and self.prefix_cache is not None:
            self.prefix_cache.on_evict = self._demote_prefix
            self.prefix_cache.promote_fn = self._promote_prefix
        # fleet KV fabric publish (tpulab.kvfabric, docs/SERVING.md
        # "Fleet KV fabric"): finished FIRST prefills export their
        # prompt-only KV to the host tier under ("fab", content_digest) —
        # the same write-behind swap_out preemption uses — plus the
        # prefill's last-position logits row under ("fablog", digest), so
        # a FetchKV RPC can serve both to the digest's routed-astray
        # fetchers without evicting this replica's own copy.  Requires
        # kv_offload (the host tier IS the export buffer).  Publishes
        # ride the legacy prefill dispatch only: the ragged plan's mixed
        # rounds never fetch a host-visible logits row (documented
        # limitation; ROADMAP follow-up).
        if kv_publish and self.kv_offload is None:
            raise ValueError("kv_publish requires kv_offload")
        self.kv_publish = bool(kv_publish)
        from collections import OrderedDict as _OD
        self._fab_handles: "Dict[bytes, Any]" = _OD()
        self._fab_lock = threading.Lock()
        self.kv_publishes = 0  # prompt snapshots exported to the fabric
        #: rolling prefill throughput (tokens/s, EWMA) — the fabric's
        #: cost gate weighs a remote fetch's wire time against simply
        #: recomputing the prompt here (0.0 until the first prefill)
        self.prefill_ewma_tok_s = 0.0
        #: optional tpulab.utils.tracing.ChromeTraceRecorder — the batcher
        #: records queue/prefill/decode-chunk spans per request (spans ride
        #: per-lane rows; the serving layer may attach one post-hoc)
        self.trace = trace
        #: optional tpulab.utils.metrics.GenerationMetrics — TTFT /
        #: inter-token / queue-wait / e2e distributions observed per
        #: completed request at the source, not polled
        self.metrics = metrics
        #: optional tpulab.obs.FlightRecorder — per-request wide events
        #: (docs/OBSERVABILITY.md "Flight recorder").  Armed, each request
        #: carries a small detail dict (block sizes, ITL samples, swap
        #: events, peak pages) and completion attaches the summary to the
        #: future as ``_tpulab_flight``; requests whose wide event the RPC
        #: layer assembles (flight_owner="rpc") are never double-recorded.
        #: None = disarmed: one None check per site, tokens unchanged
        #: either way (the recorder observes, never steers).
        self.flight = flight
        #: debugz on-demand XLA profiler capture (arm_profile): dict with
        #: remaining/dir/active, managed by the scheduler thread only
        self._profile: Optional[Dict[str, Any]] = None
        self._queue: List[_PagedRequest] = []
        self._requests: Dict[Future, _PagedRequest] = {}
        self._active: List[Optional[_PagedRequest]] = [None] * lanes
        self._admit_counter = 0
        self.preemptions = 0
        #: of those, evictions of BATCH-class lanes (the offline lane is
        #: the first preemption victim by design — a high number here
        #: with few online preemptions means the lane is doing its job)
        self.batch_preemptions = 0
        if self.hbm is not None:
            # register as the KV tenant AFTER kv_offload is settled (the
            # reclaimable estimate reads it) and claim the page store's
            # tracked bytes — the ledger now mirrors the allocator gauge
            from tpulab.hbm import KV_TENANT
            self.hbm.register(KV_TENANT, reclaim=self._hbm_reclaim,
                              reclaimable=self._hbm_reclaimable,
                              gauge=lambda: self.pool.hbm_bytes)
            self.hbm.mirror_claim(KV_TENANT, "pool", self.pool.hbm_bytes)
        self.completed_requests = 0  # futures resolved successfully
        self.tokens_generated = 0    # emitted across all requests
        self._cv = threading.Condition()
        self._shutdown = False
        self._thread = threading.Thread(target=self._run, name="cbatch",
                                        daemon=True)
        self._thread.start()

    #: the stages of a scheduler pass, in the order a pass takes them
    STAGES = ("admit", "plan", "dispatch", "fetch", "commit", "emit", "idle")

    def _jit(self, fn, donate, in_sh, out_sh):
        """``jax.jit`` with explicit in/out shardings under a mesh — the
        partitioner then inserts the collectives (psum after row-parallel
        matmuls, gathers where layouts demand) INSIDE the compiled
        program — and a plain single-device jit otherwise (``in_sh`` /
        ``out_sh`` ignored; mesh=None is exactly the pre-mesh build).

        Jitted programs are shared through a process-level memo
        (:data:`_JIT_MEMO`) keyed by the function + its baked static
        config + donation + shardings: engines with identical program
        geometry (test suites, fleets of loopback replicas, bench
        modes) reuse one compiled-program cache instead of re-tracing
        and re-compiling identical HLO per engine.  Params and pools
        are traced ARGUMENTS, never baked, so sharing is purely a
        compile-time dedupe; configs with unhashable baked state (e.g.
        a flash-attention closure) fall back to a private jit.

        With an arbiter measuring scratch, the (shared) jit is wrapped
        per engine so each distinct shape signature records its
        compile-time temp bytes as a ``("scratch", ...)`` ledger claim
        (tpulab.hbm.scratch) — the third tenant the pre-arbiter
        headroom math never saw."""
        import jax

        base = getattr(fn, "func", fn)
        if fn is not base:
            # a bare partial is ``jit__unknown`` in a trace: name the
            # program after its function (+ the block size it binds)
            k = fn.keywords.get("k")
            fn.__name__ = base.__name__ + (f"_k{k}" if k is not None else "")

        def build():
            if self.mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=in_sh, out_shardings=out_sh)

        try:
            key = (base.__module__, base.__qualname__,
                   getattr(fn, "args", ()),
                   tuple(sorted(getattr(fn, "keywords", {}).items())),
                   donate,
                   in_sh if self.mesh is not None else None,
                   out_sh if self.mesh is not None else None)
            hash(key)
        except TypeError:
            key = None
        if key is None:
            jitted = build()
        else:
            with _JIT_MEMO_LOCK:
                jitted = _JIT_MEMO.get(key)
            if jitted is None:
                jitted = build()
                with _JIT_MEMO_LOCK:
                    jitted = _JIT_MEMO.setdefault(key, jitted)
        if self.hbm is not None and self.hbm.measure_scratch:
            from tpulab.hbm import MeasuredJit
            name = getattr(getattr(fn, "func", fn), "__name__", "jit")
            jitted = MeasuredJit(jitted, self.hbm, name)
        return jitted

    def _build_prefill(self, flash: bool):
        """Jitted fused prefill, compiled per prompt-length bucket (powers
        of two); ``flash`` selects the pallas prompt-attention kernel."""
        attn_fn = None
        if flash:
            from tpulab.ops.flash_attention import make_flash_attention_fn
            attn_fn = make_flash_attention_fn(causal=True)
        rep, kvsh = self._rep, self.pool.kv_sharding
        return self._jit(
            partial(paged_prefill, attention_fn=attn_fn,
                    **self._prefill_kw),
            (1,), (self._param_sh, kvsh, rep, rep, rep), (rep, kvsh))

    # -- public -------------------------------------------------------------
    def submit(self, prompt, steps: int, on_token=None,
               sampling: Optional[SamplingParams] = None,
               priority: int = 0, stop_tokens=None,
               logprobs: bool = False, deadline=None,
               trace_id: Optional[str] = None,
               export_digest: Optional[bytes] = None,
               tenant: Optional[str] = None,
               flight_owner: Optional[str] = None,
               request_class: str = "online") -> Future:
        """``on_token(token, index)`` (optional) streams tokens as they
        decode — the hook the Generate RPC rides for paged serving.
        ``sampling`` selects the token policy (default greedy).
        ``logprobs=True`` resolves the future to ``(tokens, logprobs)``
        (each token's chosen log-probability, computed on device) instead
        of the plain token list, and ``on_token`` is then called with a
        third ``logprob`` argument.
        ``stop_tokens`` (iterable of token ids, e.g. the tokenizer's EOS)
        ends generation early: the stop token is emitted as the final
        token and the lane/pages free at that tick.
        ``priority`` orders admission (higher first; FIFO within a class)
        and arms preemption: a queued request strictly outranking an active
        one evicts it — the victim's pages free immediately and it resumes
        later by re-prefilling prompt+generated (exact-token resume; with a
        prefix cache the recompute mostly hits cached pages).
        ``deadline`` (a :class:`~tpulab.core.deadline.Deadline` or a float
        budget in seconds) bounds the request: the scheduler cancels it
        before its next step once expired — lane and KV pages free within
        one tick — and the future fails with DeadlineExceeded.
        ``trace_id`` tags this request's queue/prefill/decode spans in the
        attached ``trace`` recorder (the Generate RPC threads the client's
        id through here, merging both processes into one timeline).
        ``export_digest`` (requires ``kv_offload``) demotes the finished
        request's KV to the host tier under ``("ship", digest)`` at lane
        release — the prefill-replica half of disaggregated serving
        (tpulab.disagg): submit with ``steps=1`` and the resulting
        snapshot covers exactly the prompt; the export
        :class:`~tpulab.kvcache.offload.SwapHandle` lands on the future
        as ``_tpulab_kv_export`` (None when the swap degraded).
        ``tenant`` tags the request for flight-recorder / debugz
        attribution (never read by the scheduler); ``flight_owner="rpc"``
        marks the wide event as assembled by the RPC layer — the engine
        still attaches its completion summary to the future
        (``_tpulab_flight``) but does not record it itself.
        ``request_class`` ("online" default, or "batch" — the offline
        batch lane, docs/SERVING.md) ranks the request: a batch request
        queues behind EVERY online request regardless of priority, is
        the first preemption victim when an online arrival needs its
        lane or pages, and its ``on_token`` hook (a checkpoint sink,
        not an interactive consumer) never drags the fused-decode block
        size down."""
        if request_class not in ("online", "", "batch"):
            raise ValueError(f"unknown request_class {request_class!r} "
                             "(want 'online' or 'batch')")
        flat = np.asarray(prompt).reshape(-1)
        if isinstance(deadline, Deadline):
            deadline = deadline.expiry
        elif deadline is not None:
            deadline = _time.monotonic() + float(deadline)
        n_prompt = len(flat)
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        if n_prompt + steps > self.max_len:
            raise ValueError(f"prompt+steps exceeds max_len {self.max_len}")
        if flat.min() < 0 or flat.max() >= self.vocab:
            # XLA gather CLAMPS out-of-bounds ids — silent garbage tokens;
            # reject at the host boundary instead
            raise ValueError(f"prompt token ids outside [0, {self.vocab})")
        if export_digest is not None and self.kv_offload is None:
            raise ValueError("export_digest requires kv_offload")
        req = _PagedRequest(prompt, steps, on_token=on_token,
                            sampling=sampling, priority=priority,
                            stop_tokens=stop_tokens, logprobs=logprobs,
                            deadline=deadline, trace_id=trace_id,
                            tenant=tenant,
                            batch=request_class == "batch")
        req.export_digest = export_digest
        if self.flight is not None or flight_owner:
            self._fl_arm(req, flight_owner)
        with self._cv:
            if self._shutdown:
                raise RuntimeError("ContinuousBatcher is shut down")
            self._enqueue_locked(req, front_of_class=False)
            self._requests[req.future] = req
            self._cv.notify()
        return req.future

    def submit_shipped(self, prompt, steps: int, first_token: int,
                       handle, on_token=None,
                       sampling: Optional[SamplingParams] = None,
                       priority: int = 0, stop_tokens=None, deadline=None,
                       trace_id: Optional[str] = None,
                       tenant: Optional[str] = None,
                       flight_owner: Optional[str] = None) -> Future:
        """Admit a request whose prompt KV arrived SHIPPED from a prefill
        replica (tpulab.disagg) — the decode-replica half of
        disaggregated serving.

        ``handle`` is the resident host-tier snapshot a
        :class:`~tpulab.disagg.KVShipper` import minted (None = shipment
        lost: the request still admits and prefills locally), and
        ``first_token`` the prefill replica's index-0 pick — emitted to
        ``on_token`` here (index 0) so the stream the consumer sees is
        identical to a unified replica's.  Admission promotes the
        snapshot through the existing ``KVOffloadManager.restore`` path:
        the lane starts decoding with ZERO prefill dispatches.  Every
        degraded shipment (lost, corrupt, chaos-tripped, budget-refused,
        restore failure) falls back to the exact local prefill — which
        recomputes the same KV, so token parity holds either way.

        Host-sampled requests (``temperature > 0`` without device
        sampling) are rejected: their PRNG stream is keyed by draw
        order, which does not survive the replica hop; greedy and
        device-sampled streams are keyed by (seed, position) and do."""
        flat = np.asarray(prompt).reshape(-1)
        if isinstance(deadline, Deadline):
            deadline = deadline.expiry
        elif deadline is not None:
            deadline = _time.monotonic() + float(deadline)
        n_prompt = len(flat)
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if steps < 1:
            raise ValueError("steps must be >= 1")
        if n_prompt + steps > self.max_len:
            raise ValueError(f"prompt+steps exceeds max_len {self.max_len}")
        if flat.min() < 0 or flat.max() >= self.vocab:
            raise ValueError(f"prompt token ids outside [0, {self.vocab})")
        if not 0 <= int(first_token) < self.vocab:
            raise ValueError(
                f"shipped first token outside [0, {self.vocab})")
        sp = sampling or SamplingParams()
        if sp.temperature > 0.0 and not sp.device:
            raise ValueError(
                "shipped-KV admission requires greedy or device sampling "
                "(host-side PRNG streams do not survive the replica hop)")
        if handle is not None and self.kv_offload is None:
            raise ValueError("shipped-KV admission requires kv_offload")
        if handle is not None and handle.length != n_prompt:
            raise ValueError(
                f"shipment covers {handle.length} positions, prompt has "
                f"{n_prompt}")
        req = _PagedRequest(prompt, steps, on_token=on_token,
                            sampling=sp, priority=priority,
                            stop_tokens=stop_tokens, deadline=deadline,
                            trace_id=trace_id, tenant=tenant)
        if self.flight is not None or flight_owner:
            self._fl_arm(req, flight_owner)
        # the first-token pick already happened on the prefill replica:
        # seed the lane as a resume (a degraded restore then re-prefills
        # and DISCARDS its logits, exactly like a preemption resume)
        req.tokens_out.append(int(first_token))
        req.kv_handle = handle
        req.resumed = True
        self.tokens_generated += 1
        self._emit(req, int(first_token), 0, None)
        if req.finished():  # steps == 1 or first token hit a stop token
            if handle is not None and self.kv_offload is not None:
                self.kv_offload.discard(handle)
            req.kv_handle = None
            self._flight_complete(req)
            req.future.set_result(self._result_of(req))
            self.completed_requests += 1
            return req.future
        with self._cv:
            if self._shutdown:
                raise RuntimeError("ContinuousBatcher is shut down")
            self._enqueue_locked(req, front_of_class=False)
            self._requests[req.future] = req
            self._cv.notify()
        return req.future

    def cancel(self, future: Future) -> None:
        """Abort a submitted request (freed at the next tick boundary)."""
        with self._cv:
            req = self._requests.get(future)
            if req is not None:
                req.cancelled = True
                if req in self._queue:  # never started: finish immediately
                    self._queue.remove(req)
                    self._requests.pop(future, None)
                    self._discard_handle(req)
        if req is not None and req not in self._active and not future.done():
            future.cancel()

    def shutdown(self) -> None:
        with self._cv:
            self._shutdown = True
            self._cv.notify()
        self._thread.join(timeout=30)
        if not self._thread.is_alive() and self.prefix_cache is not None:
            self.prefix_cache.on_evict = None  # shutdown clear != pressure
            self.prefix_cache.clear()  # release the cache's page refs
        if self._owns_offload and not self._thread.is_alive():
            self.kv_offload.close()  # drain write-behind, free host tier
        if self._owns_pool and not self._thread.is_alive():
            self.pool.close()  # free the page stores' HBM eagerly
            if self.hbm is not None:
                from tpulab.hbm import KV_TENANT
                self.hbm.release(KV_TENANT, "pool")

    @property
    def active_lanes(self) -> int:
        with self._cv:
            return sum(r is not None for r in self._active)

    @property
    def queued_requests(self) -> int:
        with self._cv:
            return len(self._queue)

    @property
    def spec_acceptance(self) -> float:
        """Lifetime draft acceptance rate (accepted / drafted)."""
        return self.spec_tokens_accepted / max(1, self.spec_tokens_drafted)

    @property
    def admission_cost_factor(self) -> float:
        """Cost multiplier the admission frontend applies to this
        engine's requests (serving/admission.py).  A speculative request
        holds a SECOND page table (the draft KV) next to the target's
        and burns draft+verify compute on rejected proposals —
        drafted-but-rejected tokens are not free, so cost-aware
        admission must not plan capacity as if they were."""
        return 2.0 if self._spec is not None else 1.0

    # -- telemetry (no-ops without an attached recorder/metrics) ------------
    def _span(self, name: str, lane: int, t0: float, dur: float,
              req: _PagedRequest, **extra) -> None:
        """One request-lifecycle span on the lane's trace row."""
        tr = self.trace
        if tr is None:
            return
        if req.trace_id:
            extra["trace_id"] = req.trace_id
        tr.add_span(name, t0, dur, tid=lane, lane=lane, **extra)

    def _flush_decode_chunk(self, req: _PagedRequest, lane: int,
                            now: float, **extra) -> None:
        """Close the open decode-chunk span at ``now`` and start the next
        (K>1 dispatch passes ``block=K`` — block-sized decode spans)."""
        n = len(req.tokens_out)
        if req.chunk_t0 is not None and n > req.chunk_start:
            self._span("decode", lane, req.chunk_t0, now - req.chunk_t0,
                       req, first=req.chunk_start,
                       tokens=n - req.chunk_start, **extra)
        req.chunk_t0 = now
        req.chunk_start = n

    def _note_complete(self, req: _PagedRequest) -> None:
        if self.metrics is not None:
            self.metrics.observe_e2e(_time.perf_counter() - req.t_submit)

    # -- flight recorder (tpulab.obs, docs/OBSERVABILITY.md) ----------------
    #: per-request detail lists stay bounded — a pathological request
    #: must not turn its own wide event into a memory leak
    FLIGHT_DETAIL_CAP = 1024

    @staticmethod
    def _fl_arm(req: _PagedRequest, owner: Optional[str]) -> None:
        """Attach the per-request flight detail dict (armed path only)."""
        req.fl = {"owner": owner, "blocks": [], "itl": [],
                  "swap_outs": 0, "swap_ins": 0, "preempts": 0,
                  "pages_peak": 0, "chaos0": chaos.fired_snapshot()}

    def _fl_block(self, req: _PagedRequest, k: int, n: int,
                  dt: Optional[float]) -> None:
        """One fused-decode dispatch's contribution to the wide event:
        block size K, tokens emitted, the spread per-token latency."""
        fl = req.fl
        if fl is None:
            return
        if len(fl["blocks"]) < self.FLIGHT_DETAIL_CAP:
            fl["blocks"].append((k, n))
        if dt is not None and len(fl["itl"]) < self.FLIGHT_DETAIL_CAP:
            fl["itl"].append((dt, n))
        pages = len(req.pages) + len(req.draft_pages)
        if pages > fl["pages_peak"]:
            fl["pages_peak"] = pages

    def _fl_pages(self, req: _PagedRequest) -> None:
        fl = req.fl
        if fl is not None:
            pages = len(req.pages) + len(req.draft_pages)
            if pages > fl["pages_peak"]:
                fl["pages_peak"] = pages

    def _flight_summary(self, req: _PagedRequest,
                        outcome: str) -> Dict[str, Any]:
        """The engine's half of the wide event (the RPC layer adds
        admission/status/transport fields for requests it owns)."""
        now = _time.perf_counter()
        ev: Dict[str, Any] = {
            "kind": "paged", "outcome": outcome, "tenant": req.tenant,
            "request_class": "batch" if req.batch else "online",
            "priority": req.priority, "trace_id": req.trace_id,
            "prompt_tokens": int(len(req.prompt)), "steps": req.steps,
            "tokens": len(req.tokens_out),
            "t_submit": req.t_submit, "t_prefill0": req.t_prefill0,
            "t_first": req.t_first, "t_last": req.t_last,
            "e2e_s": now - req.t_submit, "lane": req.lane,
            "pages": len(req.pages),
        }
        if req.t_prefill0 is not None:
            ev["queue_wait_s"] = req.t_prefill0 - req.t_submit
        if req.t_first is not None:
            ev["ttft_s"] = req.t_first - req.t_submit
        if req.spec_drafted:
            ev["spec_drafted"] = req.spec_drafted
            ev["spec_accepted"] = req.spec_accepted
            ev["spec_acceptance"] = round(
                req.spec_accepted / req.spec_drafted, 4)
        fl = req.fl
        if fl is not None:
            ev["pages_peak"] = max(fl["pages_peak"], len(req.pages))
            ev["block_ks"] = [k for k, _n in fl["blocks"]]
            ev["preempts"] = fl["preempts"]
            ev["swap_outs"] = fl["swap_outs"]
            ev["swap_ins"] = fl["swap_ins"]
            if fl["itl"]:
                itl = np.repeat([d for d, _ in fl["itl"]],
                                [n for _, n in fl["itl"]])
                ev["itl_ms"] = {
                    "p50": round(float(np.percentile(itl, 50)) * 1e3, 4),
                    "p99": round(float(np.percentile(itl, 99)) * 1e3, 4),
                    "max": round(float(itl.max()) * 1e3, 4),
                    "n": int(itl.size)}
            trips = {}
            for point, n in chaos.fired_snapshot().items():
                d = n - fl["chaos0"].get(point, 0)
                if d > 0:
                    trips[point] = d
            if trips:
                ev["chaos_trips"] = trips
        if self.hbm is not None:
            ev["hbm_pressure_events"] = self.hbm.pressure_events
        return ev

    def _flight_complete(self, req: _PagedRequest,
                         outcome: str = "SUCCESS") -> None:
        """Completion hook (every future-resolution site): attach the
        engine summary to the future BEFORE it resolves (race-free, the
        ``_tpulab_compute_s`` idiom) and record it — unless the RPC layer
        owns this request's wide event."""
        fr = self.flight
        if fr is None and req.fl is None:
            return
        ev = self._flight_summary(req, outcome)
        req.future._tpulab_flight = ev
        owner = req.fl.get("owner") if req.fl is not None else None
        if fr is not None and owner != "rpc":
            fr.observe(ev)

    # -- debugz (tpulab.obs.debugz) -----------------------------------------
    def arm_profile(self, ticks: int, log_dir: Optional[str] = None) -> str:
        """Arm the process's profiler switch (``tracing.start``) around the
        next ``ticks`` scheduler ticks (the Debug RPC's ``profile_ticks``).
        The capture starts at the next pass the scheduler runs and stops
        after ``ticks`` passes; returns the trace directory
        (``tensorboard --logdir`` it).  Raises ``tracing.ProfilerBusy``
        while another owner's capture is open."""
        if int(ticks) < 1:
            raise ValueError("profile_ticks must be >= 1")
        if tracing.active():
            raise tracing.ProfilerBusy(
                "a profiler capture is already open in this process")
        if log_dir is None:
            import tempfile
            log_dir = tempfile.mkdtemp(prefix="tpulab-profile-")
        with self._cv:
            if self._profile is not None:
                raise RuntimeError("a profiler capture is already armed")
            self._profile = {"remaining": int(ticks), "dir": log_dir,
                             "active": False}
            self._cv.notify()
        return log_dir

    def _profile_step(self, done: bool = False) -> None:
        """Scheduler-thread profiler bookkeeping: start the armed capture,
        count one pass, stop at zero (or at shutdown with ``done``)."""
        prof = self._profile
        if prof is None:
            return
        if done:
            if prof["active"]:
                tracing.stop()
            self._profile = None
            return
        if not prof["active"]:
            try:
                tracing.start(prof["dir"])
            except tracing.ProfilerBusy:
                # another owner opened a capture since arm_profile: theirs
                # stands, this one is dropped (debugz shows it disarmed)
                self._profile = None
                return
            prof["active"] = True
            return  # the NEXT ticks are captured; arming pass is free
        prof["remaining"] -= 1
        if prof["remaining"] <= 0:
            tracing.stop()
            self._profile = None

    def debug_state(self) -> Dict[str, Any]:
        """Live scheduler introspection for debugz (one consistent
        snapshot under the scheduler lock): lanes, queue, elastic pool +
        ladder position, dispatch counters, speculative and prefix-cache
        state."""
        now = _time.perf_counter()
        with self._cv:
            lanes = []
            for lane, req in enumerate(self._active):
                if req is None:
                    lanes.append({"lane": lane, "state": "idle"})
                    continue
                lanes.append({
                    "lane": lane,
                    "state": ("prefill" if req.pending_prompt
                              else "decode"),
                    "request_class": "batch" if req.batch else "online",
                    "tenant": req.tenant, "priority": req.priority,
                    "trace_id": req.trace_id,
                    "age_s": round(now - req.t_submit, 6),
                    "tokens": len(req.tokens_out), "steps": req.steps,
                    "prompt_tokens": int(len(req.prompt)),
                    "pages": len(req.pages),
                    "draft_pages": len(req.draft_pages),
                    "cancelled": req.cancelled,
                })
            queue_head = [{"tenant": q.tenant, "priority": q.priority,
                           "age_s": round(now - q.t_submit, 6),
                           "prompt_tokens": int(len(q.prompt)),
                           "steps": q.steps}
                          for q in self._queue[:16]]
            queued = len(self._queue)
            profile_armed = self._profile is not None
        pool = self.pool
        rung, size = 0, self._hbm_pool_base
        while size and size * 2 <= pool.n_pages:
            size *= 2
            rung += 1
        out: Dict[str, Any] = {
            "kind": "paged",
            "lanes": lanes,
            "queued_requests": queued,
            "queue_head": queue_head,
            "pool": {"n_pages": pool.n_pages,
                     "free_pages": pool.free_pages,
                     "page_size": pool.page_size,
                     "page_nbytes": pool.page_nbytes,
                     "entry_kind": pool.entry_kind,
                     "bytes_per_token": pool.bytes_per_token,
                     "hbm_bytes": pool.hbm_bytes,
                     "n_shards": pool.n_shards,
                     "elastic": self.hbm is not None,
                     "ladder_base": self._hbm_pool_base,
                     "ladder_rung": rung,
                     "grows": self.hbm_grows,
                     "shrinks": self.hbm_shrinks},
            "dispatch": {"decode_block": self.decode_block,
                         "decode_dispatches": self.decode_dispatches,
                         "decode_host_syncs": self.decode_host_syncs,
                         "prefill_dispatches": self.prefill_dispatches,
                         "ragged": self.ragged,
                         "use_kernel": self.use_kernel,
                         "ragged_dispatches": self.ragged_dispatches,
                         "kinds": dict(self.dispatch_kinds),
                         "mixed_rows": self.mixed_rows,
                         "mixed_tokens": self.mixed_tokens,
                         "decode_block_steps": self.decode_block_steps,
                         "stages": self._stages.stages(),
                         "queue_wait_s": self.queue_wait_s,
                         "queue_waits": self.queue_waits,
                         "ttft_s": self.ttft_s,
                         "ttfts": self.ttfts,
                         "first_decode_wait_s": self.first_decode_wait_s,
                         "first_decode_waits": self.first_decode_waits,
                         "preemptions": self.preemptions,
                         "batch_preemptions": self.batch_preemptions,
                         "completed_requests": self.completed_requests,
                         "tokens_generated": self.tokens_generated},
            "profile_armed": profile_armed,
        }
        if self._spec is not None:
            out["spec"] = {"dispatches": self.spec_dispatches,
                           "fallbacks": self.spec_fallbacks,
                           "tokens_drafted": self.spec_tokens_drafted,
                           "tokens_accepted": self.spec_tokens_accepted,
                           "acceptance": round(self.spec_acceptance, 4),
                           "probes": self.spec_probes,
                           "probe_recoveries": self.spec_probe_recoveries}
        if self._moe_assignments is not None:
            out["moe"] = {
                "expert_layers": list(self.model_spec.moe_layers),
                # cumulative (row, expert) assignments, [expert layer][expert]
                "assignments": self._moe_assignments.tolist(),
                "decode_steps": self.moe_decode_steps,
                # summed over decode steps and expert layers
                "experts_hit": self.moe_experts_hit}
        pc = self.prefix_cache
        if pc is not None:
            out["prefix_cache"] = {"entries": len(pc), "hits": pc.hits,
                                   "misses": pc.misses,
                                   "host_promotions": pc.host_promotions}
        return out

    # -- scheduler ----------------------------------------------------------
    @staticmethod
    def _rank(req: _PagedRequest):
        """Scheduling rank: ``(class, priority)`` — every online request
        outranks every batch request (the offline lane sits strictly
        below online traffic at ANY priority); within a class, priority
        orders as before."""
        return (0 if req.batch else 1, req.priority)

    def _enqueue_locked(self, req: _PagedRequest,
                        front_of_class: bool) -> None:
        """Insert by rank (online before batch, higher priority first,
        FIFO within a class); ``front_of_class`` puts the request ahead
        of its equals (preempted victims resume before new same-priority
        arrivals)."""
        rank = self._rank(req)
        i = 0
        for i, q in enumerate(self._queue):
            if (self._rank(q) < rank
                    or (front_of_class and self._rank(q) == rank)):
                self._queue.insert(i, req)
                return
        self._queue.append(req)

    def _alloc_page(self) -> Optional[int]:
        """Pool page, evicting cold prefix-cache entries under pressure —
        live requests always outrank cached prefixes (with kv_offload the
        eviction DEMOTES the entry to the host tier instead of losing it)."""
        page = self.pool.allocate_page()
        while (page is None and self.prefix_cache is not None
               and self.prefix_cache.evict_for_alloc()):
            page = self.pool.allocate_page()
        return page

    # -- host KV tier (kv_offload) -------------------------------------------
    def _demote_prefix(self, digest: bytes, page: int) -> None:
        """PrefixCache.on_evict hook: spill the evicted page host-side."""
        self.kv_offload.demote(digest, page, self.pool.kv)

    def _promote_prefix(self, digest: bytes) -> Optional[int]:
        """PrefixCache.promote_fn hook: resurrect a demoted entry into a
        fresh pool page (plain allocate — promotion must not evict OTHER
        device entries and thrash the cache against itself)."""
        mgr = self.kv_offload
        if not mgr.has_prefix(digest):
            return None
        page = self.pool.allocate_page()
        if page is None:
            return None
        new_kv = mgr.promote(digest, page, self.pool.kv)
        if new_kv is None:
            self.pool.release_pages([page])
            return None
        self.pool.kv = new_kv
        return page

    # -- HBM economy (tpulab.hbm): the KV tenant --------------------------
    #: bound on how long a blocking grow request waits for a write-behind
    #: model eviction to land (only paid when every lane is starved —
    #: the scheduler had nothing else to do anyway)
    HBM_GROW_TIMEOUT_S = 0.5

    def _page_nbytes(self) -> int:
        return max(1, self.pool.page_nbytes)

    def _hbm_ladder_down(self, total: int) -> int:
        """Largest ladder size (base * 2^k) <= ``total`` (base floor)."""
        size = self._hbm_pool_base
        while size * 2 <= total:
            size *= 2
        return size

    def _hbm_reclaimable(self) -> int:
        """Non-mutating estimate of the KV bytes pressure could free:
        pages already contiguously free at the top of the store, plus
        idle prefix-cache pages, plus live-but-idle lane KV the host
        tier could absorb (demotion needs ``kv_offload`` — without the
        tier a preempted lane re-prefills, which frees pages but burns
        recompute, so it is not advertised as cheap headroom)."""
        pages = self.pool.shrinkable_pages()
        if self.prefix_cache is not None:
            pages += len(self.prefix_cache)
        if self.kv_offload is not None:
            with self._cv:
                lane_pages = sum(len(r.pages) for r in self._active
                                 if r is not None)
            pages = pages + min(lane_pages,
                                self.kv_offload.headroom_pages())
        return pages * self._page_nbytes()

    def _hbm_reclaim(self, nbytes: int) -> int:
        """Arbiter pressure hook (foreign thread): record the target and
        wake the scheduler — demotion/preemption/shrink run at the next
        tick boundary, where no dispatched block is in flight.  Returns
        the bytes this tenant expects to free (its progress promise)."""
        est = min(int(nbytes), self._hbm_reclaimable())
        if est <= 0:
            return 0
        with self._cv:
            self._hbm_reclaim_bytes = max(self._hbm_reclaim_bytes,
                                          int(nbytes))
            self._cv.notify()
        return est

    def _service_hbm_locked(self) -> None:
        """Serve an outstanding arbiter reclaim at the tick boundary:
        demote idle prefix-cache KV to the host tier, preempt
        live-but-idle lanes (their KV swaps out through the existing
        preemption path — the resumed stream is bit-exact), then shrink
        the page store's top and release the bytes to the ledger.  Only
        runs with no dispatched-ahead block in flight, so no in-flight
        decode page is ever victimized."""
        need = self._hbm_reclaim_bytes
        if not need or self.hbm is None or self._pending_block is not None:
            return
        from tpulab.hbm import KV_TENANT
        pn = self._page_nbytes()
        target = (need + pn - 1) // pn
        # snap the post-shrink total onto the size ladder (bounded
        # compiled shapes): free at least the target, landing on the
        # largest ladder size at or below what remains
        target = max(target, self.pool.n_pages
                     - self._hbm_ladder_down(
                         max(1, self.pool.n_pages - target)))
        # 1) idle KV first: cold prefix-cache entries demote for free
        while (self.pool.shrinkable_pages() < target
               and self.prefix_cache is not None
               and self.prefix_cache.evict_for_alloc()):
            pass
        # 2) live-but-idle lanes: preempt coldest-priority, least-progress
        # first — with kv_offload their KV demotes to the host tier and
        # the resume is recompute-free; without it the resume re-prefills
        # (the pre-arbiter preemption contract either way)
        while self.pool.shrinkable_pages() < target:
            victims = [(req.priority, -req.admit_seq, lane)
                       for lane, req in enumerate(self._active)
                       if req is not None]
            if not victims:
                break
            _, _, lane = min(victims)
            self._preempt_locked(lane)
            self.hbm_demotions += 1
        dropped = self.pool.shrink(target)
        self._hbm_reclaim_bytes = 0
        if dropped:
            self.hbm_shrinks += 1
            self.hbm.mirror_claim(KV_TENANT, "pool", self.pool.hbm_bytes)

    def _hbm_break_hoard_locked(self) -> None:
        """Preempt the most recently admitted lane when every lane is
        starved with nothing free — the hold-and-wait breaker for the
        elastic regime (see the _run call site).  The victim resumes
        exactly (preemption contract); progress resumes immediately."""
        if self.pool.free_pages > 0:
            return
        active = [(req.admit_seq, lane)
                  for lane, req in enumerate(self._active)
                  if req is not None and req.pages]
        if len(active) < 2:
            return  # one holder is not a hold-and-wait cycle
        _, lane = max(active)
        self._preempt_locked(lane)
        self.hbm_demotions += 1
        # the starvation streak stays up until a tick makes real
        # progress: admission is suppressed meanwhile (_admit_locked), so
        # the victim cannot re-admit and re-form the cycle before the
        # surviving holders finish

    def _hbm_maybe_grow(self, block: bool) -> bool:
        """Per-tick grow probe (scheduler thread, no locks held): when
        queued or starved requests want more pages than the pool holds,
        ask the arbiter for the bytes — the pressure protocol may evict
        a cold model to supply them.  ``block=True`` (every lane starved:
        nothing else to do) waits briefly for write-behind evictions to
        land; probes are free and retried next tick otherwise."""
        if self.hbm is None:
            return False
        with self._cv:
            if self._hbm_reclaim_bytes or self._pending_block is not None:
                return False  # being squeezed (or a block in flight)
            ps = self.page_size
            want = 0
            for req in self._queue[:self.lanes]:
                if req.kv_handle is not None:
                    want += req.kv_handle.n_pages + 1
                else:
                    t = len(req.pending_prompt) or (len(req.prompt)
                                                    + len(req.tokens_out))
                    want += (t + req.steps - len(req.tokens_out)
                             + ps - 1) // ps + 1
            for req in self._active:
                if req is None:
                    continue
                if req.pending_prompt:  # starved prefill / pending resume
                    want += max(0, (len(req.pending_prompt) + ps - 1) // ps
                                + 1 - len(req.pages))
                else:  # decoding: pages its remaining appends will write
                    need = (req.length + req.steps - len(req.tokens_out)
                            + ps - 1) // ps
                    want += max(0, need - len(req.pages))
            deficit = want - self.pool.free_pages
        if deficit <= 0:
            return False
        from tpulab.hbm import KV_TENANT
        pn = self._page_nbytes()
        # ask only for what the economy could plausibly supply (free
        # headroom + what pressure could evict) — an oversized request
        # would deny forever instead of growing incrementally — and snap
        # the new total onto the size ladder (bounded compiled shapes):
        # the smallest ladder size covering the demand we can afford,
        # else the largest affordable step toward it
        avail = (max(0, self.hbm.free_hbm_bytes)
                 + self.hbm.reclaimable_bytes(exclude=KV_TENANT))
        n = self.pool.n_pages
        affordable = n + avail // pn  # a rung may cost more than the
        #                               deficit — affordability is what
        #                               the economy could supply, period
        target = self._hbm_pool_base
        while target < n + deficit and target * 2 <= affordable:
            target *= 2
        pages = target - n
        if pages <= 0:
            return False  # static-budget degrade: queue on today's pool
        granted = self.hbm.request(
            KV_TENANT, ("pool", "grow"), pages * pn,
            timeout=self.HBM_GROW_TIMEOUT_S if block else 0.0,
            probe=not block)
        if not granted:
            return False
        with self._cv:
            if self._pending_block is None:
                self.pool.grow(pages)
                self.hbm_grows += 1
            # consolidate: fold the grant into the pool claim (mirror
            # first so the total never dips below the tracked bytes)
            self.hbm.mirror_claim(KV_TENANT, "pool", self.pool.hbm_bytes)
            self.hbm.release(KV_TENANT, ("pool", "grow"))
            self._cv.notify()
        return True

    def _admit_to_lane_locked(self, lane: int) -> bool:
        """Admit the queue head into a free lane (needs at least one page
        to start); False when the pool can't supply it."""
        page = self._alloc_page()
        if page is None:
            return False
        req = self._queue.pop(0)
        req.pages.append(page)
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        req.lane = lane
        self._active[lane] = req
        return True

    def _admit_locked(self) -> None:
        # elastic-regime hold-and-wait breaker (tpulab.hbm): while the
        # scheduler is in a starvation streak WITH live page-holders,
        # feed the pages freed by _hbm_break_hoard_locked to those
        # holders instead of re-admitting — the preempted victim
        # re-enters once decoding progresses.  With no holders at all
        # (e.g. right after an arbiter squeeze emptied every lane),
        # admission must proceed or nothing ever runs again.
        if not (self.hbm is not None and self._hbm_starved_passes >= 2
                and any(r is not None for r in self._active)):
            for lane in range(self.lanes):
                if self._active[lane] is None and self._queue:
                    if not self._admit_to_lane_locked(lane):
                        break
        # preemption: while the queue head strictly outranks the weakest
        # active request (rank = (class, priority): BATCH lanes are the
        # first victims — any online arrival evicts batch work before
        # touching another online lane; within a class the priority
        # tie-break stays most-recently-admitted falls first — least
        # progress lost), evict it and admit the head.  Zero-page lanes
        # (page-starved prefills) are skipped: evicting them frees
        # nothing and they already yield every tick.
        while self._queue:
            head = self._queue[0]
            head_rank = self._rank(head)
            # a victim only helps if releasing it can actually free a page:
            # skip lanes whose every page is prefix-cache-shared
            # (refcount > 1) — preempting them loses decode progress for
            # zero freed pages
            victims = [(self._rank(req) + (-req.admit_seq, lane))
                       for lane, req in enumerate(self._active)
                       if req is not None and self._rank(req) < head_rank
                       and any(self.pool.refcount(p) == 1
                               for p in req.pages)]
            if not victims:
                return
            lane = min(victims)[-1]
            self._preempt_locked(lane)
            if not self._admit_to_lane_locked(lane):
                # Defensive: the victim filter above requires at least one
                # refcount==1 page, so every preemption frees >=1 page and
                # a one-page admit succeeds under the current filter.  Kept
                # as a guard for future filter changes (e.g. admitting
                # multi-page heads) — the head retries next scheduling pass.
                return

    def _preempt_locked(self, lane: int) -> None:
        """Evict the lane's request: free its pages now, re-queue it for an
        exact-token resume (re-prefill of prompt+generated; no sampling
        PRNG draws are consumed on resume, so seeded sequences are
        unchanged by preemption).  With ``kv_offload`` the lane's live KV
        pages are first snapshotted device->host (async write-behind —
        only the gather dispatch happens here); the resume then swaps
        them back in with zero prefill dispatches, and the re-prefill
        below becomes the FALLBACK for a failed/dropped swap."""
        req = self._active[lane]
        self._fl_pages(req)
        if req.fl is not None:
            req.fl["preempts"] += 1
        # a mid-prompt ragged lane (length > 0 with chunks still pending)
        # is never snapshotted: its partial-prompt KV does not match the
        # resume length contract below — the resume re-prefills exactly
        if (self.kv_offload is not None and req.length > 0
                and not req.pending_prompt):
            t_sw0 = _time.perf_counter()
            needed = (req.length + self.page_size - 1) // self.page_size
            req.kv_handle = self.kv_offload.swap_out(
                req.pages[:needed], req.length, self.pool.kv)
            if req.kv_handle is not None:
                if req.fl is not None:
                    req.fl["swap_outs"] += 1
                self._span("swap_out", lane, t_sw0,
                           _time.perf_counter() - t_sw0, req,
                           pages=needed, tokens=req.length)
        self.pool.release_pages(req.pages)
        req.pages = []
        # the draft table is never snapshotted: it is cheap to regenerate
        # (one draft forward at resume), so its pages go home NOW and the
        # resume's warm-up rebuilds it exactly
        if req.draft_pages:
            self.pool.release_pages(req.draft_pages)
            req.draft_pages = []
        req.draft_len = 0
        if req.tokens_out:
            # feed everything but the last emitted token; the resume
            # prefill's logits are discarded (that pick already happened)
            req.pending_prompt = (list(req.prompt)
                                  + list(req.tokens_out[:-1]))
            req.resumed = True
        else:
            req.pending_prompt = list(req.prompt)
        req.length = 0
        req.pf_started = False   # ragged plan: the resume re-secures pages
        self._active[lane] = None
        self._enqueue_locked(req, front_of_class=True)
        self.preemptions += 1
        if req.batch:
            self.batch_preemptions += 1

    def _run(self) -> None:
        import jax.numpy as jnp
        st = self._stages
        while True:
            with stage(st, "admit"), self._cv:
                while (not self._shutdown and not self._queue
                       and not any(self._active)
                       and not self._hbm_reclaim_bytes):
                    with stage(st, "idle"):
                        self._cv.wait()
                if self._shutdown and not self._queue and not any(self._active):
                    self._profile_step(done=True)  # close an open capture
                    return
                # HBM arbiter pressure: serve an outstanding reclaim at
                # the tick boundary (no dispatched block is in flight
                # here — dispatch-ahead is suppressed while a reclaim is
                # pending, so in-flight decode pages are never victims)
                self._service_hbm_locked()
                # cancellation + deadline sweep: unconditional, so cancels
                # and expiries land even when no lane can make progress
                # (page-starved prefills).  Expired requests free their
                # lane and pages HERE — before the next step runs
                swept = []
                expired = []
                now = _time.monotonic()
                for lane, req in enumerate(self._active):
                    if req is None:
                        continue
                    if req.cancelled:
                        self._release_lane_locked(lane, req)
                        swept.append(req)
                    elif req.deadline is not None and now >= req.deadline:
                        self._release_lane_locked(lane, req)
                        expired.append(req)
                if self._queue:  # queued requests expire in place
                    still = []
                    for req in self._queue:
                        if (req.deadline is not None
                                and now >= req.deadline):
                            self._requests.pop(req.future, None)
                            self._discard_handle(req)
                            expired.append(req)
                        else:
                            still.append(req)
                    self._queue[:] = still
                self._admit_locked()
                snapshot = list(self._active)
            self._profile_step()  # debugz on-demand capture bookkeeping
            for req in swept:
                self._flight_complete(req, "CANCELLED")
                if not req.future.done():
                    req.future.cancel() or req.future.set_exception(
                        RuntimeError("generation cancelled"))
            for req in expired:
                if self.metrics is not None:
                    self.metrics.note_deadline_expired()
                self._flight_complete(req, "DEADLINE_EXCEEDED")
                if not req.future.done():
                    req.future.set_exception(DeadlineExceeded(
                        "generation deadline exceeded "
                        f"({len(req.tokens_out)}/{req.steps} tokens)"))
            try:
                prefilled = False
                if self.ragged:
                    # ragged dispatch plan: pending prompts and decode
                    # lanes advance together in ONE fused mixed round
                    prefilled = self._ragged_round(snapshot, jnp)
                else:
                    for lane, req in enumerate(snapshot):
                        if req is not None and req.pending_prompt:
                            prefilled |= self._do_prefill(req, jnp, lane)
                if prefilled:
                    # a steps==1 request can complete at prefill
                    done_reqs = []
                    with stage(st, "admit"), self._cv:
                        for lane, req in enumerate(self._active):
                            if (req is not None and not req.pending_prompt
                                    and req.finished()):
                                self._release_lane_locked(lane, req)
                                done_reqs.append(req)
                        self._admit_locked()
                        snapshot = list(self._active)
                    self._deliver((), done_reqs)
                progressed = self._tick(snapshot, jnp) or prefilled
                if self.hbm is not None:
                    # KV-burst side of the economy: queued/starved demand
                    # asks the arbiter for pool bytes (a cold model may be
                    # evicted to supply them); a cheap probe per tick,
                    # blocking only when every lane is starved anyway
                    self._hbm_maybe_grow(block=not progressed)
                if not progressed:
                    if self.hbm is not None:
                        # elastic-regime hold-and-wait breaker: lanes are
                        # sized for the GROWN pool, so a denied grow can
                        # strand N partial page-holders where the static
                        # world (lanes sized to the fixed pool) never
                        # could.  After two fully-starved passes with
                        # nothing free, preempt the newest lane (exact
                        # resume) so the eldest can finish — degraded
                        # throughput, never a livelock.
                        self._hbm_starved_passes += 1
                        if self._hbm_starved_passes >= 2:
                            with self._cv:
                                self._hbm_break_hoard_locked()
                    # every lane starved (pool pressure): back off instead
                    # of hot-spinning until pages free up
                    with stage(st, "idle"), self._cv:
                        self._cv.wait(timeout=0.01)
                else:
                    self._hbm_starved_passes = 0
            except Exception as e:  # noqa: BLE001 - fail active requests
                # a dispatched-ahead block died with the pool: its device
                # arrays and lane mapping are meaningless after recovery
                self._pending_block = None
                with self._cv:
                    for lane, req in enumerate(self._active):
                        if req is not None:
                            if not req.future.done():
                                self._flight_complete(req, "INTERNAL")
                                req.future.set_exception(e)
                            self._requests.pop(req.future, None)
                            self._active[lane] = None
                # donated pools may be gone after a failed step — rebuild
                if self.prefix_cache is not None:
                    self.prefix_cache.drop_all()  # entries died with the pool
                self.pool.reset()

    def _do_prefill(self, req: _PagedRequest, jnp, lane: int = 0) -> bool:
        """Fused prompt prefill: one compiled forward (per length bucket)
        fills the whole prompt's KV pages.  With a prefix cache, shared
        full-page prefixes are reused and only the tail runs (paged_extend);
        with ``prefill_chunk`` long tails run in page-aligned chunks.
        Returns False (retry later) when the pool can't yet supply the
        prompt's pages."""
        st = self._stages
        with stage(st, "plan"):
            if req.cancelled or req.length != 0:  # swept / already started
                return False
            t = len(req.pending_prompt)
            if req.kv_handle is not None:
                # recompute-free resume: swap the preemption snapshot back in
                # instead of re-prefilling.  True = restored (zero prefill
                # dispatches); False = page-starved (handle kept, retry next
                # pass); None = swap degraded (handle consumed, fall through
                # to the exact re-prefill below — today's path)
                swapped = self._try_swap_in(req, t, lane)
                if swapped is not None:
                    return swapped
            prompt = np.asarray(req.pending_prompt, np.int32)
            shared: List[int] = []
            digests: List[bytes] = []
            if self.prefix_cache is not None:
                shared, digests = self.prefix_cache.lookup(prompt,
                                                           self.page_size)
            # page layout: shared prefix pages first, then private pages (the
            # admission page + extras) for the tail/write region
            private = req.pages
            req.pages = shared + private
            needed = (t + self.page_size - 1) // self.page_size
            while len(req.pages) < needed:
                page = self._alloc_page()
                if page is None:
                    # page pressure: release partial holdings before
                    # retrying — two starved prefills must not hold-and-wait
                    # each other
                    self.pool.release_pages(req.pages)
                    req.pages = []
                    return False
                req.pages.append(page)
            start = len(shared) * self.page_size
        with stage(st, "dispatch"):
            tables = np.zeros((self.max_pages,), np.int32)
            tables[:len(req.pages)] = req.pages
            tables_j = jnp.asarray(tables)
            # pages secured: the queue wait ends HERE (first prefill only
            # — a preemption resume re-prefills but already left the queue
            # once)
            t_pf0 = _time.perf_counter()
            if req.t_prefill0 is None:
                req.t_prefill0 = t_pf0
                self._span("queue_wait", lane, req.t_submit,
                           t_pf0 - req.t_submit, req)
                self.queue_wait_s += t_pf0 - req.t_submit
                self.queue_waits += 1
                if self.metrics is not None:
                    self.metrics.observe_queue_wait(t_pf0 - req.t_submit)
            # chaos: prefill fault site — an error here rides the
            # scheduler's recovery path (fail actives + pool reset), a delay
            # is a slow prefill under deadline pressure
            chaos.trip("engine.prefill")
            self.prefill_dispatches += 1
            if start == 0 and (self.prefill_chunk is None
                               or t <= self.prefill_chunk):
                t_pad = 1 << (t - 1).bit_length()  # pow2: small jit cache
                tokens = np.zeros((1, t_pad), np.int32)
                tokens[0, :t] = prompt
                last_logits, self.pool.kv = self._prefill(
                    self.params, self.pool.kv, tables_j,
                    jnp.asarray(tokens), jnp.int32(t))
            else:
                # tail (and/or chunked) prefill against resident context
                chunk = self.prefill_chunk or (t - start)
                last_logits = None
                while start < t:
                    m = min(chunk, t - start)
                    m_pad = 1 << (m - 1).bit_length()
                    tokens = np.zeros((1, m_pad), np.int32)
                    tokens[0, :m] = prompt[start:start + m]
                    last_logits, self.pool.kv = self._extend(
                        self.params, self.pool.kv, tables_j,
                        jnp.asarray(tokens), jnp.int32(start),
                        jnp.int32(start + m))
                    start += m
        with stage(st, "commit"):
            req.length = t
            req.pending_prompt = []
            self._fl_pages(req)
            was_resumed = req.resumed
            if was_resumed:
                # preemption resume: the fed tail ends at tokens_out[-2];
                # the last emitted token was picked before eviction —
                # discard these logits, consume no PRNG state, just
                # continue decoding
                req.resumed = False
            else:
                sp = req.sampling
                with stage(st, "fetch"):
                    if sp.device and sp.temperature > 0.0:
                        # first token rides the SAME (seed, position) stream
                        # as the decode ticks (position t-1 = the last
                        # prompt token's query; decode ticks start at
                        # position t) — one request is one reproducible
                        # stream end to end.  The prefill logits row is
                        # fetched once per request; per-TICK logits are
                        # never fetched for device-sampled lanes.
                        import jax.numpy as _j
                        tok = int(np.asarray(_device_sample_token(
                            _j.asarray(last_logits, _j.float32),
                            _j.float32(sp.temperature),
                            _j.asarray([sp.seed & 0xFFFFFFFF,
                                        (sp.seed >> 32) & 0xFFFFFFFF],
                                       _j.uint32),
                            _j.int32(t - 1))))
                    else:
                        tok = sp.pick(np.asarray(last_logits))
                    lp = None
                    if req.want_logprobs:
                        # same f32 device log_softmax as paged_decode_step:
                        # one request's logprob stream is one precision end
                        # to end
                        import jax as _jax
                        import jax.numpy as _j
                        lp = float(np.asarray(_jax.nn.log_softmax(
                            _j.asarray(last_logits, _j.float32))[tok]))
                req.tokens_out.append(tok)
                self.tokens_generated += 1
                if req.want_logprobs:
                    req.logprobs_out.append(lp)
                with stage(st, "emit"):
                    self._emit(req, tok, 0, lp)
            # prefill span closes after the first-token pick (the pick's
            # logits fetch is the fence that makes the device time real);
            # decode chunks start from here
            t_pf1 = _time.perf_counter()
            self._span("prefill", lane, t_pf0, t_pf1 - t_pf0, req,
                       prompt_tokens=t, cached_pages=len(shared))
            req.chunk_t0 = t_pf1
            req.chunk_start = len(req.tokens_out)
            if not was_resumed:
                req.t_first = t_pf1
                req.t_last = t_pf1
                self.ttft_s += t_pf1 - req.t_submit
                self.ttfts += 1
                if self.metrics is not None:
                    self.metrics.observe_ttft(t_pf1 - req.t_submit)
            if self.prefix_cache is not None and not was_resumed:
                # count each logical request once (resume prefills re-walk
                # already-counted pages) and publish only first-prefill
                # pages: full prompt pages are immutable from here on
                # (decode writes at positions >= t), while a resume's tail
                # pages hold generated tokens unique to this request — not
                # worth caching
                self.prefix_cache.count_lookup(len(shared), len(digests))
                self.prefix_cache.insert(digests, req.pages[:len(digests)])
            dt = t_pf1 - t_pf0
            if dt > 0:
                # rolling prefill throughput — the fabric cost gate's
                # recompute-time estimate (see kv_publish in __init__)
                inst = t / dt
                self.prefill_ewma_tok_s = (
                    inst if self.prefill_ewma_tok_s == 0.0
                    else 0.7 * self.prefill_ewma_tok_s + 0.3 * inst)
            if (self.kv_publish and not was_resumed
                    and req.export_digest is None):
                self._fab_publish(req, prompt, t, last_logits)
        return True

    #: published fabric snapshots kept addressable (digest -> handle);
    #: beyond this the oldest export is forgotten — its store entries
    #: removed — so the fabric can never squat the whole host tier
    FAB_PUBLISH_CAP = 32

    def _fab_publish(self, req: _PagedRequest, prompt: np.ndarray, t: int,
                     last_logits) -> None:
        """Export a finished first prefill to the fleet KV fabric
        (tpulab.kvfabric): the prompt's pages snapshot to the host tier
        under ``("fab", digest)`` through the same write-behind swap_out
        the preemption path uses (gather dispatched HERE, before any
        decode write into the tail page, so dispatch ordering makes the
        snapshot prompt-only), and the last-position logits row lands
        beside it under ``("fablog", digest)`` so a fetcher picks the
        first token under its OWN sampling seed.  Best-effort end to
        end: a degraded swap, a budget-refused put or a mid-flight
        eviction all surface as an honest FetchKV NOT_FOUND — never a
        wrong answer."""
        from tpulab.disagg.wire import prompt_digest
        digest = prompt_digest(prompt)
        with self._fab_lock:
            if digest in self._fab_handles:
                self._fab_handles.move_to_end(digest)
                return
        n_pages = (t + self.page_size - 1) // self.page_size
        handle = self.kv_offload.swap_out(
            req.pages[:n_pages], t, self.pool.kv, key=("fab", digest))
        if handle is None:
            return
        if not self.kv_offload.store.put(
                ("fablog", digest),
                np.asarray(last_logits, np.float32).reshape(-1)):
            self.kv_offload.discard(handle)
            return
        self.kv_publishes += 1
        with self._fab_lock:
            self._fab_handles[digest] = handle
            self._fab_handles.move_to_end(digest)
            while len(self._fab_handles) > self.FAB_PUBLISH_CAP:
                old_dig, old_h = self._fab_handles.popitem(last=False)
                self.kv_offload.discard(old_h)
                self.kv_offload.store.remove(("fablog", old_dig))

    def fab_handle(self, digest: bytes):
        """The published fabric snapshot for ``digest`` (a resident or
        still-in-flight :class:`~tpulab.kvcache.offload.SwapHandle`), or
        None — the FetchKV server's lookup.  Thread-safe: the RPC thread
        reads while the scheduler publishes/evicts.  A hit bumps the
        publish-registry LRU (fabric-popular digests stay addressable)
        WITHOUT touching the host store's own recency — the store read
        goes through ``peek``."""
        with self._fab_lock:
            h = self._fab_handles.get(digest)
            if h is not None:
                self._fab_handles.move_to_end(digest)
            return h

    def _try_swap_in(self, req: _PagedRequest, t: int,
                     lane: int) -> Optional[bool]:
        """Restore a preempted lane's host-tier KV snapshot into freshly
        allocated pages (see _do_prefill for the tri-state contract).
        ``t`` is the resume length — by construction equal to the
        snapshot's covered positions (prompt + generated - 1)."""
        handle = req.kv_handle
        needed = handle.n_pages
        while len(req.pages) < needed:
            page = self._alloc_page()
            if page is None:
                # page pressure: release partial holdings (no hold-and-
                # wait), KEEP the handle — the snapshot outlives retries
                self.pool.release_pages(req.pages)
                req.pages = []
                return False
            req.pages.append(page)
        t0 = _time.perf_counter()
        new_kv = self.kv_offload.restore(handle, req.pages[:needed],
                                         self.pool.kv)
        req.kv_handle = None
        if new_kv is None:
            # degraded swap: hand the pages back and run the normal
            # re-prefill (which re-does prefix lookup and its own page
            # accounting from a clean slate)
            self.pool.release_pages(req.pages)
            req.pages = []
            return None
        self.pool.kv = new_kv
        req.length = t
        req.pending_prompt = []
        req.resumed = False  # the first-token pick happened pre-preemption
        now = _time.perf_counter()
        if req.fl is not None:
            req.fl["swap_ins"] += 1
        self._fl_pages(req)
        self._span("swap_in", lane, t0, now - t0, req,
                   pages=needed, tokens=t)
        req.chunk_t0 = now        # decode chunks restart here
        req.chunk_start = len(req.tokens_out)
        return True

    # -- ragged dispatch plan (mixed prefill+decode rounds) ------------------
    #: max prefill tokens one mixed round carries IN TOTAL (the ceiling
    #: of the pow2 bucket the mixed program is keyed by; ``prefill_chunk``
    #: lowers it): lanes that prefill at once share it, longer prompts
    #: take multiple rounds, decode lanes never stall behind them
    RAGGED_CHUNK_CAP = 256

    @property
    def _round_budget(self) -> int:
        """Prefill tokens one mixed round may carry, all lanes together
        (the token budget of chunked prefill)."""
        return min(self.prefill_chunk or self.RAGGED_CHUNK_CAP,
                   self.RAGGED_CHUNK_CAP)

    def _ragged_prefill_start(self, req: _PagedRequest, lane: int) -> bool:
        """Host half of a prefill under the ragged plan: prefix-cache
        lookup + secure EVERY page the full prompt needs (all-or-nothing,
        the legacy _do_prefill contract — two starved prefills must not
        hold-and-wait each other), then mark the lane chunk-ready.
        True = segments may build; False = page-starved (retry later)."""
        prompt = np.asarray(req.pending_prompt, np.int32)
        t = len(prompt)
        shared: List[int] = []
        digests: List[bytes] = []
        if self.prefix_cache is not None:
            shared, digests = self.prefix_cache.lookup(prompt,
                                                       self.page_size)
        private = req.pages
        req.pages = shared + private
        needed = (t + self.page_size - 1) // self.page_size
        while len(req.pages) < needed:
            page = self._alloc_page()
            if page is None:
                self.pool.release_pages(req.pages)
                req.pages = []
                return False
            req.pages.append(page)
        # shared prefix positions are already resident: chunks cover
        # only the tail (the last prompt token is never served shared)
        req.pf_digests = digests
        req.pf_shared = len(shared)
        req.length = len(shared) * self.page_size
        del req.pending_prompt[:req.length]
        req.pf_started = True
        req.pf_t0 = _time.perf_counter()
        if req.t_prefill0 is None:
            req.t_prefill0 = req.pf_t0
            self._span("queue_wait", lane, req.t_submit,
                       req.pf_t0 - req.t_submit, req)
            self.queue_wait_s += req.pf_t0 - req.t_submit
            self.queue_waits += 1
            if self.metrics is not None:
                self.metrics.observe_queue_wait(req.pf_t0 - req.t_submit)
        # chaos: same prefill fault site + semantics as _do_prefill (one
        # trip per prefill start, errors ride the scheduler's recovery)
        chaos.trip("engine.prefill")
        return True

    def _ragged_round(self, snapshot, jnp) -> bool:
        """One fused ragged mixed round (the unified dispatch plan):
        prefilling lanes advance by a prompt chunk and — with no
        dispatched-ahead block in flight — every decoding lane advances
        by one token, all through ONE ``paged_mixed_step`` dispatch over
        per-lane ``(q_len, kv_len)`` segments, packed by token.  The
        round's prompt tokens never exceed ``_round_budget`` in total:
        lanes that prefill at once share it, the oldest admission first;
        what is left of a chunk, or a lane the budget did not reach,
        waits a round (the oldest lane always advances, so none starves).
        The program is keyed by :func:`round_width` of the tokens carried,
        so the budget also bounds the programs: nine, each reached by a
        single prompt.  Lanes finishing their prompt emit their first
        token from the same dispatch (no separate prefill program, no
        per-lane logits fetch).  With no pending prompts this is a no-op
        and the K-block decode path owns the tick.  Returns True when any
        lane made progress."""
        st = self._stages
        with stage(st, "plan"):
            progressed = False
            segs: List = []                     # (lane, req)
            for lane, req in enumerate(snapshot):
                if req is None or not req.pending_prompt or req.cancelled:
                    continue
                if req.kv_handle is not None:
                    swapped = self._try_swap_in(req, len(req.pending_prompt),
                                                lane)
                    if swapped is True:
                        progressed = True
                        continue
                    if swapped is False:
                        continue         # page-starved: snapshot kept
                if not req.pf_started and not self._ragged_prefill_start(
                        req, lane):
                    continue             # page-starved: retry next pass
                segs.append((lane, req))
            if not segs:
                return progressed
            # decode lanes join the round only when no dispatched-ahead
            # block is in flight (its device carry covers those lanes)
            decode_parts: List = []
            if self._pending_block is None:
                for lane, req in enumerate(snapshot):
                    if (req is None or req.pending_prompt or req.cancelled
                            or not req.tokens_out):
                        continue
                    need = req.length // self.page_size + 1
                    new: List[int] = []
                    while len(req.pages) < need:
                        page = self._alloc_page()
                        if page is None:
                            break
                        req.pages.append(page)
                        new.append(page)
                    if len(req.pages) < need:
                        for _ in new:    # starved: return the partial take
                            self.pool.release_pages([req.pages.pop()])
                        continue
                    decode_parts.append((lane, req))
        with stage(st, "dispatch"):
            left = self._round_budget
            chunks: Dict[int, int] = {}
            for lane, req in sorted(segs, key=lambda s: s[1].admit_seq):
                chunks[lane] = min(len(req.pending_prompt), left)
                left -= chunks[lane]
            segs = [(lane, req) for lane, req in segs if chunks[lane]]
            b = self.lanes
            toks, row_lane, row_off, q_lens = pack_round(
                b, {lane: req.pending_prompt[:chunks[lane]]
                    for lane, req in segs},
                {lane: req.tokens_out[-1] for lane, req in decode_parts})
            tables = np.zeros((b, self.max_pages), np.int32)
            kv_lens = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            seeds = np.zeros((b, 2), np.uint32)
            host_lanes: List[int] = []
            lane_reqs: Dict[int, _PagedRequest] = {}
            for lane, req in segs + decode_parts:
                lane_reqs[lane] = req
                kv_lens[lane] = req.length + q_lens[lane]
                tables[lane, :len(req.pages)] = req.pages
                sp = req.sampling
                # a prompt's pick counts only off its final chunk, where
                # it IS the first token (a resumed request made it before)
                if sp.temperature > 0.0 and (not req.pending_prompt or (
                        q_lens[lane] == len(req.pending_prompt)
                        and not req.resumed)):
                    if sp.device:
                        temps[lane] = sp.temperature
                        seeds[lane] = (sp.seed & 0xFFFFFFFF,
                                       (sp.seed >> 32) & 0xFFFFFFFF)
                    else:
                        host_lanes.append(lane)
            if decode_parts:
                # decode lanes advance one tick this round — same fault site
                chaos.trip("engine.step")
            t0 = _time.perf_counter()
            nt_dev, lp_dev, last_dev, self.pool.kv, *moe = self._mixed(
                self.params, self.pool.kv, jnp.asarray(tables),
                jnp.asarray(toks), jnp.asarray(row_lane),
                jnp.asarray(row_off), jnp.asarray(q_lens),
                jnp.asarray(kv_lens), jnp.asarray(temps),
                jnp.asarray(seeds))
            self.decode_dispatches += 1
            self._note_dispatch("mixed")
            self.mixed_rows += len(toks)
            self.mixed_tokens += int(q_lens.sum())
        with stage(st, "fetch"):
            next_tokens = np.asarray(nt_dev, np.int32).copy()
            logprobs_arr = np.asarray(lp_dev, np.float32).copy()
            self.decode_host_syncs += 1
            self._note_moe(moe, decode=False)
            if host_lanes:
                # fetch ONLY the host-sampled rows (same shape discipline —
                # and PRNG rule — as _tick_single)
                rows = np.asarray(
                    last_dev[jnp.asarray(np.asarray(host_lanes, np.int32))])
                self.decode_host_syncs += 1
                for i, lane in enumerate(host_lanes):
                    req = lane_reqs[lane]
                    next_tokens[lane] = req.sampling.pick(rows[i])
                    if req.want_logprobs:
                        row = rows[i].astype(np.float32)
                        row = row - row.max()
                        logprobs_arr[lane] = float(
                            row[next_tokens[lane]]
                            - np.log(np.exp(row).sum()))
        now = _time.perf_counter()
        self._step_ewma_s = (0.8 * self._step_ewma_s + 0.2 * (now - t0)
                             if self._step_ewma_s else now - t0)
        emits: List = []
        completed: List = []
        with stage(st, "commit"), self._cv:
            for lane, req in segs:
                if self._active[lane] is not req or req.cancelled:
                    continue
                c = chunks[lane]
                req.length += c
                del req.pending_prompt[:c]
                self._fl_pages(req)
                progressed = True
                if req.pending_prompt:
                    continue         # mid-prompt: nothing emitted yet
                t_total = req.length
                was_resumed = req.resumed
                if was_resumed:
                    # the pick already happened before preemption/on the
                    # prefill replica: discard this round's (stateless)
                    # sample, just continue decoding
                    req.resumed = False
                else:
                    tok = int(next_tokens[lane])
                    req.tokens_out.append(tok)
                    self.tokens_generated += 1
                    lp = None
                    if req.want_logprobs:
                        lp = float(logprobs_arr[lane])
                        req.logprobs_out.append(lp)
                    emits.append((req, tok, len(req.tokens_out) - 1, lp))
                self._span("prefill", lane, req.pf_t0, now - req.pf_t0,
                           req, prompt_tokens=t_total,
                           cached_pages=req.pf_shared)
                req.chunk_t0 = now
                req.chunk_start = len(req.tokens_out)
                if not was_resumed:
                    req.t_first = now
                    req.t_last = now
                    self.ttft_s += now - req.t_submit
                    self.ttfts += 1
                    if self.metrics is not None:
                        self.metrics.observe_ttft(now - req.t_submit)
                if self.prefix_cache is not None and not was_resumed:
                    self.prefix_cache.count_lookup(req.pf_shared,
                                                   len(req.pf_digests))
                    self.prefix_cache.insert(
                        req.pf_digests, req.pages[:len(req.pf_digests)])
                req.pf_started = False
            for lane, req in decode_parts:
                if self._active[lane] is not req or req.cancelled:
                    continue
                self._probe_countdown_locked(req)
                self._note_second_token(req, now)
                req.length += 1
                tok = int(next_tokens[lane])
                req.tokens_out.append(tok)
                self.tokens_generated += 1
                progressed = True
                dt = (now - req.t_last) if req.t_last is not None else None
                if self.metrics is not None and dt is not None:
                    self.metrics.observe_itl(dt)
                self._fl_block(req, 1, 1, dt)
                req.t_last = now
                lp = None
                if req.want_logprobs:
                    lp = float(logprobs_arr[lane])
                    req.logprobs_out.append(lp)
                emits.append((req, tok, len(req.tokens_out) - 1, lp))
                done = req.finished()
                if (done or len(req.tokens_out) - req.chunk_start
                        >= self.TRACE_DECODE_CHUNK):
                    self._flush_decode_chunk(req, lane, now)
                if done:
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        self._deliver(emits, completed)
        return progressed or bool(segs)

    def _discard_handle(self, req: _PagedRequest) -> None:
        """Drop a never-to-be-restored snapshot (cancel/expiry while
        queued) so it stops holding host-tier budget."""
        if req.kv_handle is not None:
            if self.kv_offload is not None:
                self.kv_offload.discard(req.kv_handle)
            req.kv_handle = None

    @staticmethod
    def _emit(req: _PagedRequest, token: int, index: int,
              logprob: Optional[float] = None) -> None:
        """Explicit hook contract: ``on_token(tok, i)`` normally;
        ``on_token(tok, i, logprob)`` iff the request asked for
        ``logprobs=True`` (no signature sniffing — a 3-arg call on a
        2-arg hook with ``logprobs=True`` is a caller bug and raises)."""
        if req.on_token is not None:
            try:
                if req.want_logprobs:
                    req.on_token(token, index, logprob)
                else:
                    req.on_token(token, index)
            except Exception:  # pragma: no cover - consumer hook
                import logging
                logging.getLogger("tpulab.engine").exception(
                    "on_token hook failed")

    def _deliver(self, emits, completed) -> None:
        """User callbacks and future resolution, OUTSIDE the scheduler
        lock: a slow consumer must not head-of-line-block other lanes."""
        with stage(self._stages, "emit"):
            for req, tok, i, lp in emits:
                self._emit(req, tok, i, lp)
            for req in completed:
                if not req.future.done():
                    self._flight_complete(req)
                    req.future.set_result(self._result_of(req))
                    self.completed_requests += 1
                    self._note_complete(req)

    def _note_second_token(self, req: _PagedRequest, now: float) -> None:
        """Called as a request's second token is committed: its wait
        since the first is what a newly admitted lane waits for the
        running chain (dispatch-ahead) before it gets a decode step."""
        if len(req.tokens_out) == 1 and req.t_first is not None:
            self.first_decode_wait_s += now - req.t_first
            self.first_decode_waits += 1

    def _note_moe(self, moe, decode: bool) -> None:
        """Add a dispatch's expert counters (``[(n_moe, E + 2)]`` from the
        step program, or ``[]`` for a model without expert layers) to the
        totals.  Called where the dispatch's tokens were just fetched: the
        array is ready with them, so this is no further wait.  Mixed
        rounds count assignments only; decode dispatches also the steps
        that had a live lane and the experts those steps hit."""
        if not moe:
            return
        stats = np.asarray(moe[0], np.int64)
        n = self._moe_assignments.shape[1]
        self._moe_assignments += stats[:, :n]
        if decode:
            self.moe_experts_hit += int(stats[:, n].sum())
            self.moe_decode_steps += int(stats[0, n + 1])

    def _note_dispatch(self, kind: str) -> None:
        """Dispatch-kind accounting (the ragged plan's three descriptor
        kinds); ``ragged_dispatches`` counts the ragged kernel family —
        every mixed round, plus decode/verify dispatches whose attention
        ran the pallas ragged kernel."""
        self.dispatch_kinds[kind] += 1
        if kind == "mixed" or self.use_kernel:
            self.ragged_dispatches += 1

    # -- fused decode dispatch ----------------------------------------------
    def _block_fn(self, k: int):
        """Jitted K-step fused decode (compiled once per block size)."""
        fn = self._block_cache.get(k)
        if fn is None:
            rep, kvsh = self._rep, self.pool.kv_sharding
            fn = self._jit(partial(paged_decode_block, k=k, **self._step_kw),
                           (1,), (self._param_sh, kvsh) + (rep,) * 8,
                           (rep,) * 7 + (kvsh,))
            self._block_cache[k] = fn
        return fn

    def _tight_slack_s(self) -> float:
        """Deadline slack below which a lane counts as *tight* (adaptive K
        drops to <=2): roughly two max-size blocks of measured decode
        time, clamped to a sane band while the EWMA warms up."""
        est = self._step_ewma_s or 0.005
        return min(1.0, max(0.05, 2.0 * self.decode_block * est))

    def _pick_block_k(self, decode_lanes) -> int:
        """Adaptive fused-decode block size for this dispatch.

        - any host-sampled (``top_k``/``top_p``) lane -> 1: its per-token
          pick needs the logits row on host every tick;
        - any deadline-tight lane -> <=2: the sweep acts at block
          boundaries, so a big block would overshoot the deadline;
        - a streaming consumer with NO queue pressure -> <=2: keep ITL
          smooth when latency is what the caller is buying;
        - otherwise (throughput pressure, batch-style ``.result()``
          consumers) the full ``decode_block`` ceiling;
        - never longer than the largest remaining step budget needs
          (covering it with one block instead of trailing short blocks).
        """
        kmax = self.decode_block
        if kmax <= 1:
            return 1
        now = _time.monotonic()
        want = kmax
        streaming = False
        max_rem = 1
        for _lane, req in decode_lanes:
            sp = req.sampling
            if sp.temperature > 0.0 and not sp.device:
                return 1
            if (req.deadline is not None
                    and req.deadline - now < self._tight_slack_s()):
                want = min(want, 2)
            if req.on_token is not None and not req.batch:
                # batch lanes run throughput-optimized: their on_token
                # hook is a durable checkpoint sink, not an interactive
                # consumer — never let it drag the whole block to K<=2
                streaming = True
            max_rem = max(max_rem, req.steps - len(req.tokens_out))
        if streaming and not self._queue:
            want = min(want, 2)
        cover = next((m for m in self.BLOCK_K_MENU if m >= max_rem),
                     self.BLOCK_K_MENU[-1])
        k = min(want, cover)
        return max(m for m in self.BLOCK_K_MENU if m <= k)

    def _reserve_block_pages(self, decode_lanes, k: int):
        """Pre-allocate every page the next K appends will write, per lane.

        Decode step j writes K/V at position ``length + j`` — the device
        cannot allocate, so the block table must cover the whole block
        BEFORE dispatch.  Appends land at positions >= the prompt length,
        which always sit in the lane's private pages (the prefix cache
        only ever shares FULL prompt pages strictly below the write
        region), so pre-allocation can never hand the block a shared
        page to write.  Under pool pressure the block shrinks to what
        every participating lane can cover (snapped down onto
        BLOCK_K_MENU, surplus pages returned); a lane that cannot cover
        even one append skips this block entirely (same as the old
        per-tick starvation skip).  Returns ``(k_eff, [(lane, req,
        new_pages), ...])``.
        """
        parts = []
        cap = k
        for lane, req in decode_lanes:
            appends_want = max(1, min(k, req.steps - len(req.tokens_out)))
            need = (req.length + appends_want - 1) // self.page_size + 1
            new: List[int] = []
            while len(req.pages) < need:
                page = self._alloc_page()
                if page is None:
                    break
                req.pages.append(page)
                new.append(page)
            covered = len(req.pages) * self.page_size - req.length
            appends = min(appends_want, covered)
            if appends <= 0:
                for _ in new:  # starved: return the partial take
                    self.pool.release_pages([req.pages.pop()])
                continue
            if appends < appends_want:
                cap = min(cap, appends)
            parts.append((lane, req, new))
        if not parts:
            return k, []
        k_eff = max(m for m in self.BLOCK_K_MENU if m <= max(1, cap))
        if k_eff < k:
            # shrunk block: give back pages past the new write horizon
            for _lane, req, new in parts:
                appends_eff = max(1, min(k_eff,
                                         req.steps - len(req.tokens_out)))
                need = (req.length + appends_eff - 1) // self.page_size + 1
                while len(req.pages) > need and new:
                    self.pool.release_pages([req.pages.pop()])
                    new.pop()
        return k_eff, parts

    def _spec_eligible(self, req: _PagedRequest) -> bool:
        """May this lane ride a speculative dispatch?  Host-sampled
        (``top_k``/``top_p``/host-PRNG temperature) lanes never enter the
        speculative path — their picks need the logits row on host every
        token; degraded lanes (chaos verify trip, acceptance EWMA under
        the floor) stay plain for the rest of the request."""
        sp = req.sampling
        if sp.temperature > 0.0 and not sp.device:
            return False
        return req.spec_enabled

    def _degrade_spec(self, req: _PagedRequest,
                      probe: bool = False) -> None:
        """Drop the lane to plain decode blocks; its draft-table pages go
        straight back to the pool.  ``probe=True`` (the acceptance-EWMA
        path) schedules a periodic re-try: after ``SPEC_PROBE_INTERVAL``
        plain dispatches the lane runs ONE speculative probe block and
        recovers if acceptance came back — a transient degrade (an
        out-of-distribution stretch, a cold stretch after resume) stops
        being forever.  ``probe=False`` (chaos verify trips) stays plain
        for the rest of the request, as before."""
        if req.spec_enabled:
            req.spec_enabled = False
            self.spec_fallbacks += 1
        req.spec_probe_in = self.SPEC_PROBE_INTERVAL if probe else None
        req.spec_probing = False
        if req.draft_pages:
            self.pool.release_pages(req.draft_pages)
            req.draft_pages = []
        req.draft_len = 0

    def _probe_countdown_locked(self, req: _PagedRequest) -> None:
        """One plain dispatch elapsed for a transiently degraded lane.
        When the countdown hits zero the lane re-enters speculation as a
        PROBE: its EWMA is reset to the floor so the probe block's own
        acceptance decides — >= floor recovers the lane, < floor
        re-degrades and re-schedules the next probe."""
        if (self._spec is None or req.spec_enabled
                or req.spec_probe_in is None):
            return
        req.spec_probe_in -= 1
        if req.spec_probe_in > 0:
            return
        req.spec_probe_in = None
        req.spec_enabled = True
        req.spec_probing = True
        req.spec_ewma = self.spec_accept_floor
        self.spec_probes += 1

    def _reserve_spec_pages(self, decode_lanes, k: int):
        """Target + draft page reservation for one speculative block.

        A spec block writes ``k + 1`` positions (``lengths .. lengths+k``)
        on BOTH tables and emits up to ``k + 1`` accepted tokens.  Target
        pages are reserved FIRST (the plain fallback needs them
        regardless); under pool pressure the DRAFT table's shortfall
        shrinks the block k — it never steals or releases target pages.
        Pages past the (possibly shrunk) write horizon go straight back
        to the pool.  Returns ``(kd, parts)`` with ``parts`` entries
        ``(lane, req, new_target_pages, new_draft_pages)``; ``kd == 0``
        means the pool cannot support speculation this dispatch — the
        caller falls back to the plain path (surviving target
        reservations stay on the lanes for it, draft takes are
        returned)."""
        parts = []
        cap = k + 1                   # min covered appends across lanes
        for lane, req in decode_lanes:
            rem = req.steps - len(req.tokens_out)
            want = max(1, min(k + 1, rem))
            need = (req.length + want - 1) // self.page_size + 1
            new_t: List[int] = []
            while len(req.pages) < need:
                page = self._alloc_page()
                if page is None:
                    break
                req.pages.append(page)
                new_t.append(page)
            cov_t = len(req.pages) * self.page_size - req.length
            if cov_t <= 0:
                for _ in new_t:   # starved: return the partial take
                    self.pool.release_pages([req.pages.pop()])
                continue
            new_d: List[int] = []
            while len(req.draft_pages) < need:
                page = self._alloc_page()
                if page is None:
                    break
                req.draft_pages.append(page)
                new_d.append(page)
            cov_d = len(req.draft_pages) * self.page_size - req.length
            # only a COVERAGE shortfall shrinks the block: a lane whose
            # step budget is smaller than the block is handled by the
            # device-side steps-remaining mask (writes past the budget
            # route to scratch), exactly like plain blocks
            if cov_t < want:
                cap = min(cap, cov_t)
            if cov_d < want:
                cap = min(cap, cov_d)
            parts.append((lane, req, new_t, new_d))
        if not parts or cap < 2:
            # cannot cover even one proposal + its verify write: hand the
            # draft takes back; target reservations stay for plain blocks
            for _lane, req, _new_t, new_d in parts:
                for _ in new_d:
                    self.pool.release_pages([req.draft_pages.pop()])
            return 0, []
        kd = max(m for m in self.BLOCK_K_MENU if m <= cap - 1)
        for _lane, req, new_t, new_d in parts:
            rem = req.steps - len(req.tokens_out)
            want = max(1, min(kd + 1, rem))
            need = (req.length + want - 1) // self.page_size + 1
            while len(req.pages) > need and new_t:
                self.pool.release_pages([req.pages.pop()])
                new_t.pop()
            while len(req.draft_pages) > need and new_d:
                self.pool.release_pages([req.draft_pages.pop()])
                new_d.pop()
        return kd, parts

    def _plan_decode(self, snapshot):
        """Pick this dispatch's lanes, mode (speculative vs plain), block
        size, and page reservations.  The dispatch is speculative iff a
        draft model is armed and EVERY participating lane is eligible
        (one fused program serves the whole batch); otherwise — or when
        pool pressure cannot cover the draft tables — it is a plain
        block, which is the adaptive fallback the menu pick feeds."""
        decode_lanes = [(lane, req) for lane, req in enumerate(snapshot)
                        if req is not None and not req.cancelled
                        and not req.pending_prompt and req.tokens_out]
        if not decode_lanes:
            return None
        k = self._pick_block_k(decode_lanes)
        if (self._spec is not None
                and all(self._spec_eligible(r) for _, r in decode_lanes)):
            kd, parts = self._reserve_spec_pages(decode_lanes, k)
            if kd >= 1 and parts:
                return {"k": kd, "parts": parts, "mode": "spec"}
        k, parts = self._reserve_block_pages(decode_lanes, k)
        if not parts and any(req.draft_pages for _, req in decode_lanes):
            # every lane page-starved while draft tables hoard pages: the
            # draft KV is always regenerable, so treat pool pressure as a
            # TRANSIENT degrade — release the draft tables (arming the
            # probe countdown) and retry plain; without this the pool can
            # deadlock with target+draft tables holding every page
            for _lane, req in decode_lanes:
                if req.draft_pages:
                    self._degrade_spec(req, probe=True)
            k, parts = self._reserve_block_pages(
                decode_lanes, self._pick_block_k(decode_lanes))
        if not parts:
            return None  # every lane page-starved: caller backs off
        return {"k": k, "parts": parts, "mode": "plain"}

    def _tick(self, snapshot, jnp) -> bool:
        """One scheduler decode pass: consume the dispatched-ahead block
        if one is in flight, else plan + dispatch + consume.  Returns True
        when any lane made progress, False when every decode lane is
        starved (pool pressure) or idle."""
        st = self._stages
        if self._pending_block is not None:
            stash, self._pending_block = self._pending_block, None
            return self._consume_block(stash, jnp)
        with stage(st, "plan"):
            plan = self._plan_decode(snapshot)
        if plan is None:
            return False
        if plan["mode"] == "spec":
            with stage(st, "dispatch"):
                stash = self._dispatch_spec_block(plan["parts"], plan["k"],
                                                  jnp)
            if stash is not None:
                return self._consume_spec_block(stash, jnp)
            # verify trip (chaos) pre-dispatch: the lanes just degraded to
            # plain — re-plan this tick as a plain block (their target
            # reservations are already in place)
            with stage(st, "plan"):
                lanes = [(lane, req)
                         for lane, req, _nt, _nd in plan["parts"]]
                k, parts = self._reserve_block_pages(
                    lanes, self._pick_block_k(lanes))
            if not parts:
                return False
            plan = {"k": k, "parts": parts, "mode": "plain"}
        if plan["k"] == 1:
            return self._tick_single(plan["parts"], jnp)
        with stage(st, "dispatch"):
            stash = self._dispatch_block(plan["parts"], plan["k"], jnp)
        return self._consume_block(stash, jnp)

    def _dispatch_block(self, parts, k: int, jnp, carry=None,
                        host=None):
        """Issue one K-step fused decode dispatch (async — no host sync).

        ``carry``/``host`` chain a follow-up block from a previous one's
        device-resident final state (dispatch-ahead overlap) — the block
        table is rebuilt host-side either way (new pages may have been
        reserved), but lengths/tokens/live/steps-remaining stay on device
        so chaining costs no round trip.
        """
        b = self.lanes
        tables = np.zeros((b, self.max_pages), np.int32)
        lane_reqs = {}
        for lane, req, _new in parts:
            lane_reqs[lane] = req
            tables[lane, :len(req.pages)] = req.pages
        if host is None:
            lengths = np.zeros((b,), np.int32)
            tokens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            temps = np.zeros((b,), np.float32)
            seeds = np.zeros((b, 2), np.uint32)   # (lo, hi) words
            rem = np.zeros((b,), np.int32)
            n_stop = max((len(r.stop_tokens) for _, r, _ in parts),
                         default=0)
            width = (1 << (n_stop - 1).bit_length()) if n_stop > 1 else 1
            stops = np.full((b, width), -1, np.int32)  # ids >= 0: pad safe
            for lane, req, _new in parts:
                lengths[lane] = req.length
                tokens[lane] = req.tokens_out[-1]
                active[lane] = True
                rem[lane] = req.steps - len(req.tokens_out)
                sp = req.sampling
                if sp.device and sp.temperature > 0.0:
                    temps[lane] = sp.temperature
                    seeds[lane] = (sp.seed & 0xFFFFFFFF,
                                   (sp.seed >> 32) & 0xFFFFFFFF)
                if req.stop_tokens:
                    st = sorted(req.stop_tokens)
                    stops[lane, :len(st)] = st
        else:
            temps, seeds, stops = host
            lengths, tokens, active, rem = carry
        # chaos: decode fault site — tripped once per DECODE TICK (k times
        # per block), so a deterministic schedule written against
        # per-token serving (error@N, per-tick delays) keeps its meaning
        # under fused blocks; an error fails the in-flight requests and
        # resets the pool (the scheduler's recovery path)
        for _ in range(k):
            chaos.trip("engine.step")
        t0 = _time.perf_counter()
        (toks, lps, ems, len_f, tok_f, live_f, rem_f,
         self.pool.kv, *moe) = self._block_fn(k)(
            self.params, self.pool.kv, jnp.asarray(tables),
            jnp.asarray(lengths), jnp.asarray(tokens),
            jnp.asarray(active), jnp.asarray(temps), jnp.asarray(seeds),
            jnp.asarray(rem), jnp.asarray(stops))
        self.decode_dispatches += 1
        self.decode_block_steps += k
        self._note_dispatch("decode")
        return {"k": k, "lane_reqs": lane_reqs, "dev": (toks, lps, ems),
                "moe": moe, "carry": (len_f, tok_f, live_f, rem_f),
                "host": (temps, seeds, stops), "t0": t0}

    def _consume_block(self, stash, jnp) -> bool:
        """Fetch a dispatched block (ONE host sync for up to K tokens per
        lane) and unpack it through the per-token emit/trace/metrics
        path; may dispatch the NEXT block before running the emit
        callbacks (overlapping device compute with host-side emit)."""
        st = self._stages
        k = stash["k"]
        with stage(st, "fetch"):
            toks = np.asarray(stash["dev"][0], np.int32)
            lps = np.asarray(stash["dev"][1], np.float32)
            ems = np.asarray(stash["dev"][2], bool)
            self._note_moe(stash["moe"], decode=True)
        self.decode_host_syncs += 1
        now = _time.perf_counter()  # post-fetch: device work is done
        self._step_ewma_s = (
            0.8 * self._step_ewma_s + 0.2 * ((now - stash["t0"]) / k)
            if self._step_ewma_s else (now - stash["t0"]) / k)
        emits: List = []
        completed: List = []
        clean = True        # every dispatched lane is still this request's
        emitted_total = 0
        with stage(st, "commit"), self._cv:
            for lane, req in stash["lane_reqs"].items():
                if self._active[lane] is not req or req.cancelled:
                    # released (cancel/deadline sweep) or preempted since
                    # dispatch: its block tokens are DISCARDED — a resume
                    # regenerates them exactly, a cancel never emits them
                    clean = False
                    continue
                self._probe_countdown_locked(req)
                n = int(ems[lane].sum())   # prefix mask: first n are valid
                if n == 0:
                    continue
                emitted_total += n
                # the block is one device round trip: spread its wall time
                # evenly over the lane's tokens so ITL keeps a true mean
                # (the burst shape is documented in docs/PERFORMANCE.md)
                dt = (now - req.t_last) / n if req.t_last is not None \
                    else None
                self._note_second_token(req, now)
                for j in range(n):
                    tok = int(toks[lane, j])
                    req.length += 1
                    req.tokens_out.append(tok)
                    self.tokens_generated += 1
                    if self.metrics is not None and dt is not None:
                        self.metrics.observe_itl(dt)
                    lp = float(lps[lane, j]) if req.want_logprobs else None
                    if req.want_logprobs:
                        req.logprobs_out.append(lp)
                    emits.append((req, tok, len(req.tokens_out) - 1, lp))
                req.t_last = now
                self._fl_block(req, k, n, dt)
                self._flush_decode_chunk(req, lane, now, block=k)
                if req.finished():
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
            # a lane that finished its prompt while this chain ran (its
            # first token is out) is in no block of the chain: chaining
            # ahead would leave it without a step until a lane of the
            # chain completes, hundreds of steps at long outputs
            joiner = any(
                r is not None and lane not in stash["lane_reqs"]
                and not r.pending_prompt and r.tokens_out and not r.cancelled
                for lane, r in enumerate(self._active))
        if self.trace is not None and emitted_total:
            self.trace.add_counter("decode_block", now,
                                   tokens=emitted_total, k=k)
        # dispatch-ahead: with the lane set stable (nothing finished, no
        # cancel/preempt observed) and the SAME adaptive K still the right
        # choice, and no other lane waiting to join, enqueue block N+1
        # from the device-resident carry BEFORE running block N's
        # callbacks — the next block computes while the host emits.  Correctness never depends on this: a request
        # released between dispatch and consume has its block discarded
        # above, and its stale device writes only touch positions a new
        # page owner rewrites before reading.
        if (clean and not completed and not joiner and k > 1
                and self._pending_block is None and not self._shutdown
                and not self._hbm_reclaim_bytes):
            lanes_now = list(stash["lane_reqs"].items())
            parts2 = None
            with stage(st, "plan"):
                # a lane that just re-armed speculation (a probe countdown
                # expiring above) must flow back through _plan_decode — a
                # plain chain-ahead here would starve the probe forever
                spec_next = (self._spec is not None
                             and all(self._spec_eligible(r)
                                     for _, r in lanes_now))
                if not spec_next and self._pick_block_k(lanes_now) == k:
                    k2, parts2 = self._reserve_block_pages(lanes_now, k)
                    if k2 != k or len(parts2) != len(lanes_now):
                        # pages stay reserved on the lanes for the next
                        # regular plan (bounded hoard: <= one block per
                        # lane)
                        parts2 = None
            if parts2 is not None:
                with stage(st, "dispatch"):
                    self._pending_block = self._dispatch_block(
                        parts2, k, jnp, carry=stash["carry"],
                        host=stash["host"])
        self._deliver(emits, completed)
        return True

    # -- speculative decode dispatch -----------------------------------------
    SPEC_EWMA_DECAY = 0.5   # per-dispatch acceptance EWMA smoothing

    #: plain dispatches a transiently degraded lane (acceptance EWMA under
    #: the floor) waits before one speculative PROBE block re-tries it;
    #: chaos-verify degrades never probe (plain for the rest of the request)
    SPEC_PROBE_INTERVAL = 4

    def _spec_block_fn(self, k: int):
        """Jitted speculative block (compiled once per draft length)."""
        fn = self._spec_block_cache.get(k)
        if fn is None:
            rep, kvsh = self._rep, self.pool.kv_sharding
            fn = self._jit(partial(paged_speculative_block, k=k,
                                   **self._spec_kw),
                           (2,),
                           (self._param_sh, self._draft_param_sh, kvsh)
                           + (rep,) * 9,
                           (rep,) * 9 + (kvsh,))
            self._spec_block_cache[k] = fn
        return fn

    def _warm_draft(self, req: _PagedRequest, jnp) -> None:
        """Bring the lane's draft KV up to the target context (positions
        ``[draft_len, length)``): one fused draft forward over the
        missing tail, scattered through the SECOND page table.  Costs a
        dispatch but never a host sync (the logits are not fetched).
        Runs at first speculative entry, after a preemption resume (the
        draft table is released at preemption and regenerated exactly
        here), and after plain-block interludes."""
        t = req.length
        if req.draft_len >= t:
            return
        ctx = np.concatenate([req.prompt,
                              np.asarray(req.tokens_out[:-1], np.int32)])
        start = req.draft_len
        m = t - start
        m_pad = 1 << (m - 1).bit_length()
        tokens = np.zeros((1, m_pad), np.int32)
        tokens[0, :m] = ctx[start:t]
        tables = np.zeros((self.max_pages,), np.int32)
        tables[:len(req.draft_pages)] = req.draft_pages
        _last, self.pool.kv = self._draft_extend(
            self._spec["params"], self.pool.kv, jnp.asarray(tables),
            jnp.asarray(tokens), jnp.int32(start), jnp.int32(t))
        req.draft_len = t
        self.spec_draft_prefills += 1

    def _dispatch_spec_block(self, parts, k: int, jnp):
        """Issue one fused speculative dispatch (draft-propose + verify +
        on-device accept).  Returns None when the verify trip point
        fires (chaos): the participating lanes degrade to plain blocks
        for the rest of their requests and NOTHING was dispatched — no
        token is ever emitted twice, corrupted, or lost."""
        # chaos: the speculative verify fault site — tripped once per
        # speculative dispatch, BEFORE anything is issued, so error/drop
        # degrade cleanly (the lanes' plain fallback re-decodes the very
        # same positions).  Exercised like kvcache.swap: degradation, not
        # request failure.
        try:
            tripped = chaos.trip("engine.verify")
        except chaos.ChaosError:
            tripped = "error"
        if tripped is not None:
            for _lane, req, _nt, _nd in parts:
                self._degrade_spec(req)
            return None
        for _lane, req, _nt, _nd in parts:
            self._warm_draft(req, jnp)
        b = self.lanes
        tables = np.zeros((b, self.max_pages), np.int32)
        dtables = np.zeros((b, self.max_pages), np.int32)
        lengths = np.zeros((b,), np.int32)
        tokens = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        temps = np.zeros((b,), np.float32)
        seeds = np.zeros((b, 2), np.uint32)   # (lo, hi) words
        rem = np.zeros((b,), np.int32)
        n_stop = max((len(r.stop_tokens) for _, r, _nt, _nd in parts),
                     default=0)
        width = (1 << (n_stop - 1).bit_length()) if n_stop > 1 else 1
        stops = np.full((b, width), -1, np.int32)  # ids >= 0: pad safe
        lane_reqs = {}
        for lane, req, _nt, _nd in parts:
            lane_reqs[lane] = req
            tables[lane, :len(req.pages)] = req.pages
            dtables[lane, :len(req.draft_pages)] = req.draft_pages
            lengths[lane] = req.length
            tokens[lane] = req.tokens_out[-1]
            active[lane] = True
            rem[lane] = req.steps - len(req.tokens_out)
            sp = req.sampling
            if sp.device and sp.temperature > 0.0:
                temps[lane] = sp.temperature
                seeds[lane] = (sp.seed & 0xFFFFFFFF,
                               (sp.seed >> 32) & 0xFFFFFFFF)
            if req.stop_tokens:
                st = sorted(req.stop_tokens)
                stops[lane, :len(st)] = st
        t0 = _time.perf_counter()
        (toks, lps, ems, _len_f, _tok_f, _live_f, _rem_f, drafted,
         accepted, self.pool.kv) = self._spec_block_fn(k)(
            self.params, self._spec["params"], self.pool.kv,
            jnp.asarray(tables), jnp.asarray(dtables),
            jnp.asarray(lengths), jnp.asarray(tokens), jnp.asarray(active),
            jnp.asarray(temps), jnp.asarray(seeds), jnp.asarray(rem),
            jnp.asarray(stops))
        self.decode_dispatches += 1
        self.spec_dispatches += 1
        self._note_dispatch("verify")
        return {"k": k, "lane_reqs": lane_reqs,
                "dev": (toks, lps, ems, drafted, accepted), "t0": t0}

    def _consume_spec_block(self, stash, jnp) -> bool:
        """Fetch a speculative dispatch (ONE host sync for up to K+1
        accepted tokens per lane), update each lane's acceptance EWMA,
        and unpack through the per-token emit/trace/metrics path.
        Drafted-but-rejected proposals are counted (``spec_tokens_*``)
        but never emitted and never enter ``tokens_generated`` — so
        tokens-per-dispatch telemetry reflects accepted tokens only."""
        st = self._stages
        k = stash["k"]
        with stage(st, "fetch"):
            toks = np.asarray(stash["dev"][0], np.int32)
            lps = np.asarray(stash["dev"][1], np.float32)
            ems = np.asarray(stash["dev"][2], bool)
            drafted = np.asarray(stash["dev"][3], np.int32)
            accepted = np.asarray(stash["dev"][4], np.int32)
        self.decode_host_syncs += 1
        now = _time.perf_counter()
        self._step_ewma_s = (
            0.8 * self._step_ewma_s + 0.2 * ((now - stash["t0"]) / (k + 1))
            if self._step_ewma_s else (now - stash["t0"]) / (k + 1))
        emits: List = []
        completed: List = []
        emitted_total = 0
        accepted_total = 0
        with stage(st, "commit"), self._cv:
            for lane, req in stash["lane_reqs"].items():
                if self._active[lane] is not req or req.cancelled:
                    continue  # released since dispatch: block discarded
                d, a = int(drafted[lane]), int(accepted[lane])
                self.spec_tokens_drafted += d
                self.spec_tokens_accepted += a
                req.spec_drafted += d
                req.spec_accepted += a
                accepted_total += a
                rate = a / d if d else 0.0
                req.spec_ewma = (self.SPEC_EWMA_DECAY * req.spec_ewma
                                 + (1.0 - self.SPEC_EWMA_DECAY) * rate)
                if req.spec_probing:
                    # this dispatch WAS the probe: its acceptance decides
                    req.spec_probing = False
                    if req.spec_ewma >= self.spec_accept_floor:
                        self.spec_probe_recoveries += 1
                if req.spec_ewma < self.spec_accept_floor:
                    self._degrade_spec(req, probe=True)
                n = int(ems[lane].sum())   # prefix mask: first n are valid
                if n == 0:
                    continue
                emitted_total += n
                dt = (now - req.t_last) / n if req.t_last is not None \
                    else None
                self._note_second_token(req, now)
                for j in range(n):
                    tok = int(toks[lane, j])
                    req.length += 1
                    req.tokens_out.append(tok)
                    self.tokens_generated += 1
                    if self.metrics is not None and dt is not None:
                        self.metrics.observe_itl(dt)
                    lp = float(lps[lane, j]) if req.want_logprobs else None
                    if req.want_logprobs:
                        req.logprobs_out.append(lp)
                    emits.append((req, tok, len(req.tokens_out) - 1, lp))
                req.t_last = now
                if req.draft_pages:
                    # the block's own draft writes cover every accepted
                    # position (k+1 scan iterations: no holes)
                    req.draft_len = req.length
                self._fl_block(req, k, n, dt)
                self._flush_decode_chunk(req, lane, now, block=k,
                                         accepted=a)
                if req.finished():
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        if self.trace is not None and emitted_total:
            self.trace.add_counter("decode_block", now,
                                   tokens=emitted_total, k=k,
                                   accepted=accepted_total)
        self._deliver(emits, completed)
        return True

    def _tick_single(self, parts, jnp) -> bool:
        """K=1 decode tick (host-sampled lanes present, or decode_block=1):
        one dispatch + one fetch per token, the pre-block behavior."""
        st = self._stages
        with stage(st, "dispatch"):
            b = self.lanes
            tables = np.zeros((b, self.max_pages), np.int32)
            lengths = np.zeros((b,), np.int32)
            tokens = np.zeros((b,), np.int32)
            active = np.zeros((b,), bool)
            # device-sampled lanes carry their temperature into the step (the
            # tick then fetches only (B,)-sized arrays for them); host-sampled
            # (top_k/top_p) lanes keep temp 0 on device and pick from fetched
            # logits rows
            temps = np.zeros((b,), np.float32)
            seeds = np.zeros((b, 2), np.uint32)   # (lo, hi) words
            host_lanes = []
            want_logp = False
            lane_reqs = {}
            for lane, req, _new in parts:
                lane_reqs[lane] = req
                tokens[lane] = req.tokens_out[-1]
                tables[lane, :len(req.pages)] = req.pages
                lengths[lane] = req.length
                active[lane] = True
                want_logp |= req.want_logprobs
                sp = req.sampling
                if sp.temperature > 0.0:
                    if sp.device:
                        temps[lane] = sp.temperature
                        seeds[lane] = (sp.seed & 0xFFFFFFFF,
                                       (sp.seed >> 32) & 0xFFFFFFFF)
                    else:
                        host_lanes.append(lane)
            # chaos: decode-tick fault site — an error fails the in-flight
            # requests and resets the pool (the scheduler's recovery path); a
            # delay makes every lane's step slow (deadline-storm scenarios)
            chaos.trip("engine.step")
            t0 = _time.perf_counter()
            logprobs_arr = logp_dev = None
            if temps.any() or want_logp:
                (tok_dev, logp_dev, logits, self.pool.kv,
                 *moe) = self._step_sampled(
                    self.params, self.pool.kv,
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(tokens), jnp.asarray(active),
                    jnp.asarray(temps), jnp.asarray(seeds))
            else:
                # neither device sampling nor logprobs this tick: the plain
                # step (no temps/seeds traced) — greedy stays one device
                # argmax
                logits, self.pool.kv, *moe = self._step(
                    self.params, self.pool.kv,
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jnp.asarray(tokens), jnp.asarray(active))
                tok_dev = logits.argmax(-1)
            self.decode_dispatches += 1
            self.decode_block_steps += 1
            self._note_dispatch("decode")
        with stage(st, "fetch"):
            # greedy + device-sampled lanes: ONLY (B,)-sized arrays cross the
            # link (token ids + chosen-token logprobs)
            next_tokens = np.asarray(tok_dev, np.int32).copy()
            if logp_dev is not None:
                logprobs_arr = np.asarray(logp_dev, np.float32).copy()
            self.decode_host_syncs += 1
            self._note_moe(moe, decode=True)
            if host_lanes:
                # fetch ONLY the host-sampled rows: gather them device-side,
                # then one (n_host, vocab) transfer — not the full
                # (lanes, vocab) matrix when a single lane host-samples.
                # Only active host-sampled lanes consume PRNG state: a
                # page-starved or pending-prefill lane must not perturb a
                # seeded request's token sequence (per-request reproducibility)
                rows = np.asarray(
                    logits[jnp.asarray(np.asarray(host_lanes, np.int32))])
                self.decode_host_syncs += 1
                for i, lane in enumerate(host_lanes):
                    next_tokens[lane] = lane_reqs[lane].sampling.pick(rows[i])
                    if logprobs_arr is not None:
                        # f32 log-sum-exp: the same precision class as the
                        # device log_softmax used for prefill and for
                        # device-sampled lanes — one request, one precision
                        row = rows[i].astype(np.float32)
                        row = row - row.max()
                        logprobs_arr[lane] = float(
                            row[next_tokens[lane]]
                            - np.log(np.exp(row).sum()))

        emits: List = []
        completed: List = []
        now = _time.perf_counter()  # post-fetch: the tick's device work is
        #                             done, so per-lane deltas are real
        self._step_ewma_s = (0.8 * self._step_ewma_s + 0.2 * (now - t0)
                             if self._step_ewma_s else now - t0)
        with stage(st, "commit"), self._cv:
            for lane, req in lane_reqs.items():
                if req.cancelled:
                    continue  # the _run sweep releases it next round
                self._probe_countdown_locked(req)
                self._note_second_token(req, now)
                req.length += 1
                req.tokens_out.append(int(next_tokens[lane]))
                self.tokens_generated += 1
                if self.metrics is not None and req.t_last is not None:
                    self.metrics.observe_itl(now - req.t_last)
                self._fl_block(req, 1, 1,
                               (now - req.t_last)
                               if req.t_last is not None else None)
                req.t_last = now
                lp = (float(logprobs_arr[lane])
                      if logprobs_arr is not None else None)
                if req.want_logprobs:
                    req.logprobs_out.append(lp)
                emits.append((req, req.tokens_out[-1],
                              len(req.tokens_out) - 1, lp))
                done = req.finished()
                if (done or len(req.tokens_out) - req.chunk_start
                        >= self.TRACE_DECODE_CHUNK):
                    self._flush_decode_chunk(req, lane, now)
                if done:
                    self._release_lane_locked(lane, req)
                    completed.append(req)
            with stage(st, "admit"):
                self._admit_locked()
        self._deliver(emits, completed)
        return True

    @staticmethod
    def _result_of(req: _PagedRequest):
        toks = list(req.tokens_out[:req.steps])
        if req.want_logprobs:
            return toks, list(req.logprobs_out[:len(toks)])
        return toks

    def _release_lane_locked(self, lane: int, req: _PagedRequest) -> None:
        if (req.export_digest is not None and self.kv_offload is not None
                and not req.cancelled and req.length > 0
                and req.finished()):
            # disagg export: demote the finished KV to the host tier
            # BEFORE the pages are released (dispatch order makes the
            # gather safe — same window as preemption swap-out).  The
            # handle rides the future; the shipper's export wait is the
            # write-behind fence.
            needed = (req.length + self.page_size - 1) // self.page_size
            req.future._tpulab_kv_export = self.kv_offload.swap_out(
                req.pages[:needed], req.length, self.pool.kv,
                key=("ship", req.export_digest))
        self.pool.release_pages(req.pages)
        if req.draft_pages:
            self.pool.release_pages(req.draft_pages)
            req.draft_pages = []
        self._discard_handle(req)  # a cancelled resume never restores
        self._active[lane] = None
        self._requests.pop(req.future, None)


def _timed_decode_tok_s(step, params_dev, kv0, tables, lengths, tokens,
                        active, lanes: int, iters: int) -> float:
    """Scan-chained decode timing: all iters ride ONE dispatch via
    lax.scan, so per-dispatch host cost is not in the figure, and the
    timed region ends with a host fetch of the tiny logits trace.
    Returns best-of-2 tokens/s."""
    import time

    import jax

    @partial(jax.jit, donate_argnums=(1,))
    def run_n(p, kv, tables, lengths, tokens, active):
        def body(kv, _):
            logits, kv = step(p, kv, tables, lengths, tokens, active)
            return kv, logits[0, 0]
        kv, ls = jax.lax.scan(body, kv, None, length=iters)
        return ls, kv

    ls, kv = run_n(params_dev, kv0, tables, lengths, tokens, active)
    np.asarray(ls)  # compile + warm (fetch = execution fence)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ls, kv = run_n(params_dev, kv, tables, lengths, tokens, active)
        np.asarray(ls)
        best = min(best, time.perf_counter() - t0)
    return lanes * iters / best


def benchmark_decode_kernel_vs_gather(n_heads: int = 8, n_layers: int = 4,
                                      d_model: int = 1024,
                                      page_size: int = 32, lanes: int = 8,
                                      ctx: int = 2048, iters: int = 256,
                                      dtype=None,
                                      autotune: bool = True
                                      ) -> Dict[str, Any]:
    """tokens/s of the pallas ragged-paged-attention decode vs the XLA
    gather fallback at one long-context geometry (the bench perf row and
    the hardware test share this; VERDICT round-1 #3).

    ``autotune`` additionally times the kernel at neighboring block
    geometries (g_pages halved/doubled around the auto pick) and records
    the per-geometry numbers — one capture then attributes a win or loss
    to block size instead of requiring another hardware round
    (VERDICT r3 #3: "if it loses, profile where and iterate")."""
    import jax.numpy as jnp

    from tpulab.models.transformer import init_transformer_params
    from tpulab.ops.paged_attention import _block_geometry

    dtype = dtype or jnp.bfloat16
    mp = ctx // page_size
    params = init_transformer_params(vocab=256, d_model=d_model,
                                     n_heads=n_heads, n_layers=n_layers,
                                     d_ff=4 * d_model)
    tables = np.arange(1, lanes * mp + 1, dtype=np.int32).reshape(lanes, mp)
    lengths = np.full((lanes,), ctx - 2, np.int32)
    tokens = np.zeros((lanes,), np.int32)
    active = np.ones((lanes,), bool)
    row: Dict[str, Any] = {"b": lanes, "ctx": ctx}

    def timed(uk, geometry=None, n_iters=iters):
        pool = PagedKVPool(lanes * mp + 1, page_size, n_layers, n_heads,
                           d_model // n_heads, dtype)
        try:
            step = partial(
                paged_decode_step, n_heads=n_heads, n_layers=n_layers,
                compute_dtype=dtype, use_kernel=uk,
                kernel_geometry=geometry)
            return round(_timed_decode_tok_s(
                step, params, pool.kv, tables, lengths, tokens, active,
                lanes, n_iters), 1), None
        except Exception as e:
            return 0.0, f"{type(e).__name__}: {str(e)[:160]}"
        finally:
            pool.close()

    row["kernel_tok_s"], err = timed(True)
    if err:
        row["kernel_error"] = err
    row["gather_tok_s"], err = timed(False)
    if err:
        row["gather_error"] = err
    # the kernel's internal auto-pick is hkv*d (paged_attention.py); this
    # model is MHA so hkv == n_heads, but derive it the same way so the
    # recorded geometry stays honest if a GQA variant joins the sweep
    hkv = n_heads  # init_transformer_params above builds an MHA model
    g0, n0 = _block_geometry(page_size, mp, hkv * (d_model // n_heads),
                             jnp.dtype(dtype).itemsize)
    row["kernel_geom"] = f"g{g0}xn{n0}"
    if autotune and "kernel_error" not in row:
        tune = {row["kernel_geom"]: row["kernel_tok_s"]}
        for g in {max(1, g0 // 2), min(2 * g0, mp)} - {g0}:
            # keep g*nbuf (total staged pages, hence VMEM scratch) at the
            # auto pick's level: doubling g with n0 buffers would double
            # the scratch past the kernel's VMEM budget and fail compile
            nb = max(2, min(n0, (g0 * n0) // g))
            tok_s, err = timed(True, geometry=(g, nb),
                               n_iters=max(16, iters // 2))
            tune[f"g{g}xn{nb}"] = tok_s if not err else err
        row["kernel_autotune"] = tune
        numeric = {k: v for k, v in tune.items() if isinstance(v, float)}
        best = max(numeric, key=numeric.get)
        row["kernel_best_tok_s"] = numeric[best]
        row["kernel_best_geom"] = best
    return row


def benchmark_decode_kernel_sweep(
        combos=((8, 2048), (32, 2048), (8, 8192), (8, 16384)),
        n_heads: int = 8, n_layers: int = 4, d_model: int = 1024,
        page_size: int = 32, dtype=None) -> List[Dict[str, Any]]:
    """Kernel-vs-gather across (batch, context) — where the gather's
    O(B*ctx) HBM materialization explodes and the ragged walk should pull
    ahead (VERDICT round-2 #3).  Iteration counts scale inversely with
    per-step work to keep wall time bounded."""
    rows = []
    for lanes, ctx in combos:
        iters = max(16, int(256 * (8 * 2048) / (lanes * ctx)))
        rows.append(benchmark_decode_kernel_vs_gather(
            n_heads=n_heads, n_layers=n_layers, d_model=d_model,
            page_size=page_size, lanes=lanes, ctx=ctx, iters=iters,
            dtype=dtype,
            # bound first-capture compile time: geometry variants only at
            # the shorter contexts (the 16k point is one geometry)
            autotune=ctx <= 8192))
    return rows


def benchmark_decode_dispatch(ks=(1, 4, 8, 16), lanes: int = 4,
                              steps: int = 48, prompt_len: int = 8,
                              d_model: int = 64, n_heads: int = 4,
                              n_layers: int = 2, vocab: int = 256,
                              dtype=None) -> Dict[str, Any]:
    """Served tokens/s and host-sync accounting of the ContinuousBatcher
    across fused-decode block sizes K (the bench ``decode_dispatch`` row).

    The same submit->result workload runs at each K; per K the row
    records tok/s, decode dispatches, blocking host syncs, and
    syncs-per-token, plus greedy token parity against the K=1 run.  On
    CPU jit the dispatch/sync counts are the signal (there is no link
    RTT to amortize); on-device the tok/s uplift is — off-chip, the
    per-token cost IS the round trip, so tok/s should scale toward the
    kernel rate as K grows.
    """
    import time

    import jax.numpy as jnp

    from tpulab.models.transformer import init_transformer_params

    dtype = dtype or jnp.float32
    params = init_transformer_params(vocab=vocab, d_model=d_model,
                                     n_heads=n_heads, n_layers=n_layers,
                                     d_ff=4 * d_model)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt_len,), np.int32)
               for _ in range(lanes)]
    max_len = prompt_len + steps + 8
    row: Dict[str, Any] = {"lanes": lanes, "steps": steps, "k": {}}
    base_tokens = None
    for k in ks:
        cb = ContinuousBatcher(params, n_heads=n_heads, n_layers=n_layers,
                               lanes=lanes, max_len=max_len, page_size=8,
                               compute_dtype=dtype, decode_block=k)
        try:
            # warm the prefill/decode compiles out of the measurement
            for f in [cb.submit(p, steps) for p in prompts]:
                f.result(timeout=600)
            d0, s0 = cb.decode_dispatches, cb.decode_host_syncs
            tg0 = cb.tokens_generated
            t0 = time.perf_counter()
            futs = [cb.submit(p, steps) for p in prompts]
            outs = [list(f.result(timeout=600)) for f in futs]
            dt = time.perf_counter() - t0
            toks = cb.tokens_generated - tg0
            entry = {
                "tok_s": round(toks / max(dt, 1e-9), 1),
                "dispatches": cb.decode_dispatches - d0,
                "host_syncs": cb.decode_host_syncs - s0,
                "syncs_per_token": round(
                    (cb.decode_host_syncs - s0) / max(toks, 1), 4),
            }
            if base_tokens is None:
                base_tokens = outs
            else:
                entry["parity_vs_k1"] = outs == base_tokens
            row["k"][str(k)] = entry
        except Exception as e:  # one K's failure must not sink the row
            row["k"][str(k)] = {
                "error": f"{type(e).__name__}: {str(e)[:160]}"}
        finally:
            cb.shutdown()
    k1 = row["k"].get("1", {})
    best = max((e for e in row["k"].values() if "tok_s" in e),
               key=lambda e: e["tok_s"], default=None)
    if best is not None and k1.get("tok_s"):
        row["best_tok_s"] = best["tok_s"]
        row["uplift_vs_k1"] = round(best["tok_s"] / k1["tok_s"], 3)
    return row


def benchmark_speculative_decode(k: int = 8, lanes: int = 2,
                                 steps: int = 48, prompt_len: int = 8,
                                 d_model: int = 64, n_heads: int = 4,
                                 n_layers: int = 4, draft_layers: int = 1,
                                 vocab: int = 256,
                                 tail_scale: float = 0.05,
                                 dtype=None) -> Dict[str, Any]:
    """tok/s, tokens-per-dispatch, host syncs, and acceptance rate of
    speculative decode blocks vs plain K-blocks through the SAME
    ContinuousBatcher workload (the bench ``speculative_decode`` row).

    Supersedes the dense-path ``benchmark_speculative`` row for capture
    purposes: both modes here share one serving-shaped workload function,
    so there is no duplicated plain-baseline loop, and greedy parity is
    recorded in the row like ``decode_dispatch`` does.  The draft is the
    target's first ``draft_layers`` layers (early-exit) with the
    post-exit output projections scaled by ``tail_scale`` — the
    trained-model emulation :func:`benchmark_speculative` documents
    (raw random tail layers pin acceptance to 0 and measure nothing).

    On the CPU capture path the dispatch/sync/acceptance counts are the
    signal (no link RTT to amortize); on-device the tok/s uplift is —
    speculation multiplies the K-block amortization by the acceptance
    rate, so off-chip served tok/s scales with ``(1 + acceptance*k)``
    per round trip.
    """
    import time

    import jax.numpy as jnp

    from tpulab.models.transformer import (early_exit_draft,
                                           init_transformer_params)

    dtype = dtype or jnp.float32
    params = init_transformer_params(vocab=vocab, d_model=d_model,
                                     n_heads=n_heads, n_layers=n_layers,
                                     d_ff=4 * d_model)
    for i in range(draft_layers, n_layers):  # see tail_scale docstring
        for w in ("wo", "w2"):
            params[f"layer{i}"][w] = params[f"layer{i}"][w] * tail_scale
    draft = early_exit_draft(params, draft_layers)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt_len,), np.int32)
               for _ in range(lanes)]
    max_len = prompt_len + steps + 8
    row: Dict[str, Any] = {"lanes": lanes, "steps": steps, "k": k,
                           "draft_layers": draft_layers}
    outs: Dict[str, Any] = {}
    for mode in ("plain", "spec"):
        cb = ContinuousBatcher(
            params, n_heads=n_heads, n_layers=n_layers, lanes=lanes,
            max_len=max_len, page_size=8, compute_dtype=dtype,
            decode_block=k,
            n_pages=2 * lanes * ((max_len + 7) // 8) + 1,
            draft_params=draft if mode == "spec" else None,
            draft_n_layers=draft_layers)
        try:
            # warm the prefill/decode/draft compiles out of the measurement
            for f in [cb.submit(p, steps) for p in prompts]:
                f.result(timeout=600)
            # deterministically pre-compile EVERY block size the adaptive
            # scheduler may pick: which sizes a live warm run hits depends
            # on admission interleaving and per-lane acceptance
            # trajectories, and a compile landing in the measured window
            # would swamp the tok/s signal.  A zero throwaway pool
            # satisfies the donated argument without touching the live one.
            base = (jnp.zeros((lanes, cb.max_pages), jnp.int32),
                    jnp.zeros((lanes,), jnp.int32),
                    jnp.zeros((lanes,), jnp.int32),
                    jnp.zeros((lanes,), bool))
            extra = (jnp.zeros((lanes,), jnp.float32),
                     jnp.zeros((lanes, 2), jnp.uint32),
                     jnp.zeros((lanes,), jnp.int32),
                     jnp.full((lanes, 1), -1, jnp.int32))
            for m in cb.BLOCK_K_MENU:
                if m > k:
                    continue
                zkv = jnp.zeros(cb.pool.kv.shape, cb.pool.kv.dtype)
                if mode == "spec":
                    out = cb._spec_block_fn(m)(cb.params,
                                               cb._spec["params"], zkv,
                                               base[0], *base, *extra)
                elif m > 1:   # k=1 plain runs _tick_single's step
                    out = cb._block_fn(m)(cb.params, zkv, *base, *extra)
                else:
                    continue
                np.asarray(out[0])    # fetch = compile fence
            d0, s0 = cb.decode_dispatches, cb.decode_host_syncs
            tg0 = cb.tokens_generated
            dr0, ac0 = cb.spec_tokens_drafted, cb.spec_tokens_accepted
            t0 = time.perf_counter()
            futs = [cb.submit(p, steps) for p in prompts]
            outs[mode] = [list(f.result(timeout=600)) for f in futs]
            dt = time.perf_counter() - t0
            toks = cb.tokens_generated - tg0
            entry = {
                "tok_s": round(toks / max(dt, 1e-9), 1),
                "dispatches": cb.decode_dispatches - d0,
                "host_syncs": cb.decode_host_syncs - s0,
                # accepted (emitted) tokens only: drafted-but-rejected
                # proposals never enter tokens_generated
                "tokens_per_dispatch": round(
                    toks / max(1, cb.decode_dispatches - d0), 2),
                "syncs_per_token": round(
                    (cb.decode_host_syncs - s0) / max(toks, 1), 4),
            }
            if mode == "spec":
                drafted = cb.spec_tokens_drafted - dr0
                accepted = cb.spec_tokens_accepted - ac0
                entry["drafted"] = drafted
                entry["accepted"] = accepted
                entry["acceptance"] = round(accepted / max(1, drafted), 3)
                entry["fallbacks"] = cb.spec_fallbacks
            row[mode] = entry
        except Exception as e:  # one mode's failure must not sink the row
            row[mode] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
        finally:
            cb.shutdown()
    if "tok_s" in row.get("plain", {}) and "tok_s" in row.get("spec", {}):
        row["parity"] = outs["spec"] == outs["plain"]
        row["uplift"] = round(row["spec"]["tok_s"]
                              / max(row["plain"]["tok_s"], 1e-9), 3)
    return row


def benchmark_sharded_decode(model_shards: int = 2, lanes: int = 4,
                             steps: int = 32, prompt_len: int = 8,
                             d_model: int = 64, n_heads: int = 4,
                             n_layers: int = 2, vocab: int = 256,
                             decode_block: int = 8,
                             dtype=None) -> Dict[str, Any]:
    """Served tok/s and host-sync accounting of ONE ContinuousBatcher
    workload on a ``{"model": M}`` device mesh vs single-device (the
    bench ``sharded_decode`` row).

    Needs >= ``model_shards`` jax devices: the CPU capture path runs
    under ``--xla_force_host_platform_device_count``-style fake devices
    (bench.py spawns this in a subprocess with 8), where the signal is
    token parity plus the PRESERVED dispatch/host-sync counts — XLA's
    inserted collectives ride inside the fused block program, so the
    one-host-sync-per-block contract survives sharding.  On a real
    multi-chip slice the signal is tok/s with a model (and KV pool)
    bigger than one chip's HBM.  Greedy parity is recorded like the
    ``decode_dispatch``/``speculative_decode`` rows; one seeded
    device-sampled request rides along for ``sampled_parity``.
    """
    import time

    import jax
    import jax.numpy as jnp

    from tpulab.models.transformer import init_transformer_params
    from tpulab.parallel.mesh import make_mesh

    dtype = dtype or jnp.float32
    if len(jax.devices()) < model_shards:
        return {"error": f"needs {model_shards} devices, "
                         f"have {len(jax.devices())}"}
    params = init_transformer_params(vocab=vocab, d_model=d_model,
                                     n_heads=n_heads, n_layers=n_layers,
                                     d_ff=4 * d_model)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt_len,), np.int32)
               for _ in range(lanes)]
    max_len = prompt_len + steps + 8
    row: Dict[str, Any] = {"lanes": lanes, "steps": steps,
                           "mesh": {"model": model_shards},
                           "decode_block": decode_block}
    outs: Dict[str, Any] = {}
    sampled: Dict[str, Any] = {}
    for mode in ("single", "sharded"):
        mesh = (make_mesh({"model": model_shards},
                          jax.devices()[:model_shards])
                if mode == "sharded" else None)
        cb = ContinuousBatcher(params, n_heads=n_heads, n_layers=n_layers,
                               lanes=lanes, max_len=max_len, page_size=8,
                               compute_dtype=dtype,
                               decode_block=decode_block, mesh=mesh)
        try:
            # warm the prefill/decode compiles out of the measurement
            for f in [cb.submit(p, steps) for p in prompts]:
                f.result(timeout=600)
            d0, s0 = cb.decode_dispatches, cb.decode_host_syncs
            tg0 = cb.tokens_generated
            t0 = time.perf_counter()
            futs = [cb.submit(p, steps) for p in prompts]
            outs[mode] = [list(f.result(timeout=600)) for f in futs]
            dt = time.perf_counter() - t0
            toks = cb.tokens_generated - tg0
            row[mode] = {
                "tok_s": round(toks / max(dt, 1e-9), 1),
                "dispatches": cb.decode_dispatches - d0,
                "host_syncs": cb.decode_host_syncs - s0,
                "syncs_per_token": round(
                    (cb.decode_host_syncs - s0) / max(toks, 1), 4),
            }
            # a seeded device-sampled stream must survive sharding too
            sampled[mode] = list(cb.submit(
                prompts[0], steps,
                sampling=SamplingParams(temperature=0.8, seed=1234,
                                        device=True)).result(timeout=600))
        except Exception as e:  # one mode's failure must not sink the row
            row[mode] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
        finally:
            cb.shutdown()
    if "tok_s" in row.get("single", {}) and "tok_s" in row.get("sharded", {}):
        row["parity"] = outs["sharded"] == outs["single"]
        row["sampled_parity"] = sampled["sharded"] == sampled["single"]
        # the sharding contract is per-DISPATCH: collectives stay inside
        # the compiled block, so every dispatch costs exactly one
        # blocking fetch in both modes.  (Raw cross-mode dispatch counts
        # can differ by a timing-dependent dispatch-ahead block that
        # emits nothing, so they are reported, not compared.)
        row["one_sync_per_dispatch"] = all(
            row[m]["host_syncs"] == row[m]["dispatches"]
            for m in ("single", "sharded"))
        row["uplift"] = round(row["sharded"]["tok_s"]
                              / max(row["single"]["tok_s"], 1e-9), 3)
    return row


def benchmark_ragged_attention(lanes: int = 3, steps: int = 24,
                               prompt_len: int = 12, d_model: int = 64,
                               n_heads: int = 4, n_layers: int = 2,
                               vocab: int = 256,
                               kernel: bool = True,
                               dtype=None) -> Dict[str, Any]:
    """Dispatch/host-sync accounting + served tok/s of the ragged
    dispatch plan across batch-raggedness shapes (the bench
    ``ragged_attention`` row).

    Three workload shapes through the SAME submit->result harness:
    ``all_prefill`` (``lanes`` simultaneous steps=1 prompts — the shape
    where the unified plan folds N per-lane prefill programs into ONE
    fused dispatch), ``all_decode`` (the K-block regime, unchanged by
    the plan), and ``mixed`` (prompts arriving mid-decode — the round
    that previously cost separate prefill dispatches plus a decode
    block).  Modes: ``legacy`` (split dispatch, the use_kernel=False
    escape hatch), ``ragged`` (unified plan, XLA gather attention), and
    ``ragged_kernel`` (unified plan, pallas ragged kernel — interpret
    mode on the CPU capture path, so its tok/s there measures the
    interpreter, not the kernel; dispatch/sync counts and parity are
    the CPU signal).  Token parity vs legacy is recorded per shape.
    """
    import threading as _threading
    import time

    import jax.numpy as jnp

    from tpulab.models.transformer import init_transformer_params

    dtype = dtype or jnp.float32
    params = init_transformer_params(vocab=vocab, d_model=d_model,
                                     n_heads=n_heads, n_layers=n_layers,
                                     d_ff=4 * d_model)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt_len,), np.int32)
               for _ in range(lanes)]
    max_len = prompt_len + steps + 8
    modes = [("legacy", dict(use_kernel=False)),
             ("ragged", dict(use_kernel=False, ragged=True))]
    if kernel:
        modes.append(("ragged_kernel", dict(use_kernel=True)))
    row: Dict[str, Any] = {"lanes": lanes, "steps": steps,
                           "prompt_len": prompt_len}
    outs: Dict[str, Dict[str, Any]] = {}
    for mode, kw in modes:
        cb = ContinuousBatcher(params, n_heads=n_heads, n_layers=n_layers,
                               lanes=lanes, max_len=max_len, page_size=8,
                               compute_dtype=dtype, decode_block=8, **kw)
        entry: Dict[str, Any] = {}
        got: Dict[str, Any] = {}
        try:
            # warm every program shape out of the measurements
            for f in [cb.submit(p, steps) for p in prompts]:
                f.result(timeout=600)
            cb.submit(prompts[0], 1).result(timeout=600)

            def window(name, fn):
                d0 = (cb.decode_dispatches + cb.prefill_dispatches,
                      cb.decode_host_syncs, cb.tokens_generated)
                t0 = time.perf_counter()
                got[name] = fn()
                dt = time.perf_counter() - t0
                toks = cb.tokens_generated - d0[2]
                entry[name] = {
                    "tok_s": round(toks / max(dt, 1e-9), 1),
                    "dispatches": (cb.decode_dispatches
                                   + cb.prefill_dispatches - d0[0]),
                    "host_syncs": cb.decode_host_syncs - d0[1],
                    "syncs_per_token": round(
                        (cb.decode_host_syncs - d0[1]) / max(toks, 1), 4),
                }

            def all_prefill():
                futs = [cb.submit(p, 1) for p in prompts]
                return [list(f.result(timeout=600)) for f in futs]

            def all_decode():
                futs = [cb.submit(p, steps) for p in prompts]
                return [list(f.result(timeout=600)) for f in futs]

            def mixed():
                evt = _threading.Event()
                hook = (lambda t, i: evt.set() if i == 2 else None)
                f0 = cb.submit(prompts[0], steps, on_token=hook)
                evt.wait(60)
                rest = [cb.submit(p, steps // 2) for p in prompts[1:]]
                return ([list(f0.result(timeout=600))]
                        + [list(f.result(timeout=600)) for f in rest])

            window("all_prefill", all_prefill)
            window("all_decode", all_decode)
            window("mixed", mixed)
            entry["ragged_dispatches"] = cb.ragged_dispatches
            entry["dispatch_kinds"] = dict(cb.dispatch_kinds)
            outs[mode] = got
            row[mode] = entry
        except Exception as e:  # one mode's failure must not sink the row
            row[mode] = {"error": f"{type(e).__name__}: {str(e)[:160]}"}
        finally:
            cb.shutdown()
    base = outs.get("legacy")
    if base:
        for mode in ("ragged", "ragged_kernel"):
            if mode in outs:
                # all_prefill/all_decode are deterministic across modes;
                # the mixed window's token VALUES are too (its arrival
                # timing only changes dispatch grouping)
                row[mode]["parity"] = outs[mode] == base
        if "ragged" in row and "dispatches" in row["ragged"].get(
                "all_prefill", {}):
            row["prefill_fold"] = {
                "legacy_dispatches":
                    row["legacy"]["all_prefill"]["dispatches"],
                "ragged_dispatches":
                    row["ragged"]["all_prefill"]["dispatches"]}
    return row


def benchmark_llm_decode(n_heads: int = 16, n_kv_heads: int = 4,
                         n_layers: int = 8, d_model: int = 1024,
                         d_ff: int = 4096, vocab: int = 8192,
                         page_size: int = 16, lanes: int = 8,
                         ctx: int = 1024, iters: int = 64,
                         dtype=None) -> Dict[str, Any]:
    """Paged decode tokens/s with bf16 vs weight-only-int8 params (W8A16)
    at a Llama-ish GQA geometry — small-batch decode is weight-bandwidth
    bound, so int8 weights are the serving-latency lever this row
    measures.  Same scan-chained, fetch-fenced discipline as
    :func:`benchmark_decode_kernel_vs_gather`."""
    import jax
    import jax.numpy as jnp

    from tpulab.models.quantization import (quantize_transformer_params,
                                            transformer_param_bytes)
    from tpulab.models.transformer import init_transformer_params

    dtype = dtype or jnp.bfloat16

    def to_bf16(tree):
        # cast every float leaf; int8 payloads pass through untouched
        return jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.bfloat16)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            tree)

    mp = ctx // page_size
    # untied head so the LARGEST per-step weight read (lm_head) is part of
    # what quantization shrinks; the int8 variant's remaining float leaves
    # (embed, norms, scales) are bf16 like the baseline — the comparison
    # isolates exactly the weight-width axis
    params = init_transformer_params(vocab=vocab, d_model=d_model,
                                     n_heads=n_heads, n_layers=n_layers,
                                     d_ff=d_ff, n_kv_heads=n_kv_heads,
                                     tie_embeddings=False)
    variants = {
        "bf16": to_bf16(params),
        "int8": to_bf16(quantize_transformer_params(params)),
    }
    tables = np.arange(1, lanes * mp + 1, dtype=np.int32).reshape(lanes, mp)
    lengths = np.full((lanes,), ctx - 2, np.int32)
    tokens = np.zeros((lanes,), np.int32)
    active = np.ones((lanes,), bool)
    row: Dict[str, Any] = {"b": lanes, "ctx": ctx,
                           "layers": n_layers, "d_model": d_model}
    for label, p in variants.items():
        pool = PagedKVPool(lanes * mp + 1, page_size, n_layers, n_kv_heads,
                           d_model // n_heads, dtype)
        try:
            step = partial(paged_decode_step, n_heads=n_heads,
                           n_layers=n_layers, compute_dtype=dtype,
                           use_kernel=False, n_kv_heads=n_kv_heads)
            pdev = jax.device_put(p, pool.device)
            row[f"{label}_tok_s"] = round(_timed_decode_tok_s(
                step, pdev, pool.kv, tables, lengths, tokens, active,
                lanes, iters), 1)
            row[f"{label}_param_mb"] = round(
                transformer_param_bytes(p) / 2**20, 1)
        except Exception as e:
            row[f"{label}_tok_s"] = 0.0
            row[f"{label}_error"] = f"{type(e).__name__}: {str(e)[:160]}"
        finally:
            pool.close()
    return row

"""The page store's layout: what the device keeps, what leaves the process.

The device keeps ``(L, P, 2, S, Hkv*D)`` — the shape the ragged kernel
reads, so no step slices or reshapes a layer of the pool ahead of the
``pallas_call`` (on the chip that was a copy of a whole layer per layer
per step; only a chip run can time it, so the jaxpr is held to it here).
The host-side formats keep ``(L, n, 2, S, Hkv, D)`` and the bytes they
had before the device's layout changed: a golden taken at the parent
commit (b325c7c).
"""

import hashlib
import json
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.disagg import KVShipper
from tpulab.engine.kv_pool import PagedKVPool, kv_page_shape
from tpulab.engine.paged import ContinuousBatcher
from tpulab.engine.paged_steps import paged_decode_step, paged_ragged_forward
from tpulab.kvcache import KVOffloadManager
from tpulab.models.transformer import init_transformer_params

# ------------------------------------------------------ no pool copies ----


def _pool_sized_equations(jaxpr, n_elems):
    """Equations with an operand or a result of at least ``n_elems``
    elements, other than the scatters and the ``pallas_call``.  Call-like
    equations (pjit, scan, cond, shard_map) are looked into, not counted:
    handing the pool to a sub-program moves nothing."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call" or name.startswith("scatter"):
            continue
        inner = []
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    inner.append(j)
        if inner:
            for j in inner:
                yield from _pool_sized_equations(j, n_elems)
            continue
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", None)
            if shape is not None and int(np.prod(shape)) >= n_elems:
                yield f"{name}{tuple(shape)}"
                break


@pytest.mark.parametrize("step", ["paged_decode_step",
                                  "paged_ragged_forward"])
def test_kernel_steps_never_move_a_layer_of_the_pool(step):
    """With ``use_kernel=True`` the only equations that touch anything as
    large as one layer of the pool are the scatters (in place on the
    donated pool) and the ``pallas_call`` (whole pool in, by reference):
    no ``slice``, ``dynamic_slice``, ``reshape`` or ``convert`` of it."""
    lanes, page_size, n_pages, mp = 2, 8, 64, 4
    lm = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64)
    kv = jax.ShapeDtypeStruct(
        (2, n_pages) + kv_page_shape(page_size, 2, 16), jnp.float32)
    layer_elems = int(np.prod(kv.shape[1:]))
    assert layer_elems > max(int(np.prod(w.shape)) for w in
                             jax.tree_util.tree_leaves(lm))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    kw = dict(n_heads=2, n_layers=2, compute_dtype=jnp.float32,
              use_kernel=True)
    if step == "paged_decode_step":
        fn = lambda kv, tables, lengths, tokens, active: paged_decode_step(
            lm, kv, tables, lengths, tokens, active, **kw)
        args = (kv, i32(lanes, mp), i32(lanes), i32(lanes),
                jax.ShapeDtypeStruct((lanes,), jnp.bool_))
    else:
        fn = lambda kv, tables, seq, q_lens, kv_lens: paged_ragged_forward(
            lm, kv, tables, seq, q_lens, kv_lens, **kw)
        args = (kv, i32(lanes, mp), i32(lanes, 8), i32(lanes), i32(lanes))
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    names = [e.primitive.name for e in jaxpr.eqns]
    assert "scatter" in names                       # the walk sees them
    assert list(_pool_sized_equations(jaxpr, layer_elems)) == []


# --------------------------------------------- bytes that leave the host ----

#: taken at the parent commit (b325c7c, pool ``(L, P, 2, S, Hkv, D)``) by
#: this test's own ``_export`` on both dispatch plans: the host tier's
#: array, the wire header, the whole shipment
GOLDEN = {
    "shape": [2, 3, 2, 4, 2, 4],
    "payload_sha256":
        "e5fd8fea0fc75092073b0d777b0e4415c81ff3348b98adde42c759b24ba64ab3",
    "header": ('{"digest": "000102030405060708090a0b0c0d0e0f", '
               '"dtype": "bfloat16", "first_token": 7, "length": 11, '
               '"page_size": 4, "shape": [2, 3, 2, 4, 2, 4]}'),
    "blob_sha256":
        "1c45b095c76eef06a01eb8717113d3e88291878229286d235673f9fbc809c02f",
}
_DIGEST = bytes(range(16))


def _exact_lm():
    """A two-layer GQA model whose K/V rows are exact whatever order the
    matmuls sum in, so the golden cannot drift with the compiler: token t
    embeds as +-1 by the bits of a code, RMSNorm of such a row is one
    constant times it (rounded away by the bf16 page store), every K/V
    column selects ONE feature times a power of two that differs by
    layer, K/V, head and column, and ``wo``/``w2`` are zero so every
    layer sees the embedding."""
    d_model, n_heads, n_kv, hd = 16, 4, 2, 4
    p = init_transformer_params(vocab=16, d_model=d_model, n_heads=n_heads,
                                n_layers=2, d_ff=16, n_kv_heads=n_kv)
    code = (np.arange(16)[:, None] * 40503 + 12345) >> np.arange(d_model)
    p["embed"] = jnp.asarray(1.0 - 2.0 * (code & 1), jnp.float32)
    for layer in range(2):
        w = np.zeros((d_model, (n_heads + 2 * n_kv) * hd), np.float32)
        for j in range(2 * n_kv * hd):         # the K columns, then V
            w[(5 * j + 3 * layer) % d_model, n_heads * hd + j] = \
                2.0 ** ((j + 7 * layer) % 11 - 5)
        lp = p[f"layer{layer}"]
        lp["wqkv"] = jnp.asarray(w)
        lp["wo"] = jnp.zeros_like(lp["wo"])
        lp["w2"] = jnp.zeros_like(lp["w2"])
    return p


def _export(use_kernel):
    """One 11-token prompt through the engine (3 pages of 4, the last
    partly filled), exported as a disaggregated prefill exports it."""
    cb = ContinuousBatcher(_exact_lm(), n_heads=4, n_kv_heads=2, n_layers=2,
                           lanes=1, max_len=32, page_size=4, n_pages=8,
                           compute_dtype=jnp.float32, kv_dtype=jnp.bfloat16,
                           kv_offload=8 << 20, use_kernel=use_kernel)
    try:
        prompt = np.asarray([3, 14, 1, 5, 9, 2, 6, 11, 8, 7, 13], np.int32)
        fut = cb.submit(prompt, 1, export_digest=_DIGEST)
        fut.result(timeout=120)
        handle = fut._tpulab_kv_export
        assert handle is not None and handle.wait(30)
        held = cb.kv_offload.store.peek(handle.key)
        blob = KVShipper(cb.kv_offload).export(handle, digest=_DIGEST,
                                               first_token=7)
    finally:
        cb.shutdown()
    return held, blob


@pytest.mark.parametrize("attention", ["gather", "kernel"])
def test_exported_pages_keep_the_parents_bytes(attention):
    """A page written through the engine leaves through
    ``kvcache/offload.py`` (the host tier's array) and ``disagg/wire.py``
    (header and shipment) exactly as it did at the parent commit."""
    held, blob = _export(use_kernel=(attention == "kernel"))
    assert list(held.shape) == GOLDEN["shape"]
    assert held.dtype == jnp.bfloat16
    # written where it was: K of layer 0, position 5 (page 1, slot 1)
    k = np.asarray(held[0, 1, 0, 1], np.float32).reshape(-1)
    assert np.all(np.abs(k) == 2.0 ** (np.arange(8) % 11 - 5))
    assert (hashlib.sha256(held.tobytes()).hexdigest()
            == GOLDEN["payload_sha256"])
    version, hdr_len = struct.unpack_from("<HI", blob, 4)
    assert (blob[:4], version) == (b"TPKV", 1)
    assert blob[10:10 + hdr_len].decode() == GOLDEN["header"]
    assert json.loads(GOLDEN["header"])["shape"] == GOLDEN["shape"]
    assert hashlib.sha256(blob).hexdigest() == GOLDEN["blob_sha256"]


def test_pool_round_trips_are_bit_exact():
    """grow/shrink and swap-out/swap-in move pages without changing a
    bit, and the host tier holds them heads apart."""
    pool = PagedKVPool(6, 4, 2, 2, 8, jnp.bfloat16)
    mgr = KVOffloadManager(pool, 8 << 20)
    try:
        assert pool.kv.shape == (2, 6, 2, 4, 2 * 8)
        data = jnp.asarray(np.random.default_rng(4).standard_normal(
            pool.kv.shape), jnp.bfloat16)
        pool.kv = data
        want = np.asarray(data)
        assert pool.grow(3) == 3
        assert pool.kv.shape == (2, 9, 2, 4, 16)
        np.testing.assert_array_equal(np.asarray(pool.kv[:, :6]), want)
        assert not np.asarray(pool.kv[:, 6:]).any()
        assert pool.shrink(3) == 3
        np.testing.assert_array_equal(np.asarray(pool.kv), want)

        src = [pool.allocate_page() for _ in range(3)]
        h = mgr.swap_out(src, length=12, kv=pool.kv)
        assert h is not None and h.wait(10)
        held = mgr.store.peek(h.key)
        assert held.shape == pool.host_shape(3) == (2, 3, 2, 4, 2, 8)
        assert held.tobytes() == want[:, np.asarray(src)].tobytes()
        pool.release_pages(src)
        pool.kv = jnp.zeros_like(pool.kv)
        dst = [pool.allocate_page() for _ in range(3)]
        pool.kv = mgr.restore(h, dst, pool.kv)
        np.testing.assert_array_equal(
            np.asarray(pool.kv[:, np.asarray(dst)]),
            want[:, np.asarray(src)])
    finally:
        mgr.close()
        pool.close()


# ------------------------------------------------- the latent entry kind ----

#: taken with this test's own ``_latent_pages`` when the latent
#: cache-entry kind was added (PR 28), the same on both plans
LATENT_GOLDEN = {
    "shape": [2, 7, 1, 4, 128],
    "sha256": "f1f9e06a2f7610302480fab2055203ebaa3bc2688bd5b32146efe3f0ab730cc0",
}


def _exact_latent_lm():
    """:func:`_exact_lm` for MLA: every latent column selects ONE +-1
    feature times a power of two (sums of their squares are exact in any
    order, so the latent's RMSNorm is too), and ``wo``/``w2`` are zero so
    every layer sees the embedding."""
    from tpulab.models.spec import ModelSpec, init_params
    spec = ModelSpec(n_layers=2, d_model=16, n_heads=2, attention="mla",
                     q_lora_rank=8, kv_lora_rank=12, qk_nope_head_dim=6,
                     qk_rope_head_dim=4, v_head_dim=8, rms_eps=1e-5,
                     rope_theta=10000.0)
    p = init_params(spec, vocab=16, d_ff=16)
    code = (np.arange(16)[:, None] * 40503 + 12345) >> np.arange(16)
    p["embed"] = jnp.asarray(1.0 - 2.0 * (code & 1), jnp.float32)
    for layer in range(2):
        w = np.zeros((16, spec.latent_width), np.float32)
        for j in range(spec.latent_width):
            w[(5 * j + 3 * layer) % 16, j] = 2.0 ** ((j + 7 * layer) % 9 - 4)
        lp = p[f"layer{layer}"]
        lp["wkv_a"] = jnp.asarray(w)
        lp["wo"] = jnp.zeros_like(lp["wo"])
        lp["w2"] = jnp.zeros_like(lp["w2"])
    return spec, p


def _latent_pages(use_kernel):
    spec, params = _exact_latent_lm()
    cb = ContinuousBatcher(params, spec.n_heads, spec.n_layers, spec=spec,
                           lanes=1, max_len=32, page_size=4, n_pages=8,
                           compute_dtype=jnp.float32, use_kernel=use_kernel)
    try:
        assert cb.pool.kv.dtype == jnp.float32
        prompt = np.asarray([3, 14, 1, 5, 9, 2, 6, 11, 8, 7, 13], np.int32)
        cb.submit(prompt, 1).result(timeout=120)
        # page 0 is scratch; the stream's pages are the three lowest ids
        return spec, np.asarray(cb.pool.kv)[:, 1:]
    finally:
        cb.shutdown()


def test_latent_page_shape_is_one_padded_row_a_token():
    from tpulab.engine.kv_pool import latent_page_shape
    assert latent_page_shape(16, 576) == (1, 16, 640)
    assert latent_page_shape(8, 40) == (1, 8, 128)
    assert latent_page_shape(16, 512) == (1, 16, 512)
    pool = PagedKVPool(5, 16, 8, 0, 0, jnp.bfloat16, latent_width=576)
    assert pool.kv.shape == (8, 5, 1, 16, 640)
    assert pool.entry_kind == "latent"
    # 8 layers x 640 values x 2 B; K/V of 20 heads of 256 would be 163,840
    assert pool.bytes_per_token == 10240
    assert PagedKVPool(5, 16, 8, 20, 256, jnp.bfloat16
                       ).bytes_per_token == 163840


@pytest.mark.parametrize("plan", ["ragged_gather", "ragged_kernel"])
def test_latent_pages_hold_the_row_once(plan):
    """A prompt written through the engine leaves ``[c_kv ; k_rope ;
    zeros]`` a position a layer: normalised latent, RoPE'd shared key, the
    pad; nothing stored twice, nothing expanded."""
    spec, pages = _latent_pages(use_kernel=(plan == "ragged_kernel"))
    assert list(pages.shape) == LATENT_GOLDEN["shape"]
    used = pages[:, :3]                       # 11 tokens: 4 + 4 + 3 slots
    assert not pages[:, 3:].any()             # no other page was written
    rows = used.reshape(2, 12, 128)           # one ascending run of ids
    assert not rows[:, 11].any()              # nor the slot past the prompt
    rows = rows[:, :11]
    assert not rows[..., spec.latent_width:].any()     # the pad stays zero
    c_kv, k_r = rows[..., :12], rows[..., 12:16]
    # RMSNorm of the latent: unit mean square, signs and ratios kept
    np.testing.assert_allclose((c_kv ** 2).mean(-1), 1.0, rtol=1e-4)
    ratio = np.abs(c_kv[0, :, 1] / c_kv[0, :, 0])
    np.testing.assert_allclose(ratio, 2.0, rtol=1e-6)  # columns 2^-3, 2^-4
    # RoPE turns the shared key by position and keeps its length; position
    # 0 is not turned: +-2^((j) % 9 - 4) * rsqrt(1 + eps) for j = 12..15
    np.testing.assert_allclose(
        np.abs(k_r[0, 0]), 2.0 ** (np.arange(12, 16) % 9 - 4)
        / np.sqrt(1 + 1e-5), rtol=1e-6)
    norms = (k_r[0] ** 2).reshape(11, 2, 2).sum(axis=1)
    np.testing.assert_allclose(norms, np.broadcast_to(norms[:1], norms.shape),
                               rtol=1e-5)
    # the golden was taken when a prompt's pages popped off the top of a
    # LIFO list (ids 7, 6, 5): the same pages, laid where they lay then
    as_then = np.concatenate([pages[:, 3:], used[:, ::-1]], axis=1)
    got = hashlib.sha256(np.asarray(as_then, jnp.bfloat16).tobytes()
                         ).hexdigest()
    assert got == LATENT_GOLDEN["sha256"]

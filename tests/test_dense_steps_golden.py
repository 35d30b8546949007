"""The dense decoder's step programs, to the bit.

When the step functions moved onto ONE layer block driven by a model spec
(PR 28), a dense model had to keep the programs it had: the same logits,
bit for bit, as at the parent commit (a365295) at ``tests/test_paged.py``'s
sizes, through ``paged_ragged_forward``, ``paged_mixed_step``,
``paged_decode_step`` (XLA gather and the ragged kernel) and
``paged_extend``.  ``tests/data/dense_steps_a365295.npz`` holds the
parent's logits from :func:`dense_step_logits` below, run under the parent's
tree.  Bits can only be compared where float arithmetic is the generating
machine's: a canary product recorded with the golden says so; on another
CPU the comparison falls back to 1e-6.

Since PR 29 ``paged_mixed_step`` takes its round packed by token (7 rows
where the parent's padded form computed 3 x 4): the same segments, the same
products on another row count, so that one case compares at the 1e-6
fallback on every machine; the other four programs stay to the bit.

Since PR 35 the ragged kernel's loop over a lane's blocks takes its trip
count from the lane's length (the parent ran every block of the table under
a conditional): the same operations in the same order, but XLA:CPU, which
compiles the interpreter's program, sums the one-row products of a decode
step in another order inside the new loop (with the conditional put back
and nothing else changed the parent's bits return).  The two entries
``<model>.decode.kernel=True`` are therefore PR 35's own bits, 5.96e-8
(``mha``) and 8.94e-8 (``gqa_rope_swiglu``) from the parent's at the
largest; every other entry is a365295's, and the kernel's rows at a chunk's
width (``ragged``) stayed to the bit through that PR.

Since PR 40 one row a lane goes through the kernel ``ragged_paged_decode``:
the query heads of a KV head are the rows of ONE product and the block's
mask is one positional row, where the rows kernel made a product a head
against a mask a query row.  The same sums over the same keys, in the
order XLA:CPU gives the new shapes: the two ``decode.kernel=True`` entries
are PR 40's bits, 5.96e-8 (``mha``, 104 of 192 values) and 8.94e-8
(``gqa_rope_swiglu``, 114 of 192) from the parent's (PR 35's) at the
largest.  The round's decode row takes the same kernel (``mha.mixed``
5.96e-8 from the golden, inside that case's 1e-6); ``ragged`` stays to the
bit.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers_steps import mixed_step
from tpulab.engine.kv_pool import PagedKVPool
from tpulab.engine.paged_steps import (pack_round, paged_decode_step,
                                       paged_extend, paged_mixed_step,
                                       paged_ragged_forward)
from tpulab.models.transformer import init_transformer_params

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "dense_steps_a365295.npz")
MODELS = {
    # tests/test_paged.py's fixture, and its GQA model with RoPE and SwiGLU
    "mha": (dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64),
            dict(n_heads=2, n_kv_heads=None, rope_theta=None)),
    "gqa_rope_swiglu": (
        dict(vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64,
             n_kv_heads=2, ffn="swiglu", tie_embeddings=False),
        dict(n_heads=4, n_kv_heads=2, rope_theta=10000.0)),
}


def canary():
    """A jitted float32 product and softmax whose bits follow the CPU's
    vector units and the compiler's summation order."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
    return np.asarray(jax.jit(lambda a, b: jax.nn.softmax(a @ b, -1))(a, b))


def dense_step_logits(name, use_kernel):
    """Logits of every step function for model ``name``, one pool carried
    through them in turn (each step reads what the one before wrote)."""
    init_kw, kw = MODELS[name]
    params = init_transformer_params(**init_kw)
    n_kv = kw["n_kv_heads"] or kw["n_heads"]
    rng = np.random.default_rng(7)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0], [7, 8, 0, 0]],
                         jnp.int32)
    kv = PagedKVPool(n_pages=10, page_size=8, n_layers=2, n_heads=n_kv,
                     head_dim=init_kw["d_model"] // kw["n_heads"],
                     dtype=jnp.float32).kv
    common = dict(n_layers=2, compute_dtype=jnp.float32, **kw)
    i32 = lambda x: jnp.asarray(x, jnp.int32)
    seq = i32(rng.integers(0, 64, (3, 8)))
    out = {}
    logits, kv = jax.jit(lambda p, kv: paged_ragged_forward(
        p, kv, tables, seq, i32([8, 5, 0]), i32([8, 5, 0]),
        use_kernel=use_kernel, **common))(params, kv)
    out["ragged"] = np.asarray(logits)[:2]
    # the parent's padded round (seq[:, :4], q_lens [4, 1, 0]) packed by
    # token: lane 0's chunk of four, lane 1's decode token, lane 2 idle
    toks, row_lane, row_off, q_lens = map(i32, pack_round(
        3, {0: np.asarray(seq[0, :4])}, {1: int(seq[1, 0])}))
    _nt, _lp, last, kv_packed = mixed_step(
        jax.jit(partial(paged_mixed_step, lanes=3, max_pages=4,
                        use_kernel=use_kernel, **common)),
        params, kv, tables, toks, row_lane, row_off, q_lens, [12, 6, 0])
    out["mixed"] = np.asarray(last)[:2]
    # the steps below read what the round wrote: they get it from the
    # padded form, whose bits are the parent's, and the packed round's
    # pool is held to it here
    _last, kv = jax.jit(lambda p, kv: paged_ragged_forward(
        p, kv, tables, seq[:, :4], q_lens, i32([12, 6, 0]),
        use_kernel=use_kernel, last_only=True, **common))(params, kv)
    np.testing.assert_allclose(np.asarray(kv_packed)[:, 1:],
                               np.asarray(kv)[:, 1:], rtol=1e-6, atol=1e-6)
    logits, kv = jax.jit(lambda p, kv: paged_decode_step(
        p, kv, tables, i32([12, 6, 0]), i32([3, 9, 0]),
        jnp.asarray([True, True, False]), use_kernel=use_kernel,
        **common))(params, kv)
    out["decode"] = np.asarray(logits)
    if not use_kernel:
        last, kv = jax.jit(lambda p, kv: paged_extend(
            p, kv, tables[2], seq[2:3], jnp.int32(0), jnp.int32(6),
            **common))(params, kv)
        out["extend"] = np.asarray(last)
    return out


CASES = [(name, uk, step) for name in MODELS for uk in (False, True)
         for step in ("ragged", "mixed", "decode") + (() if uk
                                                      else ("extend",))]


@pytest.fixture(scope="module")
def golden():
    return np.load(GOLDEN)


@pytest.fixture(scope="module")
def computed():
    cache = {}

    def get(name, use_kernel):
        if (name, use_kernel) not in cache:
            cache[name, use_kernel] = dense_step_logits(name, use_kernel)
        return cache[name, use_kernel]
    return get


@pytest.mark.parametrize("name,use_kernel,step", CASES, ids=[
    f"{n}-{'kernel' if uk else 'gather'}-{s}" for n, uk, s in CASES])
def test_dense_step_logits_are_the_parents(golden, computed, name,
                                           use_kernel, step):
    got = computed(name, use_kernel)[step]
    want = golden[f"{name}.{step}.kernel={use_kernel}"]
    if step != "mixed" and np.array_equal(canary(), golden["canary"]):
        np.testing.assert_array_equal(got, want)
    else:       # another CPU's float arithmetic: the bits are not comparable
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


if __name__ == "__main__":     # PYTHONPATH=<parent tree> python <this file>
    from tpulab.tpu import platform
    platform.force_cpu(8)
    blobs = {"canary": canary()}
    for name in MODELS:
        for uk in (False, True):
            for step, arr in dense_step_logits(name, uk).items():
                blobs[f"{name}.{step}.kernel={uk}"] = arr
    np.savez(GOLDEN, **blobs)

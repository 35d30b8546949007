"""What the tests of the paged-attention kernels share: the kernel body of a
call as a jaxpr, the rule its matrix products follow, and the float32
outputs of the parent commit.

The rule (``tpulab.ops.ragged_attention.mxu_operands``): a page store
narrower than 32 bits feeds both products of a key block operands in the
query's dtype at the default precision, the staged K/V block as stored; a
float32 store keeps float32 operands at ``HIGHEST``, bit for bit what the
kernels computed before the rule (``tests/data/attention_kernels_f32_*.npz``,
taken at the parent of the PR that brought it, but for the one entry that
:func:`assert_parents_bits` names).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

#: a bf16 kernel against the XLA form of the same step, both of which round
#: the probabilities and the output to bf16: twice what the interpreter
#: shows over the grids of tests/test_ragged_attention.py and
#: tests/test_keye_sparse.py (an ulp of the output and 0.0037)
BF16_RTOL, BF16_ATOL = 1.6e-2, 8e-3
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "attention_kernels_f32_5829881.npz")


def pallas_calls(fn, *args):
    """The ``pallas_call`` equations that ``fn(*args)`` traces, in order,
    those inside loops and conditionals included: ``params["name"]`` is
    the kernel's name, ``params["jaxpr"]`` its body."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from calls(sub)
    return list(calls(jax.make_jaxpr(fn)(*args).jaxpr))


def kernel_eqns(fn, *args):
    """Every equation of the ONE ``pallas_call`` body that ``fn(*args)``
    traces, the bodies of its loops and conditionals included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    (call,) = pallas_calls(fn, *args)
    return list(walk(call.params["jaxpr"]))


def assert_operand_rule(fn, args, store_dtype, block_shape):
    """The two products of a key block as the rule has them for a store of
    ``store_dtype`` whose staged block is ``block_shape``."""
    eqns = kernel_eqns(fn, *args)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) >= 2
    highest = [e.params["precision"] is not None
               and jax.lax.Precision.HIGHEST in tuple(
                   np.ravel(e.params["precision"])) for e in dots]
    operands = {v.aval.dtype for e in dots for v in e.invars}
    widened = [e for e in eqns if e.primitive.name == "convert_element_type"
               and e.params["new_dtype"] == jnp.float32
               and e.invars[0].aval.shape == tuple(block_shape)]
    if jnp.dtype(store_dtype).itemsize >= 4:
        assert all(highest) and operands == {jnp.dtype(jnp.float32)}
    else:
        # one pass: the operands in the store's dtype, float32 accumulation,
        # and no float32 copy of the staged block for them to be rounded from
        assert not any(highest)
        assert operands == {jnp.dtype(store_dtype)}
        assert all(e.params["preferred_element_type"] == jnp.float32
                   for e in dots)
        assert not widened


_PAGE = 8


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def sparse_attend_case(dtype=jnp.float32):
    """``sparse_attend``'s arguments: rows of three lanes (one lane without
    a row, one row without a token, one row that selects nothing)."""
    rng = np.random.default_rng(4)
    r, h, d, mp = 9, 4, 32, 5
    pool = jnp.asarray(rng.standard_normal((2, 1 + 3 * mp, 2, _PAGE, 2 * d)),
                       dtype)
    tables = _i32(1 + rng.permutation(3 * mp).reshape(3, mp))
    q = jnp.asarray(rng.standard_normal((r, h, d)), dtype)
    lane = _i32([0, 0, 2, 2, 2, -1, 0, 2, 2])
    kv_lens = _i32([17, 0, 40])
    mask = rng.random((r, mp * _PAGE)) < 0.3
    mask &= np.arange(mp * _PAGE)[None, :] < np.asarray(kv_lens)[
        np.maximum(np.asarray(lane), 0)][:, None]
    mask[np.asarray(lane) < 0] = False
    mask[3] = False                       # a row that selects nothing
    return (q, jnp.asarray(mask), lane, pool, 1, tables, _i32([1, 0, 1]),
            kv_lens, dtype)


def sparse_decode_case(dtype=jnp.float32):
    """One row a lane, a lane skipped, a lane whose row selects nothing:
    ``(sparse_attend_decode's arguments, the same rows for sparse_attend)``."""
    rng = np.random.default_rng(15)
    b, h, d, mp = 4, 4, 32, 5
    pool = jnp.asarray(rng.standard_normal((2, 1 + b * mp, 2, _PAGE, 2 * d)),
                       dtype)
    tables = _i32(1 + rng.permutation(b * mp).reshape(b, mp))
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    kv_lens = np.array([17, 0, 40, 9])
    mask = (rng.random((b, mp * _PAGE)) < 0.4) & (
        np.arange(mp * _PAGE)[None, :] < kv_lens[:, None])
    mask[3] = False
    live = _i32([1, 0, 1, 1])
    return ((q, jnp.asarray(mask), pool, 1, tables, live, _i32(kv_lens)),
            (q, jnp.asarray(mask), _i32([0, -1, 2, 3]), pool, 1, tables, live,
             _i32(kv_lens), dtype))


def canary():
    """A jitted float32 product and softmax whose bits follow the CPU's
    vector units and the compiler's summation order: bits are compared only
    where this reads as it did on the machine that wrote the golden."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
    return np.asarray(jax.jit(lambda a, b: jax.nn.softmax(a @ b, -1))(a, b))


def assert_parents_bits(name, got):
    """``got`` is the parent commit's float32 output ``name``: to the bit
    where the arithmetic is the generating machine's, to 1e-6 elsewhere.
    Entries that are NaN in the golden (rows the kernel leaves unwritten)
    must be NaN.  One entry is not the parent's: the one-row walk of the
    K/V kernel (``ragged_paged_attention-one-row``).  PR 35, which brought
    the rule, left it 1.8e-7 from its parent's at the largest (72 of 128
    values): the loop over a lane's blocks takes its trip count from the
    lane's length where the parent's ran every block under a conditional,
    the same operations in the same order, but XLA:CPU, which compiles the
    interpreter's program, sums a one-row product in another order inside
    the new loop.  Since PR 40 one row a lane runs the kernel
    ``ragged_paged_decode`` (a KV head's query heads the rows of one
    product, one positional mask row): the entry holds PR 40's bits,
    2.4e-7 from PR 35's at the largest (104 of 128 values), again the
    summation order of other shapes and nothing else (both products stay
    float32 at ``HIGHEST``)."""
    with np.load(GOLDEN) as golden:
        want, same_machine = golden[name], np.array_equal(golden["canary"],
                                                          canary())
    got = np.asarray(got)
    if same_machine:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

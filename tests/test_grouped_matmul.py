"""The grouped-matmul kernel under the expert FFN (tpulab.ops.
grouped_matmul) in the Pallas interpreter at small shapes, against a loop
over the groups in float32, and the tiles its plan gives the ten products
the five expert cells' step programs are traced at."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.ops import grouped_matmul as gm
from tpulab.parallel import moe


def _loop_over_groups(lhs, rhs, sizes):
    """``(rows in groups, N)`` float32: group after group, a plain product."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out, row = [], 0
    for g, size in enumerate(sizes):
        out.append(lhs[row:row + size] @ rhs[g])
        row += size
    return np.concatenate(out)


#: name: (rows, K, N, sizes, (tm, ts, tn), dtype)
CASES = {
    # (a) groups without rows between full ones, and at both ends
    "empty-groups-between-full-ones": (
        64, 256, 256, [0, 10, 0, 0, 30, 3, 0, 21, 0], (64, 16, 128),
        jnp.float32),
    # (b) a group that straddles a row tile (rows 20-69 over tiles of 32),
    # taken in passes that start inside a packed tile
    "a-group-straddles-row-tiles": (
        96, 128, 256, [20, 50, 26], (32, 16, 256), jnp.float32),
    "a-group-of-several-passes": (
        96, 128, 128, [5, 75, 16], (96, 24, 128), jnp.float32),
    # (c) rows past the last group: whole tiles of them, never visited
    "rows-past-the-last-group": (
        128, 128, 128, [3, 0, 9, 1], (32, 8, 128), jnp.float32),
    "no-row-in-any-group": (
        32, 128, 128, [0, 0, 0], (16, 8, 128), jnp.float32),
    # (d) a row count that is no multiple of 128: 65 x 8 rows in tiles of 104
    "rows-65-x-8": (
        520, 128, 256, [100, 0, 7, 230, 64, 1, 118], (104, 64, 128),
        jnp.float32),
    # (e) the decode regime: more groups than rows, one tile, a packed tile
    # a pass
    "more-groups-than-rows": (
        16, 256, 128, [0, 1, 0, 0, 2, 0, 1, 0, 0, 0, 3, 0, 0, 1, 0, 0, 0,
                       2, 0, 0, 1, 0, 0, 1], (16, 8, 128), jnp.float32),
    # (f) bfloat16 operands, float32 accumulation
    "bf16-round": (
        128, 256, 256, [40, 0, 17, 60, 11], (64, 32, 256), jnp.bfloat16),
    "bf16-decode": (
        32, 256, 128, [1, 0, 2, 0, 0, 1, 3, 0, 1, 0, 0, 2], (32, 16, 128),
        jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_loop_over_groups(case):
    rows, k, n, sizes, (tm, ts, tn), dtype = CASES[case]
    rng = np.random.default_rng(len(case))
    lhs = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)) * k ** -0.5,
                      dtype)
    got = gm.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32), tm=tm,
                            ts=ts, tn=tn, interpret=True)
    assert got.shape == (rows, n) and got.dtype == dtype
    used = sum(sizes)
    want = _loop_over_groups(lhs, rhs, sizes)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got)[:used], want, rtol=1e-5,
                                   atol=1e-5)
    else:
        # the float32 product rounded ONCE to bfloat16: within half a unit
        # in the last place (2^-8 of the value at most) of the float32
        # loop, on top of test_glm_moe's tolerance for the accumulation (a
        # bfloat16 accumulator would be out by ~2^-8 a term)
        err = np.abs(np.asarray(got, np.float32)[:used] - want)
        assert (err <= 2.0 ** -8 * np.abs(want) + 2e-5).all()


@pytest.mark.parametrize("act", ["swiglu", "gelu"])
def test_expert_ffn_through_the_kernel_equals_ragged_dot(act, monkeypatch):
    """The expert FFN with both products through the kernel (as on a TPU;
    here in the interpreter) against itself through ``ragged_dot``: rows
    sorted, gathered, weighted and scattered as they are, a share of the
    experts held (assignments past the last group)."""
    rng = np.random.default_rng(7)
    n, d, f, e, k = 12, 128, 128, 6, 2
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(e)[:k] for _ in range(n)]),
                      jnp.int32)
    w = jnp.asarray(rng.random((n, k)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal(
        (4, d, 2 * f if act == "swiglu" else f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((4, f, d)) * 0.1, jnp.float32)
    want = moe.expert_ffn(x, idx, w, w_in, w_out, act, first=1)

    def through_the_kernel(lhs, rhs, sizes):
        return gm.grouped_matmul(lhs, rhs, sizes, tm=lhs.shape[0], ts=8,
                                 tn=128, interpret=True)
    monkeypatch.setattr(gm, "grouped_product", through_the_kernel)
    got = moe.expert_ffn(x, idx, w, w_in, w_out, act, first=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_tiles_that_do_not_fit_are_refused_by_name():
    lhs, rhs = jnp.zeros((48, 128)), jnp.zeros((2, 128, 256))
    sizes = jnp.asarray([3, 4], jnp.int32)
    # rows, a pass's packed tiles, columns, a pass taller than its tile
    for tm, ts, tn in ((32, 8, 128), (48, 12, 128), (48, 8, 192),
                       (24, 48, 128)):
        with pytest.raises(ValueError, match="do not fit"):
            gm.grouped_matmul(lhs, rhs, sizes, tm=tm, ts=ts, tn=tn,
                              interpret=True)
    with pytest.raises(ValueError, match="sizes"):
        gm.grouped_matmul(lhs, rhs, sizes[:1], tm=48, ts=8, tn=128,
                          interpret=True)


#: cell: (experts held, d_model, expert width, a decode step's rows, a
#: round's): ``lanes x top_k`` and ``(512 + lanes) x top_k``
CELLS = {
    "glm47flash-l8": (64, 2048, 1536, 32, 2080),
    "keyevl2-l6": (128, 2048, 768, 64, 4160),
    "qwen3next-l8-ep4": (128, 2048, 512, 320, 5440),
    "longcat-flash-l4-ep32": (16, 6144, 2048, 384, 6528),
    "xing4-l6": (64, 3584, 1024, 128, 2176),
}


@pytest.mark.parametrize("regime", ["decode", "round"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_plan_at_the_traced_shapes(cell, regime):
    """Both products of a cell's expert layer at the rows its step program
    is traced at: tiles that divide, a step within the budget, the rows of
    a decode step one tile taken a packed tile a pass."""
    _, d, f, decode_rows, round_rows = CELLS[cell]
    rows = decode_rows if regime == "decode" else round_rows
    for k, n in ((d, 2 * f), (f, d)):
        plan = gm._gmm_plan(rows, k, n, jnp.bfloat16)
        assert plan is not None
        assert rows % plan.tm == 0 and n % plan.tn == 0
        assert plan.tm % 16 == 0 and plan.ts % 16 == 0 and plan.tn % 128 == 0
        assert plan.ts <= plan.tm <= gm._TM_MAX
        assert plan.vmem_bytes == gm._vmem_bytes(plan.tm, plan.ts, plan.tn,
                                                 k, jnp.bfloat16)
        assert plan.vmem_bytes <= gm._TILE_BUDGET < gm._VMEM_REQUEST_MAX
        assert (plan.tm, plan.ts) == ((rows, 16) if regime == "decode"
                                      else (plan.tm, gm._ROUND_PASS))
        # a weight block is at least half a megabyte: the copy runs at the
        # bandwidth
        assert k * plan.tn * 2 >= 1 << 19


def test_plan_keeps_ragged_dot_where_the_kernel_was_not_measured():
    assert gm._gmm_plan(128, 2048, 1024, jnp.float32) is None
    assert gm._gmm_plan(128, 2048, 1000, jnp.bfloat16) is None
    assert gm._gmm_plan(128, 100, 1024, jnp.bfloat16) is None
    assert gm._gmm_plan(24, 2048, 1024, jnp.bfloat16) is None
    # a width no block of which fits the budget
    assert gm._gmm_plan(128, 1 << 18, 128, jnp.bfloat16) is None


def test_off_tpu_the_product_is_ragged_dot_and_the_shape_is_on_record():
    lhs = jnp.ones((16, 128), jnp.bfloat16)
    rhs = jnp.ones((3, 128, 384), jnp.bfloat16)
    sizes = jnp.asarray([2, 0, 5], jnp.int32)
    got = gm.grouped_product(lhs, rhs, sizes)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[:7],
        np.asarray(jax.lax.ragged_dot(lhs, rhs, sizes), np.float32)[:7])
    assert {"rows": 16, "k": 128, "n": 384, "dtype": "bfloat16",
            "tiles": None, "pass_rows": None,
            "vmem_bytes": None} in gm.traced_products()

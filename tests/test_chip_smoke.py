"""chip_smoke.py's contract off the chip, and the shape rule that took the
place of the compile probes (what only a chip can show is in the smoke)."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from tpulab.ops.ragged_attention import (kernel_geometry_error,
                                         ragged_paged_attention)

REPO = __file__.rsplit("/tests/", 1)[0]


def test_chip_smoke_fails_at_once_without_a_chip():
    """No accelerator: non-zero exit with a one-line reason, no result
    line, before any model is built (so: in seconds)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, f"{REPO}/chip_smoke.py"],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=REPO)
    assert out.returncode == 2, (out.returncode, out.stderr[-2000:])
    assert "no accelerator" in out.stderr and "'cpu'" in out.stderr
    assert out.stdout.strip() == ""       # no phase ran, no result printed


def test_kernel_shape_rule():
    """The serving geometry is admitted; an excluded one is named."""
    ok = dict(q_len=256, n_heads=16, n_kv_heads=4, head_dim=128,
              page_size=16, max_pages=512, q_dtype=jnp.bfloat16,
              kv_dtype=jnp.bfloat16)
    assert kernel_geometry_error(**ok) is None
    assert kernel_geometry_error(**dict(ok, kv_dtype=jnp.float8_e4m3fn)) \
        is None
    assert "128 lanes" in kernel_geometry_error(
        **dict(ok, n_heads=4, n_kv_heads=4, head_dim=16))
    assert "8 sublanes" in kernel_geometry_error(**dict(ok, page_size=4))
    assert "VMEM" in kernel_geometry_error(
        **dict(ok, q_len=2048, n_heads=64, n_kv_heads=64))


def test_kernel_refuses_excluded_geometry_before_mosaic():
    """Asked to compile (interpret=False) at a geometry the rule
    excludes, the kernel raises ValueError naming the constraint instead
    of handing Mosaic a program it will refuse."""
    q = jnp.zeros((1, 1, 4, 16), jnp.float32)
    pool = jnp.zeros((1, 3, 2, 8, 4 * 16), jnp.float32)   # row of 64 lanes
    args = (0, np.zeros((1, 2), np.int32), np.ones((1,), np.int32),
            np.ones((1,), np.int32))
    with pytest.raises(ValueError, match="not a multiple of 128 lanes"):
        ragged_paged_attention(q, pool, *args, interpret=False)
    # the interpreter has no tiles: the same call runs there
    assert ragged_paged_attention(q, pool, *args,
                                  interpret=True).shape == q.shape


def test_engine_names_the_constraint_up_front(monkeypatch):
    """use_kernel=True where Mosaic would compile: an excluded geometry is
    a ValueError at construction, never a silent gather."""
    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    from tpulab.tpu import platform

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    monkeypatch.setattr(platform, "pallas_interpret", lambda: False)
    with pytest.raises(ValueError, match="use_kernel=True.*128 lanes"):
        ContinuousBatcher(params, n_heads=2, n_layers=1, lanes=1,
                          max_len=32, page_size=8, use_kernel=True,
                          compute_dtype=jnp.float32)


def test_the_smokes_plan_table_is_one_plan_through_both_attentions():
    """``LM_PLANS`` names the two attentions of the engine's one dispatch
    plan, by options the constructor has: no plan is chosen by name any
    more, and the flash prefill row went with its kernel."""
    import importlib.util
    import inspect

    from tpulab.engine.paged import ContinuousBatcher
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", f"{REPO}/chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = smoke      # its dataclasses look themselves up
    try:
        spec.loader.exec_module(smoke)
    finally:
        del sys.modules[spec.name]
    assert [name for name, _ in smoke.LM_PLANS] == ["lm_gather", "lm_kernel"]
    assert [plan["use_kernel"] for _, plan in smoke.LM_PLANS] == [False, True]
    taken = set(inspect.signature(ContinuousBatcher.__init__).parameters)
    assert all(set(plan) <= taken for _, plan in smoke.LM_PLANS)
    assert not {"ragged", "prefill_flash"} & taken
    assert not hasattr(smoke, "prefill_logits")

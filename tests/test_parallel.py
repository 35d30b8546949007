"""Parallel layer tests on the 8-virtual-device CPU mesh: meshes, shardings,
ring/ulysses attention numerics, sharded train step, multi-device dispatch."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
from jax.sharding import PartitionSpec as P

from tpulab.models.transformer import (causal_attention, dense_attention,
                                       init_transformer_params,
                                       transformer_apply)
from tpulab.parallel import (MultiDeviceDispatcher, make_mesh, default_mesh,
                             transformer_param_shardings)
from tpulab.parallel.ring_attention import ring_attention, ulysses_attention
from tpulab.parallel.training import make_sharded_train_step


# ------------------------------------------------------------------- mesh ---
def test_make_mesh_shapes():
    mesh = make_mesh({"data": 2, "model": 4})
    assert mesh.shape == {"data": 2, "model": 4}
    mesh2 = default_mesh(n_model=2)
    assert mesh2.shape["model"] == 2 and mesh2.shape["data"] == 4


def test_make_mesh_too_many_devices():
    with pytest.raises(ValueError, match="needs 16 devices"):
        make_mesh({"data": 16})
    with pytest.raises(ValueError, match="needs 12 devices"):
        make_mesh({"data": 3, "model": 4})


def test_default_mesh_rejects_nondivisible_model():
    with pytest.raises(ValueError, match="not divisible"):
        default_mesh(n_model=3)          # 8 devices % 3
    with pytest.raises(ValueError, match="not divisible"):
        default_mesh(n_model=2, devices=jax.devices()[:5])


def test_transformer_param_shardings_rules():
    params = init_transformer_params(vocab=64, d_model=16, n_heads=2,
                                     n_layers=1, d_ff=32)
    mesh = make_mesh({"data": 2, "model": 4})
    sh = transformer_param_shardings(params, mesh)
    assert sh["layer0"]["wqkv"].spec == P(None, "model")
    assert sh["layer0"]["wo"].spec == P("model", None)
    assert sh["layer0"]["ln1"]["scale"].spec == P()
    assert sh["embed"].spec == P("model", None)


def test_transformer_param_shardings_full_rule_tree():
    """The complete Megatron-TP rule set over a multi-layer model:
    wqkv/w1/w3/lm_head column-parallel, wo/w2 row-parallel, every norm
    leaf replicated, tree structure preserved leaf-for-leaf, and every
    leaf a NamedSharding on the given mesh."""
    from jax.sharding import NamedSharding
    params = init_transformer_params(vocab=64, d_model=16, n_heads=2,
                                     n_layers=3, d_ff=32, ffn="swiglu",
                                     tie_embeddings=False)
    mesh = make_mesh({"model": 8})
    sh = transformer_param_shardings(params, mesh)
    # nesting preserved: identical treedef, all leaves NamedSharding
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(sh))
    for leaf in jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert isinstance(leaf, NamedSharding) and leaf.mesh == mesh
    for i in range(3):
        layer = sh[f"layer{i}"]
        for col in ("wqkv", "w1", "w3"):
            assert layer[col].spec == P(None, "model"), (i, col)
        for row in ("wo", "w2"):
            assert layer[row].spec == P("model", None), (i, row)
        for norm in ("ln1", "ln2"):
            assert layer[norm]["scale"].spec == P(), (i, norm)
    assert sh["lm_head"].spec == P(None, "model")   # vocab output dim
    assert sh["embed"].spec == P("model", None)     # vocab input dim
    assert sh["final_norm"]["scale"].spec == P()
    # a custom axis name flows through every rule
    sh2 = transformer_param_shardings(params, make_mesh({"tp": 4}),
                                      model_axis="tp")
    assert sh2["layer0"]["wqkv"].spec == P(None, "tp")
    assert sh2["layer0"]["wo"].spec == P("tp", None)


def test_kv_pool_sharding_spec():
    """Page payloads shard on the row of KV heads (axis 4 of the fused
    (L, P, 2, S, Hkv*D) layout: contiguous head groups); page tables are
    host arrays and never see this spec."""
    from tpulab.parallel import kv_pool_sharding
    mesh = make_mesh({"model": 2})
    assert kv_pool_sharding(mesh).spec == P(None, None, None, None,
                                            "model")
    mesh2 = make_mesh({"tp": 2})
    assert kv_pool_sharding(mesh2, model_axis="tp").spec == \
        P(None, None, None, None, "tp")


# -------------------------------------------------------------- attention ---
def _qkv(b=2, t=32, h=4, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def test_ring_attention_matches_full():
    """Ring attention over 8 sequence shards == single-device attention."""
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv()
    want = causal_attention(q, k, v)
    got = ring_attention(mesh, axis_name="sp")(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_noncausal():
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(t=16)
    want = dense_attention(q, k, v, causal=False)
    got = ring_attention(mesh, axis_name="sp", causal=False)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_full():
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(t=16, h=4)
    want = causal_attention(q, k, v)
    got = ulysses_attention(mesh, axis_name="sp")(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_bad_head_count():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(t=16, h=4)  # 4 heads, 8 devices
    with pytest.raises(ValueError, match="not divisible"):
        ulysses_attention(mesh, axis_name="sp")(q, k, v)


def test_transformer_with_ring_attention_under_jit():
    """End-to-end: jitted sequence-parallel transformer forward."""
    mesh = make_mesh({"data": 1, "model": 8})
    params = init_transformer_params(vocab=64, d_model=32, n_heads=4,
                                     n_layers=2, d_ff=64)
    from functools import partial
    ring = ring_attention(mesh, axis_name="model")
    f32 = jnp.float32
    ref_fn = partial(transformer_apply, n_heads=4, n_layers=2,
                     compute_dtype=f32)
    ring_fn = partial(transformer_apply, n_heads=4, n_layers=2,
                      compute_dtype=f32, attention_fn=ring)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 32), np.int32)
    want = ref_fn(params, {"tokens": tokens})["logits"]
    got = jax.jit(ring_fn)(params, {"tokens": tokens})["logits"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- training ---
def test_sharded_train_step_reduces_loss():
    mesh = make_mesh({"data": 4, "model": 2})
    params = init_transformer_params(vocab=64, d_model=32, n_heads=4,
                                     n_layers=2, d_ff=64)
    from functools import partial
    apply_fn = partial(transformer_apply, n_heads=4, n_layers=2,
                       compute_dtype=jnp.float32)
    step, sp = make_sharded_train_step(apply_fn, params, mesh,
                                       learning_rate=5e-2)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 64, (8, 16), np.int32),
             "targets": rng.integers(0, 64, (8, 16), np.int32)}
    losses = []
    for _ in range(5):
        sp, loss = step(sp, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # learning happens through the shardings


# ---------------------------------------------------------------- dispatch ---
def test_multi_device_dispatcher_policies():
    from tpulab.models.mnist import make_mnist
    disp = MultiDeviceDispatcher.create(
        lambda: make_mnist(max_batch_size=1), "mnist",
        devices=jax.devices()[:2], max_executions=1, policy="least_loaded")
    try:
        x = np.zeros((1, 28, 28, 1), np.float32)
        outs = [disp.infer("mnist", Input3=x).result(timeout=60)
                for _ in range(4)]
        assert len(outs) == 4 and disp.device_count == 2
    finally:
        disp.shutdown()


# ------------------------------------------------------------- kv decode ---
def test_kv_cache_decode_matches_full_forward():
    """Decode-step logits == full-forward logits at every position."""
    from tpulab.models.transformer import (init_kv_cache,
                                           transformer_decode_step)
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 12), np.int32)
    full = transformer_apply(params, {"tokens": tokens}, n_heads=2,
                             n_layers=2, compute_dtype=jnp.float32)["logits"]
    cache = init_kv_cache(2, 16, n_layers=2, n_heads=2, head_dim=16,
                          dtype=jnp.float32)
    for i in range(12):
        logits, cache = transformer_decode_step(
            params, cache, tokens[:, i], jnp.int32(i), n_heads=2,
            n_layers=2, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]),
                                   rtol=2e-4, atol=2e-4)


def test_generate_fn_greedy():
    from tpulab.models.transformer import make_generate_fn
    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    gen = make_generate_fn(params, n_heads=2, n_layers=2, max_len=32,
                           compute_dtype=jnp.float32)
    prompt = np.random.default_rng(1).integers(0, 32, (2, 4), np.int32)
    out = gen(prompt, 8)
    assert out.shape == (2, 8)
    assert ((np.asarray(out) >= 0) & (np.asarray(out) < 32)).all()
    # deterministic greedy
    out2 = gen(prompt, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# -------------------------------------------------------------- multihost ---
def test_multihost_helpers_single_process():
    from tpulab.parallel import multihost
    multihost.initialize()  # no-op on single host
    mesh = multihost.global_mesh(n_model=2)
    assert mesh.shape == {"data": 4, "model": 2}
    multihost.barrier(mesh)  # completes = all devices reached it
    lo, hi = multihost.local_data_slice(32, mesh)
    assert (lo, hi) == (0, 32)  # single process feeds everything


# -------------------------------------------------------------------- moe ---
def test_expert_parallel_moe_matches_dense():
    """Expert-sharded MoE (psum combine) == dense single-device MoE."""
    from tpulab.parallel.moe import (init_moe_params,
                                     make_expert_parallel_ffn, moe_ffn)
    mesh = make_mesh({"ep": 8})
    params = init_moe_params(d_model=32, d_ff=64, n_experts=8, seed=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32), jnp.float32)
    want = moe_ffn(params, x, top_k=2)
    ffn, shard = make_expert_parallel_ffn(mesh, axis_name="ep", top_k=2)
    got = ffn(shard(params), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_moe_top1_routing():
    from tpulab.parallel.moe import init_moe_params, moe_ffn, route
    params = init_moe_params(d_model=16, d_ff=32, n_experts=4, seed=2)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 16), jnp.float32)
    idx, w = route(params["router"], x, top_k=1)
    assert idx.shape == (8, 1)                        # exactly one expert
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    y = moe_ffn(params, x, top_k=1)
    assert y.shape == x.shape


# ---------------------------------------------------------------- pipeline ---
def test_pipeline_parallel_matches_sequential():
    """4-stage GPipe pipeline over ppermute == sequential layer stack."""
    from tpulab.parallel.pipeline import make_pipeline, stack_stage_params
    mesh = make_mesh({"pp": 4})
    d = 32
    rng = jax.random.PRNGKey(0)
    stage_params = []
    for i in range(4):
        k1, k2, rng = jax.random.split(rng, 3)
        stage_params.append({"w": jax.random.normal(k1, (d, d)) * 0.3,
                             "b": jax.random.normal(k2, (d,)) * 0.1})

    def stage_fn(p, x):
        return jax.nn.gelu(x @ p["w"] + p["b"])

    # sequential reference
    x = jax.random.normal(jax.random.PRNGKey(9), (6, 4, d), jnp.float32)
    want = x
    for p in stage_params:
        want = jax.vmap(lambda mb, p=p: stage_fn(p, mb))(want)

    pipeline, shard = make_pipeline(mesh, stage_fn, axis_name="pp")
    got = pipeline(shard(stack_stage_params(stage_params)), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_single_microbatch():
    from tpulab.parallel.pipeline import make_pipeline, stack_stage_params
    mesh = make_mesh({"pp": 2})
    d = 16
    stage_params = [{"w": jnp.eye(d) * (i + 1)} for i in range(2)]
    pipeline, shard = make_pipeline(mesh, lambda p, x: x @ p["w"],
                                    axis_name="pp")
    x = jnp.ones((1, 2, d), jnp.float32)
    out = pipeline(shard(stack_stage_params(stage_params)), x)
    np.testing.assert_allclose(np.asarray(out), 2.0)  # 1*1*2


def test_moe_tied_logits_exact_k():
    """Uniform router logits (padding tokens) still select exactly k."""
    from tpulab.parallel.moe import init_moe_params, route
    params = init_moe_params(d_model=16, d_ff=32, n_experts=4, seed=0)
    zeros = jnp.zeros((3, 16), jnp.float32)   # tied logits everywhere
    for k in (1, 2):
        idx, w = route(params["router"], zeros, top_k=k)
        assert all(len(set(row)) == k for row in np.asarray(idx).tolist())
        assert (np.asarray(w) > 0).all()


def test_pipeline_rejects_stage_mesh_mismatch():
    from tpulab.parallel.pipeline import make_pipeline, stack_stage_params
    mesh = make_mesh({"pp": 2})
    stages = [{"w": jnp.eye(8)} for _ in range(4)]  # 4 stages, pp=2
    _pipeline, shard = make_pipeline(mesh, lambda p, x: x, axis_name="pp")
    with pytest.raises(ValueError, match="pipeline axis"):
        shard(stack_stage_params(stages))


def test_moe_transformer_serves():
    """MoE transformer registers and serves through the engine."""
    from tpulab.engine import InferenceManager
    from tpulab.models.transformer import make_moe_transformer
    model = make_moe_transformer(vocab=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, n_experts=4,
                                 seq_len=16, max_batch_size=2,
                                 compute_dtype=jnp.float32)
    mgr = InferenceManager(max_executions=1)
    mgr.register_model("moe", model)
    mgr.update_resources()
    try:
        toks = np.random.default_rng(0).integers(0, 64, (1, 16), np.int32)
        out = mgr.infer_runner("moe").infer(tokens=toks).result(timeout=120)
        assert out["logits"].shape == (1, 16, 64)
        assert np.isfinite(out["logits"]).all()
    finally:
        mgr.shutdown()


# -------------------------------------------------------------- checkpoint ---
def _tiny_train(mesh, steps, params, batch, ckpt=None, save_at=None,
                lr=1e-2):
    from tpulab.parallel.training import make_sharded_train_step
    from tpulab.models.transformer import make_transformer
    model = make_transformer(vocab=32, d_model=32, n_heads=2, n_layers=1,
                             d_ff=64, seq_len=8, compute_dtype=jnp.float32)
    step_fn, p = make_sharded_train_step(model.apply_fn, params, mesh,
                                         learning_rate=lr)
    losses = []
    for i in range(steps):
        p, loss = step_fn(p, batch)
        losses.append(float(loss))
        if ckpt is not None and i == save_at:
            ckpt.save(i, {"step": i, "params": p}, wait=True)
    return p, losses


def test_train_checkpoint_resume_exact(tmp_path):
    """Save mid-run, restore in a fresh checkpointer, continue: the resumed
    trajectory equals the uninterrupted one bit-for-bit."""
    from tpulab.parallel import TrainCheckpointer, abstract_like, make_mesh
    from tpulab.parallel.training import make_sharded_train_step
    from tpulab.models.transformer import (init_transformer_params,
                                           make_transformer)
    mesh = make_mesh({"data": 2, "model": 4})
    params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                     n_layers=1, d_ff=64)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32),
             "targets": jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32)}

    with TrainCheckpointer(str(tmp_path / "ck")) as ck:
        p_full, losses_full = _tiny_train(mesh, 4, params, batch,
                                          ckpt=ck, save_at=1)

    # resume from step 1 in a fresh manager, run the remaining 2 steps
    model = make_transformer(vocab=32, d_model=32, n_heads=2, n_layers=1,
                             d_ff=64, seq_len=8, compute_dtype=jnp.float32)
    step_fn, p_tmpl = make_sharded_train_step(
        model.apply_fn,
        init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64), mesh,
        learning_rate=1e-2)
    with TrainCheckpointer(str(tmp_path / "ck")) as ck2:
        assert ck2.latest_step() == 1
        state = ck2.restore({"step": 0,
                             "params": abstract_like(p_tmpl)})
    p = state["params"]
    resumed = []
    for _ in range(2):
        p, loss = step_fn(p, batch)
        resumed.append(float(loss))
    assert resumed == losses_full[2:]
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(p_full)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    from tpulab.parallel import TrainCheckpointer
    with TrainCheckpointer(str(tmp_path / "ck"), max_to_keep=2) as ck:
        for s in range(5):
            ck.save(s, {"w": jnp.full((4,), s, jnp.float32)}, wait=True)
        assert ck.latest_step() == 4
        assert ck.all_steps() == [3, 4]


def test_checkpoint_cross_mesh_restore(tmp_path):
    """State saved under one mesh restores onto a DIFFERENT topology via an
    abstract target carrying the new shardings."""
    from tpulab.parallel import (TrainCheckpointer, abstract_like, make_mesh,
                                 named_sharding)
    mesh_a = make_mesh({"data": 8})
    mesh_b = make_mesh({"data": 2, "model": 4})
    x = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                       named_sharding(mesh_a, "data", None))
    with TrainCheckpointer(str(tmp_path / "ck")) as ck:
        ck.save(0, {"x": x}, wait=True)
    tgt = {"x": jax.ShapeDtypeStruct(
        (8, 8), jnp.float32,
        sharding=named_sharding(mesh_b, "model", "data"))}
    with TrainCheckpointer(str(tmp_path / "ck")) as ck2:
        got = ck2.restore(tgt)["x"]
    assert got.sharding.spec == named_sharding(mesh_b, "model", "data").spec
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


@pytest.mark.slow  # heavyweight e2e; tier-1 runtime headroom (see ROADMAP)
def test_checkpoint_resume_across_process_restart(tmp_path):
    """Crash/resume across real process boundaries: part1 trains+saves and
    exits; a fresh process resumes and must reproduce the uninterrupted
    run's losses bit-for-bit."""
    import subprocess
    import sys
    prog = """
import sys
import numpy as np
from tpulab.tpu.platform import force_cpu
force_cpu(4)
import jax.numpy as jnp
from tpulab.parallel import TrainCheckpointer, abstract_like, make_mesh
from tpulab.parallel.training import make_sharded_train_step
from tpulab.models.transformer import init_transformer_params, make_transformer

mode, ckdir = sys.argv[1], sys.argv[2]
mesh = make_mesh({"data": 2, "model": 2})
params = init_transformer_params(vocab=32, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64)
model = make_transformer(vocab=32, d_model=32, n_heads=2, n_layers=1,
                         d_ff=64, seq_len=8, compute_dtype=jnp.float32)
step_fn, p = make_sharded_train_step(model.apply_fn, params, mesh,
                                     learning_rate=1e-2)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32),
         "targets": jnp.asarray(rng.integers(0, 32, (4, 8)), jnp.int32)}
with TrainCheckpointer(ckdir) as ck:
    if mode == "full":
        for i in range(4):
            p, loss = step_fn(p, batch)
            print(f"step {i} {float(loss):.8f}")
    elif mode == "part1":
        for i in range(2):
            p, loss = step_fn(p, batch)
            print(f"step {i} {float(loss):.8f}")
        ck.save(1, {"step": 1, "params": p}, wait=True)
    else:
        s = ck.restore({"step": 0, "params": abstract_like(p)})
        assert s["step"] == 1
        p = s["params"]
        for i in range(2, 4):
            p, loss = step_fn(p, batch)
            print(f"step {i} {float(loss):.8f}")
"""
    env = {"PYTHONPATH": REPO, "PATH": "/usr/bin:/bin", "HOME": "/tmp",
           "TPULAB_FORCE_CPU": "1"}

    def run(mode, ckdir):
        out = subprocess.run([sys.executable, "-c", prog, mode, str(ckdir)],
                             capture_output=True, text=True, timeout=300,
                             env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        return [ln for ln in out.stdout.splitlines()
                if ln.startswith("step")]

    full = run("full", tmp_path / "a")
    part = (run("part1", tmp_path / "b") + run("resume", tmp_path / "b"))
    assert part == full and len(full) == 4

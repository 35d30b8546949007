"""The plain reference against the program at a tiny size, on the CPU.

The reference imports nothing from tpulab; here the program's own float32
forward pass is the other side.  Agreement is to float32 rounding, and a
reference that left out part of the mathematics would miss by far more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

LM = spec.load_module("reference", "lm")
HYPER = dict(n_layers=2, n_heads=4, n_kv_heads=2, rope_theta=10000.0)


@pytest.fixture(scope="module")
def lm_params():
    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(vocab=128, d_model=64, n_heads=4,
                                     n_layers=2, d_ff=96, seed=3,
                                     n_kv_heads=2, ffn="swiglu",
                                     tie_embeddings=False)
    # at this width the program's 0.02 init gives near-uniform logits that
    # no mistake could move: scale the matrices up until attention matters
    return jax.tree_util.tree_map(lambda a: a * 8 if a.ndim == 2 else a,
                                  params)


def program_logits(params, tokens, **kw):
    from tpulab.models.transformer import transformer_apply
    with jax.default_matmul_precision("highest"):
        out = transformer_apply(
            params, {"tokens": jnp.asarray([tokens], jnp.int32)}, n_heads=4,
            n_layers=2, compute_dtype=jnp.float32, n_kv_heads=2,
            rope_theta=kw.get("rope_theta", 10000.0))
    return np.asarray(out["logits"][0])


@pytest.mark.parametrize("block", [256, 7])
def test_lm_reference_agrees_with_the_program(lm_params, block):
    tokens = np.random.default_rng(0).integers(0, 128, 37).tolist()
    want = program_logits(lm_params, tokens)[-5:]
    got = LM.last_logits(lm_params, tokens, 5, block=block, **HYPER)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-4)


def test_lm_compare_holds_a_stream_to_the_reference(lm_params):
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, 20).tolist()
    tokens, logprobs = [], []
    for _ in range(4):       # greedy decoding by the program, full forward
        row = program_logits(lm_params, prompt + tokens)[-1].astype(np.float64)
        tokens.append(int(row.argmax()))
        logprobs.append(float(row.max() - np.log(np.exp(row).sum())))
    good = LM.compare(lm_params, prompt, tokens, logprobs, **HYPER)
    assert good["argmax_gap"] == 0 and good["logprob_err"] < 1e-3
    # another rotary base is another model: far outside the tolerance
    bad = LM.compare(lm_params, prompt, tokens, logprobs,
                     **dict(HYPER, rope_theta=100.0))
    assert max(bad.values()) > LM.TOLERANCE
    # a wrong token is caught by the gap to the reference's best logit
    wrong = list(tokens)
    wrong[2] = (wrong[2] + 1) % 128
    assert LM.compare(lm_params, prompt, wrong, logprobs,
                      **HYPER)["argmax_gap"] > LM.TOLERANCE

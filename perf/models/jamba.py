"""Model adapter, kind ``jamba``: AI21-Jamba2-3B behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``, whose warm-up,
gauges, client calls and shutdown this adapter inherits): weights ->
``ContinuousBatcher`` -> ``InferenceManager.serve(generation_engines=)`` ->
streamed over gRPC.  What differs is what the model forces: the engine is
handed a ``ModelSpec`` (``tpulab.models.spec.jamba_spec`` of the
configuration's published keys), so 26 of its 28 layers run the Mamba mixer
over a per-lane recurrent state beside the page store, which holds the two
attention layers alone; the output head is tied to the embedding; no
dispatch-plan option is passed here either.  Weights: the program's own tree
(``tpulab.models.spec.init_params`` through ``jax.eval_shape``) filled on
the device in bf16 from the seed: matrices normal 0.02, norm scales 1, the
SSM leaves by the published Mamba initialisation (``fill_rule``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

from harness.sizes import rng_for, seed_words
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME


def fill_rule(path: str, shape, key, d_conv: int):
    """One leaf of the tree, float32, by its name: what
    ``tpulab.models.spec.init_params`` draws for it."""
    import jax
    import jax.numpy as jnp

    if path.endswith("['scale']") or path.endswith("['d']"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("['a_log']"):          # (d_state, d_inner)
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if path.endswith("['dt_bias']"):        # softplus^-1 of a log-uniform dt
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if path.endswith("['conv_w']") or path.endswith("['conv_b']"):
        bound = d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def make_weights(tree, seed: int, d_conv: int):
    """``tree`` filled in bf16, on the device, by one jitted call."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def fill(key):
        return jax.tree_util.tree_unflatten(treedef, [
            fill_rule(jax.tree_util.keystr(path), leaf.shape,
                      jax.random.fold_in(key, i), d_conv).astype(jnp.bfloat16)
            for i, (path, leaf) in enumerate(leaves)])

    key = jax.random.key(seed_words(seed, 1)[0], impl="rbg")
    return jax.jit(fill)(key)


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        # not ``lm.Adapter.__init__``: its ``hyper_of`` refuses a tied head
        # and it reads ``rope_theta``, which this model does not have
        from tpulab.models.spec import jamba_spec
        if cell.chips != 1:
            raise ValueError("kind jamba is served on one chip")
        if not cell.config.get("tie_word_embeddings"):
            raise ValueError("this adapter builds a tied output head")
        self.cell, self.seed, self.say = cell, seed, say
        self.spec = jamba_spec(cell.config)
        self.hyper = dict(vocab=int(cell.config["vocab_size"]),
                          d_ff=int(cell.config["intermediate_size"]))
        self.sizes = cell.traffic["engine"]
        self.engine = self.manager = self.params = None

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        import tpulab
        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.spec import init_params
        tree = jax.eval_shape(partial(init_params, self.spec,
                                      self.hyper["vocab"],
                                      self.hyper["d_ff"]))
        self.params = jax.block_until_ready(
            make_weights(tree, self.seed, self.spec.d_conv))
        sz = self.sizes
        page = int(sz["page_size"])
        self.engine = cb = ContinuousBatcher(
            self.params, self.spec.n_heads, self.spec.n_layers,
            spec=self.spec, lanes=int(sz["lanes"]),
            max_len=int(sz["max_len"]), page_size=page,
            n_pages=int(sz["pool_tokens"]) // page + 1,
            compute_dtype=jnp.bfloat16)
        self.say(f"engine plan selected by the program: ragged={cb.ragged} "
                 f"use_kernel={cb.use_kernel} decode_block={cb.decode_block}"
                 f"; lanes={cb.lanes} max_len={cb.max_len} page_size="
                 f"{cb.page_size} pool_pages={cb.pool.n_pages} pool_bytes="
                 f"{cb.pool.hbm_bytes} pool_layers={cb.pool.n_layers} "
                 f"bytes_per_token={cb.pool.bytes_per_token} state_bytes="
                 f"{cb.state.hbm_bytes} state_bytes_per_lane="
                 f"{cb.state.bytes_per_lane}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def check_reference(self, client) -> bool:
        """As kind ``lm``: greedy streams through the Generate RPC, held to
        the plain reference on logits, the largest difference over a
        stream's tokens; the reference says how many tokens."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps = reference.REFERENCE_STEPS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        prompts = [rng_for(self.seed, 0x4EF, i).integers(
            0, self.hyper["vocab"], n).tolist() for i, n in enumerate(lens)]
        reply = client.call({
            "op": "generate", "model": MODEL_NAME, "logprobs": True,
            "concurrency": 1,
            "requests": [{"prompt": p, "steps": steps} for p in prompts]})
        ok = True
        for n, prompt, res in zip(lens, prompts, reply["results"]):
            if not res["ok"] or len(res["tokens"]) != steps:
                self.say(f"reference check: prompt of {n} failed: "
                         f"{res['error']} ({len(res['tokens'])} tokens)")
                ok = False
                continue
            got = reference.compare(self.params, prompt, res["tokens"],
                                    res["logprobs"], **hyper)
            good = max(got["logprob_err"],
                       got["argmax_gap"]) <= reference.TOLERANCE
            ok &= good
            self.say(f"reference check: prompt of {n} tokens, {steps} greedy "
                     f"tokens through the Generate RPC: logprob_err="
                     f"{got['logprob_err']:.4g} argmax_gap="
                     f"{got['argmax_gap']:.4g} (tolerance "
                     f"{reference.TOLERANCE}; logprob_err median "
                     f"{got['logprob_err_median']:.4g}) -> "
                     f"{'agrees' if good else 'DISAGREES'}")
        return ok

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {"dispatch": state["dispatch"], "pool": state["pool"],
                "state": state["state"]}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

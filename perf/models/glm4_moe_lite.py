"""Model adapter, kind ``glm4_moe_lite``: GLM-4.7-Flash behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``, whose warm-up,
gauges, client calls and shutdown this adapter inherits): weights ->
``ContinuousBatcher`` -> ``InferenceManager.serve(generation_engines=)`` ->
streamed over gRPC.  What differs is what the model forces: the engine is
handed a ``ModelSpec`` (``tpulab.models.spec.glm4_moe_lite_spec`` of the
configuration's published keys), so its page store holds latent rows and
its layer block runs absorbed MLA and the routed expert FFN; no
dispatch-plan option is passed here either.  Weights: the program's own
tree (``tpulab.models.spec.init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed, every weight normal 0.02 (the
router's selection bias too), norm scales 1.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

from harness.sizes import rng_for
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        super().__init__(cell, seed, say)
        from tpulab.models.spec import glm4_moe_lite_spec
        if cell.chips != 1:
            raise ValueError("kind glm4_moe_lite is served on one chip")
        self.spec = glm4_moe_lite_spec(cell.config)

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        import tpulab
        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.spec import init_params
        tree = jax.eval_shape(partial(init_params, self.spec,
                                      self.hyper["vocab"],
                                      self.hyper["d_ff"]))
        self.params = jax.block_until_ready(lm.make_weights(tree, self.seed))
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
                 f"{cb.pool.hbm_bytes} entry={cb.pool.entry_kind} "
                 f"bytes_per_token={cb.pool.bytes_per_token}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def check_reference(self, client) -> bool:
        """As kind ``lm``: greedy streams through the Generate RPC, held
        to the plain reference on logits; the reference says how many
        tokens and judges their lower quartile (a flipped expert near a
        routing tie moves single tokens: ``perf/reference/
        glm4_moe_lite.py``)."""
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
                     f"tokens through the Generate RPC, lower quartiles "
                     f"over them: logprob_err={got['logprob_err']:.4g} "
                     f"argmax_gap={got['argmax_gap']:.4g} (tolerance "
                     f"{reference.TOLERANCE}; logprob_err median "
                     f"{got['logprob_err_median']:.4g}, largest "
                     f"{got['logprob_err_max']:.4g}, "
                     f"{100 * got['flipped_share']:.0f} % of the tokens past "
                     f"0.05: flipped experts) -> "
                     f"{'agrees' if good else 'DISAGREES'}")
        return ok

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {"dispatch": state["dispatch"], "pool": state["pool"],
                "moe": state["moe"]}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

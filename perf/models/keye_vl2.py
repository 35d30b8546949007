"""Model adapter, kind ``keye_vl2``: the decoder of Keye-VL-2.0-30B-A3B behind
the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, gauges,
client calls and shutdown are inherited): weights -> ``ContinuousBatcher`` ->
``InferenceManager.serve(generation_engines=)`` -> streamed over gRPC.  Like
kind ``glm4_moe_lite``, whose ``build`` this adapter runs as it stands, the
engine is handed a ``ModelSpec``; here
``tpulab.models.spec.keye_vl2_spec`` of the published keys and ``sa_config``,
so the page store holds K/V pages with an index key a token beside them, and
the layer block runs the learned indexer, attention over the keys it selects
and softmax-routed experts.  No dispatch-plan option is passed.  Weights: the
program's own tree (``init_params`` through ``jax.eval_shape``) filled on the
device in bf16 from the seed, every weight normal 0.02, norm scales 1.
"""

from __future__ import annotations

from typing import Any, Dict

from harness.sizes import rng_for
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
glm = load_module("models", "glm4_moe_lite")
MODEL_NAME = lm.MODEL_NAME


class Adapter(glm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        lm.Adapter.__init__(self, cell, seed, say)
        from tpulab.models.spec import keye_vl2_spec
        if cell.chips != 1:
            raise ValueError("kind keye_vl2 is served on one chip")
        self.spec = keye_vl2_spec(cell.config)

    def check_reference(self, client) -> bool:
        """As kind ``glm4_moe_lite``: greedy streams through the Generate
        RPC, held to the plain reference on the lower quartile of their
        tokens' errors; here ``REFERENCE_STREAMS`` streams a prompt length
        (prompts drawn apart: a greedy stream on seeded weights settles on
        one token and so carries one error), their tokens judged together;
        the limit is the reference's for the streams' longest context
        (under ``topk`` keys every key is selected and the limit is the
        tighter one: ``perf/reference/keye_vl2.py``)."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        # stream 0 of length i is draw i, as kind ``glm4_moe_lite`` has it
        prompts = [rng_for(self.seed, 0x4EF, i + len(lens) * j).integers(
            0, self.hyper["vocab"], n).tolist()
            for i, n in enumerate(lens) for j in range(streams)]
        reply = client.call({
            "op": "generate", "model": MODEL_NAME, "logprobs": True,
            "concurrency": 1,
            "requests": [{"prompt": p, "steps": steps} for p in prompts]})
        ok = True
        asked = list(zip(prompts, reply["results"]))
        for i, n in enumerate(lens):
            errors = []
            for prompt, res in asked[i * streams:(i + 1) * streams]:
                if not res["ok"] or len(res["tokens"]) != steps:
                    self.say(f"reference check: prompt of {n} failed: "
                             f"{res['error']} ({len(res['tokens'])} tokens)")
                    ok = False
                    continue
                errors.append(reference.token_errors(
                    self.params, prompt, res["tokens"], res["logprobs"],
                    **hyper))
            if not errors:
                continue
            got = reference.summary(errors)
            each = ", ".join(f"{reference.summary([e])['logprob_err']:.4g}"
                             for e in errors)
            limit = reference.tolerance(n + steps, **hyper)
            good = max(got["logprob_err"], got["argmax_gap"]) <= limit
            ok &= good
            self.say(f"reference check: {len(errors)} prompts of {n} tokens, "
                     f"{steps} greedy tokens each through the Generate RPC, "
                     f"lower quartiles over all of them: "
                     f"logprob_err={got['logprob_err']:.4g} "
                     f"argmax_gap={got['argmax_gap']:.4g} (tolerance "
                     f"{limit}; a stream alone {each}; logprob_err median "
                     f"{got['logprob_err_median']:.4g}, largest "
                     f"{got['logprob_err_max']:.4g}, "
                     f"{100 * got['flipped_share']:.0f} % of the tokens past "
                     f"0.05) -> {'agrees' if good else 'DISAGREES'}")
        return ok

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {"dispatch": state["dispatch"], "pool": state["pool"],
                "moe": state["moe"], "sparse": state["sparse"]}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

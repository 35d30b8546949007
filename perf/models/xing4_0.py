"""Model adapter, kind ``xing4_0``: Xing4.0-29B-A4B behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, gauges,
client calls and shutdown are inherited): weights -> ``ContinuousBatcher(
spec=)`` -> ``InferenceManager.serve(generation_engines=)`` -> streamed over
gRPC.  The engine is handed ``tpulab.models.spec.xing4_spec`` of the
published keys, so its page store holds latent rows, its layer block runs
absorbed latent attention under YaRN and the routed expert FFN, and a
token's residual is ``hc_mult`` streams mixed a sublayer by its
hyper-connection.  No dispatch-plan option is passed.

Weights: the program's own tree (``init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed, matrices normal 0.02 (the
router's selection bias too), norm scales 1; a sublayer's hyper-connection
is the program's seeded one (``tpulab.models.spec.init_hyper_connection``:
the configuration's ``assumed`` says why it is not normal 0.02).  ``wq_b``
is drawn as the PUBLISHED ``q_b_proj`` and multiplied by the factor YaRN
puts on the softmax scale (``tpulab.models.spec.mla_scales``) by the
program's own ``scale_queries`` inside the same jitted fill; the published matrices are
kept for the reference check (57 MB) and stand in the tree the reference
reads, so the fold is part of what ``correct`` compares.

``correct`` as kind ``longcat_flash`` judges it: ``REFERENCE_STREAMS``
greedy streams a prompt length through the Generate RPC on the timed engine,
logits against ONE full forward of the plain reference each, the lower
quartile over a length's tokens under the reference's limit.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

from harness.sizes import rng_for, seed_words
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME


def weights_key(seed: int):
    import jax
    # the hardware generator: threefry over 4.8 G values is seconds of set-up
    return jax.random.key(seed_words(seed, 1)[0], impl="rbg")


def make_weights(tree, spec, config, seed: int):
    """``(params, published wq_b a layer)``: ``tree`` filled in bf16, on the
    device, by one jitted call."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.spec import (init_hyper_connection, mla_scales,
                                    scale_queries)

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    q_scale = mla_scales(config)[0]

    def leaf(path: str, shape, key):
        if path.endswith("['scale']"):
            return jnp.ones(shape, jnp.float32)
        return 0.02 * jax.random.normal(key, shape, jnp.float32)

    def fill(key):
        params = jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.tree_util.keystr(path), x.shape,
                 jax.random.fold_in(key, i)).astype(jnp.bfloat16)
            for i, (path, x) in enumerate(leaves)])
        published = [params[f"layer{i}"]["wq_b"]
                     for i in range(spec.n_layers)]
        params = scale_queries(params, spec, q_scale)
        for i in range(spec.n_layers):
            p = params[f"layer{i}"]
            for j, name in enumerate(("hc_attn", "hc_ffn")):
                p[name] = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.bfloat16), init_hyper_connection(
                        jax.random.fold_in(key, 1 << 20 | 2 * i + j), spec))
        return params, published

    return jax.jit(fill)(weights_key(seed))


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        super().__init__(cell, seed, say)
        from tpulab.models.spec import xing4_spec
        if cell.chips != 1:
            raise ValueError("kind xing4_0 is served on one chip")
        self.spec = xing4_spec(cell.config)
        self.published_wq_b = None

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        import tpulab
        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.spec import init_params
        tree = jax.eval_shape(partial(init_params, self.spec,
                                      self.hyper["vocab"],
                                      self.hyper["d_ff"]))
        self.params, self.published_wq_b = jax.block_until_ready(
            make_weights(tree, self.spec, self.cell.config, self.seed))
        sz = self.sizes
        page = int(sz["page_size"])
        self.engine = cb = ContinuousBatcher(
            self.params, self.spec.n_heads, self.spec.n_layers,
            spec=self.spec, lanes=int(sz["lanes"]),
            max_len=int(sz["max_len"]), page_size=page,
            n_pages=int(sz["pool_tokens"]) // page + 1,
            compute_dtype=jnp.bfloat16)
        n = sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params))
        self.say(f"engine plan selected by the program: ragged={cb.ragged} "
                 f"use_kernel={cb.use_kernel} decode_block={cb.decode_block}"
                 f"; lanes={cb.lanes} max_len={cb.max_len} page_size="
                 f"{cb.page_size} pool_pages={cb.pool.n_pages} pool_bytes="
                 f"{cb.pool.hbm_bytes} entry={cb.pool.entry_kind} "
                 f"bytes_per_token={cb.pool.bytes_per_token} parameters={n} "
                 f"streams={self.spec.hc_mult} sinkhorn_iters="
                 f"{self.spec.hc_sinkhorn_iters}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def published_tree(self):
        """The tree the reference reads: the served one, each layer's
        ``wq_b`` the published ``q_b_proj`` again."""
        tree = dict(self.params)
        for i, wq_b in enumerate(self.published_wq_b):
            tree[f"layer{i}"] = dict(self.params[f"layer{i}"], wq_b=wq_b)
        return tree

    def check_reference(self, client) -> bool:
        """Greedy streams through the Generate RPC, ``REFERENCE_STREAMS`` a
        prompt length (prompts drawn apart), prefill in rounds then decode
        through the latent pages; a length's tokens are judged together
        against ONE forward of the plain reference a stream: the lower
        quartile of their errors under the reference's limit."""
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
        published = self.published_tree()
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
                    published, prompt, res["tokens"], res["logprobs"],
                    **hyper))
            if not errors:
                continue
            got = reference.summary(errors)
            each = ", ".join(f"{reference.summary([e])['logprob_err']:.4g}"
                             for e in errors)
            limit = reference.tolerance(n)
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
        # the published matrices are the check's alone
        self.published_wq_b = None
        return ok

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {name: state[name] for name in ("dispatch", "pool", "moe",
                                               "mhc")}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

"""Model adapter, kind ``longcat_flash``: LongCat-Flash-Chat behind the Generate
RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, gauges,
client calls and shutdown are inherited): weights -> ``ContinuousBatcher(
spec=)`` -> ``InferenceManager.serve(generation_engines=)`` -> streamed over
gRPC.  The engine is handed ``tpulab.models.spec.longcat_flash_spec`` of the
published keys and of the share the configuration states (``share``: the
layer's published count of FFN experts and the first one held;
``n_routed_experts`` is what this chip holds), so a published layer runs as
two engine layers on two layers of the latent page store, the first of each
pair routing over all 768 columns, computing the part its 16 experts give
and the identity columns' part, and handing both to the second, which adds
them after its own FFN.  No dispatch-plan option is passed.

Weights: the program's own tree (``init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed, matrices normal 0.02, norm
scales 1, the selection bias normal ``BIAS_STD`` (the scale of the scores).
The three attention matrices the program changes when it lays its
parameters out are drawn in their PUBLISHED form (``published_attention``)
and put through ``tpulab.models.spec.longcat_flash_layout`` (the two
``mla_scale`` factors folded, rope columns reordered) inside the same jitted
fill; for the reference check they are drawn again, as published, and stand
in the tree the reference reads, so the layout is part of what ``correct``
compares.

``correct`` as kind ``keye_vl2`` judges it: ``REFERENCE_STREAMS`` greedy
streams a prompt length through the Generate RPC on the timed engine,
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
#: the selection bias's deviation: 1.5 mean scores (the configuration's
#: ``assumed`` says why)
BIAS_STD = 1.5 / 768


def spec_of(config: Dict[str, Any]):
    """The ``ModelSpec`` of a configuration file: the published keys with
    the router at its published width, this chip's share of the experts."""
    from tpulab.models.spec import longcat_flash_spec
    share = config["share"]
    return longcat_flash_spec(
        dict(config, n_routed_experts=share["n_routed_experts"]),
        first=int(share["first_expert"]),
        held=int(config["n_routed_experts"]))


def published_attention(spec, key, layer: int):
    """Engine layer ``layer``'s ``(q_b_proj, kv_a_proj_with_mqa,
    kv_b_proj)`` as published (inputs by outputs), bf16."""
    import jax
    import jax.numpy as jnp
    h = spec.n_heads
    shapes = ((spec.q_lora_rank, h * spec.qk_head_dim),
              (spec.d_model, spec.latent_width),
              (spec.kv_lora_rank, h * (spec.qk_nope_head_dim
                                       + spec.v_head_dim)))
    key = jax.random.fold_in(key, 1 << 20 | layer)
    return tuple((0.02 * jax.random.normal(jax.random.fold_in(key, j), shape,
                                           jnp.float32)).astype(jnp.bfloat16)
                 for j, shape in enumerate(shapes))


def weights_key(seed: int):
    import jax
    # the hardware generator: threefry over 5 G values is seconds of set-up
    return jax.random.key(seed_words(seed, 1)[0], impl="rbg")


def make_weights(tree, spec, config, seed: int):
    """``tree`` filled in bf16, on the device, by one jitted call."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.spec import longcat_flash_layout, mla_scales

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    scales = mla_scales(config)

    def leaf(path: str, shape, key):
        if path.endswith("['scale']"):
            return jnp.ones(shape, jnp.float32)
        std = BIAS_STD if path.endswith("['bias']") else 0.02
        return std * jax.random.normal(key, shape, jnp.float32)

    def fill(key):
        params = jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.tree_util.keystr(path), x.shape,
                 jax.random.fold_in(key, i)).astype(jnp.bfloat16)
            for i, (path, x) in enumerate(leaves)])
        for i in range(spec.n_layers):
            served = longcat_flash_layout(
                *(w.astype(jnp.float32)
                  for w in published_attention(spec, key, i)), spec, *scales)
            params[f"layer{i}"].update(zip(
                ("wq_b", "wkv_a", "w_uk", "w_uv"),
                (w.astype(jnp.bfloat16) for w in served)))
        return params

    return jax.jit(fill)(weights_key(seed))


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        self.cell, self.seed, self.say = cell, seed, say
        if cell.chips != 1:
            raise ValueError("kind longcat_flash is served on one chip (the "
                             "first chip's share of a 32-chip layer)")
        self.hyper = dict(vocab=int(cell.config["vocab_size"]),
                          d_ff=int(cell.config["ffn_hidden_size"]))
        self.sizes = cell.traffic["engine"]
        self.engine = self.manager = self.params = None
        self.spec = spec_of(cell.config)

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
                 f"pool_layers={cb.pool.n_layers} bytes_per_token="
                 f"{cb.pool.bytes_per_token} parameters={n} experts="
                 f"{self.spec.expert_first}..+{self.spec.experts_held} of "
                 f"{self.spec.ffn_experts} + {self.spec.zero_experts} "
                 f"identity columns")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def published_tree(self):
        """The tree the reference reads: the served one, each engine layer's
        ``wq_b`` / ``wkv_a`` the published matrices again and ``kv_b`` where
        the served halves stood (drawn anew from the seed: 0.49 GB, held
        while the check runs)."""
        import jax
        spec = self.spec
        drawn = jax.jit(lambda key: [published_attention(spec, key, i)
                                     for i in range(spec.n_layers)])(
            weights_key(self.seed))
        tree = dict(self.params)
        for i, (wq_b, wkv_a, kv_b) in enumerate(drawn):
            layer = {k: v for k, v in self.params[f"layer{i}"].items()
                     if k not in ("w_uk", "w_uv")}
            tree[f"layer{i}"] = dict(layer, wq_b=wq_b, wkv_a=wkv_a, kv_b=kv_b)
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
            limit = reference.TOLERANCE
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
                "moe": state["moe"]}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

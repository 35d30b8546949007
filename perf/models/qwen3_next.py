"""Model adapter, kind ``qwen3_next``: the decoder of Qwen3-Next-80B-A3B-Instruct
behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, gauges,
client calls and shutdown are inherited): weights -> ``ContinuousBatcher(
spec=)`` -> ``InferenceManager.serve(generation_engines=)`` -> streamed over
gRPC.  The engine is handed ``tpulab.models.spec.qwen3_next_spec`` of the
published keys and of the share the configuration states (``share``: the
router's published width and the first expert held; ``num_experts`` is what
this chip holds), so six of its eight layers run the Gated DeltaNet mixer
over a matrix-valued per-lane state beside the page store, which holds the
two gated-attention layers alone, and every layer routes over all 512 experts
and computes the part its 128 give.  No dispatch-plan option is passed.
Weights: the program's own tree (``init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed: matrices normal 0.02, norm
scales 1, the Gated DeltaNet leaves by ``fill_rule``.

``correct`` holds three numbers of every prompt length to the reference,
each to a limit of its own (``perf/reference/qwen3_next.py``): the streams'
log-probabilities as kind ``keye_vl2`` judges them (four streams a prompt
length, one lower quartile), and, read where the server holds them once a
stream has ended, the first Gated DeltaNet layer's state of the stream's
lane and the first attention layer's rows in the stream's pages
(``debug_state()["last_release"]`` names the lane and the pages): a state
or a K/V store kept in a lower precision than the configuration states
reads inside bf16 serving's band on the logits, and outside it there.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import numpy as np

from harness.sizes import rng_for, seed_words
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME


def spec_of(config: Dict[str, Any]):
    """The ``ModelSpec`` of a configuration file: the published keys with
    the router at its published width, this chip's share of the experts."""
    from tpulab.models.spec import qwen3_next_spec
    share = config["share"]
    return qwen3_next_spec(dict(config, num_experts=share["num_experts"]),
                           first=int(share["first_expert"]),
                           held=int(config["num_experts"]))


def fill_rule(path: str, shape, key, d_conv: int):
    """One leaf of the tree, float32, by its name: what
    ``tpulab.models.spec.init_params`` draws for it."""
    import jax
    import jax.numpy as jnp

    if path.endswith("['scale']"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("['a_log']"):          # log of U(0, 16), a value head
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-6, 16.0))
    if path.endswith("['dt_bias']"):        # softplus^-1 of a log-uniform dt
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if path.endswith("['conv_w']"):
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
        super().__init__(cell, seed, say)
        if cell.chips != 1:
            raise ValueError("kind qwen3_next is served on one chip (the "
                             "first chip's share of a four-chip layer)")
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
                 f"bytes_per_token={cb.pool.bytes_per_token} state_kind="
                 f"{cb.state.kind} state_bytes={cb.state.hbm_bytes} "
                 f"state_bytes_per_lane={cb.state.bytes_per_lane} experts="
                 f"{self.spec.expert_first}..+{self.spec.experts_held} of "
                 f"{self.spec.n_experts}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def served_stores(self, length: int):
        """``(state (Hv, d_k, d_v), kv (2, length, Hkv * D))`` of the
        request that ended last, float32 on the host: the first Gated
        DeltaNet layer's state in its lane's slot and the first attention
        layer's rows in its pages, which keep them until another request
        takes the lane or the pages; None unless that request took in
        exactly ``length`` tokens."""
        import jax.numpy as jnp
        cb = self.engine
        held = cb.debug_state()["last_release"]
        if held is None or held["length"] != length:
            return None
        state = np.asarray(cb.state.arrays[0][0, held["lane"]], np.float32)
        pages = np.asarray(held["pages"], np.int32)
        kv = np.asarray(cb.pool.kv[0, pages].astype(jnp.float32))
        # (pages, 2, page size, row) -> (2, tokens, row)
        kv = np.moveaxis(kv, 1, 0).reshape(2, -1, kv.shape[-1])
        return state, kv[:, :length]

    def check_reference(self, client) -> bool:
        """Greedy streams through the Generate RPC, one at a time,
        ``REFERENCE_STREAMS`` a prompt length (prompts drawn apart: a greedy
        stream on seeded weights settles on one token and so carries one
        error).  After each, what the server holds of it
        (:meth:`served_stores`).  A length's streams are judged together
        against ONE forward of the plain reference each: their tokens'
        errors on the lower quartile (kind ``keye_vl2``'s construction),
        their states' and their K/V rows' on the median stream, each under
        the reference's limit for it."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        limits = {"logprob_err": reference.TOLERANCE,
                  "argmax_gap": reference.TOLERANCE,
                  "state_err": reference.STATE_TOLERANCE,
                  "kv_err": reference.KV_TOLERANCE}
        ok = True
        for i, n in enumerate(lens):
            errors = []
            for j in range(streams):
                # stream 0 of length i is draw i, as kind ``glm4_moe_lite``
                # has it
                prompt = rng_for(self.seed, 0x4EF, i + len(lens) * j).integers(
                    0, self.hyper["vocab"], n).tolist()
                res = client.call({
                    "op": "generate", "model": MODEL_NAME, "logprobs": True,
                    "concurrency": 1,
                    "requests": [{"prompt": prompt, "steps": steps}]
                })["results"][0]
                if not res["ok"] or len(res["tokens"]) != steps:
                    self.say(f"reference check: prompt of {n} failed: "
                             f"{res['error']} ({len(res['tokens'])} tokens)")
                    ok = False
                    continue
                # the last token emitted is never taken in
                stores = self.served_stores(n + steps - 1)
                if stores is None:
                    self.say(f"reference check: prompt of {n}: the request "
                             "released last is not this stream's")
                    ok = False
                    continue
                errors.append(reference.token_errors(
                    self.params, prompt, res["tokens"], res["logprobs"],
                    stores=stores, **hyper))
            if not errors:
                continue
            got = reference.summary(errors)
            good = all(got[name] <= limit for name, limit in limits.items())
            ok &= good
            each = "; ".join(
                f"{name} " + ", ".join(
                    f"{reference.summary([e])[name]:.4g}" for e in errors)
                for name in ("logprob_err", "state_err", "kv_err"))
            self.say(f"reference check: {len(errors)} prompts of {n} tokens, "
                     f"{steps} greedy tokens each through the Generate RPC: "
                     + " ".join(f"{name}={got[name]:.4g} (limit {limit})"
                                for name, limit in limits.items())
                     + f" (lower quartiles over all the tokens, medians "
                     f"over the streams' stores; a stream alone: {each}; "
                     f"logprob_err median {got['logprob_err_median']:.4g}, "
                     f"largest {got['logprob_err_max']:.4g}, "
                     f"{100 * got['flipped_share']:.0f} % of the tokens past "
                     f"0.05) -> {'agrees' if good else 'DISAGREES'}")
        return ok

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {"dispatch": state["dispatch"], "pool": state["pool"],
                "moe": state["moe"], "state": state["state"]}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

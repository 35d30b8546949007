"""Model adapter, kind ``zaya``: ZAYA1-8B behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, gauges,
client calls and shutdown are inherited): weights -> ``ContinuousBatcher(
spec=)`` -> ``InferenceManager.serve(generation_engines=)`` -> streamed over
gRPC.  The engine is handed ``tpulab.models.spec.zaya_spec`` of the published
keys, so every layer runs compressed convolutional attention on K/V pages
AND a lane state (the convolutions' tails and the value's shifted half: a
layer of the page store and a layer of the lane-state store each) and an
expert block behind the MLP router with depth averaging and a skip column;
the head is the embedding.  No dispatch-plan option is passed.

Weights: the program's own tree (``init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed: matrices and biases normal 0.02,
norm scales 1, and the leaves 0.02 would switch off by the program's own
``tpulab.models.spec.zaya_leaf`` (the configuration's ``assumed`` says which
and why).

``correct`` holds eight numbers of every prompt length to the reference,
each to a limit of its own (``perf/reference/zaya.py``): the streams'
log-probabilities as kind ``xing4_0`` judges them (four streams a prompt
length, one lower quartile, a limit a length), and, read where the server
holds them once a stream has ended (``debug_state()["last_release"]`` names
the lane and the pages, as kind ``qwen3_next`` reads its stores), EVERY
layer's K/V rows in the stream's pages and three tails in the stream's lane.
Of layer 0, which no router reaches: the median row (a store kept narrower),
the LARGEST row (a tail lost at a chunk boundary is one wrong key a
boundary) and the tails.  Of all sixteen, in forms a flipped expert leaves
standing: the worst layer's median row, tails and rows at which a chunk of
the prompt began.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import numpy as np

from harness.sizes import rng_for, seed_words
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the inherited warm-up and window read (``vocab``); the rest of
    the shape comes from the spec."""
    return dict(vocab=int(config["vocab_size"]),
                d_model=int(config["hidden_size"]),
                n_layers=int(config["num_hidden_layers"]))


def make_weights(tree, seed: int):
    """``tree`` filled in bf16, on the device, by one jitted call."""
    import jax
    import jax.numpy as jnp
    from tpulab.models.spec import zaya_leaf

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def leaf(path: str, shape, key):
        if path.endswith("['scale']"):
            return jnp.ones(shape, jnp.float32)
        drawn = zaya_leaf(path, shape, key)
        if drawn is not None:
            return drawn
        return 0.02 * jax.random.normal(key, shape, jnp.float32)

    def fill(key):
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(jax.tree_util.keystr(path), x.shape,
                 jax.random.fold_in(key, i)).astype(jnp.bfloat16)
            for i, (path, x) in enumerate(leaves)])

    # the hardware generator: threefry over 3.9 G values is seconds of set-up
    key = jax.random.key(seed_words(seed, 1)[0], impl="rbg")
    return jax.jit(fill)(key)


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        # (not lm.Adapter's: this kind has no dense FFN width to read)
        self.cell, self.seed, self.say = cell, seed, say
        from tpulab.models.spec import zaya_spec
        if cell.chips != 1:
            raise ValueError("kind zaya is served on one chip")
        self.hyper = hyper_of(cell.config)
        self.sizes = cell.traffic["engine"]
        self.engine = self.manager = self.params = None
        self.spec = zaya_spec(cell.config)

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        import tpulab
        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.spec import init_params
        tree = jax.eval_shape(partial(init_params, self.spec,
                                      self.hyper["vocab"], 0))
        self.params = jax.block_until_ready(make_weights(tree, self.seed))
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
                 f"{cb.pool.hbm_bytes} pool_layers={cb.pool.n_layers} "
                 f"bytes_per_token={cb.pool.bytes_per_token} state_kind="
                 f"{cb.state.kind} state_bytes_per_lane="
                 f"{cb.state.bytes_per_lane} parameters={n} router="
                 f"{self.spec.router} columns={self.spec.n_experts} "
                 f"skip_columns={self.spec.zero_experts}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def served_stores(self, length: int):
        """``(state (L, 2 * 1280 + 128), kv (L, 2, length, Hkv * D))`` of
        the request that ended last, float32 on the host: every layer's
        three tails in its lane's slot (``[c ; a ; h W_v2]``, the one row
        each keeps at two taps) and every layer's rows in its pages, which
        keep them until another request takes the lane or the pages; None
        unless that request took in exactly ``length`` tokens."""
        cb = self.engine
        held = cb.debug_state()["last_release"]
        if held is None or held["length"] != length:
            return None
        f32 = lambda x: np.asarray(x).astype(np.float32)   # bf16 comes over
        state = np.concatenate(
            [f32(t[:, -1, held["lane"]]) for t in cb.state.arrays], axis=-1)
        pages = np.asarray(held["pages"], np.int32)
        kv = f32(cb.pool.kv[:, pages])
        # (L, pages, 2, page size, row) -> (L, 2, tokens, row)
        kv = np.moveaxis(kv, 2, 1).reshape(kv.shape[0], 2, -1, kv.shape[-1])
        return state, kv[:, :, :length]

    def seams(self, prompt_len: int):
        """The rows at which a chunk of a prompt that prefills alone begins,
        the first left out (it starts from zeros): where a round took its
        windows from the lane's tails."""
        budget = self.engine.debug_state()["dispatch"]["round_budget"]
        return list(range(budget, prompt_len, budget))

    def check_reference(self, client) -> bool:
        """Greedy streams through the Generate RPC, one at a time,
        ``REFERENCE_STREAMS`` a prompt length (prompts drawn apart).  After
        each, what the server holds of it (:meth:`served_stores`).  A
        length's streams are judged together against ONE forward of the
        plain reference each: their tokens' errors on the lower quartile
        under the length's limit, their stores' as ``reference.summary``
        joins them, each under the reference's limit for it."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        ok = True
        for i, n in enumerate(lens):
            limits = {"logprob_err": reference.tolerance(n),
                      "argmax_gap": reference.tolerance(n),
                      "kv_err": reference.KV_TOLERANCE,
                      "kv_row_max": reference.KV_ROW_TOLERANCE,
                      "state_err": reference.STATE_TOLERANCE,
                      "layers_kv_err": reference.LAYERS_TOLERANCE,
                      "layers_state_err": reference.LAYERS_TOLERANCE,
                      "layers_seam_err": reference.LAYERS_TOLERANCE}
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
                    stores=stores, seams=self.seams(n), **hyper))
            if not errors:
                continue
            got = reference.summary(errors)
            # (a prompt of one chunk has no seam to read)
            limits = {name: limit for name, limit in limits.items()
                      if name in got}
            good = all(got[name] <= limit for name, limit in limits.items())
            ok &= good
            each = "; ".join(
                f"{name} " + ", ".join(
                    f"{reference.summary([e])[name]:.4g}" for e in errors)
                for name in ("logprob_err", "kv_err", "kv_row_max",
                             "state_err"))
            self.say(f"reference check: {len(errors)} prompts of {n} tokens, "
                     f"{steps} greedy tokens each through the Generate RPC: "
                     + " ".join(f"{name}={got[name]:.4g} (limit {limit})"
                                for name, limit in limits.items())
                     + f" (lower quartiles over all the tokens, medians "
                     f"over the streams' stores of layer 0, the worst of "
                     f"{self.spec.n_layers} layers; a stream alone: {each}; "
                     f"logprob_err median {got['logprob_err_median']:.4g}, "
                     f"largest {got['logprob_err_max']:.4g}, "
                     f"{100 * got['flipped_share']:.0f} % of the tokens past "
                     f"0.05) -> {'agrees' if good else 'DISAGREES'}")
        return ok

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {name: state[name] for name in ("dispatch", "pool", "moe",
                                               "state", "cca")}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

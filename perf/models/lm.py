"""Model adapter, kind ``lm``: a decoder-only transformer behind the Generate RPC.

Builds the system under test the way ``chip_smoke.py`` shows the normal path:
weights -> ``ContinuousBatcher`` -> ``InferenceManager.serve(
generation_engines=)`` -> streamed over gRPC by the client process.  The
configuration file gives the published keys (Hugging Face names); the traffic
file gives the engine sizes its requests need (``engine``: lanes, max_len,
page_size, pool_tokens).  No dispatch-plan option is passed: ``use_kernel``,
``ragged``, ``prefill_flash``, ``prefill_chunk`` and ``decode_block`` stay at
the program's defaults, and the plan the engine selected is printed.

Weights are made on the device from the seed, in bf16, in one jitted call:
the tree's layout comes from ``jax.eval_shape`` of the program's own
``init_transformer_params`` (which itself draws float32: 29 GB at 7 B
parameters), the values from here.  With ``chips`` > 1 the adapter builds the
mesh of the configuration's ``layout`` and makes the weights sharded.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import numpy as np

from harness.sizes import rng_for, seed_words
from harness.spec import Cell

MODEL_NAME = "lm"
#: tokens asked of each reference-check stream (prefill, then decode through
#: the paged cache)
REFERENCE_STEPS = 8


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The program's names for the published keys."""
    if config.get("sliding_window") is not None:
        raise ValueError("the engine serves full attention only; the "
                         "configuration sets sliding_window")
    if config.get("tie_word_embeddings"):
        raise ValueError("this adapter builds an untied output head")
    return dict(vocab=int(config["vocab_size"]),
                d_model=int(config["hidden_size"]),
                n_heads=int(config["num_attention_heads"]),
                n_kv_heads=int(config["num_key_value_heads"]),
                n_layers=int(config["num_hidden_layers"]),
                d_ff=int(config["intermediate_size"]))


def param_tree(hyper: Dict[str, Any]):
    """Shapes of the program's own parameter tree at these sizes."""
    import jax

    from tpulab.models.transformer import init_transformer_params
    return jax.eval_shape(partial(init_transformer_params, ffn="swiglu",
                                  tie_embeddings=False, seed=0, **hyper))


def make_weights(tree, seed: int, shardings=None):
    """``tree`` filled in bf16, on the device, by one jitted call."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def fill(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            if jax.tree_util.keystr(path).endswith("['scale']"):
                out.append(jnp.ones(leaf.shape, jnp.bfloat16))
            else:
                out.append((0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), leaf.shape, jnp.float32)
                ).astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    # the hardware generator: threefry over 3.7 G values is seconds of set-up
    key = jax.random.key(seed_words(seed, 1)[0], impl="rbg")
    return jax.jit(fill, out_shardings=shardings)(key)


def pow2_buckets(lo: int, hi: int) -> List[int]:
    """The power-of-two buckets that lengths in [lo, hi] pad to."""
    out, b = [], 1 << max(0, (int(lo) - 1).bit_length())
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


class Adapter:
    def __init__(self, cell: Cell, seed: int, say):
        self.cell, self.seed, self.say = cell, seed, say
        self.hyper = hyper_of(cell.config)
        self.rope_theta = float(cell.config["rope_theta"])
        self.sizes = cell.traffic["engine"]
        self.engine = self.manager = self.params = None

    # -- build ------------------------------------------------------------------
    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        import tpulab
        from tpulab.engine.paged import ContinuousBatcher
        tree = param_tree(self.hyper)
        mesh = shardings = None
        if self.cell.chips > 1:
            from tpulab.parallel.mesh import make_mesh
            from tpulab.parallel.sharding import transformer_param_shardings
            layout = self.cell.config["layout"]
            if int(np.prod(list(layout.values()))) != self.cell.chips:
                raise ValueError(f"layout {layout} does not span "
                                 f"{self.cell.chips} chips")
            mesh = make_mesh(dict(layout), jax.devices()[:self.cell.chips])
            shardings = transformer_param_shardings(tree, mesh)
        self.params = jax.block_until_ready(
            make_weights(tree, self.seed, shardings))
        sz = self.sizes
        page = int(sz["page_size"])
        self.engine = ContinuousBatcher(
            self.params, n_heads=self.hyper["n_heads"],
            n_layers=self.hyper["n_layers"],
            n_kv_heads=self.hyper["n_kv_heads"], lanes=int(sz["lanes"]),
            max_len=int(sz["max_len"]), page_size=page,
            n_pages=int(sz["pool_tokens"]) // page + 1,
            compute_dtype=jnp.bfloat16, rope_theta=self.rope_theta,
            mesh=mesh)
        cb = self.engine
        self.say(f"engine plan selected by the program: ragged={cb.ragged} "
                 f"use_kernel={cb.use_kernel} prefill_flash="
                 f"{cb.prefill_flash} prefill_chunk={cb.prefill_chunk} "
                 f"decode_block={cb.decode_block}; lanes={cb.lanes} "
                 f"max_len={cb.max_len} page_size={cb.page_size} "
                 f"pool_pages={cb.pool.n_pages} "
                 f"pool_bytes={cb.pool.hbm_bytes}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def window_args(self) -> Dict[str, Any]:
        return {"model": MODEL_NAME, "vocab": self.hyper["vocab"]}

    # -- correct, part 1: the plain reference, through the normal path -----------
    def check_reference(self, client) -> bool:
        reference = self.cell.module("reference", self.cell.config["kind"])
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        vocab = self.hyper["vocab"]
        prompts = [rng_for(self.seed, 0x4EF, i).integers(0, vocab, n).tolist()
                   for i, n in enumerate(lens)]
        reply = client.call({
            "op": "generate", "model": MODEL_NAME, "logprobs": True,
            "concurrency": 1,
            "requests": [{"prompt": p, "steps": REFERENCE_STEPS}
                         for p in prompts]})
        ok = True
        for n, prompt, res in zip(lens, prompts, reply["results"]):
            if not res["ok"] or len(res["tokens"]) != REFERENCE_STEPS:
                self.say(f"reference check: prompt of {n} failed: "
                         f"{res['error']} ({len(res['tokens'])} tokens)")
                ok = False
                continue
            got = reference.compare(
                self.params, prompt, res["tokens"], res["logprobs"],
                n_layers=self.hyper["n_layers"],
                n_heads=self.hyper["n_heads"],
                n_kv_heads=self.hyper["n_kv_heads"],
                rope_theta=self.rope_theta)
            good = max(got.values()) <= reference.TOLERANCE
            ok &= good
            self.say(f"reference check: prompt of {n} tokens, "
                     f"{REFERENCE_STEPS} greedy tokens through the Generate "
                     f"RPC: logprob_err={got['logprob_err']:.4g} "
                     f"argmax_gap={got['argmax_gap']:.4g} (tolerance "
                     f"{reference.TOLERANCE}) -> "
                     f"{'agrees' if good else 'DISAGREES'}")
        return ok

    # -- warm-up: the shapes this cell's traffic reaches, and no others ----------
    def warm_up(self, client) -> None:
        cb, traffic = self.engine, self.cell.traffic
        lo, hi = (int(traffic["prompt_len"][k]) for k in ("min", "max"))
        steps_max = int(traffic["output_len"]["max"])
        fills: List[int] = []
        if cb.ragged:
            # a prompt advances in chunks of at most RAGGED_CHUNK_CAP; its
            # last chunk pads to a power of two, each a program of its own
            cap = cb.RAGGED_CHUNK_CAP
            fills = [cap + b if b < cap else cap
                     for b in pow2_buckets(1, cap)]
        else:
            fills = [min(b, hi) for b in pow2_buckets(lo, hi)]
        requests = [{"index": i, "prompt_len": n, "steps": 2}
                    for i, n in enumerate(fills)]
        self._generate(client, requests, concurrency=1)
        # decode blocks: K follows queue pressure and the steps left, so a
        # burst larger than the lanes walks the menu 8, 4, 2, 1 (a closed
        # loop as wide as the lanes queues too, while its first wave
        # prefills); one that can never queue needs only what two streams
        # reach
        mode = self.cell.module("loadgen", traffic["generator"]).MODE
        queues = (mode != "closed"
                  or int(traffic["concurrency"]) >= cb.lanes)
        n = cb.lanes + 4 if queues else 2
        burst = [{"index": 100 + i, "prompt_len": min(lo, 64),
                  "steps": min(steps_max, 12)} for i in range(n)]
        self._generate(client, burst, concurrency=n)

    def _generate(self, client, requests, concurrency: int) -> None:
        reply = client.call({
            "op": "generate", "model": MODEL_NAME, "seed": self.seed,
            "vocab": self.hyper["vocab"], "concurrency": concurrency,
            "requests": requests})
        bad = [r["error"] for r in reply["results"] if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0]}")

    # -- counters ------------------------------------------------------------------
    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {"dispatch": state["dispatch"], "pool": state["pool"]}

    def gauge(self) -> Dict[str, Any]:
        """Cheap reading for the 50 ms sampler of a traced run."""
        cb = self.engine
        return {"free_pages": cb.pool.free_pages, "n_pages": cb.pool.n_pages,
                "active_lanes": cb.active_lanes,
                "queued_requests": cb.queued_requests}

    def shutdown(self) -> None:
        if self.manager is not None:
            # in-flight requests end first: the program's shutdown strands
            # a unary call that is still in its batcher
            self.manager.drain(timeout=30.0, settle_s=0.0)
            self.manager.shutdown()
        if self.engine is not None:
            self.engine.shutdown()


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

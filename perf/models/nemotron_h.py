"""Model adapter, kind ``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B behind the
Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, gauges,
client calls and shutdown are inherited): weights -> ``ContinuousBatcher(
spec=)`` -> ``InferenceManager.serve(generation_engines=)`` -> streamed over
gRPC.  The engine is handed ``tpulab.models.spec.nemotron_h_spec`` of the
published keys and of the share the configuration states (``share``: the
router's published width and the first expert held; ``n_routed_experts`` is
what this chip holds), so every layer is ONE sublayer by the published
pattern: 23 Mamba-2 mixers over a head-shaped per-lane state beside the page
store, which holds the 6 attention layers alone, and 23 expert layers that
route over all 128 experts and compute the part their 16 give.  No
dispatch-plan option is passed.  Weights: the program's own tree
(``init_params`` through ``jax.eval_shape``) filled on the device in bf16
from the seed: matrices normal 0.02, norm scales 1, the Mamba-2 leaves by
``fill_rule`` (the published initialiser), the served experts' padding zero.

``correct`` holds three numbers of every prompt length to the reference,
each to a limit of its own (``perf/reference/nemotron_h.py``, where each
limit's reason and readings stand): the streams' log-probabilities as kind
``keye_vl2`` judges them (four streams a prompt length, one lower quartile:
TOLERANCE), and, read where the server holds them once a stream has ended,
the first Mamba-2 layer's state of the stream's lane
(``debug_state()["last_release"]`` names the lane; the lower quartile over
its heads of a head's error: STATE_TOLERANCE, which a state kept in bf16
fails) and the assignments its first expert layer counted over the stream's
tokens (``debug_state()["moe"]["assignments"]``, after minus before:
ROUTE_TOLERANCE after the long prompts, which a router computed in bf16
fails; the short prompts' 330 assignments guard a gross fault alone);
a bf16 state reads inside bf16 serving's band on the logits, a bf16 router a
hair outside it, and both well outside it there.
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
    from tpulab.models.spec import nemotron_h_spec
    share = config["share"]
    return nemotron_h_spec(
        dict(config, n_routed_experts=share["n_routed_experts"]),
        first=int(share["first_expert"]),
        held=int(config["n_routed_experts"]))


def fill_rule(path: str, shape, key, spec):
    """One leaf of the tree, float32, by its name: what
    ``tpulab.models.spec.init_params`` draws for it."""
    import jax
    import jax.numpy as jnp

    if path.endswith("['scale']") or path.endswith("['d']"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("['a_log']"):          # log of U(1, 16), a head
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if path.endswith("['dt_bias']"):        # softplus^-1 of a log-uniform dt
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if path.endswith("['conv_w']") or path.endswith("['conv_b']"):
        bound = spec.d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    # the served experts' padding: zero columns of w1, zero rows of w2
    if path.endswith("['moe']['w1']"):
        return jnp.where(jnp.arange(shape[2]) < spec.moe_ff, w, 0.0)
    if path.endswith("['moe']['w2']"):
        return jnp.where(jnp.arange(shape[1])[:, None] < spec.moe_ff, w, 0.0)
    return w


def make_weights(tree, seed: int, spec):
    """``tree`` filled in bf16, on the device, an entry of the tree a jitted
    call (one call over 52 layers' leaves is one program of 10 GB of
    outputs, compiled anew at every depth)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed_words(seed, 1)[0], impl="rbg")
    programs = {}       # one compiled program a kind of entry (three kinds
                        # of layer, the embedding and head, the final norm)

    def program(sub):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(sub)
        kind = (treedef, tuple(leaf.shape for _, leaf in leaves))
        if kind not in programs:
            programs[kind] = jax.jit(lambda key: jax.tree_util.tree_unflatten(
                treedef, [
                    fill_rule(jax.tree_util.keystr(path), leaf.shape,
                              jax.random.fold_in(key, i), spec
                              ).astype(jnp.bfloat16)
                    for i, (path, leaf) in enumerate(leaves)]))
        return programs[kind]

    return {name: program(sub)(jax.random.fold_in(key, i))
            for i, (name, sub) in enumerate(sorted(tree.items()))}


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        super().__init__(cell, seed, say)
        if cell.chips != 1:
            raise ValueError("kind nemotron_h is served on one chip (the "
                             "first chip's share of an eight-chip layer)")
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
            make_weights(tree, self.seed, self.spec))
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
                 f"{self.spec.n_experts} served_expert_width="
                 f"{self.spec.moe_ff_served}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def assignments(self) -> np.ndarray:
        """The expert layers' cumulative assignments ``(layers, E)``."""
        return np.asarray(self.engine.debug_state()["moe"]["assignments"],
                          np.int64)

    def served_state(self, length: int):
        """The first Mamba-2 layer's state ``(H, P, N)`` of the request that
        ended last, float32 on the host: its lane's slot keeps it until
        another request takes the lane; None unless that request took in
        exactly ``length`` tokens."""
        cb = self.engine
        held = cb.debug_state()["last_release"]
        if held is None or held["length"] != length:
            return None
        return np.asarray(cb.state.arrays[0][0, held["lane"]], np.float32)

    def check_reference(self, client) -> bool:
        """Greedy streams through the Generate RPC, one at a time,
        ``REFERENCE_STREAMS`` a prompt length (prompts drawn apart: a greedy
        stream on seeded weights settles on one token and so carries one
        error).  After each, what the server holds of it
        (:meth:`served_state`, and the assignments its expert layers
        counted since the stream began).  A length's streams are judged
        together against ONE forward of the plain reference each: their
        tokens' errors on the lower quartile (kind ``keye_vl2``'s
        construction), their states' and their routings' on the median
        stream, each under the reference's limit for it."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        ok = True
        for i, n in enumerate(lens):
            limits = {"logprob_err": reference.TOLERANCE,
                      "argmax_gap": reference.TOLERANCE,
                      "state_err_low": reference.STATE_TOLERANCE,
                      "route_err": reference.route_tolerance(n)}
            errors = []
            for j in range(streams):
                # stream 0 of length i is draw i, as kind ``glm4_moe_lite``
                # has it
                prompt = rng_for(self.seed, 0x4EF, i + len(lens) * j).integers(
                    0, self.hyper["vocab"], n).tolist()
                before = self.assignments()
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
                state = self.served_state(n + steps - 1)
                if state is None:
                    self.say(f"reference check: prompt of {n}: the request "
                             "released last is not this stream's")
                    ok = False
                    continue
                errors.append(reference.token_errors(
                    self.params, prompt, res["tokens"], res["logprobs"],
                    stores=(state, self.assignments() - before), **hyper))
            if not errors:
                continue
            got = reference.summary(errors)
            good = all(got[name] <= limit for name, limit in limits.items())
            ok &= good
            each = "; ".join(
                f"{name} " + ", ".join(
                    f"{reference.summary([e])[name]:.4g}" for e in errors)
                for name in ("logprob_err", "state_err", "state_err_low",
                             "state_err_min", "route_err", "route_err_all"))
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
                "moe": state["moe"], "state": state["state"],
                "ssd": state["ssd"]}


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

"""Model adapter, kind ``evabyte``: EvaByte behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: gauges, client
calls and shutdown are inherited): weights -> ``ContinuousBatcher(spec=)`` ->
``InferenceManager.serve(generation_engines=)`` -> streamed over gRPC.  The
engine is handed ``tpulab.models.spec.evabyte_spec`` of the published keys, so
every layer runs EVA attention: a lane's rows are its last window whole behind
128 summary rows for every window before it, and the scheduler compacts a
lane's window in place where it ends.  ``pool_tokens`` of the traffic file
counts ROWS of the page tables.  No dispatch-plan option is passed.

Weights: the program's own tree (``init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed by ``fill_rule``: matrices normal
0.02 and norm scales 1, except what decides whether the mechanism shows in a
logit: the scorers a unit normal cut at two deviations (their published
initialisation) and the query and key columns of ``wqkv`` drawn so that
attention logits and pooling logits have a deviation of about 2 (with 0.02
everywhere the pooling logits come out at ~13 and both poolings are one-hot;
the configuration's ``assumed`` says how).

``correct`` holds three numbers of every prompt length to the reference, each
to a limit of its own (``perf/reference/evabyte.py``): the streams'
log-probabilities (their median error and their largest), and, read where the server holds them once a stream has
ended, layer 0's rows in the stream's pages (``debug_state()
["last_release"]``): the summaries of its finished windows, then its last
window's rows.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import numpy as np

from harness.sizes import rng_for, seed_words
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME
#: deviations of a query's and a key's elements for a normed input (see
#: ``fill_rule``)
Q_STD, K_STD = 10.0, 0.2


def spec_of(config: Dict[str, Any]):
    from tpulab.models.spec import evabyte_spec
    return evabyte_spec(config)


def fill_rule(path: str, shape, key, spec):
    """One leaf of the tree, float32, by its name."""
    import jax
    import jax.numpy as jnp

    if path.endswith("['scale']"):
        return jnp.ones(shape, jnp.float32)
    if path.endswith("['eva_mu']") or path.endswith("['eva_phi']"):
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    if path.endswith("['wqkv']"):
        # columns [q | k | v]: a normed input has unit mean square, so a
        # column of deviation s gives elements of deviation s * sqrt(d)
        nq = spec.n_heads * spec.head_dim
        nk = spec.n_kv_heads * spec.head_dim
        per_unit = 0.02 * spec.d_model ** 0.5
        scale = jnp.concatenate([
            jnp.full((nq,), Q_STD / per_unit), jnp.full((nk,), K_STD / per_unit),
            jnp.ones((shape[1] - nq - nk,))])
        w = w * scale[None, :]
    return w


def make_weights(tree, seed: int, spec):
    """``tree`` filled in bf16, on the device, by one jitted call."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def fill(key):
        return jax.tree_util.tree_unflatten(treedef, [
            fill_rule(jax.tree_util.keystr(path), leaf.shape,
                      jax.random.fold_in(key, i), spec).astype(jnp.bfloat16)
            for i, (path, leaf) in enumerate(leaves)])

    key = jax.random.key(seed_words(seed, 1)[0], impl="rbg")
    return jax.jit(fill)(key)


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        super().__init__(cell, seed, say)
        if cell.chips != 1:
            raise ValueError("kind evabyte is served on one chip (the first "
                             "of four pipeline stages)")
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
                 f"{cb.page_size} max_pages={cb.max_pages} pool_pages="
                 f"{cb.pool.n_pages} pool_bytes={cb.pool.hbm_bytes} "
                 f"bytes_per_row={cb.pool.bytes_per_token} eva_window="
                 f"{self.spec.eva_window} eva_chunk={self.spec.eva_chunk}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def served_rows(self, length: int):
        """Layer 0's rows ``(2, rows, H * D)`` of the request that ended
        last, float32 on the host, as its pages keep them until another
        request takes them: the summaries of its finished windows, then the
        rows of its last window; None unless that request took in exactly
        ``length`` positions."""
        import jax.numpy as jnp
        cb = self.engine
        held = cb.debug_state()["last_release"]
        if held is None or held["length"] != length:
            return None
        rows = int(self.spec.cache_row(length - 1)) + 1
        pages = np.asarray(held["pages"], np.int32)
        kv = np.asarray(cb.pool.kv[0, pages].astype(jnp.float32))
        # (pages, 2, page size, row) -> (2, rows, row)
        kv = np.moveaxis(kv, 1, 0).reshape(2, -1, kv.shape[-1])
        return kv[:, :rows]

    def check_reference(self, client) -> bool:
        """Greedy streams through the Generate RPC, one at a time,
        ``REFERENCE_STREAMS`` a prompt length (prompts drawn apart).  After
        each, layer 0's rows as the server holds them
        (:meth:`served_rows`).  A length's streams are judged together
        against ONE forward of the plain reference each: the median and the
        largest log-probability error over all their tokens and the largest
        of their rows' errors, each under the reference's limit for it."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        limits = {"logprob_err": reference.TOLERANCE,
                  "argmax_gap": reference.TOLERANCE,
                  "logprob_err_max": reference.MAX_TOLERANCE,
                  "kv_err": reference.KV_TOLERANCE}
        ok = True
        for i, n in enumerate(lens):
            errors = []
            for j in range(streams):
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
                rows = self.served_rows(n + steps - 1)
                if rows is None:
                    self.say(f"reference check: prompt of {n}: the request "
                             "released last is not this stream's")
                    ok = False
                    continue
                errors.append(reference.token_errors(
                    self.params, prompt, res["tokens"], res["logprobs"],
                    stores=rows, **hyper))
            if not errors:
                continue
            got = reference.summary(errors)
            good = all(got[name] <= limit for name, limit in limits.items())
            ok &= good
            each = "; ".join(
                f"{name} " + ", ".join(
                    f"{reference.summary([e])[name]:.4g}" for e in errors)
                for name in ("logprob_err", "logprob_err_max", "kv_err"))
            self.say(f"reference check: {len(errors)} prompts of {n} bytes, "
                     f"{steps} greedy tokens each through the Generate RPC: "
                     + " ".join(f"{name}={got[name]:.4g} (limit {limit})"
                                for name, limit in limits.items())
                     + f" (medians and the largest over all the tokens, the "
                     f"largest over the streams' rows; a stream alone: "
                     f"{each}) -> {'agrees' if good else 'DISAGREES'}")
        return ok

    def warm_up(self, client) -> None:
        """Kind ``lm``'s shapes, and one prompt a row past a window: its
        compaction is the one program the rounds and blocks do not reach."""
        super().warm_up(client)
        self._generate(client, [{"index": 200,
                                 "prompt_len": self.spec.eva_window + 1,
                                 "steps": 2}], concurrency=1)

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {"dispatch": state["dispatch"], "pool": state["pool"],
                "eva": state["eva"]}

    def gauge(self) -> Dict[str, Any]:
        """Kind ``lm``'s reading, and what the decoding lanes hold: their
        pages and their positions (``eva.cache_bytes_per_position``)."""
        out = super().gauge()
        out["decode_pages"], out["decode_positions"] = (
            self.engine.decode_holdings)
        return out


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

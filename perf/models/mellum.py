"""Model adapter, kind ``mellum``: Mellum2-12B-A2.5B behind the Generate RPC.

The same normal path as kind ``lm`` (``perf/models/lm.py``: warm-up, client
calls and shutdown are inherited): weights -> ``ContinuousBatcher(spec=)`` ->
``InferenceManager.serve(generation_engines=)`` -> streamed over gRPC.  The
engine is handed ``tpulab.models.spec.mellum_spec`` of the published keys, so
its page store is TWO layer groups, the full layers' and the window layers',
each with its own array, free extents and table a lane: a window layer's
blocks behind its window go back to its group while the request lives, the
full layers keep theirs, YaRN turns the full layers' q and k and plain RoPE
the window layers'.  ``pool_tokens`` of the traffic file sizes the FULL
group; the engine sizes the window group itself (lanes, the window, a
round's budget).  No dispatch-plan option is passed.

Weights: the program's own tree (``init_params`` through ``jax.eval_shape``)
filled on the device in bf16 from the seed, every weight normal 0.02, norm
scales 1 (kind ``lm``'s ``make_weights``).

``correct`` holds six numbers of every prompt length to the reference, each
to a limit of its own (``perf/reference/mellum.py``): the streams'
log-probabilities as kind ``keye_vl2`` judges them (four streams a prompt
length, one lower quartile, a limit a length), and, read where the server
holds them once a stream has ended (``debug_state()["last_release"]`` names
the lane and the pages of BOTH groups, as kind ``zaya`` reads its two
stores), every layer's K/V rows: a full layer's at every position, a window
layer's from the first row its table still held.  Those streams run one at
a time on an idle engine (a store can be read back only while nobody takes
its pages).  A prompt length past the window is then served AGAIN, four more
streams of it UNDER LOAD (:meth:`Adapter.check_under_load`): other callers
hold lanes and keep prompts streaming, so the window blocks a lane gives
back are granted to another lane at once and the streams' decode rows ride
mixed rounds, the state the timed window is in; their log-probabilities are
held to the same limit.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import numpy as np

from harness.sizes import rng_for
from harness.spec import Cell, load_module

lm = load_module("models", "lm")
MODEL_NAME = lm.MODEL_NAME

#: The reference check under load: callers at once (the lanes where they are
#: fewer), requests beside the reference's streams, and tokens asked of each
#: (few: their lanes turn over, so a prompt is always streaming)
LOADED_CALLERS = 8
LOADED_BACKGROUND = 12
LOADED_STEPS = 48


def hyper_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the inherited warm-up and window read (``vocab``); the rest of
    the shape comes from the spec."""
    return dict(vocab=int(config["vocab_size"]),
                d_model=int(config["hidden_size"]),
                n_layers=int(config["num_hidden_layers"]))


class Adapter(lm.Adapter):
    def __init__(self, cell: Cell, seed: int, say):
        # (not lm.Adapter's: that one refuses ``sliding_window`` by name and
        # reads a dense FFN width no layer of this kind has)
        self.cell, self.seed, self.say = cell, seed, say
        from tpulab.models.spec import mellum_spec
        if cell.chips != 1:
            raise ValueError("kind mellum is served on one chip (the first "
                             "of four pipeline stages)")
        self.hyper = hyper_of(cell.config)
        self.sizes = cell.traffic["engine"]
        self.engine = self.manager = self.params = None
        self.spec = mellum_spec(cell.config)

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        import tpulab
        from tpulab.engine.paged import ContinuousBatcher
        from tpulab.models.spec import init_params
        tree = jax.eval_shape(partial(init_params, self.spec,
                                      self.hyper["vocab"], 0))
        self.params = jax.block_until_ready(lm.make_weights(tree, self.seed))
        sz = self.sizes
        page = int(sz["page_size"])
        self.engine = cb = ContinuousBatcher(
            self.params, self.spec.n_heads, self.spec.n_layers,
            spec=self.spec, lanes=int(sz["lanes"]),
            max_len=int(sz["max_len"]), page_size=page,
            n_pages=int(sz["pool_tokens"]) // page + 1,
            compute_dtype=jnp.bfloat16)
        n = sum(int(x.size) for x in jax.tree_util.tree_leaves(self.params))
        groups = cb.debug_state()["pool"]["groups"]
        self.say(f"engine plan selected by the program: ragged={cb.ragged} "
                 f"use_kernel={cb.use_kernel} decode_block={cb.decode_block}"
                 f"; lanes={cb.lanes} max_len={cb.max_len} page_size="
                 f"{cb.page_size} parameters={n} window={self.spec.window} "
                 f"round_budget={cb.RAGGED_CHUNK_CAP} walk_block_pages="
                 f"{cb.plan.walk_block_pages} page groups: " + "; ".join(
                     f"{name} layers={g['layers']} pages={g['n_pages']} "
                     f"bytes={g['hbm_bytes']} page_nbytes={g['page_nbytes']}"
                     for name, g in groups.items())
                 + f"; window pages a lane at most="
                 f"{cb.debug_state()['dispatch']['window']['lane_pages']}")
        self.manager = tpulab.InferenceManager(max_exec_concurrency=1)
        self.manager.serve(port=0, generation_engines={MODEL_NAME: cb})
        self.port = self.manager.server.bound_port

    def served_stores(self, length: int):
        """What BOTH groups hold of the request that ended last, float32 on
        the host: ``{"full" (Lf, 2, length, G * D), "window" (Lw, 2, length -
        t0, G * D), "window_start": t0}``, the full layers' rows at every
        position and the window layers' from the first position their table
        still held (``window_first`` whole pages in); the pages keep them
        until another request takes them.  None unless that request took in
        exactly ``length`` tokens."""
        cb = self.engine
        held = cb.debug_state()["last_release"]
        if held is None or held["length"] != length:
            return None

        def rows(pool, pages):
            kv = np.asarray(pool.kv[:, np.asarray(pages, np.int32)]).astype(
                np.float32)                      # bf16 comes over
            # (L, pages, 2, page size, row) -> (L, 2, tokens, row)
            return np.moveaxis(kv, 2, 1).reshape(kv.shape[0], 2, -1,
                                                 kv.shape[-1])
        t0 = held["window_first"] * cb.page_size
        return {"full": rows(cb.pool, held["pages"])[:, :, :length],
                "window": rows(cb.wpool, held["window_pages"])[
                    :, :, :length - t0],
                "window_start": t0}

    def check_reference(self, client) -> bool:
        """Greedy streams through the Generate RPC, one at a time,
        ``REFERENCE_STREAMS`` a prompt length (prompts drawn apart).  After
        each, what the server holds of it in both groups
        (:meth:`served_stores`).  A length's streams are judged together
        against ONE forward of the plain reference each: their tokens'
        errors on the lower quartile under the length's limit, their stores'
        as ``reference.summary`` joins them, each under the reference's
        limit for it."""
        reference = self.cell.module("reference", self.cell.config["kind"])
        hyper = reference.hyper_of(self.cell.config)
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = [int(n) for n in self.cell.traffic["reference_prompt_lens"]]
        ok = True
        for i, n in enumerate(lens):
            limits = {"logprob_err": reference.tolerance(n),
                      "argmax_gap": reference.tolerance(n),
                      "kv_err": reference.KV_TOLERANCE,
                      "kv1_err": reference.KV1_TOLERANCE,
                      "full_kv_err": reference.FULL_KV_TOLERANCE,
                      "layers_kv_err": reference.layers_tolerance(n)}
            errors, starts = [], []
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
                starts.append(stores["window_start"])
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
                for name in ("logprob_err", "kv_err", "kv1_err",
                             "full_kv_err"))
            layers = np.median(np.stack([e["layer_kv_err"] for e in errors]),
                               axis=0)
            self.say(f"reference check: {len(errors)} prompts of {n} tokens, "
                     f"{steps} greedy tokens each through the Generate RPC: "
                     + " ".join(f"{name}={got[name]:.4g} (limit {limit})"
                                for name, limit in limits.items())
                     + f" (lower quartiles over all the tokens, medians "
                     f"over the streams' stores; the window group held rows "
                     f"from position {starts[0]} on; a layer's "
                     f"median row, in layer order: "
                     + ", ".join(f"{x:.3g}" for x in layers)
                     + f"; a stream alone: {each}; logprob_err median "
                     f"{got['logprob_err_median']:.4g}, largest "
                     f"{got['logprob_err_max']:.4g}, "
                     f"{100 * got['flipped_share']:.0f} % of the tokens past "
                     f"0.05) -> {'agrees' if good else 'DISAGREES'}")
            if n > self.spec.window:
                ok &= self.check_under_load(client, reference, hyper, i, n)
        return ok

    def check_under_load(self, client, reference, hyper, i: int,
                         n: int) -> bool:
        """``REFERENCE_STREAMS`` more greedy streams of ``n`` prompt tokens
        (draws of their own) in ONE call of :data:`LOADED_CALLERS` callers,
        among :data:`LOADED_BACKGROUND` other requests whose prompts (``n /
        6`` to ``n / 2`` tokens, past the window too) keep streaming before,
        between and behind them: every lane's window blocks go back to the
        group under its living request and come to another lane with the
        next reservation, and a stream's decode rows ride the rounds that
        carry the others' chunks.  The streams' tokens against one forward
        of the plain reference each, their lower quartiles under the
        length's limit (no store: another request has taken the pages by
        the time a stream could be read back)."""
        steps, streams = reference.REFERENCE_STEPS, reference.REFERENCE_STREAMS
        lens = self.cell.traffic["reference_prompt_lens"]
        vocab = self.hyper["vocab"]
        prompts = [rng_for(self.seed, 0x4EF, i + len(lens) * (streams + j))
                   .integers(0, vocab, n).tolist() for j in range(streams)]
        sizes = rng_for(self.seed, 0x4F0, i).integers(
            n // 6, n // 2 + 1, LOADED_BACKGROUND)
        others = [{"index": k, "prompt_len": int(m), "steps": LOADED_STEPS}
                  for k, m in enumerate(sizes)]
        # two of the others, a stream, two others, a stream, ...: the rest
        # of the others behind the last stream
        requests, at = [], []
        for j, prompt in enumerate(prompts):
            requests += others[2 * j:2 * j + 2]
            at.append(len(requests))
            requests.append({"prompt": prompt, "steps": steps})
        requests += others[2 * streams:]
        callers = min(LOADED_CALLERS, int(self.sizes["lanes"]))

        def counted():
            d = self.engine.debug_state()["dispatch"]
            return np.asarray([d["kinds"].get("mixed", 0),
                               d["mixed_decode_rows"], d["decode_dispatches"],
                               d["window"]["pages_released"]])
        before = counted()
        results = client.call({
            "op": "generate", "model": MODEL_NAME, "logprobs": True,
            "seed": self.seed, "vocab": vocab,
            "concurrency": callers, "requests": requests})["results"]
        rounds, rows, dispatches, pages = counted() - before
        bad = [r["error"] for r in results if not r["ok"]]
        if bad or any(len(results[k]["tokens"]) != steps for k in at):
            self.say(f"reference check under load: prompts of {n}: "
                     f"{len(bad)} of {len(results)} requests failed: {bad[:2]}")
            return False
        got = reference.summary([reference.token_errors(
            self.params, prompt, results[k]["tokens"],
            results[k]["logprobs"], **hyper)
            for k, prompt in zip(at, prompts)])
        limit = reference.tolerance(n)
        good = got["logprob_err"] <= limit and got["argmax_gap"] <= limit
        self.say(f"reference check under load: {streams} prompts of {n} "
                 f"tokens, {steps} greedy tokens each, among "
                 f"{len(others)} other requests ({int(sizes.min())}-"
                 f"{int(sizes.max())} prompt tokens, {LOADED_STEPS} tokens "
                 f"each) from {callers} callers at once: "
                 f"logprob_err={got['logprob_err']:.4g} "
                 f"argmax_gap={got['argmax_gap']:.4g} (limit {limit}; "
                 f"lower quartiles over all the tokens; median "
                 f"{got['logprob_err_median']:.4g}, largest "
                 f"{got['logprob_err_max']:.4g}, "
                 f"{100 * got['flipped_share']:.0f} % of the tokens past "
                 f"0.05); meanwhile {rounds} mixed rounds carried {rows} "
                 f"decode rows, {dispatches - rounds} dispatches were "
                 f"decode blocks, {pages} window pages went back under "
                 f"living requests -> "
                 f"{'agrees' if good else 'DISAGREES'}")
        return good

    def counters(self) -> Dict[str, Any]:
        state = self.engine.debug_state()
        return {name: state[name] for name in ("dispatch", "pool", "moe")}

    def gauge(self) -> Dict[str, Any]:
        """Kind ``lm``'s reading, and what the decoding lanes hold: their
        pages in each group and the positions they took in
        (``swa.cache_bytes_per_position``)."""
        out = super().gauge()
        cb = self.engine
        out["decode_pages"], out["decode_positions"] = cb.decode_holdings
        out["decode_window_pages"] = cb.decode_window_pages
        return out


    def shutdown(self) -> None:
        """Kind ``lm``'s, behind one line on the window group's turnover
        over the whole run: what the release of blocks under living
        requests cost the scheduler's thread beside its turns."""
        if self.engine is not None:
            d = self.engine.debug_state()["dispatch"]
            w = d["window"]
            self.say(f"window group over the run: {w['pages_released']} "
                     f"pages returned under living requests in "
                     f"{w['releases']} reservations, {w['release_s']:.4f} s "
                     f"of the scheduler's thread ({d['turns']['s']:.2f} s "
                     f"in {d['turns']['n']} turns)")
        super().shutdown()


def build(cell: Cell, seed: int, say) -> Adapter:
    return Adapter(cell, seed, say)

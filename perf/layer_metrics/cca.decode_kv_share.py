"""The K/V rows' part of the bytes a decode step of kind ``zaya`` must move,
at the window's mean decode batch and context (``perf/rooflines/zaya.py``
``decode_kv_bytes`` over ``decode_step_bytes``; lanes and context from what
the scheduler dispatched, ``lane_work["decode"]``, the experts hit a layer
from ``moe.experts_hit_per_step``).  What the compressed latent is for: 2 KV
heads of 128 keep the share near a third at 32 lanes of ~7 k keys where
``mistral7b-l16``'s rows would be two thirds; a cache entry kept wider, or
contexts that grow, move it.  None on a program (or a model) without CCA."""


def read(ctx):
    cell = ctx["cell"]
    if not ctx["counters_after"].get("cca"):
        return None
    hit = cell.module("layer_metrics", "moe.experts_hit_per_step").read(ctx)
    at = cell.module("layer_metrics", "gdn.decode_roofline"
                     ).lanes_and_context(ctx, "decode", "decode_block_steps")
    if hit is None or at is None:
        return None
    roofline = cell.module("rooflines", cell.config["kind"])
    return 100.0 * roofline.decode_kv_bytes(cell.config, *at) / (
        roofline.decode_step_bytes(cell.config, at[0], hit, at[1]))

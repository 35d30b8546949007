"""Closed loop: ``concurrency`` callers replay a fixed seeded list.

Each caller sends its next request when its last completed, so a slow
server gets less load (callers that wait for a reply: a batch job, a
pipeline stage).  The list is the mix's fixed set of sizes in the order the
run's seed gives them, repeated for as long as the window lasts: every seed
schedules the same multiset of work in another order.  A mix whose requests
outlast the window sets ``ramp_max_s``: the callers then start before the
window, each when the one before it has its first token, and the window
opens when every caller is streaming tokens (or after that many seconds).
The ramp is set-up; the window is still exactly ``--seconds``.
"""

from __future__ import annotations

from typing import Any, Dict

from harness.sizes import rng_for, size_pairs

MODE = "closed"


def plan(traffic: Dict[str, Any], seed: int, seconds: float) -> Dict[str, Any]:
    n = int(traffic["set_size"])
    pairs = size_pairs(traffic, n)[rng_for(seed, 0xC10).permutation(n)]
    return {
        "mode": MODE, "seconds": float(seconds),
        "channels": int(traffic.get("channels", 1)),
        "concurrency": int(traffic["concurrency"]),
        "ramp_max_s": float(traffic.get("ramp_max_s", 0)),
        "requests": [
            {"index": i, "prompt_len": int(pairs[i, 0]),
             "steps": int(pairs[i, 1])} for i in range(n)],
    }

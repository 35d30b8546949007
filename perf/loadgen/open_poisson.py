"""Open loop: independent users, Poisson arrivals at a rate fixed in the mix.

``plan`` draws the whole window's arrivals before it starts.  The number of
requests is ``round(rate * seconds)``; their gaps and sizes are fixed sets
(see ``harness.sizes``) that the seed only reorders.  Each request is timed from
its due time, whenever the client managed to send it.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from harness.sizes import exponential_gaps, rng_for, size_pairs

MODE = "open"


def plan(traffic: Dict[str, Any], seed: int, seconds: float) -> Dict[str, Any]:
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 0x0BE)
    gaps = exponential_gaps(rate, n)[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]          # the first request is due at 0
    # the stratified gaps sum to ~n/rate; keep every arrival inside the window
    due *= min(1.0, (seconds * (n - 0.5) / n) / max(due[-1], 1e-9)) \
        if n > 1 else 1.0
    pairs = size_pairs(traffic, n)[rng.permutation(n)]
    return {
        "mode": MODE, "seconds": float(seconds),
        "drain_s": float(traffic["drain_s"]),
        "channels": int(traffic.get("channels", 1)),
        "requests": [
            {"index": i, "due_s": float(due[i]),
             "prompt_len": int(pairs[i, 0]), "steps": int(pairs[i, 1])}
            for i in range(n)],
    }

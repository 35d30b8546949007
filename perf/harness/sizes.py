"""What the generators share: sizes drawn as a fixed set, payloads from a seed.

Every seed gets the SAME multiset of sizes (and, in an open loop, of gaps
between arrivals) in another order: the sizes are the stratified quantiles
of the mix's distribution, so a run's amount of work does not depend on the
seed, only its order does.  Pure Python and numpy: the client process that
imports this never imports JAX.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def seed_words(seed: int, n: int = 2) -> List[int]:
    """``n`` 32-bit words from a seed of any size (the driver's seeds pass
    2**31, which a signed 32-bit key refuses)."""
    return [int(w) for w in np.random.SeedSequence(int(seed)).generate_state(n)]


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *map(int, stream)])


def quantile_sizes(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole-number sizes at the mid-points of ``n`` equal-probability
    strata of ``dist``, clipped to its ``min``/``max``."""
    kind = dist["dist"]
    u = (np.arange(n) + 0.5) / n
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    lo = dist.get("min", dist.get("value"))
    hi = dist.get("max", dist.get("value"))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def size_pairs(traffic: Dict[str, Any], n: int) -> np.ndarray:
    """The mix's fixed set of ``n`` (prompt_len, output_len) pairs.  The
    pairing is a fixed shuffle (the mix's own ``pairing_seed``), so long
    prompts do not always carry long answers; the run's seed never enters."""
    p = quantile_sizes(traffic["prompt_len"], n)
    o = quantile_sizes(traffic["output_len"], n)
    order = rng_for(traffic.get("pairing_seed", 0), 0xA11).permutation(n)
    return np.stack([p, o[order]], axis=1)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` gaps between Poisson arrivals at ``rate_per_s``: the stratified
    quantiles of the exponential distribution (mean 1/rate)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(rate_per_s)


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index`` of a run: no two requests of a run
    share a prefix beyond chance."""
    return rng_for(seed, 0x70C, index).integers(
        0, vocab, size=int(length), dtype=np.int32)


def percentile(values, q: float) -> float:
    """Nearest-rank-with-interpolation percentile (numpy's default), NaN
    for no samples."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))

"""What the readers of the scheduler's counters share: deltas of
``debug_state()["dispatch"]`` over the measured window.  A program that
lacks a counter (an older commit under these files) gives None, never an
error."""

from __future__ import annotations

from typing import Any, Dict, Optional


def delta(ctx: Dict[str, Any], *path: str) -> Optional[float]:
    """``after - before`` of the counter at ``path`` under ``dispatch``."""
    a = ctx["counters_before"].get("dispatch")
    b = ctx["counters_after"].get("dispatch")
    for key in path:
        if not isinstance(a, dict) or key not in a or key not in b:
            return None
        a, b = a[key], b[key]
    return b - a


def ratio(ctx: Dict[str, Any], num: tuple, den: tuple,
          scale: float = 1.0) -> Optional[float]:
    """``scale * delta(num) / delta(den)``; None where either is missing
    or nothing was counted."""
    n, d = delta(ctx, *num), delta(ctx, *den)
    if n is None or not d:
        return None
    return scale * n / d


def stage_share(ctx: Dict[str, Any], *stages: str) -> Optional[float]:
    """Percent of the window's seconds the scheduler thread spent in
    ``stages`` (``dispatch.stages.<stage>.s``)."""
    parts = [delta(ctx, "stages", s, "s") for s in stages]
    if None in parts or not ctx["window"]["seconds"]:
        return None
    return 100.0 * sum(parts) / ctx["window"]["seconds"]

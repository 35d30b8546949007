"""From the client's samples to what a run counts: the window arithmetic.

Open loop: every request of the plan is due inside the window, so
``attempted`` is their number; one that did not complete by the end of the
bounded drain, or completed wrong, is ``failed``.  Closed loop: ``attempted``
are the requests that completed or failed inside the window (one begun during
a ramp counts where it ends); those still in flight at the close are left out
of both counts.

A completed stream is *valid* when it delivered exactly the number of tokens
asked, all ids inside the vocabulary.  An invalid one counts as failed and
makes the run incorrect.
"""

from __future__ import annotations

from typing import Any, Dict, List


def _valid(rec: Dict[str, Any]) -> bool:
    return bool(rec.get("ok") and len(rec["times"]) == rec["steps"]
                and rec["in_range"])


def reduce_window(result: Dict[str, Any]) -> Dict[str, Any]:
    """``attempted``, ``failed``, ``invalid`` (completed but wrong) and the
    ``completed`` records the metrics read (``records``: every request the
    client started, also those in flight at the close)."""
    t_end = result["t_end"]
    recs: List[Dict[str, Any]] = result["requests"]
    if result["mode"] == "closed":
        recs = [r for r in recs if r.get("end") is not None
                and result["t_start"] <= r["end"] <= t_end]
    completed = [r for r in recs if _valid(r)]
    invalid = [r for r in recs if r.get("ok") and not _valid(r)]
    errors = sorted({r["error"] for r in recs if r.get("error")})
    return {"attempted": len(recs), "failed": len(recs) - len(completed),
            "invalid": len(invalid), "completed": completed,
            "records": result["requests"],
            "errors": errors[:5], "mode": result["mode"],
            "t_start": result["t_start"], "t_end": t_end,
            "seconds": t_end - result["t_start"]}

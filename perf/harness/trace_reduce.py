"""From a profiler trace (``.xplane.pb``) to numbers: the yardstick's one reducer.

Reads the trace with nothing but JAX (``jax.profiler.ProfileData``).  What it
takes from a TPU trace:

* device planes are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds
  one event per executed HLO operation and ``XLA Modules`` one per executed
  program (``jit_<function>(<fingerprint>)``);
* the benchmark wraps the traced slice of the window in a host span named
  ``WINDOW_SPAN`` (a ``jax.profiler.TraceAnnotation``), which the profiler
  puts on the same clock as the device events: everything is clipped to it.

``busy_s`` is the length of the union of the device's operation intervals
inside the span, averaged over the chips used; ``window_s`` is the span's
length.  Modules give per-program counts and times, the operations the top
ten by time, and the longest gaps between operations are named by the host
event that overlapped each most (the Python tracer's events of the
program's threads).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

WINDOW_SPAN = "perf_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: an operation's name in a trace is its whole HLO line: keep its head
OP_NAME_CHARS = 160
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_FINGERPRINT = re.compile(r"\(\d+\)$")
#: operations that only contain others (their time is their bodies' time)
_CONTAINER = re.compile(r" (while|conditional|call)\(")

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union_length(intervals: List[Interval]) -> float:
    """Total length covered by ``intervals`` (start, end), overlaps once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] that ``intervals`` leave uncovered."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return [(s, e) for s, e in out if e > s]


def module_name(event_name: str) -> str:
    """``jit_paged_decode_block(123456)`` -> ``jit_paged_decode_block``."""
    return _FINGERPRINT.sub("", event_name)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce_trace(xplane_path: str, n_chips: int = 1) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices: Dict[int, Dict[str, list]] = {}
    host_lines: List[Tuple[str, list]] = []
    span: Optional[Interval] = None
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {
                line.name: _events(line) for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                host_lines.append((line.name, evs))
                for name, s, e in evs:
                    if name == WINDOW_SPAN and (span is None
                                                or e - s > span[1] - span[0]):
                        span = (s, e)
    if not devices:
        raise ValueError(f"{xplane_path}: no /device:TPU:<n> plane")
    chips = sorted(devices)[:n_chips]
    if span is None:
        # no marker in the trace: fall back to the extent of device events
        every = [x for d in chips for evs in devices[d].values() for x in evs]
        span = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = span
    busy, op_time, mods = [], defaultdict(float), {}
    first_ops: List[Interval] = []
    for d in chips:
        ops = _clip(devices[d].get(OPS_LINE, []), lo, hi)
        busy.append(union_length([(s, e) for _, s, e in ops]) / 1e9)
        if d == chips[0]:
            first_ops = [(s, e) for _, s, e in ops]
            for name, s, e in ops:
                if not _CONTAINER.search(name):
                    op_time[name] += (e - s) / 1e9
            for name, s, e in _clip(devices[d].get(MODULES_LINE, []), lo, hi):
                rec = mods.setdefault(module_name(name),
                                      {"count": 0, "durations_s": []})
                rec["count"] += 1
                rec["durations_s"].append((e - s) / 1e9)
    for rec in mods.values():
        rec["total_s"] = sum(rec["durations_s"])
    idle = sorted(gaps(first_ops, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_chip": busy,
        "modules": mods,
        "device_ops": [[name[:OP_NAME_CHARS], t] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_host_activity(host_lines, s, e), (e - s) / 1e9]
                      for s, e in idle],
    }


def _host_activity(host_lines, lo: float, hi: float) -> str:
    """What the host was doing in [lo, hi]: the shortest event (the most
    specific frame of a call stack) that covers at least half of it,
    a Python frame with a file name before a bare lock wait; failing that,
    the event that overlaps it most."""
    best, best_key = "no host event", None
    for _line, evs in host_lines:
        for name, s, e in evs:
            if name == WINDOW_SPAN:
                continue
            overlap = min(e, hi) - max(s, lo)
            if overlap <= 0:
                continue
            covers = overlap >= 0.5 * (hi - lo)
            key = (covers, covers and ".py:" in name,
                   -(e - s) if covers else overlap)
            if best_key is None or key > best_key:
                best, best_key = name, key
    return best[:120]

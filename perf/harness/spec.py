"""Where everything the benchmark runs is found, by name.

``BENCHMARK.json`` (at the root of the checkout) names cells, configurations
and metrics; every one of them is a file of its own under ``perf/``:

=====================  ====================================  =================
what                   file                                   named by
=====================  ====================================  =================
configuration          ``<file>`` of the ``configs`` entry    cell's ``config``
traffic mix            ``perf/traffic/<traffic>.json``        cell's ``traffic``
generator              ``perf/loadgen/<generator>.py``        mix's ``generator``
model adapter          ``perf/models/<kind>.py``              config's ``kind``
plain reference        ``perf/reference/<kind>.py``           config's ``kind``
end-to-end metric      ``perf/e2e_metrics/<name>.py``         metric's ``name``
per-layer metric       ``perf/layer_metrics/<name>.py``       metric's ``name``
=====================  ====================================  =================

A later PR adds a cell by adding files and entries; nothing here is edited.
A name that resolves to no file fails with the missing path.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF_DIR)


class SpecError(Exception):
    """The benchmark's own files disagree or one is missing."""


def load_json(path: str) -> Any:
    if not os.path.isfile(path):
        raise SpecError(f"missing file: {path}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def find(kind: str, filename: str, overlay: str = None) -> str:
    """Path of ``<kind>/<filename>`` under ``overlay`` (a test's own
    directory of throw-away files) or else under ``perf/``."""
    tried = []
    for base in ([overlay] if overlay else []) + [PERF_DIR]:
        path = os.path.join(base, kind, filename)
        if os.path.isfile(path):
            return path
        tried.append(path)
    raise SpecError("missing file: " + " or ".join(tried))


def load_module(kind: str, name: str, overlay: str = None):
    """Import ``<kind>/<name>.py`` by path (names may hold dots and
    dashes, so the import system's own lookup cannot be used)."""
    path = find(kind, name + ".py", overlay)
    mod_name = "perf_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    overlay: str = None

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.overlay)


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, benchmark_path: str = None) -> Cell:
    """Resolve a cell of ``BENCHMARK.json``.  ``benchmark_path`` lets the
    tests run a throw-away benchmark: files beside it are found before
    those under ``perf/``."""
    overlay = (os.path.dirname(os.path.abspath(benchmark_path))
               if benchmark_path else None)
    bench = load_json(benchmark_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(it has {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(overlay or ROOT,
                                    configs[w["config"]]["file"]))
    traffic = load_json(find("traffic", w["traffic"] + ".json", overlay))
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
        overlay=overlay)

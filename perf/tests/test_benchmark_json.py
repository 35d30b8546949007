"""``BENCHMARK.json`` against the contract's limits, and every file it names."""

import json
import os
import re

import pytest

from harness import spec

BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_size|expansion|experts_per_tok")


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert BENCH["trace_in_run"] is True
    assert BENCH["command"][:2] == ["python3", "perf/run.py"]
    assert BENCH["paths"] == ["perf"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells must fit the driver's budget
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_units_and_whys():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"])
                for w in BENCH["workloads"]}) == len(CELLS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(CELLS) // 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perf/")
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]
    for cell in CELLS:
        mine = [m for m in BENCH["end_to_end"] if spec.applies(m, cell)]
        assert len(mine) >= 2, cell
        assert any(spec.applies(m, cell) for m in BENCH["per_layer"]), cell
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert spec.applies(e2e[m["moves"]], cell), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists(cell):
    c = spec.load_cell(cell)
    kind = c.config["kind"]
    for directory, name in [("models", kind), ("reference", kind),
                            ("loadgen", c.traffic["generator"])]:
        assert os.path.isfile(spec.find(directory, name + ".py"))
    for m in c.end_to_end:
        assert callable(c.module("e2e_metrics", m["name"]).read)
    for m in c.per_layer:
        assert callable(c.module("layer_metrics", m["name"]).read)
    entry = next(x for x in BENCH["configs"] if x["name"] == c.config_name)
    assert sorted(c.config.get("reduced", {})) == sorted(entry["reduced"])
    assert c.config["source"] == entry["source"]


def test_a_missing_file_is_named():
    with pytest.raises(spec.SpecError, match="no-such-metric.py"):
        spec.load_module("layer_metrics", "no-such-metric")
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no.such.cell")


def test_peaks_table():
    peaks = spec.load_json(os.path.join(spec.PERF_DIR, "peaks.json"))
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9

"""``perf/run.py`` end to end on the CPU, through cells that live under
``perf/tests/cells`` and are not in ``BENCHMARK.json``: a throw-away
configuration, traffic mix and per-layer metric, each a new file."""

import json
import os
import subprocess
import sys

import pytest

from harness import spec

RUN = os.path.join(spec.PERF_DIR, "run.py")
CELLS = os.path.join(spec.PERF_DIR, "tests", "cells", "bench.json")


def run(*args, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, RUN, *args], cwd=spec.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def result_of(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["rehearsal"]
    return out


def rehearse(workload, trace, **kw):
    return result_of(run("--workload", workload, "--seed", str(2**31 + 7),
                         "--seconds", "2", "--trace", str(trace),
                         "--benchmark", CELLS, "--allow-cpu", **kw))


def test_open_loop_lm_cell():
    out = rehearse("tiny-lm.open", 0)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] == 8
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_closed_loop_lm_cell_traced_reads_the_throw_away_metric():
    out = rehearse("tiny-lm.closed", 1)
    assert out["correct"] and out["attempted"] > 0
    m = out["metrics"]
    assert m["test.requests_seen"]["value"] >= out["attempted"]
    assert m["compiles_in_window.lm"]["value"] == 0
    assert m["sched.tokens_per_dispatch"]["value"] > 0
    assert 0 <= m["sched.mixed_round_share"]["value"] <= 100
    assert 0 < m["kv.pages_in_use_peak"]["value"] <= 100


def test_a_four_chip_cell_builds_its_mesh_on_four_virtual_devices():
    out = rehearse("tiny-lm-tp4.open", 0, devices=4)
    assert out["correct"] and out["device"]["count"] == 4


@pytest.mark.parametrize("cell", [w["name"] for w in spec.load_json(
    os.path.join(spec.ROOT, "BENCHMARK.json"))["workloads"]])
def test_a_listed_cell_without_a_tpu_exits_nonzero_and_prints_no_result(cell):
    proc = run("--workload", cell, "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert proc.returncode != 0
    assert "needs" in proc.stderr and "TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_allow_cpu_does_not_open_a_listed_cell():
    proc = run("--workload", "mistral7b-l16.chat", "--seed", "1", "--seconds",
               "1", "--trace", "0", "--allow-cpu")
    assert proc.returncode != 0

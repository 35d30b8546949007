"""``--trace 2``: a ``--trace 0`` run, then a traced tail of the same traffic
in the same process.  The window's arithmetic must not see the tail, the
client must plan the window as ``--trace 0`` does, and the per-layer readers
this mode brings read a hand-made ``ctx`` (``None`` from an empty one)."""

import asyncio
import copy
import os
import time

import pytest

from harness import spec
from harness.window import reduce_window
from loadgen.client import Client
from test_run_end_to_end import result_of, run

CELLS = os.path.join(spec.PERF_DIR, "tests", "cells")
BENCH = os.path.join(CELLS, "bench-trace-in-run.json")
E2E = ("tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")
COUNTER_READERS = (
    "sched.host_share", "sched.dispatch_ms", "sched.fetch_share",
    "sched.emit_us_per_token", "sched.block_k_mean",
    "sched.first_decode_wait_ms", "sched.queue_wait_ms")


def metric(kind, name):
    return spec.load_module(kind, name).read


# -- the run, end to end on the CPU -------------------------------------------

@pytest.mark.parametrize("cell,e2e", [
    ("tiny-lm.closed", {"tokens_per_s", "setup_s"}),
    ("tiny-lm.open", {"tokens_per_s", "setup_s", "ttft_p95_ms",
                      "tpot_p95_ms"})])
def test_trace_2_prints_one_line_with_both_kinds_of_metric(cell, e2e):
    proc = run("--workload", cell, "--seed", str(2**31 + 7), "--seconds",
               "2", "--trace", "2", "--benchmark", BENCH, "--allow-cpu")
    out = result_of(proc)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    m = out["metrics"]
    assert e2e <= set(m) and all(m[k]["value"] > 0 for k in e2e)
    # counter deltas over the window, sampler readings over the tail; a CPU
    # trace has no device plane, so the trace's readers are left out here
    assert set(COUNTER_READERS) <= set(m)
    assert m["compiles_in_window.lm"]["value"] == 0
    assert 0 < m["kv.pages_in_use_peak"]["value"] <= 100
    assert 0 < m["sched.fetch_share"]["value"] < 100
    assert m["sched.block_k_mean"]["value"] >= 1
    assert not any(k.startswith("step.") for k in m)
    lines = proc.stdout.strip().splitlines()
    assert sum(line.startswith("{") for line in lines) == 1
    assert any("traced tail: 3.0" in line for line in lines)
    if cell == "tiny-lm.open":      # the window's requests and no others
        assert out["attempted"] == 8


# -- the window's arithmetic does not see the tail -----------------------------

def _rec(i, sent, n, gap=0.1, ok=True, due=None, steps=None):
    times = [sent + 0.2 + gap * j for j in range(n)]
    rec = {"index": i, "due": due, "sent": sent, "steps": steps or n,
           "times": times, "ok": ok, "in_range": True}
    if ok:
        rec["end"] = times[-1] + 0.01
    return rec


def _cut(rec, t_end):
    """The record as ``--trace 0`` leaves it: cancelled at the close."""
    if rec.get("end", 0) <= t_end:
        return rec
    rec = dict(rec, ok=False, times=[t for t in rec["times"] if t <= t_end])
    del rec["end"]
    return rec


def _numbers(result):
    win = reduce_window(result)
    ctx = {"window": win, "say": lambda _m: None}
    return (win["attempted"], win["failed"], win["invalid"],
            [metric("e2e_metrics", name)(ctx) for name in E2E])


def test_closed_loop_window_numbers_are_those_of_trace_0():
    t0, t_end = 100.0, 110.0
    recs = [_rec(0, 99.0, 30), _rec(1, 101.0, 40), _rec(2, 104.0, 50),
            _rec(3, 108.5, 40),             # straddles the close
            _rec(4, 109.9, 20)]             # first token after the close
    tail = [_rec(5, 110.5, 30), _rec(6, 112.0, 30)]
    with_tail = {"t_start": t0, "t_end": t_end, "mode": "closed",
                 "requests": recs + tail, "tail_requests": []}
    # --trace 0: no caller starts a request after the close, and what is in
    # flight there is cancelled
    plain = dict(with_tail, requests=[_cut(r, t_end) for r in recs])
    assert _numbers(with_tail) == _numbers(plain)
    assert _numbers(plain)[0] == 3


def test_open_loop_window_numbers_are_those_of_trace_0():
    t0, t_end = 100.0, 110.0
    recs = [_rec(i, t0 + 2.0 * i, 30, due=t0 + 2.0 * i) for i in range(5)]
    recs.append(_rec(5, 109.5, 30, ok=False, due=109.5, steps=60))
    tail = [_rec(6, 110.5, 30), _rec(7, 112.0, 30)]
    with_tail = {"t_start": t0, "t_end": t_end, "mode": "open",
                 "requests": recs, "tail_requests": tail}
    plain = dict(with_tail, tail_requests=[])
    assert _numbers(with_tail) == _numbers(plain)
    assert _numbers(plain)[:2] == (6, 1)


# -- the client: the window's requests are those --trace 0 plans ---------------

class StubClient(Client):
    """``Client`` whose streams take a fixed time and touch no network."""

    def __init__(self, stream_s):
        self.stream_s = stream_s

    async def generate_stream(self, payload, rec, keep_tokens=False,
                              on_first=None):
        rec["sent"] = time.monotonic()
        await asyncio.sleep(self.stream_s / 2)
        rec["times"] = [time.monotonic()]
        if on_first is not None:
            on_first()
        await asyncio.sleep(self.stream_s / 2)
        rec["times"].append(time.monotonic())
        rec["ok"], rec["in_range"] = True, True


def _window(plan, tail_s, stop_after, stream_s=0.05):
    said = []

    async def stopped(tail_s):
        await asyncio.sleep(min(stop_after, tail_s))

    cmd = {"plan": plan, "tail_s": tail_s, "vocab": 64}
    payloads = [b""] * len(plan["requests"])
    result = asyncio.run(StubClient(stream_s).run_window(
        cmd, payloads, said.append, stopped))
    assert [m["event"] for m in said] == ["opened", "closed"]
    return result


def test_open_loop_tail_repeats_the_arrivals_outside_the_window():
    plan = {"mode": "open", "seconds": 0.5, "drain_s": 1.0, "requests": [
        {"index": i, "due_s": 0.1 * i, "steps": 2} for i in range(5)]}
    plain, tailed = _window(plan, 0, 0), _window(plan, 5.0, 0.25)
    for result in (plain, tailed):
        reqs = result["requests"]
        assert [r["index"] for r in reqs] == [0, 1, 2, 3, 4]
        assert [round(r["due"] - result["t_start"], 3) for r in reqs] == \
            [0.0, 0.1, 0.2, 0.3, 0.4]
        assert all(r["ok"] for r in reqs)
    assert plain["tail_requests"] == []
    tail = tailed["tail_requests"]
    # arrivals at the same gaps, a window later, until the stop: 0, .1, .2
    assert [r["index"] for r in tail] == [0, 1, 2]
    assert all(r["due"] is None and r["sent"] >= tailed["t_end"]
               for r in tail)
    assert reduce_window(tailed)["attempted"] == 5


def test_closed_loop_callers_replay_through_the_tail():
    plan = {"mode": "closed", "seconds": 0.4, "concurrency": 2,
            "ramp_max_s": 0, "requests": [
                {"index": i, "steps": 2} for i in range(3)]}
    plain, tailed = _window(plan, 0, 0), _window(plan, 5.0, 0.3)
    n_plain, n_tailed = len(plain["requests"]), len(tailed["requests"])
    # the cursor goes on: the same sequence, further
    assert [r["index"] for r in tailed["requests"]][:n_plain] == \
        [r["index"] for r in plain["requests"]]
    assert n_tailed > n_plain
    assert any(r["sent"] > tailed["t_end"] for r in tailed["requests"])
    a, b = reduce_window(plain), reduce_window(tailed)
    assert abs(a["attempted"] - b["attempted"]) <= 2     # wall-clock jitter
    assert all(r["end"] <= tailed["t_end"] for r in b["completed"])


# -- the readers this mode brings ----------------------------------------------

def _counters(scale):
    stages = {name: {"s": scale * s, "n": scale * n} for name, (s, n) in {
        "admit": (0.1, 100), "plan": (0.2, 100), "dispatch": (1.5, 100),
        "fetch": (6.0, 100), "commit": (0.3, 100), "emit": (0.4, 120),
        "idle": (1.5, 3)}.items()}
    return {"dispatch": {
        "stages": stages, "tokens_generated": scale * 2000,
        "decode_block_steps": scale * 180, "kinds": {
            "decode": scale * 90, "mixed": scale * 10, "verify": 0},
        "first_decode_wait_s": scale * 0.8, "first_decode_waits": scale * 10,
        "queue_wait_s": scale * 0.05, "queue_waits": scale * 10}}


CTX = {
    "window": {"seconds": 10.0},
    "counters_before": _counters(1), "counters_after": _counters(2),
    "trace": {"busy_s_per_chip": [2.0], "modules": {
        "jit_paged_decode_block_k2": {
            "count": 3, "durations_s": [0.028, 0.030, 0.026],
            "total_s": 0.084},
        "jit_paged_decode_block_k8": {
            "count": 1, "durations_s": [0.112], "total_s": 0.112},
        "jit_paged_mixed_step": {
            "count": 2, "durations_s": [0.2, 0.3], "total_s": 0.5}}},
}
EMPTY = {"window": {"seconds": 10.0}, "counters_before": {},
         "counters_after": {}, "trace": None}
EXPECTED = {
    "step.decode_ms": 14.0, "step.mixed_round_ms": 250.0,
    "step.mixed_share": 25.0, "sched.host_share": 25.0,
    "sched.dispatch_ms": 15.0, "sched.fetch_share": 60.0,
    "sched.emit_us_per_token": 200.0, "sched.block_k_mean": 2.0,
    "sched.first_decode_wait_ms": 80.0, "sched.queue_wait_ms": 5.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_new_reader(name):
    read = metric("layer_metrics", name)
    assert read(CTX) == pytest.approx(EXPECTED[name])
    assert read(EMPTY) is None
    # a program from before the counters, a slice without the program
    old = copy.deepcopy(CTX)
    for side in ("counters_before", "counters_after"):
        old[side]["dispatch"] = {"tokens_generated": 5, "kinds": {
            "decode": 1, "mixed": 0, "verify": 0}}
    old["trace"]["modules"] = {"jit__unknown": {
        "count": 1, "durations_s": [0.1], "total_s": 0.1}}
    assert read(old) is None


def test_no_mixed_round_in_a_slice_of_named_programs_is_a_share_of_zero():
    ctx = copy.deepcopy(CTX)
    del ctx["trace"]["modules"]["jit_paged_mixed_step"]
    assert metric("layer_metrics", "step.mixed_share")(ctx) == 0.0
    assert metric("layer_metrics", "step.mixed_round_ms")(ctx) is None


def test_every_listed_metric_has_its_reader_in_the_test_bench():
    real = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    mine = spec.load_json(BENCH)
    assert real["trace_in_run"] is True and mine["trace_in_run"] is True
    assert {m["name"] for m in real["per_layer"]} <= \
        {m["name"] for m in mine["per_layer"]}

"""The window arithmetic of both generators and of the reducer."""

import os

import numpy as np
import pytest

from harness import spec
from harness.sizes import quantile_sizes, size_pairs
from harness.window import reduce_window

CELLS = os.path.join(spec.PERF_DIR, "tests", "cells")
OPEN = spec.load_json(spec.find("traffic", "tiny-open.json", CELLS))
CHAT = spec.load_json(spec.find("traffic", "chat-closed-c8.json"))
CLOSED = spec.load_json(spec.find("traffic", "tiny-closed.json", CELLS))


def plan(traffic, seed, seconds):
    return spec.load_module("loadgen", traffic["generator"]).plan(
        traffic, seed, seconds)


def test_open_plan_is_drawn_from_the_seed_before_the_window():
    a, b = plan(OPEN, 2**31 + 11, 40), plan(OPEN, 2**31 + 11, 40)
    assert a == b
    n = round(OPEN["rate_per_s"] * 40)
    assert len(a["requests"]) == n
    due = [q["due_s"] for q in a["requests"]]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 40.0


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    a, b = plan(OPEN, 1, 40), plan(OPEN, 2, 40)
    key = lambda p: sorted((q["prompt_len"], q["steps"])  # noqa: E731
                           for q in p["requests"])
    assert key(a) == key(b)
    assert [q["prompt_len"] for q in a["requests"]] != \
        [q["prompt_len"] for q in b["requests"]]
    gaps = lambda p: np.sort(np.diff([q["due_s"]  # noqa: E731
                                      for q in p["requests"]]))
    # all gaps but the first (which the plan drops) are one fixed set
    assert abs(gaps(a).sum() - gaps(b).sum()) < 0.5


def test_sizes_respect_the_mix():
    p = quantile_sizes(CHAT["prompt_len"], 200)
    d = CHAT["prompt_len"]
    assert p.min() >= d["min"] and p.max() <= d["max"]
    assert 0.8 * d["median"] < np.median(p) < 1.2 * d["median"]
    chat = size_pairs(CHAT, CHAT["set_size"])
    assert chat[:, 1].min() >= 16 and chat[:, 1].max() <= 512
    # the engine must hold the longest request whole, and the pool every
    # lane at the longest request of the set
    assert chat.sum(1).max() <= CHAT["engine"]["max_len"]
    assert (chat.sum(1).max() * CHAT["engine"]["lanes"]
            <= CHAT["engine"]["pool_tokens"])
    # every seed's first wave is the whole set: the same work
    assert CHAT["set_size"] == CHAT["concurrency"] == CHAT["engine"]["lanes"]


@pytest.mark.parametrize("mix", [CHAT, CLOSED], ids=["chat", "tiny"])
def test_closed_plan_is_the_same_set_in_the_seeds_order(mix):
    a = plan(mix, 2**31 + 5, 40)
    assert a["mode"] == "closed" and a["concurrency"] == mix["concurrency"]
    assert len(a["requests"]) == mix["set_size"]
    assert plan(mix, 2**31 + 5, 40) == a
    b = plan(mix, 2**31 + 6, 40)
    sizes = lambda p: [(q["prompt_len"], q["steps"])  # noqa: E731
                       for q in p["requests"]]
    assert sizes(a) != sizes(b) and sorted(sizes(a)) == sorted(sizes(b))


def rec(**kw):
    base = {"ok": True, "steps": 3, "times": [1.0, 1.1, 1.2],
            "in_range": True, "sent": 0.9, "end": 1.3, "due": 0.9}
    base.update(kw)
    return base


def test_open_window_counts_every_due_request():
    result = {"mode": "open", "t_start": 0.0, "t_end": 2.0,
              "requests": [rec(), rec(ok=False, error="UNAVAILABLE: x"),
                           rec(ok=False, times=[1.0]),       # cut by the drain
                           rec(times=[1.0, 1.1]),            # too few tokens
                           rec(in_range=False)]}
    win = reduce_window(result)
    assert (win["attempted"], win["failed"], win["invalid"]) == (5, 4, 2)
    assert len(win["completed"]) == 1 and len(win["records"]) == 5


def test_closed_window_leaves_out_what_was_in_flight_at_the_close():
    in_flight = rec()
    del in_flight["end"]
    result = {"mode": "closed", "t_start": 0.0, "t_end": 2.0,
              "requests": [
                  rec(),
                  rec(sent=-3.0, end=0.2),               # begun in the ramp
                  rec(in_range=False),
                  rec(times=[1.0, 1.1]),                 # too few tokens
                  rec(ok=False, error="INTERNAL: boom"),
                  rec(sent=1.9, end=2.1),                # done after the close
                  in_flight]}
    win = reduce_window(result)
    assert (win["attempted"], win["failed"], win["invalid"]) == (5, 3, 2)
    assert len(win["records"]) == 7


@pytest.mark.parametrize("name,records,want", [
    ("tokens_per_s", [rec(times=[0.5, 1.0, 2.5])], 1.0),      # 2 of 3 inside
    ("ttft_p95_ms", [rec(due=0.5, times=[1.0, 1.1, 1.2])], 500.0),
    ("tpot_p95_ms", [rec(times=[1.0, 1.5, 2.0])], 500.0),
])
def test_end_to_end_readers(name, records, want):
    win = reduce_window({"mode": "open", "t_start": 0.0, "t_end": 2.0,
                         "requests": records})
    got = spec.load_module("e2e_metrics", name).read(
        {"window": win, "say": lambda m: None})
    assert got == pytest.approx(want)

"""The readers of the collector's pauses, the scheduler's stalls, the
fetch by readiness and the turn by cause
(``debug_state()["dispatch"]["host"]``, ``["fetches"]``,
``["turns"]["by_cause"]``) on a hand-made ``ctx``: the expected value, None
on empty counters, and None on a ``dispatch`` dict from before the program
counted them (the parent's side of a pair under these files)."""

import copy

import pytest

from harness import spec

CAUSES = ("completion", "joiner", "round", "k", "pages", "released",
          "single", "compact", "other")
#: a ``dispatch`` dict as a program from before PR 52 gave it
OLD = {"decode_block": 8, "kinds": {"decode": 100, "verify": 0, "mixed": 10},
       "completed_requests": 10, "tokens_generated": 900,
       "transfers": {"h2d": 110, "d2h": 110},
       "stages": {"fetch": {"s": 0.4, "n": 110}},
       "turns": {"n": 30, "s": 0.3, "stages": {"emit": 0.3}}}
BEFORE = dict(
    OLD,
    turns=dict(OLD["turns"], by_cause={
        c: {"n": 3, "s": 0.03} for c in CAUSES}),
    host={"gc": {"n": {"gen0": 5000, "gen1": 400, "gen2": 9},
                 "s": {"gen0": 0.5, "gen1": 0.4, "gen2": 0.9},
                 "max_s": 3.5, "collected": 12345,
                 "threshold": [700, 10, 10], "frozen": 0},
          "stalls": {"n": 40, "s": 60.0, "max_s": 30.0, "gc_s": 1.0,
                     "by_stage": {"dispatch": {"n": 40, "s": 60.0}},
                     "last": [{"stage": "dispatch", "s": 30.0, "gc_s": 0.0,
                               "in_turn": True}]}},
    fetches={"n": 110, "s": 0.4, "ready_n": 20, "ready_s": 0.01,
             "ready_slow_n": 0, "ready_slow_s": 0.0})
EXPECTED = {
    "host.gc_share": 2.0,                 # 0.25 + 0.25 + 0.5 s in 50 s
    "host.gc_full_ms": 125.0,             # 0.5 s over 4 full collections
    "sched.stall_share": 1.5,             # 0.75 s of stalls in 50 s
    "sched.stall_ms": 75.0,               # over 10 stalls
    "sched.stall_gc_share": 40.0,         # 0.3 s of the 0.75
    "sched.fetch_ready_share": 12.5,      # 50 of 400 fetches
    "sched.turn_completion_ms": 5.0,      # 0.2 s over 40 turns
    "sched.turn_compact_ms": 2.5,         # 0.05 s over 20 turns
}
#: the readers of a mean or a share OF the stalls or the full collections
#: read 0 where the counters are there and none fell in the window (every
#: cell's line carries them); the others read None there
ZERO_WHERE_NONE_FELL = {"host.gc_full_ms", "sched.stall_ms",
                        "sched.stall_gc_share"}


def _after():
    after = copy.deepcopy(BEFORE)
    gc, stalls, fetches = (after["host"]["gc"], after["host"]["stalls"],
                           after["fetches"])
    for gen, n, s in (("gen0", 20000, 0.25), ("gen1", 2000, 0.25),
                      ("gen2", 4, 0.5)):
        gc["n"][gen] += n
        gc["s"][gen] += s
    stalls["n"] += 10
    stalls["s"] += 0.75
    stalls["gc_s"] += 0.3
    stalls["by_stage"]["dispatch"] = {"n": 50, "s": 60.75}
    fetches["n"] += 400
    fetches["s"] += 30.0
    fetches["ready_n"] += 50
    fetches["ready_s"] += 0.2
    for cause, n, s in (("completion", 40, 0.2), ("compact", 20, 0.05),
                        ("other", 5, 0.01)):
        after["turns"]["by_cause"][cause]["n"] += n
        after["turns"]["by_cause"][cause]["s"] += s
    after["turns"]["n"] += 65
    after["turns"]["s"] += 0.26
    return after


def _ctx(before, after):
    return {"window": {"seconds": 50.0},
            "counters_before": {"dispatch": before},
            "counters_after": {"dispatch": after}}


def _read(name, ctx):
    return spec.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_expected_value(name):
    assert _read(name, _ctx(BEFORE, _after())) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_none_where_there_is_nothing_to_read(name):
    empty = {"window": {"seconds": 50.0}, "counters_before": {},
             "counters_after": {}}
    assert _read(name, empty) is None
    # a program from before these counters: its ``dispatch`` lacks the keys
    old_after = dict(OLD, completed_requests=50, tokens_generated=4500)
    assert _read(name, _ctx(OLD, old_after)) is None
    # the counters are there and nothing moved
    still = _read(name, _ctx(BEFORE, copy.deepcopy(BEFORE)))
    if name in ZERO_WHERE_NONE_FELL:
        assert still == 0.0
    else:
        assert still in (None, 0.0)
    assert _read(name, dict(_ctx(BEFORE, _after()),
                            window={"seconds": 0.0})) in (
        None, pytest.approx(EXPECTED[name]))


def test_a_window_with_collections_but_no_full_one_reads_its_share():
    after = _after()
    after["host"]["gc"]["n"]["gen2"] = BEFORE["host"]["gc"]["n"]["gen2"]
    after["host"]["gc"]["s"]["gen2"] = BEFORE["host"]["gc"]["s"]["gen2"]
    ctx = _ctx(BEFORE, after)
    assert _read("host.gc_full_ms", ctx) == 0.0
    assert _read("host.gc_share", ctx) == pytest.approx(1.0)


def test_every_reader_is_listed_with_a_reader_file_of_its_name():
    bench = spec.load_json(spec.os.path.join(spec.ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-8:]] == [
        "host.gc_share", "host.gc_full_ms", "sched.stall_share",
        "sched.stall_ms", "sched.stall_gc_share", "sched.fetch_ready_share",
        "sched.turn_completion_ms", "sched.turn_compact_ms"]
    for name in EXPECTED:
        m = listed[name]
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            "LM scheduler", "program_counter", "tokens_per_s", "lower")
        assert spec.os.path.exists(spec.os.path.join(
            spec.ROOT, "perf", "layer_metrics", name + ".py"))
        # every cell, but the turn behind a compaction: only the cell with
        # EVA windows has one, and a metric with no list has to be on
        # every cell's line
        if name == "sched.turn_compact_ms":
            assert m["workloads"] == ["evabyte-l8.bytes-longdoc"]
        else:
            assert "workloads" not in m

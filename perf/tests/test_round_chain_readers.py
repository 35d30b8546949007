"""The readers of the round's place in the chain (PR 49:
``debug_state()["dispatch"]["mixed_decode_rows"]``, ``["ahead_rounds"]``) on
a hand-made ``ctx``: the expected value, None on empty counters, in a window
without a round, and on a ``dispatch`` dict from before the program counted
them (the parent's side of a pair under these files)."""

import copy

import pytest

from harness import spec

#: a ``dispatch`` dict as a program from before PR 49 gave it
OLD = {"kinds": {"decode": 100, "verify": 0, "mixed": 10},
       "mixed_prompt_tokens": 4000, "ahead_blocks": 80}
BEFORE = dict(OLD, mixed_decode_rows=120, ahead_rounds=6,
              rounds_after_round=4)
EXPECTED = {
    "sched.round_decode_rows": 14.5,      # 580 rows on 40 rounds
    "sched.round_ahead_share": 85.0,      # 34 of 40 rounds
}


def _after():
    after = copy.deepcopy(BEFORE)
    after["kinds"]["mixed"] += 40
    after["kinds"]["decode"] += 25
    after["mixed_decode_rows"] += 580
    after["ahead_rounds"] += 34
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
    # a program from before this counter: its ``dispatch`` lacks the keys
    old_after = copy.deepcopy(OLD)
    old_after["kinds"]["mixed"] += 40
    assert _read(name, _ctx(OLD, old_after)) is None
    # the counters are there and the window held no round
    quiet = copy.deepcopy(BEFORE)
    quiet["kinds"]["decode"] += 25
    assert _read(name, _ctx(BEFORE, quiet)) is None


def test_both_readers_are_listed_for_all_cells():
    bench = spec.load_json(spec.os.path.join(spec.ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in (("sched.round_decode_rows", "rows"),
                       ("sched.round_ahead_share", "%")):
        m = listed[name]
        assert (m["layer"], m["source"], m["moves"], m["better"],
                m["unit"]) == ("LM scheduler", "program_counter",
                               "tokens_per_s", "higher", unit)
        assert "workloads" not in m
    # appended: nothing the benchmark had moved
    assert [m["name"] for m in bench["per_layer"]][-2:] == list(EXPECTED)[::1]

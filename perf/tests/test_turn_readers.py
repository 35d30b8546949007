"""The readers of the scheduler's turns, chain breaks and dispatch parts
(``debug_state()["dispatch"]["turns"]``, ``["chain"]``,
``["dispatch_parts"]``) on a hand-made ``ctx``: the expected value, None on
empty counters, and None on a ``dispatch`` dict from before the program
counted them (the parent's side of a pair under these files)."""

import copy

import pytest

from harness import spec

STAGES = {"admit": 0.25, "plan": 0.75, "dispatch": 2.0, "commit": 1.5,
          "emit": 0.5}
#: a ``dispatch`` dict as a program from before the turns gave it
OLD = {"decode_block": 8, "kinds": {"decode": 100, "verify": 0, "mixed": 10},
       "ahead_blocks": 80, "completed_requests": 10, "tokens_generated": 900,
       "stages": {"dispatch": {"s": 0.4, "n": 110}}}
BEFORE = dict(
    OLD,
    chain={"breaks": {"k1": 0, "shutdown_or_reclaim": 0, "released": 1,
                      "completion": 10, "joiner": 7, "spec": 0, "k": 2,
                      "pages": 0},
           "late_links": 3},
    turns={"n": 30, "s": 0.3, "stages": dict.fromkeys(STAGES, 0.06)},
    dispatch_parts={"arrays": {"s": 0.1, "n": 110},
                    "put": {"s": 0.2, "n": 110},
                    "call": {"s": 0.1, "n": 110}})
EXPECTED = {
    "sched.exposed_share": 10.0,          # 5.0 s of turns in 50 s
    "sched.turn_ms": 50.0,                # over 100 turns
    "sched.turns_per_request": 2.5,       # 100 turns, 40 requests
    "sched.turn_commit_ms": 15.0,
    "sched.turn_emit_ms": 5.0,
    "sched.turn_plan_ms": 10.0,           # plan 7.5 + admit 2.5
    "sched.turn_dispatch_ms": 20.0,
    "sched.break_completion_share": 50.0,  # 40 of 80 breaks
    "sched.break_joiner_share": 37.5,      # 30 of 80
    "sched.dispatch_arrays_ms": 0.5,       # 0.2 s over 400 dispatches
    "sched.dispatch_put_ms": 1.5,
    "sched.dispatch_call_ms": 1.25,
}


def _after():
    after = copy.deepcopy(BEFORE)
    after["completed_requests"] += 40
    after["turns"]["n"] += 100
    after["turns"]["s"] += sum(STAGES.values())
    for name, s in STAGES.items():
        after["turns"]["stages"][name] += s
    for cause, n in (("completion", 40), ("joiner", 30), ("released", 4),
                     ("k", 6)):
        after["chain"]["breaks"][cause] += n
    for name, s in (("arrays", 0.2), ("put", 0.6), ("call", 0.5)):
        after["dispatch_parts"][name]["s"] += s
        after["dispatch_parts"][name]["n"] += 400
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
    old_after = dict(OLD, completed_requests=50, tokens_generated=4500)
    assert _read(name, _ctx(OLD, old_after)) is None
    # the counters are there and nothing moved: no turn, no break, no block
    assert _read(name, _ctx(BEFORE, copy.deepcopy(BEFORE))) in (None, 0.0)
    assert _read(name, dict(_ctx(BEFORE, _after()),
                            window={"seconds": 0.0})) in (
        None, pytest.approx(EXPECTED[name]))


def test_the_four_turn_stages_sum_to_the_turn():
    ctx = _ctx(BEFORE, _after())
    parts = [_read(f"sched.turn_{s}_ms", ctx)
             for s in ("commit", "emit", "plan", "dispatch")]
    assert sum(parts) == pytest.approx(_read("sched.turn_ms", ctx))


def test_every_reader_is_listed_for_all_cells():
    bench = spec.load_json(spec.os.path.join(spec.ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = listed[name]
        assert (m["layer"], m["source"], m["moves"], m["better"]) == (
            "LM scheduler", "program_counter", "tokens_per_s", "lower")
        assert "workloads" not in m

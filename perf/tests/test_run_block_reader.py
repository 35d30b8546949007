"""``kv.run_block_share`` on a counters fixture: a share of the window's
walked key blocks, None where nothing was walked, None on a ``pool`` dict
from before the program counted them (the parent's side of a pair under
these files)."""

import pytest

from harness import spec

#: a ``pool`` dict as a program from before the counter gave it
OLD = {"n_pages": 4097, "free_pages": 1000, "page_size": 16}


def _read(before, after):
    return spec.load_module("layer_metrics", "kv.run_block_share").read(
        {"window": {"seconds": 50.0}, "counters_before": before,
         "counters_after": after})


def _pool(blocks, runs):
    return {"pool": dict(OLD, walk_block_pages=16, walk_blocks=blocks,
                         walk_run_blocks=runs)}


@pytest.mark.parametrize("before,after,want", [
    (_pool(1000, 900), _pool(5000, 3900), 75.0),
    (_pool(0, 0), _pool(640, 640), 100.0),
    (_pool(1000, 900), _pool(1000, 900), None),      # nothing walked
    ({"pool": OLD}, {"pool": OLD}, None),            # no such counter
    ({}, {}, None),
], ids=["share", "all-runs", "nothing-walked", "older-program", "empty"])
def test_run_block_share(before, after, want):
    got = _read(before, after)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_benchmark_lists_it_for_every_cell():
    import json
    import os
    bench = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "..", "BENCHMARK.json")))
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "kv.run_block_share"]
    assert entry == {"name": "kv.run_block_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "KV store", "moves": "tokens_per_s"}
    assert bench["per_layer"][-1] is entry

"""The trace reduction, on interval arithmetic and on a recorded trace.

``perf/testdata/lm_decode_slice.xplane.pb`` is 150 ms cut from a trace of
``mistral7b-l16.chat`` on a TPU v5e (chip run of PR 24): the first device
plane's ``XLA Ops`` and ``XLA Modules`` lines and the host span."""

import os

import pytest

from harness import spec
from harness.trace_reduce import (gaps, module_name, reduce_trace,
                                  union_length)

RECORDED = os.path.join(spec.PERF_DIR, "testdata",
                        "lm_decode_slice.xplane.pb")


def test_union_counts_overlaps_once():
    assert union_length([]) == 0
    assert union_length([(0, 2), (1, 3), (5, 6), (5, 5.5)]) == 4
    # an operation nested in another (a loop's body) adds nothing
    assert union_length([(0, 10), (2, 3), (4, 5)]) == 10


def test_gaps_are_what_the_intervals_leave():
    assert gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert gaps([(0, 6)], 0, 6) == []
    assert gaps([], 0, 6) == [(0, 6)]
    assert gaps([(0, 3), (1, 2), (2, 8)], 0, 6) == []


def test_module_name_drops_the_fingerprint():
    assert module_name("jit_paged_decode_block(123)") == \
        "jit_paged_decode_block"
    assert module_name("jit__unknown(5111475330265689330)") == "jit__unknown"


def test_recorded_trace():
    r = reduce_trace(RECORDED)
    assert r["window_s"] == pytest.approx(0.15)
    # decode blocks back to back: two host syncs of ~5 ms in 150 ms
    assert r["busy_s"] == pytest.approx(0.140019895, rel=1e-6)
    assert r["busy_s"] <= r["window_s"]
    assert r["busy_s_per_chip"] == [r["busy_s"]]
    unknown = r["modules"]["jit__unknown"]
    assert unknown["count"] == 3
    assert unknown["total_s"] == pytest.approx(0.140022959, rel=1e-6)
    assert len(r["device_ops"]) == 10
    name, seconds = r["device_ops"][0]
    assert "broadcast" in name and " while(" not in name
    assert seconds == pytest.approx(0.004348102, rel=1e-6)
    assert [round(g[1], 6) for g in r["idle_gaps"][:2]] == [0.005719,
                                                            0.004258]
    # the ten longest gaps are nearly all of the idle time
    idle = r["window_s"] - r["busy_s"]
    assert 0.999 * idle <= sum(g[1] for g in r["idle_gaps"]) <= idle


def test_a_trace_without_a_device_plane_is_an_error(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(ValueError, match="no /device:TPU"):
        reduce_trace(str(empty))

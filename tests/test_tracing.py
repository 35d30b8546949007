"""Tracing tests: the profiler switch, scheduler stages, program names,
request-lifecycle traces."""

import os
import threading
import time

import numpy as np
import pytest

from tpulab.utils import tracing
from tpulab.utils.tracing import annotate

REPO = __file__.rsplit("/tests/", 1)[0]


def _tiny_engine(**kw):
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    kw.setdefault("lanes", 2)
    return ContinuousBatcher(params, n_heads=2, n_layers=2, max_len=64,
                             page_size=8, compute_dtype=jnp.float32, **kw)


def _captures(log_dir):
    base = os.path.join(log_dir, "plugins", "profile")
    return [os.path.join(root, f) for root, _d, files in os.walk(base)
            for f in files if f.endswith(".xplane.pb")]


@pytest.fixture
def switch_closed():
    """The switch is process-wide: leave it closed whatever a test did."""
    assert not tracing.active()
    yield
    tracing.stop()


def test_switch_starts_and_stops_twice(tmp_path, switch_closed):
    """start/stop any number of times in one process, from any thread;
    every capture leaves its own trace directory; stop is idempotent."""
    import jax.numpy as jnp
    dirs = [str(tmp_path / "a"), str(tmp_path / "b")]
    assert tracing.start(dirs[0]) == dirs[0] and tracing.active()
    (jnp.ones((8, 8)) * 2).block_until_ready()
    assert tracing.stop() == dirs[0] and not tracing.active()
    assert tracing.stop() is None                  # nothing open: a no-op
    other = threading.Thread(target=tracing.start, args=(dirs[1],))
    other.start()
    other.join(timeout=30)
    assert tracing.active()
    with annotate("test-region", trace_id="abc"):
        (jnp.ones((8, 8)) * 3).block_until_ready()
    assert tracing.stop() == dirs[1]
    for d in dirs:
        assert _captures(d), f"no capture under {d}"


def test_switch_second_start_raises_and_first_closes(tmp_path,
                                                     switch_closed):
    first, second = str(tmp_path / "first"), str(tmp_path / "second")
    tracing.start(first)
    with pytest.raises(tracing.ProfilerBusy, match="already open"):
        tracing.start(second)
    with pytest.raises(tracing.ProfilerBusy):
        with tracing.trace(second):
            pass
    assert tracing.active()                 # the first capture still stands
    assert tracing.stop() == first
    assert _captures(first) and not os.path.exists(second)
    with tracing.trace(second, python_tracer=True):   # and a next one runs
        pass
    assert _captures(second)


def test_arm_profile_goes_through_switch(tmp_path, switch_closed):
    """The Debug RPC's profile_ticks and any other owner share the one
    switch: armed while a capture is open -> the switch's error, no JAX
    crash; armed alone -> the capture lands and the switch closes."""
    cb = _tiny_engine()
    try:
        tracing.start(str(tmp_path / "other"))
        with pytest.raises(tracing.ProfilerBusy):
            cb.arm_profile(2)
        assert cb.debug_state()["profile_armed"] is False
        tracing.stop()
        prof_dir = cb.arm_profile(2, str(tmp_path / "ticks"))
        assert len(cb.submit(np.arange(4, dtype=np.int32), 6)
                   .result(timeout=120)) == 6
        deadline = time.monotonic() + 30
        while cb._profile is not None and time.monotonic() < deadline:
            cb.submit(np.arange(4, dtype=np.int32), 2).result(timeout=120)
        assert cb._profile is None and not tracing.active()
        assert _captures(prof_dir)
    finally:
        cb.shutdown()


def test_stages_cover_the_scheduler_thread():
    """debug_state()["dispatch"]["stages"]: all seven, monotone, disjoint
    (their seconds never exceed the scheduler thread's wall time) and
    covering (at least four fifths of it)."""
    from tpulab.engine.paged import ContinuousBatcher
    t_before = time.perf_counter()
    cb = _tiny_engine(lanes=2)
    t_running = time.perf_counter()
    try:
        first = cb.debug_state()["dispatch"]["stages"]
        assert tuple(first) == ContinuousBatcher.STAGES == (
            "admit", "plan", "dispatch", "fetch", "commit", "emit", "idle")
        seen = []
        futs = [cb.submit(np.arange(3 + i, dtype=np.int32), 9,
                          on_token=lambda tok, i: seen.append(tok))
                for i in range(5)]
        for f in futs:
            assert len(f.result(timeout=120)) == 9
        mid = cb.debug_state()["dispatch"]["stages"]
        time.sleep(0.2)                     # the scheduler idles, waiting
    finally:
        cb.shutdown()
    t_after = time.perf_counter()
    assert not cb._thread.is_alive() and len(seen) == 45
    last = cb.debug_state()["dispatch"]["stages"]
    for name in ContinuousBatcher.STAGES:
        assert first[name]["s"] <= mid[name]["s"] <= last[name]["s"]
        assert first[name]["n"] <= mid[name]["n"] <= last[name]["n"]
        assert last[name]["n"] > 0 and last[name]["s"] > 0, name
    total = sum(v["s"] for v in last.values())
    assert total <= t_after - t_before
    assert total >= 0.8 * (t_after - t_running)


#: every ``sched.*`` span the scheduler can emit: the seven stages, the
#: three parts of a dispatch, a turn by what opened it, a stall
SCHED_SPANS = (
    "sched.admit", "sched.plan", "sched.dispatch", "sched.fetch",
    "sched.commit", "sched.emit", "sched.idle",
    "sched.dispatch.arrays", "sched.dispatch.put", "sched.dispatch.call",
    "sched.turn.completion", "sched.turn.joiner", "sched.turn.round",
    "sched.turn.k", "sched.turn.pages", "sched.turn.released",
    "sched.turn.single", "sched.turn.compact", "sched.turn.other",
    "sched.stall")


def _scheduler_clock():
    from tpulab.engine.paged import ContinuousBatcher as CB
    return tracing.StageClock(CB.STAGES, prefix="sched.",
                              turn=CB.TURN_STAGES, causes=CB.TURN_CAUSES,
                              parts=CB.DISPATCH_PARTS)


def test_clock_turn_opens_at_an_empty_queue_and_ends_at_the_launch():
    """The clock alone, no device: a turn opens where the fetch of the
    last un-fetched program ends, runs through the stages after it and
    ends INSIDE the dispatch stage where the next program is launched; a
    wait inside it (an idle wait, a fetch that has nothing to wait for)
    is no part of its seconds and closes its span; the stages read what
    they always read, and a part does not pause its stage."""
    from tpulab.utils.tracing import part, stage
    st = _scheduler_clock()
    nap = 0.01
    with stage(st, "dispatch"):
        time.sleep(nap)
        first = st.launched()          # no turn was open: nothing to end
    with stage(st, "fetch"):
        time.sleep(nap)
        st.landed(first, "completion", lanes=2)
    assert st.turns()["n"] == 0 and st._in_turn and st._turn is not None
    with stage(st, "commit"):
        time.sleep(nap)
        with stage(st, "admit"):       # nested: pauses commit, in the turn
            time.sleep(nap)
    with stage(st, "dispatch"):
        with part(st, "dispatch.arrays"):
            time.sleep(nap)
        second = st.launched()         # the turn ends here, mid-stage
        third = st.launched()          # chained behind it
        time.sleep(nap)                # dispatch runs on, outside the turn
    assert st.turns()["n"] == 1
    with stage(st, "fetch"):
        st.landed(second, "joiner")    # `third` is behind it: no turn
    with stage(st, "emit"):
        time.sleep(nap)
    assert not st._in_turn and st._turn is None
    with stage(st, "fetch"):
        st.landed(third)
    assert st._in_turn and st._turn is not None    # opened as `other`
    with stage(st, "idle"):            # a wait: the span closes, the turn
        assert st._in_turn and st._turn is None    # stays open
        time.sleep(nap)
    with stage(st, "fetch"):           # so does a fetch with nothing to
        assert st._turn is None        # wait for, which renames the span
        st.landed(third, "joiner", lanes=1)
    assert st._in_turn and st._turn is not None and st.turns()["n"] == 1
    with stage(st, "plan"):
        time.sleep(nap)
        st.launched()
    t, stages, parts = st.turns(), st.stages(), st.parts()
    assert t["n"] == 2 and not st._in_turn and set(t["stages"]) == {
        "admit", "plan", "dispatch", "commit", "emit"}
    assert t["s"] == pytest.approx(sum(t["stages"].values()), abs=1e-12)
    assert t["stages"]["commit"] == pytest.approx(stages["commit"]["s"])
    assert t["stages"]["admit"] == pytest.approx(stages["admit"]["s"])
    assert nap <= t["stages"]["plan"] <= stages["plan"]["s"]
    assert t["stages"]["emit"] == 0.0
    # one of dispatch's three naps fell inside the turn, no idle second did
    assert nap <= t["stages"]["dispatch"] < stages["dispatch"]["s"] - nap
    assert 4 * nap <= t["s"] < 5 * nap + 0.05
    assert stages["dispatch"]["n"] == 2 and stages["fetch"]["n"] == 4
    assert parts["dispatch.arrays"]["n"] == 1
    assert nap <= parts["dispatch.arrays"]["s"] <= stages["dispatch"]["s"]
    assert parts["dispatch.put"] == {"s": 0.0, "n": 0}


def _consumed_blocks(cb):
    """Count the decode blocks and the mixed rounds ``cb`` consumes from
    here on: ``(K of each block, rounds)``."""
    calls, rounds = [], []
    block, round_ = cb._consume_block, cb._consume_round

    def counted(stash, jnp):
        calls.append(stash["k"])
        return block(stash, jnp)

    def counted_round(stash, jnp):
        rounds.append(stash["kind"])
        return round_(stash, jnp)
    cb._consume_block, cb._consume_round = counted, counted_round
    return calls, rounds


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_turns_chain_and_parts_account_for_the_scheduler(use_kernel):
    """``dispatch["turns"]``: its stages sum to its seconds and never
    exceed the stages' own; ``dispatch["chain"]``: every consumed decode
    block and mixed round either had its successor (a block or a round)
    enqueued ahead or a counted cause;
    ``dispatch["dispatch_parts"]``: inside the ``dispatch`` stage."""
    from tpulab.engine.paged import ContinuousBatcher as CB
    cb = _tiny_engine(lanes=2, use_kernel=use_kernel)
    consumed, rounds = _consumed_blocks(cb)
    try:
        futs = [cb.submit(np.arange(3 + i, dtype=np.int32), 7 + 3 * i,
                          on_token=(lambda tok, i: None) if i % 2 else None)
                for i in range(5)]
        for i, f in enumerate(futs):
            assert len(f.result(timeout=120)) == 7 + 3 * i
    finally:
        cb.shutdown()
    d = cb.debug_state()["dispatch"]
    turns, stages = d["turns"], d["stages"]
    assert tuple(turns["stages"]) == CB.TURN_STAGES and turns["n"] > 0
    assert turns["s"] == pytest.approx(sum(turns["stages"].values()),
                                       abs=1e-12)
    for name, s in turns["stages"].items():
        assert 0 <= s <= stages[name]["s"] + 1e-9, name
    assert turns["s"] > 0
    # a turn ends with a launch: never more of them than programs launched
    assert turns["n"] <= d["decode_dispatches"]
    chain = d["chain"]
    assert tuple(chain["breaks"]) == CB.BREAK_CAUSES
    assert consumed and len(consumed) + len(rounds) == (
        d["ahead_blocks"] + d["ahead_rounds"]
        + sum(chain["breaks"].values()))
    assert len(rounds) == d["kinds"]["mixed"]
    assert chain["breaks"]["k1"] == 0 and chain["breaks"]["completion"] > 0
    assert 0 <= chain["late_links"] <= sum(chain["breaks"].values())
    parts = d["dispatch_parts"]
    assert tuple(parts) == ("arrays", "put", "call")
    blocks_and_rounds = len(consumed) + d["kinds"]["mixed"]
    assert all(p["n"] == blocks_and_rounds for p in parts.values())
    assert 0 < sum(p["s"] for p in parts.values()) <= stages["dispatch"]["s"]
    assert d["kinds"]["mixed"] > 0


def _force_completion(cb, seen):
    # one lane, nine tokens: the first from the prefill, a K=8 block whose
    # step budget ends inside it
    cb.submit(np.arange(4, dtype=np.int32), 9).result(timeout=120)
    return dict(cb.chain_breaks)


def _force_k(cb, seen):
    # twelve tokens to a batch consumer: after the K=8 block three are
    # left, which a K=4 block covers: the policy changes its mind
    def on_token(tok, i):
        if i == 1:                     # the first token of that block
            seen.append(dict(cb.chain_breaks))
    cb.submit(np.arange(4, dtype=np.int32), 12, on_token=on_token,
              request_class="batch").result(timeout=120)
    return seen[0]


def _force_released(cb, seen):
    # cancelled from its own token hook (the scheduler thread, in `emit`)
    # while the next block is already on the queue
    futs = []

    def on_token(tok, i):
        if i == 5:
            cb.cancel(futs[0])
    futs.append(cb.submit(np.arange(4, dtype=np.int32), 40,
                          on_token=on_token))
    with pytest.raises(Exception):
        futs[0].result(timeout=120)
    deadline = time.monotonic() + 30
    while cb.active_lanes and time.monotonic() < deadline:
        time.sleep(0.01)
    return dict(cb.chain_breaks)


def _force_joiner(cb, seen):
    # a second request submitted while the first one's chain runs: it
    # prefills beside the chain, and with its first token out the next
    # block of the chain is held back for it
    futs = []

    def second(tok, i):
        if i == 1:                     # it has had a decode step: joined
            seen.append(dict(cb.chain_breaks))

    def first(tok, i):
        if i == 5:
            futs.append(cb.submit(np.arange(6, dtype=np.int32), 6,
                                  on_token=second))
    futs.append(cb.submit(np.arange(4, dtype=np.int32), 40, on_token=first))
    for f in list(futs) + futs[1:]:
        f.result(timeout=120)
    return seen[0]


@pytest.mark.parametrize("cause,lanes,force", [
    ("completion", 1, _force_completion),
    ("k", 1, _force_k),
    ("released", 1, _force_released),
    ("joiner", 2, _force_joiner),
])
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["xla", "kernels-interpret"])
def test_chain_break_is_counted_under_its_cause(cause, lanes, force,
                                                use_kernel):
    """Each cause a CPU batcher can be driven into, forced from the
    scheduler's own thread (a token hook) so the order is the program's:
    counted once under its name, and nothing under any other.  A request
    that arrives beside a running chain is no ``joiner``: its prompt rides
    a round of the chain and its lane decodes from that round's carry, so
    nothing breaks."""
    cb = _tiny_engine(lanes=lanes, use_kernel=use_kernel)
    try:
        breaks = force(cb, [])
    finally:
        cb.shutdown()
    if cause == "joiner":
        assert not any(breaks.values()), breaks
        assert cb.ahead_rounds >= 1
        return
    assert breaks.pop(cause) == 1
    assert not any(breaks.values()), breaks


@pytest.mark.parametrize("name", SCHED_SPANS)
def test_sched_span_is_documented(name):
    """Every span the scheduler's clock can emit has its row in the span
    table of docs/OBSERVABILITY.md (and the list above is the clock's)."""
    st = _scheduler_clock()
    emitted = (set(st.span_names.values()) | set(st.turn_names.values())
               | {st.stall_name})
    assert emitted == set(SCHED_SPANS)
    doc = open(f"{REPO}/docs/OBSERVABILITY.md").read()
    assert f"| `{name}` |" in doc, f"{name} has no row in the span table"


def test_collector_span_is_documented():
    doc = open(f"{REPO}/docs/OBSERVABILITY.md").read()
    assert "| `host.gc` |" in doc


@pytest.fixture
def collector_watched():
    """The collector's counters are the process's: a test reads deltas,
    and automatic collections are held off while it does."""
    import gc
    tracing.watch_collector()
    enabled = gc.isenabled()
    gc.disable()
    yield gc
    if enabled:
        gc.enable()


def test_watch_collector_installs_one_callback(collector_watched):
    gc = collector_watched
    tracing.watch_collector()
    cb = _tiny_engine(lanes=1)          # an engine asks for it too
    cb.shutdown()
    assert gc.callbacks.count(tracing._on_collection) == 1
    p = tracing.collector_pauses()
    assert set(p) == {"n", "s", "max_s", "collected", "threshold", "frozen"}
    assert set(p["n"]) == set(p["s"]) == {"gen0", "gen1", "gen2"}
    assert p["threshold"] == list(gc.get_threshold())
    assert p["frozen"] == gc.get_freeze_count()


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_collection_moves_its_generation_alone(collector_watched,
                                                 generation):
    """``gc.collect(g)`` adds one entry and its seconds under ``gen<g>``;
    generations 1 and 2 land in the ring the stalls read, generation 0 is
    counted only."""
    gc = collector_watched
    before, ring = tracing.collector_pauses(), len(tracing._gc_ring)
    last = tracing._gc_ring[-1] if ring else None
    t0 = time.perf_counter()
    gc.collect(generation)
    t1 = time.perf_counter()
    after = tracing.collector_pauses()
    for g in range(3):
        name = f"gen{g}"
        moved = int(g == generation)
        assert after["n"][name] - before["n"][name] == moved
        assert (after["s"][name] > before["s"][name]) == bool(moved)
    assert after["max_s"] >= after["s"][f"gen{generation}"] - before["s"][
        f"gen{generation}"]
    assert after["collected"] >= before["collected"]
    if generation == 0:
        assert (tracing._gc_ring[-1] if ring else None) == last
    else:
        p0, dt, gen = tracing._gc_ring[-1]
        assert gen == generation and t0 <= p0 and p0 + dt <= t1 and dt > 0
        assert len(tracing._gc_ring) <= 64


def _stall_clock(**kw):
    from tpulab.engine.paged import ContinuousBatcher as CB
    return tracing.StageClock(CB.STAGES, prefix="sched.",
                              turn=CB.TURN_STAGES, causes=CB.TURN_CAUSES,
                              parts=CB.DISPATCH_PARTS, **kw)


def test_a_slow_working_stage_is_one_stall_and_a_slow_wait_is_none(
        collector_watched):
    from tpulab.utils.tracing import stage
    st = _stall_clock(slow_s=0.01)
    assert st.stalls() == {
        "n": 0, "s": 0.0, "max_s": 0.0, "gc_s": 0.0, "last": [],
        "by_stage": {n: {"n": 0, "s": 0.0} for n in (
            "admit", "plan", "dispatch", "commit", "emit")}}
    with stage(st, "fetch"):
        time.sleep(0.015)              # a wait is long by nature
    with stage(st, "idle"):
        time.sleep(0.015)
    with stage(st, "plan"):
        pass                           # fast: no stall
    assert st.stalls()["n"] == 0
    with stage(st, "commit"):
        time.sleep(0.015)
    stalls = st.stalls()
    assert stalls["n"] == 1 and stalls["by_stage"]["commit"]["n"] == 1
    assert 0.015 <= stalls["s"] == stalls["max_s"] < 0.05
    assert stalls["by_stage"]["commit"]["s"] == stalls["s"]
    assert stalls["gc_s"] == 0.0       # no collection ran inside it
    # the waits before it ended with nothing un-fetched: a turn was open
    assert stalls["last"] == [{"stage": "commit", "s": stalls["s"],
                               "gc_s": 0.0, "in_turn": True}]
    with stage(st, "dispatch"):
        st.launched()
        time.sleep(0.015)              # under a program on the queue
    assert st.stalls()["last"][-1]["in_turn"] is False
    assert st.stalls()["n"] == 2
    assert sum(v["n"] for v in stalls["by_stage"].values()) == 1


def test_a_stall_knows_the_collections_inside_it(collector_watched):
    """A full collection inside a working stage: ``0 < gc_s <= s``, and
    the stall says it stood inside a turn."""
    from tpulab.utils.tracing import stage
    gc = collector_watched
    st = _stall_clock(slow_s=0.01)
    with stage(st, "dispatch"):
        ticket = st.launched()
    with stage(st, "fetch"):
        st.landed(ticket, "completion")
    junk = [[i] for i in range(20000)]  # something to walk
    with stage(st, "emit"):
        time.sleep(0.012)
        gc.collect()
    del junk
    stalls = st.stalls()
    assert stalls["n"] == 1 and stalls["last"][0]["stage"] == "emit"
    assert stalls["last"][0]["in_turn"] is True
    assert 0 < stalls["gc_s"] <= stalls["s"]
    assert stalls["last"][0]["gc_s"] == stalls["gc_s"]
    # a pause before the run began is no part of it
    with stage(st, "plan"):
        time.sleep(0.012)
    assert st.stalls()["last"][-1] == {
        "stage": "plan", "s": st.stalls()["by_stage"]["plan"]["s"],
        "gc_s": 0.0, "in_turn": True}


def test_capture_holds_the_collection_and_the_stall(tmp_path, switch_closed,
                                                   collector_watched):
    """In a capture: ``host.gc`` around a collection of generation 1 or 2
    (none around generation 0), on the device trace's clock and the
    collecting thread, inside the stage it stopped; ``sched.stall`` where
    the slow run ends, with what the collector took of it."""
    from jax.profiler import ProfileData

    from tpulab.utils.tracing import stage
    gc = collector_watched
    st = _stall_clock(slow_s=0.005)
    with tracing.trace(str(tmp_path / "t")):
        with stage(st, "commit"):
            time.sleep(0.006)
            gc.collect(0)
            gc.collect(1)
            gc.collect(2)
    events = {}
    for plane in ProfileData.from_file(
            _captures(str(tmp_path / "t"))[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("host.gc", "sched.stall", "sched.commit"):
                    events.setdefault(ev.name, []).append(
                        (line.name, ev.start_ns, ev.duration_ns,
                         dict(ev.stats)))
    assert [e[3]["generation"] for e in events["host.gc"]] == [1, 2]
    assert all(e[3]["collected"] >= 0 for e in events["host.gc"])
    (thread, c0, cdur, _), = events["sched.commit"]
    for line, t0, dur, _stats in events["host.gc"]:
        assert line == thread and c0 <= t0 and t0 + dur <= c0 + cdur
    (line, _t0, _dur, stats), = events["sched.stall"]
    assert line == thread and stats["stage"] == "commit"
    assert 0 < stats["gc_ms"] <= stats["ms"]
    last = st.stalls()["last"][-1]
    assert stats["ms"] == pytest.approx(last["s"] * 1e3, abs=1e-3)


def test_fast_stages_never_read_the_collectors_ring(monkeypatch):
    """The normal path is one comparison a booked run."""
    from tpulab.utils.tracing import part, stage

    def raises(*a):
        raise AssertionError("the ring was read on a fast run")
    monkeypatch.setattr(tracing, "_collector_seconds_within", raises)
    st = _stall_clock()                # the default: 20 ms
    assert st.slow_s == 0.02
    for _ in range(50):
        with stage(st, "plan"):
            pass
        with stage(st, "dispatch"):
            with part(st, "dispatch.arrays"):
                pass
            ticket = st.launched()
        with stage(st, "fetch"):
            time.sleep(0.0005)
            st.landed(ticket, "completion")
        with stage(st, "commit"):
            with stage(st, "admit"):
                pass
        with stage(st, "emit"):
            pass
    assert st.stalls()["n"] == 0 and st.turns()["n"] == 49
    st.slow_s = 0.0                    # and a slow run does read it
    with pytest.raises(AssertionError, match="ring was read"):
        with stage(st, "plan"):
            pass


def test_turns_by_cause_sum_to_the_turns():
    """A scripted sequence: a turn that closes under the cause it opened
    with, a turn of two spans (a wait inside it renames it: seconds to
    both causes, the entry to the second), a cause the clock does not
    name (``other``), and a fetch that opens no turn."""
    from tpulab.utils.tracing import stage
    st = _scheduler_clock()
    nap = 0.003

    def block(*causes):
        with stage(st, "dispatch"):
            time.sleep(nap)
            ticket = st.launched()
        for cause in causes:
            with stage(st, "fetch"):
                st.landed(ticket, cause)
            with stage(st, "commit"):
                time.sleep(nap)
    block("completion")
    block("joiner", "round")           # one turn, two spans
    block("no-such-cause")
    with stage(st, "dispatch"):
        a = st.launched()
        b = st.launched()
    with stage(st, "fetch"):
        st.landed(a, "k")              # `b` is behind it: no turn
    with stage(st, "emit"):
        time.sleep(nap)
    with stage(st, "fetch"):
        st.landed(b, "compact")
    with stage(st, "idle"):            # a wait renames the open turn
        pass
    with stage(st, "plan"):
        time.sleep(nap)
        st.launched()
    t = st.turns()
    by_cause = t["by_cause"]
    assert tuple(by_cause) == (*st.turn_names,) and "other" in by_cause
    assert {c: v["n"] for c, v in by_cause.items() if v["n"]} == {
        "completion": 1, "round": 1, "other": 2}
    assert sum(v["n"] for v in by_cause.values()) == t["n"] == 4
    assert sum(v["s"] for v in by_cause.values()) == pytest.approx(
        t["s"], abs=1e-12)
    for cause in ("completion", "joiner", "round"):
        assert by_cause[cause]["s"] >= nap, cause
    assert by_cause["joiner"]["n"] == 0 and by_cause["k"]["s"] == 0.0
    assert by_cause["compact"] == {"n": 0, "s": 0.0}   # renamed at once
    assert by_cause["other"]["s"] >= 2 * nap


def test_engine_reports_host_fetches_and_turns_by_cause():
    """A paged engine on the tiny model: ``host``, ``fetches`` and
    ``turns.by_cause`` in ``debug_state()["dispatch"]``, consistent and
    monotone over two snapshots."""
    from tpulab.engine.paged import ContinuousBatcher as CB
    assert CB.STALL_S == 0.02
    cb = _tiny_engine(lanes=2)
    snaps = []
    try:
        assert cb._stages.slow_s == CB.STALL_S
        for _ in range(2):
            futs = [cb.submit(np.arange(3 + i, dtype=np.int32), 9)
                    for i in range(3)]
            for f in futs:
                assert len(f.result(timeout=120)) == 9
            snaps.append(cb.debug_state()["dispatch"])
    finally:
        cb.shutdown()
    for d in snaps:
        f, turns, host = d["fetches"], d["turns"], d["host"]
        assert set(f) == {"n", "s", "ready_n", "ready_s", "ready_slow_n",
                          "ready_slow_s"}
        assert 0 <= f["ready_slow_n"] <= f["ready_n"] <= f["n"]
        assert 0 <= f["ready_slow_s"] <= f["ready_s"] <= f["s"]
        assert f["n"] == d["transfers"]["d2h"] > 0
        by_cause = turns["by_cause"]
        assert tuple(by_cause) == (*CB.TURN_CAUSES, "other")
        assert sum(v["n"] for v in by_cause.values()) == turns["n"] > 0
        assert sum(v["s"] for v in by_cause.values()) == pytest.approx(
            turns["s"], abs=1e-9)
        stalls, pauses = host["stalls"], host["gc"]
        assert 0 <= stalls["gc_s"] <= stalls["s"] + 1e-12
        assert stalls["n"] == sum(
            v["n"] for v in stalls["by_stage"].values())
        assert tuple(stalls["by_stage"]) == CB.TURN_STAGES
        assert len(stalls["last"]) == min(stalls["n"], 8)
        assert set(pauses["n"]) == {"gen0", "gen1", "gen2"}

    def numbers(tree, path=()):
        for key, v in tree.items():
            if isinstance(v, dict):
                yield from numbers(v, path + (key,))
            elif isinstance(v, (int, float)) and key not in (
                    "frozen", "max_s"):
                yield path + (key,), v
    first = dict(numbers({k: snaps[0][k]
                          for k in ("fetches", "host", "turns")}))
    second = dict(numbers({k: snaps[1][k]
                           for k in ("fetches", "host", "turns")}))
    assert first.keys() == second.keys()
    for path, v in first.items():
        assert v <= second[path], path
    assert second[("fetches", "n")] > first[("fetches", "n")]
    assert snaps[1]["host"]["stalls"]["max_s"] >= snaps[0]["host"][
        "stalls"]["max_s"]


def test_wait_counters_match_the_requests():
    """One lane, requests one after the other, nine tokens each: the
    first from the round that carried the prompt, the other eight in one
    K=8 block."""
    cb = _tiny_engine(lanes=1, decode_block=8)
    n = 3
    try:
        for i in range(n):
            assert len(cb.submit(np.arange(4 + i, dtype=np.int32), 9)
                       .result(timeout=120)) == 9
        d = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert d["queue_waits"] == d["ttfts"] == d["first_decode_waits"] == n
    assert d["kinds"]["decode"] == n and d["decode_block_steps"] == 8 * n
    assert 0 < d["queue_wait_s"] <= d["ttft_s"]
    assert d["first_decode_wait_s"] > 0
    # single ticks count one step each; a one-token request never waits
    # for a second token
    cb = _tiny_engine(lanes=1, decode_block=1)
    try:
        cb.submit(np.arange(5, dtype=np.int32), 4).result(timeout=120)
        cb.submit(np.arange(5, dtype=np.int32), 1).result(timeout=120)
        d = cb.debug_state()["dispatch"]
    finally:
        cb.shutdown()
    assert d["queue_waits"] == d["ttfts"] == 2
    assert d["first_decode_waits"] == 1
    assert d["decode_block_steps"] == d["kinds"]["decode"] == 3


def test_tokens_identical_with_profiler_on_and_off(tmp_path, switch_closed):
    """The profiler and the stage spans observe, never steer."""
    from tpulab.engine.paged import SamplingParams
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, (5 + i,), np.int32) for i in range(4)]

    def run():
        cb = _tiny_engine(lanes=2)
        try:
            futs = [cb.submit(p, 10, sampling=SamplingParams(
                temperature=0.7 * (i % 2), seed=i, device=True))
                for i, p in enumerate(prompts)]
            return [f.result(timeout=120) for f in futs]
        finally:
            cb.shutdown()

    off = run()
    with tracing.trace(str(tmp_path / "on")):
        on = run()
    assert on == off
    captures = _captures(str(tmp_path / "on"))
    assert captures
    # the capture holds the turn, the parts and the stats on a dispatch
    from jax.profiler import ProfileData
    spans = {}
    for plane in ProfileData.from_file(captures[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sched."):
                    spans.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(spans) <= set(SCHED_SPANS)
    assert {"sched.dispatch.arrays", "sched.dispatch.put",
            "sched.dispatch.call"} <= set(spans)
    turns = {name: evs for name, evs in spans.items()
             if name.startswith("sched.turn.")}
    # every turn a fetch opened carries the lanes of what was fetched (one
    # that an idle wait opened, an `other`, has nothing to say)
    assert turns and all("lanes" in stats for name, evs in turns.items()
                         for stats in evs
                         if stats or name != "sched.turn.other")
    noted = [stats for stats in spans["sched.dispatch"] if stats]
    assert len(noted) == len(spans["sched.dispatch.call"])
    blocks = [d for d in noted if d["program"] == "paged_decode_block_k8"]
    rounds = [d for d in noted if d["program"] == "paged_mixed_step"]
    assert blocks and rounds and len(blocks) + len(rounds) == len(noted)
    assert all(set(b) == {"program", "k", "lanes", "rows", "ahead"}
               and b["k"] == 8 and b["ahead"] in (0, 1) for b in blocks)
    # the prompts came in rounds (26 tokens in all: 5 + 6 + 7 + 8)
    assert all(set(r) == {"program", "k", "lanes", "rows", "prompt_tokens",
                          "decode_rows", "ahead"}
               and r["k"] == 1 and r["ahead"] in (0, 1) for r in rounds)
    assert sum(r["prompt_tokens"] for r in rounds) == 26


def _lower_args(cb, kind):
    """The jitted step of ``kind`` and arguments of the engine's own
    shapes to lower it with."""
    import jax.numpy as jnp
    from tpulab.engine.paged_steps import pack_words
    b, mp = cb.lanes, cb.max_pages
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731

    def packed(program, width):
        """A buffer of zeros for ``program``, its open field ``width``."""
        fields = cb.programs.fields[program]
        return jnp.asarray(pack_words(fields, {
            name: np.zeros([width if n < 0 else n for n in shape], dtype)
            for name, dtype, shape in fields}))

    one = (i32(mp), i32(1, 8))
    head = (cb.params, cb.pool.kv)
    block = lambda: head + (packed("block", 1), cb._no_carry)  # noqa: E731
    return {
        "paged_decode_block_k2": lambda: (cb.programs.block(2), block()),
        "paged_decode_block_k8": lambda: (cb.programs.block(8), block()),
        "paged_decode_step_sampled": lambda: (
            cb.programs.tick, head + (packed("tick", 0),)),
        "paged_mixed_step": lambda: (
            cb.programs.mixed,
            head + (packed("round", 8 + b), cb._no_carry)),
        # the speculative draft's warm-up: the one caller ``paged_extend``
        # has left
        "paged_extend": lambda: (
            cb.programs.draft_extend,
            (cb._spec["params"], cb.pool.kv) + one
            + (jnp.int32(8), jnp.int32(13))),
        # EVA: a finished window's pages (``perf/layer_metrics/
        # eva.summary_roofline.py`` reads the program by this name)
        "paged_eva_compact": lambda: (
            cb.programs.compact,
            head + (i32(cb.plan.eva_window // cb.page_size),)),
        "paged_speculative_block_k2": lambda: (
            cb.programs.spec_block(2),
            (cb.params, cb._spec["params"], cb.pool.kv, packed("spec", 1))),
    }[kind]()


@pytest.mark.parametrize("kind", [
    "paged_decode_block_k2", "paged_decode_block_k8",
    "paged_decode_step_sampled", "paged_mixed_step", "paged_extend",
    "paged_eva_compact", "paged_speculative_block_k2"])
def test_step_programs_carry_stable_names(kind):
    """Every step program is built in StepPrograms._jit, which names
    it after its function (+ the block size the partial binds): a trace's
    XLA Modules line shows ``jit_<kind>``, never ``jit__unknown``."""
    if kind == "paged_eva_compact":
        import test_evabyte
        from tpulab.models.spec import evabyte_spec, init_params
        spec = evabyte_spec(test_evabyte.CONFIG)
        cb = test_evabyte._engine((spec, init_params(
            spec, test_evabyte.VOCAB, test_evabyte.D_FF)))
    else:
        from tpulab.models.transformer import init_transformer_params
        draft = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                        n_layers=1, d_ff=64, seed=1)
        cb = _tiny_engine(draft_params=draft, draft_n_layers=1)
    try:
        fn, args = _lower_args(cb, kind)
        text = fn.lower(*args).as_text()
    finally:
        cb.shutdown()
    assert f"module @jit_{kind} " in text.splitlines()[0]


def _pallas_names(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"]
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield from _pallas_names(inner)


@pytest.mark.parametrize("kernel", [
    "ragged_paged_attention", "ragged_paged_decode",
    "ragged_latent_attention"])
def test_pallas_kernels_carry_names(kernel):
    """``name=`` on three of the pallas_calls (interpret mode here): the
    name a device trace shows the kernel under.  One row a lane takes the
    one-row kernel, by the shape alone."""
    import jax
    import jax.numpy as jnp
    kv = jnp.ones((5, 2, 8, 2, 32))
    tables = jnp.zeros((2, 4), jnp.int32)
    lens = jnp.array([3, 9], jnp.int32)
    if kernel == "ragged_latent_attention":
        from tpulab.ops.ragged_attention import ragged_latent_attention
        fn, q = (lambda q: ragged_latent_attention(
            q, kv.reshape(1, 5, 1, 8, 4 * 32), 0, tables,
            jnp.array([1, 2], jnp.int32), lens, v_width=64,
            sm_scale=0.125)), \
            jnp.ones((2, 2, 2, 96))
    else:
        from tpulab.ops.ragged_attention import ragged_paged_attention
        rows = 1 if kernel == "ragged_paged_decode" else 2
        fn, q = (lambda q: ragged_paged_attention(
            q, kv.reshape(1, 5, 2, 8, 2 * 32), 0, tables,
            jnp.array([1, rows], jnp.int32), lens)), \
            jnp.ones((2, rows, 2, 32))
    assert list(_pallas_names(jax.make_jaxpr(fn)(q).jaxpr)) == [kernel]
    assert kernel in jax.jit(fn).lower(q).as_text(debug_info=True)


def test_annotate_runs():
    import jax.numpy as jnp
    with annotate("test-region"):
        (jnp.ones((8, 8)) * 2).block_until_ready()


@pytest.mark.slow  # heavyweight e2e; tier-1 runtime headroom (see ROADMAP)
def test_profiler_trace_capture(tmp_path):
    import os
    import jax.numpy as jnp
    from tpulab.utils.tracing import trace
    log_dir = str(tmp_path / "trace")
    with trace(log_dir):
        (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()
    # a plugins/profile capture directory must exist with content
    found = []
    for root, _dirs, files in os.walk(log_dir):
        found.extend(files)
    assert found, "profiler produced no trace files"


def test_chrome_trace_records_serving_lifecycle(tmp_path):
    """ChromeTraceRecorder through the serving path: per-request
    batch_wait/pipeline/respond spans land in a loadable trace file."""
    import json

    import numpy as np

    import tpulab
    from tpulab.models.mnist import make_mnist
    from tpulab.rpc.infer_service import (RemoteInferenceManager,
                                          build_infer_service)
    from tpulab.utils.tracing import ChromeTraceRecorder

    rec = ChromeTraceRecorder(max_events=1000)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1, max_buffers=4)
    mgr.register_model("mnist", make_mnist(max_batch_size=4))
    mgr.update_resources()
    server = build_infer_service(mgr, "0.0.0.0:0", batching=True,
                                 batch_window_s=0.002, trace=rec)
    server.async_start()
    server.wait_until_running()
    remote = RemoteInferenceManager(f"localhost:{server.bound_port}")
    try:
        runner = remote.infer_runner("mnist")
        x = np.zeros((2, 28, 28, 1), np.float32)
        for _ in range(4):
            runner.infer(Input3=x).result(timeout=60)
        assert len(rec) >= 12  # 3 spans per request
        path = rec.save(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        events = doc["traceEvents"]
        names = {e["name"] for e in events}
        assert {"batch_wait", "pipeline", "respond"} <= names
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
            assert e["args"]["model"] == "mnist"
        pipelines = [e for e in events if e["name"] == "pipeline"]
        assert all("compute_ms" in e["args"] for e in pipelines)
        # per worker row, each pipeline span starts at (or after) the end
        # of the batch_wait span preceding it — the lifecycle ordering
        by_tid = {}
        for e in sorted(events, key=lambda e: e["ts"]):
            by_tid.setdefault(e["tid"], []).append(e)
        for row in by_tid.values():
            for prev, cur in zip(row, row[1:]):
                if prev["name"] == "batch_wait" and cur["name"] == "pipeline":
                    assert cur["ts"] >= prev["ts"] + prev["dur"] - 1e-3
    finally:
        remote.close()
        server.shutdown()
        mgr.shutdown()


def test_chrome_trace_ring_bound():
    """The event ring stays bounded (long-running servers must not grow)."""
    from tpulab.utils.tracing import ChromeTraceRecorder
    rec = ChromeTraceRecorder(max_events=10)
    import time as _t
    t = _t.perf_counter()
    for i in range(50):
        rec.add_span("s", t, 0.001, tid=1, i=i)
    assert len(rec) == 10


# ------------------------------------------------ distributed tracing ----
def test_trace_context_mint_and_metadata_roundtrip():
    from tpulab.utils.tracing import TRACE_METADATA_KEY, TraceContext
    tc = TraceContext()
    assert len(tc.trace_id) == 16 and tc.trace_id != TraceContext().trace_id
    md = tc.metadata()
    assert dict(md)[TRACE_METADATA_KEY] == tc.trace_id
    assert TraceContext.from_metadata(md).trace_id == tc.trace_id
    assert TraceContext.from_metadata(()) is None
    # server-side recovery: request field first, metadata fallback
    from tpulab.rpc.protos import inference_pb2 as pb
    req = pb.GenerateRequest(trace_id=tc.trace_id)
    assert TraceContext.of_request(req).trace_id == tc.trace_id

    class Ctx:
        def invocation_metadata(self):
            return md
    assert TraceContext.of_request(pb.GenerateRequest(),
                                   Ctx()).trace_id == tc.trace_id
    assert TraceContext.of_request(pb.GenerateRequest()) is None


def test_merge_chrome_traces_rebases_clocks(tmp_path):
    """Per-process traces merge onto ONE wall-clock axis: each file's
    epoch anchor shifts its events, so a span recorded 1 s later in
    another process lands 1 s later in the merged timeline."""
    import json
    import time as _t
    from tpulab.utils.tracing import ChromeTraceRecorder, merge_chrome_traces
    r1 = ChromeTraceRecorder(process_name="client")
    r2 = ChromeTraceRecorder(process_name="server")
    t = _t.perf_counter()
    r1.add_span("a", t, 0.001, trace_id="rid1")
    r2.add_span("b", t, 0.001, trace_id="rid1")
    # simulate a process whose recorder was born 1 s earlier on the wall
    # clock: its events must shift +1 s relative to the other's
    r2._epoch0 = r1._epoch0 + 1.0
    p1 = r1.save(str(tmp_path / "c.json"))
    p2 = r2.save(str(tmp_path / "s.json"))
    doc = json.load(open(merge_chrome_traces(
        str(tmp_path / "m.json"), p1, p2)))
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(spans) == {"a", "b"}
    assert spans["b"]["ts"] - spans["a"]["ts"] == __import__(
        "pytest").approx(1e6, rel=0.01)
    # process_name metadata events survive the merge (perfetto labels)
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"client", "server"}


def test_batcher_records_spans_and_latency_histograms():
    """ContinuousBatcher telemetry at the source: queue/prefill/decode
    spans tagged with the request's trace id, and TTFT/ITL/queue-wait/e2e
    histograms observed per completed request (not polled)."""
    import jax.numpy as jnp
    from prometheus_client import CollectorRegistry

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    from tpulab.utils.metrics import GenerationMetrics
    from tpulab.utils.tracing import ChromeTraceRecorder

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    rec = ChromeTraceRecorder(max_events=1000)
    gm = GenerationMetrics(registry=CollectorRegistry())
    cb = ContinuousBatcher(params, n_heads=2, n_layers=2, lanes=2,
                           max_len=64, page_size=8,
                           compute_dtype=jnp.float32, trace=rec, metrics=gm)
    try:
        steps = 12
        futs = [cb.submit(np.arange(4, dtype=np.int32), steps,
                          trace_id=f"rid{i}") for i in range(3)]
        for f in futs:
            assert len(f.result(timeout=120)) == steps
    finally:
        cb.shutdown()
    with rec._lock:
        events = list(rec._events)
    by_rid = {}
    for e in events:
        rid = e.get("args", {}).get("trace_id")
        if rid:
            by_rid.setdefault(rid, set()).add(e["name"])
    assert set(by_rid) == {"rid0", "rid1", "rid2"}
    for names in by_rid.values():
        assert {"queue_wait", "prefill", "decode"} <= names
    s = gm.registry.get_sample_value
    assert s("tpulab_llm_ttft_seconds_count") == 3
    assert s("tpulab_llm_queue_wait_seconds_count") == 3
    assert s("tpulab_llm_e2e_seconds_count") == 3
    # every token after the first is an ITL sample
    assert s("tpulab_llm_inter_token_seconds_count") == 3 * (steps - 1)
    q = gm.ttft_quantiles()
    assert q["p50"] > 0 and q["p99"] >= q["p50"]
    assert gm.itl_quantiles()["p99"] > 0


def test_metrics_aggregated_endpoint():
    """One /metrics port exports InferenceMetrics + ReplicaSetMetrics +
    GenerationMetrics + ChaosMetrics through the aggregating collector:
    breaker-state, deadline-outcome, chaos-injection and TTFT/ITL
    histogram samples all come back from a single scrape."""
    import urllib.request

    from prometheus_client import CollectorRegistry

    from tests.conftest import free_port
    from tpulab import chaos
    from tpulab.utils.metrics import (ChaosMetrics, GenerationMetrics,
                                      InferenceMetrics, ReplicaSetMetrics,
                                      start_metrics_server)

    im = InferenceMetrics(registry=CollectorRegistry())
    rm = ReplicaSetMetrics(registry=CollectorRegistry())
    gm = GenerationMetrics(registry=CollectorRegistry())
    cm = ChaosMetrics(registry=CollectorRegistry())
    im.observe_request(0.02, 0.01)
    rm.note_breaker_transition("r0:1", "open")
    rm.note_attempt("UNAVAILABLE")
    rm.observe_deadline(True, slack_s=0.2)
    gm.observe_ttft(0.05)
    gm.observe_itl(0.003)
    cm.install()
    try:
        with chaos.inject("engine.step=error+1"):
            import pytest
            with pytest.raises(chaos.ChaosError):
                chaos.trip("engine.step")
    finally:
        cm.uninstall()
    port = free_port()
    start_metrics_server([im, rm, gm, cm], port=port)
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    for needle in (
            'tpulab_request_total 1.0',
            'tpulab_replica_breaker_state{replica="r0:1",state="open"} 1.0',
            'tpulab_replica_breaker_transitions_total{replica="r0:1",'
            'to="open"} 1.0',
            'tpulab_replica_attempts_total{code="UNAVAILABLE"} 1.0',
            'tpulab_deadline_outcomes_total{outcome="met"} 1.0',
            'tpulab_deadline_slack_seconds_count 1.0',
            'tpulab_chaos_injections_total{action="error",'
            'point="engine.step"} 1.0',
            'tpulab_llm_ttft_seconds_count 1.0',
            'tpulab_llm_inter_token_seconds_count 1.0',
    ):
        assert needle in body, f"{needle!r} missing from /metrics"


def test_two_process_merged_trace(tmp_path):
    """Acceptance: a client ReplicaSet in THIS process driving an LM
    server in ANOTHER process yields one merged Chrome trace where the
    client's attempt span and the server's queue/prefill/decode spans
    share one trace id (and two distinct pids)."""
    import json
    import os
    import subprocess
    import sys as _sys
    import time

    from tpulab.rpc.replica import GenerationReplicaSet
    from tpulab.utils.tracing import ChromeTraceRecorder, merge_chrome_traces

    repo = __file__.rsplit("/tests/", 1)[0]
    env = dict(os.environ, PYTHONPATH=repo)
    server_trace = str(tmp_path / "server_trace.json")
    proc = subprocess.Popen(
        [_sys.executable, f"{repo}/tests/helpers_lm_server.py",
         "--delay-ms", "5", "--trace-path", server_trace],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    grs = None
    try:
        import select
        deadline = time.monotonic() + 120
        port = None
        while time.monotonic() < deadline:
            ready, _, _ = select.select([proc.stdout], [], [], 1.0)
            if not ready:
                if proc.poll() is not None:
                    break
                continue
            line = proc.stdout.readline()
            if line == "":
                break
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
        assert port is not None, proc.stderr.read()[-1500:]

        client_trace = ChromeTraceRecorder(process_name="client")
        grs = GenerationReplicaSet([f"127.0.0.1:{port}"], "lm",
                                   trace=client_trace)
        toks = list(grs.generate(np.arange(5, dtype=np.int32), 10))
        assert len(toks) == 10
        # the server autosaves every 100 ms and spans land as the request
        # progresses (queue_wait first, respond last): wait until the
        # WHOLE lifecycle is on disk, not just the first span
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if os.path.exists(server_trace):
                try:
                    got = {e["name"] for e in
                           json.load(open(server_trace))["traceEvents"]}
                    if {"queue_wait", "prefill", "decode",
                            "respond"} <= got:
                        break
                except ValueError:
                    pass  # autosave is atomic, but be lenient anyway
            time.sleep(0.1)
        client_path = client_trace.save(str(tmp_path / "client_trace.json"))
        merged = merge_chrome_traces(str(tmp_path / "merged.json"),
                                     client_path, server_trace)
        doc = json.load(open(merged))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_rid = {}
        for e in spans:
            rid = e.get("args", {}).get("trace_id")
            if rid:
                by_rid.setdefault(rid, []).append(e)
        # ONE request id carries both the client attempt span and the
        # server's queue/prefill/decode spans, across two pids
        rid, evs = next(iter(by_rid.items()))
        names = {e["name"] for e in evs}
        assert "attempt" in names, names
        assert {"queue_wait", "prefill", "decode"} <= names, names
        assert len({e["pid"] for e in evs}) == 2
        att = next(e for e in evs if e["name"] == "attempt")
        assert att["args"]["replica"] == f"127.0.0.1:{port}"
        assert att["args"]["attempt"] == 0
    finally:
        if grs is not None:
            grs.close()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)


def test_chrome_trace_ring_counts_drops(tmp_path, caplog):
    """The bounded ring must not discard its head SILENTLY: overflowing
    events are counted, the count rides save()'s otherData, and the
    first drop warns once (only once)."""
    import json
    import logging

    from tpulab.utils.tracing import ChromeTraceRecorder
    rec = ChromeTraceRecorder(max_events=4)
    with caplog.at_level(logging.WARNING, logger="tpulab.tracing"):
        for i in range(10):
            rec.add_span(f"s{i}", 0.0, 0.001)
    assert len(rec) == 4
    assert rec.dropped_events == 6
    warnings = [r for r in caplog.records
                if "dropped" in r.getMessage()]
    assert len(warnings) == 1  # warn ONCE, not per event
    path = str(tmp_path / "ring.json")
    rec.save(path)
    doc = json.load(open(path))
    assert doc["otherData"]["dropped_events"] == 6
    # the survivors are the most recent window
    assert [e["name"] for e in doc["traceEvents"]] == \
        ["s6", "s7", "s8", "s9"]


def test_metrics_inventory_documented_and_disjoint():
    """Drift guard: every collector class in utils/metrics.py exports
    only families the docs/OBSERVABILITY.md inventory tables name
    (counters documented with their exported `_total` suffix), and no
    family name is owned by two collectors — the one-scrape-endpoint
    contract (MultiRegistryCollector) depends on it."""
    from prometheus_client import CollectorRegistry

    import tpulab.utils.metrics as M

    doc = open(f"{REPO}/docs/OBSERVABILITY.md").read()
    collectors = (M.InferenceMetrics, M.ReplicaSetMetrics,
                  M.GenerationMetrics, M.AdmissionMetrics,
                  M.KVTierMetrics, M.ModelStoreMetrics, M.HBMMetrics,
                  M.ChaosMetrics, M.FleetMetrics, M.BatchMetrics,
                  M.SLOMetrics, M.FederationMetrics, M.KVFabricMetrics)
    families = {}
    for cls in collectors:
        m = cls(registry=CollectorRegistry())
        names = set()
        for fam in m.registry.collect():
            # a Counter family exports `name_total` samples; the docs
            # (and PromQL users) see that name
            names.add(fam.name + ("_total" if fam.type == "counter"
                                  else ""))
        assert names, f"{cls.__name__} exported no families"
        families[cls.__name__] = names
    for cls_name, names in families.items():
        for n in sorted(names):
            assert n in doc, (
                f"{cls_name} family {n!r} is not in the "
                "docs/OBSERVABILITY.md metric inventory — new metrics "
                "must be documented (and renames must update the docs)")
    owners = sorted(families)
    for i, a in enumerate(owners):
        for b in owners[i + 1:]:
            shared = families[a] & families[b]
            assert not shared, (
                f"{a} and {b} both export {sorted(shared)} — collector "
                "name-prefixes must stay pairwise disjoint")


def test_chaos_trip_points_documented():
    """Companion drift guard: every chaos trip point armed anywhere in
    tpulab/ has a row in the docs/ROBUSTNESS.md injection-point table
    (``| `point` |``) — a new trip point lands WITH its documented
    blast radius, and a renamed one updates the docs."""
    import os
    import re

    points = set()
    for dirpath, _dirs, files in os.walk(f"{REPO}/tpulab"):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(dirpath, fn),
                       encoding="utf-8").read()
            points |= set(re.findall(r'chaos\.trip\(\s*"([a-z_.]+)"',
                                     src))
    assert len(points) >= 17, f"trip-point scan broke: {sorted(points)}"
    doc = open(f"{REPO}/docs/ROBUSTNESS.md").read()
    for point in sorted(points):
        assert f"| `{point}`" in doc, (
            f"chaos trip point {point!r} has no docs/ROBUSTNESS.md "
            "injection-point table row")

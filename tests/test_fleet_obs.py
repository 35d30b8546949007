"""Fleet observability plane (docs/OBSERVABILITY.md "Fleet
observability"): control-plane event journal, FleetObserver telemetry
federation, per-tenant SLO burn rates.

The contracts test-enforced here:

- the journal's durability model: append-only JSONL with a per-node
  monotonic sequence, torn-trailing-write-tolerant replay, and a
  reopened journal resuming its lineage's sequence (a crash-restart
  never reads as loss);
- every control-plane decision lands WITH its evidence: deaths carry
  exit-code vs probe-streak, election transitions carry the fencing
  token, autoscaler actions carry the wait-EWMA/overload/SLO-burn
  signals they evaluated;
- the takeover acceptance: SIGKILL a real leader PROCESS journaling to
  its own file; the successor's journal replays the full takeover with
  strictly increasing fencing tokens and zero sequence gaps;
- the federation acceptance: fleetz agrees with each replica's own
  Status/Debug view (lanes / inflight / residency), and the merged
  Chrome trace spans two REAL processes on one timeline (the replica's
  evidence-on-exit dump + the observer-side client trace);
- SLO burn isolation: an error burst on one tenant moves only that
  tenant's fast-window burn; the autoscaler consumes the burn signal
  only behind the default-off opt-in flag;
- retired replicas' per-replica metric label children stop exporting
  (the stale-child regression), at both replica-set and federation
  scope.
"""

import json
import os
import select
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import tpulab
from helpers_engine import wait_until
from tpulab.fleet import (FileLeaseBackend, FleetAutoscaler, FleetController,
                          FleetObserver, FleetSupervisor, LeaderElector,
                          ReplicaProvider, SubprocessReplicaProvider)
from tpulab.models.mnist import make_mnist
from tpulab.obs import (EventJournal, FlightRecorder, SLOTracker,
                        replay_journal, sequence_gaps)

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ fakes ------
# (the test_fleet_process shapes, kept local so each module stands alone)
class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class FakeSet:
    """The _BaseReplicaSet membership surface the control plane (and
    the observer) drives."""

    def __init__(self, addrs):
        self.addresses = list(addrs)
        self.overloads = 0
        self._state = {a: "closed" for a in addrs}
        self.added = []
        self.retired = []

    @property
    def active_count(self):
        return len([a for a in self.addresses
                    if self._state[a] == "closed"])

    @property
    def inflight(self):
        return [0] * len(self.addresses)

    def active_addresses(self):
        return [a for a in self.addresses if self._state[a] == "closed"]

    def draining_addresses(self):
        return [a for a, s in self._state.items() if s == "draining"]

    def breaker_states(self):
        return dict(self._state)

    def load_hints(self):
        return {a: 0 for a in self.addresses}

    def add_replica(self, addr):
        self.addresses.append(addr)
        self._state[addr] = "closed"
        self.added.append(addr)
        return len(self.addresses) - 1

    def set_draining(self, addr, draining=True):
        self._state[addr] = "draining" if draining else "closed"

    def retire_replica(self, addr):
        self._state[addr] = "retired"
        self.retired.append(addr)

    def health(self, timeout=5.0):
        return {a: {"live": True, "ready": True}
                for a, s in self._state.items() if s != "retired"}


class FakeProvider(ReplicaProvider):
    def __init__(self):
        self.n = 0
        self.alive = {}

    def spawn(self):
        self.n += 1
        addr = f"10.0.1.{self.n}:50051"
        self.alive[addr] = True
        return addr

    def drain(self, address, timeout_s=30.0):
        return True

    def retire(self, address):
        self.alive.pop(address, None)

    def is_alive(self, address):
        return self.alive.get(address)


# ----------------------------------------------------------- journal -----
def test_journal_records_and_replays_in_order(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    with EventJournal(path, node="n0", clock=FakeClock(5.0)) as j:
        j.record("scale_up", address="a:1", wait_ewma_s=0.7)
        j.record("drain_start", address="a:1")
        assert j.events_written == 2 and j.append_errors == 0
        evs = j.events()
        assert [e["kind"] for e in evs] == ["scale_up", "drain_start"]
        assert [e["seq"] for e in evs] == [1, 2]
        assert all(e["node"] == "n0" and e["wall_time"] == 5.0
                   for e in evs)
        assert evs[0]["wait_ewma_s"] == 0.7
        assert j.events(kind="drain_start") == [evs[1]]
        assert j.append_quantiles()["p99"] > 0.0
    assert sequence_gaps(replay_journal(path)) == []


def test_journal_replay_tolerates_torn_trailing_write(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    j = EventJournal(path, node="n0")
    j.record("elect_acquire", token=1)
    j.record("elect_resign", token=1)
    j.close()
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"seq": 3, "kind": "elect_acq')  # SIGKILL mid-write
    evs = replay_journal(path)
    assert [e["kind"] for e in evs] == ["elect_acquire", "elect_resign"]
    assert sequence_gaps(evs) == []  # the torn line is not a gap
    assert replay_journal(str(tmp_path / "never_armed.jsonl")) == []


def test_journal_reopen_resumes_node_sequence(tmp_path):
    """A control node's crash-restart continues its lineage's sequence:
    seq resetting to 1 would replay as overwrite, a jump as loss."""
    path = str(tmp_path / "journal.jsonl")
    j = EventJournal(path, node="ctl")
    for _ in range(3):
        j.record("membership_publish", token=1)
    j.close()
    j2 = EventJournal(path, node="ctl")  # "the restarted process"
    ev = j2.record("elect_acquire", token=2)
    j2.close()
    assert ev["seq"] == 4
    assert sequence_gaps(replay_journal(path)) == []
    # an unrelated node starts its own sequence at 1, gap-free
    j3 = EventJournal(path, node="other")
    assert j3.record("elect_acquire", token=3)["seq"] == 1
    j3.close()
    assert sequence_gaps(replay_journal(path)) == []


def test_sequence_gaps_flags_missing_events():
    evs = [{"node": "a", "seq": 1}, {"node": "b", "seq": 1},
           {"node": "a", "seq": 2}, {"node": "a", "seq": 4}]
    assert sequence_gaps(evs) == [("a", 4, 3)]


def test_supervisor_journals_death_evidence_and_respawn(tmp_path):
    clk = FakeClock(0.0)
    rs = FakeSet(["10.0.0.9:50051"])
    prov = FakeProvider()
    prov.alive = {"10.0.0.9:50051": True}
    j = EventJournal(str(tmp_path / "j.jsonl"), node="sup")
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=1.0, clock=clk,
                          journal=j)
    sup.probe()
    assert j.events() == []            # healthy tick: nothing to say

    prov.alive["10.0.0.9:50051"] = False   # the process exited
    sup.probe()
    (death,) = j.events(kind="replica_death")
    assert death["address"] == "10.0.0.9:50051"
    assert death["evidence"] == "exit"     # provider saw the exit
    assert death["respawn_backoff_s"] == 1.0
    assert death["recent_deaths"] == 1

    clk.t = 1.5
    sup.probe()
    (resp,) = j.events(kind="replica_respawn")
    assert resp["lineage"] == "10.0.0.9:50051"
    assert resp["address"] in rs.added and resp["respawns"] == 1
    j.close()


def test_supervisor_journals_crash_loop_quarantine(tmp_path):
    clk = FakeClock(0.0)
    rs = FakeSet(["10.0.0.9:50051"])
    prov = FakeProvider()
    prov.alive = {"10.0.0.9:50051": False}
    j = EventJournal(str(tmp_path / "j.jsonl"), node="sup")
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=0.0,
                          crash_loop_deaths=3, crash_loop_window_s=100.0,
                          clock=clk, journal=j)
    for _ in range(5):                   # every respawn dies instantly
        for addr in list(prov.alive):
            prov.alive[addr] = False
        sup.probe()
    deaths = j.events(kind="replica_death")
    assert len(deaths) == 3
    (quar,) = j.events(kind="replica_quarantine")
    assert quar["recent_deaths"] == 3 and quar["window_s"] == 100.0
    assert sup.unquarantine(quar["address"]) is True
    (unq,) = j.events(kind="replica_unquarantine")
    assert unq["address"] == quar["address"]
    assert sequence_gaps(j.events()) == []
    j.close()


def test_election_journals_transitions_with_tokens(tmp_path):
    be = FileLeaseBackend(str(tmp_path / "lease"))
    ja = EventJournal(str(tmp_path / "a.jsonl"), node="a")
    jb = EventJournal(str(tmp_path / "b.jsonl"), node="b")
    a = LeaderElector(be, node_id="a", ttl_s=60.0, journal=ja,
                      journal_renew_every=1)
    b = LeaderElector(be, node_id="b", ttl_s=60.0, journal=jb)
    assert a.tick() is True
    (acq,) = ja.events(kind="elect_acquire")
    assert acq["token"] == 1 and acq["node_id"] == "a"
    assert a.tick() is True              # renew journals when opted in
    (ren,) = ja.events(kind="elect_renew")
    assert ren["token"] == 1
    assert b.tick() is False and jb.events() == []
    a.resign()
    (res,) = ja.events(kind="elect_resign")
    assert res["token"] == 1
    assert b.tick() is True
    (acq_b,) = jb.events(kind="elect_acquire")
    assert acq_b["token"] == 2 > acq["token"]  # fenced past a's reign
    ja.close()
    jb.close()


def test_autoscaler_journals_decisions_with_evidence(tmp_path):
    rs = FakeSet(["a:1"])
    prov = FakeProvider()
    wait = [10.0]
    j = EventJournal(str(tmp_path / "j.jsonl"), node="asc")
    asc = FleetAutoscaler(rs, prov, wait_signal=lambda: wait[0],
                          hold=1, max_replicas=2, drain_timeout_s=5.0,
                          journal=j)
    assert asc.evaluate() == "scale_up"
    (up,) = j.events(kind="scale_up")
    assert up["wait_ewma_s"] == 10.0 and up["overload_delta"] == 0
    assert up["address"] in rs.added and up["active"] == 2
    assert "slo_burn" not in up          # trigger not armed: not evidence
    wait[0] = 0.0
    assert asc.evaluate() == "drain_started"
    (dr,) = j.events(kind="drain_start")
    assert dr["wait_ewma_s"] == 0.0
    wait_until(lambda: asc.evaluate() == "scale_down",
               "the drain completed", timeout_s=10)
    (down,) = j.events(kind="scale_down")
    assert down["drain_ok"] is True and down["active"] == 1
    assert sequence_gaps(j.events()) == []
    j.close()


# ------------------------------------------- SIGKILL takeover ------------
# the child is a REAL leader process journaling to its own file;
# election.py and journal.py are deliberately stdlib-only, so it loads
# them by path without paying for the serving stack
_CHILD_LEADER = """
import importlib.util, sys, time

def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

election = load("election_child", sys.argv[1])
journal = load("journal_child", sys.argv[2])
j = journal.EventJournal(sys.argv[4], node="child-leader")
el = election.LeaderElector(election.FileLeaseBackend(sys.argv[3]),
                            node_id="child-leader",
                            ttl_s=float(sys.argv[5]), journal=j)
print("LEADER" if el.tick() else "FOLLOWER", flush=True)
while True:
    time.sleep(0.05)
    el.tick()
"""


def test_killed_leader_takeover_reconstructs_from_journals(tmp_path):
    """The journal acceptance: SIGKILL the leader PROCESS while a
    successor runs a full control plane (supervisor + autoscaler) with
    its own journal.  Replaying both journals reconstructs the takeover
    — the child's acquire, the successor's acquire with a STRICTLY
    greater fencing token, the death classification with evidence, the
    respawn and the autoscaler's evidence-stamped action — with zero
    per-node sequence gaps."""
    ttl = 0.75
    lease_dir = str(tmp_path / "lease")
    child_journal = str(tmp_path / "child.jsonl")
    parent_journal = str(tmp_path / "parent.jsonl")
    script = tmp_path / "child_leader.py"
    script.write_text(_CHILD_LEADER)
    proc = subprocess.Popen(
        [sys.executable, str(script),
         os.path.join(REPO, "tpulab", "fleet", "election.py"),
         os.path.join(REPO, "tpulab", "obs", "journal.py"),
         lease_dir, child_journal, str(ttl)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    j = EventJournal(parent_journal, node="parent")
    try:
        role = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and role is None:
            ready, _, _ = select.select([proc.stdout], [], [], 0.5)
            if ready:
                role = proc.stdout.readline().strip()
            elif proc.poll() is not None:
                break
        assert role == "LEADER", (role, proc.stderr.read()[-1500:])

        rs = FakeSet(["10.0.0.9:50051"])
        prov = FakeProvider()
        prov.alive = {"10.0.0.9:50051": False}   # died with its leader
        ctl = FleetController(
            rs,
            LeaderElector(FileLeaseBackend(lease_dir), node_id="parent",
                          ttl_s=ttl, journal=j),
            supervisor=FleetSupervisor(rs, prov, respawn_backoff_s=0.0,
                                       journal=j),
            autoscaler=FleetAutoscaler(rs, prov,
                                       wait_signal=lambda: 10.0,
                                       hold=1, max_replicas=4,
                                       journal=j),
            journal=j)
        assert ctl.tick()["leader"] is False     # the child renews

        proc.kill()                              # no release, no goodbye
        proc.wait(timeout=10)
        wait_until(lambda: ctl.tick()["leader"], "the takeover",
                   timeout_s=5, poll_s=0.02)
        ctl.tick()                               # heal + publish again

        child_evs = replay_journal(child_journal)
        parent_evs = replay_journal(parent_journal)
        assert sequence_gaps(child_evs) == []
        assert sequence_gaps(parent_evs) == []
        assert sequence_gaps(child_evs + parent_evs) == []

        (child_acq,) = [e for e in child_evs
                        if e["kind"] == "elect_acquire"]
        (parent_acq,) = [e for e in parent_evs
                         if e["kind"] == "elect_acquire"]
        assert parent_acq["token"] > child_acq["token"]
        # the acquire timeline is strictly token-increasing
        acquires = sorted(
            [e for e in child_evs + parent_evs
             if e["kind"] == "elect_acquire"],
            key=lambda e: e["wall_time"])
        tokens = [e["token"] for e in acquires]
        assert tokens == sorted(set(tokens))

        kinds = [e["kind"] for e in parent_evs]
        assert "membership_publish" in kinds     # the successor's view
        pub = next(e for e in parent_evs
                   if e["kind"] == "membership_publish")
        assert pub["token"] == parent_acq["token"]
        death = next(e for e in parent_evs
                     if e["kind"] == "replica_death")
        assert death["evidence"] == "exit"       # positive evidence
        assert "replica_respawn" in kinds        # ...and the healing
        up = next(e for e in parent_evs if e["kind"] == "scale_up")
        assert up["wait_ewma_s"] == 10.0         # evidence-stamped
    finally:
        j.close()
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


# ---------------------------------------------------- federation ---------
def _wait_port(proc, timeout=120):
    deadline = time.monotonic() + timeout
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
            return int(line.split()[1])
    raise AssertionError(proc.stderr.read()[-1500:])


def test_fleetz_federates_real_process_and_merges_evidence(tmp_path):
    """The federation acceptance: one replica is a REAL subprocess
    (evidence paths delivered via env — the provider's per-spawn
    extra_env), one is in-process; fleetz must agree with each
    replica's own Status/Debug view, the ``_fed_*`` gauges must carry
    the per-replica children, and the merged Chrome trace must span
    both real processes on one timeline."""
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import RemoteInferenceManager
    from tpulab.rpc.replica import GenerationReplicaSet
    from tpulab.utils.metrics import HAVE_PROMETHEUS, FederationMetrics
    from tpulab.utils.tracing import ChromeTraceRecorder

    sub_trace = str(tmp_path / "sub_trace.json")
    sub_flight = str(tmp_path / "sub_flight.jsonl")
    prov = SubprocessReplicaProvider(replica_args=("--delay-ms", "5"))
    sub_addr = prov.spawn(extra_env={"TPULAB_TRACE_PATH": sub_trace,
                                     "TPULAB_FLIGHT_PATH": sub_flight})

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    cb = ContinuousBatcher(params, n_heads=2, n_layers=2, lanes=2,
                           max_len=64, page_size=8,
                           compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb},
              flight=FlightRecorder())
    in_addr = f"127.0.0.1:{mgr.server.bound_port}"

    client_trace = ChromeTraceRecorder(process_name="observer")
    rs = GenerationReplicaSet([sub_addr, in_addr], "lm")
    fed = FederationMetrics() if HAVE_PROMETHEUS else None
    obs = FleetObserver(rs, metrics=fed)
    # traffic pinned per replica (single-member sets) so BOTH replicas
    # provably serve — the subprocess one through the traced client
    rs_sub = GenerationReplicaSet([sub_addr], "lm", trace=client_trace)
    rs_in = GenerationReplicaSet([in_addr], "lm")
    try:
        for one in (rs_sub, rs_in):
            for _ in range(2):
                assert len(list(one.generate(
                    np.arange(5, dtype=np.int32), 6, timeout=120))) == 6
        snap = obs.fleetz()
        assert set(snap["replicas"]) == {sub_addr, in_addr}
        assert snap["scrape_s"] > 0 and obs.scrapes == 1
        for addr in (sub_addr, in_addr):
            doc = snap["replicas"][addr]
            assert doc["up"] is True, doc
            cli = RemoteInferenceManager(addr)
            try:
                st = cli.server_status()
                dbg = cli.debugz()
            finally:
                cli.close()
            # fleetz vs the replica's own self-report (idle: stable)
            assert doc["inflight"] == int(st.inflight_requests) == 0
            assert doc["queued"] == int(st.queued_requests)
            assert doc["free_kv_pages"] == int(st.free_kv_pages)
            assert doc["resident_models"] == \
                [str(m) for m in st.resident_models]
            assert doc["draining"] is False
            assert doc["lanes"]["lm"] == len(dbg["engines"]["lm"]["lanes"])
            assert isinstance(doc["flight_exemplars"], list)
        if fed is not None:
            fams = {f.name: f for f in fed.registry.collect()}
            ups = {s.labels["replica"]: s.value
                   for s in fams["tpulab_fed_replica_up"].samples}
            assert ups == {sub_addr: 1.0, in_addr: 1.0}
            assert [s.value for s in fams["tpulab_fed_replicas"].samples] \
                == [2.0]

        # evidence collection across the REAL process boundary: wait for
        # the subprocess autosaves, then merge onto one timeline
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if os.path.exists(sub_trace) and os.path.exists(sub_flight):
                try:
                    names = {e["name"] for e in
                             json.load(open(sub_trace))["traceEvents"]}
                    if {"prefill", "decode"} <= names:
                        break
                except ValueError:
                    pass
            time.sleep(0.1)
        client_path = client_trace.save(str(tmp_path / "client.json"))
        merged = FleetObserver.merge_traces(
            str(tmp_path / "merged.json"), client_path, sub_trace)
        doc = json.load(open(merged))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert len({e["pid"] for e in spans}) >= 2  # two REAL processes
        names = {e["name"] for e in spans}
        assert "attempt" in names and "decode" in names

        flights = FleetObserver.collect_flight(
            sub_flight, str(tmp_path / "missing.jsonl"))
        assert flights and all(f["source"] == sub_flight for f in flights)
        assert [f["wall_time"] for f in flights] == \
            sorted(f["wall_time"] for f in flights)
    finally:
        obs.close()
        for one in (rs, rs_sub, rs_in):
            one.close()
        prov.retire(sub_addr)
        for closer in (mgr.shutdown, cb.shutdown):
            try:
                closer()
            except Exception:
                pass


def test_fleetz_reports_dead_replica_as_data(tmp_path):
    rs = FakeSet(["127.0.0.1:1"])        # nothing listens there
    obs = FleetObserver(rs, timeout_s=2.0)
    try:
        snap = obs.fleetz()
        doc = snap["replicas"]["127.0.0.1:1"]
        assert doc["up"] is False and "error" in doc
        assert snap["breaker_states"] == {"127.0.0.1:1": "closed"}
    finally:
        obs.close()


# ------------------------------------------------------------- SLO -------
def _ev(tenant, outcome="SUCCESS", e2e=0.01, req_class=None):
    ev = {"tenant": tenant, "outcome": outcome, "e2e_s": e2e}
    if req_class is not None:
        ev["request_class"] = req_class
    return ev


def test_slo_error_burst_moves_only_that_tenants_fast_burn():
    clk = FakeClock(0.0)
    slo = SLOTracker(availability_objective=0.9, latency_objective_s=1.0,
                     latency_target=0.9, fast_window_s=60.0,
                     slow_window_s=600.0, clock=clk)
    for _ in range(10):
        slo.observe(_ev("a"))
        slo.observe(_ev("b"))
    for _ in range(5):                   # the burst: tenant a only
        slo.observe(_ev("a", outcome="INTERNAL"))
    rates = slo.burn_rates()
    a_fast = rates["a"]["online"]["fast"]
    b_fast = rates["b"]["online"]["fast"]
    assert a_fast["errors"] == 5 and a_fast["requests"] == 15
    # (5/15) error rate over a 0.1 budget = burn 3.33
    assert a_fast["availability_burn"] == pytest.approx(10 / 3)
    assert b_fast["errors"] == 0 and b_fast["availability_burn"] == 0.0
    assert b_fast["latency_burn"] == 0.0

    clk.t = 120.0                        # past fast, inside slow
    rates = slo.burn_rates()
    assert rates["a"]["online"]["fast"]["requests"] == 0
    assert rates["a"]["online"]["slow"]["errors"] == 5
    clk.t = 1000.0                       # past slow: pruned entirely
    assert slo.burn_rates()["a"]["online"]["slow"]["requests"] == 0


def test_slo_latency_breaches_and_neutral_cancels():
    clk = FakeClock(0.0)
    slo = SLOTracker(latency_objective_s=0.5, latency_target=0.9,
                     clock=clk)
    for _ in range(8):
        slo.observe(_ev("t", e2e=0.1))
    for _ in range(2):
        slo.observe(_ev("t", e2e=2.0))   # breach, but served
    slo.observe(_ev("t", outcome="CANCELLED", e2e=9.0))  # neutral
    fast = slo.burn_rates()["t"]["online"]["fast"]
    assert fast["requests"] == 10 and fast["breaches"] == 2
    assert fast["availability_burn"] == 0.0
    assert fast["latency_burn"] == pytest.approx((2 / 10) / 0.1)
    assert slo.observed_total == 10


def test_slo_scale_signal_excludes_batch_class():
    clk = FakeClock(0.0)
    slo = SLOTracker(availability_objective=0.99, clock=clk)
    for _ in range(4):
        slo.observe(_ev("bulk", outcome="INTERNAL", req_class="batch"))
    assert slo.burn_rates()["bulk"]["batch"]["fast"]["errors"] == 4
    assert slo.scale_signal() == 0.0     # deferrable work buys nothing
    slo.observe(_ev("web", outcome="INTERNAL"))
    assert slo.scale_signal() > 0.0


def test_flight_tap_feeds_slo_before_sampling():
    """The tap sees EVERY observed event (burn rates must be exact),
    even ones tail-sampling would drop from the exemplar ring."""
    fr = FlightRecorder(tail_capacity=4, uniform_capacity=4,
                        sample_every=1000)
    clk = FakeClock(0.0)
    slo = SLOTracker(clock=clk)
    fr.add_tap(slo.observe)
    fr.add_tap(lambda ev: 1 / 0)         # a broken consumer is ignored
    for i in range(32):
        fr.observe({"request_id": f"r{i}", "tenant": "t",
                    "outcome": "SUCCESS", "e2e_s": 0.01})
    assert slo.observed_total == 32
    assert slo.burn_rates()["t"]["online"]["fast"]["requests"] == 32


def test_chaos_error_burst_moves_only_that_tenants_burn():
    """The SLO acceptance, through the REAL serving path: a
    chaos-injected error burst during ONE tenant's requests moves that
    tenant's fast-window availability burn and nobody else's."""
    import jax.numpy as jnp

    from tpulab import chaos
    from tpulab.engine.paged import ContinuousBatcher
    from tpulab.models.transformer import init_transformer_params
    from tpulab.rpc.infer_service import (GenerateStreamClient,
                                          RemoteInferenceManager)

    params = init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                     n_layers=2, d_ff=64)
    cb = ContinuousBatcher(params, n_heads=2, n_layers=2, lanes=2,
                           max_len=64, page_size=8,
                           compute_dtype=jnp.float32)
    fr = FlightRecorder()
    slo = SLOTracker(availability_objective=0.9)
    fr.add_tap(slo.observe)               # burn fed off the wide events
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.serve(port=0, generation_engines={"lm": cb}, flight=fr)
    rm = RemoteInferenceManager(f"127.0.0.1:{mgr.server.bound_port}")
    gen = GenerateStreamClient(rm, "lm")
    prompt = np.arange(4, dtype=np.int32)
    try:
        for _ in range(3):                # tenant b: clean baseline
            assert len(list(gen.generate(prompt, 3, timeout=120,
                                         tenant_id="b"))) == 3
        with chaos.inject("engine.step=error+999"):
            for _ in range(2):            # the burst: tenant a only
                with pytest.raises(Exception):
                    list(gen.generate(prompt, 3, timeout=120,
                                      tenant_id="a"))
        rates = slo.burn_rates()
        a_fast = rates["a"]["online"]["fast"]
        b_fast = rates["b"]["online"]["fast"]
        assert a_fast["errors"] >= 1
        assert a_fast["availability_burn"] > 0.0
        assert b_fast["requests"] == 3 and b_fast["errors"] == 0
        assert b_fast["availability_burn"] == 0.0
    finally:
        rm.close()
        for closer in (mgr.shutdown, cb.shutdown):
            try:
                closer()
            except Exception:
                pass


def test_slo_metrics_gauges_export(tmp_path):
    pytest.importorskip("prometheus_client")
    from tpulab.utils.metrics import SLOMetrics

    clk = FakeClock(0.0)
    slo = SLOTracker(availability_objective=0.9, clock=clk,
                     metrics=SLOMetrics())
    slo.observe(_ev("a", outcome="INTERNAL"))
    slo.observe(_ev("a"))
    slo.export()
    fams = {f.name: f for f in slo._metrics.registry.collect()}
    burns = {(s.labels["tenant"], s.labels["window"]): s.value
             for s in fams["tpulab_slo_availability_burn_rate"].samples}
    assert burns[("a", "fast")] == pytest.approx(5.0)   # 0.5 / 0.1
    errs = {s.labels["tenant"]: s.value
            for s in fams["tpulab_slo_errors"].samples
            if s.name.endswith("_total")}
    assert errs == {"a": 1.0}


def test_autoscaler_slo_trigger_is_default_off(tmp_path):
    burn = [100.0]
    rs = FakeSet(["a:1"])
    prov = FakeProvider()
    # flag off (default): a screaming burn signal scales NOTHING
    asc = FleetAutoscaler(rs, prov, hold=1, max_replicas=3,
                          slo_signal=lambda: burn[0])
    assert asc.slo_scale_up is False
    for _ in range(3):
        assert asc.evaluate() == ""
    assert rs.added == []

    # opted in: the burn is a scale-up trigger with journaled evidence
    j = EventJournal(str(tmp_path / "j.jsonl"), node="asc")
    asc_on = FleetAutoscaler(rs, prov, hold=1, max_replicas=3,
                             slo_signal=lambda: burn[0],
                             slo_scale_up=True, up_slo_burn=10.0,
                             journal=j)
    assert asc_on.slo_scale_up is True
    assert asc_on.evaluate() == "scale_up"
    (up,) = j.events(kind="scale_up")
    assert up["slo_burn"] == 100.0
    burn[0] = 0.0                        # burn clears: idle again
    assert asc_on.evaluate() in ("", "drain_started")
    j.close()
    # the flag without a signal stays off (nothing to consume)
    assert FleetAutoscaler(rs, prov, slo_scale_up=True).slo_scale_up \
        is False


def test_autoscaler_slo_burn_blocks_scale_down():
    """A burning fleet is never 'idle': the down-streak must not build
    while the SLO trigger fires, even when cooldown blocks scale-up."""
    rs = FakeSet(["a:1", "b:2"])
    prov = FakeProvider()
    asc = FleetAutoscaler(rs, prov, hold=1, min_replicas=1,
                          max_replicas=3, slo_signal=lambda: 50.0,
                          slo_scale_up=True, cooldown_s=3600.0)
    asc._last_action_t = time.monotonic()   # cooling: no action at all
    for _ in range(3):
        assert asc.evaluate() == ""
    assert rs.draining_addresses() == [] and rs.retired == []


# -------------------------------- stale metric children (satellite) ------
def test_retired_replica_metric_children_stop_exporting():
    pytest.importorskip("prometheus_client")
    from tpulab.rpc.replica import GenerationReplicaSet
    from tpulab.utils.metrics import ReplicaSetMetrics

    m = ReplicaSetMetrics()
    a, b = "10.9.0.1:1", "10.9.0.2:1"
    rs = GenerationReplicaSet([a, b], "lm", metrics=m)
    try:
        # children a live fleet would have labeled
        for addr in (a, b):
            m.live.labels(replica=addr).set(1)
            m.prefix_hits.labels(replica=addr).set(3)
            m.prefix_lookups.labels(replica=addr).set(4)
            m.set_breaker_state(addr, "closed")
            m.note_breaker_transition(addr, "open")
        rs.retire_replica(a)

        labeled = set()
        for fam in m.registry.collect():
            for s in fam.samples:
                if "replica" in s.labels:
                    labeled.add((s.name, s.labels["replica"]))
        retired = {(n, r) for n, r in labeled if r == a}
        assert retired == set(), f"stale children export: {retired}"
        # the survivor's children are untouched
        assert ("tpulab_replica_live", b) in labeled
        assert ("tpulab_replica_breaker_state", b) in labeled
        assert ("tpulab_replica_prefix_hits", b) in labeled
    finally:
        rs.close()


def test_federation_metrics_prune_stale_replica_children():
    pytest.importorskip("prometheus_client")
    from tpulab.utils.metrics import FederationMetrics

    fed = FederationMetrics()
    fed.set_replica("a:1", up=True, inflight=2)
    fed.set_replica("b:2", up=True, inflight=0)
    fed.prune(keep=["b:2"])              # a:1 left the snapshot
    fed.observe_scrape(0.01, 1)
    labeled = set()
    for fam in fed.registry.collect():
        for s in fam.samples:
            if "replica" in s.labels:
                labeled.add((s.name, s.labels["replica"]))
    assert not any(r == "a:1" for _, r in labeled), labeled
    assert ("tpulab_fed_replica_up", "b:2") in labeled


# ------------------------- debugz fleet section across transition --------
def test_debugz_fleet_membership_agrees_across_leader_transition(
        tmp_path):
    """Satellite: leader and follower controllers served over the Debug
    RPC report the SAME membership document (token + store seq +
    members) before AND after a leader transition — the fleetz/debugz
    agreement surface an operator diffs during a handoff."""
    from tpulab.rpc.infer_service import RemoteInferenceManager

    be = FileLeaseBackend(str(tmp_path / "lease"))
    rs_a, rs_b = FakeSet(["10.0.0.1:50051"]), FakeSet(["10.0.0.1:50051"])
    el_a = LeaderElector(be, node_id="router-a", ttl_s=60.0)
    el_b = LeaderElector(be, node_id="router-b", ttl_s=60.0)
    ctl_a = FleetController(rs_a, el_a)
    ctl_b = FleetController(rs_b, el_b)
    assert ctl_a.tick()["leader"] is True
    assert ctl_b.tick()["leader"] is False

    mgrs, clients = [], []
    try:
        for ctl in (ctl_a, ctl_b):
            mgr = tpulab.InferenceManager()
            mgr.register_model("mnist", make_mnist(max_batch_size=1))
            mgr.update_resources()
            mgr.serve(port=0, fleet=ctl)
            mgrs.append(mgr)
            clients.append(RemoteInferenceManager(
                f"127.0.0.1:{mgr.server.bound_port}"))

        def fleet_docs():
            return [c.debugz()["fleet"] for c in clients]

        doc_a, doc_b = fleet_docs()
        assert doc_a["election"]["is_leader"] is True
        assert doc_b["election"]["is_leader"] is False
        for key in ("token", "seq", "members"):
            assert doc_a["membership"][key] == doc_b["membership"][key]
        assert doc_a["membership"]["token"] == 1

        el_a.resign()                    # the transition
        assert ctl_b.tick()["leader"] is True
        assert ctl_a.tick()["leader"] is False

        doc_a, doc_b = fleet_docs()
        assert doc_a["election"]["is_leader"] is False
        assert doc_b["election"]["is_leader"] is True
        assert doc_b["membership"]["token"] == 2
        for key in ("token", "seq", "members"):
            assert doc_a["membership"][key] == doc_b["membership"][key]
    finally:
        for c in clients:
            c.close()
        for mgr in mgrs:
            try:
                mgr.shutdown()
            except Exception:
                pass

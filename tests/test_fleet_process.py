"""Process-boundary fleet control plane (docs/SERVING.md "Running a
real fleet"): subprocess replicas, self-healing supervision, and
leader-elected multi-router autoscaling.

The contracts test-enforced here:

- the lease protocol: acquire/renew/expire/takeover, resign hands off
  immediately, and the fencing token REJECTS a stale leader's
  membership write (StaleLeaderError) — "at most one leader ACTS";
- two concurrent FleetControllers over one lease backend run exactly
  ONE autoscaler, and a killed leader (a real SIGKILLed process) hands
  off within one TTL;
- membership snapshots converge a follower's replica set and never
  un-drain / un-retire (one-way transitions);
- FleetSupervisor: positive-evidence death detection (provider exit OR
  an unreachable-probe streak, never a single blip), respawn under
  exponential backoff, crash-loop quarantine + unquarantine, and the
  drain-vs-death distinction (a draining member is NEVER a death);
- ``fleet.probe`` chaos forgoes evidence (healing delayed, never a
  spurious death); ``fleet.spawn`` chaos degrades to retry-with-backoff
  and the final failure propagates;
- the shared provider drain conformance contract — ``timeout_s`` is a
  HARD cap, in-flight streams finish, drained state is observable —
  run against BOTH InProcessReplicaProvider and
  SubprocessReplicaProvider;
- the slow acceptance: a chaos-killed real replica process under live
  traffic (streams complete bit-exact via resume-from-delivered), the
  supervisor respawns it, and a later scale-down drains + retires a
  real process with zero dropped streams.
"""

import os
import select
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import tpulab
from helpers_engine import wait_until
from tpulab import chaos
from tpulab.fleet import (FileLeaseBackend, FleetAutoscaler, FleetController,
                          FleetSupervisor, InProcessReplicaProvider,
                          LeaderElector, ReplicaProvider, StaleLeaderError,
                          SubprocessReplicaProvider, apply_membership,
                          membership_snapshot, spawn_with_retry)
from tpulab.models.mnist import make_mnist

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ fakes ------
class FakeClock:
    """Injectable time for sleepless lease-expiry and backoff tests."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class FakeSet:
    """The _BaseReplicaSet membership surface the control plane drives
    (tombstone indices, breaker states, health), with recording."""

    def __init__(self, addrs):
        self.addresses = list(addrs)
        self.overloads = 0
        self._state = {a: "closed" for a in addrs}
        self.health_results = {}   # addr -> dict override (default alive)
        self.added = []
        self.retired = []

    @property
    def active_count(self):
        return len(self.active_addresses())

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
        return {a: dict(self.health_results.get(
                    a, {"live": True, "ready": True}))
                for a, s in self._state.items() if s != "retired"}


class FakeProvider(ReplicaProvider):
    """Liveness-observable provider: spawned addresses are numbered,
    ``alive`` is the test's direct handle on process fate."""

    def __init__(self):
        self.n = 0
        self.alive = {}            # addr -> bool; missing = None
        self.spawn_dead = False    # newborns die instantly (crash loop)
        self.spawn_fails = 0       # next N spawns raise
        self.retired = []

    def spawn(self):
        if self.spawn_fails > 0:
            self.spawn_fails -= 1
            raise RuntimeError("injected spawn failure")
        self.n += 1
        addr = f"10.0.0.{self.n}:50051"
        self.alive[addr] = not self.spawn_dead
        return addr

    def drain(self, address, timeout_s=30.0):
        return True

    def retire(self, address):
        self.alive.pop(address, None)
        self.retired.append(address)

    def is_alive(self, address):
        return self.alive.get(address)


class CountingAutoscaler:
    """Stands in for FleetAutoscaler inside controller tests: the only
    thing under test is WHO gets to call evaluate()."""

    def __init__(self):
        self.evals = 0

    def evaluate(self):
        self.evals += 1
        return ""

    def snapshot(self):
        return {"evals": self.evals}


# ------------------------------------------------- lease + fencing ------
def test_lease_acquire_renew_expiry_takeover(tmp_path):
    clk = FakeClock()
    be = FileLeaseBackend(str(tmp_path), clock=clk)
    a = LeaderElector(be, node_id="A", ttl_s=2.0)
    b = LeaderElector(be, node_id="B", ttl_s=2.0)

    assert a.tick() is True and a.is_leader and a.fencing_token == 1
    assert b.tick() is False and not b.is_leader
    clk.t += 1.5
    assert a.tick() is True            # renew inside the TTL
    clk.t += 1.5
    assert b.tick() is False           # renewed lease still valid
    clk.t += 2.5                       # past the renewed expiry
    assert b.tick() is True            # takeover on the next tick
    assert b.fencing_token == 2        # acquisition bumps the token
    assert be.holder() == ("B", 2)
    # the old leader discovers the loss on its next tick, not before
    assert a.tick() is False
    assert not a.is_leader and a.losses == 1


def test_lease_resign_hands_off_immediately(tmp_path):
    clk = FakeClock()
    be = FileLeaseBackend(str(tmp_path), clock=clk)
    a = LeaderElector(be, node_id="A", ttl_s=30.0)
    b = LeaderElector(be, node_id="B", ttl_s=30.0)
    assert a.tick() and not b.tick()
    a.resign()                         # clean shutdown: no TTL wait
    assert not a.is_leader
    assert b.tick() is True            # same fake instant
    assert b.fencing_token == 2        # release preserved the counter


def test_fencing_token_rejects_stale_publish(tmp_path):
    clk = FakeClock()
    be = FileLeaseBackend(str(tmp_path), clock=clk)
    a = LeaderElector(be, node_id="A", ttl_s=2.0)
    b = LeaderElector(be, node_id="B", ttl_s=2.0)
    assert a.tick()
    be.publish_membership({"members": ["x:1"]}, a.fencing_token)
    clk.t += 5.0                       # A pauses past its TTL (GC, stall)
    assert b.tick() and b.fencing_token == 2

    # the woken stale leader's write is REJECTED, and its renew fails
    with pytest.raises(StaleLeaderError):
        be.publish_membership({"members": []}, 1)
    assert be.renew("A", 1, 2.0) is False
    # the current leader's write lands, seq advancing
    be.publish_membership({"members": ["x:1", "y:2"]}, 2)
    snap = be.read_membership()
    assert snap["token"] == 2 and snap["seq"] == 2
    assert snap["members"] == ["x:1", "y:2"]


def test_membership_snapshot_apply_one_way():
    lead = FakeSet(["a:1", "b:2", "c:3"])
    lead.set_draining("b:2")
    lead.retire_replica("c:3")
    snap = membership_snapshot(lead)
    assert snap == {"members": ["a:1"], "draining": ["b:2"],
                    "retired": ["c:3"]}

    fol = FakeSet(["a:1", "b:2", "c:3"])
    acts = apply_membership(fol, snap)
    assert acts == {"added": 0, "drained": 1, "retired": 1}
    assert fol.breaker_states() == {"a:1": "closed", "b:2": "draining",
                                    "c:3": "retired"}
    # idempotent re-apply
    assert apply_membership(fol, snap) == {"added": 0, "drained": 0,
                                           "retired": 0}
    # a lagging snapshot that lists b:2 active must NOT un-drain it
    stale = {"members": ["a:1", "b:2"], "draining": [], "retired": []}
    apply_membership(fol, stale)
    assert fol.breaker_states()["b:2"] == "draining"
    # unknown members are adopted
    acts = apply_membership(fol, {"members": ["a:1", "d:4"]})
    assert acts["added"] == 1 and "d:4" in fol.addresses


# ------------------------------------------- controller + election ------
def test_controller_exactly_one_autoscaler_and_ttl_takeover(tmp_path):
    """Two routers, one lease: only the leader's autoscaler ever runs;
    when the leader stops ticking, the follower takes over within one
    TTL and the follower's replica set has already converged on the
    leader's published membership."""
    clk = FakeClock()
    be = FileLeaseBackend(str(tmp_path), clock=clk)
    rs_a = FakeSet(["a:1", "b:2"])
    rs_b = FakeSet(["a:1"])            # follower starts with a stale view
    asc_a, asc_b = CountingAutoscaler(), CountingAutoscaler()
    ctl_a = FleetController(rs_a, LeaderElector(be, "A", ttl_s=2.0),
                            autoscaler=asc_a)
    ctl_b = FleetController(rs_b, LeaderElector(be, "B", ttl_s=2.0),
                            autoscaler=asc_b)

    for _ in range(3):
        out_a = ctl_a.tick()
        out_b = ctl_b.tick()
        assert out_a["leader"] and out_a["published"]
        assert not out_b["leader"]
        clk.t += 0.5
    assert asc_a.evals == 3 and asc_b.evals == 0   # exactly one acts
    assert "b:2" in rs_b.addresses                 # follower converged
    assert ctl_b.snapshots_applied >= 1

    # leader dies (stops ticking); B takes over within one TTL
    clk.t += 2.5
    out = ctl_b.tick()
    assert out["leader"] and asc_b.evals == 1
    assert ctl_b.elector.fencing_token == 2

    # the stale ex-leader comes back: renew fails, it follows, and its
    # autoscaler never runs again
    out = ctl_a.tick()
    assert out["leader"] is False
    assert asc_a.evals == 3
    snap = ctl_a.snapshot()
    assert snap["election"]["is_leader"] is False
    assert snap["leader_ticks"] == 3 and snap["follower_ticks"] == 1


_CHILD_LEADER = """
import importlib.util, sys, time
spec = importlib.util.spec_from_file_location("election_child", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
be = mod.FileLeaseBackend(sys.argv[2])
el = mod.LeaderElector(be, node_id="child", ttl_s=float(sys.argv[3]))
print("LEADER" if el.tick() else "FOLLOWER", flush=True)
while True:
    time.sleep(0.05)
    el.tick()
"""


def test_killed_leader_process_hands_off_within_one_ttl(tmp_path):
    """The real thing: the leader is a separate PROCESS holding the
    lease on disk; SIGKILL it and the local elector must acquire within
    one TTL.  election.py is deliberately stdlib-only, so the child
    loads it by path without paying for (or importing) the serving
    stack — this stays a fast tier-1 test."""
    ttl = 0.75
    lease_dir = str(tmp_path / "lease")
    script = tmp_path / "child_leader.py"
    script.write_text(_CHILD_LEADER)
    election_py = os.path.join(REPO, "tpulab", "fleet", "election.py")
    proc = subprocess.Popen(
        [sys.executable, str(script), election_py, lease_dir, str(ttl)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
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

        me = LeaderElector(FileLeaseBackend(lease_dir), node_id="parent",
                           ttl_s=ttl)
        # the child renews every 50ms: the parent cannot acquire
        for _ in range(3):
            assert me.tick() is False
            time.sleep(0.1)

        proc.kill()                    # SIGKILL: no release, no goodbye
        proc.wait(timeout=10)
        t0 = time.monotonic()
        while not me.tick():
            assert time.monotonic() - t0 < 5.0, "takeover never happened"
            time.sleep(0.02)
        took = time.monotonic() - t0
        assert took <= ttl + 1.0, f"takeover took {took:.2f}s > one TTL"
        assert me.fencing_token == 2   # fenced past the dead child
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


# ------------------------------------------------------- supervisor ------
def test_supervisor_respawns_dead_replica_under_backoff():
    clk = FakeClock(0.0)
    rs = FakeSet(["a:1", "b:2"])
    prov = FakeProvider()
    prov.alive = {"a:1": True, "b:2": True}
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=1.0, clock=clk)

    assert sup.probe() == {"deaths": [], "respawns": [], "quarantined": []}

    prov.alive["a:1"] = False          # the process exited
    acts = sup.probe()
    assert acts["deaths"] == ["a:1"] and acts["respawns"] == []
    assert rs.breaker_states()["a:1"] == "retired"   # routers stop picking
    assert "a:1" in prov.retired                     # reaped

    clk.t = 0.5                        # still inside the backoff
    assert sup.probe()["respawns"] == []
    clk.t = 1.5                        # backoff elapsed
    acts = sup.probe()
    assert len(acts["respawns"]) == 1
    new = acts["respawns"][0]
    assert new in rs.added and rs.active_count == 2  # membership healed
    assert sup.deaths == 1 and sup.respawns == 1
    snap = sup.snapshot()
    assert snap["lineages"][new]["respawns"] == 1


def test_supervisor_crash_loop_quarantine_and_unquarantine():
    clk = FakeClock(0.0)
    rs = FakeSet(["a:1"])
    prov = FakeProvider()
    prov.alive = {"a:1": False}
    prov.spawn_dead = True             # every respawn dies instantly
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=0.0,
                          crash_loop_deaths=3, crash_loop_window_s=100.0,
                          clock=clk)

    sup.probe()                        # death 1 + instant respawn
    sup.probe()                        # death 2 + instant respawn
    acts = sup.probe()                 # death 3: the breaker opens
    assert len(acts["quarantined"]) == 1
    assert sup.crash_loops == 1 and sup.deaths == 3
    spawned = prov.n
    sup.probe()
    sup.probe()
    assert prov.n == spawned           # quarantine: no spawn budget burned
    quarantined_addr = acts["quarantined"][0]
    assert sup.snapshot()["lineages"][quarantined_addr]["quarantined"]

    prov.spawn_dead = False            # "the config fix landed"
    assert sup.unquarantine(quarantined_addr) is True
    acts = sup.probe()
    assert len(acts["respawns"]) == 1
    assert sup.probe()["deaths"] == [] # the lineage is healthy again


def test_supervisor_never_kills_draining_member():
    """Drain-vs-death: a draining replica whose transport looks dead is
    a deliberate exit in progress — the autoscaler owns its retirement,
    the supervisor must not respawn it."""
    rs = FakeSet(["a:1", "b:2"])
    prov = FakeProvider()
    prov.alive = {"a:1": False, "b:2": False}
    rs.set_draining("a:1")
    rs.health_results["a:1"] = {"live": False, "ready": False}
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=10.0,
                          clock=FakeClock(0.0))
    acts = sup.probe()
    assert acts["deaths"] == ["b:2"]
    assert rs.breaker_states()["a:1"] == "draining"  # untouched
    assert "a:1" not in prov.retired


def test_supervisor_unreachable_streak_requires_consecutive_failures():
    """Without provider liveness evidence (is_alive None), only a full
    streak of failed probes kills a member — one blip never does."""
    rs = FakeSet(["a:1"])
    prov = FakeProvider()              # alive={} -> is_alive None
    sup = FleetSupervisor(rs, prov, unreachable_probes=3,
                          respawn_backoff_s=10.0, clock=FakeClock(0.0))
    rs.health_results["a:1"] = {"live": False, "ready": False}
    assert sup.probe()["deaths"] == []           # streak 1
    assert sup.probe()["deaths"] == []           # streak 2
    rs.health_results.pop("a:1")                 # one good probe resets
    assert sup.probe()["deaths"] == []
    rs.health_results["a:1"] = {"live": False, "ready": False}
    assert sup.probe()["deaths"] == []           # streak 1 again
    assert sup.probe()["deaths"] == []           # streak 2
    assert sup.probe()["deaths"] == ["a:1"]      # streak 3: dead


# ------------------------------------------------------ probe chaos ------
@pytest.mark.parametrize("action", ["error", "drop"])
def test_probe_chaos_forgoes_evidence_never_spurious_death(action):
    """fleet.probe chaos (docs/ROBUSTNESS.md): evidence discarded for
    that tick — healing is DELAYED, a healthy member is never killed."""
    rs = FakeSet(["a:1"])
    prov = FakeProvider()
    prov.alive = {"a:1": False}        # genuinely dead underneath
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=10.0,
                          clock=FakeClock(0.0))
    with chaos.inject(f"fleet.probe={action}+1") as sched:
        assert sup.probe()["deaths"] == []       # probe forgone
        assert sched.fired("fleet.probe") == 1
        assert sup.probes_forgone == 1
        assert rs.breaker_states()["a:1"] == "closed"
        assert sup.probe()["deaths"] == ["a:1"]  # rule spent: retried


# ------------------------------------------------------ spawn chaos ------
@pytest.mark.parametrize("action", ["error", "drop"])
def test_spawn_chaos_retries_with_backoff(action):
    """fleet.spawn chaos through the real InProcessReplicaProvider path:
    one injected failure degrades to retry, the spawn still lands."""

    class _Mgr:
        server = type("S", (), {"bound_port": 50123})()

        def shutdown(self):
            pass

    prov = InProcessReplicaProvider(lambda: _Mgr())
    with chaos.inject(f"fleet.spawn={action}+1") as sched:
        addr = prov.spawn()
    assert addr == "127.0.0.1:50123"
    assert sched.fired("fleet.spawn") == 1
    assert prov.is_alive(addr) is True


def test_spawn_chaos_exhaustion_propagates():
    """A fleet that cannot spawn at all must say so, not loop forever."""
    with chaos.inject("fleet.spawn=error+10") as sched:
        with pytest.raises(chaos.ChaosError):
            spawn_with_retry(lambda: "never", attempts=3, backoff_s=0.01)
    assert sched.fired("fleet.spawn") == 3


def test_supervisor_spawn_failure_backs_off():
    """A failed respawn is a scheduling fact, not a crash: the lineage
    re-arms with doubled backoff and succeeds once spawns recover."""
    clk = FakeClock(0.0)
    rs = FakeSet(["a:1"])
    prov = FakeProvider()
    prov.alive = {"a:1": False}
    sup = FleetSupervisor(rs, prov, respawn_backoff_s=0.0, clock=clk)
    prov.spawn_fails = 1
    acts = sup.probe()                 # death + failed respawn attempt
    assert acts["deaths"] == ["a:1"] and acts["respawns"] == []
    lin = sup.snapshot()["lineages"]["a:1"]
    assert lin["spawn_failures"] == 1
    clk.t = 10.0                       # past the re-armed backoff
    acts = sup.probe()
    assert len(acts["respawns"]) == 1


# ------------------------------------------- served replica fixture ------
def _lm_params():
    from tpulab.models.transformer import init_transformer_params
    return init_transformer_params(vocab=64, d_model=32, n_heads=2,
                                   n_layers=2, d_ff=64)


def _serve_paced(params, slow_s: float = 0.0, fleet=None):
    import jax.numpy as jnp

    from tpulab.engine.paged import ContinuousBatcher

    class _Paced(ContinuousBatcher):
        def submit(self, prompt, steps, on_token=None, **kw):
            if slow_s and on_token is not None:
                inner = on_token

                def paced(*a, **k):
                    time.sleep(slow_s)
                    return inner(*a, **k)
                on_token = paced
            return super().submit(prompt, steps, on_token=on_token, **kw)

    cls = _Paced if slow_s else ContinuousBatcher
    cb = cls(params, n_heads=2, n_layers=2, lanes=2, max_len=64,
             page_size=8, prefix_cache=True, compute_dtype=jnp.float32)
    mgr = tpulab.InferenceManager(max_exec_concurrency=1)
    mgr.register_model("mnist", make_mnist(max_batch_size=1))
    mgr.update_resources()
    mgr.serve(port=0, generation_engines={"lm": cb}, fleet=fleet)
    return mgr, cb


@pytest.fixture(scope="module")
def control_replica(tmp_path_factory):
    """One paced in-process replica served WITH a fleet controller
    attached (the Debug RPC's fleet section) — shared by the in-process
    drain-conformance leg and the debugz test."""
    rs_view = FakeSet(["10.0.0.1:50051"])
    ctl = FleetController(
        rs_view,
        LeaderElector(FileLeaseBackend(
            str(tmp_path_factory.mktemp("lease"))), node_id="router-a",
            ttl_s=60.0),
        supervisor=FleetSupervisor(rs_view, FakeProvider()))
    ctl.tick()
    params = _lm_params()
    mgr, cb = _serve_paced(params, slow_s=0.15, fleet=ctl)
    cb.submit(np.arange(6, dtype=np.int32), 3,
              on_token=lambda *a: None).result(timeout=300)  # pre-warm
    yield mgr, cb, ctl
    for closer in (mgr.shutdown, cb.shutdown):
        try:
            closer()
        except Exception:
            pass


def test_debugz_reports_fleet_control_plane(control_replica):
    """The fleet section rides the Debug RPC end to end: election state
    + supervision lineages show up in the wire snapshot."""
    mgr, _, ctl = control_replica
    from tpulab.rpc.infer_service import RemoteInferenceManager
    client = RemoteInferenceManager(f"127.0.0.1:{mgr.server.bound_port}")
    try:
        snap = client.debugz()
    finally:
        client.close()
    fleet = snap["fleet"]
    assert fleet["election"]["node_id"] == "router-a"
    assert fleet["election"]["is_leader"] is True
    assert fleet["election"]["fencing_token"] == 1
    assert fleet["leader_ticks"] == 1
    assert "supervisor" in fleet
    assert fleet == ctl.snapshot()


# ------------------------------------------- drain conformance ----------
def _stream_through(rs, prompt, steps):
    """Start one token stream via the replica set and return
    (first_token_event, wait_fn) where wait_fn joins the stream and
    returns the delivered tokens."""
    out = []
    first = threading.Event()
    done = threading.Event()
    err = []

    def run():
        try:
            for t in rs.generate(prompt, steps, timeout=120):
                out.append(t)
                first.set()
        except Exception as e:  # surfaced by wait_fn
            err.append(e)
        finally:
            first.set()
            done.set()

    threading.Thread(target=run, daemon=True).start()

    def wait_fn(timeout=120):
        assert done.wait(timeout), "stream never finished"
        if err:
            raise err[0]
        return out

    return first, wait_fn


@pytest.mark.parametrize("kind", ["inprocess", "subprocess"])
def test_provider_drain_conformance(kind, control_replica):
    """The shared ReplicaProvider.drain contract, against BOTH
    providers: unknown address drains trivially; ``timeout_s`` is a
    HARD cap on blocking (the in-process leg runs with a settle window
    far above the budget — the pre-fix drift this pins down); an
    in-flight stream survives the drain and completes; a drained
    replica reports True within budget."""
    from tpulab.rpc.replica import GenerationReplicaSet

    if kind == "inprocess":
        mgr, cb, _ = control_replica
        addr = f"127.0.0.1:{mgr.server.bound_port}"
        # settle_s far above the drain budget: only the timeout_s cap
        # keeps case-2 from blocking 10s
        prov = InProcessReplicaProvider(lambda: mgr, settle_s=10.0)
        prov.adopt(addr, mgr, None)
        retire_after = False
    else:
        prov = SubprocessReplicaProvider(
            replica_args=("--delay-ms", "150"))
        addr = prov.spawn()
        retire_after = True

    rs = GenerationReplicaSet([addr], "lm")
    try:
        # 1. unknown address = already gone
        assert prov.drain("127.0.0.1:1") is True

        # 2. hard cap: a paced in-flight stream outlives the budget
        first, wait_fn = _stream_through(rs, np.arange(5, dtype=np.int32),
                                         24)
        assert first.wait(60), "stream never started"
        t0 = time.monotonic()
        assert prov.drain(addr, timeout_s=1.0) is False
        assert time.monotonic() - t0 < 4.0   # the cap held

        # 3. the stream the drain found in flight still completes
        toks = wait_fn()
        assert len(toks) == 24

        # 4. now-idle draining replica: True within budget
        t0 = time.monotonic()
        assert prov.drain(addr, timeout_s=3.0) is True
        assert time.monotonic() - t0 < 6.0
    finally:
        rs.close()
        if retire_after:
            prov.retire(addr)
            assert prov.exit_code(addr) == 0   # clean SIGTERM retirement
            prov.close()


# ------------------------------------------------ slow acceptance -------
@pytest.mark.slow
def test_subprocess_fleet_kill_resume_respawn_and_scaledown():
    """The acceptance scenario end to end against REAL processes:

    1. three-headed check on a chaos-armed victim — a replica process
       os._exit()s mid-stream (TPULAB_CHAOS inherited through spawn's
       extra_env) and the client stream completes bit-exact on the
       survivor via resume-from-delivered;
    2. the supervisor detects the death (provider exit code evidence,
       KILL_EXIT_CODE) and respawns the lineage — a new ready process
       joins the routing set;
    3. the autoscaler scales down: the victim drains (its in-flight
       stream finishes — zero dropped streams) and retires with a clean
       exit 0, while the supervisor never mistakes the drain for a
       death."""
    from tpulab.rpc.replica import GenerationReplicaSet

    prompt = np.arange(5, dtype=np.int32)
    steps = 12

    # the oracle: same fixed-seed weights in process
    params = _lm_params()
    oracle_mgr, oracle_cb = _serve_paced(params)
    expected = list(oracle_cb.submit(prompt, steps).result(timeout=300))

    prov = SubprocessReplicaProvider(replica_args=("--delay-ms", "40"))
    rs = None
    try:
        # rpc.stream is the paged path's per-token emit trip; kill there
        # os._exit()s the replica mid-stream (exit code 86)
        victim = prov.spawn(extra_env={"TPULAB_CHAOS": "rpc.stream=kill@4"})
        survivor = prov.spawn()
        # the handshake names the platform: a CPU fleet reads as one
        assert prov.platform_of(survivor) == "cpu"
        rs = GenerationReplicaSet([victim, survivor], "lm")
        sup = FleetSupervisor(rs, prov, respawn_backoff_s=0.1,
                              probe_timeout_s=5.0)
        sup.probe()                            # adopt both lineages

        # 1. the kill fires mid-stream on the victim; resume finishes
        # the stream bit-exact on the survivor
        got = list(rs.generate(prompt, steps, timeout=120))
        assert got == expected, (got, expected)
        wait_until(lambda: prov.is_alive(victim) is False,
                   "the victim died", timeout_s=60, poll_s=0.1)

        # 2. the supervisor heals: death detected, lineage respawned
        acts = sup.probe()
        assert victim in acts["deaths"]
        assert prov.exit_code(victim) == chaos.KILL_EXIT_CODE
        respawned = wait_until(lambda: sup.probe()["respawns"],
                               "the respawn", timeout_s=240, poll_s=0.1)
        assert rs.active_count == 2
        got2 = list(rs.generate(prompt, steps, timeout=120))
        assert got2 == expected                # healed fleet serves

        # 3. scale down under live traffic: hold a LONG stream on EVERY
        # active replica so the drain victim necessarily has one
        steps_hold = 50
        expected_hold = list(
            oracle_cb.submit(prompt, steps_hold).result(timeout=300))
        asc = FleetAutoscaler(rs, prov, wait_signal=lambda: 0.0,
                              min_replicas=1, hold=1,
                              drain_timeout_s=120.0)
        waits = []
        for _ in range(2):
            first, wait_fn = _stream_through(rs, prompt, steps_hold)
            assert first.wait(60)
            waits.append(wait_fn)
        assert asc.evaluate() == "drain_started"
        sup_acts = sup.probe()                 # drain is NOT a death
        assert sup_acts["deaths"] == []
        assert asc.wait_for_drain(120.0)       # drained -> retired
        assert asc.scale_downs == 1
        assert rs.active_count == 1
        for wait_fn in waits:                  # zero dropped streams
            assert list(wait_fn()) == expected_hold
        clean_exits = [a for a in [victim, survivor] + respawned
                       if prov.exit_code(a) == 0]
        assert len(clean_exits) == 1           # exactly one clean retire
    finally:
        if rs is not None:
            rs.close()
        prov.close()
        for closer in (oracle_mgr.shutdown, oracle_cb.shutdown):
            try:
                closer()
            except Exception:
                pass


def test_native_replicas_are_bound_one_per_chip(monkeypatch):
    """A --native-platform replica holds a chip, and a chip belongs to
    one process: the provider needs the chip list up front, binds each
    child to its own, and refuses the spawn that would have no chip
    (instead of starting a child that hangs on a held one)."""
    with pytest.raises(ValueError, match="chips="):
        SubprocessReplicaProvider(replica_args=("--native-platform",))

    prov = SubprocessReplicaProvider(replica_args=("--native-platform",),
                                     chips=(0, 1))
    bound = []

    def fake_spawn_once(extra_env, chip):
        bound.append(chip)
        return f"127.0.0.1:{9000 + chip}"

    monkeypatch.setattr(prov, "_spawn_once", fake_spawn_once)
    assert prov.spawn() and prov.spawn()
    assert bound == [0, 1]
    with pytest.raises(RuntimeError, match="no free chip"):
        prov.spawn()
    assert bound == [0, 1]            # nothing was started for it


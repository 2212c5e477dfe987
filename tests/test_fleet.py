"""Self-healing serving fleet: replica supervision, restart backoff,
crash-loop quarantine, hot-spare promotion, rolling drain/restart, and
dispatcher membership following.

Acceptance pins (ISSUE 11):

* a respawned replica is READMITTED by a running ``RemoteDispatcher``
  without a process restart — the membership file swap installs a fresh
  client whose breaker is CLOSED, and the replica serves again;
* a forced crash loop lands the replica in ``quarantined`` with a typed
  reason (never an unbounded respawn burn);
* the smoke's SIGKILL/partition/rolling sequence ends with every request
  typed-terminal and the metrics gauges back at the serving target.
"""

import json
import os
import sys
import time

import pytest

import jax

import horovod_tpu as hvd
from horovod_tpu import config as hconfig
from horovod_tpu import faults, metrics, profiler
from horovod_tpu.serving.fleet import FleetSupervisor, ReplicaSlot
from horovod_tpu.serving.scheduler import Request, RequestQueue, \
    RequestStatus
from horovod_tpu.serving.transport import (
    RemoteClient, RemoteDispatcher, SocketReplicaServer, TransportError,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_world():
    metrics.reset_metrics()
    yield
    faults.reset()
    os.environ.pop("HOROVOD_FAULT_PLAN", None)
    for k in list(os.environ):
        if k.startswith("HOROVOD_SERVE_FLEET_") or k == \
                "HVD_TPU_FLEET_RESTART":
            os.environ.pop(k, None)
    hconfig.refresh()


class ServeNowEngine:
    """Completes every request instantly (transport-test stand-in)."""

    def __init__(self, name="fake0", slots=4, maxsize=32):
        self.name = name
        self.slots = slots
        self.alive = True
        self.queue = RequestQueue(maxsize=maxsize)
        self.submitted = []

    def start(self):
        pass

    def stop(self):
        pass

    def load(self):
        return self.queue.depth()

    def submit(self, prompt, max_new_tokens, **kw):
        kw.pop("deadline_s", None)
        req = Request(prompt if prompt is not None else [0],
                      max_new_tokens, **kw)
        self.submitted.append(req.id)
        req.tokens = list(range(max_new_tokens))
        req._finish(RequestStatus.DONE, None)
        return req


class DrainableEngine(ServeNowEngine):
    def __init__(self, **kw):
        super().__init__(**kw)
        self._draining = False

    def drain(self, timeout=60.0):
        self._draining = True


class InProcReplica:
    """Launcher handle backed by a real in-process socket server."""

    def __init__(self, rank, engine=None):
        self.eng = engine or ServeNowEngine(name=f"eng{rank}")
        self.srv = SocketReplicaServer(self.eng, rank).start()
        self._killed = False

    def alive(self):
        return not self._killed

    def address(self):
        return None if self._killed else self.srv.address

    def stop(self):
        self._killed = True
        self.srv.stop()

    def kill(self):
        self.stop()


class DeadOnArrivalHandle:
    """A replica that is already dead when the launcher returns it."""

    def alive(self):
        return False

    def address(self):
        return None

    def stop(self):
        pass

    kill = stop


def _poll_until(fleet, pred, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        fleet.poll_once()
        if pred():
            return True
        time.sleep(step)
    return False


def _launch_all(fleet):
    for slot in fleet.slots():
        fleet._launch(slot)


# ---------------------------------------------------------------------------
# fault grammar: crash_loop / flap
# ---------------------------------------------------------------------------

class TestFaultGrammar:
    def test_crash_loop_parses_with_count_and_any_restart(self):
        (a,) = faults.parse_plan("crash_loop@rank=1,step=6,count=2")
        assert a.kind == "crash_loop" and a.count == 2
        assert a.restart is None          # fires on EVERY fleet attempt
        assert a.space == "net"
        assert "count=2" in a.describe()

    def test_flap_parses_with_period(self):
        (a,) = faults.parse_plan(
            "flap@rank=2,step=5,period=0.4,seconds=2")
        assert a.kind == "flap" and a.period == 0.4 and a.seconds == 2.0
        assert a.restart is None
        assert "period=0.4" in a.describe()

    def test_count_rejected_on_other_kinds(self):
        with pytest.raises(ValueError, match="count"):
            faults.parse_plan("partition@rank=0,step=1,count=2")

    def test_period_rejected_on_other_kinds(self):
        with pytest.raises(ValueError, match="period"):
            faults.parse_plan("crash_loop@rank=0,step=1,period=0.5")

    def test_count_and_period_bounds(self):
        with pytest.raises(ValueError, match="count"):
            faults.parse_plan("crash_loop@rank=0,step=1,count=0")
        with pytest.raises(ValueError, match="period"):
            faults.parse_plan("flap@rank=0,step=1,period=0")

    def test_crash_loop_survives_past_count(self):
        # Attempt >= count: the fault is spent and _fire must NOT kill
        # this process (the supervisor out-waited the loop).
        os.environ["HVD_TPU_FLEET_RESTART"] = "2"
        (a,) = faults.parse_plan("crash_loop@rank=0,step=1,count=2")
        faults._fire(a)                   # still alive = pass
        assert metrics.snapshot()["counters"][
            "fault_injected_total"][0]["value"] >= 1

    def test_fleet_restart_env_wins_over_elastic(self):
        os.environ["HVD_TPU_FLEET_RESTART"] = "7"
        os.environ["HVD_TPU_ELASTIC_RESTART"] = "1"
        try:
            assert faults._restart_count() == 7
        finally:
            os.environ.pop("HVD_TPU_ELASTIC_RESTART", None)

    def test_flap_square_wave(self):
        a = faults.FaultAction(kind="flap", rank=9, step=1, seconds=0.5,
                               period=0.25, space="net")
        faults._fire(a)
        assert faults.partitioned(9)          # first half-period: dark
        time.sleep(0.3)
        assert not faults.partitioned(9)      # second: reachable
        time.sleep(0.3)
        assert not faults.partitioned(9)      # past `seconds`: healed
        faults.reset()
        assert not faults.partitioned(9)


# ---------------------------------------------------------------------------
# config knobs
# ---------------------------------------------------------------------------

class TestFleetKnobs:
    def test_defaults(self):
        cfg = hconfig.get_config()
        assert cfg.serve_fleet_restart_budget == 5
        assert cfg.serve_fleet_backoff_seconds == 0.5
        assert cfg.serve_fleet_backoff_cap_seconds == 10.0
        assert cfg.serve_fleet_crash_loop_k == 3
        assert cfg.serve_fleet_crash_loop_window_seconds == 30.0
        assert cfg.serve_fleet_probe_seconds == 0.5
        assert cfg.serve_fleet_spares == 0

    def test_env_overrides(self):
        os.environ.update({
            "HOROVOD_SERVE_FLEET_RESTART_BUDGET": "9",
            "HOROVOD_SERVE_FLEET_BACKOFF": "0.1",
            "HOROVOD_SERVE_FLEET_CRASH_LOOP_K": "4",
            "HOROVOD_SERVE_FLEET_SPARES": "2",
        })
        hconfig.refresh()
        cfg = hconfig.get_config()
        assert cfg.serve_fleet_restart_budget == 9
        assert cfg.serve_fleet_backoff_seconds == 0.1
        assert cfg.serve_fleet_crash_loop_k == 4
        assert cfg.serve_fleet_spares == 2
        # Supervisor defaults resolve from the refreshed config.
        fleet = FleetSupervisor(lambda n, r, a: DeadOnArrivalHandle(),
                                target=1)
        assert fleet.restart_budget == 9 and fleet.spares == 2

    def test_invalid_values_fail_loudly(self):
        os.environ["HOROVOD_SERVE_FLEET_CRASH_LOOP_K"] = "0"
        with pytest.raises(ValueError, match="CRASH_LOOP_K"):
            hconfig.refresh()
        os.environ.pop("HOROVOD_SERVE_FLEET_CRASH_LOOP_K")
        os.environ["HOROVOD_SERVE_FLEET_BACKOFF"] = "-1"
        with pytest.raises(ValueError, match="BACKOFF"):
            hconfig.refresh()

    def test_build_info_exports_fleet_knobs(self):
        hconfig.refresh()
        info = hvd.build_info()
        assert info["serve_fleet_restart_budget"] == 5
        assert info["serve_fleet_crash_loop_k"] == 3
        assert info["serve_fleet_spares"] == 0


# ---------------------------------------------------------------------------
# supervisor state machine (in-process launchers, no subprocesses)
# ---------------------------------------------------------------------------

class TestSupervision:
    def _fleet(self, launcher, **kw):
        kw.setdefault("backoff_seconds", 0.01)
        kw.setdefault("backoff_cap_seconds", 0.02)
        kw.setdefault("probe_seconds", 0.02)
        kw.setdefault("probe_rpc_timeout", 0.5)
        return FleetSupervisor(launcher, **kw)

    def test_restart_after_exit_with_attempt_stamp(self):
        handles = []

        def launcher(name, rank, attempt):
            h = InProcReplica(rank)
            handles.append((attempt, h))
            return h

        fleet = self._fleet(launcher, target=1)
        _launch_all(fleet)
        assert _poll_until(fleet, lambda: fleet.live_serving_count() == 1)
        handles[0][1].srv.stop()          # crash: process "exits"
        handles[0][1]._killed = True
        assert _poll_until(fleet, lambda: fleet.live_serving_count() == 1
                           and fleet.slot("r0").attempt == 1)
        assert [a for a, _ in handles] == [0, 1]
        assert fleet.slot("r0").restarts == 1
        snap = metrics.snapshot()
        exits = [s for s in snap["counters"]["fleet_restarts_total"]
                 if s["labels"]["reason"] == "exit"]
        assert exits and exits[0]["value"] >= 1
        for _, h in handles:
            h.stop()

    def test_crash_loop_quarantines_with_typed_reason(self):
        fleet = self._fleet(lambda n, r, a: DeadOnArrivalHandle(),
                            target=1, crash_loop_k=3,
                            crash_loop_window_seconds=60.0,
                            restart_budget=99)
        _launch_all(fleet)
        slot = fleet.slot("r0")
        assert _poll_until(fleet, lambda: slot.state == "quarantined")
        assert "crash_loop" in slot.quarantine_reason
        assert "3 deaths" in slot.quarantine_reason
        # Quarantine is sticky: further polls never respawn.
        n = slot.attempt
        for _ in range(5):
            fleet.poll_once()
        assert slot.attempt == n and slot.handle is None
        snap = metrics.snapshot()
        assert [s["value"] for s in snap["gauges"]["fleet_replicas"]
                if s["labels"]["state"] == "quarantined"] == [1.0]

    def test_restart_budget_exhaustion_quarantines(self):
        fleet = self._fleet(lambda n, r, a: DeadOnArrivalHandle(),
                            target=1, crash_loop_k=99,
                            crash_loop_window_seconds=0.001,
                            restart_budget=2)
        _launch_all(fleet)
        slot = fleet.slot("r0")
        assert _poll_until(fleet, lambda: slot.state == "quarantined")
        assert "restart budget exhausted" in slot.quarantine_reason
        assert slot.restarts == 2

    def test_spare_promotion_fills_dead_rank(self, tmp_path):
        member = str(tmp_path / "members.json")
        handles = {}

        def launcher(name, rank, attempt):
            h = InProcReplica(rank)
            handles[(name, attempt)] = h
            return h

        fleet = self._fleet(launcher, target=1, spares=1,
                            membership_path=member, crash_loop_k=99,
                            restart_budget=99)
        _launch_all(fleet)
        assert _poll_until(
            fleet, lambda: fleet.live_serving_count() == 1
            and fleet.slot("s0").state == "live")
        doc = json.load(open(member))
        assert [r["name"] for r in doc["replicas"]] == ["r0"]
        # Kill the serving replica: the warm spare must take its place
        # in the very poll that observes the death.
        handles[("r0", 0)].kill()
        fleet.poll_once()
        assert fleet.slot("s0").role == "serving"
        assert fleet.slot("r0").role == "spare"
        assert fleet.live_serving_count() == 1
        doc = json.load(open(member))
        assert [r["name"] for r in doc["replicas"]] == ["s0"]
        snap = metrics.snapshot()
        promos = snap["histograms"]["fleet_promotion_seconds"]
        assert sum(s["count"] for s in promos) == 1
        # The dead slot respawns in the background as the new spare.
        assert _poll_until(
            fleet, lambda: fleet.slot("r0").display_state() == "spare")
        for h in handles.values():
            h.stop()

    @pytest.mark.parametrize("spare_ready_first", [True, False])
    def test_spare_takes_a_quarantined_serving_slot(self,
                                                    spare_ready_first):
        """A serving replica parked while it was restarting (never LIVE
        at a death, so no promotion then) must not leave the fleet under
        its target beside a warm spare, whichever of the quarantine and
        the spare's admission comes first (the order the fleet smoke's
        two crash loops take under a loaded machine)."""
        spare = []

        def launcher(name, rank, attempt):
            if name == "r0":
                return DeadOnArrivalHandle()
            spare.append(InProcReplica(rank))
            return spare[-1]

        fleet = self._fleet(launcher, target=1, spares=1, crash_loop_k=3,
                            crash_loop_window_seconds=60.0,
                            restart_budget=99)
        r0, s0 = fleet.slot("r0"), fleet.slot("s0")
        if spare_ready_first:
            fleet._launch(s0)
            assert _poll_until(fleet, lambda: s0.state == "live")
            fleet._launch(r0)
            assert _poll_until(fleet, lambda: r0.state == "quarantined")
        else:
            fleet._launch(r0)
            s0.next_restart_at = float("inf")     # not launched yet
            assert _poll_until(fleet, lambda: r0.state == "quarantined")
            assert fleet.live_serving_count() == 0
            fleet._launch(s0)
            assert _poll_until(fleet, lambda: s0.state == "live")
        assert (s0.role, r0.role) == ("serving", "spare")
        assert fleet.live_serving_count() == 1
        assert r0.state == "quarantined"          # still parked
        for h in spare:
            h.stop()

    def test_rolling_restart_replaces_every_serving_replica(self,
                                                           tmp_path):
        member = str(tmp_path / "members.json")
        spawned = []

        def launcher(name, rank, attempt):
            h = InProcReplica(rank, engine=DrainableEngine(
                name=f"{name}.a{attempt}"))
            spawned.append((name, attempt))
            return h

        fleet = self._fleet(launcher, target=2, membership_path=member)
        _launch_all(fleet)
        assert _poll_until(fleet,
                           lambda: fleet.live_serving_count() == 2)
        v_before = json.load(open(member))["version"]
        out = fleet.rolling_restart(drain_timeout=5.0, ready_timeout=10.0)
        assert sorted(out["restarted"]) == ["r0", "r1"]
        assert fleet.slot("r0").attempt == 1
        assert fleet.slot("r1").attempt == 1
        assert fleet.live_serving_count() == 2
        doc = json.load(open(member))
        assert doc["version"] > v_before
        assert sorted(r["name"] for r in doc["replicas"]) == ["r0", "r1"]
        assert all(r["attempt"] == 1 for r in doc["replicas"])
        snap = metrics.snapshot()
        rolling = sum(s["value"] for s in
                      snap["counters"]["fleet_restarts_total"]
                      if s["labels"]["reason"] == "rolling")
        assert rolling == 2
        assert sum(s["count"] for s in
                   snap["histograms"]["rolling_restart_seconds"]) == 2
        for slot in fleet.slots():
            slot.handle.stop()

    def test_target_must_be_positive(self):
        with pytest.raises(ValueError, match="target"):
            FleetSupervisor(lambda n, r, a: DeadOnArrivalHandle(),
                            target=0)


# ---------------------------------------------------------------------------
# drain RPC
# ---------------------------------------------------------------------------

class TestDrainRPC:
    def test_drain_flips_engine_and_status_reports_it(self):
        eng = DrainableEngine()
        srv = SocketReplicaServer(eng, 0).start()
        try:
            client = RemoteClient(srv.address, name="d0")
            assert client.status()["draining"] is False
            resp = client.drain(timeout=5.0)
            assert resp["ok"] and resp["draining"]
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline and not eng._draining:
                time.sleep(0.01)      # drain() runs on a server thread
            assert eng._draining
            assert client.status()["draining"] is True
        finally:
            srv.stop()

    def test_drain_on_drainless_engine_is_typed_non_retryable(self):
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        try:
            client = RemoteClient(srv.address, name="d1")
            with pytest.raises(TransportError) as ei:
                client.drain()
            assert "cannot drain" in str(ei.value)
            assert ei.value.retryable is False
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# dispatcher dynamic membership (the acceptance-pinned readmission)
# ---------------------------------------------------------------------------

def _write_members(path, version, replicas):
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump({"version": version, "replicas": replicas}, f)
    os.replace(tmp, path)


class TestDispatcherMembership:
    def test_respawned_replica_readmitted_without_dispatcher_restart(
            self, tmp_path):
        member = str(tmp_path / "members.json")
        srv1 = SocketReplicaServer(ServeNowEngine(), 0).start()
        _write_members(member, 1, [
            {"name": "r0", "host": "127.0.0.1", "port": srv1.port,
             "attempt": 0}])
        disp = RemoteDispatcher(membership=member, rpc_timeout=0.3,
                                max_retries=0)
        h = disp.wait(disp.submit([1, 2, 3], 4, deadline_s=10.0))
        assert h.status == "done"

        # Replica dies; drive its breaker OPEN the way real traffic
        # would (consecutive connect failures).
        srv1.stop()
        old_client = disp.clients[0]
        for _ in range(10):
            try:
                old_client.status(retry=False)
            except TransportError:
                pass
            if not old_client.breaker.allow():
                break
        assert not old_client.breaker.allow()   # OPEN: routed around

        # Supervisor respawns it on a NEW port and republishes; the
        # running dispatcher must readmit with a fresh CLOSED breaker.
        srv2 = SocketReplicaServer(ServeNowEngine(), 0).start()
        try:
            _write_members(member, 2, [
                {"name": "r0", "host": "127.0.0.1", "port": srv2.port,
                 "attempt": 1}])
            time.sleep(disp._MEMBER_TTL + 0.05)   # let the TTL lapse
            h2 = disp.wait(disp.submit([4, 5], 4, deadline_s=10.0))
            assert h2.status == "done"            # serves again
            new_client = disp.clients[0]
            assert new_client is not old_client
            assert new_client.address[1] == srv2.port
            assert new_client.breaker.allow()     # fresh breaker CLOSED
            snap = metrics.snapshot()
            readmits = [s for s in
                        snap["counters"]["transport_membership_total"]
                        if s["labels"]["event"] == "readmit"]
            assert readmits and readmits[0]["value"] >= 1
        finally:
            srv2.stop()

    def test_join_and_leave_follow_the_file(self, tmp_path):
        member = str(tmp_path / "members.json")
        srv1 = SocketReplicaServer(ServeNowEngine(), 0).start()
        srv2 = SocketReplicaServer(ServeNowEngine(), 1).start()
        try:
            _write_members(member, 1, [
                {"name": "a", "host": "127.0.0.1", "port": srv1.port}])
            disp = RemoteDispatcher(membership=member, rpc_timeout=0.3)
            assert [c.name for c in disp.clients] == ["a"]
            _write_members(member, 2, [
                {"name": "a", "host": "127.0.0.1", "port": srv1.port},
                {"name": "b", "host": "127.0.0.1", "port": srv2.port}])
            disp._refresh_membership(force=True)
            assert sorted(c.name for c in disp.clients) == ["a", "b"]
            _write_members(member, 3, [
                {"name": "b", "host": "127.0.0.1", "port": srv2.port}])
            disp._refresh_membership(force=True)
            assert [c.name for c in disp.clients] == ["b"]
        finally:
            srv1.stop()
            srv2.stop()

    def test_stale_version_is_ignored(self, tmp_path):
        member = str(tmp_path / "members.json")
        srv = SocketReplicaServer(ServeNowEngine(), 0).start()
        try:
            _write_members(member, 5, [
                {"name": "a", "host": "127.0.0.1", "port": srv.port}])
            disp = RemoteDispatcher(membership=member, rpc_timeout=0.3)
            assert [c.name for c in disp.clients] == ["a"]
            _write_members(member, 4, [])     # older version: no-op
            disp._refresh_membership(force=True)
            assert [c.name for c in disp.clients] == ["a"]
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# doctor
# ---------------------------------------------------------------------------

def _snap(gauges=None, counters=None):
    return {"counters": counters or {}, "gauges": gauges or {},
            "histograms": {}}


class TestDoctorFleet:
    def test_quarantine_is_a_high_severity_finding(self):
        snap = _snap(gauges={
            "fleet_replicas": [
                {"labels": {"state": "quarantined"}, "value": 1.0},
                {"labels": {"state": "live"}, "value": 3.0}],
            "fleet_target_replicas": [{"labels": {}, "value": 3.0}]})
        (f,) = [x for x in profiler._check_fleet(snap)
                if x["category"] == "fleet_quarantine"]
        assert f["severity"] >= 0.85
        assert "HOROVOD_SERVE_FLEET_CRASH_LOOP_K" in f["suggestion"]
        assert "HOROVOD_SERVE_FLEET_RESTART_BUDGET" in f["suggestion"]

    def test_capacity_below_target_names_spares_knob(self):
        snap = _snap(gauges={
            "fleet_replicas": [{"labels": {"state": "live"},
                                "value": 2.0}],
            "fleet_target_replicas": [{"labels": {}, "value": 3.0}]})
        (f,) = profiler._check_fleet(snap)
        assert f["category"] == "fleet_capacity"
        assert "2/3" in f["title"]
        assert "HOROVOD_SERVE_FLEET_SPARES" in f["suggestion"]

    def test_restart_burn_names_backoff_knob(self):
        snap = _snap(
            gauges={"fleet_replicas": [{"labels": {"state": "live"},
                                        "value": 3.0}],
                    "fleet_target_replicas": [{"labels": {},
                                               "value": 3.0}]},
            counters={"fleet_restarts_total": [
                {"labels": {"replica": "r0", "reason": "exit"},
                 "value": 7.0}]})
        (f,) = profiler._check_fleet(snap)
        assert f["category"] == "fleet_restart_burn"
        assert "HOROVOD_SERVE_FLEET_BACKOFF" in f["suggestion"]

    def test_healthy_fleet_is_silent(self):
        snap = _snap(gauges={
            "fleet_replicas": [{"labels": {"state": "live"},
                                "value": 3.0}],
            "fleet_target_replicas": [{"labels": {}, "value": 3.0}]})
        assert profiler._check_fleet(snap) == []
        assert profiler._check_fleet(_snap()) == []

    def test_doctor_ranks_fleet_findings(self):
        snap = _snap(gauges={
            "fleet_replicas": [
                {"labels": {"state": "quarantined"}, "value": 1.0}],
            "fleet_target_replicas": [{"labels": {}, "value": 0.0}]})
        report = profiler.doctor(snapshot=snap, trace=None, programs={})
        cats = [f["category"] for f in report["findings"]]
        assert "fleet_quarantine" in cats
        assert not report["healthy"]


# ---------------------------------------------------------------------------
# four-process fault smoke (make fleet-smoke)
# ---------------------------------------------------------------------------

class TestFleetSmoke:
    def test_supervised_fleet_heals_and_rolls_zero_drop(self, tmp_path):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import fleet_smoke
        finally:
            sys.path.remove(os.path.join(_REPO, "tools"))
        rc, text = fleet_smoke.run_smoke(str(tmp_path))
        assert rc == 0, text
